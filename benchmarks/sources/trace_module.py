"""Device time (``field: seconds``) or executions (``field: count``) of
one XLA module per traced query, averaged over the chips.  The module
is named by its role (``scan``), which the configuration maps to the
name this layout's program carries in the device trace."""


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries:
        return None
    name = ctx.cell.config["kernel_modules"].get(args["module"])
    m = ctx.trace["modules"].get(name)
    if m is None:
        return None
    return m[args["field"]] * args.get("scale", 1) / len(ctx.slice_queries)
