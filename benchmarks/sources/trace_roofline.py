"""Share of the HBM roofline a scan module reached: the least time the
chips could take to read the traced queries' algorithmic bytes, over
the module's device time."""

from ..roofline import algorithmic_bytes_per_row, hbm_floor_s


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries:
        return None
    name = ctx.cell.config["kernel_modules"].get(args["module"])
    m = ctx.trace["modules"].get(name)
    if m is None or not m["seconds"]:
        return None
    n_bytes = sum(ctx.table_rows * algorithmic_bytes_per_row(ctx.cell.queries[q])
                  for q in ctx.slice_queries)
    return 100.0 * hbm_floor_s(n_bytes, ctx.device_kind, ctx.chips) / m["seconds"]
