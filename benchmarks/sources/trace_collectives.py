"""Milliseconds in collective operations per traced query, per chip."""


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries:
        return None
    return ctx.trace["collective_s"] * 1e3 / len(ctx.slice_queries)
