"""Share of the HBM roofline the module that cuts ORDER BY ... LIMIT on
the group table reached: the least time the chips could take to read
once every group that stands before the cut (``benchmarks/group_top.py``,
from the query's own ``group_state``), over the module's device time.
The groups are the program's own count (counter ``hash_groups_out`` per
query); a program without the module, or a query file without a
``group_state``, reads as nothing."""

from ..group_top import cut_floor_s


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries or not ctx.n_queries:
        return None
    name = ctx.cell.config["kernel_modules"].get(args["module"])
    m = ctx.trace["modules"].get(name)
    groups = ctx.counters.get("hash_groups_out", 0) / ctx.n_queries
    if m is None or not m["seconds"] or not groups:
        return None
    floor = sum(cut_floor_s(groups, ctx.cell.queries[q], ctx.device_kind,
                            ctx.chips)
                for q in ctx.slice_queries
                if "group_state" in ctx.cell.queries[q])
    if not floor:
        return None
    return 100.0 * floor / m["seconds"]
