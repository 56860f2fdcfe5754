"""Share of the MXU's bf16 peak the scan module reached on the group
product: the least time the chips could take for the traced queries'
multiply-adds (``benchmarks/group_product.py``), over the module's
device time.  The slots are the program's own count (counter
``direct_groups`` per query); a program that counts none (it has no
such product) reads as nothing."""

from ..group_product import mxu_floor_s, product_flops


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries or not ctx.n_queries:
        return None
    name = ctx.cell.config["kernel_modules"].get(args["module"])
    m = ctx.trace["modules"].get(name)
    slots = ctx.counters.get("direct_groups", 0) / ctx.n_queries
    if m is None or not m["seconds"] or not slots:
        return None
    flops = sum(product_flops(slots, ctx.cell.queries[q]["group_product"]["planes"],
                              ctx.table_rows)
                for q in ctx.slice_queries if "group_product" in ctx.cell.queries[q])
    if not flops:
        return None
    return 100.0 * mxu_floor_s(flops, ctx.device_kind, ctx.chips) / m["seconds"]
