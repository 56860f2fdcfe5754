"""Sum of the program's counters over the window, per completed query
(``per: query``) or whole (``per: window``)."""


def read(ctx, args):
    total = sum(ctx.counters.get(c, 0) for c in args["counters"])
    if args.get("per", "query") == "query":
        if not ctx.n_queries:
            return None
        total /= ctx.n_queries
    return total * args.get("scale", 1)
