"""Milliseconds per query in the program's own spans of the given
names, from the traces the traced run made the program export."""


def read(ctx, args):
    if not ctx.span_traces:
        return None
    total = sum(ctx.span_ms.get(name, 0.0) for name in args["spans"])
    return total / ctx.span_traces
