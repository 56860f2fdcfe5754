"""Device milliseconds per traced query, per chip, in the XLA ops whose
names start with one of ``prefixes`` -- for an op the device trace
names after the JAX primitive and not after its HLO opcode
(``all_to_all.27``: ``trace_collectives`` looks for ``all-to-all``).
0.0 where the trace holds no such op, as ``trace_collectives`` reads
a program without collectives; None without a device trace."""


def read(ctx, args):
    if ctx.trace is None or not ctx.slice_queries:
        return None
    prefixes = tuple(args["prefixes"])
    found = sum(s for name, s in ctx.trace.get("ops", {}).items()
                if name.startswith(prefixes))
    return found * 1e3 / len(ctx.slice_queries)
