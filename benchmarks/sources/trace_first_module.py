"""A device-trace metric of whichever module did the work: the reader
named by ``of`` (``trace_module``, ``trace_roofline``), asked for each
role of ``modules`` in turn, and the first answer it gives.  For a
statement the program may answer on one of several routes, each with a
module of its own (a GROUP BY on the direct table in the scan module or
in the hash module): a change of route then moves the metric instead of
emptying it.  Nothing where the trace holds none of the modules."""

from ..spec import plugin


def read(ctx, args):
    inner = plugin("sources", args["of"])
    for role in args["modules"]:
        value = inner.read(ctx, dict(args, module=role))
        if value is not None:
            return value
    return None
