"""Sums of attributes the program wrote on its spans, from the span
files the traced run made it export (``span_self``'s directory): what a
span counted about its own work, such as what the native decode pool
reported of itself on ``native_decode``.

``"spans": [names], "attrs": [keys]`` -> the sum of those attributes
over those spans per exported trace.  With ``"over": {"attr": key,
"times_span_ms": true}`` -> that sum divided by the sum, over the same
spans, of ``key`` (times the span's own milliseconds): a share, e.g.
thread time worked over threads x time offered.  ``"scale"`` multiplies
the result.  None where no named span of any trace carries any of the
attributes (a program that does not write them), or the divisor is 0.
"""

import glob
import json
import os

from benchmarks.sources.span_self import SPANS_DIR


def read_dir(spans_dir, args):
    names, attrs, over = set(args["spans"]), args["attrs"], args.get("over")
    total, offered, traces, found = 0.0, 0.0, 0, False
    for path in glob.glob(os.path.join(spans_dir, "*.json")):
        with open(path) as fh:
            events = json.load(fh)["traceEvents"]
        traces += 1
        for e in events:
            if e.get("ph") != "X" or e["name"] not in names:
                continue
            have = [e["args"][a] for a in attrs if a in e["args"]]
            if not have:
                continue
            found = True
            total += sum(have)
            if over is not None:
                weight = e["dur"] / 1e3 if over.get("times_span_ms") else 1.0
                offered += e["args"].get(over["attr"], 0) * weight
    if not found:
        return None
    if over is not None:
        return total / offered * args.get("scale", 1) if offered else None
    return total / traces * args.get("scale", 1)


def read(ctx, args):
    return read_dir(SPANS_DIR, args)
