"""Milliseconds per exported trace in the program's own spans of the
given names, from the span files the traced run made the program export
(``benchmarks/.data/trace/spans/*.json``, one per statement).

Default: *self* time, a span's duration minus what its children on the
same thread cover, so that a parent is not counted again for the layers
below it.  With ``"union": true``: the wall time the union of the named
spans covers, for spans that run on another thread beside the caller's
(``decode_batch``), where a sum would count overlapping spans twice.

Needs each event's thread (``tid``) and ``args.span_id``/``parent_id``.
An export that does not say how many thread rows it holds
(``otherData.thread_rows``) predates per-thread spans and reads as
nothing; traces that hold none of the names read as 0.0.
"""

import glob
import json
import os

from benchmarks.trace_reduce import merge

SPANS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".data", "trace", "spans")


def self_ms(events, names) -> float:
    """Self time, in ms, of the events named in ``names``."""
    covered = {}
    for e in events:
        parent = e["args"].get("parent_id")
        if parent is not None:
            key = (parent, e["pid"], e["tid"])
            covered[key] = covered.get(key, 0.0) + e["dur"]
    return sum(e["dur"] - covered.get(
        (e["args"]["span_id"], e["pid"], e["tid"]), 0.0)
        for e in events if e["name"] in names) / 1e3


def union_ms(events, names) -> float:
    """Wall time, in ms, covered by at least one of the named events."""
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                   if e["name"] in names)
    return sum(b - a for a, b in merge(spans)) / 1e3


def read_dir(spans_dir, args):
    total, traces = 0.0, 0
    measure = union_ms if args.get("union") else self_ms
    names = set(args["spans"])
    for path in glob.glob(os.path.join(spans_dir, "*.json")):
        with open(path) as fh:
            doc = json.load(fh)
        if "thread_rows" not in doc.get("otherData", {}):
            return None
        traces += 1
        total += measure([e for e in doc["traceEvents"] if e.get("ph") == "X"],
                         names)
    return total / traces if traces else None


def read(ctx, args):
    return read_dir(SPANS_DIR, args)
