"""From a profiler trace to numbers: device busy time, time per XLA
module, time in collectives, and the idle gaps by what the host was
doing.  Works on anything shaped like ``jax.profiler.ProfileData``
(planes -> lines -> events with ``name``, ``start_ns``, ``duration_ns``),
so the test drives it with a hand-made trace.

What it reads, on a TPU: each chip is a plane ``/device:TPU:<n>``; its
line ``XLA Ops`` holds one event per executed HLO op and its line
``XLA Modules`` one event per executed program, named
``<module>(<fingerprint>)``.  Host spans the benchmark wrote with
``jax.profiler.TraceAnnotation`` (``bench.execute.<query>``) are on the
host plane, on the same clock.
"""

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench.execute."
#: a collective op's name starts with its HLO opcode (``all-to-all.3``)
#: or with the JAX primitive XLA named the instruction after
#: (``all_to_all.27`` in a v5e trace)
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "all_reduce", "all_gather", "all_to_all",
               "reduce_scatter", "collective_permute", "ppermute")


def _intervals(line):
    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns))
                  for e in line.events)


def merge(intervals):
    """Union of sorted ``(start, end)`` intervals as disjoint intervals."""
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An op event carries its whole HLO line (``%fusion.3 = f32[12]...
    fusion(...)``); the name is what stands before the ``=``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def host_spans(profile):
    """``[(start, end, name)]`` of the benchmark's own annotations."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  e.name[len("bench."):]))
    return sorted(spans)


def _label_gap_pieces(gap, spans, busy):
    """``busy`` is (sorted starts, sorted ends) of the chip's disjoint
    busy intervals.  Cut one idle gap at the spans' edges and say for each piece what
    the host was doing: outside every span it sat between queries;
    inside one it was before the span's first device op (parse, plan,
    dispatch), after its last (fetch, combine, finalize) or between two."""
    g0, g1 = gap
    pieces, at = [], g0
    for s0, s1, name in spans:
        if s1 <= at or s0 >= g1:
            continue
        if s0 > at:
            pieces.append(("between queries", at, s0))
        lo, hi = max(at, s0), min(g1, s1)
        starts, ends = busy
        i = bisect.bisect_left(ends, s0)       # first op ending inside the span
        op_before = i < len(ends) and ends[i] <= lo
        j = bisect.bisect_left(starts, hi)     # first op starting after the piece
        op_after = j < len(starts) and starts[j] <= s1
        where = ("between device ops" if op_before and op_after else
                 "after last device op" if op_before else
                 "before first device op" if op_after else "no device op")
        pieces.append((f"{name}: {where}", lo, hi))
        at = hi
    if at < g1:
        pieces.append(("between queries", at, g1))
    return [(label, b - a) for label, a, b in pieces if b > a]


def reduce_trace(profile):
    """-> dict with ``window_s``, ``busy_s`` (averaged over the chips),
    ``n_devices``, ``modules`` {name: {"seconds", "count"}}, ``ops``
    {name: seconds}, ``collective_s`` (all per chip, averaged) and
    ``gaps`` {label: seconds} of the first chip; or None when the trace
    holds no device plane."""
    devices = sorted((p for p in profile.planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = [p for p in devices
               if any(ln.name == OPS_LINE and len(list(ln.events))
                      for ln in p.lines)]
    if not devices:
        return None
    spans = host_spans(profile)
    per_device_ops = []
    for p in devices:
        ops = next(ln for ln in p.lines if ln.name == OPS_LINE)
        per_device_ops.append(ops)
    if spans:
        lo, hi = spans[0][0], max(s1 for _, s1, _ in spans)
    else:
        every = [iv for ops in per_device_ops for iv in _intervals(ops)]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    n = len(devices)
    busy_ns, collective_ns = 0.0, 0.0
    ops_ns, modules = {}, {}
    first_busy = None
    for p, ops in zip(devices, per_device_ops):
        busy = clip(merge(_intervals(ops)), lo, hi)
        if first_busy is None:
            first_busy = busy
        busy_ns += sum(e - s for s, e in busy)
        for e in ops.events:
            if not lo <= e.start_ns < hi:
                continue
            name = op_name(e.name)
            ops_ns[name] = ops_ns.get(name, 0.0) + e.duration_ns
            if name.startswith(COLLECTIVES):
                collective_ns += e.duration_ns
        for line in p.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                if not lo <= e.start_ns < hi:
                    continue
                m = modules.setdefault(module_name(e.name),
                                       {"seconds": 0.0, "count": 0.0})
                m["seconds"] += e.duration_ns / 1e9 / n
                m["count"] += 1.0 / n
    gaps = {}
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    busy_edges = ([s for s, _ in first_busy], [e for _, e in first_busy])
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 > g0:
            for label, ns in _label_gap_pieces((g0, g1), spans, busy_edges):
                gaps[label] = gaps.get(label, 0.0) + ns / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "n_devices": n,
        "modules": modules,
        "ops": {k: v / 1e9 / n for k, v in ops_ns.items()},
        "collective_s": collective_ns / 1e9 / n,
        "gaps": gaps,
        "n_spans": len(spans),
    }


def top(table: dict, k: int = 10):
    """``[[name, seconds], ...]``, the ``k`` largest."""
    return [[name, s] for name, s in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ProfileData."""
    import glob
    import os

    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    return ProfileData.from_file(max(files, key=os.path.getmtime))
