"""Idle milliseconds of the first chip per traced query, by what the
program says the calling thread was doing: each idle piece inside a
``bench.execute.*`` annotation is put down to the innermost ``citus.*``
annotation (the program's own spans, written on the profiler's clock)
open on that thread at that time.

``"spans": [names]`` -> idle ms per query under those spans;
``"spans": null`` -> idle ms per query under no leaf span: inside
``citus.query`` / ``citus.execute`` themselves, or under nothing.  None
when the trace has no device plane, or no ``citus.*`` annotation at all
(a program that writes none).  The whole table (span -> idle ms per
query) goes to stderr: it is PERF.md section 5's content.
"""

import os
import sys

from benchmarks import trace_reduce
from benchmarks.trace_reduce import (
    DEVICE_PLANE, HOST_PLANE, OPS_LINE, SPAN_PREFIX, _intervals, clip, merge,
)

PROGRAM_PREFIX = "citus."
NOT_A_LEAF = ("query", "execute")
UNATTRIBUTED = "(no span)"


def first_chip_busy(profile):
    """Disjoint busy intervals of the lowest-numbered chip that ran an
    op, or None without a device plane."""
    planes = sorted((p for p in profile.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for p in planes:
        for line in p.lines:
            if line.name == OPS_LINE and len(list(line.events)):
                return merge(_intervals(line))
    return None


def host_threads(profile):
    """``[(queries, program)]`` per host thread: its ``bench.execute.*``
    and its ``citus.*`` events as ``(start, end, name)``."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            queries, program = [], []
            for e in line.events:
                iv = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                if e.name.startswith(SPAN_PREFIX):
                    queries.append(iv + (e.name,))
                elif e.name.startswith(PROGRAM_PREFIX):
                    program.append(iv + (e.name[len(PROGRAM_PREFIX):],))
            if queries or program:
                out.append((sorted(queries), sorted(program)))
    return out


def innermost_segments(q0, q1, program):
    """Cut ``[q0, q1)`` at the edges of the thread's nested annotations:
    -> ``[(start, end, innermost name or None)]``."""
    edges = []
    for s, e, name in program:
        s, e = max(s, q0), min(e, q1)
        if e > s:
            edges.append((s, 1, -e, name))      # outer spans open first
            edges.append((e, 0, 0.0, name))     # closes before opens
    segments, stack, at = [], [], q0
    for t, opens, _, name in sorted(edges):
        if t > at:
            segments.append((at, t, stack[-1] if stack else None))
            at = t
        if opens:
            stack.append(name)
        elif stack:
            stack.pop()
    if q1 > at:
        segments.append((at, q1, stack[-1] if stack else None))
    return segments


def idle_table(profile):
    """-> ({span name: idle seconds}, traced queries, host threads with
    ``citus.*`` events) or None without a device plane."""
    busy = first_chip_busy(profile)
    if busy is None:
        return None
    table, n_queries, n_threads = {}, 0, 0
    for queries, program in host_threads(profile):
        n_threads += bool(program)
        for q0, q1, _ in queries:
            n_queries += 1
            at, idle = q0, []
            for b0, b1 in clip(busy, q0, q1):
                idle.append((at, b0))
                at = b1
            idle.append((at, q1))
            for s0, s1, name in innermost_segments(q0, q1, program):
                ns = sum(b - a for a, b in clip(idle, s0, s1))
                if ns > 0:
                    label = name or UNATTRIBUTED
                    table[label] = table.get(label, 0.0) + ns / 1e9
    return table, n_queries, n_threads


def read_profile(profile, args, log=None):
    found = idle_table(profile) if profile is not None else None
    if found is None:
        return None
    table, n_queries, n_threads = found
    if not n_queries or not n_threads:
        return None
    if log is not None:
        log(f"idle ms per traced query by span ({n_queries} queries, "
            f"citus.* annotations on {n_threads} host thread(s)):")
        for name, s in sorted(table.items(), key=lambda kv: -kv[1]):
            log(f"  {name:24s} {s * 1e3 / n_queries:12.3f}")
    if args.get("spans") is None:
        names = {UNATTRIBUTED, *NOT_A_LEAF}
    else:
        names = set(args["spans"])
    return sum(s for name, s in table.items() if name in names) \
        * 1e3 / n_queries


def read(ctx, args):
    if ctx.trace is None:
        return None
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".data", "trace")
    return read_profile(
        trace_reduce.load(trace_dir), args,
        log=lambda line: print("benchmark: " + line, file=sys.stderr,
                               flush=True))
