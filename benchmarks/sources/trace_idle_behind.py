"""Idle milliseconds of the first chip per traced query while the
calling thread sat in ``citus.wait:prefetch_stall``, by what the thread
it waited for was doing: each such idle piece is put down to the
innermost ``citus.*`` annotation open AT THAT INSTANT on the producer
thread, the host line that holds ``citus.decode_batch`` events and no
``bench.execute.*`` one.  ``trace_idle_under`` names the wait; this
names the work behind it, from the same functions.

Labels: the producer's span; ``(between spans)`` where only
``decode_batch`` / ``stripe_read`` themselves are open;
``(no producer span)`` where the producer has nothing open.
``"spans": [labels]`` -> idle ms per query under those labels.  None
when the trace has no device plane, or holds no ``citus.footer_read`` /
``citus.native_decode`` annotation at all (a program that does not name
the decode thread's work from inside).  The whole table goes to stderr:
it is PERF.md section 5's second table.
"""

import os
import sys

from benchmarks import trace_reduce
from benchmarks.sources.span_self import SPANS_DIR
from benchmarks.sources.trace_idle_under import (
    first_chip_busy, host_threads, innermost_segments,
)
from benchmarks.trace_reduce import clip

STALL = "wait:prefetch_stall"
PRODUCER_MARK = "decode_batch"
NOT_A_LEAF = ("decode_batch", "stripe_read")
NAMED_FROM_INSIDE = ("footer_read", "native_decode")
BETWEEN = "(between spans)"
NO_PRODUCER = "(no producer span)"


def outside(intervals, lo, hi):
    """What is left of disjoint ``intervals`` once ``[lo, hi)`` is cut out."""
    out = []
    for s, e in intervals:
        if s < lo:
            out.append((s, min(e, lo)))
        if e > hi:
            out.append((max(s, hi), e))
    return out


def behind_table(profile):
    """-> ({label: idle seconds}, traced queries) or None."""
    busy = first_chip_busy(profile)
    if busy is None:
        return None
    threads = host_threads(profile)
    if not any(name in NAMED_FROM_INSIDE
               for _, program in threads for _, _, name in program):
        return None
    producers = [program for queries, program in threads if not queries
                 and any(name == PRODUCER_MARK for _, _, name in program)]
    table, n_queries = {}, 0

    def book(label, pieces):
        ns = sum(b - a for a, b in pieces)
        if ns > 0:
            table[label] = table.get(label, 0.0) + ns / 1e9

    for queries, program in threads:
        for q0, q1, _ in queries:
            n_queries += 1
            at, idle = q0, []
            for b0, b1 in clip(busy, q0, q1):
                idle.append((at, b0))
                at = b1
            idle.append((at, q1))
            left = [piece for s0, s1, name in
                    innermost_segments(q0, q1, program) if name == STALL
                    for piece in clip(idle, s0, s1)]
            # a piece goes to the first producer with a span open then,
            # so two producers at once never count it twice
            for produced in producers:
                for s0, s1, name in innermost_segments(q0, q1, produced):
                    if name is None or not left:
                        continue
                    book(BETWEEN if name in NOT_A_LEAF else name,
                         clip(left, s0, s1))
                    left = outside(left, s0, s1)
            book(NO_PRODUCER, left)
    return table, n_queries


def read_profile(profile, args, log=None):
    found = behind_table(profile) if profile is not None else None
    if found is None or not found[1]:
        return None
    table, n_queries = found
    if log is not None:
        log(f"idle ms per traced query under {STALL} by the producer's span "
            f"({n_queries} queries, {sum(table.values()) * 1e3 / n_queries:.3f}"
            " in all):")
        for name, s in sorted(table.items(), key=lambda kv: -kv[1]):
            log(f"  {name:24s} {s * 1e3 / n_queries:12.3f}")
    names = set(args["spans"])
    return sum(s for name, s in table.items() if name in names) \
        * 1e3 / n_queries


#: (the run's reduced trace, its profile): a cell reads six metrics from
#: one profile, which is loaded, and its table printed, once
_last = (None, None)


def _log(line):
    print("benchmark: " + line, file=sys.stderr, flush=True)


def read(ctx, args):
    global _last
    if ctx.trace is None:
        return None
    first = _last[0] is not ctx.trace
    if first:
        _last = (ctx.trace, trace_reduce.load(os.path.dirname(SPANS_DIR)))
    return read_profile(_last[1], args, log=_log if first else None)
