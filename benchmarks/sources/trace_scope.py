"""Device milliseconds per traced query and chip under the steps the
kernels name (``jax.named_scope("citus.<scope>")`` where the work is
written), read from the device trace through the maps the program
exports beside its span directory: one ``<module>.<k>.scopes.json`` a
compiled variant, ``{"module", "ops": {instruction: {"scope", "inside",
"calls"}}}`` -- the compiled module's instruction -> scope map, which is
what gives the trace's ``while.8`` and ``fusion.26`` a role.

Per chip plane the ``XLA Modules`` events are executions and an ``XLA
Ops`` event belongs to the execution that holds its start.  SELF TIME:
each instant of an execution goes to the innermost op event over it (a
``while`` holds its body's ops; of two that merely overlap, the one
that started last), then to that op's scope; an op without a scope
takes its enclosing op's, and what no scoped op covers is
UNSCOPED -- so a module's scopes and its unscoped time add up to the
module's seconds (``trace_module``'s).  A fusion counts whole under the
scope XLA left on it (its root's); the table marks the time of fusions
that hold more than one scope ``mixed``.

VARIANTS: two compiled variants of one module name (Q1's and Q6's
``jit_fused``; a short last batch's bucket) number their instructions
independently, and the number in ``jit_fused(N)`` of a module event is
XLA's own for the loaded program, which the program cannot ask for.  So
an execution takes the map of its module name whose instructions cover
its op events (99 % of their time) and most of whose entry computation's
fusions, sorts and loops it ran (the map's ``entry``); it is unscoped
whole where no map does, or where the best two give one of its ops
different scopes.

``{"module": role, "scopes": [names]}`` -> ms per traced query and chip
of the module the cell's ``kernel_modules`` gives that role, under those
scopes.  ``{"scopes": null}`` -> percent of the device time of the
modules that have a map under no scope.  None where the trace has no
device plane, the program wrote no map (a program without scopes), or
the module did not run.  The whole table goes to stderr once a run: it
is PERF.md section 5's content.
"""

import bisect
import glob
import heapq
import json
import os
import sys

from benchmarks import trace_reduce
from benchmarks.trace_reduce import (
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, host_spans, module_name, op_name,
)

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".data", "trace")
MAPS_DIR = os.path.join(TRACE_DIR, "spans.kernels")
UNSCOPED = "(unscoped)"
COVER = 0.99


class Variant(dict):
    """One compiled variant's map, ``{instruction: (scope | None, scopes
    inside)}``, and ``entry``: the fusions, sorts and loops of its entry
    computation, which every execution of it runs."""

    entry = frozenset()


def load_maps(directory) -> dict:
    """{module: [Variant]} of the maps under ``directory``."""
    maps = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.scopes.json"))):
        with open(path) as fh:
            doc = json.load(fh)
        v = Variant((name, (op["scope"], tuple(op["inside"])))
                    for name, op in doc["ops"].items())
        v.entry = frozenset(doc.get("entry", ()))
        maps.setdefault(doc["module"], []).append(v)
    return maps


def _events(plane, line_name, lo, hi):
    """Sorted ``(start, end, name)`` of one line's events that start in
    ``[lo, hi)``."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for e in line.events:
            if lo <= e.start_ns < hi:
                out.append((float(e.start_ns),
                            float(e.start_ns + e.duration_ns), e.name))
    out.sort(key=lambda ev: (ev[0], -ev[1]))
    return out


def choose_map(variants, names_ns: dict):
    """The variant that names an execution's ops, given ``{op name: its
    events' ns}``: of those whose instructions cover ``COVER`` of that
    time, the one most of whose entry ops the execution ran (two
    variants of one module name share many names, ``fusion.3`` in both,
    but not their counts of fusions); None where no variant covers it,
    or where the best ones give one of its ops different scopes."""
    total = sum(names_ns.values())
    covering = [v for v in variants
                if sum(ns for n, ns in names_ns.items() if n in v)
                >= COVER * total]
    if not covering:
        return None

    def ran(v):
        return sum(n in names_ns for n in v.entry) / len(v.entry) \
            if v.entry else 0.0
    most = max(ran(v) for v in covering)
    best = [v for v in covering if ran(v) == most]
    first = best[0]
    for other in best[1:]:
        if any(first.get(n, (None,))[0] != other.get(n, (None,))[0]
               for n in names_ns):
            return None
    return first


class Table:
    """What the scopes took: seconds summed over the chips."""

    def __init__(self):
        self.modules = {}      # module -> [seconds, executions]
        self.scopes = {}       # (module, scope) -> seconds of self time
        self.mixed = {}        # (module, scope) -> [seconds, {scopes inside}]
        self.unscoped_ops = {}     # (module, op | reason) -> seconds
        self.n_devices = 0

    def add(self, module, scope, ns):
        key = (module, scope)
        self.scopes[key] = self.scopes.get(key, 0.0) + ns / 1e9

    def mapped_modules(self):
        """The modules that ran and have a map."""
        return sorted({m for m, _ in self.scopes})


def _account(table, module, ex, ops, variants, decided):
    """One execution ``ex`` with its op events ``ops`` into ``table``."""
    e0, e1, full_name = ex
    names_ns = {}
    for s, e, n in ops:
        names_ns[n] = names_ns.get(n, 0.0) + (e - s)
    key = (full_name, frozenset(names_ns))
    if key not in decided:
        decided[key] = choose_map(variants, names_ns) if ops else None
    chosen = decided[key]
    if chosen is None:
        table.add(module, UNSCOPED, e1 - e0)
        why = (module, "(no map names this execution's ops)" if ops
               else "(no op event)")
        table.unscoped_ops[why] = table.unscoped_ops.get(why, 0.0) \
            + (e1 - e0) / 1e9
        return
    # a sweep over the execution: an instant belongs to the open event
    # that started last (index 0: the execution itself, under no scope),
    # whether the events nest -- a ``while`` over its body's ops -- or
    # merely overlap, as an asynchronous copy does with what follows it
    own, scope_of = [0.0], [None]
    label = [("(between the ops)", ())]
    open_, at = [], e0      # a heap of (-start, -index, end)

    def innermost(now):
        while open_ and open_[0][2] <= now:
            heapq.heappop(open_)
        return -open_[0][1] if open_ else 0

    def advance(to):
        nonlocal at
        while at < to:
            i = innermost(at)
            until = min(to, open_[0][2]) if i else to
            own[i] += until - at
            at = until

    for s, e, n in ops:
        advance(s)
        parent = innermost(s)
        scope, inside = chosen.get(n, (None, ()))
        own.append(0.0)
        scope_of.append(scope if scope is not None else scope_of[parent])
        label.append((n, inside if scope is not None else ()))
        heapq.heappush(open_, (-s, -(len(own) - 1), e))
    advance(e1)
    for ns, scope, (name, inside) in zip(own, scope_of, label):
        if not ns:
            continue
        if scope is None:
            table.add(module, UNSCOPED, ns)
            op = (module, name)
            table.unscoped_ops[op] = table.unscoped_ops.get(op, 0.0) + ns / 1e9
            continue
        table.add(module, scope, ns)
        if len(inside) > 1:
            m = table.mixed.setdefault((module, scope), [0.0, set()])
            m[0] += ns / 1e9
            m[1].update(inside)


def scope_table(profile, maps) -> "Table | None":
    """The self time of every scope of every module that has a map, over
    the window the benchmark's own annotations span (``reduce_trace``'s)
    and every chip; None without a device plane."""
    devices = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    spans = host_spans(profile)
    lo, hi = (spans[0][0], max(s1 for _, s1, _ in spans)) if spans \
        else (float("-inf"), float("inf"))
    table, decided = Table(), {}
    for plane in devices:
        ops = _events(plane, OPS_LINE, float("-inf"), float("inf"))
        if not ops:
            continue
        table.n_devices += 1
        executions = _events(plane, MODULES_LINE, lo, hi)
        starts = [s for s, _, _ in ops]
        for ex in executions:
            module = module_name(ex[2])
            m = table.modules.setdefault(module, [0.0, 0])
            m[0] += (ex[1] - ex[0]) / 1e9
            m[1] += 1
            if module not in maps:
                continue
            i = bisect.bisect_left(starts, ex[0])
            j = bisect.bisect_left(starts, ex[1])
            mine = [(s, min(e, ex[1]), op_name(n)) for s, e, n in ops[i:j]]
            _account(table, module, ex, mine, maps[module], decided)
    return table if table.n_devices else None


def print_table(table, n_queries, log):
    per = 1e3 / table.n_devices / n_queries
    log(f"device ms per traced query and chip by kernel scope "
        f"({n_queries} queries, {table.n_devices} chip(s)):")
    for module in table.mapped_modules():
        seconds, count = table.modules[module]
        log(f"  {module}: {seconds * per:.3f} ms, "
            f"{count / table.n_devices / n_queries:.1f} executions a query")
        rows = sorted(((s, v) for (m, s), v in table.scopes.items()
                       if m == module), key=lambda kv: -kv[1])
        for scope, v in rows:
            mixed = table.mixed.get((module, scope))
            note = "" if mixed is None else (
                f"  mixed {mixed[0] * per:.3f} ms: fusions that also hold "
                + ", ".join(sorted(mixed[1] - {scope})))
            log(f"    {scope:18s} {v * per:12.3f} "
                f"{100.0 * v / seconds if seconds else 0.0:6.1f} %{note}")
        worst = sorted(((op, v) for (m, op), v in table.unscoped_ops.items()
                        if m == module), key=lambda kv: -kv[1])[:8]
        for op, v in worst:
            log(f"      unscoped: {op:32s} {v * per:10.3f}")


#: (the run's reduced trace, its table): a cell reads eleven metrics
#: from one profile, which is loaded, and its table printed, once
_last = (None, None)


def _log(line):
    print("benchmark: " + line, file=sys.stderr, flush=True)


def read_table(table, args, kernel_modules, n_queries):
    if table is None or not n_queries:
        return None
    if args.get("scopes") is None:
        modules = table.mapped_modules()
        total = sum(table.modules[m][0] for m in modules)
        if not total:
            return None
        return 100.0 * sum(table.scopes.get((m, UNSCOPED), 0.0)
                           for m in modules) / total
    module = kernel_modules.get(args["module"])
    if module not in table.mapped_modules():
        return None
    return sum(table.scopes.get((module, s), 0.0) for s in args["scopes"]) \
        * 1e3 / table.n_devices / n_queries


def read(ctx, args):
    global _last
    if ctx.trace is None or not ctx.slice_queries:
        return None
    if _last[0] is not ctx.trace:
        maps = load_maps(MAPS_DIR)
        profile = trace_reduce.load(TRACE_DIR) if maps else None
        table = scope_table(profile, maps) if profile is not None else None
        _last = (ctx.trace, table)
        if table is not None:
            print_table(table, len(ctx.slice_queries), _log)
    return read_table(_last[1], args, ctx.cell.config["kernel_modules"],
                      len(ctx.slice_queries))
