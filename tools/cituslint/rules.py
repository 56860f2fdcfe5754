"""cituslint rules.

Each rule is a small class over the shared ``PackageIndex``.  IDs are
stable (suppressions name them):

========  ==============================================================
LOCK01    lock discipline: attribute mutated under ``with self._mu:``
          somewhere must hold the lock everywhere it is mutated
CONF01    confined calls: the data-driven table below pins risky calls
          to their single blessed module (jax.jit, perf_counter,
          time.time, sync_placement, call_binary, …)
THR01     ``threading.Thread(...)`` must pass an explicit ``daemon=``
THR02     a created thread needs a reachable join()/cancel path
SWL01     silent swallow: ``except Exception: pass`` / bare ``except:``
          with an empty body (no bump, no log, no re-raise)
CNT01     ``bump("name")`` / ``tally("name")`` / span-fold strings must
          name a counter declared in ``StatCounters.COUNTERS``
CNT02     every declared counter must have a bump site (dead counters
          lie in every dashboard)
CNT03     ``begin_wait("event")`` names must be registered in
          ``stats.WAIT_COUNTERS`` and every registered wait event must
          have a begin_wait site (both directions)
CNT04     every health-event kind in ``HEALTH_EVENT_KINDS`` must have a
          Prometheus gauge export and a ``citus_health_events()`` row
          type; ``emit_event("kind")`` literals must be declared
GUC01     ``settings.<section>.<field>`` reads must resolve to a
          declared Settings field
GUC02     every settings field the code reads must be SET/SHOW-covered
          in ``commands/config_cmds.py``'s ``_GUCS`` table
TODO01    no TODO/FIXME/XXX markers in shipped modules
SUP01/02  (engine) unjustified / unknown-id suppressions
========  ==============================================================
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from tools.cituslint.engine import ModuleIndex, PackageIndex, Rule
from tools.cituslint.concurrency import (
    BlockingCallRule, JitPurityRule, LockOrderRule,
)

# --------------------------------------------------------------- LOCK01

#: method names that mutate their receiver in place
_MUTATORS = {
    "append", "extend", "insert", "add", "remove", "discard", "pop",
    "popitem", "clear", "update", "setdefault", "appendleft", "popleft",
}

_LOCK_FACTORIES = {"threading.Lock", "threading.RLock",
                   "threading.Condition"}


def _self_attr(node: ast.AST, self_name: str) -> Optional[str]:
    """``self.attr`` / ``self.attr[...]`` (arbitrarily nested
    subscripts) → ``attr``; None otherwise."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == self_name:
        return node.attr
    return None


class LockDisciplineRule(Rule):
    """For every class that creates a ``threading.Lock/RLock/Condition``
    in ``__init__``: any attribute that is mutated under
    ``with self.<lock>:`` in ONE method is shared state — every other
    mutation of it must hold the lock too.  ``__init__`` itself is
    exempt (the object is still thread-private while constructing)."""

    id = "LOCK01"
    name = "lock discipline"

    def check_module(self, mod, pkg):
        for cls in ast.walk(mod.tree):
            if isinstance(cls, ast.ClassDef):
                yield from self._check_class(mod, cls)

    def _check_class(self, mod: ModuleIndex, cls: ast.ClassDef):
        lock_attrs = self._lock_attrs(mod, cls)
        if not lock_attrs:
            return
        # (method, attr, line, guarded) for every self-attribute
        # mutation outside __init__.  A method named *_locked is BY
        # CONVENTION called with the lock held: its mutations count as
        # guarded, and calls to it from unguarded context are flagged
        # below instead.
        records = []
        guarded_attrs: dict[str, tuple] = {}  # attr -> (method, line)
        helper_calls = []  # (method, helper, line, guarded)
        for meth in cls.body:
            if not isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            args = meth.args.posonlyargs + meth.args.args
            if not args:
                continue  # staticmethod: no shared self state
            self_name = args[0].arg
            held = meth.name.endswith("_locked")
            for attr, line, guarded in self._mutations(
                    mod, meth, self_name, lock_attrs, base=held):
                if meth.name == "__init__":
                    continue
                records.append((meth.name, attr, line, guarded))
                if guarded:
                    guarded_attrs.setdefault(attr, (meth.name, line))
            for helper, line, guarded in self._locked_helper_calls(
                    meth, self_name, lock_attrs, base=held):
                helper_calls.append((meth.name, helper, line, guarded))
        for meth_name, attr, line, guarded in records:
            if guarded or attr not in guarded_attrs:
                continue
            gm, gl = guarded_attrs[attr]
            yield self.diag(
                mod, line,
                f"{cls.name}.{meth_name} mutates 'self.{attr}' without "
                f"holding a lock, but {gm} (line {gl}) mutates it under "
                f"'with self.<lock>:' — unguarded shared-state write")
        for meth_name, helper, line, guarded in helper_calls:
            if not guarded:
                yield self.diag(
                    mod, line,
                    f"{cls.name}.{meth_name} calls lock-held helper "
                    f"self.{helper}() without holding the lock "
                    f"(*_locked methods assume the caller locked)")

    def _lock_attrs(self, mod: ModuleIndex, cls: ast.ClassDef) -> set:
        out = set()
        for meth in cls.body:
            if not isinstance(meth, ast.FunctionDef) \
                    or meth.name != "__init__":
                continue
            args = meth.args.posonlyargs + meth.args.args
            self_name = args[0].arg if args else "self"
            for node in ast.walk(meth):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call) \
                        and mod.dotted(node.value.func) in _LOCK_FACTORIES:
                    for t in node.targets:
                        attr = _self_attr(t, self_name)
                        if attr is not None:
                            out.add(attr)
        return out

    def _locked_helper_calls(self, meth, self_name: str,
                             lock_attrs: set, base: bool = False):
        """Yield (helper, line, guarded) for calls to
        ``self.<x>_locked(...)`` inside ``meth``."""

        def visit(node, guarded):
            if isinstance(node, ast.With):
                holds = guarded or any(
                    _self_attr(item.context_expr, self_name) in lock_attrs
                    for item in node.items)
                for child in node.body:
                    yield from visit(child, holds)
                return
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr.endswith("_locked") \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == self_name:
                yield (node.func.attr, node.lineno, guarded)
            for child in ast.iter_child_nodes(node):
                yield from visit(child, guarded)

        for stmt in meth.body:
            yield from visit(stmt, base)

    def _mutations(self, mod: ModuleIndex, meth: ast.AST,
                   self_name: str, lock_attrs: set, base: bool = False):
        """Yield (attr, line, guarded) for each write to a self
        attribute inside ``meth``; ``guarded`` means an enclosing
        ``with self.<lock>:`` (or an ``finally``-released
        ``self.<lock>.acquire()`` idiom is NOT recognized — use with)."""

        def visit(node, guarded):
            if isinstance(node, ast.With):
                holds = guarded or any(
                    _self_attr(item.context_expr, self_name) in lock_attrs
                    for item in node.items)
                for item in node.items:
                    yield from visit(item.context_expr, guarded)
                for child in node.body:
                    yield from visit(child, holds)
                return
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for el in (t.elts if isinstance(
                            t, (ast.Tuple, ast.List)) else [t]):
                        attr = _self_attr(el, self_name)
                        if attr is not None and attr not in lock_attrs:
                            yield (attr, el.lineno, guarded)
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    attr = _self_attr(t, self_name)
                    if attr is not None and attr not in lock_attrs:
                        yield (attr, t.lineno, guarded)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _MUTATORS:
                attr = _self_attr(node.func.value, self_name)
                if attr is not None and attr not in lock_attrs:
                    yield (attr, node.lineno, guarded)
            for child in ast.iter_child_nodes(node):
                yield from visit(child, guarded)

        for stmt in meth.body if isinstance(
                meth, (ast.FunctionDef, ast.AsyncFunctionDef)) else []:
            yield from visit(stmt, base)


# --------------------------------------------------------------- CONF01

#: dotted call -> in-package files allowed to make it.  This is the
#: generalization of the old hand-written CI checks: one table, one
#: rule, one failure shape.
CONFINED_CALLS = {
    # jax.jit only inside the kernel cache's jit_compile wrapper, so
    # ad-hoc compiles can't dodge cache accounting
    "jax.jit": ("executor/kernel_cache.py",),
    # query-axis batching: vmap-lifted kernels exist only where they
    # flow through get_kernel's batched: slots (executor/megabatch.py)
    # or the jit door itself
    "jax.vmap": ("executor/megabatch.py", "executor/kernel_cache.py"),
    # one span-timing clock for the whole package
    "time.perf_counter": ("observability/trace.py",),
    # one wall clock, swappable in tests (utils/clock.py now())
    "time.time": ("utils/clock.py",),
    # raw pool slots flow through the tenant-aware fair-share
    # scheduler only (workload/scheduler.py); anything else acquiring
    # directly would barge the per-tenant admission queues
    "citus_tpu.executor.admission.GLOBAL_POOL.acquire":
        ("workload/scheduler.py",),
    "citus_tpu.executor.admission.GLOBAL_POOL.release":
        ("workload/scheduler.py",),
    # wire codecs live in the data plane: npz is the LEGACY wire
    # fallback (zip container), and anything else serializing arrays
    # for the network must go through the frame codec there
    "numpy.savez": ("net/data_plane.py",),
    "numpy.load": ("net/data_plane.py",),
    # exactly one selector-driven dispatcher per process — ad-hoc
    # selectors would re-grow thread-per-RPC shapes around it
    "selectors.DefaultSelector": ("net/event_loop.py",),
    # the fused decode→filter→partial-agg(+merge) kernel builder lives
    # in ops/ and is entered only through the executor's jit_fused /
    # batched:jit_fused kernel-cache slots — an ad-hoc fused build
    # elsewhere would dodge both the cache and the donated-accumulator
    # discipline (the dtype/shape contract with _empty_partials)
    "citus_tpu.ops.scan_agg.build_fused_worker_fn":
        ("executor/executor.py", "executor/megabatch.py"),
    # same discipline for the streaming fused hash-table builder: only
    # the executor's jit_hash_fused / batched:jit_hash_fused slots may
    # enter it (the slot count / donated-state contract with
    # empty_hash_state); the device join aggregates its packed
    # survivors through the same slot
    "citus_tpu.ops.hash_agg.build_fused_hash_worker":
        ("executor/executor.py", "executor/megabatch.py",
         "executor/join_device.py"),
    # hash-partial frames are wire format: encoded only by the task
    # codec halves, never ad-hoc
    "citus_tpu.net.data_plane.encode_hash_partials":
        ("executor/worker_tasks.py", "net/data_plane.py"),
    # placement-mutating operations have exactly five doors: the
    # rebalancer, the autopilot's actuator, the SQL command surface,
    # tenant isolation's split+move composition, and the background-job
    # runner registration.  A bare move/split launched from query-path
    # code would race the group-write-lock + catalog-flip discipline
    # those doors ride (and dodge the operation registry the autopilot
    # uses for exactly-once).  Both dotted forms are pinned because the
    # package __init__ re-exports move_shard_placement.
    "citus_tpu.operations.shard_transfer.move_shard_placement": (
        "operations/rebalancer.py", "services/autopilot.py",
        "commands/utility.py", "workload/isolation.py", "cluster.py"),
    "citus_tpu.operations.move_shard_placement": (
        "operations/rebalancer.py", "services/autopilot.py",
        "commands/utility.py", "workload/isolation.py", "cluster.py"),
    "citus_tpu.operations.shard_split.split_shard": (
        "commands/utility.py", "workload/isolation.py"),
    "citus_tpu.operations.split_shard": (
        "commands/utility.py", "workload/isolation.py"),
    "citus_tpu.workload.isolation.isolate_tenant_to_node": (
        "commands/utility.py",),
}

#: method name -> in-package files allowed to CALL it (receiver-typed
#: calls the dotted resolver can't see; matched by attribute name)
CONFINED_METHODS = {
    # the O(placement-bytes) pull path has exactly one executor door
    "sync_placement": ("executor/batches.py",),
    # the catalog placement flip is the move's 2PC decision — it must
    # ride the non-blocking sequence (final catch-up under the group
    # write lock + commit_metadata_flip); a flip anywhere else loses
    # writes raced onto the source
    "flip_placement": ("operations/shard_transfer.py",),
    # flight-recorder segment writes are the recorder's only disk
    # side-effect — confining the write door keeps retention/rotation
    # accounting honest (no second writer aging the segments)
    "append_segment_line": ("observability/flight_recorder.py",),
    # rollup refresh is the ONE door that advances a rollup past its
    # watermark: delta fold + upsert + watermark write commit as a
    # single transaction there (exactly-once restart replay); a second
    # caller would double-apply deltas or tear the watermark
    "refresh_once": ("rollup/manager.py",),
    "_apply_batch": ("rollup/manager.py",),
    # the replicated tenant control plane has ONE write door
    # (metadata/quotas.py): every catalog quota/class write must ride
    # the 2PC commit_metadata_flip sequence and re-hydrate the local
    # registry — a bare put anywhere else forks this coordinator's
    # admission behavior from the rest of the cluster
    "put_tenant_quota": ("metadata/quotas.py",),
    "drop_tenant_quota": ("metadata/quotas.py",),
    "put_priority_class": ("metadata/quotas.py",),
}

#: method name -> files where calling it is banned outright
BANNED_METHODS = {
    # worker_tasks ships tasks through the parallel dispatcher; a
    # sequential per-task RPC loop here costs sum-of-hosts not max
    "call_binary": ("executor/worker_tasks.py",),
    "call_binary_pooled": ("executor/worker_tasks.py",),
}

#: file -> identifiers that must appear in it (the positive half of
#: the dispatch invariant)
REQUIRED_IDENTIFIERS = {
    "executor/worker_tasks.py": ("dispatch_remote_tasks",),
    # the fan-out must ride the single event-loop dispatcher
    # (cat.remote_data.event_loop()), not per-RPC threads
    "executor/pipeline.py": ("event_loop",),
}


class ConfinedCallRule(Rule):
    """Data-driven call confinement (tables above)."""

    id = "CONF01"
    name = "confined calls"

    def check_module(self, mod, pkg):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = mod.dotted(node.func)
            if dotted in CONFINED_CALLS \
                    and mod.rel not in CONFINED_CALLS[dotted]:
                yield self.diag(
                    mod, node.lineno,
                    f"call to {dotted}() is confined to "
                    f"{', '.join(CONFINED_CALLS[dotted])}")
            if isinstance(node.func, ast.Attribute):
                name = node.func.attr
                if name in CONFINED_METHODS \
                        and mod.rel not in CONFINED_METHODS[name]:
                    yield self.diag(
                        mod, node.lineno,
                        f"call to .{name}() is confined to "
                        f"{', '.join(CONFINED_METHODS[name])}")
                if name in BANNED_METHODS \
                        and mod.rel in BANNED_METHODS[name]:
                    yield self.diag(
                        mod, node.lineno,
                        f"call to .{name}() is banned in {mod.rel}")

    def check_package(self, pkg):
        for rel, idents in REQUIRED_IDENTIFIERS.items():
            mod = pkg.by_rel.get(rel)
            if mod is None:
                continue
            present = {n.id for n in ast.walk(mod.tree)
                       if isinstance(n, ast.Name)}
            present |= {n.attr for n in ast.walk(mod.tree)
                        if isinstance(n, ast.Attribute)}
            for ident in idents:
                if ident not in present:
                    yield self.diag(mod, 1,
                                    f"{rel} must reference {ident!r} "
                                    f"(architecture invariant)")


# --------------------------------------------------------------- THR01/02


def _thread_calls(mod: ModuleIndex):
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and mod.dotted(node.func) == "threading.Thread":
            yield node


def _thread_binding(node: ast.Call) -> Optional[str]:
    """Name or attribute a ``Thread(...)`` call is assigned to."""
    parent = getattr(node, "_lint_parent", None)
    if isinstance(parent, ast.Assign):
        for t in parent.targets:
            if isinstance(t, ast.Name):
                return t.id
            if isinstance(t, ast.Attribute):
                return t.attr
    return None


class ThreadDaemonRule(Rule):
    """``threading.Thread(...)`` must pass an explicit ``daemon=`` —
    thread lifetime is a decision, not a default."""

    id = "THR01"
    name = "explicit thread daemon flag"

    def check_module(self, mod, pkg):
        for node in _thread_calls(mod):
            if not any(kw.arg == "daemon" for kw in node.keywords):
                yield self.diag(
                    mod, node.lineno,
                    "threading.Thread(...) must pass an explicit "
                    "daemon= keyword")


class ThreadJoinRule(Rule):
    """A created thread needs a reachable join()/cancel path: the name
    or attribute it is bound to must be ``.join()``-ed (or
    ``.cancel()``-ed) somewhere in the module; a fire-and-forget
    Thread needs a justified suppression."""

    id = "THR02"
    name = "thread join/cancel path"

    def check_module(self, mod, pkg):
        joined = self._joined_names(mod)
        for node in _thread_calls(mod):
            bound = _thread_binding(node)
            if bound is None or bound not in joined:
                tgt = f"'{bound}'" if bound else "an unbound Thread"
                yield self.diag(
                    mod, node.lineno,
                    f"thread bound to {tgt} has no reachable .join()/"
                    f".cancel() call in this module")

    def _joined_names(self, mod: ModuleIndex) -> set:
        out = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("join", "cancel"):
                v = node.func.value
                if isinstance(v, ast.Attribute):
                    out.add(v.attr)
                elif isinstance(v, ast.Name):
                    out.add(v.id)
        return out


# ---------------------------------------------------------------- SWL01


class SilentSwallowRule(Rule):
    """``except Exception:`` / bare ``except:`` whose body is only
    ``pass``/``continue`` swallows failures invisibly: bump a counter,
    log, re-raise — or justify the suppression."""

    id = "SWL01"
    name = "silent exception swallow"

    def check_module(self, mod, pkg):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._broad(mod, node.type):
                continue
            if all(isinstance(s, (ast.Pass, ast.Continue))
                   for s in node.body):
                what = ("bare except:" if node.type is None
                        else "except Exception: pass")
                yield self.diag(
                    mod, node.lineno,
                    f"{what} silently swallows the failure — bump a "
                    f"counter, log, or re-raise")

    def _broad(self, mod: ModuleIndex, t) -> bool:
        if t is None:
            return True
        if isinstance(t, ast.Tuple):
            return any(self._broad(mod, el) for el in t.elts)
        return mod.dotted(t) in ("Exception", "BaseException",
                                 "builtins.Exception",
                                 "builtins.BaseException")


# -------------------------------------------------------------- CNT01/02


def _counters_decl(pkg: PackageIndex):
    """(names, (lineno, end_lineno), module) of StatCounters.COUNTERS
    in <pkg>/stats.py; (set(), None, None) when absent."""

    def build():
        mod = pkg.by_rel.get("stats.py")
        if mod is None:
            return (set(), None, None)
        for cls in mod.tree.body:
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == "StatCounters"):
                continue
            for stmt in cls.body:
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "COUNTERS"
                        for t in stmt.targets):
                    names = {n.value for n in ast.walk(stmt.value)
                             if isinstance(n, ast.Constant)
                             and isinstance(n.value, str)}
                    return (names, (stmt.lineno, stmt.end_lineno), mod)
        return (set(), None, None)

    return pkg.cached("counters_decl", build)


class CounterNameRule(Rule):
    """Every ``bump("name")``/``bump_max("name")`` literal, every
    ``tally("name")`` of an execution's record (``executor/pipeline.py``
    ``PipelineStats``: the statement's figure and the counter's bump in
    one call) and every value of a ``_SPAN_MS``-style span-fold dict
    must be declared in ``StatCounters.COUNTERS`` — a typo'd bump
    silently counts into the void."""

    id = "CNT01"
    name = "counter names declared"

    def check_module(self, mod, pkg):
        names, _span, _mod = _counters_decl(pkg)
        if _mod is None:
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("bump", "bump_max", "tally") \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value not in names:
                yield self.diag(
                    mod, node.lineno,
                    f"bump of undeclared counter "
                    f"{node.args[0].value!r} (not in "
                    f"StatCounters.COUNTERS)")
            if isinstance(node, ast.Assign) \
                    and any(isinstance(t, ast.Name)
                            and t.id.endswith("_SPAN_MS")
                            for t in node.targets) \
                    and isinstance(node.value, ast.Dict):
                for v in node.value.values:
                    if isinstance(v, ast.Constant) \
                            and isinstance(v.value, str) \
                            and v.value not in names:
                        yield self.diag(
                            mod, v.lineno,
                            f"span-fold target {v.value!r} is not a "
                            f"declared counter")


class DeadCounterRule(Rule):
    """Inverse of CNT01: every declared counter needs at least one
    bump site (a string-literal use outside the declaration: a
    ``tally("name")`` is one as a ``bump("name")`` is)."""

    id = "CNT02"
    name = "no dead counters"

    def check_package(self, pkg):
        names, span, decl_mod = _counters_decl(pkg)
        if decl_mod is None or not names:
            return
        used = set()
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)):
                    continue
                if mod is decl_mod and span \
                        and span[0] <= node.lineno <= span[1]:
                    continue  # the declaration itself is not a use
                used.add(node.value)
        for name in sorted(names - used):
            yield self.diag(
                decl_mod, span[0],
                f"counter {name!r} is declared but never bumped "
                f"anywhere in the package")


def _wait_events_decl(pkg: PackageIndex):
    """(event names, (lineno, end_lineno), module) of the module-level
    ``WAIT_COUNTERS`` dict in <pkg>/stats.py; (set(), None, None) when
    absent."""

    def build():
        mod = pkg.by_rel.get("stats.py")
        if mod is None:
            return (set(), None, None)
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "WAIT_COUNTERS"
                    for t in stmt.targets) \
                    and isinstance(stmt.value, ast.Dict):
                keys = {k.value for k in stmt.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)}
                return (keys, (stmt.lineno, stmt.end_lineno), mod)
        return (set(), None, None)

    return pkg.cached("wait_events_decl", build)


class WaitEventRule(Rule):
    """Cross-consistency for the wait-event seam: every literal
    ``begin_wait("event")`` in the package must name a key of
    ``stats.WAIT_COUNTERS`` (a typo'd event books blocked time into a
    KeyError at end_wait), and every registered event must have at
    least one begin_wait site (an unentered event lies in every
    wait-profile dashboard)."""

    id = "CNT03"
    name = "wait events registered"

    def check_package(self, pkg):
        events, span, decl_mod = _wait_events_decl(pkg)
        if decl_mod is None or not events:
            return
        entered = set()
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                fname = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fname != "begin_wait" or not node.args:
                    continue
                arg = node.args[0]
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    continue
                if arg.value not in events:
                    yield self.diag(
                        mod, node.lineno,
                        f"begin_wait of unregistered wait event "
                        f"{arg.value!r} (not a stats.WAIT_COUNTERS key)")
                else:
                    entered.add(arg.value)
        for ev in sorted(events - entered):
            yield self.diag(
                decl_mod, span[0],
                f"wait event {ev!r} is registered but no begin_wait "
                f"site enters it")


def _health_kinds_decl(pkg: PackageIndex):
    """(kind names, (lineno, end_lineno), module) of the module-level
    ``HEALTH_EVENT_KINDS`` dict in <pkg>/observability/flight_recorder.py;
    (set(), None, None) when absent."""

    def build():
        mod = pkg.by_rel.get("observability/flight_recorder.py")
        if mod is None:
            return (set(), None, None)
        for stmt in mod.tree.body:
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name)
                    and t.id == "HEALTH_EVENT_KINDS"
                    for t in stmt.targets) \
                    and isinstance(stmt.value, ast.Dict):
                keys = {k.value for k in stmt.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)}
                return (keys, (stmt.lineno, stmt.end_lineno), mod)
        return (set(), None, None)

    return pkg.cached("health_kinds_decl", build)


def _module_strings(mod: ModuleIndex) -> set:
    """All string constants appearing anywhere in a module."""
    return {n.value for n in ast.walk(mod.tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


class HealthEventRule(Rule):
    """Cross-consistency for the health-event seam: every kind declared
    in ``flight_recorder.HEALTH_EVENT_KINDS`` must surface BOTH as a
    Prometheus gauge in ``observability/export.py`` (the ``health_<kind>``
    literal) and as a ``citus_health_events()`` row type in
    ``commands/utility.py`` (the severity table) — an alert kind that
    exists in only one surface is invisible to half the operators.  And
    every literal ``emit_event("kind")`` must name a declared kind (a
    typo'd kind raises at runtime on the sampler thread, where nobody
    is watching)."""

    id = "CNT04"
    name = "health-event kinds exported"

    #: kind must appear (bare or ``health_``-prefixed) in each of these
    SURFACES = (
        ("observability/export.py", "Prometheus gauge export"),
        ("commands/utility.py", "citus_health_events() row type"),
    )

    def check_package(self, pkg):
        kinds, span, decl_mod = _health_kinds_decl(pkg)
        if decl_mod is None or not kinds:
            return
        for rel, what in self.SURFACES:
            mod = pkg.by_rel.get(rel)
            if mod is None:
                continue
            strings = _module_strings(mod)
            for kind in sorted(kinds):
                if kind not in strings \
                        and f"health_{kind}" not in strings:
                    yield self.diag(
                        decl_mod, span[0],
                        f"health-event kind {kind!r} has no {what} "
                        f"in {rel}")
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                fname = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else None)
                if fname not in ("emit_event", "_emit_locked") \
                        or not node.args:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str) \
                        and arg.value not in kinds:
                    yield self.diag(
                        mod, node.lineno,
                        f"emit of undeclared health-event kind "
                        f"{arg.value!r} (not a HEALTH_EVENT_KINDS key)")


# -------------------------------------------------------------- GUC01/02


def _settings_schema(pkg: PackageIndex):
    """Parse <pkg>/config.py: ({section: field-set}, direct-field-set,
    methods).  Empty when config.py is absent."""

    def build():
        mod = pkg.by_rel.get("config.py")
        if mod is None:
            return ({}, set(), set(), None)
        class_fields: dict[str, set] = {}
        class_methods: dict[str, set] = {}
        for cls in mod.tree.body:
            if not isinstance(cls, ast.ClassDef):
                continue
            fields, methods = set(), set()
            for stmt in cls.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    fields.add(stmt.target.id)
                elif isinstance(stmt, ast.FunctionDef):
                    methods.add(stmt.name)
            class_fields[cls.name] = fields
            class_methods[cls.name] = methods
        sections: dict[str, set] = {}
        direct: set = set()
        for cls in mod.tree.body:
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == "Settings"):
                continue
            for stmt in cls.body:
                if not (isinstance(stmt, ast.AnnAssign)
                        and isinstance(stmt.target, ast.Name)):
                    continue
                ann = stmt.annotation
                ann_name = ann.id if isinstance(ann, ast.Name) else None
                if ann_name in class_fields and ann_name != "Settings":
                    sections[stmt.target.id] = class_fields[ann_name]
                else:
                    direct.add(stmt.target.id)
            methods = class_methods.get("Settings", set())
            return (sections, direct, methods, mod)
        return ({}, set(), set(), None)

    return pkg.cached("settings_schema", build)


def _guc_coverage(pkg: PackageIndex):
    """(section, field) pairs covered by _GUCS in
    <pkg>/commands/config_cmds.py."""

    def build():
        mod = pkg.by_rel.get("commands/config_cmds.py")
        if mod is None:
            return (set(), None)
        covered = set()
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "_GUCS"
                            for t in node.targets)
                    and isinstance(node.value, ast.Dict)):
                continue
            for v in node.value.values:
                if isinstance(v, ast.Tuple) and len(v.elts) >= 2 \
                        and isinstance(v.elts[0], ast.Constant) \
                        and isinstance(v.elts[1], ast.Constant):
                    covered.add((v.elts[0].value, v.elts[1].value))
        return (covered, mod)

    return pkg.cached("guc_coverage", build)


class SettingsFieldRule(Rule):
    """Every ``settings.<section>.<field>`` attribute read must resolve
    to a declared Settings field (GUC01) and that field must have
    SET/SHOW coverage in the ``_GUCS`` table (GUC02) — config a DBA
    cannot inspect or change at runtime is a support hazard."""

    id = "GUC01"
    name = "settings reads resolve + SET/SHOW covered"

    def check_module(self, mod, pkg):
        sections, direct, methods, cfg_mod = _settings_schema(pkg)
        if cfg_mod is None or mod is cfg_mod:
            return
        covered, gucs_mod = _guc_coverage(pkg)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Attribute):
                continue
            parent = getattr(node, "_lint_parent", None)
            if isinstance(parent, ast.Attribute):
                continue  # only the outermost link of a chain
            chain = self._chain_after_settings(mod, node)
            if not chain:
                continue
            head = chain[0]
            if head in sections:
                if len(chain) < 2:
                    continue
                f = chain[1]
                if f not in sections[head]:
                    yield self.diag(
                        mod, node.lineno,
                        f"settings.{head}.{f} does not resolve to a "
                        f"declared {head.capitalize()}Settings field")
                elif gucs_mod is not None \
                        and (head, f) not in covered:
                    yield self.diag(
                        mod, node.lineno,
                        f"settings.{head}.{f} is read here but has no "
                        f"SET/SHOW entry in commands/config_cmds.py "
                        f"_GUCS (GUC02)")
            elif head not in direct and head not in methods:
                yield self.diag(
                    mod, node.lineno,
                    f"settings.{head} does not resolve to a declared "
                    f"Settings field or section")
            elif head in direct and gucs_mod is not None \
                    and (None, head) not in covered:
                yield self.diag(
                    mod, node.lineno,
                    f"settings.{head} is read here but has no SET/SHOW "
                    f"entry in commands/config_cmds.py _GUCS (GUC02)")

    def _chain_after_settings(self, mod: ModuleIndex,
                              node: ast.Attribute) -> Optional[list]:
        parts: list[str] = []
        cur: ast.AST = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        parts.reverse()
        if "settings" in parts:
            return parts[parts.index("settings") + 1:]
        if isinstance(cur, ast.Name) and cur.id == "settings":
            return parts
        if isinstance(cur, ast.Call):
            fn = mod.dotted(cur.func)
            if fn and fn.split(".")[-1] == "current_settings":
                return parts
        return None


# --------------------------------------------------------------- TODO01

_TODO = re.compile(r"\b(TODO|FIXME|XXX)\b")


class TodoMarkerRule(Rule):
    """No TODO/FIXME/XXX stubs in shipped modules — the package ships
    complete components, not placeholders."""

    id = "TODO01"
    name = "no TODO markers"

    def check_module(self, mod, pkg):
        for i, line in enumerate(mod.lines, 1):
            if _TODO.search(line):
                yield self.diag(mod, i,
                                f"{_TODO.search(line).group(1)} marker "
                                f"in shipped module")


ALL_RULES = [
    LockDisciplineRule,
    LockOrderRule,
    BlockingCallRule,
    JitPurityRule,
    ConfinedCallRule,
    ThreadDaemonRule,
    ThreadJoinRule,
    SilentSwallowRule,
    CounterNameRule,
    DeadCounterRule,
    WaitEventRule,
    HealthEventRule,
    SettingsFieldRule,
    TodoMarkerRule,
]
