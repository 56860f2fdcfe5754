"""Process-wide compiled-kernel cache keyed by structural plan fingerprint.

The reference caches one local plan per prepared statement
(local_plan_cache.c); the TPU-native analog caches the *compiled XLA
program* per plan **family**.  Two queries that differ only in hoisted
comparison literals (planner/auto_param.py) bind to structurally
identical plans, so their worker/merge/filter kernels are the same
program — this module makes that sharing explicit and process-wide:

- ``plan_fingerprint(plan)`` — canonical digest over everything the
  kernel builders in ops/scan_agg.py, ops/hash_agg.py and the executor's
  filter/merge closures actually close over: the bound filter tree, the
  group keys, deduped aggregate args, partial-op kinds/dtypes, the group
  mode (domains/strides), the scan columns with their device dtypes, and
  the parameter count (env layout).  Deliberately EXCLUDED: pruning
  intervals, shard indexes, router key, limit/order, final_exprs and
  agg_extract — the combine/finalize half runs on the host and per-batch
  shapes key into jax.jit's own trace cache, so none of them change the
  compiled program.  Worker-side decoded plans (executor/worker_tasks.py
  ``_decode_plan``) rebuild these fields deterministically, which is how
  repeated remote ``execute_task`` RPCs share one compiled kernel.
- ``get_kernel(plan, slot, build)`` — per-plan ``runtime_cache`` mirror
  in front of a global LRU (``citus.kernel_cache_size`` entries), so a
  plan-cache hit costs a dict lookup and a plan-cache miss that lands on
  a known fingerprint skips XLA entirely (kernel_cache_hits counter).
  The mirror is shared by every caller of a cached plan and holds what
  is compiled from it alone: kernels by slot, ``_fingerprint``, the
  numpy arm's ``np_filter`` / ``np_final_fns`` closures.
- ``jit_compile(fn)`` — the ONLY ``jax.jit`` call site in the package
  (CI-enforced, tests/test_ci_invariants.py); asks the no-silent-CPU
  device guard, then wraps the jitted callable to attribute
  trace+compile time to the ``kernel_compile_ms`` counter.
- ``configure_persistent_cache()`` — JAX's on-disk XLA compilation
  cache, on at every Cluster open, so process restarts skip compiles.
- ``export_kernel_scopes(directory)`` — one ``<module>.<k>.scopes.json``
  per compiled variant of every kernel whose body names its steps
  (``observability/trace.py`` ``kernel_scope``): the compiled module's
  instruction -> scope map (``scope_map``), which gives the ``while.8``
  and ``fusion.26`` of a device trace their roles.
  ``follow_kernel_scopes`` keeps a directory current as kernels compile.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import re
import threading
import weakref
from collections import OrderedDict
from typing import Callable, Optional

from citus_tpu.observability import trace as _trace
from citus_tpu.observability.trace import clock

#: default LRU entry cap (kernels, not bytes: compiled executables are
#: host-memory cheap relative to HBM batches) — citus.kernel_cache_size
DEFAULT_CAPACITY = 512


def _counters():
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    return GLOBAL_COUNTERS


class _TimedJit:
    """jax.jit wrapper that detects compiles (the underlying trace cache
    grew across a call) and books their wall time into kernel_compile_ms.
    Everything else — ``_cache_size`` introspection included — delegates
    to the jitted callable.

    Calls are serialized per kernel: shared kernels make concurrent
    invocations of ONE compiled executable the common case (every reader
    of a query family lands on the same object), and XLA:CPU collectives
    (psum/all_gather in the mesh kernels) can interleave their device
    rendezvous when the same executable runs from two threads at once —
    observed as a wedged jitted call under a reader/writer storm.  The
    lock also keeps the before/after trace-cache compile accounting
    race-free."""

    __slots__ = ("_fn", "_mu", "_variants", "__weakref__")

    def __init__(self, fn):
        self._fn = fn
        self._mu = threading.Lock()
        #: [(serial, abstract (args, kwargs))] of the compiles that
        #: traced a ``kernel_scope``: what ``export_kernel_scopes`` lowers
        self._variants: list = []

    def __call__(self, *args, **kw):
        from citus_tpu.testing.faults import FAULTS
        fn = self._fn
        with self._mu:
            # per-dispatch injection point UNDER the kernel lock: a
            # delay armed here serializes across every caller of this
            # compiled executable, which is what makes the megabatch
            # A/B throughput test (tests/test_megabatch.py) a fair
            # model of per-dispatch device latency
            FAULTS.hit("kernel_dispatch", "")
            try:
                before = fn._cache_size()
            except Exception:
                before = None
            t0 = clock()
            out = fn(*args, **kw)
            if before is not None:
                try:
                    grew = fn._cache_size() > before
                except Exception:
                    grew = False
                if grew:
                    t1 = clock()
                    _counters().bump("kernel_compile_ms",
                                     max(1, int((t1 - t0) * 1000)))
                    _counters().bump("kernel_compiles")
                    # compiles are detected after the fact (the trace
                    # cache grew across the call) — record retroactively
                    ctx = _trace.current()
                    if ctx is not None:
                        tr, parent = ctx
                        tr.add_closed(
                            "kernel_compile", parent.span_id, t0, t1,
                            {"module": self.module,
                             "shapes": _leading_shapes(args)})
                    if _trace.take_kernel_scopes():
                        # lint: disable=BLK01 -- only at a compile, which held this lock for seconds already: a followed directory gets the kernel's map here
                        self._scoped_compile(args, kw)
        return out

    @property
    def module(self) -> str:
        """The XLA module's name, as a device trace shows it."""
        return "jit_" + getattr(self._fn, "__name__", "fn")

    def _scoped_compile(self, args, kw) -> None:
        """Remember the signature this call compiled for (a donated,
        deleted argument still says its shape, dtype and sharding) and,
        while a directory is followed, write its map there."""
        variant = (next(_serials), _abstract_signature((args, kw)))
        self._variants.append(variant)
        if _followed is not None:
            try:
                _write_scope_map(self, variant, _followed)
            except OSError:
                pass  # export is best-effort; never fail the query

    def __getattr__(self, name):
        return getattr(self._fn, name)


def jit_compile(fn: Callable, **jit_kwargs) -> _TimedJit:
    """The package's single jax.jit entry point — and so the one point
    every kernel slot passes before it can exist: the no-silent-CPU
    guard (parallel/mesh.py ``executor_devices``) is asked here, once
    per kernel built, whatever path reached the build."""
    import jax
    from citus_tpu.parallel.mesh import executor_devices
    executor_devices()
    kernel = _TimedJit(jax.jit(fn, **jit_kwargs))
    with _kernels_mu:
        _kernels.add(kernel)
    return kernel


# ------------------------------------------------------- kernel scopes

#: every live kernel ``jit_compile`` made (weak: the LRU and the plans'
#: mirrors own them)
_kernels: "weakref.WeakSet[_TimedJit]" = weakref.WeakSet()
_kernels_mu = threading.Lock()
#: the ``<k>`` of a variant's file: one number a remembered compile, so
#: a second export of a directory rewrites its files and adds none
_serials = itertools.count()
#: where a scoped kernel writes its map as it compiles, or None
_followed: Optional[str] = None


def _abstract_signature(tree):
    """``tree`` with every array leaf as its ``jax.ShapeDtypeStruct``
    (shape, dtype, weak type, and the sharding of an array that is
    COMMITTED to its devices: a sharding named for an uncommitted one
    lowers to another module text, which misses the compile cache's
    entry of the call); what is no array (a static argument) stays."""
    import jax

    def leaf(x):
        shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        return jax.ShapeDtypeStruct(
            shape, dtype, weak_type=bool(getattr(x, "weak_type", False)),
            sharding=x.sharding if getattr(x, "committed", False) else None)
    return jax.tree_util.tree_map(leaf, tree)


def _leading_shapes(args, limit: int = 120) -> str:
    """``int32[4194304] bool[4194304] ...`` of the leading argument
    leaves, cut to ``limit`` characters: what a kernel recompiled for."""
    import jax
    out = ""
    for x in jax.tree_util.tree_leaves(args):
        shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
        word = type(x).__name__ if shape is None or dtype is None else \
            f"{dtype}[{','.join(str(d) for d in shape)}]"
        if len(out) + len(word) + 1 > limit:
            return out + "..." if len(out) + 3 <= limit else out
        out += (" " if out else "") + word
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"citus\.([A-Za-z_][\w.]*)")
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
#: the opcodes whose called computations run as ops of their own in a
#: device trace (a reduce's or a sort's ``to_apply`` does not)
_RUNS = ("while", "conditional", "call")
_ALWAYS_RUN = ("fusion", "sort", "while", "conditional")


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``name = ``: what
    stands between the shape (a tuple's parentheses nest) and ``(``."""
    at = 0
    if rest.startswith("("):
        depth = 0
        for at, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        at += 1
    else:
        at = rest.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[at:])
    return m.group(1) if m else ""


def scope_map(hlo_text: str) -> dict:
    """-> {instruction: {"scope", "inside", "calls"}} over every
    computation of an optimized module's text.  ``scope``: the last
    ``citus.<scope>`` component of the instruction's ``op_name`` (None
    where it has none: an instruction XLA made, or one of a body that
    names no step).  ``inside``: for a fusion, the scopes of its fused
    computation's instructions, sorted -- more than one means XLA fused
    across steps and the fusion counts whole under ``scope``, its
    root's; for an instruction XLA merged of several (their names joined
    by ``;``), the scopes those name, where they differ.  ``calls``: for
    a ``while`` / ``conditional`` / ``call``, the computations it
    runs."""
    return _parse_module(hlo_text)[0]


def _parse_module(hlo_text: str) -> tuple:
    """-> (``scope_map``'s dict, the ENTRY computation's fusions, sorts,
    loops and conditionals by name: what every execution of the module
    runs, by which a trace's reader tells two variants of one module
    name apart)."""
    computations, ops, current, entry = {}, {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(1), [])
            if line.startswith("ENTRY "):
                entry = current
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        scopes = _SCOPE.findall(found.group(1)) if found else []
        called = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        ops[name] = {"scope": scopes[-1] if scopes else None,
                     "own": set(scopes), "opcode": _opcode(rest),
                     "called": called}
        current.append(name)
    out = {}
    for name, op in ops.items():
        inside = op["own"] if len(op["own"]) > 1 else set()
        row = {"scope": op["scope"], "inside": [], "calls": []}
        if op["opcode"] == "fusion":
            inside = inside.union(*(ops[i]["own"] for c in op["called"]
                                    for i in computations.get(c, ())))
        elif op["opcode"] in _RUNS:
            row["calls"] = op["called"]
        row["inside"] = sorted(inside)
        out[name] = row
    return out, [n for n in entry or ()
                 if ops[n]["opcode"] in _ALWAYS_RUN]


def _named_text(kernel: _TimedJit, args, kw) -> str:
    """The optimized module of ``kernel`` for a signature, with the
    scopes' names on its instructions.  Lowered and compiled ahead of
    time: the executable the jitted call made, found again in JAX's
    caches (a load, not a compile).  JAX keys its persistent cache
    WITHOUT the instructions' metadata, so a cache that a build without
    scopes filled serves that build's executable -- the same
    instructions (``tests/test_kernel_scopes.py``), no names.  Then,
    once a checkout, the module is compiled under a key of its own (the
    metadata in it), which later processes find."""
    import jax
    lowered = kernel._fn.lower(*args, **kw)
    # the lowering may have traced the body again: those scopes belong
    # to no call's compile
    _trace.take_kernel_scopes()
    text = lowered.compile().as_text()
    if _SCOPE.search(text):
        return text
    flag = "jax_compilation_cache_include_metadata_in_key"
    jax.config.update(flag, True)
    try:
        # (an option at its default: it only steps past the executable
        # this process holds for the lowering)
        return lowered.compile(compiler_options={
            "xla_embed_ir_in_executable": False}).as_text()
    # lint: disable=SWL01 -- the names are best-effort: a backend that refuses the second compile leaves the map without them
    except Exception:
        return text
    finally:
        jax.config.update(flag, False)


def _write_scope_map(kernel: _TimedJit, variant, directory: str) -> str:
    """``<directory>/<module>.<k>.scopes.json`` of one compiled variant
    (``export_kernel_scopes``)."""
    serial, (args, kw) = variant
    t0 = clock()
    ops, entry = _parse_module(_named_text(kernel, args, kw))
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{kernel.module}.{serial}.scopes.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"module": kernel.module,
                   "signature": _leading_shapes(args, limit=2000),
                   "export_ms": round((clock() - t0) * 1000.0, 3),
                   "entry": entry, "ops": ops}, fh)
    os.replace(tmp, path)
    return path


def export_kernel_scopes(directory: str) -> list:
    """Write ``<directory>/<module>.<k>.scopes.json`` for every compiled
    variant of every live kernel whose body entered a ``kernel_scope``:
    ``{"module", "signature", "entry": [the entry computation's fusions,
    sorts and loops], "ops": {instruction: {"scope", "inside",
    "calls"}}}`` (``scope_map``).  -> the paths written."""
    with _kernels_mu:
        kernels = list(_kernels)
    return [_write_scope_map(k, v, directory)
            for k in kernels for v in list(k._variants)]


def follow_kernel_scopes(directory: Optional[str]) -> None:
    """Keep ``directory`` current: the maps of what is compiled now
    (``export_kernel_scopes``), and each scoped kernel's as it compiles
    from here on; None stops."""
    global _followed
    _followed = directory or None
    if _followed is not None:
        export_kernel_scopes(_followed)


class KernelLRU:
    """Entry-counted LRU of compiled kernels, shared by every plan (and
    every decoded worker task) in the process."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._mu = threading.RLock()
        self._e: OrderedDict[tuple, object] = OrderedDict()
        self.capacity = capacity

    def get(self, key: tuple):
        with self._mu:
            k = self._e.get(key)
            if k is not None:
                self._e.move_to_end(key)
            return k

    def put(self, key: tuple, kernel) -> None:
        with self._mu:
            self._e[key] = kernel
            self._e.move_to_end(key)
            while len(self._e) > max(1, self.capacity):
                self._e.popitem(last=False)

    def set_capacity(self, n: int) -> None:
        with self._mu:
            self.capacity = int(n)
            while len(self._e) > max(1, self.capacity):
                self._e.popitem(last=False)

    def clear(self) -> None:
        with self._mu:
            self._e.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._e)


GLOBAL_KERNELS = KernelLRU()


def plan_fingerprint(plan) -> str:
    """Canonical structural digest of a plan's kernel-relevant parts.

    Contract (docs/COMPONENTS.md): includes exactly the closure deps of
    the kernel builders — bound filter, group keys, agg_args, partial
    ops, group mode, (scan column, device dtype) pairs, parameter count.
    Bound expression nodes are frozen dataclasses, so their reprs are
    canonical; param count (not spec contents) keeps coordinator plans
    and worker-decoded plans (logical specs rebuilt from the task's
    param_specs types) on one fingerprint.
    """
    fp = plan.runtime_cache.get("_fingerprint")
    if fp is None:
        schema = plan.bound.table.schema
        parts = [
            repr(plan.bound.filter),
            repr(plan.bound.group_keys),
            repr(plan.agg_args),
            repr(plan.partial_ops),
            repr(plan.group_mode),
            repr([(c, str(schema.scan_dtype(c, device=True)))
                  for c in plan.scan_columns]),
            str(len(plan.bound.param_specs)),
        ]
        fp = hashlib.sha256("\x1f".join(parts).encode()).hexdigest()
        plan.runtime_cache["_fingerprint"] = fp
    return fp


def get_kernel(plan, slot: str, build: Callable[[], object],
               extra: tuple = ()):
    """Compiled kernel for (plan family, slot): runtime_cache first (no
    counter traffic — same plan object re-executing), then the global
    LRU by fingerprint, building and publishing on a true miss."""
    rc = plan.runtime_cache
    extra = tuple(extra)
    k = rc.get(slot)
    # the mirror holds one kernel a slot: it serves only what it was
    # built for (a slot's kernel differs by ``extra``: where its state
    # lives, the HAVING it compiled)
    if k is not None and rc.get(slot + "#extra", ()) == extra:
        return k
    k = shared_kernel(slot, build, extra, plan_fingerprint(plan))
    rc[slot] = k
    rc[slot + "#extra"] = extra
    return k


def shared_kernel(slot: str, build: Callable[[], object], extra: tuple = (),
                  family: str = ""):
    """The global LRU's kernel for (family, slot) + extra, built and
    published on a true miss.  ``family`` is a plan's fingerprint
    (``get_kernel``); a slot that no plan shapes -- the scan loop's
    lane convert -- keeps one kernel a process under the empty family."""
    key = (family, slot) + tuple(extra)
    k = GLOBAL_KERNELS.get(key)
    if k is None:
        _counters().bump("kernel_cache_misses")
        _trace.set_phase("compile")
        with _trace.span("kernel", slot=slot, cache="miss"):
            k = build()
        GLOBAL_KERNELS.put(key, k)
    else:
        _counters().bump("kernel_cache_hits")
        with _trace.span("kernel", slot=slot, cache="hit"):
            pass
    return k


#: where the on-disk XLA compilation cache lives when the environment
#: does not place it: a fixed directory of the checkout, never a temp
#: name — a cache directory that moves between runs never hits
DEFAULT_PERSISTENT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_persistent_cache() -> None:
    """Switch on JAX's on-disk XLA compilation cache so a process
    restart reuses serialized executables; called at every Cluster
    open.  ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside:
    JAX reads it into ``jax_compilation_cache_dir`` itself, so when it
    is set no directory is set in code.  Otherwise the cache lives at
    ``DEFAULT_PERSISTENT_CACHE_DIR``.  Thresholds drop to zero so even
    the small analytical kernels persist."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_PERSISTENT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
