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
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Callable

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

    __slots__ = ("_fn", "_mu")

    def __init__(self, fn):
        self._fn = fn
        self._mu = threading.Lock()

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
                        tr.add_closed("kernel_compile", parent.span_id,
                                      t0, t1)
        return out

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
    return _TimedJit(jax.jit(fn, **jit_kwargs))


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
