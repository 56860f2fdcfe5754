"""Query megabatching: coalesce same-family queries into ONE dispatch.

The kernel cache (executor/kernel_cache.py) already collapses literal
variants of a query into one plan family via ``plan_fingerprint``; this
module collapses their *executions*.  Queries whose plans share a
fingerprint and arrive within ``citus.megabatch_window_ms`` (bounded by
``citus.megabatch_max_size``) stack along a leading query axis: their
$N parameters gather into [Q] arrays and a single ``jax.vmap``-lifted
kernel — obtained through ``get_kernel`` under a distinct ``batched:``
slot, compiled through the package's one jit door — evaluates every
query's filter + partial aggregation in one device dispatch over one
shared scan of the shard batches.

Leader/follower protocol (no background thread): the first arrival for
a family becomes the batch leader, parks on the window (cut short when
the batch fills), pops the queue and executes; followers park on a
per-waiter event.  Both park under the ``megabatch_wait`` wait event —
a coalescing stall is scheduling, not device backpressure, so it must
never masquerade as ``device_round`` in the activity view.

Scatter keeps everything per-QUERY: the leader produces per-query
partial states (agg) or per-query row masks (projection); each caller
then combines/finalizes/orders **on its own thread**, so per-query
errors isolate to their caller, trace spans land in the caller's own
tree, and citus_stat_statements / tenant stats book one entry per
query exactly as on the serial path.

Correctness is never traded for occupancy:

- queries whose bind-time pruning diverged sub-batch by shard set;
- the shared scan drops per-literal chunk intervals and index probes
  (each query's own predicate re-applies on device with its own
  params), trading skip-list pruning for occupancy — results are
  identical either way;
- any shared-infrastructure failure (admission timeout, shard-map
  flip, scan error) falls the whole group back to the serial path on
  the callers' own threads;
- ``citus.megabatch_window_ms = 0`` (the default) short-circuits in
  execute_select before this module is even imported: byte-identical
  serial behavior.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional

import numpy as np

from citus_tpu.observability import trace as _trace
from citus_tpu.observability.trace import clock
from citus_tpu.stats import begin_wait, end_wait


def _counters():
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    return GLOBAL_COUNTERS


# expected inter-arrival gap (s) beyond which an auto-sized window
# treats a plan family as sparse and stops waiting
_AUTO_SPARSE_S = 0.025


class _Waiter:
    """One query parked in a dispatch queue: its full execution context
    plus the scatter slots the leader fills."""

    __slots__ = ("cat", "bound", "settings", "plan", "params", "done",
                 "payload", "record", "serial", "occupancy", "t_enq")

    def __init__(self, cat, bound, settings, plan, params):
        self.cat = cat
        self.bound = bound
        self.settings = settings
        self.plan = plan
        self.params = params
        self.done = threading.Event()
        # ("agg", [per-batch partial tuples]) or ("proj", env_batches)
        self.payload = None
        # the group's ONE device run as this rider's execution record
        self.record = None
        self.serial = False
        self.occupancy = 0
        self.t_enq = clock()


class _Queue:
    __slots__ = ("waiters", "full", "sealed")

    def __init__(self):
        self.waiters: list[_Waiter] = []
        self.full = threading.Event()
        self.sealed = False


class MegabatchDispatcher:
    """Per-fingerprint dispatch queues + process-wide occupancy stats
    (rendered by SELECT citus_megabatch_stats())."""

    def __init__(self):
        self._mu = threading.Lock()
        self._queues: dict[tuple, _Queue] = {}
        # auto-window state: plan family -> (last arrival t, EWMA gap s)
        self._arrivals: dict[tuple, tuple[float, float]] = {}
        self.batches = 0
        self.queries = 0
        self.fallbacks = 0
        # batch-level view: dispatch occupancy -> batch count
        self.occupancy_hist: dict[int, int] = {}
        # query-level view (fed from cluster.execute, one note per user
        # statement): occupancy a query rode in -> query count
        self.query_occupancy_hist: dict[int, int] = {}

    # ------------------------------------------------------- protocol

    def submit(self, w: _Waiter, key: tuple, window_s: float,
               max_size: int) -> None:
        """Enqueue ``w``; returns once ``w`` carries a payload or a
        serial verdict.  The first arrival for ``key`` leads the batch:
        it parks on the window (cut short when the queue fills), seals
        the queue and executes for everyone."""
        with self._mu:
            q = self._queues.get(key)
            if q is not None and not q.sealed and len(q.waiters) < max_size:
                q.waiters.append(w)
                if len(q.waiters) >= max_size:
                    q.full.set()
                leader = False
            else:
                q = _Queue()
                q.waiters.append(w)
                self._queues[key] = q
                leader = True
        if not leader:
            wtok = begin_wait("megabatch_wait")
            try:
                # generous bound: the leader always sets done (finally
                # below); the timeout only guards a leader thread dying
                # to an un-catchable exception
                ok = w.done.wait(window_s
                                 + w.settings.executor.lock_timeout_s + 30.0)
            finally:
                end_wait(wtok)
            if not ok:
                w.serial = True
            return
        wtok = begin_wait("megabatch_wait")
        try:
            if max_size > 1:
                q.full.wait(window_s)
        finally:
            end_wait(wtok)
        with self._mu:
            q.sealed = True
            if self._queues.get(key) is q:
                del self._queues[key]
            batch = list(q.waiters)
        try:
            self._dispatch(batch)
        finally:
            # never leave a caller parked: anything unserved retries
            # serially on its own thread
            for x in batch:
                if x.payload is None:
                    x.serial = True
                x.done.set()

    # ------------------------------------------------- adaptive window

    def resolve_window(self, key: tuple, window_ms: float) -> float:
        """Window (seconds) for this submission.  A fixed setting
        passes through; negative (SET citus.megabatch_window_ms =
        auto) sizes the window from the family's inter-arrival EWMA:
        wait ~4 expected gaps (bounded to 0.5-10 ms) while arrivals
        are bursty, and don't wait at all once the family goes sparse
        (expected gap above _AUTO_SPARSE_S) — a sparse family would
        pay the whole window's latency for an empty batch."""
        if window_ms >= 0:
            return window_ms / 1000.0
        now = clock()
        with self._mu:
            prev = self._arrivals.get(key)
            if prev is None:
                if len(self._arrivals) >= 4096:
                    self._arrivals.clear()
                self._arrivals[key] = (now, _AUTO_SPARSE_S)
                return 0.0
            t_last, ewma = prev
            ewma = 0.8 * ewma + 0.2 * (now - t_last)
            self._arrivals[key] = (now, ewma)
        if ewma > _AUTO_SPARSE_S:
            return 0.0
        return min(max(4.0 * ewma, 0.0005), 0.010)

    # ------------------------------------------------------- execution

    def _dispatch(self, batch: list[_Waiter]) -> None:
        # divergent bind-time pruning sub-batches by placement: only
        # queries scanning the SAME shard set share a device dispatch
        groups: dict[tuple, list[_Waiter]] = {}
        for w in batch:
            groups.setdefault(tuple(w.plan.shard_indexes), []).append(w)
        for group in groups.values():
            try:
                self._run_group(group)
            except Exception:
                # shared-infrastructure failure (admission timeout,
                # shard-map flip, scan error): the whole group retries
                # serially — the serial path re-plans and attributes
                # any real error to its own caller
                _counters().bump("megabatch_fallbacks", len(group))
                with self._mu:
                    self.fallbacks += len(group)
                for w in group:
                    w.serial = True
            except BaseException:
                for w in group:
                    w.serial = True
                raise

    def _run_group(self, group: list[_Waiter]) -> None:
        from citus_tpu.executor.pipeline import PipelineStats
        from citus_tpu.transaction.snapshot import snapshot_read
        from citus_tpu.workload import GLOBAL_SCHEDULER, tenant_key
        w0 = group[0]
        cat, settings, plan = w0.cat, w0.settings, w0.plan
        bound = plan.bound
        occ = len(group)
        if plan.table_shard_count not in (-1, len(bound.table.shards)):
            # shard map changed under the cached plan (split/rebalance
            # racing the window): serial path re-plans per query
            raise RuntimeError("megabatch: shard map changed")
        # the shared scan reads every chunk of the group's shards; each
        # query's own predicate (with its own params) re-applies on
        # device, so per-literal interval/index pruning can be dropped
        # without changing any result
        scan_plan = dataclasses.replace(plan, intervals=[], index_eq=None)
        # ONE admission slot per device dispatch, admitted under the
        # batch LEADER's tenant; coalesced followers (who may belong
        # to other tenants) are bookkept against their own tenants,
        # not admitted
        with GLOBAL_SCHEDULER.slot(settings, tenant_key(plan.router_key),
                                   timeout=settings.executor.lock_timeout_s):
            GLOBAL_SCHEDULER.note_coalesced(
                [tenant_key(x.plan.router_key) for x in group[1:]])

            def _attempt():
                record = PipelineStats()    # of the attempt that answers
                if not bound.has_aggs:
                    # a host loop: no device scan, nothing to record
                    return _batched_projection(cat, scan_plan, settings,
                                               group), record
                run = _batched_hash_agg \
                    if plan.group_mode.kind == "hash_host" else _batched_agg
                return run(cat, scan_plan, settings, group, record), record
            payloads, record = snapshot_read(
                cat.data_dir, bound.table, _attempt,
                timeout=settings.executor.lock_timeout_s)
        c = _counters()
        c.bump("megabatch_batches")
        c.bump("megabatch_queries", occ)
        with self._mu:
            self.batches += 1
            self.queries += occ
            self.occupancy_hist[occ] = self.occupancy_hist.get(occ, 0) + 1
        for w, payload in zip(group, payloads):
            w.occupancy = occ
            w.payload = payload
            # the device run's task log goes to the first rider alone:
            # the load ledger books the group's device work once
            w.record = record if w is w0 else record.rider()

    # ------------------------------------------------------- stats

    def note_query_occupancy(self, occ: int) -> None:
        """Per-query attribution (called from cluster.execute once per
        user statement that rode a batch)."""
        with self._mu:
            self.query_occupancy_hist[occ] = \
                self.query_occupancy_hist.get(occ, 0) + 1

    def stats(self) -> dict:
        with self._mu:
            return {
                "batches": self.batches,
                "queries": self.queries,
                "fallbacks": self.fallbacks,
                "avg_occupancy": (self.queries / self.batches)
                if self.batches else 0.0,
                "occupancy_hist": dict(self.occupancy_hist),
                "query_occupancy_hist": dict(self.query_occupancy_hist),
            }


GLOBAL_MEGABATCH = MegabatchDispatcher()


# --------------------------------------------------- batched kernels


def _stacked_params(group: list[_Waiter], q_pad: int):
    """Gather each $N across the group into a [q_pad] array (leading
    query axis).  Padding replicates the first query's values so padded
    lanes compute something valid and get discarded at scatter."""
    from citus_tpu.planner.bound import param_env_names
    w0 = group[0]
    n_params = len(param_env_names(w0.bound.param_specs))
    pcols, pvalids = [], []
    for j in range(n_params):
        vals = [w.params[0][j] for w in group]
        vlds = [w.params[1][j] for w in group]
        vals += [vals[0]] * (q_pad - len(group))
        vlds += [vlds[0]] * (q_pad - len(group))
        pcols.append(np.stack(vals))
        pvalids.append(np.stack(vlds))
    return tuple(pcols), tuple(pvalids)


def _q_pad(q: int) -> int:
    """Pad the query axis to a power of two so the vmapped kernel
    compiles once per bucket, not once per occupancy."""
    return 1 << max(0, q - 1).bit_length()


def _batched_agg(cat, plan, settings, group: list[_Waiter], record) -> list:
    """Scan the group's shards ONCE, run the vmap-lifted worker over
    the query axis, and slice per-query partial states back out.
    Returns one ("agg", [per-batch partial tuples]) payload per
    waiter; combine + finalize happen on the callers' threads."""
    import jax
    import jax.numpy as jnp

    from citus_tpu.executor.device_cache import GLOBAL_CACHE, plan_cache_key
    from citus_tpu.executor.executor import (
        _empty_partials, _fetch_acc, _iter_padded_batches,
    )
    from citus_tpu.executor.kernel_cache import get_kernel, jit_compile
    from citus_tpu.executor.scan_loop import OneDevice, Step, drive
    from citus_tpu.ops.scan_agg import build_fused_worker_fn

    q = len(group)
    qp = _q_pad(q)
    from citus_tpu.planner.bound import param_env_names
    n_cols = len(plan.scan_columns)
    n_params = len(param_env_names(plan.bound.param_specs))
    axes = (None,) * n_cols + (0,) * n_params

    def _build():
        # data columns broadcast across the query axis; the running
        # accumulator registers and the trailing 0-d param "columns"
        # map over it.  Same fused single-dispatch shape as the serial
        # path: one kernel round per batch folds every rider's partials
        # in place (acc donated — the [qp]-stacked registers stay
        # device-resident across the whole shared scan)
        return jit_compile(jax.vmap(build_fused_worker_fn(plan, jnp),
                                    in_axes=(0, axes, axes, None)),
                           donate_argnums=0)
    step = Step(get_kernel(plan, "batched:jit_fused", _build),
                "batched:jit_fused", "fused_dispatches")

    _trace.set_phase("device")
    # the [qp] parameter stacks ride along as one device's parameters do
    placement = OneDevice(record)
    placement.bind(_stacked_params(group, qp))
    # interval-free scan: the device-cache entry is the family-wide
    # full-shard batch set, shared by every literal variant and
    # attributed to the shared tenant bucket, not one rider
    key = plan_cache_key(plan, cat.data_dir)
    cached = GLOBAL_CACHE.get(key)
    # [qp]-stacked accumulator registers, one slot per rider (padding
    # slots replay rider 0's params; their results are sliced off)
    acc = tuple(jax.device_put(np.stack([p] * qp))
                for p in _empty_partials(plan, np))
    acc = drive(plan, settings, placement, step, acc, record,
                cached=cached, cache_key=key,
                stream=None if cached is not None
                else _iter_padded_batches(cat, plan, settings, record))
    host = _fetch_acc(acc)
    return [("agg", [tuple(o[qi] for o in host)]) for qi in range(q)]


def _batched_hash_agg(cat, plan, settings, group: list[_Waiter],
                      record) -> list:
    """Shared scan + ONE vmap-lifted fused hash dispatch per batch over
    [qp]-stacked donated hash tables (kernel slot
    ``batched:jit_hash_fused``).  Spill masks drain per prefetch window
    into per-query HostGroupAccumulators with each rider's own params
    env; scatter hands every waiter its table slice + accumulator and
    the exact host merge + finalize run on the callers' threads."""
    import jax
    import jax.numpy as jnp

    from citus_tpu.executor.executor import (
        _SpillDrain, _hash_key_dtypes, _hash_slots, _iter_padded_batches,
        _params_env,
    )
    from citus_tpu.executor.host_agg import HostGroupAccumulator
    from citus_tpu.executor.kernel_cache import get_kernel, jit_compile
    from citus_tpu.executor.scan_loop import OneDevice, Step, drive
    from citus_tpu.ops.hash_agg import build_fused_hash_worker, \
        empty_hash_state
    from citus_tpu.planner.bound import param_env_names

    q = len(group)
    qp = _q_pad(q)
    penvs = [_params_env(plan, w.params) for w in group]
    n_cols = len(plan.scan_columns)
    n_params = len(param_env_names(plan.bound.param_specs))
    axes = (None,) * n_cols + (0,) * n_params
    key_dtypes = _hash_key_dtypes(plan, penvs[0])
    S, _ = _hash_slots(cat, plan, settings, key_dtypes, tables=qp)

    def _build():
        # table state maps over the query axis (donated, stays
        # device-resident across the shared scan); data columns
        # broadcast; the 0-d param "columns" map
        return jit_compile(
            jax.vmap(build_fused_hash_worker(plan, jnp, key_dtypes),
                     in_axes=(0, axes, axes, None)),
            donate_argnums=0)
    step = Step(get_kernel(plan, "batched:jit_hash_fused", _build),
                "batched:jit_hash_fused", "hash_fused_dispatches")

    accs = [HostGroupAccumulator(len(plan.bound.group_keys),
                                 plan.partial_ops) for _ in group]

    _trace.set_phase("device")
    placement = OneDevice(record)
    placement.bind(_stacked_params(group, qp))
    state = jax.device_put(jax.tree_util.tree_map(
        lambda a: np.stack([a] * qp), empty_hash_state(plan, S, key_dtypes)))
    state = drive(plan, settings, placement, step, state, record,
                  stream=_iter_padded_batches(cat, plan, settings, record),
                  on_sync=_SpillDrain(plan, accs, S))
    host = jax.device_get(state)
    return [("hash_agg",
             (jax.tree_util.tree_map(lambda a: np.asarray(a)[qi], host),
              accs[qi]))
            for qi in range(q)]


def _batched_projection(cat, plan, settings, group: list[_Waiter]) -> list:
    """Shared scan + one vmapped filter evaluation -> per-query (env,
    mask) batches.  Row extraction (project_rows) happens per query on
    the callers' threads."""
    from citus_tpu.executor.batches import load_shard_batches
    from citus_tpu.executor.executor import _params_env
    from citus_tpu.executor.kernel_cache import get_kernel, jit_compile
    from citus_tpu.testing.faults import FAULTS

    q = len(group)
    qp = _q_pad(q)
    pcols, pvalids = _stacked_params(group, qp)
    penvs = [_params_env(plan, w.params) for w in group]
    from citus_tpu.planner.bound import param_env_names
    n_cols = len(plan.scan_columns)
    n_params = len(param_env_names(plan.bound.param_specs))
    axes = (None,) * n_cols + (0,) * n_params

    batched = None
    if plan.bound.filter is not None:
        import jax
        import jax.numpy as jnp
        from citus_tpu.planner.bound import compile_expr, predicate_mask

        def _build():
            cfn = compile_expr(plan.bound.filter, jnp)
            names = tuple(plan.scan_columns) + tuple(penvs[0])

            def device_mask(cols, valids, row_mask):
                env = {n: (c, v) for n, c, v in zip(names, cols, valids)}
                return row_mask & predicate_mask(jnp, cfn, env, row_mask)
            return jit_compile(jax.vmap(device_mask,
                                        in_axes=(axes, axes, None)))
        batched = get_kernel(plan, "batched:jit_filter", _build)

    _trace.set_phase("device")
    schema = plan.bound.table.schema
    per_query: list[list] = [[] for _ in group]
    for si in plan.shard_indexes:
        for values, masks, n in load_shard_batches(cat, plan, si):
            cols = tuple(values[c].astype(schema.scan_dtype(c, device=True),
                                          copy=False)
                         for c in plan.scan_columns)
            valids = tuple(masks[c] for c in plan.scan_columns)
            if batched is not None:
                FAULTS.hit("device_round", plan.bound.table.name)
                qmasks = np.asarray(batched(cols + pcols, valids + pvalids,
                                            np.ones(n, bool)))
            else:
                qmasks = None
            base = {c: (cols[i], valids[i])
                    for i, c in enumerate(plan.scan_columns)}
            for qi in range(q):
                env = dict(base)
                env.update(penvs[qi])
                per_query[qi].append(
                    (env, qmasks[qi] if qmasks is not None
                     else np.ones(n, bool)))
    return [("proj", batches) for batches in per_query]


# --------------------------------------------------- caller-side entry


def megabatch_eligible(cat, bound, settings, plan) -> bool:
    """A query may coalesce when the batched runners can reproduce the
    serial result exactly: parameterized single-table plan, scalar /
    direct-gid aggregation or projection, local placements only, no
    open transaction overlay (staged writes are per-session state the
    shared scan must not see)."""
    ex = settings.executor
    if ex.megabatch_window_ms == 0 or ex.task_executor_backend == "cpu":
        return False
    if not bound.param_specs or not plan.shard_indexes:
        return False
    if bound.has_aggs and plan.group_mode.kind not in ("scalar", "direct"):
        # hash_host rides too (vmap-lifted fused hash kernel) unless its
        # partials are exact value sets / sketches — those accumulate on
        # the host per query and gain nothing from a shared dispatch
        from citus_tpu.executor.executor import _hash_has_exact
        if plan.group_mode.kind != "hash_host" or _hash_has_exact(plan):
            return False
    from citus_tpu.storage.overlay import current_overlay
    if current_overlay() is not None:
        return False
    from citus_tpu.executor.worker_tasks import split_pushable
    _local, remote = split_pushable(cat, plan, settings)
    if remote:
        return False
    return True


def _finalize_agg(cat, plan, batch_partials, params) -> list[tuple]:
    """Per-query combine + finalize — the exact tail of the serial
    _run_agg, run on the caller's own thread."""
    from citus_tpu.executor.executor import (
        _decode_direct_keys, _params_env,
    )
    from citus_tpu.executor.finalize import finalize_groups
    from citus_tpu.ops.scan_agg import combine_partials_host
    penv = _params_env(plan, params)
    partials = combine_partials_host(plan, batch_partials)
    if plan.group_mode.kind == "scalar":
        partials = tuple(
            np.asarray(p).reshape(1) if np.asarray(p).ndim == 0
            else np.asarray(p)[None, ...] for p in partials)
        return finalize_groups(plan, cat, [], partials, params_env=penv)
    *parts, grows = partials
    keys, occupied = _decode_direct_keys(plan, grows)
    if occupied.size == 0:
        return []
    sel = tuple(np.asarray(p)[occupied] for p in parts)
    return finalize_groups(plan, cat, keys, sel, params_env=penv)


def _finalize_hash_agg(cat, plan, data, params, record) -> list[tuple]:
    """Per-query exact merge + finalize of a hash_host rider's table
    slice — the exact tail of the serial _run_agg_hash_host, run on the
    caller's own thread."""
    from citus_tpu.executor.executor import _finish_hash_agg, _params_env
    table, acc = data
    return _finish_hash_agg(cat, plan, acc, table, _params_env(plan, params),
                            record)


def maybe_megabatch(cat, bound, settings, plan, params, t0, exec_span):
    """Coalescing gate called from execute_select after bind-time
    pruning.  Returns a Result when this query rode a batch, or None —
    caller continues on the (unchanged) serial path."""
    if not megabatch_eligible(cat, bound, settings, plan):
        return None
    from citus_tpu.executor.executor import GLOBAL_COUNTERS, _finish_select
    from citus_tpu.executor.finalize import project_rows
    from citus_tpu.executor.kernel_cache import plan_fingerprint
    from citus_tpu.testing.faults import FAULTS
    ex = settings.executor
    w = _Waiter(cat, bound, settings, plan, params)
    key = (cat.data_dir, bound.table.name, plan_fingerprint(plan))
    window_s = GLOBAL_MEGABATCH.resolve_window(key, ex.megabatch_window_ms)
    if window_s <= 0.0 and ex.megabatch_window_ms < 0:
        # auto judged this family sparse: run serial, pay no window
        return None
    GLOBAL_MEGABATCH.submit(w, key, window_s,
                            max(1, ex.megabatch_max_size))
    if w.serial or w.payload is None:
        return None
    # ---- per-query scatter, on this caller's own thread ----
    GLOBAL_COUNTERS.bump("queries_executed")
    if plan.is_router:
        GLOBAL_COUNTERS.bump("router_queries")
    elif len(plan.shard_indexes) > 1:
        GLOBAL_COUNTERS.bump("multi_shard_queries")
    # deterministic per-query failure injection for the isolation tests
    FAULTS.hit("megabatch_finalize",
               f"{bound.table.name}:{plan.router_key}")
    kind, data = w.payload
    if kind == "agg":
        rows = _finalize_agg(cat, plan, data, params)
    elif kind == "hash_agg":
        rows = _finalize_hash_agg(cat, plan, data, params, w.record)
    else:
        rows = project_rows(plan, cat, data)
    wait_ms = (clock() - w.t_enq) * 1000.0
    info = {"occupancy": w.occupancy,
            "window_ms": round(window_s * 1000.0, 3),
            "wait_ms": round(wait_ms, 3)}
    ctx = _trace.current()
    if ctx is not None:
        tr, parent = ctx
        tr.add_closed("megabatch", parent.span_id, w.t_enq, clock(),
                      dict(info))
    return _finish_select(bound, plan, rows, t0, exec_span, w.record,
                          megabatch=info)
