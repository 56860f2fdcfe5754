"""Executor orchestration.

Maps a PhysicalPlan onto the available backend:

- ``cpu``: numpy worker per shard — the bit-exact oracle path (and the
  moral equivalent of the reference's local_executor.c in-process path)
- ``tpu``: jitted worker kernels; with a multi-device mesh, shards run
  under shard_map and combine with one psum/pmin/pmax (adaptive-executor
  analog where the event loop is replaced by XLA's async dispatch)

Partial states from multiple rounds (more shards/batches than devices)
merge on the device, in an accumulator every round folds into; the host
fetches it once a query.  Only the states of other coordinators' tasks
merge on the host, like the reference merges per-task tuples on the
coordinator.
"""

from __future__ import annotations

import threading
import dataclasses
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import numpy as np

from citus_tpu import types as T
from citus_tpu.catalog import Catalog
from citus_tpu.config import Settings
from citus_tpu.errors import ExecutionError
from citus_tpu.executor.batches import (
    load_padded_batches, load_shard_batches,
)
from citus_tpu.executor.finalize import finalize_groups, order_and_limit, project_rows
from citus_tpu.executor.kernel_cache import get_kernel, jit_compile
from citus_tpu.executor.pipeline import (
    PipelineStats, dispatch_remote_tasks, one_after_another,
)
from citus_tpu.executor.scan_loop import (
    ScanLanesBelied, Step, _block_ready, _nbytes, _prefetch_depth,
    choose_affine_placement, choose_placement, drive,
)
from citus_tpu.observability import trace as _trace
from citus_tpu.observability.trace import clock
from citus_tpu.ops.scan_agg import (
    build_fused_worker_fn, build_worker_fn, combine_kinds,
    combine_partials_host, direct_id_lanes,
)
from citus_tpu.planner.auto_param import PHYSICAL_SRC, substitute_params
from citus_tpu.planner.bind import BoundSelect
from citus_tpu.planner.bound import (
    BAggRef, BBinOp, BCase, BCast, BIsNull, BKeyRef, BLiteral, BParam,
    BScale, BUnOp, walk,
)
from citus_tpu.planner.physical import (
    PhysicalPlan, _index_eq, extract_intervals, plan_select, prune_shards,
    sees_staged_rows,
)
from citus_tpu.stats import StatCounters

# process-wide counters (the citus_stat_counters analog); Cluster exposes
# a view over this
GLOBAL_COUNTERS = StatCounters()


def _combine(plan: PhysicalPlan, partial_sets: list):
    """combine_partials_host under its span: the host half of the
    partial-agg -> combine step, wherever the partial sets came from
    (mesh rounds, remote tasks, the numpy arm's batches)."""
    with _trace.span("combine") as sp:
        if sp.recording:
            sp.set(partial_sets=len(partial_sets))
        return combine_partials_host(plan, partial_sets)


def _fetch_acc(acc_dev):
    """Tail of every aggregate scan: wait for the chip, then copy the
    partial states back (one device_get).  On the mesh ``acc_dev`` is
    replicated, and a copy comes from one chip.

    PR 23 measured +42 % for "wait for all mesh rounds, then fetch".
    That trap is gone with its cause: every round then had 13 outputs
    of its own, so 104 copies ran one after another behind the kernel,
    where a copy per round had run beside the next round.  With the
    states folded on the chips there are 13 arrays to copy, once, and
    nothing to copy before the last round has run."""
    import jax
    _block_ready(acc_dev[-1:])
    with _trace.span("fetch") as sp:
        out = tuple(np.asarray(o) for o in jax.device_get(acc_dev))
        if sp.recording:
            sp.set(arrays=len(out), bytes=_nbytes(out))
    return out


@dataclass
class Result:
    columns: list[str]
    rows: list[tuple]
    explain: dict = field(default_factory=dict)
    # per-visible-column ColumnType where the planner knows them (used by
    # intermediate-result materialization: CTEs, derived tables, set ops)
    types: Optional[list] = None

    @property
    def rowcount(self) -> int:
        return len(self.rows)

    def __iter__(self):
        return iter(self.rows)


# ------------------------------------------------------------ agg paths


def encode_params(cat: Catalog, bound, values: Optional[list]):
    """$N python values -> (tuple of 0-d value arrays, tuple of 0-d
    valid arrays) per bound.param_specs.  Text parameters resolve
    through the column's dictionary; unseen strings map to -1 (match
    nothing, like a nonexistent id)."""
    if not bound.param_specs:
        return (), ()
    if values is None or len(values) < len(bound.param_specs):
        raise ExecutionError(
            f"query requires {len(bound.param_specs)} parameters")
    pcols, pvalids = [], []
    for (ptype, src), v in zip(bound.param_specs, values):
        is_uuid = ptype.kind == T.UUID
        if v is None:
            # a uuid parameter occupies two env slots (hi + lo lanes)
            for _ in range(2 if is_uuid else 1):
                pcols.append(np.zeros((), np.int64 if is_uuid
                                      else ptype.device_dtype))
                pvalids.append(np.zeros((), bool))
            continue
        if src == PHYSICAL_SRC:
            # auto-parameterized literal: value is already bound-level
            # physical (dates, scaled decimals, dictionary ids)
            pcols.append(np.asarray(v, ptype.device_dtype))
            pvalids.append(np.ones((), bool))
            continue
        if ptype.is_text:
            pid = cat.lookup_string_id(src[0], src[1], str(v))
            phys = -1 if pid is None else pid
        elif is_uuid:
            hi, lo = T.uuid_int_to_lanes(ptype.to_physical(v))
            for lane in (hi, lo):
                pcols.append(np.asarray(lane, np.int64))
                pvalids.append(np.ones((), bool))
            continue
        else:
            phys = ptype.to_physical(v)
        pcols.append(np.asarray(phys, ptype.device_dtype))
        pvalids.append(np.ones((), bool))
    return tuple(pcols), tuple(pvalids)


def _run_partials_cpu(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                      params=((), ()), record=None):
    """The numpy arm of ``_run_partials_jax``; books nothing."""
    worker = build_worker_fn(plan, np)
    pcols, pvalids = params
    shard_results = []
    for si in plan.shard_indexes:
        for values, masks, n in load_shard_batches(cat, plan, si):
            cols = tuple(values[c].astype(
                plan.bound.table.schema.scan_dtype(c, device=True),
                copy=False) for c in plan.scan_columns)
            valids = tuple(masks[c] for c in plan.scan_columns)
            shard_results.append(worker(cols + pcols, valids + pvalids,
                                        np.ones(n, bool)))
    if not shard_results:
        shard_results.append(_empty_partials(plan, np))
    return _combine(plan, shard_results)


def _empty_partials(plan: PhysicalPlan, xp):
    """Zero-row partial states (so empty tables still produce a row for
    global aggregates); with jax.numpy, traced: the mesh loop fills its
    first accumulator on the chips."""
    from citus_tpu.ops.scan_agg import _sentinel
    from citus_tpu.planner.aggregates import DDSK_M, HLL_M, TOPK_M
    G = plan.group_mode.n_groups if plan.group_mode.kind == "direct" else None
    outs = []
    for op in plan.partial_ops:
        dt = np.dtype(op.dtype)
        if op.kind == "hll":
            outs.append(xp.zeros((HLL_M,), np.int32))
        elif op.kind == "ddsk":
            outs.append(xp.zeros((DDSK_M,), np.int64))
        elif op.kind == "topk":
            outs.append(xp.zeros((TOPK_M,), np.int64))
        elif op.kind == "topkv":
            outs.append(xp.full((TOPK_M,), np.iinfo(np.int64).min, np.int64))
        elif op.kind in ("sum", "count"):
            base = np.int64(0) if op.kind == "count" else dt.type(0)
            outs.append(xp.zeros((G,), dt) if G else xp.asarray(base, dt))
        else:
            sent = dt.type(_sentinel(op.kind, dt))
            outs.append(xp.full((G,), sent, dt) if G else xp.asarray(sent, dt))
    if G:
        outs.append(xp.zeros((G,), np.int64))
    return tuple(outs)


def _iter_padded_batches(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                         record: PipelineStats):
    """Lazily yield host ShardBatches of at most 1 << 22 rows, nothing
    materialized up front — the streaming scan path's host half
    (reference analog: ColumnarReadNextRow never materializes a stripe,
    columnar_reader.c:323).  A full batch is exactly its bucket; only a
    shard's last batch is padded, to its own power-of-two bucket, so
    the per-shape jit cache stays small.  Every shard is a stream of
    its own (``pipeline.one_after_another``): the sequence is shard by
    shard in ``plan.shard_indexes`` order, a shard's batches in file
    order, whichever threads decode them.  Books real against padded
    rows, and the bytes decoded in place against those copied there:
    process counters, and the statement's EXPLAIN pad_share and
    decoded-in-place share -- as the batches pass in their order, on
    the one thread that pulls this generator."""
    from citus_tpu.testing.faults import FAULTS

    def shard_batches(si):
        # one decode_batch span per batch, on whichever thread pulls
        # this shard's stream (a producer of the prefetcher, its
        # pulling thread, the caller at depth 0), closed BEFORE the
        # yield: a span held across a yield would stay on the puller's
        # span stack while the consumer runs.  The pull that finds the
        # shard exhausted is a last span without a batch (eof).
        FAULTS.hit("dispatch_task", f"{plan.bound.table.name}:{si}")
        GLOBAL_COUNTERS.bump("tasks_dispatched")
        batches = load_padded_batches(
            cat, plan, si,
            min_batch_rows=settings.executor.min_batch_rows,
            prefer_secondary=settings.executor.use_secondary_nodes)
        try:
            while True:
                with _trace.span("decode_batch") as sp:
                    hb = next(batches, None)
                    if sp.recording:
                        sp.set(thread=threading.current_thread().name)
                        if hb is None:
                            sp.set(eof=True)
                        else:
                            sp.set(shard_index=int(hb.shard_index),
                                   rows=int(hb.n_rows), bytes=hb.nbytes)
                if hb is None:
                    return
                yield hb
        finally:
            batches.close()

    batches = one_after_another(
        [shard_batches(si) for si in plan.shard_indexes])
    try:
        for hb in batches:
            record.tally("batch_rows_real", hb.n_rows, add=True)
            record.tally("batch_rows_padded", hb.padded_rows, add=True)
            record.tally("decode_bytes_in_place", hb.bytes_in_place, add=True)
            record.tally("decode_bytes_copied", hb.bytes_copied, add=True)
            yield hb
    finally:
        batches.close()


def _agg_step(plan: PhysicalPlan, placement):
    """The scan-aggregate round kernel for where the rounds live, and
    the maker of its first state.  XLA's fusion of the jitted body is
    the kernel: it folds the per-batch worker AND the running merge into
    ONE dispatch, the partial-agg registers riding along as a donated
    argument, so the accumulators never leave the device until the
    final device_get.  On the mesh a round is run(acc, inputs) -> acc':
    every chip runs the worker on its batch, the collective merges the
    chips' partial states and the result folds into the replicated
    ``acc``; the first one is filled on the chips (one dispatch)."""
    import jax
    import jax.numpy as jnp
    if placement.mesh is None:
        fused = get_kernel(
            plan, "jit_fused",
            lambda: jit_compile(build_fused_worker_fn(plan, jnp),
                                donate_argnums=0))
        return (Step(fused, "jit_fused", "fused_dispatches"),
                lambda: tuple(jax.device_put(p)
                              for p in _empty_partials(plan, np)))
    from citus_tpu.parallel.mesh import sharded_partial_agg, zero_partials
    run = get_kernel(
        plan, "mesh_run",
        lambda: sharded_partial_agg(build_worker_fn(plan, jnp),
                                    combine_kinds(plan), placement.mesh),
        extra=placement.key_suffix)
    zero = get_kernel(
        plan, "mesh_zero",
        lambda: zero_partials(lambda: _empty_partials(plan, jnp),
                              placement.mesh),
        extra=placement.key_suffix)
    return Step(run, "mesh_run", "fused_dispatches"), zero


def _run_partials_jax(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                      params, record: PipelineStats):
    from citus_tpu.storage.overlay import current_overlay
    from citus_tpu.workload import tenant_key

    with _trace.span("scan_setup"):
        _trace.set_phase("device")
        # an open transaction's staged writes change what a scan sees
        # without bumping table.version — bypass the HBM cache for
        # tables the transaction touched (other tables still hit it)
        txn = current_overlay()
        overlaid = txn is not None and plan.bound.table.name in txn.tables
    placement, key, cached, stream = choose_placement(
        plan, cat.data_dir, not overlaid,
        lambda: _iter_padded_batches(cat, plan, settings, record), record)
    step, first_state = _agg_step(plan, placement)
    placement.bind(params)
    with _trace.span("init_acc") as sp:
        acc_dev = first_state()
        if sp.recording:
            sp.set(arrays=len(acc_dev), bytes=_nbytes(acc_dev))
    # HBM attribution: resident entries are charged to the tenant whose
    # query pinned them (the shared bucket for non-router scans)
    acc_dev = drive(plan, settings, placement, step, acc_dev, record,
                    cached=cached, stream=stream, cache_key=key,
                    cache_tenant=tenant_key(plan.router_key))
    if plan.group_mode.kind == "direct":
        # padded rows the group reduction ran over
        record.tally("group_rows_in", placement.rows_padded)
    t_dev = clock()
    partials = _fetch_acc(acc_dev)
    if cached is None:
        record.device_s += clock() - t_dev
        record.book_timings()
    return partials


def _decode_direct_keys(plan: PhysicalPlan, rows: np.ndarray):
    """Occupied gids -> per-key (values, valid) arrays + occupancy index."""
    occupied = np.nonzero(rows > 0)[0]
    keys = []
    for d, stride in zip(plan.group_mode.domains, plan.group_mode.strides):
        codes = (occupied // stride) % d.size
        valid = codes > 0
        vals = np.where(valid, d.lo + (codes - 1) * d.step, 0)
        keys.append((vals.astype(np.int64), valid))
    return keys, occupied


def _run_agg(cat: Catalog, plan: PhysicalPlan, settings: Settings,
             params, record: PipelineStats) -> list[tuple]:
    backend = settings.executor.task_executor_backend
    mode = plan.group_mode.kind
    penv = _params_env(plan, params)
    if mode in ("scalar", "direct"):
        # push the worker half to coordinators OWNING remote-only
        # placements (ship partial-agg states, not stripe files) and
        # OVERLAP the remote waits with the local shard scan: dispatch
        # first, scan while the RPCs fly, collect as they complete.
        # Push fallbacks scan locally in a second pass; combine is
        # associative, so the split changes nothing in the result.
        run = _run_partials_cpu if backend == "cpu" else _run_partials_jax
        with _trace.span("remote_dispatch") as sp:
            local, dispatch = dispatch_remote_tasks(cat, plan, settings,
                                                    params, record)
            if sp.recording:
                sp.set(tasks=len(plan.shard_indexes) - len(local))
        run_plan = plan
        if local != plan.shard_indexes:
            run_plan = dataclasses.replace(plan, shard_indexes=local)
        try:
            partials = run(cat, run_plan, settings, params, record)
        except BaseException:
            dispatch.abort()  # no RPC thread outlives the attempt
            raise
        with _trace.span("remote_dispatch") as sp:
            fallback, remote_partials = dispatch.collect()
            if sp.recording:
                sp.set(tasks=len(remote_partials), fallback=len(fallback))
        if fallback:
            fb_plan = dataclasses.replace(plan, shard_indexes=fallback)
            remote_partials = [*remote_partials,
                               run(cat, fb_plan, settings, params, record)]
        if remote_partials:
            partials = _combine(plan, [partials, *remote_partials])
        with _trace.span("finalize_groups") as sp:
            if mode == "scalar":
                # one group: scalars become length-1 arrays; vector
                # partials (HLL registers) gain a leading group axis
                partials = tuple(
                    np.asarray(p).reshape(1) if np.asarray(p).ndim == 0
                    else np.asarray(p)[None, ...] for p in partials)
                out = finalize_groups(plan, cat, [], partials,
                                      params_env=penv)
            else:
                *parts, rows = partials
                keys, occupied = _decode_direct_keys(plan, rows)
                out = []
                if occupied.size:
                    sel_parts = tuple(np.asarray(p)[occupied] for p in parts)
                    out = finalize_groups(plan, cat, keys, sel_parts,
                                          params_env=penv)
                # the slots the group reduction was sized and chosen by,
                # and the groups that came out of them (after HAVING)
                record.tally("direct_groups", plan.group_mode.n_groups)
                record.tally("direct_groups_out", len(out))
                # the rows the WHERE kept (beside group_rows_in, the
                # padded rows the reduction ran over), the state fetched
                record.tally("group_rows_kept", int(np.asarray(rows).sum()))
                record.tally("direct_bytes_fetched", _nbytes(partials))
                # how the kernel made the group id: keys whose code took
                # no 64-bit division and rode 32-bit lanes, and the keys
                # that divide at all (a date_trunc unit)
                lanes = direct_id_lanes(plan)
                record.tally("direct_gid_keys", len(lanes))
                record.tally("direct_gid_keys_narrow",
                             sum(l.narrow for l in lanes))
                record.figures["direct_gid_divisions"] = sum(
                    l.divide != "none" for l in lanes)
            if sp.recording:
                sp.set(groups=len(out))
            return out
    # unbounded-cardinality GROUP BY: per-shard hash tables merge on the
    # host, so the whole strategy renders as one host_agg span
    with _trace.span("host_agg", shards=len(plan.shard_indexes)):
        return _run_agg_hash_host(cat, plan, settings, params, record)


def _params_env(plan, params) -> dict:
    from citus_tpu.planner.bound import param_env_names
    pcols, pvalids = params
    return dict(zip(param_env_names(plan.bound.param_specs),
                    zip(pcols, pvalids)))


def _hash_has_exact(plan: PhysicalPlan) -> bool:
    """distinct/collect partial states are exact value (multi)sets and
    sketch registers have their own merge laws: only the host
    accumulation path (and the pull path on the wire) can carry them."""
    return any(op.kind in ("distinct", "collect", "collect_set", "hll",
                           "ddsk", "topk", "topkv")
               for op in plan.partial_ops)


#: share of the device's free memory one query's hash tables may take:
#: the donated state, the kernel's per-probe claim tables beside it and
#: the scan batches in flight all have to fit next to the batch cache
HASH_STATE_MEMORY_SHARE = 0.25
#: free bytes assumed where the platform reports no memory statistics
#: (the CPU platform: the tables then live in host memory)
_UNREPORTED_FREE_BYTES = 4 << 30


def _pow2_at_least(n: int, floor: int) -> int:
    """The power of two at or above ``n``, at least ``floor``: the
    counts the hash path pads to, so few shapes ever compile."""
    return max(floor, 1 << (max(1, n) - 1).bit_length())


def _hash_slots(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                key_dtypes: tuple, tables: int = 1,
                placement=None) -> tuple[int, str]:
    """Slots of ONE of a query's device hash tables, and where the number
    came from (EXPLAIN ANALYZE's ``Hash:`` line says it).
    ``citus.hash_agg_slots = n`` fixes them (``"setting"``); ``auto``
    (0, the default) derives
    them from what bounds the groups: the catalog's row count (every row
    may be a group: the next power of two at or above it, ``"row
    count"``) or, where the plan proves a domain for every group key and
    that is the smaller, the power of two at or above TWICE the domains'
    product (``"key domain"``: such a domain is often full, and a
    two-probe table at half load spills little) -- at least 1024, and at
    most what ``HASH_STATE_MEMORY_SHARE`` of the device's free memory
    holds of ``tables`` such tables at this plan's bytes per slot
    (``"free memory"``).  With a ``placement`` of one table a DEVICE
    (``AffineMeshPlacement``) the row count is that of the fullest
    device's own shards and the free memory that of the device with the
    least: the tables share one shape.  Groups beyond a table spill to
    the host accumulator, exactly."""
    S = settings.planner.hash_agg_slots
    if S > 0:
        return S, "setting"
    from citus_tpu.catalog.stats import shard_row_counts
    from citus_tpu.ops.hash_agg import empty_hash_state, hash_state_bytes
    from citus_tpu.parallel.mesh import executor_devices
    try:
        counts = shard_row_counts(cat, cat.table(plan.bound.table.name))
    except Exception:
        counts = []
    devices = executor_devices()[:1]
    n = sum(counts)
    if placement is not None and placement.mesh is not None:
        devices = list(placement.mesh.devices.flat)
        per_device = [0] * len(devices)
        for si, rows in enumerate(counts):
            per_device[placement.owner(si)] += rows
        n = max(per_device)
    want, origin = _pow2_at_least(int(n), 1024), "row count"
    domain = plan.group_mode.domain_slots
    if domain is not None:
        by_domain = _pow2_at_least(2 * domain, 1024)
        if by_domain < want:
            want, origin = by_domain, "key domain"
    free = min((st["bytes_limit"] - st["bytes_in_use"]
                for st in (d.memory_stats() for d in devices) if st),
               default=_UNREPORTED_FREE_BYTES)
    slot_bytes = hash_state_bytes(empty_hash_state(plan, 1, key_dtypes))
    fit = int(free * HASH_STATE_MEMORY_SHARE) // (slot_bytes * tables)
    cap = 1 << max(10, fit.bit_length() - 1)    # power of two at or under
    return (want, origin) if want <= cap else (cap, "free memory")


def _hash_key_dtypes(plan: PhysicalPlan, penv: dict) -> tuple:
    """Device dtype of each group-key expression, probed by evaluating
    the compiled key on a zero-row scan env (uuid lanes, casts and
    dictionary remaps all resolve without trusting declared types)."""
    from citus_tpu.planner.bound import compile_expr
    schema = plan.bound.table.schema
    env = {c: (np.zeros(0, schema.scan_dtype(c, device=True)),
               np.zeros(0, bool))
           for c in plan.scan_columns}
    env.update(penv)
    dts = []
    for k in plan.bound.group_keys:
        kv, _ = compile_expr(k, np)(env)
        dts.append(np.asarray(kv).dtype)
    return tuple(dts)


def _device_rows(a) -> list:
    """The rows of an array whose leading axis is sharded one row a
    device, each as that device's own array (no program runs, and none
    that spans the mesh)."""
    rows = [None] * a.shape[0]
    for sh in a.addressable_shards:
        rows[sh.index[0].start or 0] = sh.data
    return rows


class _SpillDrain:
    """The hash scan's sync hook: at the points where the loop waits
    for the device anyway (one per prefetch window, not per batch) the
    window's spills come back — per batch two scalars (entries offered
    to the table, entries that lost both probes) and, only where some
    did, the spill mask and the entries it marks — and those merge into
    the host accumulator, exactly.  The serial scan's outputs are
    ``[n]``; a megabatched group's ``[qp, n]``, rider by rider (one
    accumulator each); the per-device tables' ``[n_dev, n]`` (``devices``
    of them), every device's into the ONE accumulator.  ``rows`` counts
    the rows of the spilled entries, ``updates`` the entries offered and
    ``slots`` the entry slots the kernel's offer loop ran over for them
    (whole chunks a batch and table: ``updates`` / ``slots`` is the
    share of the loop's gathers and scatters that carried an entry)."""

    def __init__(self, plan: PhysicalPlan, accs: list, table_slots: int,
                 devices: int = 0):
        self.plan, self.accs, self.devices = plan, accs, devices
        self.table_slots = table_slots
        self.rows = self.updates = self.slots = 0

    def __call__(self, pending: list) -> None:
        # one span per drained window: the wait for the window's
        # counts (the device is behind them) and the host merge
        if not pending:
            return
        import jax
        from citus_tpu.ops.hash_agg import merge_hash_tables_into, offer_slots
        q = self.devices or len(self.accs)
        with _trace.span("spill_drain") as dsp:
            n_rows = n_updates = n_slots = 0
            spilling = set()
            for _, (offered, n_spilled, lost, *entries) in pending:
                offered = np.asarray(offered)
                serial = offered.ndim == 0
                offered = np.atleast_1d(offered)[:q].astype(np.int64)
                n_updates += int(offered.sum())
                n_slots += int(offer_slots(offered, self.table_slots,
                                           lost.shape[-1]).sum())
                if not np.atleast_1d(np.asarray(n_spilled))[:q].any():
                    continue
                lost = np.atleast_2d(np.asarray(lost))
                entries = tuple(entries)
                if self.devices:
                    # leaves become lists: a device's row apiece
                    entries = jax.tree_util.tree_map(_device_rows, entries)
                for qi in range(q):
                    at = np.flatnonzero(lost[qi])
                    if not at.size:
                        continue
                    spilling.add(qi)
                    # the marked entries are gathered on the device, in
                    # a power-of-two count so few shapes ever compile:
                    # some tens of KB come back, not the batch's lanes
                    fill = _pow2_at_least(at.size, 1024)
                    idx = np.concatenate(
                        [at, np.full(fill - at.size, at[0])])
                    if self.devices:
                        # the indexes go to the device whose row it is
                        take = lambda rows: rows[qi][0, idx]
                        is_leaf = lambda x: isinstance(x, list)
                    else:
                        idx = jax.device_put(idx)
                        take = lambda a: (a if serial else a[qi])[idx]
                        is_leaf = None
                    keys, parts, rows = jax.device_get(jax.tree_util.tree_map(
                        take, entries, is_leaf=is_leaf))
                    real = np.arange(fill) < at.size
                    n_rows += int(rows[real].sum())
                    merge_hash_tables_into(
                        self.accs[0 if self.devices else qi], self.plan,
                        keys, parts, rows, entry_mask=real)
            GLOBAL_COUNTERS.bump("hash_spill_rows", n_rows)
            GLOBAL_COUNTERS.bump("hash_table_updates", n_updates)
            GLOBAL_COUNTERS.bump("hash_offer_slots", n_slots)
            self.rows += n_rows
            self.updates += n_updates
            self.slots += n_slots
            if dsp.recording:
                dsp.set(batches=len(pending), rows=n_rows,
                        devices=len(spilling) if self.devices
                        else int(bool(spilling)))


def _table_kernel(plan: PhysicalPlan, mesh, slot: str, build, *,
                  extra: tuple = (), replicated: tuple = (), **jit_kwargs):
    """A hash slot's kernel for where the tables live: ``build()``'s
    function jitted as it is (one table, ``mesh`` None) or run by each
    device of the mesh on its own table (``per_device``; the arguments
    at ``replicated`` go to every device whole)."""
    if mesh is None:
        return get_kernel(plan, slot,
                          lambda: jit_compile(build(), **jit_kwargs),
                          extra=extra)
    from citus_tpu.parallel.mesh import per_device, shard_axis_size
    return get_kernel(
        plan, slot,
        lambda: jit_compile(per_device(build(), mesh, replicated),
                            **jit_kwargs),
        extra=extra + ("mesh", shard_axis_size(mesh), "affine"))


class _HashTables(NamedTuple):
    """A hash scan's device state and what the endings need to know of
    it: ``state`` is one table (arrays ``[S]``, on the default device)
    or one a device (``[n_dev, S]``, a row a chip; ``mesh`` then says
    where), ``disjoint`` names the distribution column where no group
    can sit in two of the tables."""
    state: tuple
    mesh: object = None
    disjoint: Optional[str] = None

    @property
    def tables(self) -> int:
        return 1 if self.mesh is None else int(self.state[2].shape[0])

    @property
    def slots(self) -> int:
        return int(self.state[2].shape[-1])


def _disjoint_on(plan: PhysicalPlan) -> Optional[str]:
    """The distribution column, where the plan's group keys hold it as
    it is: every group then lives in ONE shard of a hash-distributed
    table, and per-device tables fed shard-affinely share no group."""
    from citus_tpu.catalog.catalog import DistributionMethod
    from citus_tpu.planner.bound import BColumn
    table = plan.bound.table
    if table.method != DistributionMethod.HASH or table.dist_column is None:
        return None
    held = any(isinstance(k, BColumn) and k.name == table.dist_column
               for k in plan.bound.group_keys)
    return table.dist_column if held else None


def _run_hash_device(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                     params, acc, penv, push_remote: bool,
                     record: PipelineStats) -> _HashTables:
    """Device half of a hash_host plan: stream every local batch into
    donated HBM-resident hash tables (kernel slot ``jit_hash_fused``),
    draining spills into ``acc`` exactly.  ONE table on a host of one
    device, or where the stream is a single batch; else one table a
    DEVICE, each fed its own shards' batches (``AffineMeshPlacement``)
    by the one-device kernel's body under ``shard_map``, no collective.
    With ``push_remote``,
    remote-only shards ship as hash tasks first and their returned table
    partials re-insert through the fused device merge door
    (``jit_hash_merge``; across the devices' tables where there are
    several, so a group may then sit in more than one); push fallbacks
    re-stream locally.  Returns the
    tables, still on the device(s) and ready: how they come home is the
    caller's choice (``_fetch_hash_table``: all of it;
    ``_fetch_hash_survivors``: what HAVING leaves)."""
    import jax
    import jax.numpy as jnp
    from citus_tpu.ops.hash_agg import (
        build_fused_hash_worker, build_fused_entry_merge, empty_hash_state,
        merge_hash_tables_into,
    )

    _trace.set_phase("device")
    key_dtypes = _hash_key_dtypes(plan, penv)
    dispatch = None
    run_plan = plan
    if push_remote:
        local, dispatch = dispatch_remote_tasks(cat, plan, settings, params,
                                                record)
        if local != plan.shard_indexes:
            run_plan = dataclasses.replace(plan, shard_indexes=local)

    def open_stream(shard_indexes):
        return _iter_padded_batches(
            cat, dataclasses.replace(plan, shard_indexes=shard_indexes),
            settings, record)

    try:
        # both passes (local, then push fallbacks) book into the one
        # placement the first pass chose
        placement, stream = choose_affine_placement(run_plan, open_stream,
                                                    record)
        mesh = placement.mesh
        n_dev = 0 if mesh is None else placement.round_size

        step = Step(_table_kernel(
            plan, mesh, "jit_hash_fused",
            lambda: build_fused_hash_worker(plan, jnp, key_dtypes),
            donate_argnums=0), "jit_hash_fused", "hash_fused_dispatches")
        placement.bind(params)
        with _trace.span("hash_init") as sp:
            S, slots_from = _hash_slots(cat, plan, settings, key_dtypes,
                                        placement=placement)
            drain = _SpillDrain(plan, [acc], S, devices=n_dev)
            if mesh is None:
                state = jax.device_put(empty_hash_state(plan, S, key_dtypes))
            else:
                # filled on the chips, each its own table: one small
                # dispatch a query, nothing put from the host
                from jax.sharding import NamedSharding, PartitionSpec

                def hash_zero(slots):
                    return empty_hash_state(plan, slots, key_dtypes, jnp,
                                            tables=n_dev)
                state = get_kernel(
                    plan, "mesh_hash_zero",
                    lambda: jit_compile(
                        hash_zero, static_argnums=0,
                        out_shardings=NamedSharding(
                            mesh, PartitionSpec("shard"))),
                    extra=placement.key_suffix)(S)
            if sp.recording:
                sp.set(slots=S, slots_from=slots_from, devices=n_dev or 1)

        def scan(state, stream):
            # never cached (no key): the window bounds the un-synced H2D
            # bytes from the first round on, so peak device footprint stays
            # O(slots) + depth x batch bytes
            return drive(plan, settings, placement, step, state, record,
                         stream=stream, on_sync=drain)

        state = scan(state, stream)
    except BaseException:
        if dispatch is not None:
            dispatch.abort()  # no RPC thread outlives the attempt
        raise
    disjoint = _disjoint_on(plan) if mesh is not None else None
    if dispatch is not None:
        fallback, remote = dispatch.collect()
        if fallback or remote:
            # the plan proves the tables apart for its own local pass
            # alone: whatever else met them, they come home whole
            disjoint = None
        if fallback:
            state = scan(state, open_stream(fallback) if mesh is None
                         else placement.affine(fallback, open_stream))
        if remote:
            merge_jit = _table_kernel(
                plan, mesh, "jit_hash_merge",
                lambda: build_fused_entry_merge(plan, jnp, key_dtypes),
                donate_argnums=0)
            for table, spilled in remote:
                if table is not None:
                    key_e, part_e, row_e = table
                    # a peer's entries go to the tables as they come, a
                    # slice a device: nothing says which holds a group
                    deal = (lambda a: jnp.asarray(a)) if mesh is None \
                        else placement.deal
                    state, espill = merge_jit(
                        state,
                        tuple((deal(kv), deal(kf)) for kv, kf in key_e),
                        tuple(deal(p) for p in part_e), deal(row_e))
                    espill = np.asarray(espill).reshape(-1)[:len(row_e)]
                    if espill.any():
                        # fingerprint-collision losers among remote
                        # entries: merge exactly on the host
                        merge_hash_tables_into(acc, plan, key_e, part_e,
                                               row_e, entry_mask=espill)
                if spilled is not None:
                    sk, sp, sr = spilled
                    merge_hash_tables_into(acc, plan, sk, sp, sr)
                GLOBAL_COUNTERS.bump("hash_partials_pushed")
    t_dev = clock()
    _block_ready(state)
    record.device_s += clock() - t_dev
    record.book_timings()
    tables = n_dev or 1
    record.tally("hash_tables", tables)
    record.tally("hash_slots", tables * S)
    record.figures["hash_slots_from"] = slots_from
    record.figures["hash_disjoint_on"] = disjoint
    # the drain bumped its counters window by window
    record.figures["hash_spilled_rows"] = drain.rows
    record.figures["hash_table_updates"] = drain.updates
    record.figures["hash_offer_slots"] = drain.slots
    # table rows the scan took, and the fullest device's share of them
    per_device = placement.device_rows if mesh is not None \
        else [sum(n for _, n, _ in record.task_times)]
    record.tally("hash_rows_in", sum(per_device))
    record.tally("hash_rows_in_max_device", max(per_device))
    record.tally("group_rows_in", placement.rows_padded)
    return _HashTables(state, mesh, disjoint)


def _fetch_hash_table(tables: _HashTables, record: PipelineStats):
    """The whole-table ending of a hash scan: every slot of every table
    comes home, as (key_tables, partials, rows) host arrays, table
    after table."""
    import jax
    with _trace.span("fetch") as sp:
        fetched = jax.tree_util.tree_map(
            lambda a: np.asarray(a).reshape(-1),
            jax.device_get(tables.state))
        if sp.recording:
            sp.set(tables=tables.tables, entries=int(fetched[2].shape[0]))
    h_keys = [(kv, kf) for kv, kf in fetched[0]]
    h_partials = tuple(fetched[1])
    h_rows = fetched[2]
    pl = record.figures
    pl["hash_occupancy_pct"] = round(
        100.0 * int((h_rows > 0).sum()) / h_rows.shape[0], 1)
    # every kept row is in an entry's count or among the spilled
    record.tally("group_rows_kept",
                 int(h_rows.sum()) + pl.get("hash_spilled_rows", 0))
    return h_keys, h_partials, h_rows


#: node kinds and value kinds of a HAVING the chip decides as the host
#: does: integer arithmetic, comparisons and three-valued logic
_HAVING_NODES = (BBinOp, BUnOp, BScale, BCast, BIsNull, BCase, BLiteral,
                 BParam, BAggRef, BKeyRef)


def _table_decides(plan: PhysicalPlan, e, dates: bool = False) -> bool:
    """``e`` is computed on a hash table's entries EXACTLY as the host
    computes it: nodes of ``_HAVING_NODES`` over integers, decimals and
    booleans (``dates``: and dates, which order as integers), aggregates
    whose extraction is plain integer arithmetic -- no ``avg`` and its
    division, no ``count(DISTINCT)``, no sketch, no float state on the
    TPU's float32 pairs, nothing through a dictionary."""
    from citus_tpu.executor.finalize import PLAIN_AGGS
    for n in walk(e):
        t = n.type
        if not (isinstance(n, _HAVING_NODES)
                and (t.is_integer or t.is_decimal or t.kind == T.BOOL
                     or (dates and t.kind == T.DATE))):
            return False
        if isinstance(n, BAggRef):
            ex = plan.agg_extract[n.index]
            if ex.kind not in PLAIN_AGGS or not all(
                    np.issubdtype(np.dtype(plan.partial_ops[i].dtype),
                                  np.integer) for i in ex.slots[:2]):
                return False
    return True


def _device_having(plan: PhysicalPlan):
    """The plan's HAVING where the chip can decide it EXACTLY on a hash
    table's entries (``_table_decides``), as ``(generic_having, specs,
    values)`` with its comparison literals hoisted (one compiled filter
    per statement family), or None.  Read from the plan alone."""
    from citus_tpu.planner.auto_param import hoist_literals
    having = plan.bound.having
    if having is None or not _table_decides(plan, having):
        return None
    return hoist_literals(having, len(plan.bound.param_specs))


#: rows an ORDER BY ... LIMIT may ask for and still be cut on the chip
TOP_ROWS_MAX = 1 << 16
#: the host's keys go up to the cut in a power-of-two count of at least
#: this many, so that a table that spills a few hundred keys more under
#: one parameter than under another compiles nothing new
TOP_HOST_KEYS = 2048


def _device_top(plan: PhysicalPlan):
    """The plan's ORDER BY ... LIMIT (+ OFFSET) where the chip can cut
    it EXACTLY on a hash table's entries, as ``(having, order, rows)``
    -- ``having`` as ``_device_having`` gives it (None: the statement
    has none), ``order`` the ORDER BY's ``(final expression, ascending,
    nulls first)``, ``rows`` LIMIT + OFFSET -- or the reason (a string)
    every group comes home: no LIMIT, a DISTINCT above the groups, a
    HAVING or an ORDER BY key the table does not decide (a text key:
    dictionary ids are not ordered; a float; an ``avg``).  Read from the
    plan alone."""
    b = plan.bound
    if not b.order_by or b.limit is None:
        return "no ORDER BY ... LIMIT"
    if b.distinct:
        return "DISTINCT above the groups"
    rows = int(b.limit) + int(b.offset or 0)
    if rows > TOP_ROWS_MAX:
        return f"LIMIT + OFFSET past {TOP_ROWS_MAX}"
    having = None
    if b.having is not None:
        having = _device_having(plan)
        if having is None:
            return "HAVING is not decided on the chip"
    order = []
    for idx, asc, nulls_first in b.order_by:
        e = b.final_exprs[idx]
        if not _table_decides(plan, e, dates=True):
            return (f"ORDER BY key {b.output_names[idx]} ({e.type.kind}) is "
                    f"not ordered on the chip")
        order.append((e, asc, nulls_first))
    return having, order, rows


def _ending_arguments(acc, key_dtypes: tuple, M: int, params, having):
    """What both filtered endings hand their kernel after the table
    state: the plan's parameters and then HAVING's hoisted literals
    (``having``: ``_device_having``'s, or None), and the keys the host
    accumulator holds, padded to ``M``, with their count."""
    pcols, pvalids = params
    _, specs, values = having or (None, [], [])
    hoisted = tuple(np.asarray(v, t.device_dtype)
                    for (t, _), v in zip(specs, values))
    n_host = acc.n_groups
    host_keys = tuple(
        (np.concatenate([kv, np.zeros(M - n_host, kv.dtype)]),
         np.concatenate([kvm, np.zeros(M - n_host, bool)]))
        for kv, kvm in acc.key_arrays(key_dtypes))
    return (pcols + hoisted, pvalids + (np.ones((), bool),) * len(hoisted),
            host_keys, np.int32(n_host))


def _raise_on_overflow(plan: PhysicalPlan, overflows, host_entries,
                       found) -> None:
    """The chip's verdicts count for the keys the host holds no part
    of: an overflow among those (``overflows``, per aggregate that
    carries a shadow, over every occupied entry) raises as
    ``finalize_groups`` would, kept or not; the host's keys (their
    entries ``host_entries``, in the table where ``found``) are checked
    after their merge."""
    from citus_tpu.executor.finalize import (
        raise_sum_overflow, sum_overflow_mask,
    )
    of_host_keys = [int((bad & found).sum()) for bad in (
        sum_overflow_mask(np, ex, host_entries[1])
        for ex in plan.agg_extract) if bad is not None]
    if any(int(np.sum(n)) > m for n, m in zip(overflows, of_host_keys)):
        raise_sum_overflow()


def _fetch_hash_top(plan: PhysicalPlan, state, acc, params, top,
                    record: PipelineStats, key_lanes=None):
    """The cut ending of a hash scan of ONE table: ORDER BY ... LIMIT is
    cut on the table on its chip (kernel slot ``jit_hash_top``,
    ``ops/hash_agg.py`` ``build_hash_top``) and the first ``rows``
    candidates come home as a power-of-two block, beside the entries of
    the keys ``acc`` holds a part of (their final state is entry + host
    part, so they stood aside from the sort and the host decides them).
    ``top`` is ``_device_top``'s; ``key_lanes`` as ``build_hash_top``
    takes it.  Returns ``(table, entry_mask, groups)``, the layout
    ``_finish_hash_agg`` merges, ``groups`` the aggregation's groups
    before HAVING and the cut; or None where block and host keys would
    pass half the table and the whole of it may as well come."""
    import jax
    import jax.numpy as jnp
    from citus_tpu.ops.hash_agg import build_hash_top
    from citus_tpu.planner.bound import param_env_names

    having, order, rows = top
    S = int(state[2].shape[-1])
    n_host = acc.n_groups
    M = _pow2_at_least(n_host, TOP_HOST_KEYS)
    B = _pow2_at_least(rows, 32)
    if B + M > S // 2:
        return None
    generic, specs, _ = having or (None, [], [])
    key_dtypes = tuple(a.dtype for a, _ in state[0])
    names = tuple(param_env_names(list(plan.bound.param_specs) + specs))
    kernel = get_kernel(
        plan, "jit_hash_top",
        lambda: jit_compile(build_hash_top(plan, jnp, generic, order, names,
                                           B, key_lanes)),
        extra=(repr(generic), repr(order), B, key_lanes,
               repr(plan.agg_extract)))
    with _trace.span("group_top") as sp:
        # the host's keys go up once; the winners' block and the host's
        # keys' entries come back in ONE device_get
        winners, candidates, occupied, overflows, host_slots, entries = \
            jax.device_get(kernel(state, *_ending_arguments(
                acc, key_dtypes, M, params, having)))
        found = host_slots < S
        _raise_on_overflow(plan, overflows, entries, found)
        kept = min(int(candidates), B)
        if sp.recording:
            sp.set(slots=S, entries=B + M, kept=kept, spilled_keys=n_host,
                   limit=rows, candidates=int(candidates))
    record.figures["hash_occupancy_pct"] = round(
        100.0 * int(occupied) / S, 1)
    record.tally("group_top_cuts", 1)
    record.tally("group_top_entries", B + M)
    table = jax.tree_util.tree_map(
        lambda a, b: np.concatenate([a.reshape(-1), b.reshape(-1)]),
        entries, winners)
    return (table, np.concatenate([found, np.arange(B) < kept]),
            int(occupied) + n_host - int(found.sum()))


def _fetch_hash_survivors(plan: PhysicalPlan, tables: _HashTables, acc,
                          params, having, record: PipelineStats):
    """The filtered ending of the coordinator's hash scan: HAVING is
    decided on each table on its chip (kernel slot ``jit_hash_having``)
    and what can still matter comes home — the blocks that hold a
    survivor and the entries of the keys ``acc`` holds a part of (their
    final state is entry + host part: the host decides those; every
    table is probed for them).  Sound for one table, and for several
    that share no group.  Returns
    ``(table, entry_mask, groups)``, the layout ``_finish_hash_agg``
    merges, with ``groups`` the aggregation's groups before HAVING; or
    None where those would pass half a table and the whole of it may
    as well come (a HAVING that keeps most groups, a table so small
    that most keys spilled)."""
    import jax
    import jax.numpy as jnp
    from citus_tpu.ops.hash_agg import (
        FILTER_BLOCK, build_hash_having, hash_take,
    )
    from citus_tpu.planner.bound import param_env_names

    # the keys the accumulator holds go up in a power-of-two count and
    # the survivors' blocks are gathered in one of at least 1/256 of
    # the table, so few shapes ever compile
    state, mesh = tables.state, tables.mesh
    S = tables.slots
    n_host = acc.n_groups
    M = _pow2_at_least(n_host, 1024)
    least_blocks = max(8, -(-S // FILTER_BLOCK) >> 8)
    if least_blocks * FILTER_BLOCK + M > S // 2:
        return None
    generic, specs, _ = having
    key_dtypes = tuple(a.dtype for a, _ in state[0])
    names = tuple(param_env_names(list(plan.bound.param_specs) + specs))
    kernel = _table_kernel(
        plan, mesh, "jit_hash_having",
        lambda: build_hash_having(plan, jnp, generic, names),
        extra=(repr(generic), repr(plan.agg_extract)),
        replicated=(1, 2, 3, 4))
    # one table: its results as the first of one
    lead = (lambda tree: tree) if mesh is not None else (
        lambda tree: jax.tree_util.tree_map(lambda a: a[None], tree))
    with _trace.span("hash_filter") as sp:
        # the host's keys go up once; their entries come back with the
        # marks, in ONE device_get
        keep, home = kernel(state, *_ending_arguments(
            acc, key_dtypes, M, params, having))
        marks, occupied, overflows, host_slots, entries = \
            lead(jax.device_get(home))
        found = host_slots < S
        _raise_on_overflow(plan, overflows, entries, found)
        blocks = [np.flatnonzero(m) for m in marks]
        if sp.recording:
            sp.set(slots=S, host_keys=n_host, tables=tables.tables,
                   blocks=sum(b.size for b in blocks),
                   host_keys_in_table=int(found.sum()))
    pl = record.figures
    occupied = int(occupied.sum())
    pl["hash_occupancy_pct"] = round(100.0 * occupied / (S * len(marks)), 1)
    n_blocks = _pow2_at_least(max(b.size for b in blocks), least_blocks)
    if n_blocks * FILTER_BLOCK + M > S // 2:
        return None
    with _trace.span("fetch") as sp:
        # a block past the last marked one repeats slot 0, masked; the
        # table's last block may run past its end: clipped and masked
        at = np.stack([
            (np.concatenate([b, np.full(n_blocks - b.size, marks.shape[1])]
                            )[:, None] * FILTER_BLOCK
             + np.arange(FILTER_BLOCK)).reshape(-1) for b in blocks])
        take = _table_kernel(plan, mesh, "jit_hash_take", lambda: hash_take)
        at_dev = np.where(at < S, at, 0).astype(np.int32)
        survivors, kept = lead(jax.device_get(take(
            (state, keep), at_dev if mesh is not None else at_dev[0])))
        if sp.recording:
            sp.set(tables=tables.tables, entries=int(at.size + found.size))
    entries = jax.tree_util.tree_map(
        lambda a, b: np.concatenate([a.reshape(-1), b.reshape(-1)]),
        entries, survivors)
    # no entry merges twice: a host key's entry came with the first fetch
    kept = kept & (at < S) & ~np.stack([
        np.isin(a, slots[f]) for a, slots, f in zip(at, host_slots, found)])
    pl["hash_having_on_device"] = True
    return (entries, np.concatenate([found.reshape(-1), kept.reshape(-1)]),
            occupied + n_host - int(found.sum()))


def _run_hash_partial_state(cat: Catalog, plan: PhysicalPlan,
                            settings: Settings, params,
                            record: PipelineStats):
    """Worker half of a pushed hash task: -> (table | None, spilled |
    None) where ``table`` is the merged device hash table's host arrays
    and ``spilled`` re-renders the host accumulator's exact groups as
    entry arrays (key values, int8 flags [valid+1], partial values, one
    synthetic row per group).  cpu-backend workers ship spill-only."""
    from citus_tpu.executor.host_agg import HostGroupAccumulator

    acc = HostGroupAccumulator(len(plan.bound.group_keys), plan.partial_ops)
    penv = _params_env(plan, params)
    table = None
    if settings.executor.task_executor_backend != "cpu":
        # a worker ships its whole table: HAVING on a worker is sound
        # only where the group key holds the distribution column
        table = _fetch_hash_table(_run_hash_device(
            cat, plan, settings, params, acc, penv, push_remote=False,
            record=record), record)
    else:
        pcols, pvalids = params
        worker = build_worker_fn(plan, np)
        for si in plan.shard_indexes:
            for values, masks, n in load_shard_batches(cat, plan, si):
                cols = tuple(values[c].astype(
                    plan.bound.table.schema.scan_dtype(c, device=True),
                    copy=False) for c in plan.scan_columns)
                valids = tuple(masks[c] for c in plan.scan_columns)
                mask, keys, args = worker(cols + pcols, valids + pvalids,
                                          np.ones(n, bool))
                acc.add_batch(
                    np.asarray(mask),
                    [(np.asarray(v), m if isinstance(m, bool)
                      else np.asarray(m)) for v, m in keys],
                    [(np.asarray(v), m if isinstance(m, bool)
                      else np.asarray(m)) for v, m in args])
    key_arrays, partials = acc.finalize(
        [k.type for k in plan.bound.group_keys])
    spilled = None
    if key_arrays:
        G = int(np.asarray(key_arrays[0][0]).shape[0])
        keys_w = [(np.asarray(vals),
                   np.asarray(valid).astype(np.int8) + 1)
                  for vals, valid in key_arrays]
        spilled = (keys_w, tuple(np.asarray(p) for p in partials or ()),
                   np.ones(G, np.int64))
    return table, spilled


def _finish_hash_agg(cat: Catalog, plan: PhysicalPlan, acc, table,
                     penv: dict, record: PipelineStats, entry_mask=None,
                     groups=None, pieces: int = 1) -> list[tuple]:
    """The exact tail of a device hash aggregation, on the caller's
    thread: the fetched ``table`` (key tables, partials, rows; of them
    the entries ``entry_mask`` marks, all where it is None) merges into
    ``acc``, which holds the spilled rows' groups already; then the
    accumulator's arrays, HAVING and the rendering of the kept groups.
    ``groups`` are the aggregation's groups before HAVING, where the
    filtered ending left some of them on the chip.  ``pieces`` > 1:
    ``table`` is that many tables end to end which may hold a group
    more than once between them; they merge one after another, each a
    table of distinct keys."""
    from citus_tpu.ops.hash_agg import (
        hash_state_bytes, merge_hash_tables_into,
    )
    fetched = hash_state_bytes(table)
    entries = int(table[2].shape[0])
    with _trace.span("hash_merge") as sp:
        if pieces > 1:
            for i in range(pieces):
                cut = slice(i * entries // pieces, (i + 1) * entries // pieces)
                merge_hash_tables_into(
                    acc, plan, [(kv[cut], kf[cut]) for kv, kf in table[0]],
                    [p[cut] for p in table[1]], table[2][cut])
        else:
            merge_hash_tables_into(acc, plan, *table, entry_mask=entry_mask)
        if sp.recording:
            sp.set(tables=pieces, entries=entries)
    with _trace.span("hash_finalize") as sp:
        key_arrays, parts = acc.finalize(
            [k.type for k in plan.bound.group_keys],
            scalar=not plan.bound.group_keys)
        record.tally("hash_groups_out",
                     acc.n_groups if groups is None else groups)
        record.tally("hash_table_bytes_fetched", fetched)
        record.tally("hash_entries_fetched", entries)
        out = [] if parts is None else finalize_groups(
            plan, cat, key_arrays, parts, params_env=penv)
        if sp.recording:
            sp.set(groups=acc.n_groups, rows=len(out))
        return out


def _run_agg_hash_host(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                       params, record: PipelineStats) -> list[tuple]:
    """Unbounded GROUP BY cardinality.

    tpu backend: streaming fused device hash aggregation
    (ops/hash_agg.py build_fused_hash_worker) — one donated HBM-resident
    table (one a device where the host has several), one dispatch per
    batch (per round), exact host merge of what comes home
    and of spilled entries; remote-only shards push hash tasks and ship
    table partials back over CTFR frames.  cpu backend (and exact
    value-set partials): full host grouping over the pull path."""
    from citus_tpu.executor.host_agg import HostGroupAccumulator
    from citus_tpu.executor.worker_tasks import note_inexpressible

    backend = settings.executor.task_executor_backend
    acc = HostGroupAccumulator(len(plan.bound.group_keys), plan.partial_ops)
    pcols, pvalids = params
    penv = _params_env(plan, params)

    if backend != "cpu" and not _hash_has_exact(plan):
        tables = _run_hash_device(cat, plan, settings, params, acc, penv,
                                  push_remote=True, record=record)
        # the ending adapts to what the plan proves.  One table, or one
        # a device that share no group (the keys hold the distribution
        # column and each device took its own shards): every part of a
        # group that is not in its table is in ``acc`` already, so where
        # the chip can decide HAVING, only what can still matter comes
        # home.  Tables that may hold a group several times come home
        # whole and merge exactly, HAVING after the merge.
        apart = tables.tables == 1 or tables.disjoint is not None
        merged = record.tally("hash_tables_merged",
                              0 if apart else tables.tables)
        # ORDER BY ... LIMIT over one table that the chip can cut: the
        # winners' block comes home; else what HAVING leaves, else all
        top = _device_top(plan) if tables.mesh is None \
            else "one table a device"
        record.figures["group_top"] = top if isinstance(top, str) \
            else f"first {top[2]} on device"
        home = isinstance(top, tuple) and _fetch_hash_top(
            plan, tables.state, acc, params, top, record)
        having = not home and apart and _device_having(plan)
        home = home or (having and _fetch_hash_survivors(
            plan, tables, acc, params, having, record))
        if home:
            table, entry_mask, groups = home
            return _finish_hash_agg(cat, plan, acc, table, penv, record,
                                    entry_mask, groups)
        return _finish_hash_agg(cat, plan, acc,
                                _fetch_hash_table(tables, record), penv,
                                record, pieces=max(1, merged))

    # exact value-set partials (or the cpu oracle backend) stay host-only
    # and are not elementwise-combinable — remote-only shards pull
    note_inexpressible(cat, plan, settings)
    worker = build_worker_fn(plan, np)
    for si in plan.shard_indexes:
        for values, masks, n in load_shard_batches(cat, plan, si):
            cols = tuple(values[c].astype(plan.bound.table.schema.scan_dtype(c, device=True),
                                          copy=False) for c in plan.scan_columns)
            valids = tuple(masks[c] for c in plan.scan_columns)
            mask, keys, args = worker(cols + pcols, valids + pvalids,
                                      np.ones(n, bool))
            acc.add_batch(np.asarray(mask),
                          [(np.asarray(v), m if isinstance(m, bool) else np.asarray(m))
                           for v, m in keys],
                          [(np.asarray(v), m if isinstance(m, bool) else np.asarray(m))
                           for v, m in args])
    key_arrays, partials = acc.finalize([k.type for k in plan.bound.group_keys],
                                        scalar=not plan.bound.group_keys)
    if partials is None:
        return []
    return finalize_groups(plan, cat, key_arrays, partials, params_env=penv)


# ----------------------------------------------------------- projection


def _run_projection(cat: Catalog, plan: PhysicalPlan, settings: Settings,
                    params, record: PipelineStats) -> list[tuple]:
    backend = settings.executor.task_executor_backend
    use_jax = backend != "cpu"
    pcols, pvalids = params
    penv = _params_env(plan, params)
    pnames = tuple(penv)
    filter_fn = None
    if use_jax and plan.bound.filter is not None:
        import jax
        import jax.numpy as jnp
        from citus_tpu.planner.bound import compile_expr, predicate_mask

        def _build_filter():
            cfn = compile_expr(plan.bound.filter, jnp)
            all_names = tuple(plan.scan_columns) + pnames

            def device_mask(cols, valids, row_mask):
                env = {n: (c, v) for n, c, v in zip(all_names, cols, valids)}
                return row_mask & predicate_mask(jnp, cfn, env, row_mask)
            return jit_compile(device_mask)
        filter_fn = get_kernel(plan, "jit_filter", _build_filter)

    def _scan_shards(rp, out: list) -> None:
        for si in rp.shard_indexes:
            for values, masks, n in load_shard_batches(cat, plan, si):
                cols = tuple(values[c].astype(plan.bound.table.schema.scan_dtype(c, device=True),
                                              copy=False) for c in plan.scan_columns)
                valids = tuple(masks[c] for c in plan.scan_columns)
                if filter_fn is not None:
                    mask = np.asarray(filter_fn(cols + pcols, valids + pvalids,
                                                np.ones(n, bool)))
                elif plan.bound.filter is not None:
                    from citus_tpu.planner.bound import compile_expr, predicate_mask
                    cfn_np = plan.runtime_cache.get("np_filter")
                    if cfn_np is None:
                        cfn_np = compile_expr(plan.bound.filter, np)
                        plan.runtime_cache["np_filter"] = cfn_np
                    env = {c: (cols[i], valids[i]) for i, c in enumerate(plan.scan_columns)}
                    env.update(penv)
                    mask = np.asarray(predicate_mask(np, cfn_np, env, np.ones(n, bool)))
                    mask = mask & np.ones(n, bool)
                else:
                    mask = np.ones(n, bool)
                env = {c: (cols[i], valids[i]) for i, c in enumerate(plan.scan_columns)}
                env.update(penv)
                out.append((env, mask))

    # remote-only placements execute scan+filter where the data lives
    # and return already-compacted rows; local shards stream HERE while
    # the remote RPCs are in flight (the adaptive executor's overlap of
    # worker waits with the coordinator's own placements)
    local, dispatch = dispatch_remote_tasks(cat, plan, settings, params,
                                            record)
    run_plan = plan
    if local != plan.shard_indexes:
        run_plan = dataclasses.replace(plan, shard_indexes=local)
    local_batches: list = []
    try:
        _scan_shards(run_plan, local_batches)
    except BaseException:
        dispatch.abort()  # no RPC thread outlives the attempt
        raise
    fallback, remote_batches = dispatch.collect()
    env_batches = []
    for values, validity in remote_batches:
        if not plan.scan_columns:
            continue
        n = len(values[plan.scan_columns[0]])
        if n == 0:
            continue
        env = {c: (values[c].astype(
                       plan.bound.table.schema.scan_dtype(c, device=True),
                       copy=False),
                   validity[c]) for c in plan.scan_columns}
        env.update(penv)
        env_batches.append((env, np.ones(n, bool)))
    env_batches.extend(local_batches)
    if fallback:
        _scan_shards(dataclasses.replace(plan, shard_indexes=fallback),
                     env_batches)
    return project_rows(plan, cat, env_batches)


# ---------------------------------------------------------------- entry


def _guard_remote_written(cat, table_names) -> None:
    """Refuse reads of tables whose REMOTE shards this transaction
    wrote: the staged state lives in branch sessions on other hosts and
    is invisible to local scans — silently returning the pre-image
    would be wrong.  This executor-level check catches every route to
    the table (views, subqueries, joins), not just top-level FROMs."""
    from citus_tpu.storage.overlay import current_overlay
    txn = current_overlay()
    if txn is None or not getattr(txn, "remote_written_tables", None):
        return
    hit = set(table_names) & txn.remote_written_tables
    if hit:
        from citus_tpu.errors import UnsupportedFeatureError
        raise UnsupportedFeatureError(
            f"cannot read {sorted(hit)[0]!r} in this transaction after "
            "writing its remote-hosted shards (remote staged state is "
            "not visible here); COMMIT first")


def _bind_time_prune(plan: PhysicalPlan, params) -> PhysicalPlan:
    """Custom-plan pruning for one execution of a generic plan: the
    bind-time physical param values are substituted back into the filter
    and the shard set, chunk intervals, tenant router key and index
    fast-path are re-derived — a cached generic plan prunes exactly like
    a freshly-planned literal query (reference: deferred pruning on
    Job->deferredPruning).  The shared runtime_cache dict rides along,
    so what was compiled from the plan (kernels, the numpy arm's
    closures, the fingerprint) is reused across parameter values; what
    an execution counts goes to its own ``PipelineStats``."""
    bound = plan.bound
    pcols, pvalids = params
    phys = [pcols[i].item() if bool(pvalids[i]) else None
            for i in range(len(pcols))]
    sub = substitute_params(bound.filter, phys)
    shard_indexes, router_key = prune_shards(bound.table, sub, return_key=True)
    if plan.router_param is not None and phys[plan.router_param] is None:
        shard_indexes = []  # dist = NULL matches nothing
    return dataclasses.replace(
        plan, shard_indexes=shard_indexes, router_key=router_key,
        intervals=extract_intervals(sub),
        index_eq=_index_eq(bound.table, sub))


def execute_select(cat: Catalog, bound: BoundSelect, settings: Settings,
                   plan: Optional[PhysicalPlan] = None,
                   param_values: Optional[list] = None) -> Result:
    t0 = clock()
    _guard_remote_written(cat, [bound.table.name])
    if plan is not None and (any(plan.proved_away)
                             or plan.group_mode.kind == "direct"
                             or plan.narrow_lanes) \
            and sees_staged_rows(bound.table):
        # a cached plan dropped partial states, sized its group table
        # or narrowed a scan column's lane on the strength of the
        # table's statistics, and this scan sees rows they do not cover
        # (the transaction's own staged writes): plan with every guard,
        # no bound taken from them and every column at its full width
        plan = None
    if plan is None:
        with _trace.span("plan_physical"):
            plan = plan_select(
                cat, bound, direct_limit=settings.planner.direct_gid_limit)
    with _trace.span("bind_params") as sp:
        params = encode_params(cat, bound, param_values)
        if sp.recording:
            sp.set(params=len(params[0]))
    _exec_span = _trace.span("execute")
    _exec_span.__enter__()
    try:
        if bound.param_specs:
            # deferred pruning: re-derive the shard/interval view of the
            # cached generic plan for THESE parameter values
            with _trace.span("prune"):
                plan = _bind_time_prune(plan, params)
            # window != 0 opts parameterized queries into same-family
            # coalescing (negative = auto-sized from the plan family's
            # arrival rate); at 0 (default) the module is never imported
            # and the serial path below is byte-identical to before
            if settings.executor.megabatch_window_ms != 0:
                from citus_tpu.executor.megabatch import maybe_megabatch
                r = maybe_megabatch(cat, bound, settings, plan, params,
                                    t0, _exec_span)
                if r is not None:
                    return r
        return _execute_select_traced(cat, bound, settings, plan, params,
                                      t0, _exec_span)
    finally:
        _exec_span.__exit__(None, None, None)


def _execute_select_traced(cat: Catalog, bound: BoundSelect,
                           settings: Settings, plan: PhysicalPlan,
                           params, t0: float, exec_span) -> Result:
    GLOBAL_COUNTERS.bump("queries_executed")
    if plan.is_router:
        GLOBAL_COUNTERS.bump("router_queries")
    elif len(plan.shard_indexes) > 1:
        GLOBAL_COUNTERS.bump("multi_shard_queries")
    # admission control: one device-dispatch slot per executing query
    # (the citus.max_shared_pool_size analog; 0 = unlimited), granted
    # through the tenant-aware fair-share scheduler — router queries
    # are charged to their distribution-key tenant, multi-shard
    # analytics to the shared "*" tenant
    from citus_tpu.transaction.snapshot import snapshot_read
    from citus_tpu.workload import GLOBAL_SCHEDULER, tenant_key
    with GLOBAL_SCHEDULER.slot(settings, tenant_key(plan.router_key),
                               timeout=settings.executor.lock_timeout_s):
        # snapshot read: never blocks behind writers — the scan is
        # validated against the table's flip generation and retried if
        # a multi-file metadata flip (TRUNCATE, DML commit, shard
        # split) overlapped (transaction/snapshot.py; the MVCC
        # never-block property the reference inherits from PostgreSQL)
        run_plan = plan

        def _replan(trust_stats: bool = True) -> PhysicalPlan:
            fresh = plan_select(
                cat, bound, direct_limit=settings.planner.direct_gid_limit,
                trust_stats=trust_stats)
            if bound.param_specs:
                fresh = _bind_time_prune(fresh, params)
            return fresh

        def _run(run):
            # the record is of the attempt that answers: one that is
            # run again (a flip, belied lanes) leaves nothing in it
            record = PipelineStats()
            return run(cat, run_plan, settings, params, record), record

        def _attempt():
            nonlocal run_plan
            if run_plan.table_shard_count not in (-1,
                                                  len(bound.table.shards)):
                # the table's shard map changed since this plan was
                # built (a split's catalog flip racing the scan):
                # planned shard indexes would resolve against the NEW
                # shard list — re-plan before (re)trying
                run_plan = _replan()
            if not bound.has_aggs:
                return _run(_run_projection)
            try:
                return _run(_run_agg)
            except ScanLanesBelied:
                # a batch held a value the table's statistics rule out
                # (nothing was cached, no state fetched): what else the
                # plan took from them stands no better, so the statement
                # runs once more on a plan that takes nothing from them
                run_plan = _replan(trust_stats=False)
                return _run(_run_agg)
        rows, record = snapshot_read(
            cat.data_dir, bound.table, _attempt,
            timeout=settings.executor.lock_timeout_s)
        plan = run_plan
    return _finish_select(bound, plan, rows, t0, exec_span, record)


def _finish_select(bound: BoundSelect, plan: PhysicalPlan, rows: list[tuple],
                   t0: float, exec_span, record: PipelineStats,
                   megabatch: Optional[dict] = None) -> Result:
    """Shared tail of the serial and megabatched paths: ORDER/LIMIT +
    hidden-output trim, result-shape counters, span attrs and the
    explain dict, read from the execution's ``record``.  Runs on the
    issuing caller's own thread either way, so per-query spans and stat
    attribution are identical under coalescing (``megabatch`` adds the
    occupancy attrs)."""
    _trace.set_phase("finalize")
    with _trace.span("finalize"):
        rows = order_and_limit(plan, rows)
        if bound.hidden_outputs:
            keep = len(bound.output_names) - bound.hidden_outputs
            rows = [r[:keep] for r in rows]
    GLOBAL_COUNTERS.bump("rows_returned", len(rows))
    elapsed = clock() - t0
    if exec_span.recording:
        exec_span.set(
            strategy=plan.group_mode.kind if bound.has_aggs else "projection",
            shards=len(plan.shard_indexes), router=bool(plan.is_router),
            rows=len(rows))
        if record.figures:
            # the full pipeline-overlap dict rides the span so EXPLAIN
            # ANALYZE and the Chrome export render from one source
            exec_span.attrs["pipeline"] = dict(record.figures)
        if megabatch:
            exec_span.attrs["megabatch"] = dict(megabatch)
    visible = list(bound.output_names)
    if bound.hidden_outputs:
        visible = visible[:len(visible) - bound.hidden_outputs]
    from citus_tpu.observability.load_attribution import GLOBAL_ATTRIBUTION
    from citus_tpu.workload import tenant_key
    with _trace.span("book_stats"):
        GLOBAL_ATTRIBUTION.book_query(
            bound.table, tenant_key(plan.router_key),
            record.task_times + record.mesh_task_times, record.task_bytes,
            len(rows), record.remote_tasks,
            head_si=plan.shard_indexes[0] if plan.shard_indexes else None)
    explain = {
        "strategy": plan.group_mode.kind if bound.has_aggs else "projection",
        "shards": len(plan.shard_indexes),
        "router": plan.is_router,
        "intervals": [c.column for c in plan.intervals],
        "elapsed_s": elapsed,
        "tasks": record.task_times,
        "remote_tasks": record.remote_tasks,
        "pipeline": record.figures,
        "router_key": plan.router_key,
    }
    if megabatch:
        explain["megabatch"] = dict(megabatch)
    if bound.has_aggs:
        # the states this plan's kernels compute, and the overflow
        # guards and NULL counts the table's statistics proved away
        guards, counts = plan.proved_away
        explain["partials"] = {"computed": len(plan.partial_ops),
                               "overflow_guards_proved_away": guards,
                               "null_counts_proved_away": counts}
        GLOBAL_COUNTERS.bump("agg_partials", len(plan.partial_ops))
        GLOBAL_COUNTERS.bump("agg_partials_proved_away", guards + counts)
    return Result(
        columns=visible,
        rows=rows,
        types=[e.type for e in bound.final_exprs][:len(visible)],
        explain=explain,
    )
