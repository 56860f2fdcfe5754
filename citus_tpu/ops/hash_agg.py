"""Device-side hash aggregation for unbounded GROUP BY cardinality.

When the key domain can't be proven small (no direct-gid mode), the
executor aggregates on device into ONE fixed-size open-addressed hash
table that lives in HBM for the whole scan: ``build_fused_hash_worker``
composes filter→fingerprint→claim→insert *and* the merge into the prior
table state in a single traced body, so the executor jits it with
``donate_argnums=0`` (kernel-cache slot ``jit_hash_fused``) and XLA
reuses the table buffers in place — one dispatch per batch, no per-batch
tables, no concatenate+re-insert merge kernels.

Placement is exact, never probabilistic: a row claims a slot by 64-bit
key fingerprint (minimum fingerprint wins the scatter race), but the
claim only counts when the slot's stored *key values* match the row's
keys exactly.  Each fingerprint gets two candidate slots (a
second-chance probe through a remixed hash); rows that lose both are
reported in a spill mask and re-aggregated exactly on the host
(HostGroupAccumulator) — the static-shape analog of a hash-agg spilling
to disk.  Occupancy only grows and the probe sequence is deterministic,
so a group keeps matching the slot it first landed in across batches.

Float keys are canonicalized before fingerprinting and storage
(``-0.0`` → ``0.0``, every NaN payload → the canonical quiet NaN) so
SQL-equal values share one bit pattern; HostGroupAccumulator applies the
same canonicalization to its key bytes, keeping the two paths in one
group space.

The merged table is fixed-shape arrays, which also makes it a wire
value: workers ship (key values, key flags, partial tables, rows) as
CTFR frame columns (net/data_plane.py encode_hash_partials) and the
coordinator re-inserts remote entries through the same claim/match core
(``build_fused_entry_merge``, slot ``jit_hash_merge``) — the reference's
two-stage worker_partial_agg / coord_combine_agg seam
(multi_logical_optimizer.c), with O(slots) on the wire instead of
O(rows).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from citus_tpu.planner.bound import _as_mask, compile_expr, param_env_names, predicate_mask
from citus_tpu.planner.aggregates import float_bits
from citus_tpu.planner.physical import PhysicalPlan
from citus_tpu.ops.scan_agg import _sentinel

_FNV = np.uint64(0xCBF29CE484222325)
_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


def _mix(xp, h, v):
    h = (h ^ v) + _GOLD
    h = h ^ (h >> np.uint64(30))
    h = h * _C1
    h = h ^ (h >> np.uint64(27))
    h = h * _C2
    return h ^ (h >> np.uint64(31))


def _fingerprint(xp, keys, shape):
    """keys: [(values, valid_mask)] -> uint64 fingerprints."""
    h = xp.full(shape, _FNV, np.uint64)
    for kv, kvm in keys:
        kv = xp.asarray(kv)
        if np.issubdtype(kv.dtype, np.floating):
            bits = float_bits(xp, kv)
        else:
            bits = kv.astype(np.int64).view(np.uint64)
        bits = xp.where(kvm, bits, _GOLD)
        h = _mix(xp, h, bits + kvm.astype(np.uint64))
    return h


def _key_sentinel(dt: np.dtype):
    """Empty-slot fill for a key value table: the dtype's minimum, so
    occupied slots survive neutral ``.at[].max`` writes."""
    dt = np.dtype(dt)
    if np.issubdtype(dt, np.floating):
        return dt.type(-np.inf)
    if dt == np.dtype(bool):
        return False
    return dt.type(np.iinfo(dt).min)


def _canon_keys(xp, keys):
    """Canonical float key values: ``-0.0`` → ``0.0`` and every NaN
    payload → the dtype's canonical quiet NaN, so SQL-equal values share
    one bit pattern in fingerprints AND key-table storage.  Null key
    values are zeroed (the valid flag disambiguates) so equal nulls
    always match their stored entry instead of spilling on whatever the
    scan left in the value lane."""
    out = []
    for kv, kvm in keys:
        kv = xp.asarray(kv)
        dt = kv.dtype
        if np.issubdtype(dt, np.floating):
            kv = xp.where(kv == dt.type(0), dt.type(0.0), kv)
            kv = xp.where(xp.isnan(kv), dt.type(np.nan), kv)
        kv = xp.where(kvm, kv, dt.type(0))
        out.append((kv, kvm))
    return out


def _stored_eq(xp, kvt, kft, slot, kv, kvm):
    """Slot ``slot`` stores exactly this key value+validity.  NaN-aware
    for float keys: the canonical NaN equals itself."""
    sv = kvt[slot]
    eq = sv == kv
    if np.issubdtype(np.dtype(kvt.dtype), np.floating):
        eq = eq | (xp.isnan(sv) & xp.isnan(kv))
    return eq & (kft[slot] == kvm.astype(np.int8) + 1)


def _insert_keys(xp, keys, mask, h, key_tables, occ):
    """Two-probe match-or-claim into a RUNNING table.

    keys are canonical (``_canon_keys``); ``occ`` marks slots occupied
    before this batch.  Each probe round first matches rows against the
    stored entry at their candidate slot, then lets unmatched rows claim
    an UNOCCUPIED slot (min fingerprint wins; stored key values verify
    the claim exactly — fingerprint collisions lose and spill).  Returns
    ``(slot, placed, key_tables, occ)`` with the updated tables; rows
    with ``placed`` False must spill to the host.
    """
    S = occ.shape[0]
    sent = np.uint64(0xFFFFFFFFFFFFFFFF)
    fslot = None
    placed = xp.zeros(mask.shape, bool)
    for hp in (h, _mix(xp, h, _GOLD)):
        cand = (hp % np.uint64(S)).astype(np.int32)
        want = mask & ~placed
        cand = xp.where(want, cand, 0)
        match = want & occ[cand]
        for (kv, kvm), (kvt, kft) in zip(keys, key_tables):
            match = match & _stored_eq(xp, kvt, kft, cand, kv, kvm)
        wants_claim = want & ~match & ~occ[cand]
        claimed = xp.full((S,), sent, np.uint64).at[cand].min(
            xp.where(wants_claim, hp, sent))
        claim_ok = wants_claim & (claimed[cand] == hp)
        new_tables = []
        for (kv, kvm), (kvt, kft) in zip(keys, key_tables):
            ksent = _key_sentinel(kvt.dtype)
            kvt = kvt.at[cand].max(
                xp.where(claim_ok, kv, ksent).astype(kvt.dtype))
            kft = kft.at[cand].max(
                xp.where(claim_ok, kvm.astype(np.int8) + 1, 0).astype(np.int8))
            new_tables.append((kvt, kft))
        verified = claim_ok
        for (kv, kvm), (kvt, kft) in zip(keys, new_tables):
            verified = verified & _stored_eq(xp, kvt, kft, cand, kv, kvm)
        key_tables = new_tables
        occ = occ | (xp.zeros((S,), np.int32).at[cand].add(
            verified.astype(np.int32)) > 0)
        took = match | verified
        fslot = cand if fslot is None else xp.where(took, cand, fslot)
        placed = placed | took
    return fslot, placed, key_tables, occ


def _eval_keys(xp, key_fns, key_dtypes, env, shape):
    keys = []
    for kf, kdt in zip(key_fns, key_dtypes):
        kv, kvalid = kf(env)
        kv = xp.asarray(kv).astype(np.dtype(kdt))
        if kv.ndim == 0:
            kv = xp.broadcast_to(kv, shape)
        kvm = _as_mask(xp, kvalid, kv)
        if getattr(kvm, "ndim", 1) == 0:
            kvm = xp.broadcast_to(kvm, shape)
        keys.append((kv, kvm))
    return _canon_keys(xp, keys)


def build_fused_hash_worker(plan: PhysicalPlan, xp,
                            key_dtypes: tuple) -> Callable:
    """Fused streaming insert: (table_state, cols, valids, row_mask) ->
    (table_state', spill_mask[N]).

    ``table_state`` is ``(key_tables [(vals[S], flags[S] int8)...],
    partial tables tuple [S], rows[S] int64)`` (see ``empty_hash_state``)
    and is meant to be DONATED: every output array derives from an
    in-place ``.at[]`` update of the matching input, so XLA reuses the
    table's HBM buffers across batches.  The slot count is read off the
    state shapes, not baked into the closure — one cached kernel serves
    any ``citus.hash_agg_slots`` setting."""
    filter_fn = compile_expr(plan.bound.filter, xp) \
        if plan.bound.filter is not None else None
    key_fns = [compile_expr(k, xp) for k in plan.bound.group_keys]
    arg_fns = [compile_expr(a, xp) for a in plan.agg_args]
    names = plan.scan_columns + param_env_names(plan.bound.param_specs)
    partial_ops = plan.partial_ops
    key_dtypes = tuple(np.dtype(d) for d in key_dtypes)

    # named for its kernel slot: the XLA module in a device trace is
    # jit_hash_fused, apart from the scan kernel's jit_fused
    def hash_fused(table_state, cols, valids, row_mask):
        key_tables, partials, rows = table_state
        key_tables = list(key_tables)
        env = {n: (c, v) for n, c, v in zip(names, cols, valids)}
        mask = row_mask
        if filter_fn is not None:
            mask = mask & predicate_mask(xp, filter_fn, env, row_mask)
        keys = _eval_keys(xp, key_fns, key_dtypes, env, row_mask.shape)
        h = _fingerprint(xp, keys, row_mask.shape)
        slot, placed, key_tables, _ = _insert_keys(
            xp, keys, mask, h, key_tables, rows > 0)
        spill = mask & ~placed
        outs = []
        for op, prior in zip(partial_ops, partials):
            dt = np.dtype(op.dtype)
            if op.arg_index < 0:
                outs.append(prior.at[slot].add(
                    xp.where(placed, 1, 0).astype(np.int64)))
                continue
            v, valid = arg_fns[op.arg_index](env)
            v = xp.asarray(v)
            if v.ndim == 0:
                v = xp.broadcast_to(v, row_mask.shape)
            ok = placed & _as_mask(xp, valid, placed)
            if op.kind == "count":
                outs.append(prior.at[slot].add(
                    xp.where(ok, 1, 0).astype(np.int64)))
            elif op.kind == "sum":
                outs.append(prior.at[slot].add(
                    xp.where(ok, v, 0).astype(dt)))
            else:
                s_ = dt.type(_sentinel(op.kind, dt))
                upd = xp.where(ok, v, s_).astype(dt)
                outs.append(prior.at[slot].min(upd) if op.kind == "min"
                            else prior.at[slot].max(upd))
        rows = rows.at[slot].add(xp.where(placed, 1, 0).astype(np.int64))
        return (tuple(key_tables), tuple(outs), rows), spill
    return hash_fused


def build_fused_entry_merge(plan: PhysicalPlan, xp,
                            key_dtypes: tuple) -> Callable:
    """Device merge door for remote hash partials:
    (table_state, key_entries, partial_entries, row_entries) ->
    (table_state', entry_spill_mask).

    Entries are occupied slots of a peer's table — ``key_entries`` as
    [(values[M], flags[M] int8)], ``partial_entries`` the stored partial
    states, ``row_entries`` the per-entry row counts (0 = empty, skip).
    Same two-probe match-or-claim as the streaming insert, but partial
    states MERGE (count/sum add their accumulators, min/max keep
    extrema) and rows adds the entry counts.  ``table_state`` is donated
    exactly like the streaming kernel's."""
    partial_ops = plan.partial_ops
    key_dtypes = tuple(np.dtype(d) for d in key_dtypes)

    def merge(table_state, key_entries, partial_entries, row_entries):
        key_tables, partials, rows = table_state
        key_tables = list(key_tables)
        row_entries = xp.asarray(row_entries)
        mask = row_entries > 0
        keys = [(xp.asarray(kv).astype(kdt), xp.asarray(kf) == 2)
                for (kv, kf), kdt in zip(key_entries, key_dtypes)]
        keys = _canon_keys(xp, keys)
        h = _fingerprint(xp, keys, row_entries.shape)
        slot, placed, key_tables, _ = _insert_keys(
            xp, keys, mask, h, key_tables, rows > 0)
        spill = mask & ~placed
        outs = []
        for op, prior, p in zip(partial_ops, partials, partial_entries):
            dt = np.dtype(prior.dtype)
            p = xp.asarray(p)
            if op.kind in ("sum", "count"):
                outs.append(prior.at[slot].add(
                    xp.where(placed, p, dt.type(0)).astype(dt)))
            else:
                s_ = dt.type(_sentinel(op.kind, dt))
                upd = xp.where(placed, p, s_).astype(dt)
                outs.append(prior.at[slot].min(upd) if op.kind == "min"
                            else prior.at[slot].max(upd))
        rows = rows.at[slot].add(
            xp.where(placed, row_entries, 0).astype(np.int64))
        return (tuple(key_tables), tuple(outs), rows), spill
    return merge


def empty_hash_state(plan: PhysicalPlan, slots: int, key_dtypes: tuple):
    """Host-built empty table state for the fused kernels: key value
    tables filled with their dtype minimum (neutral under ``.at[].max``
    claims), int8 flag tables at 0 (1 = stored null, 2 = stored valid),
    partial tables at their op's identity/sentinel, rows at 0."""
    S = int(slots)
    key_tables = []
    for kdt in key_dtypes:
        kdt = np.dtype(kdt)
        key_tables.append((np.full((S,), _key_sentinel(kdt), kdt),
                           np.zeros((S,), np.int8)))
    partials = []
    for op in plan.partial_ops:
        dt = np.dtype(op.dtype)
        if op.kind == "count" or op.arg_index < 0:
            partials.append(np.zeros((S,), np.int64))
        elif op.kind == "sum":
            partials.append(np.zeros((S,), dt))
        else:
            partials.append(np.full((S,), dt.type(_sentinel(op.kind, dt)), dt))
    return tuple(key_tables), tuple(partials), np.zeros((S,), np.int64)


def hash_state_bytes(state) -> int:
    """Bytes a table state (``empty_hash_state``'s layout) occupies."""
    key_tables, partials, rows = state
    return (sum(kv.nbytes + kf.nbytes for kv, kf in key_tables)
            + sum(p.nbytes for p in partials) + rows.nbytes)


def merge_hash_tables_into(acc, plan: PhysicalPlan, key_tables, partials, rows,
                           entry_mask=None):
    """Feed a device hash table (or its spilled entries) into a
    HostGroupAccumulator."""
    rows = np.asarray(rows)
    occupied = rows > 0
    if entry_mask is not None:
        occupied = occupied & np.asarray(entry_mask)
    keys = []
    for (kvt, kvalid_t), key in zip(key_tables, plan.bound.group_keys):
        kvt = np.asarray(kvt)
        kvalid = np.asarray(kvalid_t) == 2  # stored flag: valid keys are +1
        keys.append((kvt, kvalid))
    partial_vals = [np.asarray(p) for p in partials]
    acc.merge_partials(occupied, keys, partial_vals, rows)
