"""Device-side hash aggregation for unbounded GROUP BY cardinality.

When the key domain can't be proven small (no direct-gid mode), the
executor aggregates on device into a fixed-size open-addressed hash
table that lives in HBM for the whole scan (one a query, or one a
device of a multi-chip host, each over its own shards: the executor's
``_run_hash_device``): ``build_fused_hash_worker``
composes the whole of a batch's work in a single traced body, so the
executor jits it with ``donate_argnums=0`` (kernel-cache slot
``jit_hash_fused``) and XLA reuses the table buffers in place — one
dispatch per batch, no per-batch tables, no concatenate+re-insert merge
kernels.

A scatter is a serial loop on a TPU (about 120 ns an update on 64-bit
lanes, 20 ns on 32-bit ones), so a batch is aggregated by key BEFORE it
meets the table and the table takes one update per distinct key of the
batch, nothing per padded or repeated row:

1. filter → canonical keys → fingerprint → SORT the row indexes by 31
   fingerprint bits, masked-out rows last, and gather the rows into
   that order;
2. SEGMENT REDUCE: neighbours with exactly equal keys form a segment,
   and a log-step segmented scan (shifts and selects, no scatter) leaves
   every segment's count/sum/min/max on its last row;
3. a second sort brings the D segment ends to the front;
4. CHUNKED MATCH-OR-CLAIM: a loop whose trip count is ceil(D /
   chunk) at run time offers the entries to the table and merges
   their partial states into their slots; the chunk (``entry_chunk``)
   is small where the table's lanes live in the chip's fast memory,
   so that trips x chunk follows D.

Placement is exact, never probabilistic: an entry takes a slot only
when the slot's stored *key values* equal its keys exactly or the slot
is empty, and among the entries of one chunk a scatter race (lowest
entry index wins) gives each slot to one of them, so a slot's partial
state is read, merged and written back without a combining scatter.
Segments are cut on the key values, not on the sort bits, so two keys
that share them stay two entries (and a key whose rows interleave with
another's becomes several entries of one key: the first takes the slot,
the repeats spill).  Each fingerprint gets two candidate slots (a
second-chance probe through a remixed hash); entries that lose both are
returned with their merged partial states and re-aggregated exactly on
the host (HostGroupAccumulator) — the static-shape analog of a hash-agg
spilling to disk.  Occupancy only grows and the probe sequence is
deterministic, so a group keeps matching the slot it first landed in
across batches.

Float keys are canonicalized before fingerprinting and storage
(``-0.0`` → ``0.0``, every NaN payload → the canonical quiet NaN) so
SQL-equal values share one bit pattern; HostGroupAccumulator applies the
same canonicalization to its key bytes, keeping the two paths in one
group space.

The merged table is fixed-shape arrays, which also makes it a wire
value: workers ship (key values, key flags, partial tables, rows) as
CTFR frame columns (net/data_plane.py encode_hash_partials) and the
coordinator re-inserts remote entries through the same match-or-claim
core (``build_fused_entry_merge``, slot ``jit_hash_merge``; a peer's
entries are distinct already and need no sort) — the reference's
two-stage worker_partial_agg / coord_combine_agg seam
(multi_logical_optimizer.c), with O(slots) on the wire instead of
O(rows).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from citus_tpu.observability.trace import kernel_scope
from citus_tpu.planner.bound import _as_mask, compile_expr, predicate_mask
from citus_tpu.planner.aggregates import float_bits
from citus_tpu.planner.physical import PhysicalPlan
from citus_tpu.ops.scan_agg import _sentinel, scan_env_fn

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


def _combine(xp, kind, a, b):
    """Two partial states of a ``kind`` ("sum", "count", "min", "max")
    merged: accumulators add, extrema keep the extreme."""
    if kind in ("min", "max"):
        return xp.minimum(a, b) if kind == "min" else xp.maximum(a, b)
    return a + b


def _set_distinct(xp, table, at, values):
    """``table`` with ``values`` written at the DISTINCT slots ``at``
    (a slot past the end: dropped).  A 64-bit scatter costs a TPU six
    times a 32-bit one (120 ns an update against 20), so an int64 table
    is written as its two 32-bit halves."""
    if table.dtype != np.int64:
        return table.at[at].set(values.astype(table.dtype), mode="drop")
    low = np.int64(0xFFFFFFFF)
    values = values.astype(np.int64)
    lo = (table & low).astype(np.uint32).at[at].set(
        (values & low).astype(np.uint32), mode="drop")
    hi = (table >> 32).astype(np.int32).at[at].set(
        (values >> 32).astype(np.int32), mode="drop")
    return (hi.astype(np.int64) << 32) | lo.astype(np.int64)


def _stores(xp, keys, key_tables, slot, shape):
    """Slot ``slot`` stores exactly each entry's keys (values and
    validity): the test a match-or-claim and a read-only probe share."""
    eq = xp.ones(shape, bool)
    for (kv, kvm), (kvt, kft) in zip(keys, key_tables):
        eq = eq & _stored_eq(xp, kvt, kft, slot, kv, kvm)
    return eq


def _insert_keys(xp, keys, mask, h, key_tables, occ):
    """Two-probe match-or-claim of entries into a RUNNING table, each
    placed entry in a slot of its own.

    keys are canonical (``_canon_keys``); ``occ`` marks slots occupied
    before these entries.  In each probe round an entry whose candidate
    slot stores exactly its key, or is empty, enters a scatter race for
    the slot (lowest entry index wins; int32, the cheap kind of scatter)
    and the winner is placed, writing its key where the slot was empty.
    A loser with another key (it shares the slot, or the fingerprint)
    tries its second slot and spills after that; a loser that finds its
    OWN key there — a key repeated among the entries — spills at once,
    so no key ever sits in two slots and no slot takes two entries of
    one call.  Returns ``(slot, placed, key_tables)`` with the updated
    tables; entries with ``placed`` False must spill to the host.
    """
    S = occ.shape[0]
    none = np.int32(np.iinfo(np.int32).max)
    ids = xp.arange(mask.shape[0], dtype=np.int32)

    def stores(tables, slot):
        return _stores(xp, keys, tables, slot, mask.shape)

    fslot = xp.zeros(mask.shape, np.int32)
    placed = repeated = xp.zeros(mask.shape, bool)
    for hp in (h, _mix(xp, h, _GOLD)):
        want = mask & ~placed & ~repeated
        cand = xp.where(want, (hp % np.uint64(S)).astype(np.int32), 0)
        free = ~occ[cand]
        enter = want & (free | stores(key_tables, cand))
        first = xp.full((S,), none, np.int32).at[cand].min(
            xp.where(enter, ids, none))
        won = enter & (first[cand] == ids)
        at = xp.where(won & free, cand, S)
        key_tables = [
            (_set_distinct(xp, kvt, at, kv),
             _set_distinct(xp, kft, at, kvm.astype(np.int8) + 1))
            for (kv, kvm), (kvt, kft) in zip(keys, key_tables)]
        repeated = repeated | (enter & ~won & stores(key_tables, cand))
        occ = occ | (first != none)
        fslot = xp.where(won, cand, fslot)
        placed = placed | won
    return fslot, placed, key_tables


def _merge_entries(xp, partial_ops, table_state, keys, mask,
                   partial_entries, row_entries, h=None):
    """Match-or-claim ``keys`` (canonical) into the table and MERGE the
    placed entries' partial states into their slots (count/sum add their
    accumulators, min/max keep extrema, rows adds the entry counts):
    read the slot, combine, write it back, since ``_insert_keys`` gives
    each placed entry a slot of its own.  ``h`` are the entries' hashes
    where they are not the keys' fingerprints (a join table's later
    probe levels, ops/join.py).  -> (table_state', placed)."""
    key_tables, partials, rows = table_state
    if h is None:
        h = _fingerprint(xp, keys, mask.shape)
    slot, placed, key_tables = _insert_keys(
        xp, keys, mask, h, list(key_tables), rows > 0)
    at = xp.where(placed, slot, rows.shape[0])
    outs = [_set_distinct(xp, prior, at, _combine(
                xp, op.kind, prior[slot], xp.asarray(p).astype(prior.dtype)))
            for op, prior, p in zip(partial_ops, partials, partial_entries)]
    rows = _set_distinct(xp, rows, at, rows[slot] + row_entries)
    return (tuple(key_tables), tuple(outs), rows), placed


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


def _shift_in(xp, x, d, fill):
    """``x`` moved ``d`` places to the right, ``fill`` shifted in."""
    return xp.concatenate([xp.full((d,), fill, x.dtype), x[:-d]])


def _segment_scan(xp, start, lanes):
    """Inclusive scan of every lane within its segment (``start`` marks
    a segment's first row), so a segment's last row holds its reduction.
    ``lanes`` is [(values, "sum" | "min" | "max")].  Log-step shifts and
    elementwise combines: no scatter, no prefix-sum difference (a float
    sum is a tree of additions within its segment alone), and nothing
    that XLA for TPU is slow to compile (``cumsum`` and
    ``associative_scan`` over a million rows take it a minute each)."""
    vals = [v for v, _ in lanes]
    flag, d = start, 1
    while d < start.shape[0]:
        vals = [xp.where(flag, v, _combine(
                    xp, kind, _shift_in(xp, v, d, _sentinel(kind, v.dtype)),
                    v))
                for v, (_, kind) in zip(vals, lanes)]
        flag = flag | _shift_in(xp, flag, d, True)
        d *= 2
    return vals


#: entries offered to the table per trip of the hash module's offer loop:
#: the static length of a trip's gathers and scatters, which cost the
#: same for an entry slot live or not, so a batch's D distinct keys cost
#: ceil(D / chunk) * chunk of them.  A table whose lanes the chip keeps
#: in fast memory (``FAST_TABLE_SLOTS``, measured on a TPU v5e: PERF.md
#: section 6, PR 51) pays about the same for an entry slot at any chunk,
#: so there the chunk is small and trips x chunk follows D: a join's
#: block of 65,536 rows holding 7,000 groups takes one trip of 8,192,
#: not one of 65,536.  A larger table pays more for an entry slot the
#: smaller the chunk -- at 8,192 half as much again with 8,388,608
#: slots and four times with 16,777,216 (its lanes are worked on in
#: HBM, or moved whole a trip) -- so it keeps ``WIDE_CHUNK``.  The
#: join's own build loops keep a chunk of their own (``ops/join.py``
#: ``BUILD_CHUNK``).
ENTRY_CHUNK = 1 << 13
WIDE_CHUNK = 1 << 16
FAST_TABLE_SLOTS = 1 << 22


def entry_chunk(slots: int, n: int) -> int:
    """Entries a trip of the offer loop offers a table of ``slots``
    slots out of a batch of ``n`` rows: static, from the shapes."""
    return min(ENTRY_CHUNK if slots <= FAST_TABLE_SLOTS else WIDE_CHUNK,
               int(n))


def offer_slots(offered, slots: int, n_pad: int):
    """Entry slots the offer loop ran its gathers and scatters over for
    a batch of ``offered`` entries (an integer or an array of them; the
    table ``slots`` long, the spill arrays ``n_pad``): whole chunks."""
    chunk = entry_chunk(slots, n_pad)
    return -(-offered // chunk) * chunk


def build_fused_hash_worker(plan: PhysicalPlan, xp,
                            key_dtypes: tuple) -> Callable:
    """Fused streaming aggregation of one batch into the table:
    (table_state, cols, valids, row_mask) -> (table_state', spill).

    ``table_state`` is ``(key_tables [(vals[S], flags[S] int8)...],
    partial tables tuple [S], rows[S] int64)`` (see ``empty_hash_state``)
    and is meant to be DONATED: every output array is the matching
    input with some slots rewritten, so XLA reuses the table's HBM
    buffers across batches.  The slot count is read off the
    state shapes, not baked into the closure — one cached kernel serves
    any ``citus.hash_agg_slots`` setting.

    The batch is aggregated by key BEFORE it meets the table (module
    docstring): sort, segment scan, compaction, then the insert loop
    over the batch's D entries.  ``spill`` is ``(D, n_spilled,
    spill_mask, key_entries, partial_entries, row_entries)``: the
    scalars first, then the batch's entries compacted to the front of
    arrays a whole number of chunks long (``entry_chunk``; the layout
    ``merge_hash_tables_into`` reads), of which ``spill_mask`` marks the
    ones that lost both probes: the host fetches the scalars, the mask
    only if ``n_spilled`` is not 0, and of the lanes the marked entries
    alone."""
    from jax import lax

    filter_fn = compile_expr(plan.bound.filter, xp) \
        if plan.bound.filter is not None else None
    key_fns = [compile_expr(k, xp) for k in plan.bound.group_keys]
    arg_fns = [compile_expr(a, xp) for a in plan.agg_args]
    make_env = scan_env_fn(plan)
    partial_ops = plan.partial_ops
    key_dtypes = tuple(np.dtype(d) for d in key_dtypes)
    used_args = sorted({op.arg_index for op in partial_ops
                        if op.arg_index >= 0})

    def same_as_left(v):
        eq = v[1:] == v[:-1]
        if np.issubdtype(v.dtype, np.floating):
            eq = eq | (xp.isnan(v[1:]) & xp.isnan(v[:-1]))
        return xp.concatenate([xp.zeros((1,), bool), eq])

    # named for its kernel slot: the XLA module in a device trace is
    # jit_hash_fused, apart from the scan kernel's jit_fused
    def hash_fused(table_state, cols, valids, row_mask):
        N = row_mask.shape[0]
        with kernel_scope(xp, "hash.keys"):
            env = make_env(cols, valids)
            mask = row_mask
            if filter_fn is not None:
                mask = mask & predicate_mask(xp, filter_fn, env, row_mask)
            h = _fingerprint(
                xp, _eval_keys(xp, key_fns, key_dtypes, env, (N,)), (N,))

        # 1. sort: 31 bits of the fingerprint bring equal keys together
        # and the masked-out rows last.  One uint32 key and the row
        # index: XLA for TPU compiles a sort in 10-15 s PER 32-bit lane
        # (five lanes: 78 s), so the rows follow by gather — the scan
        # columns a key or an argument reads (XLA drops the others), at
        # the width they arrived (an int64 column that rides as int32
        # is ONE gather lane: ``make_env`` widens after the gather),
        # their validity bits packed 31 to a lane — and keys and
        # arguments are evaluated on the rows in that order.
        with kernel_scope(xp, "hash.sort"):
            last = np.uint32(1 << 31)
            order, perm = lax.sort(
                (xp.where(mask, (h >> np.uint64(33)).astype(np.uint32), last),
                 xp.arange(N, dtype=np.int32)), num_keys=1)
            real = order != last
        with kernel_scope(xp, "hash.gather"):
            rowwise = [i for i, v in enumerate(valids) if xp.ndim(v)]
            packs = [sum(valids[i].astype(np.int32) << b
                         for b, i in enumerate(rowwise[at:at + 31]))[perm]
                     for at in range(0, len(rowwise), 31)]
            cols, valids = list(cols), list(valids)
            for j, i in enumerate(rowwise):
                cols[i] = cols[i][perm]
                valids[i] = (packs[j // 31] >> (j % 31)) & 1 == 1
            env = make_env(cols, valids)
            keys = _eval_keys(xp, key_fns, key_dtypes, env, (N,))
            args = {}
            for ai in used_args:
                v, valid = arg_fns[ai](env)
                args[ai] = (xp.broadcast_to(xp.asarray(v), (N,)),
                            _as_mask(xp, valid, real))

        # 2. segments of EQUAL KEYS (values and validity, exactly: two
        # keys that share the 31 bits are two segments, or more where
        # their rows interleave), each reduced onto its last row
        # (masked rows sit last: a real row's left neighbour is real)
        with kernel_scope(xp, "hash.segments"):
            same = xp.concatenate([xp.zeros((1,), bool), real[1:]])
            for kv, kvm in keys:
                same = same & same_as_left(kv) & same_as_left(kvm)
            start = real & ~same
            end = real & ~xp.concatenate([same[1:], xp.zeros((1,), bool)])
            lanes = [(real.astype(np.int32), "sum")]
            for op in partial_ops:
                dt = np.dtype(op.dtype)
                if op.arg_index < 0:
                    continue   # count(*) is the segment's rows
                v, ok = args[op.arg_index]
                ok = real & ok
                if op.kind == "count":
                    lanes.append((ok.astype(np.int32), "sum"))
                elif op.kind == "sum":
                    lanes.append((xp.where(ok, v, 0).astype(dt), "sum"))
                else:
                    lanes.append((xp.where(
                        ok, v, _sentinel(op.kind, dt)).astype(dt), op.kind))
            seg_rows, *seg = _segment_scan(xp, start, lanes)
            seg = iter(seg)
            reduced = [seg_rows if op.arg_index < 0 else next(seg)
                       for op in partial_ops]

        # 3. the D segment ends to the front, in order
        C = entry_chunk(table_state[2].shape[0], N)
        n_pad = -(-N // C) * C
        with kernel_scope(xp, "hash.ends"):
            pos = xp.arange(N, dtype=np.int32)
            ends = lax.sort(xp.where(end, pos, pos + np.int32(N)))
            ends = xp.concatenate([ends, xp.zeros((n_pad - N,), np.int32)])
            D = end.sum(dtype=np.int32)

        # 4. offer them to the table a chunk at a time: the trip count,
        # and so the cost of the scatters, follows D
        def offer(c, carry):
            state, n_spilled, spill, okeys, oparts, orows = carry
            at = c * C
            e = lax.dynamic_slice(ends, (at,), (C,))
            live = at + xp.arange(C, dtype=np.int32) < D
            e = xp.where(live, e, 0)
            ekeys = [(kv[e], kvm[e]) for kv, kvm in keys]
            eparts = [p[e].astype(t.dtype)
                      for p, t in zip(reduced, state[1])]
            erows = xp.where(live, seg_rows[e], 0).astype(np.int64)
            state, placed = _merge_entries(
                xp, partial_ops, state, ekeys, live, eparts, erows)
            lost = live & ~placed
            put = lambda buf, x: lax.dynamic_update_slice(buf, x, (at,))
            return (state, n_spilled + lost.sum(dtype=np.int32),
                    put(spill, lost),
                    [(put(bv, kv), put(bf, kvm.astype(np.int8) + 1))
                     for (bv, bf), (kv, kvm) in zip(okeys, ekeys)],
                    [put(b, p) for b, p in zip(oparts, eparts)],
                    put(orows, erows))

        with kernel_scope(xp, "hash.offer"):
            _, partials, _ = table_state
            empty = lambda dt: xp.zeros((n_pad,), dt)
            state, n_spilled, spill, okeys, oparts, orows = lax.fori_loop(
                0, (D + C - 1) // C, offer,
                (table_state, np.int32(0), empty(bool),
                 [(empty(kdt), empty(np.int8)) for kdt in key_dtypes],
                 [empty(p.dtype) for p in partials], empty(np.int64)))
        return state, (D, n_spilled, spill, tuple(okeys), tuple(oparts),
                       orows)
    return hash_fused


def build_fused_entry_merge(plan: PhysicalPlan, xp,
                            key_dtypes: tuple) -> Callable:
    """Device merge door for remote hash partials:
    (table_state, key_entries, partial_entries, row_entries) ->
    (table_state', entry_spill_mask).

    Entries are occupied slots of a peer's table — ``key_entries`` as
    [(values[M], flags[M] int8)], ``partial_entries`` the stored partial
    states, ``row_entries`` the per-entry row counts (0 = empty, skip).
    A peer's entries are distinct keys already, so they go straight
    through the streaming kernel's ``_merge_entries``.  ``table_state``
    is donated exactly like the streaming kernel's."""
    partial_ops = plan.partial_ops
    key_dtypes = tuple(np.dtype(d) for d in key_dtypes)

    def merge(table_state, key_entries, partial_entries, row_entries):
        row_entries = xp.asarray(row_entries)
        mask = row_entries > 0
        keys = _canon_keys(xp, [
            (xp.asarray(kv).astype(kdt), xp.asarray(kf) == 2)
            for (kv, kf), kdt in zip(key_entries, key_dtypes)])
        state, placed = _merge_entries(
            xp, partial_ops, table_state, keys, mask,
            [xp.asarray(p) for p in partial_entries], row_entries)
        return state, mask & ~placed
    return merge


def _probe_slots(xp, keys, mask, key_tables, occ=None, h=None,
                 crowded=False):
    """Read-only side of ``_insert_keys``: the slot that stores each
    key (canonical), looked for in its two candidate slots, or the slot
    count where neither does (or ``mask`` is off).  Nothing is claimed.
    ``h`` as in ``_merge_entries``.  Without ``occ`` a slot is occupied
    where its first key lane's flag says so (the gather the comparison
    makes anyway: a probe of every row of a join's batch pays for no
    other).  With ``crowded`` -> ``(slot, crowded)``: the keys found in
    neither slot whose two slots are BOTH taken -- where alone an
    insert can have gone on to other slots."""
    S = key_tables[0][1].shape[0] if occ is None else occ.shape[0]
    if h is None:
        h = _fingerprint(xp, keys, mask.shape)
    slot = xp.full(mask.shape, S, np.int32)
    taken = mask
    for hp in (h, _mix(xp, h, _GOLD)):
        cand = (hp % np.uint64(S)).astype(np.int32)
        full = key_tables[0][1][cand] != 0 if occ is None else occ[cand]
        hit = mask & full & _stores(xp, keys, key_tables, cand, mask.shape)
        slot = xp.where(hit & (slot == S), cand, slot)
        taken = taken & full
    return (slot, taken & (slot == S)) if crowded else slot


#: slots per block of the filtered ending: the host reads one bit a
#: block and gathers the blocks that hold a survivor
FILTER_BLOCK = 512


def build_hash_having(plan: PhysicalPlan, xp, having,
                      param_names: tuple) -> Callable:
    """HAVING decided on the table: (table_state, pcols, pvalids,
    host_keys, n_host_keys) -> (keep[S], (block_marks, occupied,
    overflows, host_slots, host_entries)).

    ``having`` is the plan's HAVING with its literals hoisted
    (``param_names`` names the plan's parameters and then the hoisted
    ones, the layout of ``pcols`` / ``pvalids``), so one program serves
    the statement's family.  The aggregates of every occupied entry are
    extracted as ``finalize.extract_aggs`` does and ``keep`` = HAVING
    passes; ``block_marks`` is ``keep`` reduced to one bit per
    ``FILTER_BLOCK`` slots (no scan over the slots: XLA for TPU compiles
    a ``cumsum`` over a million rows in a minute), ``occupied`` counts
    the occupied slots and ``overflows`` the entries that fail
    ``finalize.sum_overflow_mask``, per aggregate that carries a shadow.

    That verdict is final only for a key the host holds no part of.
    ``host_keys`` ([(values[M], valid[M])], the first ``n_host_keys``
    real, in the key tables' dtypes) are the keys the host accumulator
    holds: the read-only probe
    finds their slots (``host_slots``, the slot count = not in the
    table) and their entries come back whatever HAVING says of them
    (``host_entries``, a table state of M entries), so the host can set
    the chip's verdict on them aside — by slot, not by a scatter over
    the table, which XLA for TPU takes 7 s to compile.  The table state
    is read, not donated."""
    from citus_tpu.executor.finalize import plain_agg, sum_overflow_mask
    from citus_tpu.planner.bound import BAggRef, walk

    having_fn = compile_expr(having, xp)
    read = {n.index for n in walk(having) if isinstance(n, BAggRef)}
    extracts = plan.agg_extract

    # named for its kernel slot, like hash_fused: the XLA module in a
    # device trace is jit_hash_having
    def hash_having(table_state, pcols, pvalids, host_keys, n_host_keys):
        key_tables, partials, rows = table_state
        S = rows.shape[0]
        occ = rows > 0
        env = {n: (c, v) for n, c, v in zip(param_names, pcols, pvalids)}
        env["__keys__"] = [(kvt, kft == 2) for kvt, kft in key_tables]
        env["__aggs__"] = [plain_agg(xp, ex, partials) if i in read else None
                           for i, ex in enumerate(extracts)]
        keep = occ & predicate_mask(xp, having_fn, env, rows)
        B = FILTER_BLOCK
        marks = xp.concatenate(
            [keep, xp.zeros((-S % B,), bool)]).reshape(-1, B).any(axis=1)
        bad = [sum_overflow_mask(xp, ex, partials) for ex in extracts]
        overflows = [(b & occ).sum(dtype=np.int32)
                     for b in bad if b is not None]
        live = xp.arange(host_keys[0][0].shape[0],
                         dtype=np.int32) < n_host_keys
        slot = _probe_slots(xp, _canon_keys(xp, host_keys), live,
                            key_tables, occ)
        return keep, (marks, occ.sum(dtype=np.int32), overflows, slot,
                      hash_take(table_state, xp.minimum(slot, S - 1)))
    return hash_having


def build_hash_top(plan: PhysicalPlan, xp, having, order, param_names: tuple,
                   block: int, key_lanes=None) -> Callable:
    """ORDER BY ... LIMIT cut on the table: (table_state, pcols, pvalids,
    host_keys, n_host_keys) -> (winners, candidates, occupied, overflows,
    host_slots, host_entries).

    ``having`` (or None) and ``param_names`` as in ``build_hash_having``;
    ``order`` is the ORDER BY as ``[(final expression, ascending, nulls
    first), ...]`` over the table's keys and its ``finalize.PLAIN_AGGS``
    aggregates in integers; ``key_lanes[i]`` is the table's lane that
    holds the statement's group key ``i`` (None: every key has its own,
    in order; an entry None: a key the table does not hold and no
    expression here reads).  Every occupied entry is a CANDIDATE where
    HAVING passes -- except the entries of the keys the host accumulator
    holds a part of (``host_keys``, probed as ``build_hash_having``
    probes them): theirs is a partial state, which bounds nothing (a sum
    may be negative), so they stand aside from the sort and come home
    whatever they hold (``host_slots``, ``host_entries``) for the host
    to complete.  The candidates are sorted by the ORDER BY's keys --
    PostgreSQL's NULL placement, a descending key as its complement, the
    slot last so that the order is total -- and ``winners`` are the first
    ``block`` of them as a table state of ``block`` entries, of which
    the first min(``candidates``, ``block``) are real.  Whatever the
    whole table and the host's groups would have given as the first
    ``block`` rows is among the winners and the host's groups, each
    complete once merged.  The table state is read, not donated."""
    from jax import lax

    from citus_tpu.executor.finalize import plain_agg, sum_overflow_mask
    from citus_tpu.planner.bound import BAggRef, walk

    having_fn = compile_expr(having, xp) if having is not None else None
    order_fns = [(compile_expr(e, xp), asc, (not asc) if nf is None else nf)
                 for e, asc, nf in order]
    read = {n.index for e in [having] + [e for e, _, _ in order]
            if e is not None for n in walk(e) if isinstance(n, BAggRef)}
    extracts = plan.agg_extract

    # named for its kernel slot, like hash_fused: the XLA module in a
    # device trace is jit_hash_top
    def hash_top(table_state, pcols, pvalids, host_keys, n_host_keys):
        key_tables, partials, rows = table_state
        S = rows.shape[0]
        occ = rows > 0
        env = {n: (c, v) for n, c, v in zip(param_names, pcols, pvalids)}
        lanes = [(kvt, kft == 2) for kvt, kft in key_tables]
        env["__keys__"] = lanes if key_lanes is None else [
            None if i is None else lanes[i] for i in key_lanes]
        env["__aggs__"] = [plain_agg(xp, ex, partials) if i in read else None
                           for i, ex in enumerate(extracts)]
        keep = occ
        if having_fn is not None:
            keep = keep & predicate_mask(xp, having_fn, env, rows)
        live = xp.arange(host_keys[0][0].shape[0],
                         dtype=np.int32) < n_host_keys
        slot = _probe_slots(xp, _canon_keys(xp, host_keys), live,
                            key_tables, occ)
        aside = xp.zeros((S,), bool).at[slot].set(True, mode="drop")
        candidate = keep & ~aside
        operands = []
        for fn, asc, nulls_first in order_fns:
            v, valid = fn(env)
            # a sort lane as wide as the key: XLA for TPU compiles a
            # sort by the 32-bit lane
            v = xp.asarray(v)
            v = xp.broadcast_to(v, (S,)).astype(
                np.int64 if v.dtype.itemsize > 4 else np.int32)
            valid = xp.broadcast_to(_as_mask(xp, valid, v), (S,))
            rank = xp.where(valid, np.int32(nulls_first),
                            np.int32(not nulls_first))
            if not operands:
                # the entries that are no candidate sort behind all
                rank = xp.where(candidate, rank, np.int32(2))
            operands += [rank, xp.where(valid, v if asc else ~v, 0)]
        operands.append(xp.arange(S, dtype=np.int32))
        at = lax.sort(tuple(operands), num_keys=len(operands))[-1][:block]
        bad = [sum_overflow_mask(xp, ex, partials) for ex in extracts]
        overflows = [(b & occ).sum(dtype=np.int32)
                     for b in bad if b is not None]
        return (hash_take(table_state, at), candidate.sum(dtype=np.int32),
                occ.sum(dtype=np.int32), overflows, slot,
                hash_take(table_state, xp.minimum(slot, S - 1)))
    return hash_top


def hash_take(tree, at):
    """The entries ``at`` of every array of ``tree`` (a table state, a
    mask over its slots): the gather of the filtered ending, one
    dispatch (kernel slot ``jit_hash_take``) for the whole tree."""
    import jax
    return jax.tree_util.tree_map(lambda a: a[at], tree)


def empty_hash_state(plan: PhysicalPlan, slots: int, key_dtypes: tuple,
                     xp=np, tables: int = 0):
    """Empty table state for the fused kernels: key value
    tables filled with their dtype minimum (neutral under ``.at[].max``
    claims), int8 flag tables at 0 (1 = stored null, 2 = stored valid),
    partial tables at their op's identity/sentinel, rows at 0.  Built
    on the host by default; with ``xp`` = jax.numpy inside a jitted
    function it is filled where its output lives.  ``tables`` > 0 gives
    every array a leading axis of that many tables (one a device)."""
    shape = (int(tables), int(slots)) if tables else (int(slots),)
    key_tables = []
    for kdt in key_dtypes:
        kdt = np.dtype(kdt)
        key_tables.append((xp.full(shape, _key_sentinel(kdt), kdt),
                           xp.zeros(shape, np.int8)))
    partials = []
    for op in plan.partial_ops:
        dt = np.dtype(op.dtype)
        if op.kind == "count" or op.arg_index < 0:
            partials.append(xp.zeros(shape, np.int64))
        elif op.kind == "sum":
            partials.append(xp.zeros(shape, dt))
        else:
            partials.append(xp.full(shape, dt.type(_sentinel(op.kind, dt)), dt))
    return tuple(key_tables), tuple(partials), xp.zeros(shape, np.int64)


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
