"""Device-side many-to-one equi-join: build once, probe every batch.

A join the device runs is a TREE of relations rooted at the one that is
streamed and probed (``planner/join_planner.py`` ``plan_device_join``:
the largest distributed relation).  Every other relation is a *build*
node: its rows that pass its own filter and find a partner in each of
its own children go into a device-resident lookup table keyed by the
edge to its parent, carrying the payload columns the root needs of it
and of what lies below it.  The root's batches then probe its
children's tables, and the rows that matched and passed every filter
are PACKED into a fixed-capacity block -- the only rows the aggregate
kernel ever sees.  A join GRAPH with a cycle is that tree and the
equalities of the edges off it (*cycle filters*): both sides of one
ride up as payload of their builds, and the root decides it over the
block with its other cross-relation conjuncts (scope ``probe.filter``).

The table is the aggregation's (``ops/hash_agg.py``): the state layout
``(key_tables, lane tables, rows)`` of ``empty_hash_state``, filled
through ``_merge_entries`` / ``_insert_keys`` and read through
``_probe_slots`` / ``_stores`` -- open addressing on the key lanes
(int64 each: integers, dates, decimals' scaled integers), exact, never
probabilistic.  A join cannot spill an entry to the host as a GROUP BY
can, so an entry that loses both slots of its pair goes on to the
next of ``JOIN_LEVELS`` pairs (the hash remixed); a probe looks into
the first pair for every row its lookup is handed -- two gathers a
lane -- and into the later pairs only for the few rows whose first
pair is taken twice over by other keys, after packing.  The join is
many-to-one: a slot's ``rows`` counts the build rows that claimed it,
and the build counts keys that came twice and entries no pair would
take (``COUNTS``); either sends the statement to the host path.  NULL
keys never match: they are masked out on both sides.

Packing is one sort of the row positions (a scatter is a serial loop
on a TPU and ``cumsum`` over a million rows takes XLA for TPU a minute
to compile: PERF.md section 6).

The probe has ONE path and its cost follows the rows the probe
relation's own filter keeps (``build_join_probe``).  A gather on this
chip costs by the index, whatever it hits -- 24-29 ns an element, be it
``index[key - min]`` of a 240 MB table or a column of the batch -- so
the only way to pay less is to gather for fewer rows: the kept rows are
packed WITH what their lookup reads (``_Prefix.probe_lanes``, carried
as further operands of the packing sort: 5.6 ms for a batch of 4 M
rows where the gather is 99), and a loop looks the first K packed rows
up, a ``lookup_chunk`` a trip.  No second order, no setting: K is what
the kernel sees in its batch.  And a batch is looked up ONCE, whatever
the rounds its block takes: round 0 returns, beside its block, what it
worked out -- each child's slots, the packing of the rows that came
through (the *carry*, ``_Block``) -- and a further round is a second,
small kernel (``build_join_probe_round``) that cuts its block from the
carry: no lookup loop, no sort, a block's rows of gathers.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import numpy as np

from citus_tpu.ops.hash_agg import (
    _fingerprint, _merge_entries, _mix, _probe_slots, empty_hash_state,
)
from citus_tpu.observability.trace import kernel_scope
from citus_tpu.planner.bound import (
    BExpr, _as_mask, compile_expr, predicate_mask,
)

#: pairs of candidate slots a build may try for an entry
JOIN_LEVELS = 5
_LEVEL_SALT = (None, np.uint64(0xD6E8FEB86659FD93),
               np.uint64(0xA0761D6478BD642F), np.uint64(0xE7037ED1A0B428DB),
               np.uint64(0x8EBC6AF09C88C6E3))
#: rows a build offers its table per trip of its loop (the exchange's
#: build step too), and the room a direct table's lanes keep past their
#: rows.  The hash module's chunk is smaller over a small table
#: (``ops/hash_agg.py`` ``entry_chunk``), where its block holds a
#: fraction of its rows as groups; a build offers every row it keeps,
#: its loops' scopes have no measurement yet, so this one stays
BUILD_CHUNK = 1 << 16
#: slots a build row of a table: at a load of an eighth or less an entry
#: finds all ``2 x JOIN_LEVELS`` of its slots taken once in 10^9
SLOTS_PER_ROW = 8

#: what a build counts, in the int32 vector that rides with its table
BUILT, LATER_LEVEL, REPEATED, UNPLACED, PACKED_ROWS = range(5)
COUNTS = 5

#: what a probe round counts: rows packed (candidates for the block,
#: over all rounds), looked-up rows with a partner in every child's first
#: pair, rows handed on, rows looked up (those the node's own filter
#: kept; its bucket where it has none), rows of the block the
#: cross-relation conjuncts saw (``OUT`` of them passed).  A batch is
#: looked up in its round 0 alone: a further round counts 0 ``LOOKED``
#: and 0 ``MATCHED``
PACKED, MATCHED, OUT, LOOKED, SEEN = range(5)
N_PROBE_COUNTS = 5

#: what an exchange round counts: rows this device sent, rows it
#: received, rows of its batch that no round has taken yet
SENT, RECEIVED, LEFT = range(3)
#: room a (source, destination) block of an exchange has over the even
#: share of its batch, as a fraction: the catalog's hash spreads a
#: batch's keys evenly, and what a block cannot hold takes a further
#: round
EXCHANGE_MARGIN = (5, 4)


class _Lane(NamedTuple):
    """A payload lane of a join table, in the terms of a partial state:
    a unique key's lane is 0 + its value."""
    dtype: str
    kind: str = "sum"
    arg_index: int = 0


class _Lanes(NamedTuple):
    partial_ops: tuple


@dataclass(frozen=True)
class ChildProbe:
    """How a node's rows meet one of its children's tables."""
    alias: str
    keys: tuple            # BExpr over the node's own columns, a lane each
    payload: tuple         # ((env name, dtype), ...) the child's table carries
    kind: str = "hash"     # the child's table: "hash" | "direct"


@dataclass(frozen=True)
class JoinNode:
    """One relation of the join tree, as its kernel sees it."""
    alias: str
    names: tuple                   # env names of its scan columns
    filter: Optional[BExpr]        # its own conjuncts, literals hoisted
    children: tuple                # ChildProbe, ...
    key: tuple = ()                # the edge to its parent (() = the root)
    payload: tuple = ()            # ((env name, dtype), ...) it hands up
    post_filter: Optional[BExpr] = None    # the root's: cross-relation conjuncts
    out: tuple = ()                # the root's: env names of its block
    kind: str = "hash"             # a build node's own table


def lane_dtype(dt) -> np.dtype:
    dt = np.dtype(dt)
    return np.dtype(np.int8) if dt == np.dtype(bool) else dt


def payload_lanes(payload: tuple) -> _Lanes:
    """A value lane and a validity lane (int8) per payload column."""
    ops = []
    for _, dt in payload:
        ops += [_Lane(str(lane_dtype(dt))), _Lane("int8")]
    return _Lanes(tuple(ops))


def empty_join_table(node: JoinNode, slots: int, xp=np, rows: int = 0,
                     lo=0):
    """An empty table for build node ``node`` and the zeroed counts that
    ride with it.  ``hash``: ``slots`` slots of the aggregation's state
    layout.  ``direct``: ``(index, lanes, lo)`` -- ``index[key - lo]``
    is 1 + the build row's place in the payload lanes (0: no such key),
    ``slots`` the keys' span, ``rows`` the lanes' capacity.  Either
    way ``table[0][1]`` are the payload lanes and a row's *slot* is its
    place in them, the table's ``_span`` where it has none."""
    if node.kind == "direct":
        lanes = tuple(xp.zeros((rows,), np.dtype(op.dtype))
                      for op in payload_lanes(node.payload).partial_ops)
        state = (xp.zeros((slots,), np.int32), lanes,
                 xp.asarray(lo, np.int64))
    else:
        state = empty_hash_state(payload_lanes(node.payload), slots,
                                 (np.int64,) * len(node.key), xp)
    return state, xp.zeros((COUNTS,), np.int32)


def _span(kind: str, state) -> int:
    """What a row's slot reads where it found no partner."""
    return state[0].shape[0] if kind == "direct" else state[2].shape[0]


def _direct_at(xp, state, keys, mask):
    """-> (place in ``index`` of each key, the keys inside its span)."""
    index, _, lo = state
    (kv, _), = keys
    k = kv - lo
    ok = mask & (k >= 0) & (k < index.shape[0])
    return xp.where(ok, k, 0).astype(np.int32), ok


def block_capacity(n: int, block_rows: Optional[int] = None) -> int:
    """Rows of the block a probe round of a batch of ``n`` rows hands
    on: ``block_rows`` where given, else 1/64 of the bucket (a join
    that keeps more of its probe rows takes further rounds)."""
    return block_rows or min(n, max(1024, n // 64))


def exchange_capacity(n: int, n_dev: int,
                      block_rows: Optional[int] = None) -> int:
    """Rows of one (source, destination) block of an exchange round of
    a batch of ``n`` rows between ``n_dev`` devices: ``block_rows``
    where given, else the even share and ``EXCHANGE_MARGIN`` of it, to
    the next 1,024."""
    if block_rows:
        return block_rows
    num, den = EXCHANGE_MARGIN
    share = -(-n * num // (den * n_dev))
    return min(n, -(-share // 1024) * 1024)


def _level_hash(xp, h, level):
    """The hash an entry's pair of slots at ``level`` >= 1 comes from
    (``level`` may be traced: the later levels are one loop)."""
    return _mix(xp, h, xp.asarray(np.array(_LEVEL_SALT[1:], np.uint64))[
        level - 1])


def _lane_keys(xp, lane):
    """A hash child's probe lane (validity, key lanes ...) as the key
    pairs ``_probe_slots`` reads; a direct child's lane has none."""
    return [(kv, xp.ones(kv.shape, bool)) for kv in lane[1:]]


def _key_lanes(xp, key_fns, env, shape):
    """-> ([(int64 values, all-true validity)], every lane valid)."""
    ok = xp.ones(shape, bool)
    vals = []
    for fn in key_fns:
        v, valid = fn(env)
        v = xp.broadcast_to(xp.asarray(v).astype(np.int64), shape)
        ok = ok & xp.broadcast_to(_as_mask(xp, valid, v), shape)
        vals.append(v)
    ones = xp.ones(shape, bool)
    return [(xp.where(ok, v, 0), ones) for v in vals], ok


def _marked(xp, mask):
    """Each row's position where ``mask`` is on, past the batch's length
    where it is off."""
    n = mask.shape[0]
    pos = xp.arange(n, dtype=np.int32)
    return xp.where(mask, pos, pos + np.int32(n))


def _pack_with(xp, mask, lanes=()):
    """-> (the positions where ``mask`` is on, in order, then the others
    past the batch's length; ``lanes`` in that order): ONE sort whose
    key is the position and whose further operands are the lanes, so
    what a packed row carries is not fetched by a gather a row."""
    from jax import lax
    order, *lanes = lax.sort((_marked(xp, mask), *lanes), num_keys=1)
    return order, lanes


def _pack(xp, mask, width: int):
    """The positions where ``mask`` is on, in order, then the others:
    padded to a whole number of ``width``; and their count."""
    n = mask.shape[0]
    order, _ = _pack_with(xp, mask)
    order = xp.where(order >= n, 0, order)
    pad = -n % width
    if pad:
        order = xp.concatenate([order, xp.zeros((pad,), np.int32)])
    return order, mask.sum(dtype=np.int32)


class _Prefix:
    """What the build and the probe kernels share: a batch's env, the
    node's own filter, and its rows' way through the children's tables
    -- what a row's lookup reads (``probe_lanes``), the first pair of
    slots for the rows handed to ``look_up``, the later pairs for the
    packed rows that may need them."""

    def __init__(self, node: JoinNode, param_names: tuple, xp):
        self.node, self.xp = node, xp
        self.names = node.names + tuple(param_names)
        self.filter_fn = compile_expr(node.filter, xp) \
            if node.filter is not None else None
        self.child_key_fns = [[compile_expr(k, xp) for k in ch.keys]
                              for ch in node.children]

    def env(self, cols, valids):
        return {n: (c, v) for n, c, v in zip(self.names, cols, valids)}

    def own_filter(self, env, row_mask):
        if self.filter_fn is None:
            return row_mask
        return row_mask & predicate_mask(self.xp, self.filter_fn, env,
                                         row_mask)

    def probe_lanes(self, env, mask, child_tables):
        """What each child's lookup reads of a row, elementwise over the
        batch: for a ``direct`` child ONE int32 lane, 1 + the key's
        place in ``index`` (0 where ``mask`` is off, the key is NULL or
        lies outside the span); for a ``hash`` child the joint validity
        of its keys (int8) and then its int64 key lanes."""
        xp = self.xp
        lanes = []
        for ch, fns, (state, _) in zip(
                self.node.children, self.child_key_fns, child_tables):
            keys, ok = _key_lanes(xp, fns, env, mask.shape)
            if ch.kind == "direct":
                at, ok = _direct_at(xp, state, keys, mask & ok)
                lanes.append((xp.where(ok, at + np.int32(1), 0),))
            else:
                lanes.append(((mask & ok).astype(np.int8),)
                             + tuple(kv for kv, _ in keys))
        return lanes

    def look_up(self, lanes, mask, child_tables):
        """The first pair of slots for the rows whose ``probe_lanes``
        are ``lanes`` -> (rows that found a partner in every child or
        may yet, rows that found one in the first pair, per child
        (slot, keys))."""
        xp = self.xp
        found_all = mask
        through = mask
        probes = []
        for ch, lane, (state, counts) in zip(
                self.node.children, lanes, child_tables):
            if ch.kind == "direct":
                # one gather a row: the key is the address
                at1, = lane
                place = state[0][xp.maximum(at1 - np.int32(1), 0)]
                found = mask & (at1 > 0) & (place > 0)
                slot = xp.where(found, place - 1, _span(ch.kind, state))
                may = found
            else:
                slot, crowded = _probe_slots(
                    xp, _lane_keys(xp, lane), mask & (lane[0] != 0),
                    state[0], crowded=True)
                found = slot < _span(ch.kind, state)
                may = found | (crowded & (counts[LATER_LEVEL] > 0))
            found_all = found_all & found
            through = through & may
            probes.append((slot, _lane_keys(xp, lane)))
        return through, found_all, probes

    def first_pair(self, env, mask, child_tables):
        """``look_up`` of every row of an unpacked batch."""
        return self.look_up(self.probe_lanes(env, mask, child_tables), mask,
                            child_tables)

    def later_pairs(self, at, live, probes, child_tables):
        """For the packed rows ``at``: the slot of each child's partner
        (looked for in the later pairs where the first held none) and
        the rows that have one in every child."""
        return self.partners(
            live, [(slot[at], [(kv[at], kvm[at]) for kv, kvm in keys])
                   for slot, keys in probes], child_tables)

    def partners(self, live, found, child_tables):
        """``later_pairs`` of rows that bring, per child, their own
        ``(first pair's slot, keys)``."""
        from jax import lax
        xp = self.xp
        slots = []
        for ch, (slot, keys), (state, _) in zip(
                self.node.children, found, child_tables):
            S = _span(ch.kind, state)
            if ch.kind == "direct":
                live = live & (slot < S)
                slots.append(slot)
                continue
            h = _fingerprint(xp, keys, live.shape)

            def look(level, slot, keys=keys, h=h, state=state, S=S):
                need = live & (slot == S)
                return lax.cond(
                    need.any(),
                    lambda slot: xp.minimum(slot, _probe_slots(
                        xp, keys, need, state[0],
                        h=_level_hash(xp, h, level))),
                    lambda slot: slot, slot)

            slot = lax.fori_loop(1, JOIN_LEVELS, look, slot)
            live = live & (slot < S)
            slots.append(xp.minimum(slot, S - 1))
        return live, slots

    def child_payloads(self, env, slots, child_tables):
        """The children's payload columns of the packed rows, into
        ``env`` under their names."""
        for ch, slot, (state, _) in zip(self.node.children, slots,
                                        child_tables):
            lanes = state[1]
            for i, (name, dt) in enumerate(ch.payload):
                v = lanes[2 * i][slot]
                if np.dtype(dt) == np.dtype(bool):
                    v = v != 0
                env[name] = (v, lanes[2 * i + 1][slot] != 0)
        return env


def build_join_build(node: JoinNode, param_names: tuple, xp) -> Callable:
    """The build step of node ``node``: (table, child_tables, cols,
    valids, row_mask) -> table', ``table`` DONATED.  The batch's rows
    that pass the node's filter, hold no NULL key and have a partner in
    every child are packed to the front and offered to the table a
    chunk at a time (the trip count follows their number), each with
    its payload lanes: the node's own columns and its children's."""
    from jax import lax

    pre = _Prefix(node, param_names, xp)
    key_fns = [compile_expr(k, xp) for k in node.key]
    lanes = payload_lanes(node.payload)
    own = set(node.names)

    # named for its kernel slot: the XLA module in a device trace is
    # jit_join_build
    def join_build(table, child_tables, cols, valids, row_mask):
        N = row_mask.shape[0]
        C = min(BUILD_CHUNK, N)
        with kernel_scope(xp, "build.keys"):
            env = pre.env(cols, valids)
            mask = pre.own_filter(env, row_mask)
            keys, ok = _key_lanes(xp, key_fns, env, (N,))
            through, _, probes = pre.first_pair(env, mask & ok, child_tables)
            order, D = _pack(xp, through, C)

        def offer(c, carry):
            state, counts = carry
            S = _span(node.kind, state)
            at = lax.dynamic_slice(order, (c * C,), (C,))
            live = c * C + xp.arange(C, dtype=np.int32) < D
            live, slots = pre.later_pairs(at, live, probes, child_tables)
            take = lambda a: a[at] if xp.ndim(a) else a
            packed = {n: (take(env[n][0]), take(env[n][1]))
                      for n, _ in node.payload if n in own}
            packed = pre.child_payloads(packed, slots, child_tables)
            entries = []
            for name, dt in node.payload:
                v, m = packed[name]
                v = xp.broadcast_to(xp.asarray(v), (C,))
                m = xp.broadcast_to(_as_mask(xp, m, v), (C,))
                entries += [xp.where(m, v, 0).astype(lane_dtype(dt)),
                            m.astype(np.int8)]
            ekeys = [(kv[at], kvm[at]) for kv, kvm in keys]
            if node.kind == "direct":
                return direct(c, state, counts, ekeys, live, entries)
            h = _fingerprint(xp, ekeys, (C,))
            one = xp.ones((C,), np.int64)

            def place(state, lost, hl):
                state, placed = _merge_entries(
                    xp, lanes.partial_ops, state, ekeys, lost, entries, one,
                    h=hl)
                lost = lost & ~placed
                # a key met twice among these entries: the loser finds
                # its own key stored
                again = lost & (_probe_slots(
                    xp, ekeys, lost, state[0], h=hl) < S)
                return state, lost & ~again, again.sum(dtype=np.int32)

            def later(level, carry):
                state, lost, repeated = carry
                state, lost, again = lax.cond(
                    lost.any(),
                    lambda a: place(*a, _level_hash(xp, h, level)),
                    lambda a: (*a, np.int32(0)), (state, lost))
                return state, lost, repeated + again

            tally = [live.sum(dtype=np.int32)] + [np.int32(0)] * (COUNTS - 1)
            state, lost, repeated = place(state, live, h)
            tally[LATER_LEVEL] = lost.sum(dtype=np.int32)
            state, lost, tally[REPEATED] = lax.fori_loop(
                1, JOIN_LEVELS, later, (state, lost, repeated))
            tally[UNPLACED] = lost.sum(dtype=np.int32)
            return state, counts + xp.stack(tally)

        def direct(c, state, counts, ekeys, live, entries):
            """A chunk into a direct-address table: the rows' payload
            goes to the lanes where the chunk stands among the packed
            rows (contiguous: no scatter), their places to ``index`` at
            their keys; a key that is there already, or that another
            row of the chunk took, is a key met twice, and one outside
            the span the statistics promised is refused."""
            index, lane_tables, lo = state
            at_k, ok = _direct_at(xp, state, ekeys, live)
            first = counts[PACKED_ROWS] + c * C
            place = first + xp.arange(C, dtype=np.int32) + 1
            again = ok & (index[at_k] != 0)
            new = ok & ~again
            index = index.at[xp.where(new, at_k, index.shape[0])].set(
                place, mode="drop")
            lost = new & (index[at_k] != place)
            lane_tables = tuple(
                lax.dynamic_update_slice(t, e.astype(t.dtype), (first,))
                for t, e in zip(lane_tables, entries))
            tally = [np.int32(0)] * COUNTS
            tally[BUILT] = (new & ~lost).sum(dtype=np.int32)
            tally[REPEATED] = (again | lost).sum(dtype=np.int32)
            tally[UNPLACED] = (live & ~ok).sum(dtype=np.int32)
            return (index, lane_tables, lo), counts + xp.stack(tally)

        with kernel_scope(xp, "build.insert"):
            state, counts = lax.fori_loop(0, (D + C - 1) // C, offer, table)
            return state, counts.at[PACKED_ROWS].add(D)
    return join_build


def join_table_verdict(xp, table):
    """-> int32 [COUNTS + 1]: a build's counts and the slots more than
    one build row claimed (a key met twice across chunks or batches)."""
    state, counts = table
    twice = (state[2] > 1).sum(dtype=np.int32) if len(state[2].shape) \
        else xp.zeros((), np.int32)      # a direct table counts as it builds
    return xp.concatenate([counts, twice[None]])


def build_join_exchange(node: JoinNode, param_names: tuple, xp, n_dev: int,
                        hash_lane: int,
                        block_rows: Optional[int] = None) -> Callable:
    """The exchange step of build node ``node``, one device's body under
    ``shard_map``: (table, child_tables, bounds, cols, valids, row_mask,
    round) -> (table', counts), ``table`` DONATED.  The rows of the
    device's batch that pass the node's filter and hold no NULL key go
    to the device that owns the shard of the PROBE relation their key
    hashes to, by the catalog's own map -- ``catalog/hashing.py``
    ``hash_int64`` of key lane ``hash_lane``, and ``bounds``: the least
    hash of each device's shards (``TableMeta.route_hashes`` over the
    shard-to-device map of the placement) -- through
    ``parallel/shuffle.py`` ``exchange_rows``: one ``all_to_all`` a
    lane, ``exchange_capacity`` rows a (source, destination) block,
    round ``round`` of them.  What the device receives is a batch
    whose row mask is the received validity, and goes into its table by
    the build's own step (``build_join_build``, not a copy of it).
    ``counts``: ``SENT`` / ``RECEIVED`` / ``LEFT`` -- ``LEFT`` > 0 on
    any device asks for another round."""
    from citus_tpu.catalog.hashing import hash_int64
    from citus_tpu.parallel.shuffle import exchange_rows

    pre = _Prefix(node, param_names, xp)
    key_fns = [compile_expr(k, xp) for k in node.key]
    build = build_join_build(node, param_names, xp)
    n_own = len(node.names)

    # named for its kernel slot: the XLA module in a device trace is
    # jit_join_exchange
    def join_exchange(table, child_tables, bounds, cols, valids, row_mask,
                      rnd):
        N = row_mask.shape[0]
        with kernel_scope(xp, "exchange.target"):
            env = pre.env(cols, valids)
            mask = pre.own_filter(env, row_mask)
            keys, ok = _key_lanes(xp, key_fns, env, (N,))
            h = hash_int64(keys[hash_lane][0], xp)
            target = (h[:, None] >= bounds[None, 1:]).sum(
                axis=1, dtype=np.int32)
            whole = lambda a, dt: xp.broadcast_to(xp.asarray(a, dt), (N,))
            lanes = tuple(whole(c, c.dtype) for c in cols[:n_own]) \
                + tuple(whole(v, bool) for v in valids[:n_own])
        # (the exchange and the build step name their own steps)
        received, rvalid, counts = exchange_rows(
            lanes, target, mask & ok, n_dev,
            exchange_capacity(N, n_dev, block_rows), rnd)
        table = build(table, child_tables,
                      received[:n_own] + tuple(cols[n_own:]),
                      received[n_own:] + tuple(valids[n_own:]), rvalid)
        return table, counts
    return join_exchange


def build_join_lookup(node: JoinNode, xp) -> Callable:
    """The lookup of a block of keys in build tables that stand after
    the probe: (child_tables, cols, valids, mask) -> (payload cols,
    payload valids, found).  ``node`` has no relation behind it: its
    ``names`` are the lanes of the block (the key lanes a GROUP BY kept)
    and each of its ``children`` a resident table, met by the probe's
    own way through it -- the first pair of slots, the later pairs for
    the rows that need them, the payload gathered by slot -- so a row
    finds here what a probed row of the same key found.  The payload
    comes back child after child in the order of ``ChildProbe.payload``;
    ``found`` marks the rows with a partner in every child."""
    pre = _Prefix(node, (), xp)

    # named for its kernel slot: the XLA module in a device trace is
    # jit_join_lookup
    def join_lookup(child_tables, cols, valids, mask):
        env = pre.env(cols, valids)
        through, _, probes = pre.first_pair(env, mask, child_tables)
        at = xp.arange(mask.shape[0], dtype=np.int32)
        found, slots = pre.later_pairs(at, through, probes, child_tables)
        got = pre.child_payloads({}, slots, child_tables)
        names = [n for ch in node.children for n, _ in ch.payload]
        return (tuple(got[n][0] for n in names),
                tuple(got[n][1] for n in names), found)
    return join_lookup


def lookup_chunk(n: int) -> int:
    """Rows a trip of the probe's lookup loop gathers for, of a batch
    of ``n`` rows: the smallest of the bucket's halvings down to 1/64
    that holds 1,024 rows and divides it (a small bucket is one
    chunk)."""
    for share in (64, 32, 16, 8, 4, 2):
        if n % share == 0 and n // share >= 1024:
            return n // share
    return n


class _Block:
    """What the probe's two kernels share: how a round's block is cut
    from what round 0 of its batch worked out -- the *carry*
    ``(slots, again, order, D)``: per child the slot each packed row's
    lookup found (``[N]`` int32), the packed places of the rows that
    came through, marked (``_marked``: a place past ``N`` is no row;
    padded to whole blocks), the batch positions of the packed rows
    (marked too; ``()`` for a node without a filter, whose batch is its
    own packing) and the count of the rows that came through."""

    def __init__(self, node: JoinNode, param_names: tuple, xp,
                 block_rows: Optional[int]):
        self.node, self.xp, self.block_rows = node, xp, block_rows
        self.pre = _Prefix(node, param_names, xp)
        self.post_fn = compile_expr(node.post_filter, xp) \
            if node.post_filter is not None else None
        self.params = tuple(param_names)
        #: the kept rows are packed by a sort (else the batch stands)
        self.sorts = self.pre.filter_fn is not None

    def cut(self, child_tables, env, row_mask, carry, rnd):
        """Round ``rnd``'s block of a batch -> (block cols, block
        valids, block mask, counts): ``C`` gathers a column and no
        more, whatever the batch."""
        from jax import lax
        xp, pre, node = self.xp, self.pre, self.node
        slots, again, order, D = carry
        N = row_mask.shape[0]
        C = block_capacity(N, self.block_rows)
        with kernel_scope(xp, "probe.block"):
            among = lax.dynamic_slice(again, (rnd * C,), (C,))
            live = among < N
            among = xp.where(live, among, 0)
            # the rows' places in the batch
            at = order[among] if self.sorts \
                else xp.where(row_mask[among], among, N)
            at = xp.where(at >= N, 0, at)
        with kernel_scope(xp, "probe.payload"):
            take = lambda a: a[at] if xp.ndim(a) else a
            block = {n: (take(v), take(m))
                     for n, (v, m) in env.items() if n not in self.params}
            penv = dict(block)
            penv.update({n: env[n] for n in self.params})
            # a hash child's keys of the block's rows, for its later
            # pairs: from the rows' own columns, as ``probe_lanes``
            # makes them over a batch
            found = [(slot[among],
                      [] if ch.kind == "direct"
                      else _key_lanes(xp, fns, penv, (C,))[0])
                     for ch, fns, slot in zip(
                         node.children, pre.child_key_fns, slots)]
            live, slots = pre.partners(live, found, child_tables)
            block = pre.child_payloads(block, slots, child_tables)
            seen = live.sum(dtype=np.int32)
        if self.post_fn is not None:
            # the cross-relation conjuncts, a cycle filter among them
            with kernel_scope(xp, "probe.filter"):
                penv.update(block)
                live = live & predicate_mask(xp, self.post_fn, penv, live)
        with kernel_scope(xp, "probe.payload"):
            out_cols, out_valids = [], []
            for name in node.out:
                v, m = block[name]
                v = xp.broadcast_to(xp.asarray(v), (C,))
                out_cols.append(v)
                out_valids.append(xp.broadcast_to(_as_mask(xp, m, v), (C,)))
            counts = [np.int32(0)] * N_PROBE_COUNTS
            counts[PACKED], counts[SEEN] = D, seen
            counts[OUT] = live.sum(dtype=np.int32)
        return tuple(out_cols), tuple(out_valids), live, counts


def build_join_probe(node: JoinNode, param_names: tuple, xp,
                     block_rows: Optional[int] = None) -> Callable:
    """The probe step of the root ``node``, round 0 of a batch:
    (child_tables, cols, valids, row_mask) -> (block cols, block valids,
    block mask, counts, carry).  The rows that pass the node's filter
    and have a partner in every child are packed, and round ``r`` hands
    on the ``r``-th ``block_rows`` of them (a power of two from the
    batch's bucket where not given) with the columns ``node.out`` names
    -- the node's own and the payload gathered from the tables -- and
    the cross-relation conjuncts decided.  This kernel hands on the
    first block and the *carry* (``_Block``) that every further round's
    kernel cuts its block from (``build_join_probe_round``).
    ``counts`` (``PACKED`` / ``MATCHED`` / ``OUT`` / ``LOOKED`` /
    ``SEEN``) say whether another round is due: ``PACKED`` >
    (r + 1) x ``block_rows``.

    One path, whose cost follows the K rows the node's own filter
    keeps, which the kernel sees in its batch: (1) the filter and every
    child's ``probe_lanes`` over the whole batch, elementwise; (2) ONE
    sort packs the kept rows' positions to the front and carries the
    lanes as further operands, so a kept row's key is never fetched by
    a gather of its own; (3) a loop of ceil(K / ``lookup_chunk``)
    trips looks the first K packed rows up -- a slice of the lanes,
    ``look_up``'s gathers, a slice of the slots written back; (4) the
    rows that came through are packed again (where K fits the block its
    first rows ARE the block: no second sort) and the block is cut: the
    later pairs, the payload and the block's own columns are gathered
    for the block's rows alone, by slot and by original position, and
    the cross-relation conjuncts (the statement's and a join graph's
    cycle filters: ``SEEN`` rows of the block reach them, ``OUT`` pass)
    are decided over the block, scope ``probe.filter``.  A node without
    a filter keeps its whole bucket: the batch is its own packing and
    nothing is sorted.  ``LOOKED`` counts K (the bucket where nothing is
    filtered; the gathers issued are K to the chunk), ``MATCHED`` the
    looked-up rows with a partner in every child's first pair: once a
    batch, whatever the rounds -- a later round looks nothing up."""
    from jax import lax

    blk = _Block(node, param_names, xp, block_rows)
    pre = blk.pre

    # named for its kernel slot: the XLA module in a device trace is
    # jit_join_probe
    def join_probe(child_tables, cols, valids, row_mask):
        N = row_mask.shape[0]
        C = block_capacity(N, block_rows)
        CH = lookup_chunk(N)
        with kernel_scope(xp, "probe.lanes"):
            env = pre.env(cols, valids)
            own = pre.own_filter(env, row_mask)
            lanes = pre.probe_lanes(env, own, child_tables)
        with kernel_scope(xp, "probe.pack"):
            if blk.sorts:
                order, flat = _pack_with(xp, own,
                                         [a for lane in lanes for a in lane])
                flat = iter(flat)
                lanes = [tuple(next(flat) for _ in lane) for lane in lanes]
                K = own.sum(dtype=np.int32)
            else:
                # a node without a filter keeps its bucket: nothing to pack
                order, K = _marked(xp, own), np.int32(N)

        def look(c, carry):
            slots, through, matched = carry
            cut = lambda a: lax.dynamic_slice(a, (c * CH,), (CH,))
            chunk = [tuple(cut(a) for a in lane) for lane in lanes]
            went, found, probes = pre.look_up(chunk, cut(order) < N,
                                              child_tables)
            put = lambda whole, part: lax.dynamic_update_slice(
                whole, part, (c * CH,))
            return (tuple(put(s, slot) for s, (slot, _) in zip(slots, probes)),
                    put(through, went),
                    matched + found.sum(dtype=np.int32))

        with kernel_scope(xp, "probe.lookup"):
            slots, through, matched = lax.fori_loop(
                0, (K + CH - 1) // CH, look,
                (tuple(xp.zeros((N,), np.int32) for _ in lanes),
                 xp.zeros((N,), bool), np.int32(0)))

        with kernel_scope(xp, "probe.block"):
            D = through.sum(dtype=np.int32)
            # where the kept rows fit the block they are its first rows
            again = lax.cond(K <= C, lambda _: _marked(xp, through),
                             lambda _: _pack_with(xp, through)[0], None)
            if -N % C:
                again = xp.concatenate(
                    [again, xp.full((-N % C,), N, np.int32)])
        carry = (slots, again, order if blk.sorts else (), D)
        out_cols, out_valids, live, counts = blk.cut(
            child_tables, env, row_mask, carry, np.int32(0))
        counts[MATCHED], counts[LOOKED] = matched, K
        return out_cols, out_valids, live, xp.stack(counts), carry
    return join_probe


def build_join_probe_round(node: JoinNode, param_names: tuple, xp,
                           block_rows: Optional[int] = None) -> Callable:
    """A further round of the probe: (child_tables, cols, valids,
    row_mask, carry, round) -> (block cols, block valids, block mask,
    counts), round ``r`` >= 1 of the batch whose round 0
    (``build_join_probe``) returned ``carry``.  It cuts its block from
    the carry and fills it (``_Block.cut``: the later pairs, the
    payload and own columns of the block's rows, the cross-relation
    conjuncts) -- no lookup loop, no sort, no gather of more than a
    block's rows -- so it counts no ``LOOKED`` and no ``MATCHED``."""
    blk = _Block(node, param_names, xp, block_rows)

    # named as round 0's kernel is: a second variant of the XLA module
    # jit_join_probe in a device trace
    def join_probe(child_tables, cols, valids, row_mask, carry, rnd):
        out_cols, out_valids, live, counts = blk.cut(
            child_tables, blk.pre.env(cols, valids), row_mask, carry, rnd)
        return out_cols, out_valids, live, xp.stack(counts)
    return join_probe
