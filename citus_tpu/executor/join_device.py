"""The device path of a many-to-one join (``ops/join.py``): colocated,
or a single-hash repartition.

``run_device_join`` answers a ``join:colocated`` statement whose join
graph ``planner/join_planner.py`` ``plan_device_join`` takes -- and a
``join:repartition`` one whose probe relation is distributed on the
join key -- or says why the host path (``executor/join_executor.py``,
the oracle) has to.  The plan is a tree of builds under the probe
relation and, of a graph with a cycle (TPC-H Q5), the equalities left
over: each joins the root's cross-relation conjuncts, so its two
columns are payload of their builds through every level up to the
root, and the probe decides it over each round's block
(``explain["join"]``: ``tree``, ``cycle_filters``).

One stream, one ``scan_loop.drive``, one decode thread a query: the
batches of every relation in the order their tables are needed -- the
reference and local relations first, built once a query and kept on the
device for every shard pair (span ``join_broadcast``); then per shard
index the build-side relations' shards (span ``join_build``, once a
shard and relation) and the probe relation's shard, each of whose
batches runs one step: ``jit_join_probe`` (probe, filters, the
survivors packed into a block) and the aggregate's update over that
block (``jit_hash_fused``, the device hash table every GROUP BY of
unbounded cardinality uses).  A block that overflows takes further
rounds (``join_overflow_round``), found where the loop waits for the
device anyway: round 0 leaves its lookups' slots and its packing on the
device (the *carry*, held with the batch's inputs until that wait), and
a further round is a second, small variant of ``jit_join_probe`` that
cuts its block from them and looks nothing up.  The statement's
literals reach the kernels as parameters
(``auto_param.hoist_literals``): a new SEGMENT or DATE compiles
nothing.  One fetch brings home the aggregate's table (at
most ``AGG_SLOTS[1]`` entries; the groups it cannot hold spill to the
host accumulator, exactly).

**The single-hash repartition** (``DeviceJoinTree.exchanged``: the
reference's ``MapMergeJob`` with partition type ``SINGLE_HASH``).  The
probe relation stays where its shards lie; the other distributed
relation is built ONCE a query.  On several devices the stream's rounds
are shard-affine (``_MeshRounds`` over ``AffineMeshPlacement``: a
round's members are batches of one relation, member ``i`` of a shard
device ``i`` owns) and every kernel runs per device under
``shard_map``: first the rounds of the exchanged relation, each ONE
dispatch of ``jit_join_exchange`` -- a row's target is the device that
owns the probe relation's shard its key hashes to by the catalog's map,
one ``all_to_all`` a lane, and what a device receives goes into its ONE
lookup table by the build's own step (span ``join_exchange``; a block
that could not hold its rows takes further rounds, found where the loop
waits anyway and always before the first probe) -- then the rounds of
the probe relation's own shards, each ``jit_join_probe`` and the
aggregate's update per device, no collective; the per-device group
tables come home in one fetch and merge exactly.  On one device the
same tree and kernels with the exchange skipped: every shard of the
relation builds the one table.
"""

from __future__ import annotations

import hashlib
from contextlib import nullcontext
from types import SimpleNamespace
from typing import Optional

import numpy as np

from citus_tpu import types as T
from citus_tpu.catalog import Catalog
from citus_tpu.config import Settings
from citus_tpu.executor.batches import empty_batch
from citus_tpu.executor.executor import (
    _UNREPORTED_FREE_BYTES, _pow2_at_least, _table_kernel,
)
from citus_tpu.executor.finalize import finalize_groups
from citus_tpu.executor.kernel_cache import get_kernel, jit_compile
from citus_tpu.executor.scan_loop import (
    AffineMeshPlacement, OneDevice, Step, _rounds, drive,
)
from citus_tpu.observability import trace as _trace
from citus_tpu.ops import join as J
from citus_tpu.planner.auto_param import hoist_literals
from citus_tpu.planner.bound import (
    BColumn, BKeyRef, compile_expr, param_env_names, walk,
)
from citus_tpu.planner.join_planner import (
    BoundJoinSelect, DeviceJoinTree, _and_all, _conjuncts,
    dependent_group_keys, plan_device_join,
)

#: slots of the aggregate's table: the probe relation's rows over this,
#: between these bounds (what it cannot hold spills to the host, exactly)
AGG_ROWS_PER_SLOT = 8
AGG_SLOTS = (1 << 10, 1 << 20)
#: rows of the block the returned groups' keys are looked up in, at
#: least: a cut's winners and the host's groups fit it whatever the
#: parameter, so a new draw compiles nothing
LOOKUP_ROWS = 4096
#: share of the device's free memory a direct-address table's index may
#: take
DIRECT_MEMORY_SHARE = 0.25


class _HostFallback(Exception):
    """What the build found out on the device: the host path answers."""


class _Placement(OneDevice):
    """One device; remembers which relation's batch the round holds."""

    def put(self, plan, members: list):
        self.host = members[0]
        self.rows_in = self.host.n_rows
        return super().put(plan, members)


class _MeshRounds(AffineMeshPlacement):
    """The devices of the mesh, a table each: a round's members are
    batches of ONE relation (``scans``: how each relation's batches are
    shaped), member ``i`` a batch of a shard device ``i`` owns -- or,
    of a replicated relation, the same batch for every device."""

    def __init__(self, mesh, record, scans: dict) -> None:
        super().__init__(mesh, 1, record)
        self.scans = scans

    def put(self, plan, members: list):
        real = {id(m): m for m in members if m is not None}
        self.host = next(iter(real.values()))
        self.rows_in = sum(m.n_rows for m in real.values())
        return super().put(self.scans[self.host.tag[0]], members)


def _names_in(exprs) -> list:
    seen: list = []
    for e in exprs:
        if e is None:
            continue
        for n in walk(e):
            if isinstance(n, BColumn) and n.name not in seen:
                seen.append(n.name)
    return seen


def run_device_join(cat: Catalog, bj: BoundJoinSelect, settings: Settings,
                    t0: float):
    """-> the statement's Result, or the reason (a string) the host
    path answers it."""
    from citus_tpu.catalog.stats import column_bounds, shard_row_counts
    from citus_tpu.executor.executor import _hash_has_exact
    if bj.strategy == "repartition" \
            and not settings.planner.enable_repartition_joins:
        return "repartition joins are disabled"
    shard_rows = {}
    with _trace.span("scan_setup"):
        for alias, t in bj.rels:
            try:
                shard_rows[alias] = shard_row_counts(cat, t)
            except Exception:
                shard_rows[alias] = [0] * max(1, t.shard_count)
    tables_of = dict(bj.rels)
    with _trace.span("plan_physical") as sp:
        tree = plan_device_join(
            bj, {a: sum(c) for a, c in shard_rows.items()},
            bounds=lambda a: column_bounds(cat, tables_of[a]))
        if isinstance(tree, str):
            return tree
        if sp.recording:
            sp.set(cycle_filters=len(tree.cycle_filters))
        if _hash_has_exact(bj):
            return "exact value-set partials"
        join = _DeviceJoin(cat, bj, settings, tree, shard_rows)
    try:
        return join.run(t0)
    except _HostFallback as e:
        return str(e)


class _DeviceJoin:
    #: the block's capacity: None = from the batch's bucket
    block_rows: Optional[int] = None
    #: rows of a (source, destination) block of an exchange round:
    #: None = ``ops/join.py`` ``exchange_capacity`` of the batch's bucket
    exchange_rows: Optional[int] = None

    def __init__(self, cat: Catalog, bj: BoundJoinSelect, settings: Settings,
                 tree: DeviceJoinTree, shard_rows: dict):
        self.cat, self.bj, self.settings, self.tree = cat, bj, settings, tree
        self.shard_rows = shard_rows
        self.tables_of = dict(bj.rels)
        root = tree.root

        # the statement's literals, hoisted: one generic tree a family
        specs: list = []
        values: list = []

        def hoist(e):
            if e is None:
                return None
            g, sp, vals = hoist_literals(e, len(specs), in_lists=True)
            specs.extend(sp)
            values.extend(vals)
            return g

        filters = {a: hoist(bj.rel_plans[a].filter) for a, _ in bj.rels}
        # the root's cross-relation conjuncts: the statement's own and
        # the equalities of the join graph's edges off the tree
        post = hoist(_and_all(_conjuncts(bj.post_filter)
                              + list(tree.cycle_filters)))
        self.specs = specs
        self.param_names = tuple(param_env_names(specs))
        self.params = (
            tuple(np.asarray(v, t.device_dtype)
                  for (t, _), v in zip(specs, values)),
            (np.ones((), bool),) * len(specs))

        def dtype_of(name: str) -> str:
            alias, col = name.split(".", 1)
            return str(self.tables_of[alias].schema.scan_dtype(
                col, device=True))

        # a single-hash repartition runs over every device, a table
        # each; whatever else the device joins, on the first
        from citus_tpu.executor.executor import _device_top
        from citus_tpu.parallel.mesh import default_mesh, executor_devices
        self.exchanged = tree.exchanged[0] if tree.exchanged else None
        # the key lane the exchange hashes, as its column is named
        self.exchange_key = self.exchanged and getattr(
            tree.edge[self.exchanged][0][tree.exchanged[1]], "name", "expr")
        self.mesh, self.n_dev = None, 1
        if self.exchanged and 1 < len(executor_devices()) <= \
                self.tables_of[root].shard_count:
            self.mesh = default_mesh()
            self.n_dev = len(executor_devices())
        # a build node is rebuilt per shard where it, or a relation
        # under it, is distributed; else once a query -- as every one
        # is where the distributed build is exchanged
        self.per_shard = {
            a: not self.exchanged and any(
                self.tables_of[b].is_distributed for b in tree.subtree(a))
            for a in tree.builds}

        # the statement above the groups, as the table's endings read a
        # plan: ORDER BY ... LIMIT is cut on the chip where the table
        # decides it (one table: the tables of a mesh may share a group)
        above = SimpleNamespace(
            having=bj.having, order_by=bj.order_by, limit=bj.limit,
            offset=bj.offset, distinct=bj.distinct,
            final_exprs=bj.final_exprs, output_names=bj.output_names,
            param_specs=specs)
        self.top = "one table a device" if self.mesh is not None \
            else _device_top(SimpleNamespace(
                bound=above, agg_extract=bj.agg_extract,
                partial_ops=bj.partial_ops))
        # the group keys that decide the groups; the others are looked
        # up for the groups that are returned, in the tables that still
        # stand then (one built a shard is gone with its shard, and a
        # mesh holds a table a device: their keys stay lanes).  A key
        # the cut reads before any lookup stays a lane too: the table
        # has its lane anyway, and a lookup a slot would walk the build
        # table once more for every occupied entry
        pinned = set()
        if isinstance(self.top, tuple):
            read = [e for e, _, _ in self.top[1]]
            read += [self.top[0][0]] if self.top[0] else []
            pinned = {n.index for e in read for n in walk(e)
                      if isinstance(n, BKeyRef)}
        self.keys = dependent_group_keys(
            bj, tree, pinned,
            resident={a for a in tree.builds if not self.per_shard[a]}
            if self.mesh is None else set())
        resolvers = set(self.keys.lookups)
        held = {bj.group_keys[i].name: b
                for i, b in self.keys.resolver.items()}

        out = _names_in(list(self.keys.lanes) + list(bj.agg_args))
        at_root = _names_in([post]) + out
        # a build's table carries what the root needs of its subtree
        # and, after those, the dependants that it or a build above it
        # holds for the lookup; a parent gathers the lanes it carries on
        # itself: all of them, or the first ones alone where the child
        # is the build that holds the dependants
        carried, payload = {}, {}
        for a in tree.builds:
            under = set(tree.subtree(a))
            base = sorted({(n, dtype_of(n)) for n in at_root
                           if n.split(".", 1)[0] in under})
            extra = sorted({(n, dtype_of(n)) for n, b in held.items()
                            if a in tree.subtree(b)
                            and n.split(".", 1)[0] in under} - set(base))
            carried[a] = tuple(base + extra)
            payload[a] = tuple(base) if a in resolvers else carried[a]
        self.carried = carried

        self.kind = {a: "hash" for a in tree.builds}
        self.spans: dict = {}
        for a in tree.builds:
            span = self._direct_span(a)
            if span is not None:
                self.kind[a], self.spans[a] = "direct", span

        def node(a: str) -> J.JoinNode:
            rp = bj.rel_plans[a]
            return J.JoinNode(
                alias=a,
                names=tuple(f"{a}.{c}" for c in rp.columns),
                filter=filters[a],
                children=tuple(
                    J.ChildProbe(c, tuple(tree.edge[c][1]), payload[c],
                                 self.kind[c])
                    for c in tree.children(a)),
                key=() if a == root else tuple(tree.edge[a][0]),
                payload=() if a == root else carried[a],
                post_filter=post if a == root else None,
                out=tuple(out) if a == root else (),
                kind=self.kind.get(a, "hash"))

        self.nodes = {a: node(a) for a in tree.builds + [root]}
        self.out_dtypes = tuple(dtype_of(n) for n in out)
        scans = [(a, [(c, dtype_of(f"{a}.{c}"))
                      for c in bj.rel_plans[a].columns])
                 for a in self.nodes]
        fp = hashlib.sha256(repr((
            sorted(self.nodes.items()), scans, bj.group_keys, self.keys.lanes,
            bj.agg_args, bj.partial_ops, len(specs),
            tree.exchanged)).encode()).hexdigest()
        # every relation's columns ride at their logical widths: the
        # join's lanes are not narrowed from the statistics (yet).
        # This view, the aggregate's and the scans' share one
        # runtime_cache, which holds their kernels and nothing else
        self.holder = SimpleNamespace(
            bound=SimpleNamespace(table=self.tables_of[root]),
            narrow_lanes=(), wide_lanes=0,
            runtime_cache={"_fingerprint": fp})
        # the aggregate over the block, as the hash kernel reads a plan
        self.agg = SimpleNamespace(
            bound=SimpleNamespace(filter=None, group_keys=list(self.keys.lanes),
                                  param_specs=specs, table=None),
            agg_args=bj.agg_args, scan_columns=list(out),
            partial_ops=bj.partial_ops, agg_extract=bj.agg_extract,
            runtime_cache=self.holder.runtime_cache)

        # shard pairs the stream goes through one after another: none
        # where nothing is built per shard
        self.n_shards = 0 if self.exchanged else max(
            [t.shard_count for _, t in bj.rels if t.is_distributed] or [0])
        self.tables: dict = {}
        self.slots: dict = {}
        self.lane_rows: dict = {}
        self.rows_in: dict = {}
        self.bytes_in: dict = {}
        self.built = {a: 0 for a in tree.builds}
        self.totals = np.zeros(J.N_PROBE_COUNTS, np.int64)
        self.probed = self.overflow_rounds = self.later_level = 0
        self.looked_up = 0       # groups whose dependants were looked up
        # the exchange: rounds whose counts are not home yet, and what
        # the ones that are have counted
        self.exchange_pending: list = []
        self.sent = self.exchange_overflow_rounds = 0
        self.spanned = (0, 0)    # of those, what a span has reported
        self.received = np.zeros(self.n_dev, np.int64)

    # ------------------------------------------------------------ scans

    def _scan(self, alias: str, shard_indexes: list):
        rp = self.bj.rel_plans[alias]
        return SimpleNamespace(
            bound=SimpleNamespace(table=rp.table), scan_columns=list(rp.columns),
            intervals=rp.intervals, index_eq=None,
            shard_indexes=shard_indexes,
            runtime_cache=self.holder.runtime_cache)

    def _batches(self, alias: str, shard_indexes: list):
        from citus_tpu.executor.executor import _iter_padded_batches
        return _iter_padded_batches(
            self.cat, self._scan(alias, shard_indexes), self.settings,
            self.record)

    def _filler(self, alias: str, si: int):
        """A batch of padding alone: a build relation with no batch
        still makes its table."""
        plan = self._scan(alias, [])
        return empty_batch(plan.bound.table, plan,
                           max(1, self.settings.executor.min_batch_rows), si)

    def _tagged(self, alias: str, rounds, si: int, filler):
        """``rounds`` (lists of host batches, None where a device has
        none) flat, every batch tagged ``(alias, shard index, first,
        last)`` by its round; a build relation with no round yields
        ``filler()``, so that its table is made."""
        held, first = next(rounds, None), True
        if held is None:
            if alias == self.tree.root:
                return
            held = filler()
        while held is not None:
            nxt = next(rounds, None)
            for m in held:
                if m is not None:
                    m.tag = (alias, si, first, nxt is None)
            yield from held
            held, first = nxt, False

    def _one_device(self, alias: str, shard_indexes: list, si: int):
        return self._tagged(
            alias, ([b] for b in self._batches(alias, shard_indexes)), si,
            lambda: [self._filler(alias, si)])

    def _rounds_of(self, alias: str):
        """A relation's batches in the mesh's rounds: shard-affine by
        its own table's map, or every batch to every device."""
        table, n = self.tables_of[alias], self.n_dev
        every = list(range(table.shard_count))
        if table.is_distributed:
            rounds = _rounds(self.placement.affine(
                every, lambda mine: self._batches(alias, mine),
                n_shards=table.shard_count), n)
        else:
            rounds = ([b] * n for b in self._batches(alias, every))
        return self._tagged(alias, rounds, -1,
                            lambda: [self._filler(alias, -1)] * n)

    def _stream(self):
        tree = self.tree
        all_of = lambda a: list(range(self.tables_of[a].shard_count))
        if self.mesh is not None:
            for a in tree.builds + [tree.root]:
                yield from self._rounds_of(a)
            return
        for a in tree.builds:
            if not self.per_shard[a]:
                yield from self._one_device(a, all_of(a), -1)
        if not self.n_shards:
            yield from self._one_device(tree.root, all_of(tree.root), -1)
            return
        for si in range(self.n_shards):
            one = lambda a: [si] if self.tables_of[a].is_distributed \
                else all_of(a)
            for a in tree.builds:
                if self.per_shard[a]:
                    yield from self._one_device(a, one(a), si)
            yield from self._one_device(tree.root, one(tree.root), si)

    # ---------------------------------------------------------- kernels

    def _kernel(self, slot: str, build, extra: tuple = (),
                replicated: tuple = (), **jit_kwargs):
        """``build()``'s function jitted as it is, or run by each device
        of the mesh on its own tables (the arguments at ``replicated``
        go to every device whole)."""
        return _table_kernel(self.holder, self.mesh, slot, build,
                             extra=extra, replicated=replicated, **jit_kwargs)

    def _filled_on_devices(self) -> dict:
        """jit arguments of a kernel that fills a state where it lives:
        on the mesh a row a device."""
        if self.mesh is None:
            return {}
        from jax.sharding import NamedSharding, PartitionSpec
        return {"out_shardings": NamedSharding(self.mesh,
                                               PartitionSpec("shard"))}

    def _direct_span(self, alias: str):
        """-> (lowest key, slots) of a direct-address table for build
        node ``alias``, or None: its ONE key lane is a plain integer or
        date column whose every value the footers bound
        (``catalog/stats.py`` ``table_facts``; no transaction of this
        thread has staged rows into the table), and an int32 a key of
        that span fits ``DIRECT_MEMORY_SHARE`` of the device's free
        memory.  From that proof alone -- the way ``choose_group_mode``
        picks the direct group table; no setting."""
        from citus_tpu.catalog.stats import table_facts
        from citus_tpu.parallel.mesh import executor_devices
        from citus_tpu.planner.physical import sees_staged_rows
        key = self.tree.edge[alias][0]
        table = self.tables_of[alias]
        if len(key) != 1 or not isinstance(key[0], BColumn) \
                or not (key[0].type.is_integer or key[0].type.kind == T.DATE) \
                or sees_staged_rows(table):
            return None
        facts = table_facts(self.cat, table)
        bounds = facts and facts.columns.get(key[0].name.split(".", 1)[1])
        if not bounds or bounds[0] is None or bounds[1] is None:
            return None
        lo, hi = int(bounds[0]), int(bounds[1])
        slots = _pow2_at_least(hi - lo + 1, 1024)
        st = executor_devices()[0].memory_stats()
        free = st["bytes_limit"] - st["bytes_in_use"] if st \
            else _UNREPORTED_FREE_BYTES
        if slots > 1 << 30 or 4 * slots > DIRECT_MEMORY_SHARE * free:
            return None
        return lo, slots

    def _rows_of(self, alias: str) -> int:
        """Rows one build of ``alias`` can take: a shard's, all -- or,
        of the exchanged relation on the mesh, a device's even share of
        all and the exchange's margin of it."""
        rows = self.shard_rows[alias]
        if self.per_shard[alias] and self.tables_of[alias].is_distributed:
            return max(rows, default=0)
        if alias == self.exchanged and self.mesh is not None:
            num, den = J.EXCHANGE_MARGIN
            return -(-sum(rows) * num // (den * self.n_dev))
        return sum(rows)

    def _slots_of(self, alias: str) -> int:
        if self.kind[alias] == "direct":
            return self.spans[alias][1]
        return _pow2_at_least(J.SLOTS_PER_ROW * self._rows_of(alias), 1024)

    def _zero(self, alias: str):
        import jax
        import jax.numpy as jnp
        node = self.nodes[alias]
        S = self.slots.setdefault(alias, self._slots_of(alias))
        n = self.n_dev if self.mesh is not None else 0

        def make(slots, rows, lo):
            table = J.empty_join_table(node, slots, jnp, rows, lo)
            # on the mesh the same table a device
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), table) \
                if n else table
        zero = get_kernel(
            self.holder, f"jit_join_zero:{alias}",
            lambda: jit_compile(make, static_argnums=(0, 1),
                                **self._filled_on_devices()),
            extra=(n,))
        # a direct table's lanes: every row of the build, and a chunk
        self.lane_rows[alias] = _pow2_at_least(self._rows_of(alias), 1024)
        return zero(S, self.lane_rows[alias] + J.BUILD_CHUNK,
                    np.int64(self.spans.get(alias, (0, 0))[0]))

    def _exchange_kernel(self):
        import jax.numpy as jnp
        node = self.nodes[self.exchanged]
        lane = self.tree.exchanged[1]
        n_dev, rows = self.n_dev, self.exchange_rows
        return self._kernel(
            "jit_join_exchange",
            lambda: J.build_join_exchange(node, self.param_names, jnp, n_dev,
                                          lane, rows),
            extra=(rows,), replicated=(2, 6), donate_argnums=0)

    def _bounds(self) -> np.ndarray:
        """The least hash of each device's shards of the probe
        relation: ``TableMeta.route_hashes``' ranges over the
        placement's shard-to-device map."""
        table = self.tables_of[self.tree.root]
        first = {}
        for si, shard in enumerate(table.shards):
            first.setdefault(
                self.placement.owner(si, table.shard_count), shard.hash_min)
        return np.array([first[d] for d in range(self.n_dev)], np.int32)

    # ------------------------------------------------------------- step

    def _step(self, _token, cols, valids, row_mask):
        import jax.numpy as jnp
        hb = self.placement.host
        alias, si, first, last = hb.tag
        node = self.nodes[alias]
        children = tuple(self.tables[c.alias] for c in node.children)
        if alias == self.tree.root:
            probe = self._probe_kernel()
            bcols, bvalids, bmask, counts, carry = probe(
                children, cols, valids, row_mask)
            spill = self._aggregate(bcols, bvalids, bmask)
            self.probed += int(row_mask.size)
            return (counts,), (counts, children, (cols, valids, row_mask),
                               spill, carry)
        if first:
            self.tables[alias] = self._zero(alias)
            self.rows_in[alias] = self.bytes_in[alias] = 0
        self.rows_in[alias] += self.placement.rows_in
        self.bytes_in[alias] += hb.nbytes
        if alias == self.exchanged and self.mesh is not None:
            return self._exchange_round(children, cols, valids, row_mask,
                                        last), None
        build = self._kernel(
            f"jit_join_build:{alias}",
            lambda: J.build_join_build(node, self.param_names, jnp),
            donate_argnums=0)
        # the span of a build: its last dispatch and the wait for
        # its counts
        name = "join_build" if self.per_shard[alias] or \
            self.tables_of[alias].is_distributed else "join_broadcast"
        with (_trace.span(name) if last else nullcontext()) as sp:
            table = self.tables[alias] = build(
                self.tables[alias], children, cols, valids, row_mask)
            built = self._verdict(alias, table) if last else 0
            if last and sp.recording:
                if name == "join_build":
                    sp.set(shard_index=int(si), relation=alias,
                           rows_in=self.rows_in[alias], rows_built=built,
                           slots=self.slots[alias],
                           table=self.kind[alias])
                else:
                    sp.set(relation=alias, rows=self.rows_in[alias],
                           rows_kept=built, bytes=self.bytes_in[alias])
        return (table[1],), None

    def _exchange_round(self, children, cols, valids, row_mask, last: bool):
        """One round of the exchanged relation: the dispatch and, on
        the relation's last round, the wait for every round's counts
        (further rounds for the rows a block left behind) and the
        build's verdict -- before any probe."""
        alias = self.exchanged
        exchange = self._exchange_kernel()
        with _trace.span("join_exchange") as sp:
            table, counts = exchange(
                self.tables[alias], children, self.bounds, cols, valids,
                row_mask, np.int32(0))
            self.tables[alias] = table
            self.exchange_pending.append(
                (counts, children, (cols, valids, row_mask)))
            if last:
                self._settle_exchange()
                with _trace.span("join_build") as bsp:
                    built = self._verdict(alias, self.tables[alias])
                    if bsp.recording:
                        bsp.set(shard_index=-1, relation=alias,
                                rows_in=self.rows_in[alias],
                                rows_built=built, slots=self.slots[alias],
                                table=self.kind[alias])
            if sp.recording:
                # what has come home since the last round's span
                sent, further = self.sent, self.exchange_overflow_rounds
                sp.set(relation=alias, key=self.exchange_key,
                       rows_in=self.placement.rows_in,
                       rows_sent=sent - self.spanned[0],
                       capacity=J.exchange_capacity(
                           int(row_mask.shape[-1]), self.n_dev,
                           self.exchange_rows),
                       overflow=further - self.spanned[1],
                       devices=self.n_dev)
                self.spanned = (sent, further)
        return (self.tables[alias][1],)

    def _settle_exchange(self) -> None:
        """The counts of the exchange rounds dispatched since the last
        time come home; a round that left rows behind takes further
        rounds of them, exactly."""
        import jax
        pending, self.exchange_pending = self.exchange_pending, []
        if not pending:
            return
        alias = self.exchanged
        exchange = self._exchange_kernel()
        homes = jax.device_get([p[0] for p in pending])
        for c, (_, children, inputs) in zip(homes, pending):
            rnd = 0
            while True:
                c = np.asarray(c, np.int64).reshape(self.n_dev, 3)
                self.sent += int(c[:, J.SENT].sum())
                self.received += c[:, J.RECEIVED]
                if not c[:, J.LEFT].any():
                    break
                rnd += 1
                self.exchange_overflow_rounds += 1
                with _trace.span("join_exchange_overflow_round", round=rnd):
                    self.tables[alias], c = exchange(
                        self.tables[alias], children, self.bounds, *inputs,
                        np.int32(rnd))
                    c = jax.device_get(c)

    def _probe_kernel(self, further: bool = False):
        """Round 0 of a batch or, with ``further``, the kernel of its
        later rounds: a block cut from the carry that round 0 left on
        the device (the round's number goes to every device whole)."""
        import jax.numpy as jnp
        root = self.nodes[self.tree.root]
        build = J.build_join_probe_round if further else J.build_join_probe
        return self._kernel(
            "jit_join_probe:round" if further else "jit_join_probe",
            lambda: build(root, self.param_names, jnp, self.block_rows),
            extra=(self.block_rows,), replicated=(5,) if further else ())

    def _aggregate(self, bcols, bvalids, bmask):
        pcols, pvalids = self.placement.pcols, self.placement.pvalids
        self.agg_state, spill = self.agg_kernel(
            self.agg_state, bcols + pcols, bvalids + pvalids, bmask)
        return spill

    def _verdict(self, alias: str, table) -> int:
        """The build's counts, fetched (the one wait a build costs): a
        key that came twice or an entry no pair of slots would take
        sends the statement to the host path."""
        import jax
        import jax.numpy as jnp
        verdict = self._kernel(
            "jit_join_verdict",
            lambda: lambda t: J.join_table_verdict(jnp, t))
        v = np.asarray(jax.device_get(verdict(table))).reshape(
            -1, J.COUNTS + 1)
        if v[:, J.REPEATED].any() or v[:, J.COUNTS].any():
            raise _HostFallback(f"build key of {alias} is not unique")
        # a direct table's lanes hold the rows the catalog counted
        if v[:, J.UNPLACED].any() or (
                self.kind[alias] == "direct"
                and v[:, J.PACKED_ROWS].max() > self.lane_rows[alias]):
            raise _HostFallback(f"build table of {alias} is full")
        self.built[alias] += int(v[:, J.BUILT].sum())
        self.later_level += int(v[:, J.LATER_LEVEL].sum())
        return int(v[:, J.BUILT].sum())

    def _sync(self, pending: list) -> None:
        """Where the loop waits for the device anyway: the rounds'
        counts come home, a block that overflowed takes its further
        rounds, the aggregate's spilled entries drain to the host."""
        import jax
        self._settle_exchange()
        rounds = [aux for _, aux in pending if aux is not None]
        if not rounds:
            return
        spills = []
        with _trace.span("join_counts", rounds=len(rounds)):
            counts = jax.device_get([r[0] for r in rounds])
        for (_, children, inputs, spill, carry), c in zip(rounds, counts):
            c = np.asarray(c, np.int64).reshape(self.n_dev, -1)
            spills.append((None, spill))
            C = J.block_capacity(int(inputs[2].shape[-1]), self.block_rows)
            for r in range(1, -(-int(c[:, J.PACKED].max()) // C)):
                with _trace.span("join_overflow_round", round=r):
                    further = self._probe_kernel(further=True)
                    bcols, bvalids, bmask, cr = further(
                        children, *inputs, carry, np.int32(r))
                    spills.append(
                        (None, self._aggregate(bcols, bvalids, bmask)))
                    c[:, 1:] += np.asarray(cr, np.int64).reshape(
                        self.n_dev, -1)[:, 1:]
                self.overflow_rounds += 1
            self.totals += c.sum(axis=0)
        self.drain(spills)

    # -------------------------------------------------------------- run

    def _agg_slots(self, key_dtypes: tuple) -> tuple:
        """Slots of a device's group table and where the number came
        from: the probe relation's rows over ``AGG_ROWS_PER_SLOT`` (a
        device's share of them) between ``AGG_SLOTS`` (``row count``)
        -- or, where every key lane is a column whose domain is proved
        (by its type: a dictionary, a boolean, ``planner/physical.py``
        ``_key_domain``; or by the footers' bounds of an integer or date
        column of a table no transaction of this thread has staged rows
        into), twice the domains' product (``key domain``): where that
        is fewer slots, and also where it is more than ``AGG_SLOTS[1]``
        and no more than the rows ask for -- the groups are at most the
        product, so such a table stays under half full and few of its
        keys spill to the host."""
        from citus_tpu.catalog.stats import table_facts
        from citus_tpu.planner.physical import _key_domain, sees_staged_rows
        rows = sum(self.shard_rows[self.tree.root]) // self.n_dev
        by_rows = _pow2_at_least(rows // AGG_ROWS_PER_SLOT, AGG_SLOTS[0])
        S = min(AGG_SLOTS[1], by_rows)
        domain = 1
        for k in self.keys.lanes:
            size = None
            if isinstance(k, BColumn) and "." in k.name:
                alias, col = k.name.split(".", 1)
                table = self.tables_of[alias]
                d = _key_domain(self.cat, table, BColumn(col, k.type), None)
                size = d and d.size
                if size is None and (k.type.is_integer
                                     or k.type.kind == T.DATE) \
                        and not sees_staged_rows(table):
                    facts = table_facts(self.cat, table)
                    lo, hi, *_ = (facts and facts.columns.get(col)) \
                        or (None, None)
                    if lo is not None and hi is not None:
                        size = int(hi) - int(lo) + 2
            if size is None:
                return S, "row count"
            domain *= size
        by_domain = _pow2_at_least(2 * domain, AGG_SLOTS[0])
        return (by_domain, "key domain") \
            if by_domain < S or S < by_domain <= by_rows \
            else (S, "row count")

    def _materialize(self, key_arrays: list) -> list:
        """The returned groups' key lanes -> a (values, valid) pair for
        every group key of the statement: a key that is a lane as it
        came, a dependant looked up in the table of the build that holds
        it, still resident -- the groups' lanes that are the build's
        edge key go through the table as ONE block, the way the probe
        walks it (``ops/join.py`` ``build_join_lookup``), and the
        dependants' payload lanes come home with it."""
        import jax
        import jax.numpy as jnp
        from citus_tpu.errors import ExecutionError
        keys, bj = self.keys, self.bj
        self.looked_up = 0
        if not keys.dependants:
            return [key_arrays[i] for i in keys.lane_of]
        n = int(key_arrays[0][0].shape[0])
        found = {}
        with _trace.span("materialize_keys") as sp:
            P = _pow2_at_least(n, LOOKUP_ROWS)
            pad = lambda a: np.concatenate(
                [a, np.zeros(P - n, a.dtype)])
            for b, lane_ids in keys.lookups.items():
                names = tuple(f"__lane_{i}" for i in lane_ids)
                node = J.JoinNode(
                    alias="__groups__", names=names, filter=None,
                    children=(J.ChildProbe(
                        b, tuple(BColumn(nm, keys.lanes[i].type)
                                 for nm, i in zip(names, lane_ids)),
                        self.carried[b], self.kind[b]),))
                lookup = self._kernel(
                    f"jit_join_lookup:{b}",
                    lambda node=node: J.build_join_lookup(node, jnp))
                cols, valids, hit = jax.device_get(lookup(
                    (self.tables[b],),
                    tuple(pad(np.asarray(key_arrays[i][0])) for i in lane_ids),
                    tuple(pad(np.asarray(key_arrays[i][1], bool))
                          for i in lane_ids),
                    np.arange(P) < n))
                if not hit[:n].all():
                    # an inner join's group has a row in every build
                    raise ExecutionError(
                        f"a group's key is not in the table of {b}")
                for (name, _), c, v in zip(self.carried[b], cols, valids):
                    found[name] = (c[:n], v[:n])
            self.looked_up = n
            if sp.recording:
                sp.set(groups=n, keys=keys.dependants,
                       words=n * sum(bj.group_keys[i].type.is_text
                                     for i in keys.resolver),
                       tables=len(keys.lookups))
        return [key_arrays[lane] if lane is not None
                else found[bj.group_keys[i].name]
                for i, lane in enumerate(keys.lane_of)]

    def run(self, t0: float):
        import jax
        import jax.numpy as jnp
        from citus_tpu.executor.executor import (
            GLOBAL_COUNTERS, _HashTables, _SpillDrain, _fetch_hash_table,
            _fetch_hash_top,
        )
        from citus_tpu.executor.host_agg import HostGroupAccumulator
        from citus_tpu.executor.join_executor import (
            _JoinPlanView, _join_text_src, finish_join,
        )
        from citus_tpu.executor.pipeline import PipelineStats
        from citus_tpu.ops.hash_agg import (
            build_fused_hash_worker, empty_hash_state, hash_state_bytes,
            merge_hash_tables_into,
        )
        bj, tree = self.bj, self.tree
        record = self.record = PipelineStats()
        _trace.set_phase("device")
        if self.mesh is None:
            self.placement = _Placement(record)
        else:
            self.placement = _MeshRounds(
                self.mesh, record, {a: self._scan(a, []) for a in self.nodes})
            self.bounds = self._bounds()
        self.placement.bind(self.params)

        # the aggregate's table: key dtypes by evaluating the keys on a
        # block of no rows
        env = {n: (np.zeros(0, dt), np.zeros(0, bool))
               for n, dt in zip(self.nodes[tree.root].out, self.out_dtypes)}
        key_dtypes = tuple(
            np.asarray(compile_expr(k, np)(env)[0]).dtype
            for k in self.keys.lanes)
        self.agg_kernel = self._kernel(
            "jit_hash_fused",
            lambda: build_fused_hash_worker(self.agg, jnp, key_dtypes),
            donate_argnums=0)
        acc = HostGroupAccumulator(len(self.keys.lanes), bj.partial_ops)
        with _trace.span("hash_init") as sp:
            S, slots_from = self._agg_slots(key_dtypes)
            self.drain = _SpillDrain(self.agg, [acc], S,
                                     devices=self.n_dev if self.mesh else 0)
            agg = self.agg      # the cached kernel must not hold ``self``
            n_tables = self.n_dev if self.mesh is not None else 0
            zero = get_kernel(
                self.holder, "jit_join_agg_zero",
                lambda: jit_compile(
                    lambda slots: empty_hash_state(
                        agg, slots, key_dtypes, jnp, tables=n_tables),
                    static_argnums=0, **self._filled_on_devices()),
                extra=(n_tables,))
            self.agg_state = zero(S)
            if sp.recording:
                sp.set(slots=S, slots_from=slots_from, devices=self.n_dev)

        token = (jnp.zeros((), np.int32),)
        drive(self.holder, self.settings, self.placement,
              Step(self._step, "jit_join_probe", "join_dispatches"), token,
              record, stream=self._stream(), on_sync=self._sync)
        record.book_timings()

        # the ending: ORDER BY ... LIMIT cut on the chip where the table
        # decides it -- the winners' block and the entries of the host's
        # keys come home -- else one fetch of the tables whole (what
        # they could not hold is in ``acc`` already)
        lanes = self.keys.lanes
        view = _JoinPlanView(bj)
        home = isinstance(self.top, tuple) and _fetch_hash_top(
            self.agg, self.agg_state, acc, self.params, self.top, record,
            key_lanes=tuple(self.keys.lane_of))
        not_cut = self.top if isinstance(self.top, str) \
            else "block and host keys past half the table"
        (h_keys, h_parts, h_rows), entry_mask, groups = home or (
            _fetch_hash_table(_HashTables(self.agg_state, self.mesh), record),
            None, None)
        with _trace.span("finalize_groups") as sp:
            fetched = hash_state_bytes((h_keys, h_parts, h_rows))
            entries = int(h_rows.shape[0])
            occupied = h_rows > 0
            if not home and acc.n_groups == 0 and occupied.any() \
                    and self.mesh is None:
                # every entry came and nothing spilled: they are the groups
                key_arrays = [(kv[occupied], kf[occupied] == 2)
                              for kv, kf in h_keys]
                partials = tuple(p[occupied] for p in h_parts)
                groups = int(occupied.sum())
            else:
                # the host holds a part of some groups, and a group may
                # sit in every device's table: they merge into the one
                # accumulator, exactly
                merge_hash_tables_into(acc, self.agg, h_keys, h_parts, h_rows,
                                       entry_mask=entry_mask)
                key_arrays, partials = acc.finalize(
                    [g.type for g in lanes], scalar=not lanes)
                groups = groups if home else acc.n_groups
            rows = [] if partials is None else finalize_groups(
                view, self.cat, self._materialize(key_arrays), partials,
                text_src=_join_text_src(bj))
            if sp.recording:
                sp.set(groups=groups, rows=len(rows))

        table_bytes = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
            [t[0] for t in self.tables.values()]))
        cycle = bool(tree.cycle_filters)
        join = {
            "on": "device", "probe": tree.root,
            "tree": dict(tree.parent),
            "cycle_filters": [f"{e.left.name} = {e.right.name}"
                              for e in tree.cycle_filters],
            # rows the root's cross-relation conjuncts saw and kept,
            # every round, where a cycle filter is among them
            "cycle_rows_in": int(self.totals[J.SEEN]) if cycle else 0,
            "cycle_rows_kept": int(self.totals[J.OUT]) if cycle else 0,
            "probe_children": len(tree.children(tree.root)),
            "tables": {a: {"table": self.kind[a],
                           "slots": self.slots.get(a, 0),
                           "built_per": "shard" if self.per_shard[a]
                           else "query"} for a in tree.builds},
            "rows_built": sum(self.built.values()),
            "later_level_rows": self.later_level,
            "rows_probed": self.probed,
            "rows_looked_up": int(self.totals[J.LOOKED]),
            "rows_matched": int(self.totals[J.MATCHED]),
            "rows_out": int(self.totals[J.OUT]),
            "overflow_rounds": self.overflow_rounds,
            "table_bytes": table_bytes,
            "agg_slots": S, "agg_slots_from": slots_from, "groups": groups,
            "spilled_rows": self.drain.rows,
            "group_keys": len(bj.group_keys),
            "group_key_lanes": len(lanes),
            "group_keys_dependent": self.keys.dependants,
            "groups_looked_up": self.looked_up,
            # the rows the cut asked for and the entries that came
            # home, or why every group did
            "top": {"rows": self.top[2], "entries": entries} if home
            else not_cut,
        }
        record.figures["group_top"] = not_cut if not home \
            else f"first {self.top[2]} on device"
        record.tally("group_keys", join["group_keys"])
        record.tally("group_key_lanes", join["group_key_lanes"])
        record.tally("group_keys_dependent", join["group_keys_dependent"])
        explain = {"join": join, "pipeline": record.figures}
        if self.exchanged:
            explain["shuffle"] = "local" if self.mesh is None \
                else "all_to_all:device"
            node = self.nodes[self.exchanged]
            # a row travels as its columns and a validity byte each,
            # and a byte that says the lane holds a row
            row_bytes = 1 + sum(
                np.dtype(self.tables_of[self.exchanged].schema.scan_dtype(
                    c.split(".", 1)[1], device=True)).itemsize + 1
                for c in node.names)
            join["exchange"] = {
                "relation": self.exchanged,
                "key": self.exchange_key.split(".", 1)[-1],
                "rows": self.sent, "bytes": self.sent * row_bytes,
                "devices": self.n_dev,
                "rows_received_max_device": int(self.received.max()),
                "overflow_rounds": self.exchange_overflow_rounds,
            }
            ex = join["exchange"]
            GLOBAL_COUNTERS.bump("join_rows_exchanged", ex["rows"])
            GLOBAL_COUNTERS.bump("join_bytes_exchanged", ex["bytes"])
            GLOBAL_COUNTERS.bump("join_rows_received_max_device",
                                 ex["rows_received_max_device"])
            GLOBAL_COUNTERS.bump("join_exchange_overflow_rounds",
                                 ex["overflow_rounds"])
        GLOBAL_COUNTERS.bump("join_rows_built", join["rows_built"])
        GLOBAL_COUNTERS.bump("join_rows_probed", join["rows_probed"])
        GLOBAL_COUNTERS.bump("join_rows_looked_up", join["rows_looked_up"])
        GLOBAL_COUNTERS.bump("join_rows_matched", join["rows_matched"])
        GLOBAL_COUNTERS.bump("join_rows_out", join["rows_out"])
        GLOBAL_COUNTERS.bump("join_cycle_filters", len(join["cycle_filters"]))
        GLOBAL_COUNTERS.bump("join_cycle_rows_in", join["cycle_rows_in"])
        GLOBAL_COUNTERS.bump("join_cycle_rows_kept", join["cycle_rows_kept"])
        GLOBAL_COUNTERS.bump("join_probe_children", join["probe_children"])
        GLOBAL_COUNTERS.bump("join_overflow_rounds", join["overflow_rounds"])
        GLOBAL_COUNTERS.bump("join_table_bytes", join["table_bytes"])
        GLOBAL_COUNTERS.bump("hash_table_bytes_fetched", fetched)
        GLOBAL_COUNTERS.bump("hash_entries_fetched", entries)
        GLOBAL_COUNTERS.bump("hash_groups_out", join["groups"])
        tasks = self.tables_of[tree.root].shard_count if self.exchanged \
            else max(1, self.n_shards)
        return finish_join(bj, view, rows, bj.strategy, tasks, t0, explain)
