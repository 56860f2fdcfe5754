"""Join planning.

The reference supports three physical join strategies for distributed
relations (src/backend/distributed/planner/ — query_pushdown_planning.c,
multi_join_order.c, multi_physical_planner.c MapMergeJob):

1. *colocated* joins — equality on distribution columns within one
   colocation group: each shard joins locally with its colocated peers.
2. *broadcast* joins — reference/local tables are replicated, so any
   relation can join against them shard-locally.
3. *repartition* joins — equality on non-distribution columns: both
   sides are re-hashed on the join key (MapMergeJob / all_to_all).

This planner classifies a left-deep join tree into those strategies and
pushes single-relation WHERE conjuncts down to each scan (with chunk
pruning intervals), mirroring the reference's qual pushdown.  When
colocation cannot be proven, the executor falls back to a repartitioned
or pull-to-coordinator join — same degradation ladder as the reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from citus_tpu import types as T
from citus_tpu.catalog import Catalog, TableMeta
from citus_tpu.errors import AnalysisError, UnsupportedFeatureError
from citus_tpu.planner import ast_nodes as A
from citus_tpu.planner.bind import AggSpec, Binder, _contains_agg, _default_name
from citus_tpu.planner.bound import (
    BBinOp, BColumn, BExpr, BKeyRef, BLiteral, walk,
)
from citus_tpu.planner.physical import (
    AggExtract, PartialOp, extract_intervals, lower_aggregates,
)
from citus_tpu.storage.reader import Interval


@dataclass
class RelPlan:
    """Per-relation scan spec."""
    alias: str
    table: TableMeta
    columns: list[str] = field(default_factory=list)   # unqualified
    filter: Optional[BExpr] = None                     # single-rel conjuncts
    intervals: list[Interval] = field(default_factory=list)


@dataclass
class JoinStep:
    right_alias: str
    kind: str                                   # inner | left | right | full | cross
    left_keys: list[BExpr] = field(default_factory=list)
    right_keys: list[BExpr] = field(default_factory=list)
    residual: Optional[BExpr] = None            # non-equi ON conjuncts


@dataclass
class BoundJoinSelect:
    rels: list[tuple[str, TableMeta]]
    rel_plans: dict[str, RelPlan]
    steps: list[JoinStep]
    post_filter: Optional[BExpr]                # cross-rel WHERE conjuncts
    group_keys: list[BExpr]
    aggs: list[AggSpec]
    final_exprs: list[BExpr]
    output_names: list[str]
    having: Optional[BExpr]
    order_by: list[tuple[int, bool, Optional[bool]]]
    limit: Optional[int]
    offset: Optional[int]
    distinct: bool
    agg_args: list[BExpr] = field(default_factory=list)
    partial_ops: list[PartialOp] = field(default_factory=list)
    agg_extract: list[AggExtract] = field(default_factory=list)
    strategy: str = "colocated"                 # colocated | repartition | pull
    # for repartition: (left_alias, right_alias, left_keys, right_keys)
    # of the step connecting the two distributed relations
    repartition_spec: Optional[tuple] = None
    binder: Optional[Binder] = None
    hidden_outputs: int = 0

    @property
    def has_aggs(self) -> bool:
        return bool(self.aggs) or bool(self.group_keys)


def _flatten_joins(item) -> tuple[list[A.TableRef], list[tuple[A.TableRef, str, Optional[A.Expr]]]]:
    """Left-deep join tree -> (base rel, [(right rel, kind, on-cond)...])."""
    if isinstance(item, A.TableRef):
        return [item], []
    if isinstance(item, A.Join):
        refs, steps = _flatten_joins(item.left)
        if not isinstance(item.right, A.TableRef):
            raise UnsupportedFeatureError("right-nested joins are not supported")
        steps.append((item.right, item.kind, item.condition))
        refs.append(item.right)
        return refs, steps
    raise AnalysisError("bad FROM item")


def _rel_of(e: BExpr, qualified: bool) -> Optional[str]:
    """The single relation alias an expression references, or None."""
    aliases = set()
    for n in walk(e):
        if isinstance(n, BColumn):
            aliases.add(n.name.split(".", 1)[0] if qualified and "." in n.name else n.name)
    if not qualified:
        return None
    return aliases.pop() if len(aliases) == 1 else None


def _conjuncts(e: Optional[BExpr]) -> list[BExpr]:
    if e is None:
        return []
    if isinstance(e, BBinOp) and e.op == "and":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def _and_all(parts: list[BExpr]) -> Optional[BExpr]:
    out = None
    for p in parts:
        out = p if out is None else BBinOp("and", out, p, T.BOOL_T)
    return out


def bind_join_select(catalog: Catalog, stmt: A.Select) -> BoundJoinSelect:
    refs, raw_steps = _flatten_joins(stmt.from_)
    rels: list[tuple[str, TableMeta]] = []
    seen = set()
    for r in refs:
        alias = r.alias or r.name
        if alias in seen:
            raise AnalysisError(f"duplicate relation alias {alias!r}")
        seen.add(alias)
        rels.append((alias, catalog.table(r.name)))
    binder = Binder(catalog, rels[0][1], rels=rels)

    def rel_alias_of_col(e: BExpr) -> Optional[str]:
        return _rel_of(e, binder.qualified)

    # ---- join steps: split ON into equi-pairs and residual ------------
    joined: list[str] = [rels[0][0]]
    steps: list[JoinStep] = []
    for (r, kind, cond) in raw_steps:
        alias = r.alias or r.name
        step = JoinStep(right_alias=alias, kind=kind)
        residual = []
        if cond is not None:
            for c in _conjuncts(binder.bind_scalar(cond)):
                ok = False
                if isinstance(c, BBinOp) and c.op == "=":
                    la, ra = rel_alias_of_col(c.left), rel_alias_of_col(c.right)
                    if la == alias and ra in joined:
                        step.left_keys.append(c.right)
                        step.right_keys.append(c.left)
                        ok = True
                    elif ra == alias and la in joined:
                        step.left_keys.append(c.left)
                        step.right_keys.append(c.right)
                        ok = True
                if not ok:
                    residual.append(c)
        if residual:
            if kind != "inner":
                raise UnsupportedFeatureError(
                    "non-equi ON conditions on outer joins are not supported yet")
            step.residual = _and_all(residual)
        if kind != "cross" and not step.left_keys and step.residual is None:
            raise AnalysisError("JOIN requires an ON condition")
        steps.append(step)
        joined.append(alias)

    # ---- WHERE: push single-relation conjuncts to scans ----------------
    where = binder.bind_scalar(stmt.where) if stmt.where is not None else None
    rel_plans = {alias: RelPlan(alias, t) for alias, t in rels}
    cross_conjuncts: list[BExpr] = []
    outer_right = {s.right_alias for s in steps if s.kind in ("left", "full")}
    left_of_right_join = set()
    for s in steps:
        if s.kind in ("right", "full"):
            left_of_right_join.update(a for a in joined if a != s.right_alias)
    below_outer = outer_right | left_of_right_join
    for c in _conjuncts(where):
        alias = rel_alias_of_col(c)
        # pushing a filter below an outer join's null-supplying side would
        # change semantics; keep those conjuncts post-join
        if alias is not None and alias not in below_outer:
            rp = rel_plans[alias]
            rp.filter = c if rp.filter is None else BBinOp("and", rp.filter, c, T.BOOL_T)
        elif not _promote_equi_key(c, joined, steps, below_outer,
                                   rel_alias_of_col):
            cross_conjuncts.append(c)
    post_filter = _and_all(cross_conjuncts)
    for rp in rel_plans.values():
        # intervals operate on unqualified column names within the relation
        rp.intervals = [Interval(c.column.split(".", 1)[-1], c.lo, c.hi,
                                 c.lo_inclusive, c.hi_inclusive)
                        for c in extract_intervals(rp.filter)]

    # ---- outputs / aggregates ------------------------------------------
    items: list[A.SelectItem] = []
    for item in stmt.items:
        if isinstance(item.expr, A.Star):
            for alias, t in rels:
                for col in t.schema:
                    items.append(A.SelectItem(A.ColumnRef(col.name, table=alias), col.name))
        else:
            items.append(item)

    group_keys = [binder.bind_scalar(g) for g in stmt.group_by]
    key_map = {k: i for i, k in enumerate(group_keys)}
    binder._ast_key_map = {}
    binder._ast_key_types = [k.type for k in group_keys]
    for i, g in enumerate(stmt.group_by):
        try:
            binder._ast_key_map.setdefault(g, i)
        except TypeError:
            pass
    has_aggs = any(_contains_agg(i.expr) for i in items) or stmt.having is not None or bool(group_keys)

    aggs: list[AggSpec] = []
    final_exprs: list[BExpr] = []
    output_names: list[str] = []
    having = None
    if has_aggs:
        for i, item in enumerate(items):
            final_exprs.append(binder.bind_select_expr(item.expr, key_map, aggs))
            output_names.append(item.alias or _default_name(item.expr, i))
        if stmt.having is not None:
            having = binder.bind_select_expr(stmt.having, key_map, aggs)
    else:
        for i, item in enumerate(items):
            final_exprs.append(binder.bind_scalar(item.expr))
            output_names.append(item.alias or _default_name(item.expr, i))

    order_by = []
    hidden = 0
    for oi in stmt.order_by:
        try:
            idx = _resolve_order(oi.expr, items, output_names, binder,
                                 final_exprs, key_map, aggs)
        except AnalysisError:
            if stmt.distinct:
                raise
            bound_e = binder.bind_select_expr(oi.expr, key_map, aggs)                 if has_aggs else binder.bind_scalar(oi.expr)
            final_exprs.append(bound_e)
            output_names.append(f"__order_{hidden}")
            idx = len(final_exprs) - 1
            hidden += 1
        order_by.append((idx, oi.ascending, oi.nulls_first))

    # enum ORDER BY keys sort by declaration rank (enumsortorder) — same
    # redirect as bind_select's: hidden rank column, functionally
    # dependent on the enum value
    from citus_tpu.planner.bound import BDictLookup, BKeyRef
    for oi_pos, (idx, asc, nf) in enumerate(order_by):
        e_b = final_exprs[idx]
        under = e_b
        if isinstance(e_b, BKeyRef) and group_keys:
            under = group_keys[e_b.index]
        if not (isinstance(under, BColumn) and under.type.is_text):
            continue
        info = binder.enum_info(under)
        if info is None:
            continue
        final_exprs.append(BDictLookup(e_b, binder.enum_rank_lut(info)))
        output_names.append(f"__order_{hidden}")
        order_by[oi_pos] = (len(final_exprs) - 1, asc, nf)
        hidden += 1

    agg_args, partial_ops, agg_extract, _ = lower_aggregates(aggs)

    # ---- column requirements per relation ------------------------------
    def note_columns(e: Optional[BExpr]):
        if e is None:
            return
        for n in walk(e):
            if isinstance(n, BColumn):
                if binder.qualified and "." in n.name:
                    alias, col = n.name.split(".", 1)
                else:
                    # resolve bare name (only possible when unambiguous)
                    _, c, alias, _t = binder.resolve_column(n.name)
                    col = c.name
                rp = rel_plans[alias]
                if col not in rp.columns:
                    rp.columns.append(col)

    for rp in rel_plans.values():
        note_columns(rp.filter)
    note_columns(post_filter)
    for s in steps:
        for e in s.left_keys + s.right_keys:
            note_columns(e)
        note_columns(s.residual)
    for e in group_keys + agg_args:
        note_columns(e)
    if not has_aggs:
        for e in final_exprs:
            note_columns(e)
    if having is not None:
        note_columns(having)
    # (hidden ORDER BY columns were appended to final_exprs above and are
    # covered by the loop when not aggregating; grouped hidden outputs
    # reference keys/aggs already noted)

    bj = BoundJoinSelect(
        rels=rels, rel_plans=rel_plans, steps=steps, post_filter=post_filter,
        group_keys=group_keys, aggs=aggs, final_exprs=final_exprs,
        output_names=output_names, having=having, order_by=order_by,
        limit=stmt.limit, offset=stmt.offset, distinct=stmt.distinct,
        agg_args=agg_args, partial_ops=partial_ops, agg_extract=agg_extract,
        binder=binder, hidden_outputs=hidden,
    )
    bj.strategy = _choose_strategy(bj)
    return bj


def _promote_equi_key(c: BExpr, joined: list[str], steps: list[JoinStep],
                      below_outer: set, rel_of) -> bool:
    """A WHERE conjunct ``a = b`` whose sides each name one relation is
    a join key of the first step at which both relations are present --
    the published comma form of a join (``from customer, orders where
    c_custkey = o_custkey``) then plans as its ``JOIN ... ON`` form
    does.  For ``cross`` and ``inner`` steps only, and never below an
    outer join's null-supplying side: there it stays a filter.  A
    ``cross`` step that gains a key becomes ``inner``."""
    if not (isinstance(c, BBinOp) and c.op == "="):
        return False
    la, ra = rel_of(c.left), rel_of(c.right)
    if la is None or ra is None or la == ra \
            or la in below_outer or ra in below_outer:
        return False
    li, ri = joined.index(la), joined.index(ra)
    step = steps[max(li, ri) - 1]
    if step.kind not in ("cross", "inner"):
        return False
    earlier, later = (c.left, c.right) if li < ri else (c.right, c.left)
    step.left_keys.append(earlier)
    step.right_keys.append(later)
    step.kind = "inner"
    return True


def _resolve_order(e: A.Expr, items, names, binder, final_exprs, key_map, aggs) -> int:
    if isinstance(e, A.Literal) and isinstance(e.value, int):
        idx = e.value - 1
        if not (0 <= idx < len(items)):
            raise AnalysisError(f"ORDER BY position {e.value} out of range")
        return idx
    if isinstance(e, A.ColumnRef) and e.table is None and e.name in names:
        return names.index(e.name)
    for i, item in enumerate(items):
        if item.expr == e:
            return i
    # try binding and matching structurally against final exprs
    try:
        bound = binder.bind_select_expr(e, key_map, list(aggs)) if aggs or key_map \
            else binder.bind_scalar(e)
    except Exception:
        bound = None
    if bound is not None:
        for i, fe in enumerate(final_exprs):
            if fe == bound:
                return i
    raise AnalysisError("ORDER BY expression must be an output column, alias, or position")


def _dist_col_expr(alias: str, t: TableMeta, qualified: bool) -> Optional[BColumn]:
    if not t.is_distributed or t.dist_column is None:
        return None
    col = t.schema.column(t.dist_column)
    name = f"{alias}.{col.name}" if qualified else col.name
    return BColumn(name, col.type)


def _choose_strategy(bj: BoundJoinSelect) -> str:
    """colocated: every distributed relation is equi-joined on its
    distribution column to an already-aligned distributed relation in the
    same colocation group (reference/local relations are replicated and
    always alignable).  Otherwise: pull (repartition on the coordinator).
    """
    qualified = bj.binder.qualified
    dist_rels = [(a, t) for a, t in bj.rels if t.is_distributed]
    if not dist_rels:
        return "colocated"  # everything replicated/local: single task
    anchor_alias, anchor = dist_rels[0]
    aligned = {anchor_alias}
    # iterate to fixpoint over join steps
    changed = True
    while changed:
        changed = False
        for s in bj.steps:
            t_right = dict(bj.rels)[s.right_alias]
            if not t_right.is_distributed or s.right_alias in aligned:
                continue
            rd = _dist_col_expr(s.right_alias, t_right, qualified)
            for lk, rk in zip(s.left_keys, s.right_keys):
                other = None
                if rk == rd:
                    other = lk
                elif lk == rd:
                    other = rk
                if other is None:
                    continue
                oa = _rel_of(other, qualified)
                if oa is None or oa not in aligned:
                    continue
                t_other = dict(bj.rels)[oa]
                od = _dist_col_expr(oa, t_other, qualified)
                if od is not None and other == od and \
                        t_other.colocation_id == t_right.colocation_id and \
                        t_other.shard_count == t_right.shard_count:
                    aligned.add(s.right_alias)
                    changed = True
    if all(a in aligned for a, t in dist_rels):
        return "colocated"
    spec = _repartition_spec(bj)
    if spec is not None:
        bj.repartition_spec = spec
        return "repartition"
    if any(s.left_keys for s in bj.steps):
        # general case: step-wise shuffle DAG (each equi step partitions
        # both sides on its keys, joins per bucket) — always correct,
        # bounds each join's working set; repartition_spec stays None
        return "repartition"
    return "pull"


def _repartition_spec(bj: BoundJoinSelect) -> Optional[tuple]:
    """Eligibility for the hash-repartition (all_to_all) join — the
    analog of the reference's single-repartition MapMergeJob
    (multi_physical_planner.h:160): exactly two distributed relations,
    connected by an equi-join step whose keys live one per side; every
    other relation replicated (reference/local) and inner-joined.  Rows
    then match only within a hash bucket, so per-bucket joins are exact
    — including an outer dist-dist step (NULL-key rows never match and
    are preserved bucket-locally).

    Returns (left_alias, right_alias, left_key_exprs, right_key_exprs)
    or None."""
    qualified = bj.binder.qualified
    dist = [(a, t) for a, t in bj.rels if t.is_distributed]
    if len(dist) != 2:
        return None
    d_aliases = {a for a, _ in dist}
    connecting = None
    for s in bj.steps:
        if s.right_alias in d_aliases and s.left_keys:
            lks, rks = [], []
            for lk, rk in zip(s.left_keys, s.right_keys):
                la, ra = _rel_of(lk, qualified), _rel_of(rk, qualified)
                if la in d_aliases and ra in d_aliases and la != ra:
                    lks.append(lk)
                    rks.append(rk)
            if lks:
                if connecting is not None:
                    return None  # two dist-dist steps: not single-repartition
                connecting = (s, lks, rks)
        elif s.right_alias in d_aliases:
            return None  # dist rel joined without usable equi keys
        elif s.kind in ("right", "full"):
            # preserved unmatched rows of a replicated right side would
            # re-appear in every bucket
            return None
    if connecting is None:
        return None
    s, lks, rks = connecting
    left_alias = _rel_of(lks[0], qualified)
    return (left_alias, s.right_alias, lks, rks)


# ------------------------------------------------------ the device join


@dataclass
class DeviceJoinTree:
    """An inner equi-join as the device runs it (``ops/join.py``): the
    relations as a tree rooted at the one that is streamed and probed;
    every other relation is built into a lookup table keyed by the edge
    to its parent.  Colocated, or a single-hash repartition
    (``exchanged``): the root stays where its shards lie and the other
    distributed relation's rows travel to the device that owns the
    root's shard their key hashes to."""
    root: str
    parent: dict        # alias -> its parent's alias
    edge: dict          # alias -> (its own key exprs, its parent's)
    builds: list        # the build nodes, children before parents
    # a single-hash repartition: (the build node whose rows are
    # EXCHANGED between the devices on its edge's key, the lane of that
    # key the root is distributed on); None = every relation meets its
    # partners where it lies (colocated, replicated)
    exchanged: Optional[tuple] = None
    # the equalities of the edges off the tree (a join graph with a
    # cycle): conjuncts the root decides with its ``post_filter``, each
    # side a plain column its relation hands up
    cycle_filters: list = field(default_factory=list)

    def children(self, alias: str) -> list:
        return [a for a in self.builds if self.parent[a] == alias]

    def subtree(self, alias: str) -> list:
        out = [alias]
        for c in self.children(alias):
            out += self.subtree(c)
        return out


@dataclass
class GroupKeyPlan:
    """Which group keys of a device join decide the groups, and which
    are functions of those (``dependent_group_keys``)."""
    lanes: list         # the key lanes the device groups on (BExpr)
    lane_of: list       # per group key: its lane's index, None = dependant
    resolver: dict      # dependant's key index -> the build that holds it
    lookups: dict       # such a build -> the lanes that are its edge key

    @property
    def dependants(self) -> int:
        return len(self.resolver)


def dependent_group_keys(bj: BoundJoinSelect, tree: DeviceJoinTree,
                         pinned=(), resident=None) -> GroupKeyPlan:
    """From the tree alone: which group keys are functions of which.

    A build node is UNIQUE on its edge key (the verdict the device join
    takes before the first probe, else the host path answers), and so
    is every build under it: a column of the build, or of a node under
    it, is a function of the build's edge key on the PARENT's side.  A
    build therefore *collapses* where every lane of its edge is among
    the lanes the statement groups on, on either side of the equality
    (an inner step: the two sides are equal on every joined row, so the
    parent's side stands for the build's own): the group keys that are
    plain columns of its subtree stop being lanes and become
    *dependants*, looked up in the build's table for the groups that are
    returned, and the parent's side of the edge is grouped on instead --
    the same partition of the rows, since the old lanes determined the
    edge and the edge determines them.  Builds are tried children before
    parents, so the highest build that collapses holds every dependant
    under it; a collapse that would not leave fewer lanes is not taken.
    A key that is the build's own side of the edge is no dependant: it
    reads the lane of the parent's side (equal on every joined row).

    What stays a lane, as today: a key of the root, an expression over
    several relations (any key that is not a plain column), a key under
    a step that is not inner, a key in ``pinned`` (indexes: the keys an
    ORDER BY or a HAVING decided on the chip reads before any lookup),
    and every key under a build that is not in ``resident`` (the builds
    whose tables still stand when the groups are returned; None = all).
    Duplicate keys share a lane."""
    keys = list(bj.group_keys)
    qualified = bj.binder.qualified
    inner = {s.right_alias: s.kind == "inner" for s in bj.steps}
    resolver: dict = {}

    def lanes_now() -> list:
        out: list = []
        for i, k in enumerate(keys):
            if i not in resolver and k not in out:
                out.append(k)
        for b in dict.fromkeys(resolver.values()):
            for t in tree.edge[b][1]:
                if t not in out:
                    out.append(t)
        return out

    for b in tree.builds:
        under = set(tree.subtree(b))
        # every step on the way down from ``b`` joins inner
        if (resident is not None and b not in resident) \
                or not all(inner.get(a, True) for a in under):
            continue
        own, theirs = tree.edge[b]
        lanes = lanes_now()
        if not all(o in lanes or t in lanes for o, t in zip(own, theirs)):
            continue
        mine = [i for i, k in enumerate(keys)
                if i not in pinned and isinstance(k, BColumn)
                and _rel_of(k, qualified) in under]
        before = dict(resolver)
        resolver.update({i: b for i in mine})
        if len(lanes_now()) >= len(lanes):
            resolver = before
    lanes = lanes_now()
    lane_of = [None if i in resolver else lanes.index(k)
               for i, k in enumerate(keys)]
    # a key that IS the build's side of its edge needs no lookup: on
    # every joined row it equals the parent's side, which is the lane
    for i, b in list(resolver.items()):
        for o, t in zip(*tree.edge[b]):
            if keys[i] == o and o.type == t.type:
                lane_of[i] = lanes.index(t)
                del resolver[i]
                break
    return GroupKeyPlan(
        lanes=lanes,
        lane_of=lane_of,
        resolver=resolver,
        lookups={b: [lanes.index(t) for t in tree.edge[b][1]]
                 for b in dict.fromkeys(resolver.values())})


def _device_key_type(t: T.ColumnType) -> bool:
    return t.is_integer or t.is_decimal or t.kind in (
        T.DATE, T.BOOL, T.TIMESTAMP, T.TIMESTAMPTZ, T.TIME)


def _exchange_of(bj: BoundJoinSelect, rel_rows: dict):
    """-> (root, exchanged, key lane) of a single-hash repartition the
    device runs, or the reason (a string) it does not: the two
    distributed relations meet in ONE inner step, one of them (the
    root) is hash-distributed on its side of a key lane that both hold
    as the same integer or date type -- so the catalog's hash of the
    other's lane names the root's shard -- and the root is the larger
    (the many side of a many-to-one join)."""
    qualified = bj.binder.qualified
    spec = bj.repartition_spec
    if spec is None:
        return "several steps between distributed relations"
    tables = dict(bj.rels)
    left, right, lks, rks = spec
    sides = []
    for mine, other, my_keys, their_keys in ((left, right, lks, rks),
                                             (right, left, rks, lks)):
        d = _dist_col_expr(mine, tables[mine], qualified)
        for lane, (k, o) in enumerate(zip(my_keys, their_keys)):
            if k == d and isinstance(o, BColumn) and o.type == k.type \
                    and (k.type.is_integer or k.type.kind == T.DATE):
                sides.append((mine, other, lane))
                break
    if not sides:
        return "neither side is distributed on the join key " \
               "(a dual repartition)"
    root, exchanged, lane = max(sides, key=lambda s: rel_rows.get(s[0], 0))
    if rel_rows.get(exchanged, 0) > rel_rows.get(root, 0):
        return f"{exchanged}, the relation off the join key, is the " \
               f"larger: the build side would not be unique"
    return root, exchanged, lane


def _repeats(keys: list, rows: int, known: Optional[dict]) -> bool:
    """The catalog's count DISPROVES that ``keys`` (a build's side of an
    edge) are unique over their table's ``rows`` rows: every lane a
    plain integer or date column without NULLs that ``known`` (the
    table's ``column_bounds``) bounds, and fewer distinct keys than rows
    can be (the product of the lanes' spans ``max - min + 1``) --
    ``s_nationkey``, span 25 over 100,000 suppliers, repeats;
    ``s_suppkey``, span 100,000, may not."""
    if not known or not rows:
        return False
    distinct = 1
    for k in keys:
        if not isinstance(k, BColumn) \
                or not (k.type.is_integer or k.type.kind == T.DATE):
            return False
        lo, hi, has_nulls = known.get(k.name.split(".", 1)[-1]) \
            or (None, None, True)
        if lo is None or hi is None or has_nulls:
            return False
        distinct *= int(hi) - int(lo) + 1
    return distinct < rows


def plan_device_join(bj: BoundJoinSelect, rel_rows: dict, bounds=None):
    """-> the ``DeviceJoinTree`` of a join the device can run, or the
    reason (a string) it goes to the host path.  The device runs an
    inner equi-join with an aggregate above; this plans its GRAPH:

    *Edges.*  Every ``left_keys[i] = right_keys[i]`` of every step
    (inner, no residual) is an edge between the two relations its sides
    name; the lanes between one pair of relations are ONE composite
    edge.  A comma step that gained no key of its own is no edge at all.

    *Root.*  The distributed relation with the most rows (``rel_rows``,
    from the catalog; the later in FROM on a tie) -- the many side of a
    many-to-one join; without a distributed relation, the largest.  Of
    a ``repartition`` strategy the single-hash kind (``_exchange_of``):
    the root is the relation distributed on the key, and the other
    distributed relation a build whose edge is marked exchanged.

    *Tree.*  A spanning tree grown from the root in which every build
    CAN be unique on its edge key, its own side of the edge to its
    parent.  What the catalog holds decides: a unique index on the key
    proves it; the footers' bounds disprove it by counting
    (``_repeats``; ``bounds``: alias -> ``catalog/stats.py``
    ``column_bounds`` of its table, None = nothing is disproved; nor is
    anything in a graph WITHOUT a cycle, which has one spanning tree
    and is planned as it stands); a key lane the device does not hold
    as an integer hangs no build.  Of the edges that may hang a further
    relation on the tree, first a proved one, then the one whose parent
    has the more rows (the rule the root is chosen by), then by the
    relations' names: the order of FROM and of the steps decides
    NOTHING here -- ``from supplier, customer, ...`` plans what ``from
    customer, ..., supplier`` does.  The build's own verdict
    (``REPEATED``) stays the last word, on the device.

    *Cycle filters.*  Every lane of an edge off the tree, both sides
    plain columns of device key types, is an equality the root decides
    among its cross-relation conjuncts (``DeviceJoinTree.
    cycle_filters``): its two columns ride up as payload of their
    builds, and NULL never equals (``predicate_mask``).

    The host path answers, each for its own reason: a step that is not
    an inner equi-join, a cycle filter over float / text lanes, a graph
    with no spanning tree of unique builds (an expansion join), a
    disconnected graph, no aggregate above."""
    qualified = bj.binder.qualified
    exchange = None
    if bj.strategy == "repartition":
        exchange = _exchange_of(bj, rel_rows)
        if isinstance(exchange, str):
            return exchange
    elif bj.strategy != "colocated":
        return f"strategy {bj.strategy}"
    if not bj.has_aggs:
        return "no aggregate above the join"
    if not bj.steps:
        return "no join step"
    order = [a for a, _ in bj.rels]
    tables = dict(bj.rels)
    # the graph: {(earlier, later in FROM): [(earlier's lane, later's)]}
    edges: dict = {}
    for s in bj.steps:
        others = {_rel_of(lk, qualified) for lk in s.left_keys}
        mine = {_rel_of(rk, qualified) for rk in s.right_keys}
        if s.kind not in ("inner", "cross"):
            return "an edge off the tree under an outer step" \
                if len(others) > 1 else f"{s.kind} step"
        if s.residual is not None:
            return "residual ON condition"
        if s.kind == "cross" and not s.left_keys:
            continue        # a relation the later steps' keys tie in
        if mine != {s.right_alias} or None in others:
            return "a step's key names no one relation"
        for lk, rk in zip(s.left_keys, s.right_keys):
            edges.setdefault((_rel_of(lk, qualified), s.right_alias),
                             []).append((lk, rk))
    dist = [a for a, t in bj.rels if t.is_distributed]
    root = exchange[0] if exchange else max(
        dist or order, key=lambda a: (rel_rows.get(a, 0), order.index(a)))

    # each edge from either end: (parent, child, parent's lanes, child's)
    hangs = []
    for (a, b), lanes in edges.items():
        theirs, mine = [l for l, _ in lanes], [r for _, r in lanes]
        hangs += [(a, b, theirs, mine), (b, a, mine, theirs)]

    # a graph that is a tree has ONE spanning tree and nothing to
    # choose: it is planned as it stands and its builds' verdicts decide
    choice = len(edges) >= len(order)

    def verdict(child: str, keys: list) -> str:
        if not all(_device_key_type(k.type) for k in keys):
            return "type"
        if len(keys) == 1 and isinstance(keys[0], BColumn) and any(
                ix["column"] == keys[0].name.split(".", 1)[-1]
                for ix in tables[child].unique_indexes):
            return "proved"
        if choice and bounds is not None and _repeats(
                keys, rel_rows.get(child, 0), bounds(child)):
            return "repeats"
        return "may"

    verdicts = {(a, b): verdict(b, mine) for a, b, _, mine in hangs
                if b != root}
    parent, edge = {}, {}
    while True:
        frontier = [h for h in hangs
                    if (h[0] == root or h[0] in parent)
                    and h[1] != root and h[1] not in parent
                    and verdicts[h[0], h[1]] in ("proved", "may")]
        if not frontier:
            break
        a, b, theirs, mine = min(frontier, key=lambda h: (
            verdicts[h[0], h[1]] != "proved", -rel_rows.get(h[0], 0),
            h[0], h[1]))
        parent[b], edge[b] = a, (mine, theirs)
    if len(parent) < len(order) - 1:
        # why the tree stops short: the edges that reach a relation
        # outside it, from inside
        short = {verdicts[a, b] for a, b, _, _ in hangs
                 if (a == root or a in parent) and b != root
                 and b not in parent}
        if "type" in short:
            return "a key lane the device does not hold as an integer"
        if "repeats" in short:
            return "no spanning tree of unique builds (an expansion join)"
        return "a disconnected join graph"
    # the walk the streams follow, as the steps' own tree gave it: from
    # the root, a relation's children in the order their steps stand
    at = order.index
    walk_order = [root]
    for a in walk_order:
        walk_order += sorted(
            (b for b in parent if parent[b] == a),
            key=lambda b: (max(at(a), at(b)), at(b)))
    tree = DeviceJoinTree(root, parent, edge, walk_order[:0:-1])
    for (a, b), lanes in edges.items():
        if parent.get(a) == b or parent.get(b) == a:
            continue
        for l, r in lanes:
            if not (isinstance(l, BColumn) and isinstance(r, BColumn)
                    and _device_key_type(l.type)
                    and _device_key_type(r.type)):
                return "a cycle filter over float / text lanes"
            tree.cycle_filters.append(BBinOp("=", l, r, T.BOOL_T))
    if exchange:
        _, exchanged, lane = exchange
        if parent.get(exchanged) != root:
            return "the exchanged relation does not hang on the probe's"
        # the spec's lanes are the connecting step's, and so the edge's
        tree.exchanged = (exchanged, lane)
    return tree
