"""Physical distributed planning.

From a BoundSelect this derives everything the executor needs:

- shard pruning: equality on the distribution column routes to one shard
  (reference: shard_pruning.c's PruneShards + the fast-path router)
- chunk pruning intervals from WHERE conjuncts (reference: the columnar
  CustomScan's ExtractPushdownClause + BuildBaseConstraint)
- the worker/combine aggregate split: every SQL aggregate lowers to a set
  of combinable partial ops — sum/count/min/max over expressions
  (reference: multi_logical_optimizer.c WorkerExtendedOpNode /
  MasterExtendedOpNode; avg becomes sum+count exactly as there)
- the GROUP BY strategy:
    * scalar  — no GROUP BY, one global group
    * direct  — composite key domain provably small (from skip-list
                stats / text dictionary sizes): exact gid scatter-add,
                combinable with a single psum — the north-star lowering
    * hash_host — unbounded key domain: the device still does scan,
                filter and agg-input evaluation; grouping happens on the
                host per shard and merges on the coordinator (analog of
                the reference pulling worker rows when aggregates can't
                be pushed down)
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import cached_property
from typing import Optional

import numpy as np

from citus_tpu import types as T
from citus_tpu.catalog import Catalog, TableMeta
from citus_tpu.catalog.hashing import hash_int64_scalar
from citus_tpu.catalog.stats import TableFacts, column_bounds, table_facts
from citus_tpu.planner.bind import AggSpec, BoundSelect
from citus_tpu.planner.bound import (
    BBinOp, BCase, BCast, BColumn, BDateTrunc, BExpr, BLiteral, BScale, BUnOp,
)
from citus_tpu.storage.reader import Interval


@dataclass(frozen=True)
class PartialOp:
    """One combinable per-shard accumulator."""
    kind: str        # sum | count | min | max | distinct | collect
    arg_index: int   # index into PhysicalPlan.agg_args; -1 = count rows
    dtype: str       # numpy dtype name of the accumulator
    # collect only: additional agg_arg indexes gathered alongside the
    # value (ordered aggregates collect (value, sortkey...) tuples)
    extra_args: tuple = ()


@dataclass
class AggExtract:
    """How to produce one SQL aggregate's value from partial slots."""
    kind: str        # sum | count | count_star | avg | min | max | registry
    slots: list[int] # indexes into partial op results
    out_type: T.ColumnType
    param: object = None  # registry-aggregate parameter (fraction, delim, ...)


@dataclass
class KeyDomain:
    lo: int          # physical minimum (code 0 is reserved for NULL)
    size: int        # number of codes including the NULL slot
    step: int = 1    # code stride in physical space (e.g. date_trunc unit)


@dataclass
class GroupMode:
    kind: str                      # scalar | direct | hash_host
    domains: list[KeyDomain] = field(default_factory=list)
    strides: list[int] = field(default_factory=list)
    n_groups: int = 1
    # hash_host only: the product of the keys' provable domains where
    # every key has one (the groups cannot outnumber it: the executor
    # bounds the device table's slots by it), else None.  Not in the
    # repr: the kernels' fingerprint holds what the kernels are built from
    domain_slots: Optional[int] = field(default=None, repr=False)


@dataclass
class PhysicalPlan:
    bound: BoundSelect
    scan_columns: list[str]
    intervals: list[Interval]
    shard_indexes: list[int]        # shards that survived pruning
    group_mode: GroupMode
    agg_args: list[BExpr]           # deduped aggregate input expressions
    partial_ops: list[PartialOp]
    agg_extract: list[AggExtract]
    # partial states ``lower_aggregates`` proved redundant from the
    # table's statistics and did not emit: (overflow guards, null counts)
    proved_away: tuple = (0, 0)
    # the width each scan column rides the device at, one entry a
    # ``scan_columns`` entry (``scan_lanes_of``); empty = every column
    # at its logical device dtype (a plan not made by ``plan_select``)
    scan_lanes: tuple = ()
    # what the executor compiled from this plan and nothing else: jitted
    # kernels by slot, the numpy arm's closures (np_filter,
    # np_final_fns), _fingerprint.  Lives with the plan so a plan cache
    # hit skips XLA recompilation (the analog of the reference's
    # prepared-statement local plan cache, local_plan_cache.c); what one
    # execution counted is in its own executor/pipeline.py PipelineStats
    runtime_cache: dict = field(default_factory=dict)
    # distribution-key literal when the router path was chosen (tenant id)
    router_key: Optional[object] = None
    # deferred router pruning (reference: Job->deferredPruning): the
    # filter pins the distribution column to $N — the executor prunes to
    # one shard once the parameter value is bound, reusing this plan and
    # its jitted kernels across values
    router_param: Optional[int] = None
    # (column, physical value, index name) when an equality conjunct hits
    # a secondary index: the scan gathers exact rows via per-stripe
    # segments instead of reading every chunk
    index_eq: Optional[tuple] = None
    # shard-map size at plan time: a mismatch against the live table at
    # execution detects a shard split's catalog flip racing the scan
    # (shard_indexes would resolve against the NEW list) -> re-plan
    table_shard_count: int = -1

    @property
    def is_router(self) -> bool:
        return len(self.shard_indexes) == 1 and self.bound.table.is_distributed

    @cached_property
    def lanes(self) -> tuple:
        """``scan_lanes``, the logical device dtypes where it is empty."""
        if self.scan_lanes:
            return self.scan_lanes
        schema = self.bound.table.schema
        return tuple(np.dtype(schema.scan_dtype(c, device=True))
                     for c in self.scan_columns)

    @cached_property
    def narrow_lanes(self) -> tuple:
        """Indexes into ``scan_columns`` of the int64 columns that ride
        the device as int32."""
        schema = self.bound.table.schema
        return tuple(i for i, (c, lane) in enumerate(
            zip(self.scan_columns, self.scan_lanes))
            if lane != schema.scan_dtype(c, device=True))

    @cached_property
    def wide_lanes(self) -> int:
        """Scan columns whose logical device dtype is int64."""
        schema = self.bound.table.schema
        return sum(schema.scan_dtype(c, device=True) == np.int64
                   for c in self.scan_columns)

    def resolve_shards(self, param_values: Optional[list]) -> list[int]:
        """Shard indexes for one execution; applies deferred pruning."""
        if self.router_param is None or param_values is None:
            return self.shard_indexes
        v = param_values[self.router_param]
        if v is None:
            return []  # dist = NULL matches nothing
        h = hash_int64_scalar(int(v))
        return [self.bound.table.route_hash(h)]


# ------------------------------------------------------------ pruning


def _conjuncts(e: Optional[BExpr]) -> list[BExpr]:
    if e is None:
        return []
    if isinstance(e, BBinOp) and e.op == "and":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def _strip_scale(e: BExpr) -> tuple[BExpr, int]:
    """Peel BScale so `col` compared at an adjusted scale still prunes."""
    if isinstance(e, BScale):
        return e.operand, e.power
    return e, 0


def extract_intervals(filter_: Optional[BExpr]) -> list[Interval]:
    """Chunk-pruning intervals from top-level AND conjuncts of the form
    column <op> literal (possibly scale-adjusted)."""
    out: list[Interval] = []
    for c in _conjuncts(filter_):
        if not (isinstance(c, BBinOp) and c.op in ("=", "<", "<=", ">", ">=")):
            continue
        left, lpow = _strip_scale(c.left)
        right, rpow = _strip_scale(c.right)
        col, lit, op = None, None, c.op
        if isinstance(left, BColumn) and isinstance(right, BLiteral):
            col, lit, colpow, litpow = left, right, lpow, rpow
        elif isinstance(right, BColumn) and isinstance(left, BLiteral):
            col, lit, colpow, litpow = right, left, rpow, lpow
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
        if col is None or lit is None or lit.value is None:
            continue
        if col.type.is_text:
            continue  # dictionary ids are not value-ordered
        # value seen by comparison = col * 10^colpow vs lit * 10^litpow
        # -> compare col against lit * 10^(litpow - colpow); only safe when
        # the adjustment is an integer scale-up of the literal
        shift = litpow - colpow
        v = lit.value
        if shift > 0:
            v = v * (10 ** shift)
        elif shift < 0:
            continue
        if op == "=":
            out.append(Interval(col.name, lo=v, hi=v))
        elif op == "<":
            out.append(Interval(col.name, hi=v, hi_inclusive=False))
        elif op == "<=":
            out.append(Interval(col.name, hi=v))
        elif op == ">":
            out.append(Interval(col.name, lo=v, lo_inclusive=False))
        elif op == ">=":
            out.append(Interval(col.name, lo=v))
    return out


def prune_shards(table: TableMeta, filter_: Optional[BExpr],
                 return_key: bool = False):
    """Route to a single shard on distcol = const (reference fast path:
    fast_path_router_planner.c); otherwise all shards."""
    all_idx = list(range(table.shard_count))
    key = None
    if not table.is_distributed or table.dist_column is None:
        return (all_idx, key) if return_key else all_idx
    for c in _conjuncts(filter_):
        if not (isinstance(c, BBinOp) and c.op == "="):
            continue
        left, right = c.left, c.right
        if isinstance(right, BColumn) and isinstance(left, BLiteral):
            left, right = right, left
        if (isinstance(left, BColumn) and left.name == table.dist_column
                and isinstance(right, BLiteral) and right.value is not None
                and not isinstance(right.value, float)):
            h = hash_int64_scalar(int(right.value))
            idx = table.route_hash(h)
            return ([idx], right.value) if return_key else [idx]
    return (all_idx, key) if return_key else all_idx


# ------------------------------------------------------ group strategy


def _key_domain(cat: Catalog, table: TableMeta, key: BExpr,
                bounds: Optional[dict[str, tuple]]) -> Optional[KeyDomain]:
    """Provable physical domain of a group key, or None.  ``bounds`` is
    None where the scan sees rows no statistic covers: only a domain the
    key's type proves (a dictionary, a boolean) stands then."""
    if isinstance(key, BColumn):
        if key.type.kind == T.UUID or T.is_uuid_lane(key.name):
            # 128-bit lane pairs have no enumerable domain
            return None
        if key.type.is_text:
            size = len(cat.dictionary(table.name, key.name))
            return KeyDomain(lo=0, size=size + 1)
        if key.type.kind == T.BOOL:
            return KeyDomain(lo=0, size=3)
        if key.type.is_float:
            # never direct-encode floats: -0.0/0.0 and NaN payloads
            # need the hash path's canonical equality, and NaN poisons
            # min/max stats (which would masquerade as "all null" here)
            return None
        if bounds is None:
            return None
        b = bounds.get(key.name)
        if b is None:
            return KeyDomain(lo=0, size=1)  # no rows / all null
        lo, hi, _ = b
        return KeyDomain(lo=int(lo), size=int(hi) - int(lo) + 2)
    if isinstance(key, BDateTrunc):
        inner = _key_domain(cat, table, key.operand, bounds)
        if inner is None:
            return None
        units_date = {"day": 1, "week": 7}
        units_ts = {"minute": 60_000_000, "hour": 3_600_000_000,
                    "day": 86_400_000_000, "week": 7 * 86_400_000_000}
        unit = (units_date if key.operand.type.kind == T.DATE else units_ts).get(key.unit)
        if unit is None:
            return None
        off = 3 * (1 if key.operand.type.kind == T.DATE else 86_400_000_000) if key.unit == "week" else 0
        lo_t = ((inner.lo + off) // unit) * unit - off
        hi_raw = inner.lo + inner.size - 2
        hi_t = ((hi_raw + off) // unit) * unit - off
        n = (hi_t - lo_t) // unit + 1
        return KeyDomain(lo=int(lo_t), size=int(n) + 1, step=int(unit))
    return None


_INT64_MAX = (1 << 63) - 1


def arg_facts(facts: Optional[TableFacts], table: TableMeta,
              e: BExpr) -> Optional[tuple[int, int, bool]]:
    """``(lo, hi, nullable)`` of an aggregate argument over every row a
    scan of ``table`` can return, proved from the footers' ``facts``:
    exact Python integers over PHYSICAL (scaled) values, ``nullable``
    False only where no column read has a NULL and no node makes one.
    None = nothing proved: no facts, a column without bounds, a
    parameter, a division, a function, a float — or an intermediate
    that may leave int64, where the kernel's arithmetic would wrap and
    the interval no longer holds."""
    if facts is None or not (e.type.is_integer or e.type.is_decimal):
        return None

    def sub(x):
        return arg_facts(facts, table, x)

    out = None
    if isinstance(e, BColumn):
        if table.schema.has(e.name):
            b = facts.columns.get(table.schema.column(e.name).storage_name)
            out = None if b is None else (int(b[0]), int(b[1]), bool(b[2]))
    elif isinstance(e, BLiteral):
        if e.value is not None:
            out = (int(e.value), int(e.value), False)
    elif isinstance(e, BUnOp) and e.op == "-":
        a = sub(e.operand)
        out = a and (-a[1], -a[0], a[2])
    elif isinstance(e, BScale) and e.power >= 0:
        a = sub(e.operand)
        out = a and (a[0] * 10 ** e.power, a[1] * 10 ** e.power, a[2])
    elif isinstance(e, BCast):
        # the casts that only rescale upward (or widen an integer)
        src, up = e.operand.type, None
        if src.is_decimal and e.type.is_decimal:
            up = e.type.scale - src.scale
        elif src.is_integer:
            up = e.type.scale if e.type.is_decimal else 0
        a = sub(e.operand) if up is not None and up >= 0 else None
        out = a and (a[0] * 10 ** up, a[1] * 10 ** up, a[2])
    elif isinstance(e, BBinOp) and e.op in ("+", "-", "*"):
        a, b = sub(e.left), sub(e.right)
        if a and b:
            if e.op == "+":
                lo, hi = a[0] + b[0], a[1] + b[1]
            elif e.op == "-":
                lo, hi = a[0] - b[1], a[1] - b[0]
            else:
                corners = [x * y for x in a[:2] for y in b[:2]]
                lo, hi = min(corners), max(corners)
            out = (lo, hi, a[2] or b[2])
    elif isinstance(e, BCase):
        # the union of its arms; no arm taken is a NULL
        arms = [sub(v) for _, v in e.whens]
        arms += [sub(e.else_)] if e.else_ is not None else []
        if all(arms):
            out = (min(a[0] for a in arms), max(a[1] for a in arms),
                   e.else_ is None or any(a[2] for a in arms))
    if out is None or max(abs(out[0]), abs(out[1])) > _INT64_MAX:
        return None
    return out


def shadow_sources(partial_ops, agg_args) -> dict:
    """{partial index: (arg index of the int64 sum it guards, divisor)}
    for the float64 shadow sums ``lower_aggregates`` puts beside every
    int64 sum: sum(CAST(x AS float8)) where sum(x) is accumulated in
    int64.  The cast of a decimal yields the logical value, x / 10**scale."""
    int_sums = {op.arg_index for op in partial_ops
                if op.kind == "sum" and op.dtype == "int64"}
    out = {}
    for i, op in enumerate(partial_ops):
        if op.kind != "sum" or op.dtype != "float64":
            continue
        arg = agg_args[op.arg_index]
        if not (isinstance(arg, BCast) and arg.type.is_float):
            continue
        src = arg.operand.type
        if not (src.is_decimal or src.is_integer):
            continue
        for j in int_sums:
            if agg_args[j] == arg.operand:
                out[i] = (j, 10.0 ** src.scale if src.is_decimal else 1.0)
    return out


def product_planes(partial_ops, agg_args) -> Optional[int]:
    """Planes of the factored one-hot product (ops/scan_agg.py
    ``_MatmulGroupSums``) that hold a plan's whole partial state, or
    None where some partial cannot ride it: a 0/1 plane for the group
    rows and one a count, eight 8-bit limbs an int64 sum; the float64
    shadow of such a sum is read from its limbs."""
    shadows = shadow_sources(partial_ops, agg_args)
    planes = 1
    for i, op in enumerate(partial_ops):
        if op.kind == "count":
            planes += op.arg_index >= 0
        elif op.kind == "sum" and op.dtype == "int64":
            planes += 8
        elif i not in shadows:
            return None
    return planes


#: slots up to which a provable key domain takes the direct table
#: whatever its partials are (the one-hot reduction to 64 slots, the
#: product for counts and int64 sums, XLA's scatter for the rest)
DIRECT_MAX_SLOTS = 65536
#: slots x planes up to which the product reduces a direct table past
#: ``DIRECT_MAX_SLOTS``: it costs 2 x slots x planes operations a row on
#: the MXU.  Read on a v5e (PERF.md section 6, PR 31): 11.7 ns a padded
#: row at 100,001 slots x 10 planes (77 % of the bf16 peak) where the
#: hash kernel took 206 ns a padded row of the same statement; at
#: 303,240 slots x 10 planes (chip_smoke.py's legs 5d and 5e, 67.1 M
#: padded rows) a warm query took 3.08 s on the product and 14.16 s on
#: the hash table, so the bound is on the safe side of the crossover
PRODUCT_MAX_WORK = 1 << 22
#: ... and rows a slot from which the dense table is worth its slots:
#: every one of them is initialised, reduced, fetched and searched on
#: every query whatever the rows hold; below this the table would
#: mostly be empty, and the hash table holds the keys that occur.
#: Provisional: no reading sits near this crossover (PERF.md section 7)
DENSE_ROWS_PER_SLOT = 2


def _product_reaches(cat: Catalog, bound: BoundSelect, slots: int,
                     planes: Optional[int]) -> bool:
    """Whether a key domain past ``DIRECT_MAX_SLOTS`` still takes the
    direct table: every partial rides the MXU product, the product's
    work is bounded, and the catalog's row count says the rows meet
    each slot more than once (groups met again in every batch and on
    every shard: matches, where a hash table would take them one by
    one)."""
    if planes is None or slots * planes > PRODUCT_MAX_WORK:
        return False
    from citus_tpu.catalog.stats import table_row_count
    return table_row_count(cat, bound.table) >= DENSE_ROWS_PER_SLOT * slots


def choose_group_mode(cat: Catalog, bound: BoundSelect, direct_limit: int = 0,
                      planes: Optional[int] = None,
                      trust_stats: bool = True) -> GroupMode:
    """``direct_limit``: ``citus.direct_gid_limit``; 0 (auto, the
    default) leaves the bound on the direct table's slots to the plan:
    ``DIRECT_MAX_SLOTS``, and past it what ``_product_reaches``.  A
    positive value is the operator's bound, and nothing passes it.
    ``planes``: ``product_planes`` of the plan's partials.  Without
    ``trust_stats`` (``plan_select``: the scan sees staged rows, or a
    batch belied them) no key's domain is bounded by the statistics."""
    # distinct and collect-based aggregates need exact value multisets:
    # only the host grouping path carries them (reference:
    # worker_partial_agg cannot combine DISTINCT either and falls back to
    # pulling rows)
    from citus_tpu.planner.aggregates import AGG_REGISTRY
    if any(a.distinct or (a.kind in AGG_REGISTRY
                          and AGG_REGISTRY[a.kind].needs_exact)
           for a in bound.aggs):
        return GroupMode(kind="hash_host")
    if not bound.group_keys:
        return GroupMode(kind="scalar")
    # sketch partials whose device shape exists only ungrouped route
    # grouped queries through host grouping
    if any(a.kind in AGG_REGISTRY and AGG_REGISTRY[a.kind].host_grouped
           for a in bound.aggs):
        return GroupMode(kind="hash_host")
    bounds = column_bounds(cat, bound.table) if trust_stats else None
    domains: list[KeyDomain] = []
    for key in bound.group_keys:
        d = _key_domain(cat, bound.table, key, bounds)
        if d is None:
            return GroupMode(kind="hash_host")
        domains.append(d)
    total = 1
    for d in domains:
        total *= d.size
    if direct_limit:
        fits = total <= direct_limit
    else:
        fits = (total <= DIRECT_MAX_SLOTS
                or _product_reaches(cat, bound, total, planes))
    if not fits:
        return GroupMode(kind="hash_host", domain_slots=total)
    strides = []
    acc = 1
    for d in reversed(domains):
        strides.append(acc)
        acc *= d.size
    strides.reverse()
    return GroupMode(kind="direct", domains=domains, strides=strides, n_groups=total)


# ------------------------------------------------------ aggregate split


def lower_aggregates(aggs: list[AggSpec], prove=None, rows: int = 0
                     ) -> tuple[list[BExpr], list[PartialOp],
                                list[AggExtract], tuple[int, int]]:
    """SQL aggregates -> deduped partial ops (the worker half),
    extraction recipes (the combine/final half) and the partial states
    proved away: ``(overflow guards, null counts)`` not emitted.

    ``prove(arg)`` is ``arg_facts`` over the scanned table's statistics
    and ``rows`` their bound on the rows a scan returns (``plan_select``;
    a join, whose rows multiply, passes none).  A partial state they
    prove redundant is not emitted: the NULL count of an argument that
    has no NULL is ``count(*)``, and an int64 sum that ``rows`` times the
    argument's largest magnitude cannot push out of int64 — exact
    integer arithmetic, no margin — carries no float64 shadow."""
    agg_args: list[BExpr] = []
    partials: list[PartialOp] = []
    extracts: list[AggExtract] = []
    gone: list[tuple] = []   # (guard | count, argument), each once

    def arg_slot(e: BExpr) -> int:
        for i, a in enumerate(agg_args):
            if a == e:
                return i
        agg_args.append(e)
        return len(agg_args) - 1

    def partial_slot(kind: str, arg_index: int, dtype: str,
                     extra_args: tuple = ()) -> int:
        op = PartialOp(kind, arg_index, dtype, tuple(extra_args))
        for i, p in enumerate(partials):
            if p == op:
                return i
        partials.append(op)
        return len(partials) - 1

    def proved(what: str, arg: BExpr) -> None:
        if (what, arg) not in gone:
            gone.append((what, arg))

    def null_count(arg: BExpr, facts) -> int:
        if facts is not None and not facts[2]:
            proved("count", arg)
            return partial_slot("count", -1, "int64")
        return partial_slot("count", arg_slot(arg), "int64")

    for spec in aggs:
        if spec.kind == "count_star":
            s = partial_slot("count", -1, "int64")
            extracts.append(AggExtract("count_star", [s], spec.out_type))
            continue
        acc_dtype = "float64" if spec.arg.type.is_float else "int64"
        facts = prove(spec.arg) if prove else None
        if spec.kind == "count" and spec.distinct:
            s = partial_slot("distinct", arg_slot(spec.arg), "int64")
            extracts.append(AggExtract("count_distinct", [s], spec.out_type))
        elif spec.kind == "count":
            s = null_count(spec.arg, facts)
            extracts.append(AggExtract("count", [s], spec.out_type))
        elif spec.kind in ("sum", "avg"):
            s = partial_slot("sum", arg_slot(spec.arg), acc_dtype)
            c = null_count(spec.arg, facts)
            slots = [s, c]
            if acc_dtype == "int64" and spec.arg.type.is_numeric:
                # overflow guard (round-4 weak #7): an int64 partial sum
                # wraps silently; a float64 SHADOW sum of the same
                # argument rides alongside — int64 addition is exact mod
                # 2^64, so the final value is correct iff the true sum
                # fits, and |shadow| >= 2^62 proves it cannot (float
                # error is relative, far below the 2x margin).  The
                # reference's NUMERIC never overflows; we error instead
                # of silently wrapping.  No shadow where the statistics
                # prove that the sum fits.
                if facts is not None and \
                        rows * max(abs(facts[0]), abs(facts[1])) <= _INT64_MAX:
                    proved("guard", spec.arg)
                else:
                    fa = arg_slot(BCast(spec.arg, T.FLOAT64_T))
                    slots.append(partial_slot("sum", fa, "float64"))
            extracts.append(AggExtract(spec.kind, slots, spec.out_type))
        elif spec.kind in ("min", "max"):
            dt = str(spec.arg.type.device_dtype)
            s = partial_slot(spec.kind, arg_slot(spec.arg), dt)
            c = null_count(spec.arg, facts)
            extracts.append(AggExtract(spec.kind, [s, c], spec.out_type))
        else:
            from citus_tpu.planner.aggregates import AGG_REGISTRY
            defn = AGG_REGISTRY.get(spec.kind)
            if defn is None:
                raise AssertionError(spec.kind)
            arg_slot(spec.arg)
            extracts.append(defn.lower(spec, arg_slot, partial_slot))
    away = (sum(w == "guard" for w, _ in gone),
            sum(w == "count" for w, _ in gone))
    return agg_args, partials, extracts, away


# ------------------------------------------------------------ lanes


_INT32 = np.dtype(np.int32)
_INT32_MIN, _INT32_MAX = -(1 << 31), (1 << 31) - 1


def scan_lanes_of(facts: Optional[TableFacts], table: TableMeta,
                  scan_columns: list[str]) -> tuple:
    """The device form of each scan column: as wide as the table's
    statistics prove it has to be.  The column's logical device dtype,
    except int32 where that is int64 and the footers bound every stored
    value inside int32 (``facts.columns[storage name]``: the physical,
    scaled values of every row of one ``table.version``).  Whether the
    column has NULLs does not matter: the validity bit decides what a
    NULL slot means, not what it holds.  No facts, a column absent from
    them, a float, a narrower integer: the logical dtype."""
    schema = table.schema
    lanes = []
    for c in scan_columns:
        lane = np.dtype(schema.scan_dtype(c, device=True))
        if facts is not None and lane == np.int64 and schema.has(c):
            b = facts.columns.get(schema.column(c).storage_name)
            if b is not None and _INT32_MIN <= int(b[0]) \
                    and int(b[1]) <= _INT32_MAX:
                lane = _INT32
        lanes.append(lane)
    return tuple(lanes)


# ------------------------------------------------------------ entry


def _deferred_router_param(table: TableMeta, filter_: Optional[BExpr]) -> Optional[int]:
    """distcol = $N in the filter -> parameter index for deferred pruning."""
    from citus_tpu.planner.bound import BParam
    if not table.is_distributed or table.dist_column is None:
        return None
    for c in _conjuncts(filter_):
        if not (isinstance(c, BBinOp) and c.op == "="):
            continue
        left, right = c.left, c.right
        if isinstance(right, BColumn) and isinstance(left, BParam):
            left, right = right, left
        if (isinstance(left, BColumn) and left.name == table.dist_column
                and isinstance(right, BParam) and not right.type.is_float):
            return right.index
    return None


def _index_eq(table: TableMeta, filter_: Optional[BExpr]):
    """(column, physical value, index name) when an AND conjunct pins an
    indexed column to a literal — the index point-lookup path (reference:
    an index path winning over ColumnarScan in the planner,
    columnar_customscan.c costing vs btree)."""
    for c in _conjuncts(filter_):
        if not (isinstance(c, BBinOp) and c.op == "="):
            continue
        left, right = c.left, c.right
        if isinstance(right, BColumn) and isinstance(left, BLiteral):
            left, right = right, left
        if not (isinstance(left, BColumn) and isinstance(right, BLiteral)
                and right.value is not None):
            continue
        ix = table.index_on(left.name)
        if ix is not None:
            return (left.name, right.value, ix["name"])
    return None


def sees_staged_rows(table: TableMeta) -> bool:
    """Whether this thread's scans of ``table`` return rows no live
    footer covers: the staged writes of its own open transaction, which
    bump no version.  No statistic is a fact about such a scan."""
    from citus_tpu.storage.overlay import current_overlay
    txn = current_overlay()
    return txn is not None and table.name in txn.tables


def plan_select(cat: Catalog, bound: BoundSelect, *, direct_limit: int = 0,
                trust_stats: bool = True) -> PhysicalPlan:
    """``trust_stats`` False: a plan that takes nothing from the table's
    statistics -- every guard, no bounded group domain, every scan
    column at its logical width -- as for a scan that sees staged rows:
    what a statement runs on once a batch belied them."""
    intervals = extract_intervals(bound.filter)
    shard_indexes, router_key = prune_shards(bound.table, bound.filter, return_key=True)
    trust_stats = trust_stats and not sees_staged_rows(bound.table)
    facts = (table_facts(cat, bound.table)
             if bound.aggs and trust_stats else None)
    agg_args, partial_ops, agg_extract, proved_away = lower_aggregates(
        bound.aggs, lambda e: arg_facts(facts, bound.table, e),
        facts.rows if facts else 0)
    group_mode = choose_group_mode(cat, bound, direct_limit,
                                   product_planes(partial_ops, agg_args),
                                   trust_stats)
    return PhysicalPlan(
        bound=bound,
        scan_columns=bound.scan_columns,
        intervals=intervals,
        shard_indexes=shard_indexes,
        group_mode=group_mode,
        agg_args=agg_args,
        partial_ops=partial_ops,
        agg_extract=agg_extract,
        proved_away=proved_away,
        scan_lanes=scan_lanes_of(facts, bound.table, bound.scan_columns),
        router_key=router_key,
        router_param=_deferred_router_param(bound.table, bound.filter),
        index_eq=_index_eq(bound.table, bound.filter),
        table_shard_count=len(bound.table.shards),
    )
