"""Partial states the table's statistics prove away
(``planner/physical.py`` ``arg_facts`` / ``lower_aggregates``).

Beside every int64 sum the planner used to put a float64 overflow
shadow, and beside every argument a count of its non-NULL rows.  Where
the stripe footers' min / max / has-nulls and the row count prove that
the sum fits in int64 and that the argument is never NULL, neither is
emitted: Q1 computes 6 partial states, not 16, and Q18's block 2, not 3.

- the plans of the benchmark's own Q1 and Q18 texts on a table in
  TPC-H's shapes;
- what keeps a guard: a NULL, a bound that rows x max|arg| carries past
  2^63 - 1, a parameter, a division, a float, an intermediate that may
  wrap, a table or a shard without statistics, staged rows of an open
  transaction;
- the proof lives as long as ``table.version``: after an ingest that
  widens the bounds the cached statement plans again WITH its guard,
  and a sum that really overflows still raises;
- answers: with and without the proofs the same rows, and Q1 and Q18's
  block equal the plain references in every mode of the cluster.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import citus_tpu as ct  # noqa: E402
from benchmarks.generators import tpch_lineitem as gen  # noqa: E402
from benchmarks.references import q1 as q1_reference  # noqa: E402
from citus_tpu.errors import ExecutionError  # noqa: E402
from citus_tpu.planner import parse_sql, physical  # noqa: E402
from citus_tpu.planner.bind import bind_select  # noqa: E402

# 4,000 orders from the two ends of a 40,000-order table: order keys
# span 1..160,000, so Q18's block takes the device hash table
PARAMS = {"orders": 40_000, "parts": 200_000, "chunk_orders": 2_000,
          "lookup_sample_orders": 64}
CHUNKS = (0, 19)
DATA_SEED = 22

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tpch_sf10_1chip.json")) as fh:
    CONFIG = json.load(fh)


def _query(name, **params):
    with open(os.path.join(ROOT, "benchmarks", "queries", name)) as fh:
        return json.load(fh)["sql"].format(**params)


Q1 = _query("q1.json", DELTA=90)
Q18 = _query("q18_orders.json", QUANTITY=250)


def plan_of(cl, sql, n_params=0):
    bound = bind_select(cl.catalog, parse_sql(sql)[0], param_count=n_params)
    return physical.plan_select(cl.catalog, bound)


def states(plan):
    """The plan's partial states as text: ``count(*)``, ``sum:int64`` ..."""
    return ["count(*)" if op.kind == "count" and op.arg_index < 0
            else f"{op.kind}:{op.dtype}" for op in plan.partial_ops]


@pytest.fixture(scope="module")
def chunks():
    return [gen.generate_chunk(PARAMS, DATA_SEED, i) for i in CHUNKS]


@pytest.fixture(scope="module")
def tpch(tmp_path_factory, chunks):
    cl = ct.Cluster(str(tmp_path_factory.mktemp("elision") / "tpch"))
    cl.execute(CONFIG["ddl"])
    cl.execute("SELECT create_distributed_table('lineitem', 'l_orderkey', 8)")
    for c in chunks:
        cl.copy_from("lineitem", columns=gen.copy_columns(c))
    yield cl
    cl.close()


@pytest.mark.parametrize("sql, want, away", [
    (Q1, ["sum:int64", "count(*)"] + ["sum:int64"] * 4, (5, 5)),
    (Q18, ["sum:int64", "count(*)"], (1, 1)),
], ids=["q1", "q18_block"])
def test_benchmark_plans_on_a_tpch_shaped_table(tpch, sql, want, away):
    plan = plan_of(tpch, sql)
    assert states(plan) == want
    assert plan.proved_away == away
    # every sum / avg reads [sum, count(*)]: no third slot, no shadow
    star = states(plan).index("count(*)")
    for ex in plan.agg_extract:
        assert ex.slots[-1] == star and len(ex.slots) <= 2


def test_q1_without_statistics_is_the_sixteen_states(tpch, monkeypatch):
    monkeypatch.setattr(physical, "table_facts", lambda cat, t: None)
    plan = plan_of(tpch, Q1)
    assert sorted(states(plan)) == sorted(
        ["sum:int64"] * 5 + ["sum:float64"] * 5 + ["count:int64"] * 5
        + ["count(*)"])
    assert plan.proved_away == (0, 0)


# ------------------------------------------------ what keeps a guard

ROWS = 4_000


@pytest.fixture(scope="module")
def facts_table(tmp_path_factory):
    """``a`` 0..9, ``d`` cents, ``n`` with a NULL, ``big`` with a value
    that 4,000 rows of would carry past int64, ``f`` a float."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("elision") / "facts"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, g int, a bigint, "
               "d decimal(12,2), n bigint, big bigint, f double precision)")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    k = np.arange(ROWS)
    n = (k % 11).tolist()
    n[7] = None
    big = k % 5
    big[3] = 1 << 52     # 4,000 x 2^52 > 2^63 - 1
    cl.copy_from("t", columns={"k": k, "g": k % 3, "a": k % 10,
                               "d": (k % 997) / 100.0, "n": n, "big": big,
                               "f": k / 8.0})
    yield cl
    cl.close()


GUARDED = ["sum:int64", "count:int64", "sum:float64"]
# (aggregate, its partial states, (guards, counts) proved away)
CASES = [
    ("sum(a)", ["sum:int64", "count(*)"], (1, 1)),
    ("avg(d)", ["sum:int64", "count(*)"], (1, 1)),
    ("sum(-a)", ["sum:int64", "count(*)"], (1, 1)),
    ("sum(d * (1 - d) * (1 + d))", ["sum:int64", "count(*)"], (1, 1)),
    ("sum(a + 5 - g)", ["sum:int64", "count(*)"], (1, 1)),
    ("sum(CAST(a AS decimal(12,2)) + d)", ["sum:int64", "count(*)"], (1, 1)),
    ("sum(CASE WHEN a > 3 THEN d ELSE 0 END)", ["sum:int64", "count(*)"],
     (1, 1)),
    ("count(a)", ["count(*)"], (0, 1)),
    ("min(a)", ["min:int64", "count(*)"], (0, 1)),
    ("max(d)", ["max:int64", "count(*)"], (0, 1)),
    # a NULL keeps the count: the sum still fits
    ("sum(n)", ["sum:int64", "count:int64"], (1, 0)),
    ("count(n)", ["count:int64"], (0, 0)),
    ("min(n)", ["min:int64", "count:int64"], (0, 0)),
    # no arm taken is a NULL
    ("sum(CASE WHEN a > 3 THEN d END)", ["sum:int64", "count:int64"], (1, 0)),
    ("sum(CASE WHEN a > 3 THEN n ELSE 0 END)", ["sum:int64", "count:int64"],
     (1, 0)),
    # rows x max|arg| passes 2^63 - 1: the shadow stays, the count goes
    ("sum(big)", ["sum:int64", "count(*)", "sum:float64"], (0, 1)),
    ("sum(big * 500)", ["sum:int64", "count(*)", "sum:float64"], (0, 1)),
    # an intermediate that may leave int64 would wrap: nothing is proved
    ("sum(big * 4096 - big * 4096)", GUARDED, (0, 0)),
    # a parameter, a division, a function: nothing is proved
    ("sum(a * $1)", GUARDED, (0, 0)),
    ("sum(a / 2)", GUARDED, (0, 0)),
    ("sum(a % 3)", GUARDED, (0, 0)),
    ("sum(sign(a))", GUARDED, (0, 0)),
    # a float argument has no int64 sum to guard, and keeps its count
    ("sum(f)", ["sum:float64", "count:int64"], (0, 0)),
    ("sum(a * f)", ["sum:float64", "count:int64"], (0, 0)),
    ("sum(CAST(f AS bigint))", GUARDED, (0, 0)),
]


@pytest.mark.parametrize("agg, want, away", CASES,
                         ids=[c[0].replace(" ", "") for c in CASES])
def test_what_the_statistics_prove_of_an_argument(facts_table, monkeypatch,
                                                  agg, want, away):
    cl = facts_table
    sql = f"SELECT g, {agg} FROM t GROUP BY g ORDER BY g"
    params = [3] if "$1" in agg else None
    plan = plan_of(cl, sql, n_params=len(params or ()))
    assert states(plan) == want
    assert plan.proved_away == away
    # the answer is the guarded plan's, row for row
    r = cl.execute(sql, params=params)
    assert r.explain["partials"] == {
        "computed": len(want), "overflow_guards_proved_away": away[0],
        "null_counts_proved_away": away[1]}
    monkeypatch.setattr(physical, "table_facts", lambda cat, t: None)
    cl._plan_cache.clear()
    guarded = cl.execute(sql, params=params)
    cl._plan_cache.clear()
    assert guarded.explain["partials"]["overflow_guards_proved_away"] == 0
    assert guarded.explain["partials"]["null_counts_proved_away"] == 0
    assert r.rows == guarded.rows and len(r.rows) == 3


def test_a_table_at_a_scale_where_one_bound_passes_keeps_that_one_shadow(
        tpch, monkeypatch):
    """Q1 on the same footers under a row count of SF100's order: only
    ``sum_charge``'s bound passes 2^63 - 1, and only it keeps its
    shadow."""
    real = physical.table_facts

    def scaled(cat, table):
        f = real(cat, table)
        return type(f)(600_000_000, f.columns)

    monkeypatch.setattr(physical, "table_facts", scaled)
    plan = plan_of(tpch, Q1)
    assert sorted(states(plan)) == sorted(
        ["sum:int64"] * 5 + ["count(*)", "sum:float64"])
    assert plan.proved_away == (4, 5)
    charge = next(ex for ex in plan.agg_extract if len(ex.slots) == 3)
    assert plan.agg_extract.index(charge) == 3      # sum_charge


def test_an_empty_table_proves_nothing(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE e (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('e', 'k', 4)")
    plan = plan_of(cl, "SELECT sum(v) FROM e")
    assert states(plan) == GUARDED and plan.proved_away == (0, 0)
    assert cl.execute("SELECT sum(v), count(v) FROM e").rows == [(None, 0)]
    cl.close()


def test_a_shard_without_statistics_proves_nothing(tmp_path):
    """Two rows over four shards: some shard has no directory, so no
    footer speaks for it, and every guard stays -- until every shard
    has spoken."""
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE s (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('s', 'k', 4)")
    cl.copy_from("s", columns={"k": np.arange(2), "v": np.arange(2)})
    from citus_tpu.catalog.stats import table_facts
    assert table_facts(cl.catalog, cl.catalog.table("s")) is None
    assert states(plan_of(cl, "SELECT sum(v) FROM s")) == GUARDED
    cl.copy_from("s", columns={"k": np.arange(2, 200), "v": np.arange(2, 200)})
    facts = table_facts(cl.catalog, cl.catalog.table("s"))
    assert facts.rows == 200 and facts.columns["v"] == (0, 199, False)
    assert states(plan_of(cl, "SELECT sum(v) FROM s")) \
        == ["sum:int64", "count(*)"]
    assert cl.execute("SELECT sum(v), count(v) FROM s").rows == [(19900, 200)]
    cl.close()


def test_a_column_added_later_reads_null_in_the_older_stripes(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE l (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('l', 'k', 2)")
    cl.copy_from("l", columns={"k": np.arange(100), "v": np.arange(100)})
    cl.execute("ALTER TABLE l ADD COLUMN w bigint")
    cl.copy_from("l", columns={"k": np.arange(100, 200),
                               "v": np.arange(100, 200),
                               "w": np.arange(100, 200)})
    # no stripe holds a NULL of w, and half the rows read NULL
    assert states(plan_of(cl, "SELECT sum(w) FROM l")) \
        == ["sum:int64", "count:int64"]
    assert cl.execute("SELECT sum(w), count(w), count(*) FROM l").rows \
        == [(sum(range(100, 200)), 100, 200)]
    cl.close()


# ------------------------------ the proof lives as long as table.version

STATEMENT = "SELECT g, sum(v) FROM w GROUP BY g ORDER BY g"


@pytest.fixture()
def widening(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE w (k bigint NOT NULL, g int, v bigint)")
    cl.execute("SELECT create_distributed_table('w', 'k', 4)")
    k = np.arange(400)
    cl.copy_from("w", columns={"k": k, "g": k % 2, "v": k})
    yield cl
    cl.close()


def _partials(r):
    p = r.explain["partials"]
    return (p["computed"], p["overflow_guards_proved_away"],
            p["null_counts_proved_away"])


def test_an_ingest_that_widens_the_bounds_brings_the_guard_back(widening):
    cl = widening
    want = [(0, sum(range(0, 400, 2))), (1, sum(range(1, 400, 2)))]
    first = cl.execute(STATEMENT)
    assert first.rows == want and _partials(first) == (2, 1, 1)
    c0 = cl.counters.snapshot()
    again = cl.execute(STATEMENT)
    c1 = cl.counters.snapshot()
    assert again.rows == want and _partials(again) == (2, 1, 1)
    assert c1["plan_cache_hits"] - c0["plan_cache_hits"] == 1
    # five rows of group 1 whose sum leaves int64: the version moves,
    # the cached plan is dropped, the new one carries the shadow -- and
    # the statement raises instead of returning the wrapped sum
    cl.copy_from("w", columns={"k": np.arange(400, 405),
                               "g": np.ones(5, np.int64),
                               "v": np.full(5, 1 << 61)})
    with pytest.raises(ExecutionError, match="out of range"):
        cl.execute(STATEMENT)
    c2 = cl.counters.snapshot()
    assert c2["plan_cache_invalidations"] - c1["plan_cache_invalidations"] >= 1
    assert states(plan_of(cl, STATEMENT)) \
        == ["sum:int64", "count(*)", "sum:float64"]
    # without the rows that overflow: the guard stays (the bound is
    # still wide), the sums are exact
    cl.execute("DELETE FROM w WHERE k > 400")
    kept = cl.execute(STATEMENT)
    assert kept.rows == [want[0], (1, want[1][1] + (1 << 61))]
    assert _partials(kept) == (3, 0, 1)


def test_staged_rows_of_an_open_transaction_prove_nothing(widening):
    """A transaction's own writes reach its scans without a version
    bump: the plan cached before BEGIN must not answer for them."""
    cl = widening
    assert _partials(cl.execute(STATEMENT)) == (2, 1, 1)
    cl.execute("BEGIN")
    cl.execute(f"INSERT INTO w VALUES (400, 1, {1 << 62}), "
               f"(401, 1, {1 << 62}), (402, 1, {1 << 62})")
    with pytest.raises(ExecutionError, match="out of range"):
        cl.execute(STATEMENT)
    cl.execute("ROLLBACK")
    after = cl.execute(STATEMENT)
    assert _partials(after) == (2, 1, 1)
    assert after.rows == [(0, sum(range(0, 400, 2))),
                          (1, sum(range(1, 400, 2)))]


# ------------------------------------- answers, in every mode of the cluster

MODES = ("one_device", "four_devices", "eight_devices", "numpy_arm")


@pytest.fixture()
def mode(tpch, limit_devices, request):
    m = request.param
    if m in ("one_device", "four_devices"):
        limit_devices(1 if m == "one_device" else 4)
    if m == "numpy_arm":
        tpch.execute("SET citus.task_executor_backend = 'cpu'")
    tpch._plan_cache.clear()
    yield m
    tpch.execute("SET citus.task_executor_backend = 'tpu'")
    tpch._plan_cache.clear()


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_q1_equals_the_plain_reference(tpch, chunks, mode):
    stats = gen.Statistics(PARAMS)
    for c in chunks:
        stats.add(c)
    want = q1_reference.expected(stats.arrays(), {"DELTA": 90})
    c0 = tpch.counters.snapshot()
    r = tpch.execute(Q1)
    c1 = tpch.counters.snapshot()
    assert [tuple(row) for row in r.rows] == want and len(want) == 4
    assert r.explain["strategy"] == "direct"
    assert _partials(r) == (6, 5, 5)
    assert c1["agg_partials"] - c0["agg_partials"] == 6
    assert c1["agg_partials_proved_away"] - c0["agg_partials_proved_away"] == 10
    text = "\n".join(l for (l,) in tpch.execute(f"EXPLAIN ANALYZE {Q1}").rows)
    assert ("Partials: 6 computed, 10 proved away: 5 overflow guards, "
            "5 null counts") in text


@pytest.mark.parametrize("mode", MODES, indirect=True)
def test_q18_block_equals_a_numpy_sum(tpch, chunks, mode):
    okey = np.concatenate([c["okey"] for c in chunks])
    qty = np.concatenate([c["qty"] for c in chunks])
    keys, inverse = np.unique(okey, return_inverse=True)
    sums = np.zeros(keys.size, np.int64)
    np.add.at(sums, inverse, qty)
    from benchmarks.references.common import dec
    want = [(int(k), dec(s, 2)) for k, s in zip(keys, sums) if s > 25_000]
    assert len(want) >= 5
    c0 = tpch.counters.snapshot()
    r = tpch.execute(Q18)
    c1 = tpch.counters.snapshot()
    assert sorted(tuple(row) for row in r.rows) == want
    assert r.explain["strategy"] == "hash_host"
    assert _partials(r) == (2, 1, 1)
    assert c1["agg_partials_proved_away"] - c0["agg_partials_proved_away"] == 2
