"""The hourly rollup over taxi trips (BASELINE config 3) and the group
reduction it forced: for group tables in the thousands the direct mode
sums its counts and int64 sums as one factored one-hot product
(ops/scan_agg.py ``_MatmulGroupSums``), where twelve groups keep the
masked one-hot and the numpy arm its scatter.  On the CPU: answers and
counts, never a speed."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import citus_tpu as ct
from benchmarks.generators import nyctaxi_trips as gen
from benchmarks.references import taxi_hourly
from citus_tpu.ops import scan_agg
from citus_tpu.planner import parse_sql
from citus_tpu.planner.bind import bind_select
from citus_tpu.planner.physical import plan_select

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as fh:
        return json.load(fh)


CONFIG = load("configs", "nyctaxi_hourly_1chip.json")
SQL = load("queries", "taxi_hourly.json")["sql"]
PARAMS = dict(CONFIG["generator"], orders=3000, chunk_orders=1000)
SLOTS = 181 * 24 + 1        # every hour of the span and the NULL slot


@pytest.fixture(scope="module")
def trips(tmp_path_factory):
    cl = ct.Cluster(str(tmp_path_factory.mktemp("taxi") / "db"))
    cl.execute(CONFIG["ddl"])
    cl.execute(f"SELECT create_distributed_table('trips', "
               f"'{CONFIG['distribution_column']}', 8)")
    stats = gen.Statistics(PARAMS)
    for i in range(gen.n_chunks(PARAMS)):
        chunk = gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        stats.add(chunk)
        cl.copy_from("trips", columns=gen.copy_columns(chunk))
    yield cl, stats.arrays()
    cl.close()


def test_statement_equals_the_reference_row_for_row(trips):
    cl, stats = trips
    want = sorted(taxi_hourly.expected(stats, {}))
    assert 1500 < len(want) < 3000 and sum(r[1] for r in want) == 3000
    r = cl.execute(SQL)
    assert r.columns == ["hour", "trips", "avg_fare", "avg_total"]
    assert sorted(r.rows) == want
    assert r.explain["strategy"] == "direct"
    assert r.explain["pipeline"]["direct_groups"] == SLOTS
    assert r.explain["pipeline"]["direct_groups_out"] == len(want)


@pytest.mark.parametrize("arm", ["numpy", "mesh4", "one_device"])
def test_statement_on_every_arm(trips, arm, limit_devices):
    cl, stats = trips
    if arm == "numpy":
        cl.execute("SET citus.task_executor_backend = 'cpu'")
    else:
        limit_devices(4 if arm == "mesh4" else 1)
    try:
        assert sorted(cl.execute(SQL).rows) == sorted(
            taxi_hourly.expected(stats, {}))
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")


def test_plan_is_direct_and_explain_names_the_reduction(trips):
    cl, stats = trips
    plan = plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(SQL)[0]))
    assert plan.group_mode.kind == "direct"
    assert plan.group_mode.n_groups == SLOTS
    assert plan.group_mode.domains[0].step == 3_600_000_000
    lines = [row[0] for row in cl.execute("EXPLAIN " + SQL).rows]
    assert any(f"Direct GroupBy (groups: {SLOTS}, reduce: matmul" in ln
               for ln in lines), lines
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        lines = [row[0] for row in cl.execute("EXPLAIN " + SQL).rows]
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    assert any("reduce: scatter" in ln for ln in lines), lines
    small = [row[0] for row in cl.execute(
        "EXPLAIN SELECT payment_type, count(*) FROM trips GROUP BY 1").rows]
    assert any("reduce: onehot" in ln for ln in small), small


def test_counters_and_the_analyze_line(trips):
    cl, stats = trips
    groups = int(np.count_nonzero(stats["hourly"][:, 0]))
    before = cl.counters.snapshot()
    lines = [row[0] for row in cl.execute("EXPLAIN ANALYZE " + SQL).rows]
    after = cl.counters.snapshot()
    assert after["direct_groups"] - before.get("direct_groups", 0) == SLOTS
    assert after["direct_groups_out"] - before.get(
        "direct_groups_out", 0) == groups
    assert any(f"Direct: group slots {SLOTS}, groups {groups}" in ln
               for ln in lines), lines


@pytest.mark.parametrize("n_groups,want", [
    (1, "onehot"), (12, "onehot"), (64, "onehot"), (65, "matmul"),
    (4345, "matmul"), (8193, "matmul"), (65536, "matmul")])
def test_reduction_follows_the_group_count(n_groups, want):
    assert scan_agg.direct_reduction(n_groups, False) == want
    assert scan_agg.direct_reduction(n_groups, True) == "scatter"


# ---- the reduction against the one-hot and the numpy scatter --------------

N_ROWS = 5000
AGG_SQL = ("SELECT k, sum(v), count(v), min(v), max(v), sum(d), avg(d), "
           "count(*) FROM g{G} GROUP BY k")


@pytest.fixture(scope="module")
def keyed(tmp_path_factory):
    """plan(G): a direct plan over a table whose key domain has G slots
    (G - 1 values and the NULL slot), from the skip lists of two rows."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("keyed") / "db"))
    plans = {}

    def plan(G):
        if G not in plans:
            cl.execute(f"CREATE TABLE g{G} (k int, v bigint, d decimal(12,2))")
            cl.execute(f"SELECT create_distributed_table('g{G}', 'v', 2)")
            cl.copy_from(f"g{G}", columns={
                "k": np.array([0, G - 2], np.int32), "v": np.arange(2),
                "d": np.array([1.5, 2.5])})
            plans[G] = plan_select(cl.catalog, bind_select(
                cl.catalog, parse_sql(AGG_SQL.format(G=G))[0]))
            assert plans[G].group_mode.n_groups == G
        return plans[G]
    yield plan
    cl.close()


def batch(plan, G, layout, seed, big=False):
    """One padded batch in the executor's convention: NULL keys and
    values, a masked tail, keys in ``layout`` order."""
    rng = np.random.default_rng([seed, G])
    k = rng.integers(0, G - 1, N_ROWS)
    if layout == "time_order":
        k.sort()
    elif layout == "one_group":
        k[:] = G - 2
    if big:
        # a handful of rows a group, each near +-2**61: sums wrap int64
        # or come close, which is what the float64 shadow is there for
        v = rng.choice([-1, 1], N_ROWS) * ((1 << 61) - rng.integers(0, 9, N_ROWS))
        d = rng.choice([-1, 1], N_ROWS) * ((1 << 62) - rng.integers(0, 9, N_ROWS))
    else:
        v = rng.integers(-10 ** 12, 10 ** 12, N_ROWS)
        d = rng.integers(0, 10 ** 7, N_ROWS)
    values = {"k": k.astype(np.int32), "v": v.astype(np.int64),
              "d": d.astype(np.int64)}
    valid = {c: rng.random(N_ROWS) < 0.9 for c in values}
    mask = np.arange(N_ROWS) < N_ROWS - 700
    cols = tuple(np.where(valid[c] & mask, values[c], 0)
                 for c in plan.scan_columns)
    return cols, tuple(valid[c] for c in plan.scan_columns), mask


def run_worker(plan, xp, args, monkeypatch, onehot_max=None):
    if onehot_max is not None:
        monkeypatch.setattr(scan_agg, "ONEHOT_MAX_GROUPS", onehot_max)
    worker = scan_agg.build_worker_fn(plan, xp)
    out = jax.jit(worker)(*args) if xp is jnp else worker(*args)
    monkeypatch.undo()
    return [np.asarray(o) for o in out]


def assert_same(plan, got, want):
    assert len(got) == len(want) == len(plan.partial_ops) + 1
    for op, g, w in zip(list(plan.partial_ops) + [None], got, want):
        if op is not None and op.dtype == "float64":
            # the shadow of an int64 sum: a guard, held to float error
            scale = max(np.abs(w).max(), 1.0)
            assert np.allclose(g, w, rtol=0, atol=1e-9 * scale), op
        else:
            assert g.dtype == w.dtype and (g == w).all(), op


@pytest.mark.parametrize("layout", ["time_order", "shuffled", "one_group"])
@pytest.mark.parametrize("G", [12, 4345, 8193, 65536])
def test_product_equals_onehot_and_scatter(keyed, monkeypatch, G, layout):
    plan = keyed(G)
    args = batch(plan, G, layout, seed=1)
    product = run_worker(plan, jnp, args, monkeypatch, onehot_max=0)
    assert_same(plan, product, run_worker(plan, np, args, monkeypatch))
    # the code the small tables keep: the masked one-hot up to 8,192
    # slots, XLA's scatter above
    assert_same(plan, product,
                run_worker(plan, jnp, args, monkeypatch, onehot_max=1 << 30))
    rows = product[-1]
    assert rows.sum() == np.count_nonzero(args[2])
    if layout == "one_group":
        assert np.count_nonzero(rows) <= 2        # the group and NULL keys


@pytest.mark.parametrize("G", [12, 4345, 8193, 65536])
def test_product_near_the_overflow_guard(keyed, monkeypatch, G):
    plan = keyed(G)
    args = batch(plan, G, "shuffled", seed=2, big=True)
    product = run_worker(plan, jnp, args, monkeypatch, onehot_max=0)
    assert_same(plan, product, run_worker(plan, np, args, monkeypatch))
    # the shadow of sum(d) passes 2**62 / 100 in some group: the guard
    # the finalize step reads is still there to trip
    shadows = [np.abs(o).max() for op, o in zip(plan.partial_ops, product)
               if op.dtype == "float64"]
    assert len(shadows) == 2 and max(shadows) >= float(1 << 62) / 100


# ---- the host's half at thousands of groups --------------------------------


@pytest.mark.parametrize("wide", [False, True])
def test_decimal_average_of_every_group_at_once_is_exact(wide):
    """``finalize._avg_scaled`` against Decimal's own ROUND_HALF_UP: ties
    go away from zero, empty groups read 0, and sums too wide for int64
    once scaled by 10**6 take the route through Python integers."""
    import decimal
    from citus_tpu.executor.finalize import _avg_scaled
    rng = np.random.default_rng(7)
    s = rng.integers(-10 ** 6, 10 ** 6, 4000) * 10 ** 6 \
        + 500_000 * rng.integers(0, 2, 4000)
    c = rng.integers(0, 50, 4000)
    s[:6], c[:6] = [5, -5, 15, -15, 1, -1], [2, 2, 2, 2, 3, 3]
    if wide:
        s[10:12], c[10:12] = [10 ** 13, -10 ** 13 - 500], 1000
    got = _avg_scaled(s, c)
    for i in np.nonzero(c)[0]:
        q = decimal.Decimal(int(s[i])) * 10 ** 6 / decimal.Decimal(int(c[i]))
        assert got[i] == int(q.to_integral_value(decimal.ROUND_HALF_UP)), i
    assert (got[c == 0] == 0).all()


def test_floor_division_by_a_date_trunc_unit_is_exact():
    """``_floor_div_small_quotient`` against int64 ``//`` over every
    quotient the direct mode can hold, at the edges of each bucket."""
    hour, n = 3_600_000_000, 1 << 16
    q = np.repeat(np.arange(n, dtype=np.int64), 4)
    d = q * hour + np.tile(np.array([0, 1, hour // 2, hour - 1]), n)
    for step, dd in ((hour, d), (7, q * 7 + np.tile(np.arange(4), n)),
                     (7 * 86_400_000_000, d // hour * 7 * 86_400_000_000 + 5)):
        got = jax.jit(lambda x, step=step: scan_agg._floor_div_small_quotient(
            jnp, x, step, n))(dd)
        assert (np.asarray(got) == dd // step).all(), step
    # rows the caller masks (padding, NULL keys) stay in [-1, q_max + 1]
    wild = np.array([-2 ** 62, -hour - 1, -1, 2 ** 62, n * hour * 3])
    got = np.asarray(scan_agg._floor_div_small_quotient(jnp, wild, hour, n))
    assert got.min() >= -2 and got.max() <= n + 2


def test_megabatched_riders_share_the_product(trips):
    """The vmap-lifted fused worker (executor/megabatch.py) at 4,345
    slots: riders that differ in ``$1`` share one scan, each gets the
    rows the serial path gives it."""
    import threading
    cl, _ = trips
    sql = ("SELECT date_trunc('hour', pickup_datetime), count(*), "
           "sum(fare_amount), avg(total_amount) FROM trips "
           "WHERE passenger_count = $1 GROUP BY 1")
    want = {p: sorted(cl.execute(sql, params=[p]).rows) for p in (1, 2, 5)}
    assert len(want[1]) > len(want[5]) > 0
    cl.execute("SET citus.megabatch_window_ms = 300")
    try:
        for _ in range(4):
            bar, got, mu = threading.Barrier(3), {}, threading.Lock()

            def run(p):
                bar.wait()
                r = cl.execute(sql, params=[p])
                with mu:
                    got[p] = (sorted(r.rows),
                              r.explain.get("megabatch", {}).get("occupancy", 1))
            ts = [threading.Thread(target=run, args=(p,)) for p in want]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert {p: rows for p, (rows, _) in got.items()} == want
            if max(occ for _, occ in got.values()) > 1:
                break
        assert max(occ for _, occ in got.values()) > 1, got
    finally:
        cl.execute("SET citus.megabatch_window_ms = 0")
