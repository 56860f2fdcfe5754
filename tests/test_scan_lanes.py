"""The device form of a scan column is as wide as the table's statistics
prove it has to be (planner/physical.py ``scan_lanes_of``,
executor/scan_loop.py ``jit_narrow``, ops/scan_agg.py ``scan_env_fn``).

- the lane chosen: int32 for an int64 column whose footers bound every
  stored value inside int32, at the boundaries to the digit; the logical
  dtype for everything else (no facts, a shard without a footer, staged
  rows, a float, an int32 column);
- answers: statements of the benchmark cells' shapes give the rows they
  give at logical widths and on the numpy arm, on one device, under
  ``shard_map`` on a mesh of 4 of the harness's 8 CPU devices, under
  megabatch's ``vmap`` and on the hash path;
- the programs: Q1's lowered module takes no 64-bit batch column, and the
  hash kernel's gather of a narrowed key moves 32-bit lanes;
- the guard: a batch that belies forged facts is caught, counted and
  answered at full width; nothing of it is cached;
- the cache: a second run retraces nothing and hits; an entry put at
  narrow lanes is not served to a plan at logical widths, and books the
  bytes it holds;
- the counters and EXPLAIN ANALYZE's pipeline line.

Counts and answers on the CPU backend, never a time.
"""

import re
import threading
from decimal import Decimal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.catalog.stats import TableFacts
from citus_tpu.executor import executor as X
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.ops.hash_agg import build_fused_hash_worker, empty_hash_state
from citus_tpu.ops.scan_agg import build_fused_worker_fn
from citus_tpu.planner import parse_sql, physical
from citus_tpu.planner.bind import bind_select

I32_MAX, I32_MIN = (1 << 31) - 1, -(1 << 31)
N_ROWS, SHARDS = 6000, 8
T0 = 1_388_534_400_000_000          # 2014-01-01 00:00, microseconds
HOUR = 3_600_000_000

Q1 = ("SELECT l_returnflag, l_linestatus, sum(l_quantity), "
      "sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)), "
      "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)), "
      "avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*) "
      "FROM li WHERE l_shipdate <= date '1998-12-01' - interval '90' day "
      "GROUP BY l_returnflag, l_linestatus ORDER BY 1, 2")
Q6 = ("SELECT sum(l_extendedprice * l_discount) FROM li "
      "WHERE l_shipdate >= date '1994-01-01' "
      "AND l_shipdate < date '1994-01-01' + interval '1' year "
      "AND l_discount BETWEEN {d} - 0.01 AND {d} + 0.01 AND l_quantity < 24")
# statement -> int64 scan columns that ride at 32 bits, of how many
STATEMENTS = {
    "q1": (Q1, 4, 4),
    "q6": (Q6.format(d="0.06"), 3, 3),
    "q15_view": ("SELECT l_suppkey, sum(l_extendedprice * (1 - l_discount)) "
                 "FROM li WHERE l_shipdate >= date '1996-01-01' "
                 "AND l_shipdate < date '1996-01-01' + interval '3' month "
                 "GROUP BY l_suppkey ORDER BY 1", 3, 3),
    "q18_block": ("SELECT l_orderkey, sum(l_quantity) FROM li "
                  "GROUP BY l_orderkey HAVING sum(l_quantity) > 120 "
                  "ORDER BY 1", 2, 2),
    # pickup_datetime does not fit: two of three
    "hourly": ("SELECT date_trunc('hour', pickup_datetime), count(*), "
               "avg(fare_amount), avg(total_amount) FROM trips "
               "GROUP BY 1 ORDER BY 1", 2, 3),
    "min_max_avg": ("SELECT l_linestatus, min(l_extendedprice), "
                    "max(l_extendedprice), avg(l_tax), max(l_orderkey) "
                    "FROM li GROUP BY l_linestatus ORDER BY 1", 3, 3),
    # (an ``int`` column's device form is int64 too)
    "nulls": ("SELECT g, count(v), sum(v), min(v), max(v), avg(w), count(*) "
              "FROM nl GROUP BY g ORDER BY 1", 3, 3),
    "nulls_hash": ("SELECT v, count(*), sum(w) FROM nl GROUP BY v ORDER BY 1",
                   2, 2),
}
HASHED = {"q18_block", "nulls_hash"}


def _logical(real):
    return lambda facts, table, columns: real(None, table, columns)


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    """One cluster with the cells' tables in miniature; ``data`` keeps
    what went in, for the references."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("lanes") / "db"))
    rng = np.random.default_rng(41)
    n = N_ROWS
    cl.execute(
        "CREATE TABLE li (l_orderkey bigint NOT NULL, l_suppkey bigint, "
        "l_quantity decimal(12,2), l_extendedprice decimal(12,2), "
        "l_discount decimal(12,2), l_tax decimal(12,2), l_returnflag text, "
        "l_linestatus text, l_shipdate date)")
    cl.execute(f"SELECT create_distributed_table('li', 'l_orderkey', {SHARDS})")
    li = {
        # sparse keys up to SF10's 6.0e7: far more slots than rows
        "l_orderkey": np.repeat(rng.choice(60_000_000, n // 4, False), 4),
        "l_suppkey": rng.integers(1, 2001, n),
        "l_quantity": rng.integers(1, 51, n).astype(float),
        "l_extendedprice": rng.integers(90_000, 10_495_000, n) / 100,
        "l_discount": rng.integers(0, 11, n) / 100,
        "l_tax": rng.integers(0, 9, n) / 100,
        "l_returnflag": rng.choice(["A", "N", "R"], n).tolist(),
        "l_linestatus": rng.choice(["F", "O"], n).tolist(),
        "l_shipdate": rng.integers(8036, 10560, n).astype(np.int32)}
    cl.copy_from("li", columns=li)
    cl.execute("CREATE TABLE trips (trip_id bigint NOT NULL, "
               "pickup_datetime timestamp, fare_amount numeric(10,2), "
               "total_amount numeric(10,2))")
    cl.execute(f"SELECT create_distributed_table('trips', 'trip_id', {SHARDS})")
    trips = {"trip_id": np.arange(n),
             "pickup_datetime": T0 + rng.integers(0, 72 * HOUR, n),
             "fare_amount": rng.integers(250, 20_000, n) / 100,
             "total_amount": rng.integers(300, 30_000, n) / 100}
    cl.copy_from("trips", columns=trips)
    # NULLs in a narrowed key / argument (v) and a narrowed decimal (w)
    cl.execute("CREATE TABLE nl (k bigint NOT NULL, g int, v bigint, "
               "w decimal(12,2))")
    cl.execute(f"SELECT create_distributed_table('nl', 'k', {SHARDS})")
    v = rng.integers(-3_000_000, 3_000_000, n) * 700
    w = rng.integers(-10 ** 9, 10 ** 9, n)
    rows = [(int(k), int(k % 7), None if k % 11 == 0 else int(v[k]),
             None if k % 13 == 0 else Decimal(int(w[k])) / 100)
            for k in range(n)]
    for at in range(0, n, 1000):
        cl.execute("INSERT INTO nl VALUES " + ", ".join(
            "(%s)" % ", ".join("NULL" if x is None else str(x) for x in r)
            for r in rows[at:at + 1000]))
    cl.data = {"li": li, "trips": trips, "nl": rows}
    yield cl
    cl.close()
    GLOBAL_CACHE.clear()


def _plan(cl, sql, **kw):
    return physical.plan_select(
        cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]), **kw)


def _lanes(plan):
    return dict(zip(plan.scan_columns, (str(l) for l in plan.lanes)))


# ------------------------------------------------------------ the lane chosen


@pytest.mark.parametrize("column, bounds, lane", [
    ("l_quantity", (0, I32_MAX, False), "int32"),
    ("l_quantity", (0, I32_MAX + 1, False), "int64"),
    ("l_quantity", (I32_MIN, 5, False), "int32"),
    ("l_quantity", (I32_MIN - 1, 5, False), "int64"),
    ("l_quantity", (I32_MIN, I32_MAX, True), "int32"),    # NULLs present
    ("l_quantity", None, "int64"),              # absent from the facts
    ("l_orderkey", (1, 60_000_000, False), "int32"),
    ("l_shipdate", (8036, 10560, False), "int32"),        # an int32 column
    ("l_returnflag", (0, 3, False), "int32"),             # dictionary codes
], ids=["max_fits", "max_one_past", "min_fits", "min_one_past", "with_nulls",
        "no_bounds", "bigint_key", "date", "text"])
def test_the_lane_at_the_boundaries(db, column, bounds, lane):
    table = db.catalog.table("li")
    name = table.schema.column(column).storage_name
    facts = TableFacts(N_ROWS, {} if bounds is None else {name: bounds})
    got, = physical.scan_lanes_of(facts, table, [column])
    assert str(got) == lane
    # no facts at all: the logical dtype
    logical, = physical.scan_lanes_of(None, table, [column])
    assert logical == table.schema.scan_dtype(column, device=True)


def test_a_float_column_keeps_its_lane(tmp_cluster):
    cl = tmp_cluster
    cl.execute("CREATE TABLE f (k bigint NOT NULL, x float8, y real)")
    cl.execute("SELECT create_distributed_table('f', 'k', 2)")
    cl.copy_from("f", columns={"k": np.arange(64), "x": np.arange(64) / 3,
                               "y": np.arange(64, dtype=np.float32)})
    plan = _plan(cl, "SELECT sum(x), sum(y), max(k) FROM f")
    assert _lanes(plan) == {"k": "int32", "x": "float64", "y": "float32"}
    assert plan.narrow_lanes == (plan.scan_columns.index("k"),)
    assert plan.wide_lanes == 1
    r = cl.execute("SELECT sum(x), sum(y), max(k) FROM f")
    assert r.rows[0][2] == 63 and r.rows[0][0] == pytest.approx(672.0)


def test_real_footers_nulls_and_what_proves_nothing(db, tmp_path):
    """From the table's own footers: a NULL-bearing column that fits is
    narrowed (a NULL slot holds what the reader left there, zero from
    this reader: the validity bit decides, so its value is never looked
    at, by the kernels or by the convert's check); a table one of whose
    shards has no footer, and a scan that sees staged rows, ride at the
    logical widths."""
    assert _lanes(_plan(db, STATEMENTS["nulls"][0])) == {
        "g": "int32", "v": "int32", "w": "int32"}
    assert _lanes(_plan(db, STATEMENTS["hourly"][0])) == {
        "pickup_datetime": "int64", "fare_amount": "int32",
        "total_amount": "int32"}
    # plan_select with no trust in the statistics: every guard, no lane
    cold = _plan(db, Q1, trust_stats=False)
    assert cold.narrow_lanes == () and set(_lanes(cold).values()) == {
        "int32", "int64"}
    assert cold.proved_away == (0, 0)

    cl = ct.Cluster(str(tmp_path / "few"))
    try:
        cl.execute("CREATE TABLE s (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('s', 'k', 8)")
        cl.copy_from("s", columns={"k": np.arange(3), "v": np.arange(3)})
        from citus_tpu.catalog.stats import table_facts
        assert table_facts(cl.catalog, cl.catalog.table("s")) is None
        plan = _plan(cl, "SELECT sum(v) FROM s")
        assert plan.narrow_lanes == () and _lanes(plan) == {"v": "int64"}
        assert cl.execute("SELECT sum(v) FROM s").rows == [(3,)]
    finally:
        cl.close()


def test_staged_rows_ride_at_full_width(tmp_cluster, limit_devices):
    """A cached plan with narrow lanes must not answer for a
    transaction's own staged rows: the ONE guard at the head of
    execute_select re-makes it, and a staged value past int32 is summed
    exactly."""
    limit_devices(1)
    cl = tmp_cluster
    cl.execute("CREATE TABLE st (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('st', 'k', 2)")
    cl.copy_from("st", columns={"k": np.arange(100), "v": np.arange(100)})
    q = "SELECT sum(v), max(v), count(*) FROM st"
    r = cl.execute(q)
    assert r.rows == [(4950, 99, 100)]
    assert r.explain["pipeline"]["scan_lanes_narrow"] == 1
    big = (1 << 40) + 7
    cl.execute("BEGIN")
    cl.execute(f"INSERT INTO st VALUES (100, {big})")
    staged = cl.execute(q)
    assert staged.rows == [(4950 + big, big, 101)]
    assert staged.explain["pipeline"]["scan_lanes_narrow"] == 0
    cl.execute("ROLLBACK")
    after = cl.execute(q)
    assert after.rows == [(4950, 99, 100)]
    assert after.explain["pipeline"]["scan_lanes_narrow"] == 1


# ------------------------------------------------------------------- answers


def _reference(cl, name):
    """Plain Python over what went in, for the statements whose point is
    the narrowed values themselves."""
    if name == "min_max_avg":
        li = cl.data["li"]
        out = []
        for s in ("F", "O"):
            at = np.array(li["l_linestatus"]) == s
            price = np.round(li["l_extendedprice"][at] * 100).astype(np.int64)
            tax = np.round(li["l_tax"][at] * 100).astype(np.int64)
            out.append((s, Decimal(int(price.min())) / 100,
                        Decimal(int(price.max())) / 100,
                        int(tax.sum()), int(at.sum()),
                        int(li["l_orderkey"][at].max())))
        return out
    assert name == "nulls"
    out = []
    for g in range(7):
        rows = [r for r in cl.data["nl"] if r[1] == g]
        vs = [r[2] for r in rows if r[2] is not None]
        ws = [r[3] for r in rows if r[3] is not None]
        out.append((g, len(vs), sum(vs), min(vs), max(vs), sum(ws), len(ws),
                    len(rows)))
    return out


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one_device", "mesh"])
@pytest.mark.parametrize("name", list(STATEMENTS))
def test_statements_equal_their_logical_width_selves(
        db, monkeypatch, limit_devices, name, n_dev):
    limit_devices(n_dev)
    sql, narrow, wide = STATEMENTS[name]
    db._plan_cache.clear()
    c0 = db.counters.snapshot()
    got = db.execute(sql)
    c1 = db.counters.snapshot()
    pl = got.explain["pipeline"]
    assert (pl["scan_lanes_narrow"], pl["scan_lanes"]) == (narrow, wide)
    assert c1["scan_lanes_narrow"] - c0["scan_lanes_narrow"] == narrow
    assert c1["scan_lanes"] - c0["scan_lanes"] == wide
    assert c1["scan_lanes_belied"] == c0["scan_lanes_belied"]
    assert got.explain["strategy"] == (
        "hash_host" if name in HASHED else
        "scalar" if name == "q6" else "direct")
    if n_dev > 1 and name in HASHED:
        assert pl["hash_tables"] == n_dev       # the shard-affine rounds
    assert got.rows

    # ... the same statement with every column at its logical width
    with monkeypatch.context() as m:
        m.setattr(physical, "scan_lanes_of",
                  _logical(physical.scan_lanes_of))
        db._plan_cache.clear()
        full = db.execute(sql)
        assert full.explain["pipeline"]["scan_lanes_narrow"] == 0
        assert full.explain["pipeline"]["scan_lanes"] == wide
    db._plan_cache.clear()
    assert got.rows == full.rows
    # ... and on the numpy arm, which puts nothing through a placement
    db.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        assert db.execute(sql).rows == got.rows
    finally:
        db.execute("SET citus.task_executor_backend = 'tpu'")
        db._plan_cache.clear()

    if name == "min_max_avg":
        want = _reference(db, name)
        assert [(r[0], r[1], r[2], r[4]) for r in got.rows] == \
            [(w[0], w[1], w[2], w[5]) for w in want]
        for r, w in zip(got.rows, want):
            assert r[3] == (Decimal(w[3]) / 100 / w[4]).quantize(r[3])
    if name == "nulls":
        want = _reference(db, name)
        assert [r[:5] + (r[6],) for r in got.rows] == \
            [w[:5] + (w[7],) for w in want]
        for r, w in zip(got.rows, want):
            assert r[5] == (w[5] / w[6]).quantize(r[5])


def test_megabatched_riders_read_narrow_lanes(db, limit_devices):
    """Megabatch's vmap lifts the same worker over the query axis: the
    shared scan puts narrow lanes and every rider gets the answer its
    statement gives alone, on the numpy arm."""
    limit_devices(1)
    sqls = [Q6.format(d=d) for d in ("0.03", "0.05", "0.06", "0.08")]
    db._plan_cache.clear()
    db.execute("SET citus.task_executor_backend = 'cpu'")
    want = [db.execute(s).rows for s in sqls]
    db.execute("SET citus.task_executor_backend = 'tpu'")
    db._plan_cache.clear()
    GLOBAL_CACHE.clear()
    db.execute("SET citus.megabatch_window_ms = 2000")
    db.execute(f"SET citus.megabatch_max_size = {len(sqls)}")
    results, errors = {}, {}
    bar = threading.Barrier(len(sqls))

    def run(i, sql):
        bar.wait()
        try:
            results[i] = db.execute(sql).rows
        except Exception as e:  # noqa: BLE001 - asserted below
            errors[i] = e
    c0 = db.counters.snapshot()
    threads = [threading.Thread(target=run, args=(i, s))
               for i, s in enumerate(sqls)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        db.execute("SET citus.megabatch_window_ms = 0")
        db._plan_cache.clear()
    c1 = db.counters.snapshot()
    assert errors == {}
    assert [results[i] for i in range(len(sqls))] == want
    assert c1["megabatch_queries"] - c0["megabatch_queries"] == len(sqls)
    assert c1["megabatch_fallbacks"] == c0["megabatch_fallbacks"]
    batches = c1["megabatch_batches"] - c0["megabatch_batches"]
    assert 1 <= batches < len(sqls)
    # every shared scan put Q6's three decimal columns at 32 bits
    assert c1["scan_lanes_narrow"] - c0["scan_lanes_narrow"] == 3 * batches
    assert c1["scan_lanes_belied"] == c0["scan_lanes_belied"]


def test_the_vmapped_kernel_widens_like_the_serial_one(db):
    """Kernel level: the worker under ``vmap`` over [q]-stacked states,
    on int32 lanes and on the same values at logical widths."""
    plan = _plan(db, STATEMENTS["min_max_avg"][0])
    assert plan.narrow_lanes
    rng = np.random.default_rng(7)
    n, q = 512, 2
    schema = plan.bound.table.schema
    wide, thin = [], []
    for c, lane in zip(plan.scan_columns, plan.lanes):
        dt = np.dtype(schema.scan_dtype(c, device=True))
        hi = 2 if schema.column(c).type.is_text else 10 ** 6
        vals = rng.integers(0, hi, n)
        wide.append(vals.astype(dt))
        thin.append(vals.astype(lane))
    valids = tuple(rng.random(n) < 0.9 for _ in plan.scan_columns)
    mask = rng.random(n) < 0.95
    fn = jax.jit(jax.vmap(build_fused_worker_fn(plan, jnp),
                          in_axes=(0, None, None, None)))
    acc = tuple(np.stack([p] * q) for p in X._empty_partials(plan, np))
    a = fn(acc, tuple(wide), valids, mask)
    b = fn(acc, tuple(thin), valids, mask)
    assert any(t.dtype != w.dtype for t, w in zip(thin, wide))
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


# ------------------------------------------------------------- the programs


def _main_args(text):
    """(shape, element type) of the lowered module's public arguments."""
    sig = text[text.index("func.func public @main("):]
    sig = sig[:sig.index("->")]
    return re.findall(r"%arg\d+: tensor<([0-9x]*?)x?(i\d+|f\d+)>", sig)


def test_q1s_module_takes_no_64_bit_batch_column(db):
    plan = _plan(db, Q1)
    assert len(plan.narrow_lanes) == 4 and plan.wide_lanes == 4
    n = 4096
    fused = jax.jit(build_fused_worker_fn(plan, jnp), donate_argnums=0)
    acc = X._empty_partials(plan, np)

    def batch_args(lanes):
        cols = tuple(jax.ShapeDtypeStruct((n,), l) for l in lanes)
        valids = tuple(jax.ShapeDtypeStruct((n,), np.bool_) for _ in lanes)
        text = fused.lower(acc, cols, valids,
                           jax.ShapeDtypeStruct((n,), np.bool_)).as_text()
        return [t for shape, t in _main_args(text) if shape == str(n)]

    schema = plan.bound.table.schema
    logical = [np.dtype(schema.scan_dtype(c, device=True))
               for c in plan.scan_columns]
    # the parent's program: four s64 parameters a batch (on a TPU each is
    # cut into two u32 arrays by an HBM pass of its own before any fusion)
    assert sorted(batch_args(logical)).count("i64") == 4
    at_lanes = batch_args(plan.lanes)
    assert "i64" not in at_lanes and at_lanes.count("i32") == 7


def test_the_hash_kernels_gather_of_a_narrowed_key_is_32_bit(db):
    plan = _plan(db, STATEMENTS["q18_block"][0])
    assert plan.group_mode.kind == "hash_host"
    assert _lanes(plan) == {"l_orderkey": "int32", "l_quantity": "int32"}
    key_dtypes = X._hash_key_dtypes(plan, {})
    assert key_dtypes == (np.dtype(np.int64),)      # the key stays a bigint
    n = 2048
    kernel = jax.jit(build_fused_hash_worker(plan, jnp, key_dtypes))
    state = empty_hash_state(plan, 1024, key_dtypes)
    valids = tuple(np.ones(n, bool) for _ in plan.scan_columns)

    def gathers(lanes):
        cols = tuple(np.zeros(n, l) for l in lanes)
        text = kernel.lower(state, cols, valids, np.ones(n, bool)).as_text()
        # the gathers of whole batch columns into sorted order
        return re.findall(
            r"stablehlo\.gather.*?: \(tensor<%dx(i\d+)>, tensor<%dx1xi32>\)"
            % (n, n), text)

    schema = plan.bound.table.schema
    wide = gathers([schema.scan_dtype(c, device=True)
                    for c in plan.scan_columns])
    thin = gathers(plan.lanes)
    assert wide.count("i64") >= 2
    assert thin.count("i64") == wide.count("i64") - 2
    assert thin.count("i32") == wide.count("i32") + 2


# ------------------------------------------------------------------ the guard


def _forged(real, column, bounds):
    def facts(cat, table):
        f = real(cat, table)
        if f is None or table.name != "bl":
            return f
        name = table.schema.column(column).storage_name
        return TableFacts(f.rows, {**f.columns, name: bounds})
    return facts


@pytest.mark.parametrize("shape, n_dev", [
    ("scalar", 1), ("scalar", 4), ("direct", 1), ("hash", 1), ("hash", 4)])
def test_a_batch_that_belies_its_facts_is_caught(
        tmp_cluster, monkeypatch, limit_devices, shape, n_dev):
    """Forged footers say v fits int32; one row holds 2^40 + 5.  The
    convert's flag is read where the loop blocks anyway: nothing enters
    the batch cache, the counter says so, and the statement is answered
    once more on a plan that takes nothing from the statistics."""
    limit_devices(n_dev)
    GLOBAL_CACHE.clear()
    cl = tmp_cluster
    cl.execute("CREATE TABLE bl (k bigint NOT NULL, g int, v bigint)")
    cl.execute("SELECT create_distributed_table('bl', 'k', 8)")
    n = 4000
    v = np.arange(n, dtype=np.int64) * 3
    v[1234] = (1 << 40) + 5
    cl.copy_from("bl", columns={"k": np.arange(n), "g": np.arange(n) % 3,
                                "v": v})
    sql = {"scalar": "SELECT sum(v), max(v), count(*) FROM bl",
           "direct": "SELECT g, sum(v), max(v) FROM bl GROUP BY g ORDER BY g",
           "hash": "SELECT v, count(*) FROM bl GROUP BY v "
                   "HAVING v > 11000 ORDER BY v"}[shape]
    want = {"scalar": [(int(v.sum()), int(v.max()), n)],
            "direct": [(g, int(v[g::3].sum()), int(v[g::3].max()))
                       for g in range(3)],
            "hash": [(int(x), 1) for x in sorted(v[v > 11000])]}[shape]
    honest = cl.execute(sql)
    assert honest.rows == want
    # the true footers narrow g alone (where it is scanned), never v
    assert honest.explain["pipeline"]["scan_lanes_narrow"] == \
        (shape == "direct")

    import citus_tpu.catalog.stats as S
    monkeypatch.setattr(physical, "table_facts",
                        _forged(S.table_facts, "v", (0, 3 * n, False)))
    cl._plan_cache.clear()
    GLOBAL_CACHE.clear()
    forged_plan = _plan(cl, sql)
    assert "v" in [forged_plan.scan_columns[i]
                   for i in forged_plan.narrow_lanes]
    c0 = cl.counters.snapshot()
    got = cl.execute(sql)
    c1 = cl.counters.snapshot()
    assert got.rows == want
    assert c1["scan_lanes_belied"] - c0["scan_lanes_belied"] == 1
    # the answer came from the plan made without the statistics
    assert got.explain["pipeline"]["scan_lanes_narrow"] == 0
    # whatever is cached now holds v at 64 bits
    for key, (entry, _, _) in GLOBAL_CACHE._entries.items():
        assert np.dtype(np.int64) in key[7], key
        for inputs in entry:
            cols = inputs.cols if hasattr(inputs, "cols") else inputs[0]
            assert cols[forged_plan.scan_columns.index("v")].dtype == np.int64


# ------------------------------------------------------------------ the cache


def test_a_second_run_retraces_nothing_and_hits(db, limit_devices):
    limit_devices(1)
    db._plan_cache.clear()
    GLOBAL_CACHE.clear()
    first = db.execute(Q1)
    c0 = db.counters.snapshot()
    second = db.execute(Q1)
    third = db.execute(Q1)
    c1 = db.counters.snapshot()
    assert first.rows == second.rows == third.rows
    assert c1["kernel_compiles"] == c0["kernel_compiles"]
    assert c1["kernel_cache_misses"] == c0["kernel_cache_misses"]
    assert c1["device_cache_hits"] - c0["device_cache_hits"] == 2
    assert c1["scan_lanes_narrow"] - c0["scan_lanes_narrow"] == 8
    # streamed again past the cache: the convert is compiled already
    GLOBAL_CACHE.clear()
    c0 = db.counters.snapshot()
    assert db.execute(Q1).rows == first.rows
    c1 = db.counters.snapshot()
    assert c1["kernel_compiles"] == c0["kernel_compiles"]
    assert c1["device_cache_hits"] == c0["device_cache_hits"]


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one_device", "mesh"])
def test_a_narrow_entry_serves_only_narrow_plans(
        db, monkeypatch, limit_devices, n_dev):
    limit_devices(n_dev)
    db._plan_cache.clear()
    GLOBAL_CACHE.clear()
    sql = STATEMENTS["min_max_avg"][0]
    narrow = db.execute(sql)
    (key, (entry, nbytes, _)), = GLOBAL_CACHE._entries.items()
    plan = _plan(db, sql)
    assert key[7] == plan.lanes

    def arrays(inputs):
        if hasattr(inputs, "cols"):
            return inputs.cols + inputs.valids + (inputs.row_mask,)
        return inputs[0] + inputs[1] + (inputs[2],)
    # the entry holds the narrow arrays, and books the bytes they take
    for inputs in entry:
        assert [str(a.dtype) for a in arrays(inputs)[:len(plan.scan_columns)]] \
            == [str(l) for l in plan.lanes]
    assert nbytes == sum(a.nbytes for i in entry for a in arrays(i))
    assert GLOBAL_CACHE.memory_view()["live_bytes"] == nbytes

    with monkeypatch.context() as m:
        m.setattr(physical, "scan_lanes_of",
                  _logical(physical.scan_lanes_of))
        db._plan_cache.clear()
        c0 = db.counters.snapshot()
        full = db.execute(sql)
        c1 = db.counters.snapshot()
    db._plan_cache.clear()
    assert full.rows == narrow.rows
    assert c1["device_cache_hits"] == c0["device_cache_hits"]
    assert len(GLOBAL_CACHE._entries) == 2
    sizes = sorted(e[1] for e in GLOBAL_CACHE._entries.values())
    rows = sum(arrays(i)[-1].size for i in entry)
    assert sizes[1] - sizes[0] == 4 * rows * len(plan.narrow_lanes)
    # and the narrow plan finds its own entry again
    c0 = db.counters.snapshot()
    assert db.execute(sql).rows == narrow.rows
    assert db.counters.snapshot()["device_cache_hits"] \
        - c0["device_cache_hits"] == 1


# -------------------------------------------------- counters and EXPLAIN ANALYZE


def test_counters_and_the_explain_analyze_line(db, limit_devices):
    limit_devices(1)
    db._plan_cache.clear()
    text = "\n".join(r[0] for r in db.execute("EXPLAIN ANALYZE " + Q1).rows)
    assert re.search(r"Pipeline: .*lanes: 4 of 4 64-bit columns at 32 bits",
                     text), text
    text = "\n".join(r[0] for r in db.execute(
        "EXPLAIN ANALYZE " + STATEMENTS["hourly"][0]).rows)
    assert "lanes: 2 of 3 64-bit columns at 32 bits" in text, text
    from citus_tpu.observability.export import METRIC_HELP
    from citus_tpu.stats import StatCounters
    for name in ("scan_lanes", "scan_lanes_narrow", "scan_lanes_belied"):
        assert name in METRIC_HELP and name in StatCounters.COUNTERS
        assert name in db.counters.snapshot()
