"""The single-hash repartition join on the device: one relation
distributed on the join key (probed where its shards lie), the other
distributed off it (its rows exchanged between the devices by one
``all_to_all`` and built into a lookup table a device).

TPC-H Q12 as published over ``benchmarks/generators/tpch_q12_tables``'
tables in small -- ``lineitem`` hash-distributed on ``l_orderkey``,
``orders`` on ``o_custkey`` -- against the plain reference
(``benchmarks/references/q12.py``: the generator's kept counts, and a
join written in Python over the generated columns) and against the host
path (``task_executor_backend = 'cpu'``), on 1, 4 and 8 of the harness's
devices.  Answers are EQUAL.
"""

import numpy as np
import pytest

import citus_tpu as ct
from benchmarks.generators import tpch_q12_tables as G
from benchmarks.references import q12 as R
from citus_tpu.catalog.hashing import hash_int64
from citus_tpu.executor import join_device as JD
from citus_tpu.executor import join_executor as JX
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.ops import join as J

ORDERS, SHARDS = 6000, 16
PARAMS = {"data_seed": 5, "orders": ORDERS, "customers": 600,
          "parts": 2000, "chunk_orders": 2500}

Q12 = ("select l_shipmode, sum(case when o_orderpriority = '1-URGENT' or "
       "o_orderpriority = '2-HIGH' then 1 else 0 end) as high_line_count, "
       "sum(case when o_orderpriority <> '1-URGENT' and o_orderpriority <> "
       "'2-HIGH' then 1 else 0 end) as low_line_count from orders, lineitem "
       "where o_orderkey = l_orderkey and l_shipmode in ('{SHIPMODES}') and "
       "l_commitdate < l_receiptdate and l_shipdate < l_commitdate and "
       "l_receiptdate >= date '{DATE}-01-01' and l_receiptdate < date "
       "'{DATE}-01-01' + interval '1' year group by l_shipmode "
       "order by l_shipmode")

LINEITEM = ("CREATE TABLE lineitem (l_orderkey bigint NOT NULL, l_quantity "
            "decimal(12,2), l_extendedprice decimal(12,2), l_discount "
            "decimal(12,2), l_tax decimal(12,2), l_returnflag text, "
            "l_linestatus text, l_shipdate date, l_commitdate date, "
            "l_receiptdate date, l_shipmode text)")
ORDERS_DDL = ("CREATE TABLE orders (o_orderkey bigint NOT NULL, o_custkey "
              "bigint, o_orderstatus text, o_totalprice decimal(15,2), "
              "o_orderdate date, o_orderpriority text, o_shippriority "
              "integer)")

#: the validation pair, a pair that shares no mode with it, and a year
#: at the edge of the range
DRAWS = [("MAIL', 'SHIP", 1994), ("REG AIR', 'FOB", 1997),
         ("AIR', 'TRUCK", 1993)]


class Tables:
    """The generator's chunks, kept as it made them."""

    def __init__(self):
        self.stats = G.Statistics(PARAMS)
        self.chunks = []
        for i in range(G.n_chunks(PARAMS)):
            chunk = G.generate_chunk(PARAMS, PARAMS["data_seed"], i)
            self.stats.add(chunk)
            self.chunks.append(chunk)
        cat = lambda t, c: np.concatenate([ch[t][c] for ch in self.chunks])
        self.orders = {c: cat("orders", c)
                       for c in ("o_orderkey", "o_orderpriority")}
        self.lineitem = {c: cat("lineitem", c)
                         for c in ("okey", "mode", "ship", "commit", "receipt")}

    def load(self, cl):
        cl.execute(LINEITEM)
        cl.execute(f"SELECT create_distributed_table('lineitem', "
                   f"'l_orderkey', {SHARDS})")
        cl.execute(ORDERS_DDL)
        cl.execute(f"SELECT create_distributed_table('orders', 'o_custkey', "
                   f"{SHARDS})")
        for chunk in self.chunks:
            for table, columns in G.copy_columns(chunk).items():
                cl.copy_from(table, columns=columns)


@pytest.fixture(scope="module")
def tables():
    return Tables()


@pytest.fixture(scope="module")
def cl(tables, tmp_path_factory):
    cluster = ct.Cluster(str(tmp_path_factory.mktemp("q12") / "db"))
    tables.load(cluster)
    return cluster


def q12(modes="MAIL', 'SHIP", year=1994):
    return Q12.format(SHIPMODES=modes, DATE=year)


def host_arm(cl, sql):
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        return cl.execute(sql)
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")


def on_device(explain):
    return explain["strategy"] == "join:repartition" \
        and explain["join"]["on"] == "device"


# ------------------------------------------- (a) the answer, three ways


def test_the_kept_counts_equal_a_join_of_the_generated_columns(tables):
    stats = tables.stats.arrays()
    for modes in R.SHIPMODES[1:]:
        for year in R.YEARS:
            raw = {"SHIPMODES": f"REG AIR', '{modes}", "DATE": year}
            assert R.expected(stats, raw) \
                == R.joined(tables.orders, tables.lineitem, raw)
    assert stats["rows.orders"] == ORDERS
    assert int(stats["q12"].sum()) > 100


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_q12_as_published(cl, tables, limit_devices, monkeypatch, devices):
    def no_frame(*a, **k):
        raise AssertionError("a relation whole in host memory")
    limit_devices(devices)
    stats = tables.stats.arrays()
    for modes, year in DRAWS:
        raw = {"SHIPMODES": modes, "DATE": year}
        with monkeypatch.context() as m:
            m.setattr(JX, "_load_rel_frame", no_frame)
            r = cl.execute(q12(modes, year))
        assert on_device(r.explain), r.explain
        want = R.expected(stats, raw)
        assert [tuple(x) for x in r.rows] == want and len(want) == 2
        assert r.rows == host_arm(cl, q12(modes, year)).rows
        j = r.explain["join"]
        assert j["probe"] == "lineitem" and j["rows_built"] == ORDERS
        assert j["tables"]["orders"]["built_per"] == "query"
        x = j["exchange"]
        assert x["relation"] == "orders" and x["key"] == "o_orderkey"
        assert x["devices"] == devices
        if devices == 1:
            assert r.explain["shuffle"] == "local" and x["rows"] == 0
        else:
            assert r.explain["shuffle"] == "all_to_all:device"
            assert x["rows"] == ORDERS
            assert ORDERS / devices <= x["rows_received_max_device"] \
                < 1.25 * ORDERS / devices
            assert x["bytes"] == ORDERS * (8 + 4 + 2 + 1)


def test_a_new_draw_compiles_nothing(cl, limit_devices):
    limit_devices(4)
    cl.execute(q12("MAIL', 'SHIP", 1994))
    c0 = GLOBAL_COUNTERS.snapshot()
    r = cl.execute(q12("RAIL', 'FOB", 1996))
    c1 = GLOBAL_COUNTERS.snapshot()
    assert on_device(r.explain)
    for name in ("kernel_cache_misses", "kernel_compiles"):
        assert c1.get(name, 0) == c0.get(name, 0), name


# ------------------------------------- (b) the share tied to the whole


@pytest.mark.parametrize("devices", [4, 8])
def test_every_order_lands_on_the_owner_of_its_lineitem_shard(
        cl, tables, limit_devices, monkeypatch, devices):
    """Sum over the devices of the rows built = ``orders``' rows, every
    order on exactly one device, and that device the owner of the
    ``lineitem`` shard its key hashes to by ``catalog/hashing.py``."""
    limit_devices(devices)
    seen = {}
    verdict = JD._DeviceJoin._verdict

    def keep(self, alias, table):
        seen[alias] = (table, self.spans.get(alias), self.kind[alias])
        return verdict(self, alias, table)
    monkeypatch.setattr(JD._DeviceJoin, "_verdict", keep)
    r = cl.execute(q12())
    assert on_device(r.explain)
    (state, _counts), (lo, _slots), kind = seen["orders"]
    assert kind == "direct"
    index = np.asarray(state[0])              # [devices, span]
    assert index.shape[0] == devices
    lineitem = cl.catalog.table("lineitem")
    held = []
    for d in range(devices):
        keys = lo + np.flatnonzero(index[d])
        shard = lineitem.route_hashes(hash_int64(keys))
        assert (shard * devices // SHARDS == d).all()
        held.append(keys)
    held = np.concatenate(held)
    assert held.size == ORDERS == np.unique(held).size
    assert set(held.tolist()) == set(tables.orders["o_orderkey"].tolist())
    # ... and each device probes the lineitem shards it owns alone: the
    # answer is the reference's
    assert [tuple(x) for x in r.rows] == R.expected(
        tables.stats.arrays(), {"SHIPMODES": "MAIL', 'SHIP", "DATE": 1994})


def test_the_device_hash_is_the_catalogs():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    v = np.concatenate([rng.integers(-2 ** 63, 2 ** 63 - 1, 5000),
                        np.arange(-3, 4), [2 ** 63 - 1, -2 ** 63]])
    assert (np.asarray(hash_int64(jnp.asarray(v), jnp)) == hash_int64(v)).all()
    assert hash_int64(np.array([1, 2, 3])).tolist() \
        == [-1861603860, -1755826722, 487265508]


def test_pack_blocks_rounds_take_every_row_once():
    import jax.numpy as jnp
    from citus_tpu.parallel.shuffle import _pack_blocks
    rng = np.random.default_rng(4)
    n, n_dev, cap = 1000, 4, 96
    vals = jnp.asarray(rng.integers(0, 10 ** 9, n))
    target = jnp.asarray(rng.choice(n_dev, n, p=[.55, .25, .15, .05])
                         .astype(np.int32))
    mask = jnp.asarray(rng.random(n) > 0.2)
    got = [[] for _ in range(n_dev)]
    for rnd in range(12):
        (packed,), valid, left = _pack_blocks((vals,), target, mask, n_dev,
                                              cap, rnd)
        for d in range(n_dev):
            got[d] += np.asarray(packed)[d][np.asarray(valid)[d]].tolist()
        if int(left) == 0:
            break
    assert rnd > 1 and int(left) == 0
    for d in range(n_dev):
        want = np.asarray(vals)[np.asarray(mask) & (np.asarray(target) == d)]
        assert got[d] == want.tolist()      # in their order, each once


# ------------------------------------------------------ (c) overflow


def test_an_exchange_block_that_overflows_takes_further_rounds(
        cl, tables, limit_devices, monkeypatch):
    limit_devices(4)
    monkeypatch.setattr(JD._DeviceJoin, "exchange_rows", 128)
    c0 = GLOBAL_COUNTERS.snapshot()
    r = cl.execute(q12())
    c1 = GLOBAL_COUNTERS.snapshot()
    assert on_device(r.explain)
    x = r.explain["join"]["exchange"]
    assert x["overflow_rounds"] > 0 and x["rows"] == ORDERS
    assert c1["join_exchange_overflow_rounds"] \
        - c0.get("join_exchange_overflow_rounds", 0) == x["overflow_rounds"]
    assert r.explain["join"]["rows_built"] == ORDERS
    assert [tuple(row) for row in r.rows] == R.expected(
        tables.stats.arrays(), {"SHIPMODES": "MAIL', 'SHIP", "DATE": 1994})


def test_a_batch_picks_the_filter_first_where_its_rows_fit_a_block(
        cl, limit_devices):
    """Since PR 49 the probe has ONE order -- filter, pack, look the
    kept rows up (``tests/test_join_probe_chunks.py``) -- and a batch
    whose kept rows fit a block pays for a block's lookups."""
    limit_devices(4)
    r = cl.execute(q12())
    j = r.explain["join"]
    assert j["rows_looked_up"] == j["rows_out"] > 0 \
        and j["overflow_rounds"] == 0
    # no filter on the probe relation: every row is looked up
    r = cl.execute("select count(*) from orders, lineitem "
                   "where o_orderkey = l_orderkey")
    j = r.explain["join"]
    assert on_device(r.explain) and j["rows_looked_up"] == j["rows_probed"]
    assert r.rows == host_arm(cl, "select count(*) from orders, lineitem "
                                  "where o_orderkey = l_orderkey").rows


def test_a_probe_block_that_overflows_on_the_mesh_is_cut_from_the_carry(
        cl, limit_devices, monkeypatch):
    """Eight devices, a block of 16 rows a device: every device's
    further rounds cut their blocks from the carry its round 0 left
    (a pytree with a leading device axis, like every other result), so
    the batches are looked up once whatever the rounds, and the answer
    is the host arm's."""
    limit_devices(8)
    sql = ("select l_shipmode, count(*), sum(l_quantity), "
           "min(o_orderpriority) from orders, lineitem "
           "where o_orderkey = l_orderkey group by l_shipmode "
           "order by l_shipmode")
    whole = cl.execute(sql)        # blocks of 1,024: few rounds or none
    assert on_device(whole.explain)
    monkeypatch.setattr(JD._DeviceJoin, "block_rows", 16)
    c0 = GLOBAL_COUNTERS.snapshot()
    r = cl.execute(sql)
    c1 = GLOBAL_COUNTERS.snapshot()
    assert on_device(r.explain) \
        and r.explain["shuffle"] == "all_to_all:device"
    j = r.explain["join"]
    assert j["overflow_rounds"] >= 3 * 8 \
        > whole.explain["join"]["overflow_rounds"]
    assert j["rows_looked_up"] == j["rows_probed"] \
        == whole.explain["join"]["rows_probed"]
    assert c1["join_rows_looked_up"] - c0.get("join_rows_looked_up", 0) \
        == j["rows_probed"]
    assert j["rows_out"] == whole.explain["join"]["rows_out"] > 16 * 8 * 3
    assert r.rows == whole.rows == host_arm(cl, sql).rows


# ----------------------------------------- (d) what goes to the host path


@pytest.fixture(scope="module")
def others(tmp_path_factory):
    """``f`` (fact) on its key; ``d`` off it with unique keys; ``t`` off
    it with a key met twice; ``n`` a reference table; ``g`` and ``h``
    both off the key they join on; ``s`` on its key and smaller than
    ``h``."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("others") / "db"))
    rng = np.random.default_rng(2)
    for t, dist in (("f", "k"), ("d", "c"), ("t", "c"), ("g", "c"),
                    ("h", "c"), ("s", "k")):
        cl.execute(f"CREATE TABLE {t} (k bigint NOT NULL, c bigint, v bigint)")
        cl.execute(f"SELECT create_distributed_table('{t}', '{dist}', 8)")
    cl.execute("CREATE TABLE n (id bigint NOT NULL, name text)")
    cl.execute("SELECT create_reference_table('n')")
    cl.copy_from("n", columns={"id": np.arange(4),
                               "name": ["aa", "bb", "cc", "dd"]})
    fk = rng.integers(0, 500, 4000)
    cl.copy_from("f", columns={"k": fk, "c": rng.integers(0, 9, 4000),
                               "v": rng.integers(0, 100, 4000)})
    dk = np.arange(450)
    for t, keys in (("d", dk), ("t", np.concatenate([dk, dk[:3]])),
                    ("g", dk), ("h", rng.integers(0, 500, 900)), ("s", dk)):
        cl.copy_from(t, columns={"k": keys, "c": rng.integers(0, 4, keys.size),
                                 "v": rng.integers(0, 100, keys.size)})
    return cl


@pytest.mark.parametrize("sql,why", [
    ("select count(*), sum(f.v) from f join t on f.k = t.k",
     "build key of t is not unique"),
    ("select count(*) from g join h on g.k = h.k",
     "neither side is distributed on the join key (a dual repartition)"),
    ("select count(*), sum(d.v) from f left join d on f.k = d.k",
     "left step"),
    ("select count(*) from f join d on f.k = d.k and f.v < d.v",
     "residual ON condition"),
    ("select f.k, d.v from f join d on f.k = d.k order by 1, 2 limit 5",
     "no aggregate above the join"),
    ("select count(*) from d join f on f.k = d.k join g on g.k = f.c",
     "several steps between distributed relations"),
])
def test_what_the_device_does_not_run_goes_to_the_host(others, limit_devices,
                                                       sql, why):
    limit_devices(4)
    c0 = GLOBAL_COUNTERS.snapshot().get("join_host_fallbacks", 0)
    r = others.execute(sql)
    assert r.explain["strategy"] == "join:repartition"
    assert r.explain["join"] == {"on": "host", "why": why}
    assert GLOBAL_COUNTERS.snapshot()["join_host_fallbacks"] == c0 + 1
    assert r.rows == host_arm(others, sql).rows


def test_the_larger_side_off_the_key_stays_on_the_host(others):
    """``s`` lies on the key, so ``h`` would have to be the build: it
    is the larger, the many side -- known before a row is read."""
    r = others.execute("select count(*) from s join h on s.k = h.k")
    assert r.explain["join"]["on"] == "host"
    assert "is the larger" in r.explain["join"]["why"]


def test_a_reference_table_under_the_exchanged_relation(others,
                                                        limit_devices):
    """``n`` is built on every device (a round's members are one batch,
    n times), ``d`` is exchanged with ``n``'s name as its payload."""
    sql = ("select n.name, count(*), sum(f.v) from f, d, n where f.k = d.k "
           "and d.c = n.id and f.v < 90 group by n.name order by n.name")
    for devices in (1, 4):
        limit_devices(devices)
        r = others.execute(sql)
        assert on_device(r.explain), r.explain
        assert r.explain["join"]["exchange"]["relation"] == "d"
        assert r.rows == host_arm(others, sql).rows and len(r.rows) == 4


def test_the_setting_that_forbids_repartition_joins(others):
    others.execute("SET citus.enable_repartition_joins = off")
    try:
        r = others.execute("select count(*) from f join d on f.k = d.k")
    finally:
        others.execute("SET citus.enable_repartition_joins = on")
    assert r.explain["strategy"] == "join:pull"
    assert r.explain["join"]["why"] == "repartition joins are disabled"


# ------------------------------------------------ (e) spans and counters


def test_spans_and_counters(cl, tables, limit_devices):
    from citus_tpu.observability import trace as T
    limit_devices(4)
    sql = q12()
    cl.execute(sql)
    cl.execute("SET citus.trace_sample_rate = 1.0")
    try:
        c0 = GLOBAL_COUNTERS.snapshot()
        r = cl.execute(sql)
        c1 = GLOBAL_COUNTERS.snapshot()
        tr = T.last_trace()
    finally:
        cl.execute("SET citus.trace_sample_rate = 0")
    d = lambda n: c1.get(n, 0) - c0.get(n, 0)
    j = r.explain["join"]
    x = j["exchange"]
    assert d("join_host_fallbacks") == 0 and d("kernel_cache_misses") == 0
    assert d("join_rows_exchanged") == x["rows"] == ORDERS
    assert d("join_bytes_exchanged") == x["bytes"] > 0
    assert d("join_rows_received_max_device") \
        == x["rows_received_max_device"] > 0
    assert d("join_exchange_overflow_rounds") == 0
    assert d("join_rows_built") == ORDERS
    assert d("join_rows_probed") == j["rows_probed"] \
        >= int(tables.stats.rows["lineitem"])
    assert d("join_rows_matched") == j["rows_matched"] >= d("join_rows_out") > 0
    assert d("join_table_bytes") == j["table_bytes"] > 0
    assert tr.find("execute").attrs["strategy"] == "join:repartition"
    rounds = tr.find_all("join_exchange")
    assert rounds and all(
        s.attrs["relation"] == "orders" and s.attrs["key"] == "orders.o_orderkey"
        and s.attrs["devices"] == 4 for s in rounds)
    assert sum(s.attrs["rows_in"] for s in rounds) == ORDERS
    assert sum(s.attrs["rows_sent"] for s in rounds) == ORDERS
    assert all(s.attrs["capacity"] == J.exchange_capacity(8192, 4)
               for s in rounds)
    (build,) = tr.find_all("join_build")
    assert build.attrs["relation"] == "orders" \
        and build.attrs["rows_built"] == ORDERS \
        and build.attrs["table"] == "direct"
    assert tr.find("hash_init").attrs["devices"] == 4
    assert tr.find("fetch").attrs["tables"] == 4
    for name in ("stack", "h2d", "dispatch", "device_round", "bind_params",
                 "finalize_groups", "order_and_limit", "decode_batch"):
        assert tr.find(name) is not None, name
    lines = "\n".join(l for (l,) in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    assert "Join: on device, probe lineitem" in lines
    assert lines.rstrip().endswith(
        f"exchange: orders on o_orderkey, {ORDERS} rows, {x['bytes']} bytes "
        f"over 4 devices, fullest device {x['rows_received_max_device']}")
    assert "join:repartition" in "\n".join(
        l for (l,) in cl.execute("EXPLAIN " + sql).rows) or \
        "repartition" in lines
