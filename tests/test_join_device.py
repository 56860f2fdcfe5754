"""The many-to-one equi-join on the device (``ops/join.py``,
``executor/join_device.py``) against the numpy arm of the engine
(``task_executor_backend = 'cpu'``: the host join, the oracle) and
against a join written here in plain Python over the generated columns
(no engine code).  Answers are EQUAL: decimals are scaled int64.

The tables are TPC-H Q3's in small: ``orders`` and ``lineitem``
hash-distributed and colocated on the order key, ``customer`` a
reference table.
"""

import datetime
import decimal
import functools

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import join_device as JD
from citus_tpu.executor import join_executor as JX
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.planner.join_planner import bind_join_select, plan_device_join
from citus_tpu.planner.parser import parse_statement

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
EPOCH = datetime.date(1970, 1, 1)
DAY0 = (datetime.date(1995, 1, 1) - EPOCH).days

Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
 o_orderdate, o_shippriority from customer, orders, lineitem
 where c_mktsegment = '{seg}' and c_custkey = o_custkey
 and l_orderkey = o_orderkey and o_orderdate < date '{date}'
 and l_shipdate > date '{date}'
 group by l_orderkey, o_orderdate, o_shippriority
 order by revenue desc, o_orderdate limit 10"""

Q3_ON = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
 o_orderdate, o_shippriority from customer
 join orders on c_custkey = o_custkey
 join lineitem on l_orderkey = o_orderkey
 where c_mktsegment = '{seg}' and o_orderdate < date '{date}'
 and l_shipdate > date '{date}'
 group by l_orderkey, o_orderdate, o_shippriority
 order by revenue desc, o_orderdate limit 10"""


class Data:
    """Seeded columns of the three tables, as Python sees them."""

    def __init__(self, seed, orders=1500, customers=200, dense=False):
        rng = np.random.default_rng(seed)
        self.c_custkey = np.arange(1, customers + 1)
        self.c_mktsegment = [SEGMENTS[i] for i in
                             rng.integers(0, 5, customers)]
        self.c_altkey = [None if rng.random() < 0.1 else int(k)
                         for k in self.c_custkey]
        # sparse keys: no direct-address table fits them; ``dense``: 8
        # used of every 32, as dbgen's
        self.o_orderkey = rng.permutation(
            (np.arange(orders) // 8) * 32 + np.arange(orders) % 8 + 1) \
            if dense else rng.choice(10 ** 9, orders, replace=False)
        self.o_custkey = [None if rng.random() < 0.05
                          else int(rng.integers(1, customers + 40))
                          for _ in range(orders)]
        self.o_orderdate = DAY0 + rng.integers(0, 120, orders)
        self.o_shippriority = rng.integers(0, 3, orders)
        lines = rng.integers(1, 6, orders)
        at = np.repeat(np.arange(orders), lines)
        self.l_orderkey = self.o_orderkey[at]
        self.l_extendedprice = rng.integers(100, 10 ** 6, at.size)
        self.l_discount = rng.integers(0, 11, at.size)
        self.l_shipdate = self.o_orderdate[at] + rng.integers(1, 122, at.size)

    def load(self, cl, shards):
        cl.execute("CREATE TABLE orders (o_orderkey bigint NOT NULL, "
                   "o_custkey bigint, o_orderdate date, "
                   "o_shippriority integer)")
        cl.execute(f"SELECT create_distributed_table('orders', "
                   f"'o_orderkey', {shards})")
        cl.execute("CREATE TABLE lineitem (l_orderkey bigint NOT NULL, "
                   "l_extendedprice decimal(15,2), l_discount decimal(15,2), "
                   "l_shipdate date)")
        cl.execute(f"SELECT create_distributed_table('lineitem', "
                   f"'l_orderkey', {shards})")
        cl.execute("CREATE TABLE customer (c_custkey bigint NOT NULL, "
                   "c_mktsegment text, c_altkey bigint)")
        cl.execute("SELECT create_reference_table('customer')")
        cl.copy_from("customer", columns={
            "c_custkey": self.c_custkey, "c_mktsegment": self.c_mktsegment,
            "c_altkey": self.c_altkey})
        self.load_orders(cl, slice(None))

    def load_orders(self, cl, part):
        keys = set(self.o_orderkey[part].tolist())
        lines = np.array([k in keys for k in self.l_orderkey.tolist()])
        cl.copy_from("orders", columns={
            "o_orderkey": self.o_orderkey[part],
            "o_custkey": self.o_custkey[part],
            "o_orderdate": self.o_orderdate[part].astype(np.int32),
            "o_shippriority": self.o_shippriority[part].astype(np.int32)})
        dec = lambda a: [decimal.Decimal(int(v)).scaleb(-2) for v in a]
        cl.copy_from("lineitem", columns={
            "l_orderkey": self.l_orderkey[lines],
            "l_extendedprice": dec(self.l_extendedprice[lines]),
            "l_discount": dec(self.l_discount[lines]),
            "l_shipdate": self.l_shipdate[lines].astype(np.int32)})

    # ------------------------------------------------ the plain join
    def joined(self, cust_key="c_custkey", orders=slice(None)):
        """(line index, order index, customer index) of every joined row."""
        cust = {k: i for i, k in enumerate(getattr(self, cust_key))
                if k is not None}
        okeys = self.o_orderkey[orders].tolist()
        base = range(len(self.o_orderkey))[orders]
        order = {k: i for k, i in zip(okeys, base)}
        for li, k in enumerate(self.l_orderkey.tolist()):
            oi = order.get(k)
            if oi is None or self.o_custkey[oi] is None:
                continue
            ci = cust.get(self.o_custkey[oi])
            if ci is not None:
                yield li, oi, ci

    def q3(self, seg, day, **kw):
        groups = {}
        for li, oi, ci in self.joined(**kw):
            if self.c_mktsegment[ci] == seg and self.o_orderdate[oi] < day \
                    and self.l_shipdate[li] > day:
                key = (int(self.l_orderkey[li]), int(self.o_orderdate[oi]),
                       int(self.o_shippriority[oi]))
                groups[key] = groups.get(key, 0) + int(
                    self.l_extendedprice[li]) * (100 - int(
                        self.l_discount[li]))
        rows = [(k[0], decimal.Decimal(v).scaleb(-4),
                 EPOCH + datetime.timedelta(days=k[1]), k[2])
                for k, v in groups.items()]
        return sorted(rows, key=lambda r: (-r[1], r[2]))[:10]


def iso(day):
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


def both_arms(cl, sql):
    """-> (device answer, numpy-arm answer, device explain)."""
    dev = cl.execute(sql)
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        host = cl.execute(sql)
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    return dev.rows, host.rows, dev.explain


def on_device(explain):
    return explain["strategy"] == "join:colocated" \
        and explain["join"]["on"] == "device"


@pytest.fixture(scope="module")
def data():
    return Data(7)


@pytest.fixture(scope="module", params=[4, 8])
def cl(request, data, tmp_path_factory):
    cluster = ct.Cluster(str(tmp_path_factory.mktemp("join") / "db"))
    data.load(cluster, request.param)
    return cluster


# ------------------------------------------------------------ (a) planner


def steps_of(cl, sql):
    bj = bind_join_select(cl.catalog, parse_statement(sql))
    return bj, [(s.right_alias, s.kind, repr(s.left_keys), repr(s.right_keys))
                for s in bj.steps]


def test_published_text_plans_as_its_join_on_form(cl):
    args = dict(seg="BUILDING", date="1995-03-15")
    comma, csteps = steps_of(cl, Q3.format(**args))
    on, osteps = steps_of(cl, Q3_ON.format(**args))
    assert csteps == osteps
    assert [s[:2] for s in csteps] == [("orders", "inner"),
                                       ("lineitem", "inner")]
    assert comma.strategy == on.strategy == "colocated"
    assert comma.post_filter is None
    assert repr(comma.rel_plans) == repr(on.rel_plans)
    tree = plan_device_join(comma, {"customer": 200, "orders": 1500,
                                    "lineitem": 4500})
    assert tree.root == "lineitem" and tree.builds == ["customer", "orders"]
    assert tree.parent == {"orders": "lineitem", "customer": "orders"}


def test_comma_join_without_an_equality_stays_cross(cl):
    bj, steps = steps_of(
        cl, "select count(*) from customer, orders where o_custkey > c_custkey")
    assert [s[1] for s in steps] == ["cross"]
    assert bj.post_filter is not None
    assert isinstance(plan_device_join(bj, {}), str)


def test_equality_under_an_outer_step_stays_a_filter(cl):
    bj, steps = steps_of(
        cl, "select count(*) from orders left join customer on "
            "c_altkey = o_custkey where c_custkey = o_custkey")
    assert steps[0][1] == "left" and "c_altkey" in steps[0][3] \
        and "c_custkey" not in steps[0][3]
    assert bj.post_filter is not None
    # ... and the equality of an inner pair before an outer step that
    # null-extends them is not moved either
    bj, steps = steps_of(
        cl, "select count(*) from customer, orders right join lineitem on "
            "l_orderkey = o_orderkey where c_custkey = o_custkey")
    assert steps[0][1] == "cross" and bj.post_filter is not None


def test_fixture_order_plans_the_same_tree(cl):
    bj, _ = steps_of(
        cl, "select count(*) from lineitem join orders on l_orderkey = "
            "o_orderkey join customer on c_custkey = o_custkey")
    tree = plan_device_join(bj, {"customer": 200, "orders": 1500,
                                 "lineitem": 4500})
    assert tree.root == "lineitem" and tree.builds == ["customer", "orders"]


# ------------------------------------------- (b) the device path's answers


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("seg,day", [("BUILDING", 74), ("MACHINERY", 60)])
def test_q3_as_published(cl, data, limit_devices, devices, seg, day):
    limit_devices(devices)
    dev, host, explain = both_arms(
        cl, Q3.format(seg=seg, date=iso(DAY0 + day)))
    assert on_device(explain)
    assert dev == host == data.q3(seg, DAY0 + day)
    assert len(dev) == 10


def test_a_new_draw_compiles_nothing(cl):
    cl.execute(Q3.format(seg="BUILDING", date="1995-03-02"))
    c0 = GLOBAL_COUNTERS.snapshot()
    r = cl.execute(Q3.format(seg="FURNITURE", date="1995-03-28"))
    c1 = GLOBAL_COUNTERS.snapshot()
    assert on_device(r.explain)
    for name in ("kernel_cache_misses", "kernel_compiles"):
        assert c1.get(name, 0) == c0.get(name, 0), name


def test_null_keys_match_nothing(cl, data):
    sql = ("select o_shippriority, count(*), sum(l_extendedprice) from "
           "orders, lineitem, customer where o_custkey = c_altkey and "
           "l_orderkey = o_orderkey group by o_shippriority "
           "order by o_shippriority")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain)
    want = {}
    for li, oi, _ in data.joined(cust_key="c_altkey"):
        n, s = want.get(int(data.o_shippriority[oi]), (0, 0))
        want[int(data.o_shippriority[oi])] = (
            n + 1, s + int(data.l_extendedprice[li]))
    assert dev == host == [(k, n, decimal.Decimal(s).scaleb(-2))
                           for k, (n, s) in sorted(want.items())]


def test_a_build_filter_that_keeps_nothing(cl):
    dev, host, explain = both_arms(
        cl, Q3.format(seg="BUILDING", date="1990-01-01"))
    assert on_device(explain) and dev == host == []
    assert explain["join"]["rows_out"] == 0
    # a scalar aggregate over no joined row is still one row
    sql = ("select count(*), sum(l_discount) from orders, lineitem where "
           "l_orderkey = o_orderkey and o_orderdate < date '1990-01-01'")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain) and dev == host == [(0, None)]


def test_batches_cut_mid_chunk(cl, data, monkeypatch):
    from citus_tpu.executor import executor as ex
    from citus_tpu.executor.batches import load_padded_batches
    monkeypatch.setattr(ex, "load_padded_batches", functools.partial(
        load_padded_batches, max_batch_rows=128))
    cl.execute("SET citus.executor_min_batch_rows = 64")
    dev, host, explain = both_arms(
        cl, Q3.format(seg="HOUSEHOLD", date=iso(DAY0 + 80)))
    assert on_device(explain)
    assert explain["pipeline"]["fused_dispatches"] > 40
    cl.execute("SET citus.executor_min_batch_rows = 8192")
    assert dev == host == data.q3("HOUSEHOLD", DAY0 + 80)


def test_a_block_that_overflows_takes_further_rounds(cl, data, monkeypatch):
    # the capacity is the kernel builder's argument
    monkeypatch.setattr(JD._DeviceJoin, "block_rows", 16)
    sql = ("select o_orderdate, count(*), sum(l_extendedprice) from "
           "lineitem, orders where l_orderkey = o_orderkey "
           "group by o_orderdate order by o_orderdate")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain) and explain["join"]["overflow_rounds"] > 10
    want = {}
    order = {k: i for i, k in enumerate(data.o_orderkey.tolist())}
    for li, k in enumerate(data.l_orderkey.tolist()):
        oi = order[k]
        n, s = want.get(int(data.o_orderdate[oi]), (0, 0))
        want[int(data.o_orderdate[oi])] = (
            n + 1, s + int(data.l_extendedprice[li]))
    assert dev == host == [
        (EPOCH + datetime.timedelta(days=d), n, decimal.Decimal(s).scaleb(-2))
        for d, (n, s) in sorted(want.items())]
    assert explain["join"]["rows_out"] == len(data.l_orderkey)


def test_text_and_date_payload_having_order_limit(cl, data):
    sql = ("select c_mktsegment, o_orderdate, count(*) as n, "
           "min(l_shipdate) from lineitem join orders on l_orderkey = "
           "o_orderkey join customer on c_custkey = o_custkey "
           "where l_discount >= 0.03 group by c_mktsegment, o_orderdate "
           "having count(*) > 4 order by n desc, c_mktsegment, o_orderdate "
           "limit 7")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain)
    want = {}
    for li, oi, ci in data.joined():
        if data.l_discount[li] >= 3:
            key = (data.c_mktsegment[ci], int(data.o_orderdate[oi]))
            n, m = want.get(key, (0, 10 ** 9))
            want[key] = (n + 1, min(m, int(data.l_shipdate[li])))
    rows = [(k[0], EPOCH + datetime.timedelta(days=k[1]), n,
             EPOCH + datetime.timedelta(days=m))
            for k, (n, m) in want.items() if n > 4]
    rows.sort(key=lambda r: (-r[2], r[0], r[1]))
    assert dev == host == rows[:7] and len(dev) == 7


@pytest.fixture(scope="module")
def pairs(tmp_path_factory):
    """Two tables colocated on ``k1`` and joined on (k1, k2); ``b`` has
    shards with no row, and ``d`` (same keys twice) is not unique."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("pairs") / "db"))
    rng = np.random.default_rng(11)
    for t in "abd":
        cl.execute(f"CREATE TABLE {t} (k1 bigint NOT NULL, k2 integer, "
                   f"v bigint)")
        cl.execute(f"SELECT create_distributed_table('{t}', 'k1', 8)")
    bk1 = rng.choice(50, 3, replace=False)        # three keys: empty shards
    b = [(int(k), j, int(rng.integers(0, 100))) for k in bk1 for j in range(4)]
    a = [(int(rng.integers(0, 50)), int(rng.integers(0, 6)),
          int(rng.integers(0, 1000))) for _ in range(3000)]
    for t, rows in (("a", a), ("b", b), ("d", b + b[:5])):
        cl.copy_from(t, columns={"k1": [r[0] for r in rows],
                                 "k2": [r[1] for r in rows],
                                 "v": [r[2] for r in rows]})
    return cl, a, b


def test_a_two_lane_key_and_empty_build_shards(pairs):
    cl, a, b = pairs
    sql = ("select b.v, count(*), sum(a.v) from a join b on a.k1 = b.k1 "
           "and a.k2 = b.k2 group by b.v order by b.v")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain) and explain["join"]["probe"] == "a"
    at = {(k1, k2): v for k1, k2, v in b}
    want = {}
    for k1, k2, v in a:
        if (k1, k2) in at:
            n, s = want.get(at[k1, k2], (0, 0))
            want[at[k1, k2]] = (n + 1, s + v)
    assert dev == host == [(k, n, s) for k, (n, s) in sorted(want.items())]
    assert dev


def test_later_probe_levels_hold_what_the_first_pair_cannot(cl, data,
                                                             monkeypatch):
    sql = ("select count(*), sum(l_extendedprice) from lineitem, orders "
           "where l_orderkey = o_orderkey")
    want = [(len(data.l_orderkey),
             decimal.Decimal(int(data.l_extendedprice.sum())).scaleb(-2))]
    shard = len(data.o_orderkey) // cl.catalog.table("orders").shard_count
    # a table at a load of 0.37: one entry in twenty loses its first
    # pair of slots and goes on to a later one
    monkeypatch.setattr(JD._DeviceJoin, "_slots_of",
                        lambda self, a: JD._pow2_at_least(2 * shard, 256))
    r = cl.execute(sql)
    assert on_device(r.explain) and r.explain["join"]["later_level_rows"] > 0
    assert r.rows == want
    # a table with fewer slots than rows: the host path answers
    monkeypatch.setattr(JD._DeviceJoin, "_slots_of", lambda self, a: 128)
    r = cl.execute(sql)
    assert "is full" in r.explain["join"]["why"] and r.rows == want


# ------------------------------------------- (d) the direct-address table


def test_direct_table_from_the_footers_facts_alone(tmp_path, monkeypatch):
    """A build whose ONE integer key the footers bound takes the
    direct-address table (index = key - min, one gather a probe row);
    without the proof -- a shard that contributed no footer -- or past
    the memory rule, the hash table; the same answers either way."""
    from citus_tpu.catalog import stats
    data = Data(13, orders=1200, dense=True)
    cl = ct.Cluster(str(tmp_path / "db"))
    data.load(cl, 4)
    sql = Q3.format(seg="FURNITURE", date=iso(DAY0 + 66))
    want = data.q3("FURNITURE", DAY0 + 66)
    kinds = lambda r: {a: (t["table"], t["slots"])
                       for a, t in r.explain["join"]["tables"].items()}
    r = cl.execute(sql)
    assert on_device(r.explain) and r.rows == want
    # customer's keys span 1..200, the orders' 1..4,800 (8 of every 32)
    assert kinds(r) == {"customer": ("direct", 1024),
                        "orders": ("direct", 8192)}
    lines = "\n".join(l for (l,) in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    assert "customer direct 1024 slots a query" in lines \
        and "orders direct 8192 slots a shard" in lines
    assert r.explain["join"]["later_level_rows"] == 0
    # NULL keys and keys outside the span match nothing
    sql_alt = ("select count(*), sum(l_discount) from orders, lineitem, "
               "customer where o_custkey = c_altkey and "
               "l_orderkey = o_orderkey")
    dev, host, explain = both_arms(cl, sql_alt)
    assert on_device(explain) and dev == host
    assert dev[0][0] == sum(1 for _ in data.joined(cust_key="c_altkey")) > 0
    # a nullable key column's footers bound its values all the same
    assert explain["join"]["tables"]["customer"]["table"] == "direct"
    # no proof: a shard without a footer proves nothing
    monkeypatch.setattr(stats, "table_facts", lambda cat, table: None)
    r = cl.execute(sql)
    assert {k: v[0] for k, v in kinds(r).items()} == {
        "customer": "hash", "orders": "hash"} and r.rows == want
    monkeypatch.undo()
    # the memory rule: an index that would take more than its share
    monkeypatch.setattr(JD, "DIRECT_MEMORY_SHARE", 1e-6)
    r = cl.execute(sql)
    assert kinds(r) == {"customer": ("direct", 1024),
                        "orders": ("hash", 4096)} and r.rows == want


def test_direct_table_fuller_than_the_catalog_counted(tmp_path, monkeypatch):
    """The lanes of a direct-address table hold the rows the catalog
    counted: a build that packs more goes to the host path."""
    from citus_tpu.catalog import stats
    data = Data(17, orders=5000, dense=True)
    cl = ct.Cluster(str(tmp_path / "db"))
    data.load(cl, 4)
    sql = ("select count(*), sum(l_discount) from customer, orders, lineitem "
           "where c_custkey = o_custkey and l_orderkey = o_orderkey")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain) and dev == host
    assert explain["join"]["tables"]["orders"]["table"] == "direct"
    monkeypatch.setattr(stats, "shard_row_counts",
                        lambda cat, t: [0] * max(1, t.shard_count))
    r = cl.execute(sql)
    assert r.explain["join"] == {
        "on": "host", "why": "build table of orders is full"}
    assert r.rows == host


def test_direct_table_sends_a_key_met_twice_to_the_host(pairs):
    cl, a, b = pairs
    cl.execute("CREATE TABLE e (k1 bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('e', 'k1', 8)")
    keys = sorted({r[0] for r in b})
    cl.copy_from("e", columns={"k1": keys + keys[:1],
                               "v": list(range(len(keys) + 1))})
    dev, host, explain = both_arms(
        cl, "select count(*), sum(e.v) from a join e on a.k1 = e.k1")
    assert explain["join"]["on"] == "host" \
        and "not unique" in explain["join"]["why"]
    assert dev == host and dev[0][0] > 0


# ------------------------------------------------- (c) the host path stays


@pytest.mark.parametrize("sql,why", [
    ("select count(*), sum(a.v) from a join d on a.k1 = d.k1 and "
     "a.k2 = d.k2", "not unique"),
    ("select count(*), sum(d.v) from a left join d on a.k1 = d.k1 and "
     "a.k2 = d.k2", "left step"),
    ("select count(*), sum(a.v) from a join b on a.k1 = b.k1 and "
     "a.k2 = b.k2 and a.v > b.v", "residual"),
    ("select a.v, b.v from a join b on a.k1 = b.k1 and a.k2 = b.k2 "
     "where a.v < 100 order by 1, 2", "no aggregate"),
])
def test_what_the_device_does_not_run_goes_to_the_host(pairs, sql, why):
    cl, _, _ = pairs
    c0 = GLOBAL_COUNTERS.snapshot().get("join_host_fallbacks", 0)
    dev, host, explain = both_arms(cl, sql)
    assert GLOBAL_COUNTERS.snapshot()["join_host_fallbacks"] == c0 + 1
    assert explain["join"]["on"] == "host" and why in explain["join"]["why"]
    assert dev == host and dev


# ------------------------------------------------ (e) spans and counters


def test_spans_and_counters(cl, data, monkeypatch):
    from citus_tpu.observability import trace as T

    def no_frame(*a, **k):
        raise AssertionError("a relation whole in host memory")
    monkeypatch.setattr(JX, "_load_rel_frame", no_frame)
    sql = Q3.format(seg="BUILDING", date=iso(DAY0 + 74))
    cl.execute(sql)
    cl.execute("SET citus.trace_sample_rate = 1.0")
    try:
        c0 = GLOBAL_COUNTERS.snapshot()
        r = cl.execute(sql)
        c1 = GLOBAL_COUNTERS.snapshot()
        tr = T.last_trace()
    finally:
        cl.execute("SET citus.trace_sample_rate = 0")
    assert r.rows == data.q3("BUILDING", DAY0 + 74)
    d = lambda n: c1.get(n, 0) - c0.get(n, 0)
    shards = r.explain["tasks"]
    j = r.explain["join"]
    assert d("join_host_fallbacks") == 0 and d("join_queries") == 1
    assert d("join_rows_probed") == j["rows_probed"] >= len(data.l_orderkey)
    assert d("join_rows_probed") >= d("join_rows_matched") \
        >= d("join_rows_out") > 0
    assert d("join_rows_built") == j["rows_built"] > 0
    assert d("join_table_bytes") == j["table_bytes"] > 0
    assert d("join_overflow_rounds") == 0 and d("kernel_cache_misses") == 0
    execute = tr.find("execute")
    assert execute.attrs["strategy"] == "join:colocated"
    (bc,) = tr.find_all("join_broadcast")
    assert bc.attrs["relation"] == "customer" and bc.attrs["rows"] == 200
    assert 0 < bc.attrs["rows_kept"] < 200 and bc.attrs["bytes"] > 0
    builds = tr.find_all("join_build")
    assert [b.attrs["shard_index"] for b in builds] == list(range(shards))
    assert all(b.attrs["relation"] == "orders" and b.attrs["table"] == "hash"
               for b in builds)
    assert sum(b.attrs["rows_in"] for b in builds) == len(data.o_orderkey)
    assert sum(b.attrs["rows_built"] for b in builds) + bc.attrs["rows_kept"] \
        == j["rows_built"]
    rounds = tr.find_all("device_round")
    assert len(rounds) == len(tr.find_all("h2d")) \
        == len(tr.find_all("dispatch")) == 1 + 2 * shards
    for name in ("plan", "bind", "hash_init", "fetch", "finalize_groups",
                 "order_and_limit", "decode_batch"):
        assert tr.find(name) is not None, name
    assert tr.find("fetch").attrs["entries"] == j["agg_slots"]
    assert 0 < j["groups"] == tr.find("finalize_groups").attrs["groups"]
    lines = "\n".join(l for (l,) in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    assert "Join: on device, probe lineitem" in lines \
        and "orders hash" in lines and "rows built" in lines


# ---------------------------------------------- (f) ingest between joins


def test_an_ingest_between_two_joins(tmp_path, monkeypatch):
    from citus_tpu.transaction import snapshot
    data = Data(3, orders=600)
    cl = ct.Cluster(str(tmp_path / "db"))
    half = slice(0, 300)
    # load the first half of the orders (and their lines), join, load
    # the rest, join again: each answer is the plain join's over what
    # was visible, on both arms
    full = data.load_orders
    data.load_orders = lambda c, part: full(c, half)
    data.load(cl, 4)
    seen = []
    multi = snapshot.snapshot_read_multi
    monkeypatch.setattr(
        snapshot, "snapshot_read_multi",
        lambda d, tables, fn, **kw: (seen.append(
            sorted(t.name for t in tables)), multi(d, tables, fn, **kw))[1])
    sql = Q3.format(seg="BUILDING", date=iso(DAY0 + 74))
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain)
    assert dev == host == data.q3("BUILDING", DAY0 + 74, orders=half)
    full(cl, slice(300, None))
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain)
    assert dev == host == data.q3("BUILDING", DAY0 + 74)
    assert seen and all(s == ["customer", "lineitem", "orders"] for s in seen)
