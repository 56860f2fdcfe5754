"""A join GRAPH with a cycle on the device (``planner/join_planner.py``
``plan_device_join``: a spanning tree of unique builds and the
equalities left over as cycle filters of the root;
``executor/join_device.py``; ``ops/join.py`` scope ``probe.filter``)
against the numpy arm of the engine (``task_executor_backend = 'cpu'``:
the host join, the oracle) and against a join written here in plain
Python over the generated columns (no engine code).  Answers are EQUAL.

The tables are TPC-H Q5's in small: ``orders`` and ``lineitem``
hash-distributed and colocated on the order key; ``customer``,
``supplier``, ``nation`` and ``region`` reference tables.  ``lineitem``
has no filter of its own and is probed against TWO children (``orders``,
a table a shard; ``supplier``, a table a query); ``customer –
orders – lineitem – supplier – customer`` is the cycle.
"""

import datetime
import decimal

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import join_device as JD
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.planner.join_planner import (
    DeviceJoinTree, bind_join_select, plan_device_join,
)
from citus_tpu.planner.parser import parse_statement

EPOCH = datetime.date(1970, 1, 1)
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
#: (n_name, n_regionkey) by n_nationkey, the spec's 25
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1))

FROM = ["customer", "orders", "lineitem", "supplier", "nation", "region"]
WHERE = """c_custkey = o_custkey and l_orderkey = o_orderkey
 and l_suppkey = s_suppkey and c_nationkey = s_nationkey
 and s_nationkey = n_nationkey and n_regionkey = r_regionkey
 and r_name = '{region}' and o_orderdate >= date '{date}'
 and o_orderdate < date '{date}' + interval '1' year"""
Q5 = ("select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue "
      "from {tables} where " + WHERE + "{more} "
      "group by n_name order by revenue desc")
Q5_ON = """select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
 from lineitem join orders on l_orderkey = o_orderkey
 join customer on c_custkey = o_custkey
 join supplier on l_suppkey = s_suppkey and c_nationkey = s_nationkey
 join nation on s_nationkey = n_nationkey
 join region on n_regionkey = r_regionkey
 where r_name = '{region}' and o_orderdate >= date '{date}'
 and o_orderdate < date '{date}' + interval '1' year
 group by n_name order by revenue desc"""
#: the tree every form of Q5 plans, and its one filter
TREE = {"orders": "lineitem", "supplier": "lineitem", "customer": "orders",
        "nation": "supplier", "region": "nation"}
FILTER = "customer.c_nationkey = supplier.s_nationkey"


def q5(region="ASIA", date="1994-01-01", tables=FROM, more=""):
    return Q5.format(tables=", ".join(tables), region=region, date=date,
                     more=more)


def day_of(iso):
    return (datetime.date.fromisoformat(iso) - EPOCH).days


class Data:
    """Seeded columns of the six tables, as Python sees them."""

    def __init__(self, seed, orders=1200, customers=120, suppliers=40,
                 null_nations=False, repeat_supplier=None):
        rng = np.random.default_rng(seed)
        self.c_custkey = np.arange(1, customers + 1)
        self.c_nationkey = rng.integers(0, 25, customers).tolist()
        self.s_suppkey = np.arange(1, suppliers + 1)
        if repeat_supplier == "dense":
            self.s_suppkey[-1] = self.s_suppkey[0]
        elif repeat_supplier == "sparse":
            self.s_suppkey = self.s_suppkey * 7
            self.s_suppkey[-1] = self.s_suppkey[0]
        self.s_nationkey = rng.integers(0, 25, suppliers).tolist()
        if repeat_supplier:
            # both rows of the key reach the build: INDIA, in ASIA
            self.s_nationkey[0] = self.s_nationkey[-1] = 8
        if null_nations:
            for i in range(0, customers, 9):
                self.c_nationkey[i] = None
            for i in range(0, suppliers, 7):
                self.s_nationkey[i] = None
        self.o_orderkey = rng.choice(10 ** 9, orders, replace=False)
        # some orders name a customer the table does not hold
        self.o_custkey = rng.integers(1, customers + 10, orders)
        self.o_orderdate = day_of("1993-01-01") + rng.integers(
            0, 5 * 365, orders)
        lines = rng.integers(1, 5, orders)
        at = np.repeat(np.arange(orders), lines)
        self.l_orderkey = self.o_orderkey[at]
        # ... and some lines a supplier it does not hold
        scale = 7 if repeat_supplier == "sparse" else 1
        self.l_suppkey = rng.integers(1, suppliers + 4, at.size) * scale
        self.l_extendedprice = rng.integers(100, 10 ** 6, at.size)
        self.l_discount = rng.integers(0, 11, at.size)

    def load(self, cl, shards=4):
        def table(name, ddl, dist=None):
            cl.execute(f"CREATE TABLE {name} ({ddl})")
            cl.execute(f"SELECT create_distributed_table('{name}', "
                       f"'{dist}', {shards})" if dist
                       else f"SELECT create_reference_table('{name}')")
        table("orders", "o_orderkey bigint NOT NULL, o_custkey bigint, "
              "o_orderdate date", "o_orderkey")
        table("lineitem", "l_orderkey bigint NOT NULL, l_suppkey bigint, "
              "l_extendedprice decimal(15,2), l_discount decimal(15,2)",
              "l_orderkey")
        table("customer", "c_custkey bigint NOT NULL, c_nationkey integer, "
              "c_name text")
        table("supplier", "s_suppkey bigint NOT NULL, s_nationkey integer, "
              "s_name text")
        table("nation", "n_nationkey integer NOT NULL, n_name text, "
              "n_regionkey integer")
        table("region", "r_regionkey integer NOT NULL, r_name text")
        dec = lambda a: [decimal.Decimal(int(v)).scaleb(-2) for v in a]
        cl.copy_from("region", columns={
            "r_regionkey": np.arange(5).astype(np.int32), "r_name": REGIONS})
        cl.copy_from("nation", columns={
            "n_nationkey": np.arange(25).astype(np.int32),
            "n_name": [n for n, _ in NATIONS],
            "n_regionkey": np.array([r for _, r in NATIONS], np.int32)})
        cl.copy_from("supplier", columns={
            "s_suppkey": self.s_suppkey, "s_nationkey": self.s_nationkey,
            "s_name": [f"Supplier#{i:09d}"
                       for i in range(1, len(self.s_suppkey) + 1)]})
        cl.copy_from("customer", columns={
            "c_custkey": self.c_custkey, "c_nationkey": self.c_nationkey,
            "c_name": [f"Customer#{k:09d}" for k in self.c_custkey]})
        cl.copy_from("orders", columns={
            "o_orderkey": self.o_orderkey, "o_custkey": self.o_custkey,
            "o_orderdate": self.o_orderdate.astype(np.int32)})
        cl.copy_from("lineitem", columns={
            "l_orderkey": self.l_orderkey, "l_suppkey": self.l_suppkey,
            "l_extendedprice": dec(self.l_extendedprice),
            "l_discount": dec(self.l_discount)})

    # ------------------------------------------------ the plain join
    def joined(self, region=None, date=None):
        """Per line with a partner in every relation of the TREE (the
        region's and the year's filters applied, the cycle's equality
        not): (line, the customer's nation key, the supplier's)."""
        cust = dict(zip(self.c_custkey.tolist(), self.c_nationkey))
        supp = dict(zip(self.s_suppkey.tolist(), self.s_nationkey))
        order = {int(k): i for i, k in enumerate(self.o_orderkey)}
        lo = day_of(date) if date else None
        hi = date and day_of(f"{int(date[:4]) + 1}{date[4:]}")
        for li, (k, s) in enumerate(zip(self.l_orderkey.tolist(),
                                        self.l_suppkey.tolist())):
            oi = order[k]
            c = int(self.o_custkey[oi])
            if c not in cust or s not in supp:
                continue
            if date and not lo <= self.o_orderdate[oi] < hi:
                continue
            sn = supp[s]
            # the supplier's nation joins nation and region
            if sn is None or (region is not None and
                              REGIONS[NATIONS[sn][1]] != region):
                continue
            yield li, cust[c], sn

    def q5(self, region, date):
        revenue = {}
        for li, cn, sn in self.joined(region, date):
            if cn is not None and cn == sn:
                revenue[sn] = revenue.get(sn, 0) + int(
                    self.l_extendedprice[li]) * (100 - int(
                        self.l_discount[li]))
        rows = [(NATIONS[n][0], decimal.Decimal(v).scaleb(-4))
                for n, v in revenue.items()]
        return sorted(rows, key=lambda r: -r[1])

    def cycle_counts(self, region, date):
        """(rows the cycle filter sees, rows it keeps)."""
        pairs = [(cn, sn) for _, cn, sn in self.joined(region, date)]
        return len(pairs), sum(cn is not None and cn == sn
                               for cn, sn in pairs)


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


def no_tie(rows):
    return len({r[1] for r in rows}) == len(rows)


def cluster(tmp_path_factory, data, name):
    cl = ct.Cluster(str(tmp_path_factory.mktemp(name) / "db"))
    data.load(cl)
    return cl


@pytest.fixture(scope="module")
def data():
    return Data(11)


@pytest.fixture(scope="module")
def cl(data, tmp_path_factory):
    return cluster(tmp_path_factory, data, "cycle")


def plan_of(cl, sql, bounds=True):
    """The plan as ``run_device_join`` asks for it."""
    from citus_tpu.catalog.stats import column_bounds, shard_row_counts
    bj = bind_join_select(cl.catalog, parse_statement(sql))
    tables = dict(bj.rels)
    rows = {a: sum(shard_row_counts(cl.catalog, t)) for a, t in bj.rels}
    return bj, plan_device_join(
        bj, rows, bounds=(lambda a: column_bounds(cl.catalog, tables[a]))
        if bounds else None)


# ------------------------------------------------------------ (a) the plan


def test_q5_plans_a_tree_and_one_cycle_filter(cl):
    bj, tree = plan_of(cl, q5())
    assert bj.strategy == "colocated"
    assert isinstance(tree, DeviceJoinTree), tree
    assert tree.root == "lineitem" and tree.parent == TREE
    assert tree.builds == ["region", "nation", "customer", "supplier",
                           "orders"]
    assert [(e.left.name, e.op, e.right.name)
            for e in tree.cycle_filters] == [
        ("customer.c_nationkey", "=", "supplier.s_nationkey")]
    # each build is keyed by ITS side of the edge to its parent
    assert [k.name for k in tree.edge["supplier"][0]] == [
        "supplier.s_suppkey"]
    assert [k.name for k in tree.edge["customer"][0]] == [
        "customer.c_custkey"]


def test_the_counts_disprove_the_nation_key_as_a_build_key(cl):
    """``s_nationkey`` (span 25 over 40 suppliers) can be no build key,
    ``s_suppkey`` (span 40) can; what the footers do not bound is not
    disproved.  (What the count decides in a plan:
    ``test_a_graph_whose_only_tree_hangs_the_extra_edge_elsewhere``.)"""
    from citus_tpu.catalog.stats import column_bounds
    from citus_tpu.planner.join_planner import _repeats
    bj, _ = plan_of(cl, q5())
    tables = dict(bj.rels)
    col = {k.name: k for s in bj.steps for k in s.left_keys + s.right_keys}
    known = lambda a: column_bounds(cl.catalog, tables[a])
    assert _repeats([col["supplier.s_nationkey"]], 40, known("supplier"))
    assert not _repeats([col["supplier.s_suppkey"]], 40, known("supplier"))
    assert _repeats([col["customer.c_nationkey"]], 120, known("customer"))
    assert not _repeats([col["nation.n_nationkey"]], 25, known("nation"))
    # nothing known, nothing disproved
    assert not _repeats([col["supplier.s_nationkey"]], 40, None)
    assert not _repeats([col["supplier.s_nationkey"]], 40, {})


PERMUTATIONS = [
    ["supplier", "customer", "orders", "lineitem", "nation", "region"],
    ["customer", "lineitem", "orders", "region", "nation", "supplier"],
    ["region", "nation", "supplier", "lineitem", "orders", "customer"],
    ["lineitem", "supplier", "customer", "orders", "nation", "region"],
    ["nation", "region", "customer", "supplier", "lineitem", "orders"],
    ["orders", "customer", "region", "lineitem", "nation", "supplier"],
]


@pytest.mark.parametrize("tables", PERMUTATIONS,
                         ids=lambda t: "-".join(a[0] for a in t))
def test_a_permutation_of_from_plans_the_same_tree(cl, data, tables):
    assert sorted(tables) == sorted(FROM)
    _, tree = plan_of(cl, q5(tables=tables))
    assert isinstance(tree, DeviceJoinTree), tree
    assert tree.root == "lineitem" and tree.parent == TREE
    assert len(tree.cycle_filters) == 1
    dev, host, explain = both_arms(cl, q5(tables=tables))
    want = data.q5("ASIA", "1994-01-01")
    assert want and no_tie(want)
    assert dev == want and host == want
    assert on_device(explain)
    assert explain["join"]["tree"] == TREE
    # the equality as the statement wrote it, either way round
    assert [set(f.split(" = ")) for f in explain["join"]["cycle_filters"]] \
        == [set(FILTER.split(" = "))]


# ------------------------------------------------------ (b) Q5, both arms


@pytest.mark.parametrize("region,date", [
    ("ASIA", "1994-01-01"), ("EUROPE", "1993-01-01"),
    ("AMERICA", "1997-01-01"), ("MIDDLE EAST", "1995-01-01"),
    ("AFRICA", "1996-01-01")])
def test_q5_as_published_equals_the_plain_join(cl, data, region, date):
    dev, host, explain = both_arms(cl, q5(region, date))
    want = data.q5(region, date)
    assert want and no_tie(want)
    assert dev == want and host == want
    assert on_device(explain)
    j = explain["join"]
    assert j["probe"] == "lineitem" and j["tree"] == TREE
    assert j["cycle_filters"] == [FILTER]
    assert j["probe_children"] == 2
    # orders a table a shard, supplier (and what hangs on it) a query
    assert {a: t["built_per"] for a, t in j["tables"].items()} == {
        "orders": "shard", "customer": "query", "supplier": "query",
        "nation": "query", "region": "query"}
    # lineitem has no filter of its own: every probed row is looked up
    assert j["rows_looked_up"] == j["rows_probed"] >= len(data.l_orderkey)
    assert (j["cycle_rows_in"], j["cycle_rows_kept"]) \
        == data.cycle_counts(region, date)
    assert j["rows_out"] == j["cycle_rows_kept"] > 0
    assert j["groups"] == len(want) <= 5


def test_q5_written_with_join_on(cl, data):
    sql = Q5_ON.format(region="ASIA", date="1995-01-01")
    _, tree = plan_of(cl, sql)
    assert tree.parent == TREE and len(tree.cycle_filters) == 1
    dev, host, explain = both_arms(cl, sql)
    want = data.q5("ASIA", "1995-01-01")
    assert want and dev == want and host == want
    assert on_device(explain)
    assert explain["join"]["cycle_filters"] == [FILTER]


def test_a_new_region_and_date_compile_nothing(cl):
    cl.execute(q5("ASIA", "1994-01-01"))
    before = GLOBAL_COUNTERS.snapshot()
    for region, date in (("EUROPE", "1996-01-01"), ("AFRICA", "1993-01-01")):
        assert on_device(cl.execute(q5(region, date)).explain)
    after = GLOBAL_COUNTERS.snapshot()
    for name in ("kernel_cache_misses", "kernel_compiles"):
        assert after.get(name, 0) == before.get(name, 0), name


def test_a_null_nation_key_drops_the_row(tmp_path_factory):
    data = Data(12, null_nations=True)
    cl = cluster(tmp_path_factory, data, "nulls")
    assert None in data.c_nationkey and None in data.s_nationkey
    nulls_seen = rows = 0
    for region, date in (("ASIA", "1994-01-01"), ("EUROPE", "1996-01-01"),
                         ("AMERICA", "1993-01-01")):
        dev, host, explain = both_arms(cl, q5(region, date))
        want = data.q5(region, date)
        assert dev == want and host == want
        rows += len(want)
        assert on_device(explain)
        j = explain["join"]
        assert (j["cycle_rows_in"], j["cycle_rows_kept"]) \
            == data.cycle_counts(region, date)
        nulls_seen += sum(cn is None
                          for _, cn, _ in data.joined(region, date))
    # a customer without a nation reaches the filter and fails it
    assert nulls_seen and rows


def test_a_cycle_of_two_filters(cl, data):
    """``c_nationkey = n_nationkey`` is implied by the two equalities it
    closes a second cycle with: the answers stand; ``nation`` (and
    ``region`` with it) now hangs on ``customer``, the larger parent,
    and both equalities to ``supplier`` are filters."""
    more = " and c_nationkey = n_nationkey"
    _, tree = plan_of(cl, q5(more=more))
    assert tree.parent == {
        "orders": "lineitem", "supplier": "lineitem", "customer": "orders",
        "nation": "customer", "region": "nation"}
    assert sorted(tuple(sorted((e.left.name, e.right.name)))
                  for e in tree.cycle_filters) == [
        ("customer.c_nationkey", "supplier.s_nationkey"),
        ("nation.n_nationkey", "supplier.s_nationkey")]
    for region, date in (("ASIA", "1994-01-01"), ("AMERICA", "1995-01-01")):
        dev, host, explain = both_arms(cl, q5(region, date, more=more))
        want = data.q5(region, date)
        assert want and dev == want and host == want
        assert on_device(explain)
        assert len(explain["join"]["cycle_filters"]) == 2
        # region's filter now thins the orders' builds
        assert explain["join"]["tables"]["customer"]["built_per"] == "query"


def test_an_unfiltered_probe_takes_overflow_rounds_with_a_cycle_filter(
        cl, data, monkeypatch):
    sql = q5("ASIA", "1994-01-01")
    # the batches' rows, padding included: a block that holds its batch
    whole = cl.execute(sql).explain["join"]
    padded = whole["rows_probed"]
    assert whole["overflow_rounds"] == 0 and padded >= len(data.l_orderkey)
    # the capacity is the kernel builder's argument
    monkeypatch.setattr(JD._DeviceJoin, "block_rows", 4)
    before = GLOBAL_COUNTERS.snapshot()
    dev, host, explain = both_arms(cl, sql)
    after = GLOBAL_COUNTERS.snapshot()
    want = data.q5("ASIA", "1994-01-01")
    assert dev == want and host == want and on_device(explain)
    j = explain["join"]
    seen, kept = data.cycle_counts("ASIA", "1994-01-01")
    # the rows with a partner in both children are many blocks of 4: a
    # shard's one batch takes three further rounds and more -- and is
    # looked up ONCE: a further round cuts its block from what round 0
    # left on the device
    shards = explain["tasks"]
    assert seen > 4 * 4 * shards and j["overflow_rounds"] >= 3 * shards
    assert j["rows_probed"] == j["rows_looked_up"] == padded
    assert (j["cycle_rows_in"], j["cycle_rows_kept"]) == (seen, kept)
    d = lambda n: after.get(n, 0) - before.get(n, 0)
    assert d("join_rows_looked_up") == d("join_rows_probed") == padded
    assert d("join_cycle_rows_in") == seen
    assert d("join_cycle_rows_kept") == kept
    assert d("join_cycle_filters") == 1 and d("join_probe_children") == 2
    assert d("join_overflow_rounds") == j["overflow_rounds"]
    assert d("join_host_fallbacks") == 0


# --------------------------------------- (c) other graphs, and the host path


def test_a_graph_whose_only_tree_hangs_the_extra_edge_elsewhere(
        tmp_path_factory):
    """``fact – a`` on a's unique key, ``fact – b`` on a lane of ``b``
    that repeats, ``a – b`` on b's unique key: ``b`` can only hang on
    ``a``, and the edge from the fact table is the cycle's filter."""
    rng = np.random.default_rng(3)
    cl = ct.Cluster(str(tmp_path_factory.mktemp("other") / "db"))
    cl.execute("CREATE TABLE fact (f_id bigint NOT NULL, f_a bigint, "
               "f_g integer, f_v bigint)")
    cl.execute("SELECT create_distributed_table('fact', 'f_id', 4)")
    cl.execute("CREATE TABLE a (a_id bigint NOT NULL, a_b bigint)")
    cl.execute("SELECT create_reference_table('a')")
    cl.execute("CREATE TABLE b (b_id bigint NOT NULL, b_g integer, "
               "b_name text)")
    cl.execute("SELECT create_reference_table('b')")
    n, na, nb = 3000, 200, 60
    a_b = rng.integers(1, nb + 5, na)
    b_g = rng.integers(0, 6, nb)
    f_a, f_g = rng.integers(1, na + 10, n), rng.integers(0, 6, n)
    f_v = rng.integers(0, 1000, n)
    cl.copy_from("a", columns={"a_id": np.arange(1, na + 1), "a_b": a_b})
    cl.copy_from("b", columns={
        "b_id": np.arange(1, nb + 1), "b_g": b_g.astype(np.int32),
        "b_name": [f"b{i % 7}" for i in range(nb)]})
    cl.copy_from("fact", columns={
        "f_id": np.arange(n), "f_a": f_a, "f_g": f_g.astype(np.int32),
        "f_v": f_v})
    sql = ("select b_name, count(*), sum(f_v) from b, fact, a "
           "where f_a = a_id and f_g = b_g and a_b = b_id "
           "group by b_name order by b_name")
    _, tree = plan_of(cl, sql)
    assert isinstance(tree, DeviceJoinTree), tree
    assert tree.root == "fact" and tree.parent == {"a": "fact", "b": "a"}
    assert [{e.left.name, e.right.name} for e in tree.cycle_filters] == [
        {"fact.f_g", "b.b_g"}]
    # blind to the counts the fact table, the larger parent, takes b
    # by the lane that repeats: the build says so, and the host answers
    _, blind = plan_of(cl, sql, bounds=False)
    assert blind.parent == {"a": "fact", "b": "fact"}
    dev, host, explain = both_arms(cl, sql)
    want = {}
    for i in range(n):
        if f_a[i] <= na and a_b[f_a[i] - 1] <= nb \
                and b_g[a_b[f_a[i] - 1] - 1] == f_g[i]:
            name = f"b{(a_b[f_a[i] - 1] - 1) % 7}"
            c, s = want.get(name, (0, 0))
            want[name] = (c + 1, s + int(f_v[i]))
    want = [(k, c, s) for k, (c, s) in sorted(want.items())]
    assert want and dev == want and host == want
    assert on_device(explain)
    assert explain["join"]["tree"] == {"a": "fact", "b": "a"}
    assert explain["join"]["probe_children"] == 1


@pytest.mark.parametrize("repeat,why", [
    ("dense", "no spanning tree of unique builds (an expansion join)"),
    ("sparse", "build key of supplier is not unique")])
def test_a_supplier_key_that_repeats_goes_to_the_host(
        tmp_path_factory, repeat, why):
    """Dense keys: the count disproves the key before anything runs (41
    keys cannot be 40 suppliers... 40 rows over a span of 39).  Sparse
    keys: the build finds the key twice."""
    data = Data(13, repeat_supplier=repeat)
    cl = cluster(tmp_path_factory, data, repeat)
    before = GLOBAL_COUNTERS.snapshot()
    dev, host, explain = both_arms(cl, q5("ASIA", "1994-01-01"))
    after = GLOBAL_COUNTERS.snapshot()
    assert explain["join"] == {"on": "host", "why": why}
    assert after["join_host_fallbacks"] - before.get(
        "join_host_fallbacks", 0) == 1
    assert after.get("join_cycle_filters", 0) \
        == before.get("join_cycle_filters", 0)
    assert dev == host and dev


HOST = {
    "an edge off the tree under an outer step": (
        "select n_name, count(*) from lineitem join orders on l_orderkey = "
        "o_orderkey join customer on c_custkey = o_custkey left join "
        "supplier on l_suppkey = s_suppkey and c_nationkey = s_nationkey "
        "join nation on c_nationkey = n_nationkey group by n_name "
        "order by n_name"),
    "a cycle filter over float / text lanes": (
        "select n_name, count(*) from customer, orders, lineitem, supplier, "
        "nation where c_custkey = o_custkey and l_orderkey = o_orderkey "
        "and l_suppkey = s_suppkey and c_name = s_name "
        "and s_nationkey = n_nationkey group by n_name order by n_name"),
    "a disconnected join graph": (
        "select r_name, count(*) from lineitem, orders, region "
        "where l_orderkey = o_orderkey group by r_name order by r_name"),
    "a key lane the device does not hold as an integer": (
        "select s_name, count(*) from customer, supplier, orders "
        "where c_name = s_name and c_custkey = o_custkey "
        "group by s_name order by s_name"),
}


@pytest.mark.parametrize("why", list(HOST))
def test_the_host_path_names_its_reason(cl, why):
    sql = HOST[why]
    _, tree = plan_of(cl, sql)
    assert tree == why
    dev, host, explain = both_arms(cl, sql)
    assert explain["join"] == {"on": "host", "why": why}
    assert dev == host


def test_a_chain_plans_no_cycle_filter(cl, data):
    """Q3's and Q10's shape -- a chain under one child of the probe
    relation -- plans the tree it planned and no filter."""
    sql = ("select n_name, count(*) from customer, orders, lineitem, nation "
           "where c_custkey = o_custkey and l_orderkey = o_orderkey "
           "and c_nationkey = n_nationkey group by n_name order by n_name")
    _, tree = plan_of(cl, sql)
    assert tree.parent == {"orders": "lineitem", "customer": "orders",
                           "nation": "customer"}
    assert tree.builds == ["nation", "customer", "orders"]
    assert tree.cycle_filters == []
    before = GLOBAL_COUNTERS.snapshot()
    dev, host, explain = both_arms(cl, sql)
    after = GLOBAL_COUNTERS.snapshot()
    assert dev == host and on_device(explain)
    j = explain["join"]
    assert j["cycle_filters"] == [] and j["probe_children"] == 1
    assert (j["cycle_rows_in"], j["cycle_rows_kept"]) == (0, 0)
    d = lambda n: after.get(n, 0) - before.get(n, 0)
    assert d("join_cycle_filters") == d("join_cycle_rows_in") == 0
    assert d("join_probe_children") == 1


# ------------------------------------------------- (d) spans, EXPLAIN, scope


def test_the_span_and_explain_analyze_name_the_plan(cl):
    from citus_tpu.observability import trace as T
    sql = q5("ASIA", "1994-01-01")
    cl.execute(sql)
    cl.execute("SET citus.trace_sample_rate = 1.0")
    try:
        cl.execute(sql)
        tr = T.last_trace()
    finally:
        cl.execute("SET citus.trace_sample_rate = 0")
    (plan,) = tr.find_all("plan_physical")
    assert plan.attrs["cycle_filters"] == 1
    assert len(tr.find_all("join_broadcast")) == 4     # a query each
    assert {b.attrs["relation"] for b in tr.find_all("join_build")} \
        == {"orders"}
    lines = [r[0] for r in cl.execute("EXPLAIN ANALYZE " + sql).rows]
    (line,) = [l for l in lines if l.lstrip().startswith("Join:")]
    assert "probe lineitem" in line
    assert "tree: " in line and "supplier under lineitem" in line \
        and "customer under orders" in line
    assert f"cycle filters: {FILTER} (" in line


def test_the_probe_names_its_filter_scope(cl, tmp_path):
    """``probe.filter`` is a scope of the compiled probe where the root
    has cross-relation conjuncts, and of no probe without them."""
    from citus_tpu.executor import kernel_cache as KC
    import json

    def scopes_of(sql, name):
        """The scopes of the probe this statement compiled."""
        from citus_tpu.executor.device_cache import GLOBAL_CACHE
        KC.GLOBAL_KERNELS.clear()
        GLOBAL_CACHE.clear()
        cl._plan_cache.clear()
        with KC._kernels_mu:
            before = set(KC._kernels)
        assert on_device(cl.execute(sql).explain)
        with KC._kernels_mu:
            new = [k for k in KC._kernels if k not in before
                   and k.module == "jit_join_probe"]
        assert new
        found = set()
        for k in new:
            for v in list(k._variants):
                with open(KC._write_scope_map(k, v, str(tmp_path / name))) \
                        as fh:
                    ops = json.load(fh)["ops"]
                found |= {op["scope"] for op in ops.values()} \
                    | {s for op in ops.values() for s in op["inside"]}
        return found

    with_filter = scopes_of(q5(), "cycle")
    assert {"probe.lanes", "probe.lookup", "probe.block", "probe.payload",
            "probe.filter"} <= with_filter
    chain = scopes_of(
        "select count(*), sum(l_extendedprice) from lineitem, orders "
        "where l_orderkey = o_orderkey", "chain")
    assert "probe.payload" in chain and "probe.filter" not in chain


# ------------------------------------------- (e) a cycle under the exchange


@pytest.mark.parametrize("devices", [1, 4])
def test_a_cycle_under_a_single_hash_repartition(tmp_path_factory,
                                                 limit_devices, devices):
    """``orders`` distributed OFF the join key (Q12's layout): the
    single-hash repartition plans the same graph -- the exchanged
    relation hangs on the probe's, the cycle's equality is a filter --
    by the same code, on one device and on the mesh."""
    data = Data(14)
    cl = ct.Cluster(str(tmp_path_factory.mktemp(f"xchg{devices}") / "db"))
    real = cl.execute

    def load_off_key(sql, *a, **kw):
        return real(sql.replace("'orders', 'o_orderkey'",
                                "'orders', 'o_custkey'"), *a, **kw)
    cl.execute = load_off_key
    try:
        data.load(cl)
    finally:
        cl.execute = real
    limit_devices(devices)
    sql = q5("ASIA", "1994-01-01")
    bj, tree = plan_of(cl, sql)
    assert bj.strategy == "repartition"
    assert isinstance(tree, DeviceJoinTree), tree
    assert tree.parent == TREE and tree.exchanged == ("orders", 0)
    assert len(tree.cycle_filters) == 1
    dev = cl.execute(sql)
    want = data.q5("ASIA", "1994-01-01")
    assert want and dev.rows == want
    j = dev.explain["join"]
    assert dev.explain["strategy"] == "join:repartition" \
        and j["on"] == "device" and j["cycle_filters"] == [FILTER]
    assert dev.explain["shuffle"] == (
        "local" if devices == 1 else "all_to_all:device")
    assert (j["cycle_rows_in"], j["cycle_rows_kept"]) \
        == data.cycle_counts("ASIA", "1994-01-01")
