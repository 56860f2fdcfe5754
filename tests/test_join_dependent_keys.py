"""Group keys that a join proves functions of another, the ORDER BY ...
LIMIT cut on the chip and the late lookup of the dependants
(``planner/join_planner.py`` ``dependent_group_keys``, ``ops/hash_agg.py``
``build_hash_top``, ``ops/join.py`` ``build_join_lookup``,
``executor/join_device.py``) against the numpy arm of the engine
(``task_executor_backend = 'cpu'``: the host join, the oracle, which
groups on every key) and against a join written here in plain Python
over the generated columns (no engine code).  Answers are EQUAL.

The tables are TPC-H Q10's in small: ``orders`` and ``lineitem``
hash-distributed and colocated on the order key, ``customer`` and
``nation`` reference tables, ``nation`` reached through ``customer``.
"""

import datetime
import decimal

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import executor as EX
from citus_tpu.executor import join_device as JD
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.planner.join_planner import (
    DeviceJoinTree, bind_join_select, dependent_group_keys, plan_device_join,
)
from citus_tpu.planner.parser import parse_statement

EPOCH = datetime.date(1970, 1, 1)
DAY0 = (datetime.date(1993, 1, 1) - EPOCH).days
NATIONS = ["ALGERIA", "BRAZIL", "CANADA", "EGYPT", "FRANCE"]

Q10 = """select c_custkey, c_name, sum(l_extendedprice * (1 - l_discount)) as
 revenue, c_acctbal, n_name, c_address, c_phone, c_comment
 from customer, orders, lineitem, nation
 where c_custkey = o_custkey and l_orderkey = o_orderkey
 and o_orderdate >= date '{date}'
 and o_orderdate < date '{date}' + interval '3' month
 and l_returnflag = 'R' and c_nationkey = n_nationkey
 group by c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
 order by revenue desc limit 20"""

Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
 o_orderdate, o_shippriority from customer, orders, lineitem
 where c_custkey = o_custkey and l_orderkey = o_orderkey
 and o_orderdate < date '1993-06-01'
 group by l_orderkey, o_orderdate, o_shippriority
 order by revenue desc, o_orderdate limit 10"""

#: a GROUP BY over the four relations whose tail the cases below vary
BY_CUSTOMER = """select {out} from customer, orders, lineitem, nation
 where c_custkey = o_custkey and l_orderkey = o_orderkey
 and c_nationkey = n_nationkey and l_returnflag = 'R'
 group by {keys} {tail}"""


def iso(day):
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


def add_months(day, months):
    d = EPOCH + datetime.timedelta(days=int(day))
    m = d.month - 1 + months
    return (d.replace(year=d.year + m // 12, month=m % 12 + 1) - EPOCH).days


class Data:
    """Seeded columns of the four tables, as Python sees them."""

    def __init__(self, seed, orders=1200, customers=150, repeat_customer=False):
        rng = np.random.default_rng(seed)
        word = lambda n: "".join(
            chr(97 + c) for c in rng.integers(0, 26, n))
        self.c_custkey = np.arange(1, customers + 1)
        if repeat_customer:
            self.c_custkey[-1] = self.c_custkey[0]
        self.c_name = [f"Customer#{k:09d}" for k in range(1, customers + 1)]
        self.c_acctbal = rng.integers(-99999, 10 ** 6, customers)
        self.c_nationkey = rng.integers(0, len(NATIONS), customers)
        self.c_address = [word(12) for _ in range(customers)]
        self.c_phone = [f"{10 + n}-{word(6)}" for n in self.c_nationkey]
        self.c_comment = [None if i % 17 == 3 else word(20)
                          for i in range(customers)]
        self.c_since = DAY0 - rng.integers(0, 300, customers)
        self.o_orderkey = rng.choice(10 ** 9, orders, replace=False)
        self.o_custkey = rng.integers(1, customers + 20, orders)
        self.o_orderdate = DAY0 + rng.integers(0, 360, orders)
        lines = rng.integers(1, 5, orders)
        at = np.repeat(np.arange(orders), lines)
        self.l_orderkey = self.o_orderkey[at]
        self.l_extendedprice = rng.integers(100, 10 ** 6, at.size)
        self.l_discount = rng.integers(0, 11, at.size)
        self.l_returnflag = [("A", "N", "R", "R")[i]
                             for i in rng.integers(0, 4, at.size)]

    def load(self, cl, shards=4):
        cl.execute("CREATE TABLE orders (o_orderkey bigint NOT NULL, "
                   "o_custkey bigint, o_orderdate date, "
                   "o_shippriority integer)")
        cl.execute(f"SELECT create_distributed_table('orders', "
                   f"'o_orderkey', {shards})")
        cl.execute("CREATE TABLE lineitem (l_orderkey bigint NOT NULL, "
                   "l_extendedprice decimal(15,2), l_discount decimal(15,2), "
                   "l_returnflag text)")
        cl.execute(f"SELECT create_distributed_table('lineitem', "
                   f"'l_orderkey', {shards})")
        cl.execute("CREATE TABLE customer (c_custkey bigint NOT NULL, "
                   "c_name text, c_address text, c_nationkey integer, "
                   "c_phone text, c_acctbal decimal(15,2), c_comment text, "
                   "c_since date)")
        cl.execute("SELECT create_reference_table('customer')")
        cl.execute("CREATE TABLE nation (n_nationkey integer NOT NULL, "
                   "n_name text)")
        cl.execute("SELECT create_reference_table('nation')")
        dec = lambda a: [decimal.Decimal(int(v)).scaleb(-2) for v in a]
        cl.copy_from("nation", columns={
            "n_nationkey": np.arange(len(NATIONS)).astype(np.int32),
            "n_name": NATIONS})
        cl.copy_from("customer", columns={
            "c_custkey": self.c_custkey, "c_name": self.c_name,
            "c_address": self.c_address,
            "c_nationkey": self.c_nationkey.astype(np.int32),
            "c_phone": self.c_phone, "c_acctbal": dec(self.c_acctbal),
            "c_comment": self.c_comment,
            "c_since": self.c_since.astype(np.int32)})
        cl.copy_from("orders", columns={
            "o_orderkey": self.o_orderkey, "o_custkey": self.o_custkey,
            "o_orderdate": self.o_orderdate.astype(np.int32),
            "o_shippriority": np.zeros(len(self.o_orderkey), np.int32)})
        cl.copy_from("lineitem", columns={
            "l_orderkey": self.l_orderkey,
            "l_extendedprice": dec(self.l_extendedprice),
            "l_discount": dec(self.l_discount),
            "l_returnflag": self.l_returnflag})

    # ------------------------------------------------ the plain join
    def revenue(self, keep_order=lambda oi: True, flag="R"):
        """{customer index: [revenue scaled by 10**4, joined rows]}."""
        cust = {int(k): i for i, k in enumerate(self.c_custkey)}
        order = {int(k): i for i, k in enumerate(self.o_orderkey)}
        out = {}
        for li, k in enumerate(self.l_orderkey.tolist()):
            oi = order[k]
            ci = cust.get(int(self.o_custkey[oi]))
            if ci is None or self.l_returnflag[li] != flag \
                    or not keep_order(oi):
                continue
            g = out.setdefault(ci, [0, 0])
            g[0] += int(self.l_extendedprice[li]) * (
                100 - int(self.l_discount[li]))
            g[1] += 1
        return out

    def row(self, ci, revenue):
        return (int(self.c_custkey[ci]), self.c_name[ci],
                decimal.Decimal(revenue).scaleb(-4),
                decimal.Decimal(int(self.c_acctbal[ci])).scaleb(-2),
                NATIONS[self.c_nationkey[ci]], self.c_address[ci],
                self.c_phone[ci], self.c_comment[ci])

    def q10(self, day):
        end = add_months(day, 3)
        groups = self.revenue(
            lambda oi: day <= self.o_orderdate[oi] < end)
        rows = [self.row(ci, v) for ci, (v, _) in groups.items()]
        return sorted(rows, key=lambda r: -r[2])[:20]


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


def no_tie(rows, at):
    """The rows' sort values at ``at`` are all different: ORDER BY
    orders them one way only."""
    return len({r[at] for r in rows}) == len(rows)


@pytest.fixture(scope="module")
def data():
    return Data(10)


@pytest.fixture(scope="module")
def cl(data, tmp_path_factory):
    cluster = ct.Cluster(str(tmp_path_factory.mktemp("depkeys") / "db"))
    data.load(cluster)
    return cluster


@pytest.fixture
def cut(monkeypatch):
    """A table of 1,024 slots is cut: the host's keys go up in blocks
    of 64 and not of 2,048."""
    monkeypatch.setattr(EX, "TOP_HOST_KEYS", 64)


# ------------------------------------------------------------ (a) the rule


def key_plan(cl, sql, rows=None, **kw):
    bj = bind_join_select(cl.catalog, parse_statement(sql))
    tree = plan_device_join(bj, rows or {
        "nation": 5, "customer": 150, "orders": 1200, "lineitem": 3000})
    assert isinstance(tree, DeviceJoinTree), tree
    return bj, tree, dependent_group_keys(bj, tree, **kw)


def names(exprs):
    return [getattr(e, "name", None) for e in exprs]


def test_q10s_seven_keys_are_one_lane_and_six_dependants(cl):
    bj, tree, plan = key_plan(cl, Q10.format(date="1993-04-01"))
    assert tree.root == "lineitem"
    assert tree.parent == {"orders": "lineitem", "customer": "orders",
                           "nation": "customer"}
    assert names(plan.lanes) == ["orders.o_custkey"]
    # c_custkey reads the lane; the six others hang on customer's table
    assert plan.lane_of == [0] + [None] * 6
    assert plan.dependants == 6
    assert set(plan.resolver.values()) == {"customer"}
    assert plan.lookups == {"customer": [0]}


def test_q3s_three_keys_are_one_lane(cl):
    bj, tree, plan = key_plan(cl, Q3)
    assert names(plan.lanes) == ["lineitem.l_orderkey"]
    assert plan.lane_of == [0, None, None]
    assert plan.resolver == {1: "orders", 2: "orders"}
    # orders' table is rebuilt a shard and gone when the groups are
    # returned: the executor leaves out what is not resident
    held = dependent_group_keys(bj, tree, resident={"customer"})
    assert names(held.lanes) == ["lineitem.l_orderkey", "orders.o_orderdate",
                                 "orders.o_shippriority"]
    assert held.dependants == 0 and held.lane_of == [0, 1, 2]


def test_a_key_of_the_root_stays_a_lane(cl):
    _, _, plan = key_plan(cl, BY_CUSTOMER.format(
        out="l_returnflag, c_custkey, c_name, count(*)",
        keys="l_returnflag, c_custkey, c_name", tail=""))
    assert names(plan.lanes) == ["lineitem.l_returnflag", "orders.o_custkey"]
    assert plan.lane_of == [0, 1, None]


def test_an_expression_over_two_relations_stays_a_lane(cl):
    _, _, plan = key_plan(cl, BY_CUSTOMER.format(
        out="c_custkey, c_name, c_acctbal + o_shippriority, count(*)",
        keys="c_custkey, c_name, c_acctbal + o_shippriority", tail=""))
    assert names(plan.lanes) == [None, "orders.o_custkey"]
    assert plan.lane_of == [1, None, 0] and plan.dependants == 1


def test_keys_without_the_edge_key_stay_lanes(cl):
    # nothing says that two customers do not share a name
    _, _, plan = key_plan(cl, BY_CUSTOMER.format(
        out="c_name, n_name, count(*)", keys="c_name, n_name", tail=""))
    assert names(plan.lanes) == ["customer.c_name", "nation.n_name"]
    assert plan.dependants == 0


def test_a_pinned_key_stays_a_lane_beside_the_edge(cl):
    _, _, plan = key_plan(cl, BY_CUSTOMER.format(
        out="c_custkey, c_name, c_since, count(*)",
        keys="c_custkey, c_name, c_since", tail=""), pinned={2})
    assert names(plan.lanes) == ["customer.c_since", "orders.o_custkey"]
    assert plan.lane_of == [1, None, 0]


def test_a_key_under_an_outer_step_stays_a_lane(cl):
    sql = BY_CUSTOMER.format(out="c_custkey, c_name, n_name, count(*)",
                             keys="c_custkey, c_name, n_name", tail="")
    bj, tree, inner = key_plan(cl, sql)
    assert inner.dependants == 2
    # the same tree, had nation been joined by an outer step
    for s in bj.steps:
        if s.right_alias == "nation":
            s.kind = "left"
    plan = dependent_group_keys(bj, tree)
    assert plan.dependants == 0 and len(plan.lanes) == 3


def test_a_collapse_that_saves_no_lane_is_not_taken(cl):
    _, _, plan = key_plan(cl, BY_CUSTOMER.format(
        out="c_custkey, count(*)", keys="c_custkey", tail=""))
    assert names(plan.lanes) == ["customer.c_custkey"]
    assert plan.dependants == 0


# ------------------------------------------------------ (b) Q10, both arms


@pytest.mark.parametrize("month", [0, 3, 5, 8])
def test_q10_equals_the_plain_join(cl, data, cut, month):
    day = add_months(DAY0, month)
    dev, host, explain = both_arms(cl, Q10.format(date=iso(day)))
    want = data.q10(day)
    assert no_tie(want, 2)
    assert dev == want and host == want
    assert on_device(explain)
    j = explain["join"]
    assert (j["group_keys"], j["group_key_lanes"],
            j["group_keys_dependent"]) == (7, 1, 6)
    assert j["top"] == {"rows": 20, "entries": 32 + 64}
    assert j["groups"] == len(data.revenue(
        lambda oi: day <= data.o_orderdate[oi] < add_months(day, 3)))
    # the winners' block and the host's groups, not every group
    assert 20 <= j["groups_looked_up"] <= 32 + 64


def test_a_new_date_compiles_nothing(cl, cut):
    cl.execute(Q10.format(date="1993-02-01"))
    before = GLOBAL_COUNTERS.snapshot()
    for date in ("1993-03-01", "1993-07-01", "1993-09-01"):
        assert on_device(cl.execute(Q10.format(date=date)).explain)
    after = GLOBAL_COUNTERS.snapshot()
    for name in ("kernel_cache_misses", "kernel_compiles"):
        assert after.get(name, 0) == before.get(name, 0), name


def test_the_payload_carries_the_lane_alone(cl):
    bj = bind_join_select(cl.catalog, parse_statement(
        Q10.format(date="1993-04-01")))
    from citus_tpu.catalog.stats import shard_row_counts
    rows = {a: shard_row_counts(cl.catalog, t) for a, t in bj.rels}
    tree = plan_device_join(bj, {a: sum(c) for a, c in rows.items()})
    join = JD._DeviceJoin(cl.catalog, bj, cl.settings, tree, rows)
    carried = {a: [n for n, _ in join.nodes[a].payload] for a in tree.builds}
    assert carried["orders"] == ["orders.o_custkey"]
    assert carried["nation"] == ["nation.n_name"]
    assert sorted(carried["customer"]) == sorted(
        ["customer.c_name", "customer.c_acctbal", "customer.c_phone",
         "nation.n_name", "customer.c_address", "customer.c_comment"])
    # orders gathers nothing of customer's: a partner is all it asks
    (child,) = join.nodes["orders"].children
    assert child.alias == "customer" and child.payload == ()
    assert [n for n, _ in join.nodes["lineitem"].children[0].payload] \
        == ["orders.o_custkey"]


# ------------------------------------------------- (c) the cut on the chip


def by_customer(data, out, tail, having=lambda v, n: True):
    """The plain answer of ``BY_CUSTOMER`` grouped on the customer:
    ``out(ci, revenue, rows)`` a group, ``tail(rows)`` orders and cuts."""
    groups = data.revenue()
    return tail([out(ci, v, n) for ci, (v, n) in groups.items()
                 if having(v, n)])


REVENUE = "sum(l_extendedprice * (1 - l_discount))"


def cut_case(cl, data, tail, order, want_top, out=None, having_sql="",
             keys="c_custkey, c_name"):
    sql = BY_CUSTOMER.format(
        out=out or f"c_custkey, c_name, {REVENUE} as revenue, count(*) as n",
        keys=keys,
        tail=f"{having_sql} order by {order} {tail}")
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain)
    assert dev == host
    if want_top is not None:
        assert explain["join"]["top"] == want_top
    return dev, explain


def test_the_cut_descending_and_ascending(cl, data, cut):
    rows = lambda: by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: rs)
    assert no_tie(rows(), 2)
    top = {"rows": 7, "entries": 96}
    dev, _ = cut_case(cl, data, "limit 7", "revenue desc", top)
    assert dev == sorted(rows(), key=lambda r: -r[2])[:7]
    dev, _ = cut_case(cl, data, "limit 7", "revenue", top)
    assert dev == sorted(rows(), key=lambda r: r[2])[:7]


def test_the_cut_with_offset(cl, data, cut):
    dev, explain = cut_case(cl, data, "limit 5 offset 30", "revenue desc",
                            {"rows": 35, "entries": 64 + 64})
    want = by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: sorted(rs, key=lambda r: -r[2])[30:35])
    assert dev == want


def test_the_cut_with_having(cl, data, cut):
    dev, explain = cut_case(
        cl, data, "limit 6", "revenue desc", {"rows": 6, "entries": 96},
        having_sql="having count(*) > 12")
    want = by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: sorted(rs, key=lambda r: -r[2])[:6],
        having=lambda v, n: n > 12)
    assert dev == want and 0 < len(want) <= 6


def test_the_cut_with_fewer_groups_than_the_limit(cl, data, cut):
    dev, explain = cut_case(
        cl, data, "limit 20", "revenue desc", {"rows": 20, "entries": 96},
        having_sql="having count(*) > 19")
    want = by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: sorted(rs, key=lambda r: -r[2]),
        having=lambda v, n: n > 19)
    assert dev == want and 0 < len(want) < 20


def test_the_cut_with_no_group(cl, data, cut):
    dev, explain = cut_case(
        cl, data, "limit 20", "revenue desc", {"rows": 20, "entries": 96},
        having_sql="having count(*) > 100000")
    assert dev == []
    sql = Q10.format(date="1999-01-01")
    dev, host, explain = both_arms(cl, sql)
    assert dev == host == [] and on_device(explain)


def test_the_cut_on_a_dependant_date_key_keeps_its_lane(cl, data, cut):
    dev, explain = cut_case(
        cl, data, "limit 9", "c_since desc, revenue desc",
        {"rows": 9, "entries": 96},
        out=f"c_custkey, c_name, c_since, {REVENUE} as revenue",
        keys="c_custkey, c_name, c_since")
    j = explain["join"]
    # c_since is read by the sort before any lookup: it stays a lane
    # beside the customer key; c_name is looked up for the winners
    assert (j["group_keys"], j["group_key_lanes"],
            j["group_keys_dependent"]) == (3, 2, 1)
    want = by_customer(
        data, lambda ci, v, n: (
            int(data.c_custkey[ci]), data.c_name[ci],
            EPOCH + datetime.timedelta(days=int(data.c_since[ci])),
            decimal.Decimal(v).scaleb(-4)),
        lambda rs: sorted(rs, key=lambda r: (-(r[2] - EPOCH).days,
                                             -r[3]))[:9])
    assert dev == want


def test_the_cut_is_not_taken_on_a_text_key(cl, data, cut):
    dev, explain = cut_case(cl, data, "limit 9", "c_name desc", None)
    assert "c_name (text) is not ordered on the chip" in explain["join"]["top"]
    assert explain["pipeline"]["group_top"] == explain["join"]["top"]
    want = by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: sorted(rs, key=lambda r: r[1], reverse=True)[:9])
    assert dev == want
    # every group came home, and every group's name was looked up
    assert explain["join"]["groups_looked_up"] == explain["join"]["groups"]


@pytest.mark.parametrize("tail, why", [
    ("", "no ORDER BY ... LIMIT"),
    ("order by 3 desc", "no ORDER BY ... LIMIT"),
    ("order by avg(l_discount), c_custkey limit 3",
     "is not ordered on the chip"),
    ("having avg(l_discount) > 0.01 order by 3, c_custkey limit 3",
     "HAVING is not decided on the chip"),
])
def test_what_is_not_cut_says_why_and_equals_the_oracle(cl, data, cut, tail,
                                                        why):
    sql = BY_CUSTOMER.format(
        out=f"c_custkey, c_name, {REVENUE}, c_comment",
        keys="c_custkey, c_name, c_comment", tail=tail)
    dev, host, explain = both_arms(cl, sql)
    assert on_device(explain) and why in explain["join"]["top"]
    if "limit" in tail:
        assert dev == host
    else:
        assert sorted(dev, key=repr) == sorted(host, key=repr)
    j = explain["join"]
    assert j["group_key_lanes"] == 1 and j["group_keys_dependent"] == 2
    # without a cut every group's dependants are looked up
    assert j["groups_looked_up"] == j["groups"] == len(data.revenue())


def test_the_cut_beside_spilled_groups_is_exact(cl, data, cut, monkeypatch):
    """A table too small for its groups: the host accumulator holds a
    part of many, their entries stand aside from the sort and the host
    completes them -- the answer is the whole fetch's."""
    monkeypatch.setattr(JD, "AGG_SLOTS", (256, 256))
    monkeypatch.setattr(JD, "AGG_ROWS_PER_SLOT", 64)
    monkeypatch.setattr(EX, "TOP_HOST_KEYS", 16)
    sql = BY_CUSTOMER.format(
        out=f"c_custkey, c_name, {REVENUE} as revenue, count(*) as n",
        keys="c_custkey, c_name", tail="order by revenue desc limit 10")
    c0 = GLOBAL_COUNTERS.snapshot()
    dev, host, explain = both_arms(cl, sql)
    c1 = GLOBAL_COUNTERS.snapshot()
    want = by_customer(
        data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                decimal.Decimal(v).scaleb(-4), n),
        lambda rs: sorted(rs, key=lambda r: -r[2])[:10])
    assert dev == want == host
    j = explain["join"]
    assert j["agg_slots"] == 256 and j["spilled_rows"] > 0
    assert isinstance(j["top"], dict) and j["top"]["entries"] < 256
    assert c1["hash_spill_rows"] > c0.get("hash_spill_rows", 0)
    assert j["groups"] == len(data.revenue())


def test_a_negative_sum_is_bounded_by_nothing(tmp_path, cut, monkeypatch):
    """Sums that may be negative, a small table that spills: a partial
    state on the chip may rank anywhere, so only complete groups are
    ranked."""
    monkeypatch.setattr(JD, "AGG_SLOTS", (128, 128))
    monkeypatch.setattr(JD, "AGG_ROWS_PER_SLOT", 64)
    monkeypatch.setattr(EX, "TOP_HOST_KEYS", 16)
    data = Data(23, orders=900, customers=110)
    rng = np.random.default_rng(5)
    data.l_extendedprice = rng.integers(-10 ** 6, 10 ** 6,
                                        len(data.l_orderkey))
    cl = ct.Cluster(str(tmp_path / "db"))
    data.load(cl)
    for order in ("revenue desc", "revenue"):
        sql = BY_CUSTOMER.format(
            out=f"c_custkey, c_name, {REVENUE} as revenue",
            keys="c_custkey, c_name", tail=f"order by {order} limit 8")
        dev, host, explain = both_arms(cl, sql)
        sign = -1 if "desc" in order else 1
        want = by_customer(
            data, lambda ci, v, n: (int(data.c_custkey[ci]), data.c_name[ci],
                                    decimal.Decimal(v).scaleb(-4)),
            lambda rs: sorted(rs, key=lambda r: sign * r[2])[:8])
        assert dev == want == host
        assert explain["join"]["spilled_rows"] > 0


def test_nulls_sort_where_postgres_puts_them(cl, data, cut):
    """sum() of a column that is NULL for a whole group: NULLS FIRST of
    a descending key, LAST of an ascending one."""
    sql = BY_CUSTOMER.format(
        out="c_custkey, c_name, sum(nullif(o_shippriority, 0)) as s",
        keys="c_custkey, c_name", tail="order by s {}, c_custkey limit 5")
    for direction in ("desc", "asc", "desc nulls last", "asc nulls first"):
        dev, host, explain = both_arms(cl, sql.format(direction))
        if not on_device(explain):
            pytest.skip(explain["join"].get("why", ""))
        assert dev == host and len(dev) == 5


# ------------------------------------------------------ (d) not unique


def test_a_build_that_is_not_unique_goes_to_the_host_path(tmp_path, cut):
    data = Data(3, orders=400, customers=60, repeat_customer=True)
    cl = ct.Cluster(str(tmp_path / "db"))
    data.load(cl)
    c0 = GLOBAL_COUNTERS.snapshot()
    sql = Q10.format(date="1993-02-01")
    dev, host, explain = both_arms(cl, sql)
    assert explain["join"] == {
        "on": "host", "why": "build key of customer is not unique"}
    assert GLOBAL_COUNTERS.snapshot()["join_host_fallbacks"] \
        == c0.get("join_host_fallbacks", 0) + 1
    # every group on every key, twice where the key came twice
    assert dev == host and len(dev) > 0


# ------------------------------------------------- (e) spans and counters


def test_spans_and_counters(cl, data, cut):
    from citus_tpu.observability import trace as T
    sql = Q10.format(date="1993-04-01")
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
    assert r.rows == data.q10(add_months(DAY0, 3))
    j, pl = r.explain["join"], r.explain["pipeline"]
    assert (d("group_keys"), d("group_keys_dependent"),
            d("group_key_lanes")) == (7, 6, 1)
    assert d("group_top_cuts") == 1 and d("group_top_entries") == 96
    assert d("hash_entries_fetched") == 96 < j["agg_slots"]
    assert d("hash_groups_out") == j["groups"]
    assert d("join_host_fallbacks") == 0 and d("kernel_compiles") == 0
    assert (pl["group_keys"], pl["group_keys_dependent"],
            pl["group_key_lanes"], pl["group_top_cuts"],
            pl["group_top_entries"]) == (7, 6, 1, 1, 96)
    assert pl["group_top"] == "first 20 on device"
    top = tr.find("group_top")
    assert top.attrs["entries"] == 96 and top.attrs["limit"] == 20
    assert top.attrs["kept"] == min(32, top.attrs["candidates"])
    assert top.attrs["spilled_keys"] >= 0
    keys = tr.find("materialize_keys")
    assert keys.attrs["keys"] == 6
    assert keys.attrs["groups"] == j["groups_looked_up"]
    assert keys.attrs["words"] == 5 * keys.attrs["groups"]
    assert tr.find("fetch") is None          # no table came home whole
    bc = {s.attrs["relation"]: s for s in tr.find_all("join_broadcast")}
    assert set(bc) == {"nation", "customer"}
    lines = "\n".join(l for (l,) in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    assert lines.rstrip().endswith(
        f"keys: 1 of 7 grouped, 6 looked up for {j['groups_looked_up']} "
        f"groups; top 20 on device: 96 entries fetched")


def test_a_single_table_group_by_is_cut_too(tmp_path, cut, limit_devices):
    """The same ending under a GROUP BY of one table (the hash path of
    ``executor.py``) on one device: ORDER BY ... LIMIT is cut on the
    table; several devices' tables come home as before."""
    limit_devices(1)
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, w bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    rng = np.random.default_rng(4)
    n = 6000
    w = rng.integers(0, 10 ** 12, 700)[rng.integers(0, 700, n)]
    v = rng.integers(-1000, 1000, n)
    cl.copy_from("t", columns={"k": np.arange(n), "w": w, "v": v})
    sql = "SELECT w, sum(v) AS s, min(v) FROM t GROUP BY w " \
          "HAVING count(*) > 2 ORDER BY s DESC, w LIMIT 12 OFFSET 3"
    dev, host, explain = both_arms(cl, sql)
    assert explain["strategy"] == "hash_host"
    assert explain["pipeline"]["group_top"] == "first 15 on device"
    assert explain["pipeline"]["group_top_entries"] == 32 + 64
    groups = {}
    for wi, vi in zip(w.tolist(), v.tolist()):
        g = groups.setdefault(wi, [0, vi, 0])
        g[0] += vi
        g[1] = min(g[1], vi)
        g[2] += 1
    want = sorted(((k, s, m) for k, (s, m, c) in groups.items() if c > 2),
                  key=lambda r: (-r[1], r[0]))[3:15]
    assert dev == want == host
