"""TPC-H Q15's view ``revenue`` (``benchmarks/queries/q15_revenue.json``)
through ``cl.execute``, and the rule it forced: a group key with a
provable domain past ``DIRECT_MAX_SLOTS`` keeps the direct table where
every partial rides the MXU product and the rows outnumber the slots
(``planner/physical.py`` ``_product_reaches``); else the device hash
table, its slots bounded by the domain (``executor.py`` ``_hash_slots``).
``citus.direct_gid_limit`` (``auto`` by default) is the operator's bound
on the direct table, and where it is set nothing passes it.

The reference here is this file's own: numpy over the rows the test
made, in integers.  The tolerance is equality: prices and discounts are
decimals held as scaled int64, and the sum is an integer sum.  On the
CPU: answers and counts, never a speed.
"""

import datetime
import decimal
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import citus_tpu as ct  # noqa: E402
from citus_tpu.planner.physical import (  # noqa: E402
    DENSE_ROWS_PER_SLOT, PRODUCT_MAX_WORK,
)

EPOCH = datetime.date(1970, 1, 1)
SUPPLIERS = 100_000
ROWS = 240_000
SHARDS = 8

with open(os.path.join(ROOT, "benchmarks", "queries",
                       "q15_revenue.json")) as fh:
    QUERY = json.load(fh)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tpch_sf10_supp_1chip.json")) as fh:
    CONFIG = json.load(fh)


def day(y, m, d=1) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def three_months(date: str):
    d = datetime.date.fromisoformat(date)
    end = datetime.date(d.year + (d.month + 2) // 12, (d.month + 2) % 12 + 1, 1)
    return (d - EPOCH).days, (end - EPOCH).days


def make_rows(n, suppliers, seed, null_discounts=0):
    """Columns of ``n`` lineitems in the configuration's schema: ship
    dates uniform over 1992..1998, suppliers uniform over 1..``suppliers``
    (every one present where n allows, the first and the last always),
    prices and discounts in cents."""
    rng = np.random.default_rng(seed)
    supp = rng.integers(1, suppliers + 1, n)
    if n >= suppliers:
        supp[:suppliers] = np.arange(1, suppliers + 1)
    else:
        supp[:2] = 1, suppliers
    rows = {
        "okey": np.arange(n, dtype=np.int64) + 1,
        "supp": supp,
        "price": rng.integers(90_000, 10_000_000, n),
        "disc": rng.integers(0, 11, n),
        "ship": rng.integers(day(1992, 1, 2), day(1998, 12, 2), n),
        "disc_null": np.zeros(n, bool),
    }
    rows["disc_null"][rng.choice(n, null_discounts, replace=False)] = True
    return rows


def load(cl, rows):
    disc = [None if null else d / 100.0
            for d, null in zip(rows["disc"].tolist(),
                               rows["disc_null"].tolist())] \
        if rows["disc_null"].any() else rows["disc"] / 100.0
    n = rows["okey"].size
    cl.copy_from("lineitem", columns={
        "l_orderkey": rows["okey"], "l_quantity": np.ones(n),
        "l_extendedprice": rows["price"] / 100.0, "l_discount": disc,
        "l_tax": np.zeros(n), "l_returnflag": ["N"] * n,
        "l_linestatus": ["O"] * n, "l_shipdate": rows["ship"].astype(np.int32),
        "l_suppkey": rows["supp"]})


def new_cluster(path, shards=SHARDS):
    cl = ct.Cluster(str(path))
    cl.execute(CONFIG["ddl"])
    cl.execute(f"SELECT create_distributed_table('lineitem', "
               f"'{CONFIG['distribution_column']}', {shards})")
    return cl


def reference(rows, date):
    """The view in this file's own words: per supplier with a row in the
    window, the sum of price x (100 - discount) over the rows whose
    discount is not NULL (NULL where it has none), scaled by 10**4."""
    lo, hi = three_months(date)
    inside = (rows["ship"] >= lo) & (rows["ship"] < hi)
    term = rows["price"] * (100 - rows["disc"])
    total, known = {}, {}
    for s, t, null in zip(rows["supp"][inside].tolist(),
                          term[inside].tolist(),
                          rows["disc_null"][inside].tolist()):
        total[s] = total.get(s, 0) + (0 if null else t)
        known[s] = known.get(s, False) or not null
    return sorted((s, decimal.Decimal(total[s]).scaleb(-4) if known[s]
                   else None) for s in total), int(inside.sum())


def view(date):
    return QUERY["sql"].format(DATE=date)


def analyze(cl, sql) -> str:
    return "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """240,000 rows over 100,000 suppliers: a key domain of 100,001
    slots, past ``DIRECT_MAX_SLOTS``, met 2.4 times a slot."""
    cl = new_cluster(tmp_path_factory.mktemp("q15") / "db")
    rows = make_rows(ROWS, SUPPLIERS, 15)
    # two copies: a supplier's rows lie in both, and on several shards
    half = ROWS // 2
    for part in (slice(0, half), slice(half, ROWS)):
        load(cl, {k: v[part] for k, v in rows.items()})
    yield cl, rows
    cl.close()


def test_published_text_is_the_query_file_s():
    assert QUERY["parameters"]["DATE"]["fixed"] == "1996-01-01"
    choices = QUERY["parameters"]["DATE"]["choices"]
    assert len(choices) == 58 and choices[0] == "1993-01-01" \
        and choices[-1] == "1997-10-01"
    assert "interval '3' month" in QUERY["sql"]


# the validation value, a window that crosses a year end, the range's ends
@pytest.mark.parametrize("date", ["1996-01-01", "1997-10-01", "1993-01-01",
                                  "1994-11-01"])
def test_view_equals_the_reference_on_the_direct_table(wide, date):
    cl, rows = wide
    want, kept = reference(rows, date)
    assert len(want) > 5_000
    r = cl.execute(view(date))
    assert sorted(r.rows) == want   # every key and every decimal sum
    assert r.explain["strategy"] == "direct"
    pl = r.explain["pipeline"]
    assert pl["direct_groups"] == SUPPLIERS + 1
    assert pl["direct_groups_out"] == len(want)
    # every row of every batch went through the product; the WHERE kept
    # one in 27
    assert pl["group_rows_kept"] == kept
    assert pl["group_rows_in"] >= ROWS > 20 * kept
    # three partial states (sum, count(*), rows) of 100,001 slots: the
    # table's statistics prove the sum's float64 shadow and the
    # argument's NULL count away (planner/physical.py lower_aggregates)
    assert r.explain["partials"] == {
        "computed": 2, "overflow_guards_proved_away": 1,
        "null_counts_proved_away": 1}
    assert pl["direct_bytes_fetched"] == 3 * 8 * (SUPPLIERS + 1)


# the harness's default is the mesh loop over its 8 virtual devices; the
# cell runs on one chip (``OneDevice``), and four is a v5e host
@pytest.mark.parametrize("n_dev", [1, 4])
def test_view_on_one_device_and_on_four(wide, limit_devices, n_dev):
    cl, rows = wide
    limit_devices(n_dev)
    date = "1995-08-01" if n_dev == 1 else "1996-11-01"
    want, kept = reference(rows, date)
    r = cl.execute(view(date))
    assert sorted(r.rows) == want
    pl = r.explain["pipeline"]
    assert r.explain["strategy"] == "direct"
    assert pl["group_rows_kept"] == kept and pl["group_rows_in"] >= ROWS
    assert pl["fused_dispatches"] == (SHARDS if n_dev == 1 else SHARDS // 4)


def test_explain_says_slots_rows_in_and_rows_kept(wide):
    cl, rows = wide
    want, kept = reference(rows, "1996-01-01")
    text = analyze(cl, view("1996-01-01"))
    assert "Direct GroupBy (groups: 100001, reduce: matmul" in text
    m = re.search(r"Direct: group slots (\d+), groups (\d+), rows in (\d+) "
                  r"\(kept (\d+)\), fetched (\d+) bytes", text)
    assert m, text
    slots, groups, rows_in, rows_kept, fetched = map(int, m.groups())
    assert (slots, groups, rows_kept) == (SUPPLIERS + 1, len(want), kept)
    assert rows_in >= ROWS and fetched == 24 * slots


def test_counters_hold_what_explain_says(wide):
    cl, rows = wide
    _, kept = reference(rows, "1995-05-01")
    before = cl.counters.snapshot()
    r = cl.execute(view("1995-05-01"))
    after = cl.counters.snapshot()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    pl = r.explain["pipeline"]
    assert delta["group_rows_kept"] == kept
    assert delta["group_rows_in"] == pl["group_rows_in"]
    assert delta["direct_groups"] == SUPPLIERS + 1
    assert delta["direct_bytes_fetched"] == pl["direct_bytes_fetched"]
    assert delta["hash_slots"] == 0


def test_a_window_in_which_no_row_passes_and_one_in_which_every_row_does(wide):
    cl, rows = wide
    r = cl.execute("select l_suppkey, sum(l_extendedprice * (1 - l_discount))"
                   " from lineitem where l_shipdate >= date '2001-01-01' and "
                   "l_shipdate < date '2001-01-01' + interval '3' month "
                   "group by l_suppkey")
    assert r.rows == [] and r.explain["pipeline"]["group_rows_kept"] == 0
    r = cl.execute("select l_suppkey, sum(l_extendedprice * (1 - l_discount))"
                   " from lineitem where l_shipdate >= date '1992-01-01' "
                   "group by l_suppkey")
    term = rows["price"] * (100 - rows["disc"])
    total = np.zeros(SUPPLIERS + 1, np.int64)
    np.add.at(total, rows["supp"], term)
    assert sorted(r.rows) == [(s, decimal.Decimal(int(total[s])).scaleb(-4))
                              for s in range(1, SUPPLIERS + 1)]
    assert r.explain["pipeline"]["group_rows_kept"] == ROWS
    assert r.explain["strategy"] == "direct"


def test_numpy_arm_agrees(wide):
    cl, rows = wide
    want, _ = reference(rows, "1996-01-01")
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        assert sorted(cl.execute(view("1996-01-01")).rows) == want
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")


# ---- the rule -------------------------------------------------------------


def plan_of(cl, sql):
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    bound = bind_select(cl.catalog, parse_sql(sql)[0])
    return plan_select(cl.catalog, bound,
                       direct_limit=cl.settings.planner.direct_gid_limit)


@pytest.mark.parametrize("select,kind", [
    # count and int64 sums (and their shadows): the product holds them
    ("sum(l_extendedprice * (1 - l_discount))", "direct"),
    ("count(*), sum(l_quantity), avg(l_extendedprice)", "direct"),
    # min / max / float sums would scatter on 100,001 slots: hash
    ("min(l_extendedprice)", "hash_host"),
    ("sum(l_extendedprice), max(l_shipdate)", "hash_host"),
    ("sum(cast(l_extendedprice as float8))", "hash_host"),
])
def test_rule_follows_the_partials_kinds(wide, select, kind):
    cl, _ = wide
    plan = plan_of(cl, f"select l_suppkey, {select} from lineitem "
                       "group by l_suppkey")
    assert plan.group_mode.kind == kind
    if kind == "hash_host":
        assert plan.group_mode.domain_slots == SUPPLIERS + 1
    else:
        assert plan.group_mode.n_groups == SUPPLIERS + 1


def test_rule_follows_the_domain_and_the_rows(wide):
    cl, _ = wide
    # two keys: 100,001 x 12 slots of 10 planes pass the product's work
    plan = plan_of(cl, "select l_suppkey, l_discount, sum(l_quantity) from "
                       "lineitem group by l_suppkey, l_discount")
    assert plan.group_mode.kind == "hash_host"
    assert plan.group_mode.domain_slots == (SUPPLIERS + 1) * 12 \
        > PRODUCT_MAX_WORK // 10
    # as many order keys as rows: the product could take the 240,001
    # slots, but a slot would be met once, under DENSE_ROWS_PER_SLOT
    plan = plan_of(cl, "select l_orderkey, sum(l_quantity) from lineitem "
                       "group by l_orderkey")
    assert plan.group_mode.kind == "hash_host"
    assert plan.group_mode.domain_slots == ROWS + 1 <= PRODUCT_MAX_WORK // 10
    assert ROWS < DENSE_ROWS_PER_SLOT * plan.group_mode.domain_slots
    # under DIRECT_MAX_SLOTS the partials' kinds do not matter
    plan = plan_of(cl, "select l_discount, min(l_tax) from lineitem "
                       "group by l_discount")
    assert plan.group_mode.kind == "direct" and plan.group_mode.n_groups == 12


# auto leaves the bound to the plan; a number is a bound nothing passes,
# lowered (the view back on the hash table) or raised (a max scattered
# over 100,001 slots, as the operator asked)
@pytest.mark.parametrize("limit,select,kind", [
    ("auto", "sum(l_extendedprice)", "direct"),
    ("65536", "sum(l_extendedprice)", "hash_host"),
    ("4", "sum(l_extendedprice)", "hash_host"),
    ("200000", "sum(l_extendedprice)", "direct"),
    ("auto", "max(l_extendedprice)", "hash_host"),
    ("200000", "max(l_extendedprice)", "direct"),
])
def test_the_setting_bounds_the_rule(wide, limit, select, kind):
    cl, rows = wide
    sql = f"select l_suppkey, {select} from lineitem group by l_suppkey"
    want = [np.zeros(SUPPLIERS + 1, np.int64) for _ in range(2)]
    np.add.at(want[0], rows["supp"], rows["price"])
    np.maximum.at(want[1], rows["supp"], rows["price"])
    want = want[select.startswith("max")]
    cl.execute(f"SET citus.direct_gid_limit = {limit}")
    try:
        assert plan_of(cl, sql).group_mode.kind == kind
        r = cl.execute(sql)
    finally:
        cl.execute("SET citus.direct_gid_limit = auto")
    assert cl.settings.planner.direct_gid_limit == 0
    assert r.explain["strategy"] == kind
    assert sorted(r.rows) == [
        (s, decimal.Decimal(int(want[s])).scaleb(-2))
        for s in np.unique(rows["supp"]).tolist()]


# ---- the hash table, where the rule leaves the plan to it -----------------


@pytest.fixture(scope="module")
def sparse(tmp_path_factory):
    """60,000 rows over the same 100,000 suppliers: fewer rows than
    slots, so the view takes the device hash table; 2,000 of the
    discounts are NULL."""
    cl = new_cluster(tmp_path_factory.mktemp("q15s") / "db")
    rows = make_rows(60_000, SUPPLIERS, 16, null_discounts=2_000)
    for part in (slice(0, 30_000), slice(30_000, 60_000)):
        load(cl, {k: v[part] for k, v in rows.items()})
    yield cl, rows
    cl.close()


def hash_line(text):
    m = re.search(r"Hash: hash slots (\d+), occupancy ([\d.]+)%, spilled "
                  r"(\d+) rows, groups (\d+), fetched (\d+) bytes, table "
                  r"updates (\d+) \((\d+) rows\), rows in (\d+) "
                  r"\(kept (\d+)\), slots from ([a-z ]+)", text)
    assert m, text
    return [int(g) for g in m.group(1, 3, 4, 5, 8, 9)] + [m.group(10)]


@pytest.mark.parametrize("date", ["1996-01-01", "1997-10-01"])
def test_view_on_the_hash_table_with_null_discounts(sparse, date):
    cl, rows = sparse
    want, kept = reference(rows, date)
    assert any(v is None for _, v in want) or date != "1996-01-01"
    r = cl.execute(view(date))
    assert sorted(r.rows, key=lambda t: t[0]) == want
    assert r.explain["strategy"] == "hash_host"
    slots, spilled, groups, _fetched, rows_in, rows_kept, origin = \
        hash_line(analyze(cl, view(date)))
    # 60,000 rows bound the groups more tightly than 2 x 100,001 slots
    assert (slots, origin) == (65_536, "row count")
    assert groups == len(want) and rows_kept == kept
    assert rows_in >= 60_000 > 20 * kept and spilled < kept


def test_hash_slots_bounded_by_the_key_domain(tmp_path, limit_devices):
    """A key domain past ``DIRECT_MAX_SLOTS`` under many rows whose
    partial (a max) cannot ride the product: the table is sized by the
    domain, not by the catalog's row count.  (One device, one table:
    tests/test_hash_agg_mesh.py sizes the per-device tables.)"""
    limit_devices(1)
    cl = new_cluster(tmp_path / "db", shards=4)
    n, keys = 200_000, 70_000
    rows = make_rows(n, keys, 17)
    load(cl, rows)
    sql = ("select l_suppkey, max(l_extendedprice), count(*) from lineitem "
           "group by l_suppkey")
    want_max = np.zeros(keys + 1, np.int64)
    np.maximum.at(want_max, rows["supp"], rows["price"])
    want_n = np.bincount(rows["supp"], minlength=keys + 1)
    r = cl.execute(sql)
    assert sorted(r.rows) == [
        (s, decimal.Decimal(int(want_max[s])).scaleb(-2), int(want_n[s]))
        for s in range(1, keys + 1)]
    slots, spilled, groups, fetched, rows_in, rows_kept, origin = \
        hash_line(analyze(cl, sql))
    # 2 x 70,001 slots -> 262,144; the row count alone would say 262,144
    # too at 200,000 rows, so tell them apart at the counter's bound
    assert groups == keys and rows_kept == n and rows_in >= n
    assert origin in ("key domain", "row count") and slots == 262_144
    # more rows, the same domain: the slots stay where the domain put them
    load(cl, make_rows(n, keys, 18))
    slots, *_rest, origin = hash_line(analyze(cl, sql))
    assert (slots, origin) == (262_144, "key domain")
    before = cl.counters.snapshot()["hash_slots"]
    cl.execute(sql)
    assert cl.counters.snapshot()["hash_slots"] - before == 262_144
    cl.close()


def test_forced_spill_stays_exact(sparse):
    cl, rows = sparse
    want, kept = reference(rows, "1995-02-01")
    cl.execute("SET citus.hash_agg_slots = 64")
    try:
        r = cl.execute(view("1995-02-01"))
        assert sorted(r.rows, key=lambda t: t[0]) == want
        slots, spilled, groups, *_rest, origin = \
            hash_line(analyze(cl, view("1995-02-01")))
        # the setting is a table's slots, and a device has a table
        tables = r.explain["pipeline"]["hash_tables"]
        assert (slots, origin) == (64 * tables, "setting")
        assert spilled > kept // 2 and groups == len(want)
    finally:
        cl.execute("SET citus.hash_agg_slots = auto")


# ---- the other cells' statements still equal their references -------------


def test_q18_block_and_taxi_rollup_still_equal_their_references(tmp_path):
    from benchmarks.generators import nyctaxi_trips, tpch_lineitem_orders
    from benchmarks.references import q18_orders, taxi_hourly

    def cell(config, generator, params, chunks):
        with open(os.path.join(ROOT, "benchmarks", "configs", config)) as fh:
            cfg = json.load(fh)
        cl = ct.Cluster(str(tmp_path / cfg["name"]))
        cl.execute(cfg["ddl"])
        cl.execute(f"SELECT create_distributed_table('{cfg['table']}', "
                   f"'{cfg['distribution_column']}', 8)")
        params = dict(cfg["generator"], **params)
        stats = generator.Statistics(params)
        for i in chunks:
            c = generator.generate_chunk(params, params["data_seed"], i)
            stats.add(c)
            cl.copy_from(cfg["table"], columns=generator.copy_columns(c))
        return cl, stats.arrays()

    with open(os.path.join(ROOT, "benchmarks", "queries",
                           "q18_orders.json")) as fh:
        q18 = json.load(fh)["sql"]
    cl, stats = cell("tpch_sf1_1chip.json", tpch_lineitem_orders,
                     {"orders": 40_000, "chunk_orders": 2_000,
                      "lookup_sample_orders": 64}, (0, 19))
    r = cl.execute(q18.format(QUANTITY=250))
    assert sorted(tuple(x) for x in r.rows) == sorted(
        q18_orders.expected(stats, {"QUANTITY": 250}))
    # keys 1..160,000 over 16,000 rows: the hash table, as at SF1
    assert r.explain["strategy"] == "hash_host"
    assert r.explain["pipeline"]["hash_slots_from"] == "row count"
    cl.close()
    with open(os.path.join(ROOT, "benchmarks", "queries",
                           "taxi_hourly.json")) as fh:
        taxi = json.load(fh)["sql"]
    cl, stats = cell("nyctaxi_hourly_1chip.json", nyctaxi_trips,
                     {"orders": 3000, "chunk_orders": 1000}, range(3))
    r = cl.execute(taxi)
    assert sorted(r.rows) == sorted(taxi_hourly.expected(stats, {}))
    assert r.explain["strategy"] == "direct"
    assert r.explain["pipeline"]["direct_groups"] == 181 * 24 + 1
    cl.close()
