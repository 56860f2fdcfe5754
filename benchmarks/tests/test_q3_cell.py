"""TPC-H Q3's configuration ``tpch_sf10_q3_1chip`` and its cell
``tpch_sf10_q3_params`` (PR 38): the generator's three tables, the plain
reference against a brute-force join written here, and the cell end to
end on the CPU at a few thousand orders (a scratch checkout, as the
other cells' rehearsals)."""

import datetime
import decimal

import numpy as np
import pytest

from benchmarks import spec
from benchmarks.generators import tpch_lineitem as base
from benchmarks.generators import tpch_q3_tables as gen
from benchmarks.references import q3
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import (  # noqa: F401
    bench, checkout, expected_metrics, run,
)

CELL = "tpch_sf10_q3_params"
PARAMS = {"data_seed": 5, "orders": 24_000, "customers": 1_500_000,
          "parts": 200_000, "chunk_orders": 7_000}
EPOCH = datetime.date(1970, 1, 1)
DRAWS = [{"SEGMENT": s, "DATE": d} for s in q3.SEGMENTS
         for d in range(1, 32)]


@pytest.fixture(scope="module")
def tables():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        stats.add(c)
        chunks.append(c)
    cat = lambda t, col: np.concatenate([c[t][col] for c in chunks
                                         if t in c])
    return chunks, stats.arrays(), cat


def test_the_configuration_loads_and_lists_three_tables():
    config = spec.load_json("configs", "tpch_sf10_q3_1chip.json")
    tables = spec.tables_of(config)
    assert [(t["name"], t["distribution"]) for t in tables] == [
        ("orders", {"kind": "hash", "column": "o_orderkey"}),
        ("lineitem", {"kind": "hash", "column": "l_orderkey",
                      "colocate_with": "orders"}),
        ("customer", {"kind": "reference"})]
    one = spec.load_json("configs", "tpch_sf10_1chip.json")
    assert tables[1]["ddl"] == one["ddl"]
    assert config["reduced"] == ["scale_factor"]
    assert config["generator"]["orders"] == 15_000_000 \
        and config["generator"]["customers"] == 1_500_000
    cell = spec.Cell(CELL)
    assert cell.query_tables == {"q3": ["lineitem", "orders", "customer"]}
    entry = next(c for c in bench_json()["configs"]
                 if c["name"] == config["name"])
    assert entry["source"] == config["source"] \
        and len(entry["source"]) <= 200


def bench_json():
    import json
    import os
    with open(os.path.join(spec.HERE, os.pardir, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_lineitem_is_tpch_lineitems_draw_for_draw(tables):
    chunks, arrays, _ = tables
    for i, c in enumerate(chunks):
        same = base.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        assert set(c["lineitem"]) == set(same)
        assert all((c["lineitem"][k] == same[k]).all() for k in same)
        assert gen.copy_columns(c)["lineitem"].keys() \
            == base.copy_columns(same).keys()
        assert ("customer" in c) == (i == 0)
    assert arrays["rows.lineitem"] == sum(c["lineitem"]["okey"].size
                                          for c in chunks)
    assert arrays["rows.orders"] == PARAMS["orders"]
    assert arrays["rows.customer"] == PARAMS["orders"] // 10


def test_the_tables_have_the_specs_shapes(tables):
    chunks, arrays, cat = tables
    okey, custkey = cat("orders", "o_orderkey"), cat("orders", "o_custkey")
    customers = int(arrays["rows.customer"])
    assert (okey == base.order_key(np.arange(PARAMS["orders"]))).all()
    assert custkey.min() >= 1 and custkey.max() <= customers
    assert (custkey % 3 != 0).all()             # a third have no order
    assert np.unique(custkey).size > 0.6 * customers
    assert (cat("orders", "o_shippriority") == 0).all()
    assert set(np.unique(cat("customer", "c_mktsegment"))) == set(range(5))
    assert (cat("customer", "c_custkey") == np.arange(1, customers + 1)).all()
    # every line's key is an order's, and ships 1..121 days after it
    date = dict(zip(okey.tolist(), cat("orders", "o_orderdate").tolist()))
    lkey, ship = cat("lineitem", "okey"), cat("lineitem", "ship")
    lag = ship - np.array([date[k] for k in lkey.tolist()])
    assert lag.min() >= 1 and lag.max() <= 121
    lines = np.unique(lkey, return_counts=True)[1]
    assert lines.min() >= 1 and lines.max() <= 7 and lines.size == okey.size
    # o_orderstatus follows the lines; o_totalprice is their sum
    status = cat("orders", "o_orderstatus")
    assert set(np.unique(status)) <= {0, 1, 2}
    first = chunks[0]
    l, o = first["lineitem"], first["orders"]
    mine = l["okey"] == o["o_orderkey"][3]
    want = int((l["price"][mine] * (100 + l["tax"][mine])
                * (100 - l["disc"][mine])).sum())
    assert int(o["o_totalprice"][3]) == (want + 5000) // 10000


def brute_force(cat, params):
    """Q3 over every row of the three tables, in Python integers."""
    date = (datetime.date(1995, 3, params["DATE"]) - EPOCH).days
    wanted = q3.SEGMENTS.index(params["SEGMENT"])
    segment = dict(zip(cat("customer", "c_custkey").tolist(),
                       cat("customer", "c_mktsegment").tolist()))
    orders = {k: d for k, c, d in zip(
        cat("orders", "o_orderkey").tolist(),
        cat("orders", "o_custkey").tolist(),
        cat("orders", "o_orderdate").tolist())
        if d < date and segment[c] == wanted}
    revenue = {}
    for k, ship, price, disc in zip(
            cat("lineitem", "okey").tolist(), cat("lineitem", "ship").tolist(),
            cat("lineitem", "price").tolist(),
            cat("lineitem", "disc").tolist()):
        if ship > date and k in orders:
            revenue[k] = revenue.get(k, 0) + price * (100 - disc)
    rows = sorted(revenue.items(), key=lambda kv: (-kv[1], orders[kv[0]]))
    return [(k, decimal.Decimal(v).scaleb(-4),
             EPOCH + datetime.timedelta(days=orders[k]), 0)
            for k, v in rows[:10]]


def test_reference_equals_a_brute_force_join_for_all_155_draws(tables):
    _, arrays, cat = tables
    assert len(DRAWS) == 155
    some = 0
    for params in DRAWS:
        got = q3.expected(arrays, params)
        assert got == brute_force(cat, params), params
        some += bool(got)
    assert some == 155


def test_reference_raises_on_a_planted_tie(tables):
    _, arrays, _ = tables
    params = {"SEGMENT": "BUILDING", "DATE": 15}
    top = q3.expected(arrays, params)
    planted = {k: v.copy() for k, v in arrays.items()}
    # give the second row the first row's revenue and order date
    at = {int(k): i for i, k in enumerate(arrays["q3_o_orderkey"].tolist())}
    a, b = at[top[0][0]], at[top[1][0]]
    planted["q3_o_orderdate"][b] = planted["q3_o_orderdate"][a]
    lines_b = np.flatnonzero(planted["q3_l_order"] == b)
    planted["q3_l_revenue"][lines_b] = 0
    planted["q3_l_shipdate"][lines_b[0]] = 10 ** 6      # it counts
    planted["q3_l_revenue"][lines_b[0]] = int(top[0][1].scaleb(4))
    with pytest.raises(ValueError, match="tie on"):
        q3.expected(planted, params)


def test_cell_untraced(checkout):
    p, out = run(checkout, CELL, 0)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    rows = out["info"]["data"]["table_rows"]
    assert set(rows) == {"orders", "lineitem", "customer"}
    assert out["info"]["rows"] == sum(rows.values())
    # scan_rows_per_s counts the three tables' rows a query
    window = out["info"]["seconds"]
    assert out["metrics"]["scan_rows_per_s"]["value"] <= \
        out["info"]["rows"] * out["attempted"] / window * 1.001
    assert out["metrics"]["scan_rows_per_s"]["value"] >= \
        out["info"]["rows"] * (out["attempted"] - 1) / (window * 2)


def test_cell_traced_gives_every_program_metric_a_number(checkout):
    p, out = run(checkout, CELL, 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    # a CPU rehearsal has no device plane: what the device trace feeds
    # is left out, every span and counter metric is a number
    device = {m["name"] for m in bench(checkout)["per_layer"]
              if m["source"] == "device_trace"}
    want = expected_metrics(checkout, CELL, "per_layer") - device \
        - {"peak_hbm_gb", "idle_unattributed_ms"}
    # the twelve span and counter metrics PR 38 brought: eight under the
    # cell's prefix, four folded by PR 47 into entries other cells share
    q3_metrics = {n for n in want if n.startswith("q3_")}
    shared = {"join_host_fallbacks_per_query", "decode_wait_ms", "h2d_ms",
              "result_fetch_ms"}
    assert len(q3_metrics) >= 8 and shared <= want
    assert q3_metrics | shared <= set(out["metrics"])
    assert want <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["join_host_fallbacks_per_query"] == 0
    assert m["q3_overflow_rounds_per_query"] == 0
    assert m["compiles_in_window"] == 0
    assert m["q3_rows_probed_per_query"] >= \
        out["info"]["data"]["table_rows"]["lineitem"]
    assert m["q3_rows_probed_per_query"] >= m["q3_rows_matched_per_query"] \
        >= m["q3_rows_out_per_query"] > 0
    assert m["q3_table_bytes"] > 0 and m["q3_build_ms"] > 0
    counters = out["info"]["counters"]
    assert counters["join_queries"] == out["attempted"]
    assert "join_host_fallbacks" not in counters
