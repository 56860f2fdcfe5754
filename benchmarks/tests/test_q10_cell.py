"""TPC-H Q10's configuration ``tpch_sf10_q10_1chip`` and its cell
``tpch_sf10_q10_params`` (PR 48): the generator's four tables, the plain
reference against a brute-force join written here, the new kernel's
yardstick, and the cell end to end on the CPU at a few thousand orders
(a scratch checkout, as the other cells' rehearsals)."""

import decimal
import json
import os

import numpy as np
import pytest

from benchmarks import group_top, spec
from benchmarks.generators import tpch_q10_tables as gen
from benchmarks.generators import tpch_q3_tables as q3gen
from benchmarks.references import q10
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import (  # noqa: F401
    bench, checkout, expected_metrics, run,
)

CELL = "tpch_sf10_q10_params"
PARAMS = {"data_seed": 5, "orders": 30_000, "customers": 1_500_000,
          "parts": 200_000, "chunk_orders": 8_000}
METRICS = {
    "q10_join_kernel_ms", "q10_join_kernel_hbm_roofline", "q10_agg_kernel_ms",
    "q10_top_kernel_ms", "q10_top_kernel_hbm_roofline", "q10_build_ms",
    "q10_group_top_ms", "q10_materialize_ms", "q10_finalize_ms",
    "q10_decode_wait_ms", "q10_entries_fetched_per_query",
    "q10_dependent_keys_per_query", "q10_key_lanes_per_query",
    "q10_host_fallbacks_per_query"}


def bench_json():
    with open(os.path.join(spec.HERE, os.pardir, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tables():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        stats.add(c)
        chunks.append(c)
    cat = lambda t, col: np.concatenate(
        [np.asarray(c[t][col]) for c in chunks if t in c])
    return chunks, stats.arrays(), cat


def test_the_files_load_and_the_entries_are_the_issues():
    config = spec.load_json("configs", "tpch_sf10_q10_1chip.json")
    tables = spec.tables_of(config)
    q3 = spec.load_json("configs", "tpch_sf10_q3_1chip.json")
    assert tables[:2] == spec.tables_of(q3)[:2]
    assert [(t["name"], t["distribution"]) for t in tables[2:]] == [
        ("customer", {"kind": "reference"}), ("nation", {"kind": "reference"})]
    assert config["reduced"] == ["scale_factor"] and config["chips"] == 1
    assert {"exactness", "isolation", "replication_factor", "durability"} \
        <= set(config["guarantees"])
    assert set(q3["kernel_modules"].items()) \
        < set(config["kernel_modules"].items())
    assert config["kernel_modules"]["top"] == "jit_hash_top"
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["clients"] == 1
    assert cell.query_tables == {
        "q10": ["lineitem", "orders", "customer", "nation"]}
    query = cell.queries["q10"]
    assert [len(query["scanned_columns"][t])
            for t in cell.query_tables["q10"]] == [4, 3, 7, 2]
    date = query["parameters"]["DATE"]
    assert len(date["choices"]) == 24 and date["fixed"] == "1993-10-01"
    assert query["ordered"] is True and "limit 20" in query["sql"]
    b = bench_json()
    entry = b["configs"][-1]
    assert entry["name"] == config["name"] \
        and entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert b["workloads"][-1]["name"] == CELL
    assert len(b["workloads"]) == 13 \
        and sum(w["chips"] == 4 for w in b["workloads"]) == 3
    assert len(b["per_layer"]) == 114 <= 128


def test_the_cells_metric_set():
    b = bench_json()
    mine = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} == METRICS
    assert b["per_layer"][-len(METRICS):] == mine      # entries at the end
    layers = {m["layer"] for m in b["per_layer"][:-len(METRICS)]}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "scan_rows_per_s"
        assert m["layer"] in layers
        file = spec.load_json("layer_metrics", m["name"] + ".json")
        assert {k: file[k] for k in ("name", "unit", "better", "layer",
                                     "moves", "source")} \
            == {k: m[k] for k in m if k != "workloads"}
        spec.plugin("sources", file["reader"]["kind"])
    cell = spec.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} \
        == {"scan_rows_per_s", "setup_s"}


def test_the_tables_are_q3s_and_the_specs_shapes(tables):
    chunks, arrays, cat = tables
    for i, c in enumerate(chunks):
        same = q3gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        for t in ("orders", "lineitem"):
            assert all((c[t][k] == same[t][k]).all() for k in same[t])
        assert ("customer" in c) == ("nation" in c) == (i == 0)
    customers = int(arrays["rows.customer"])
    assert customers == PARAMS["orders"] // 10 and arrays["rows.nation"] == 25
    q3c = q3gen.customer(PARAMS)
    assert all((cat("customer", k) == q3c[k]).all() for k in q3c)
    name, phone = cat("customer", "c_name"), cat("customer", "c_phone")
    assert name[41] == "Customer#000000042"
    length = lambda col: np.array([len(w) for w in cat("customer", col)])
    assert 10 <= length("c_address").min() and length("c_address").max() <= 40
    assert 29 <= length("c_comment").min() \
        and length("c_comment").max() <= 116
    nation = cat("customer", "c_nationkey")
    assert all(p.startswith(f"{n + 10}-") and len(p) == 15
               for p, n in zip(phone.tolist(), nation.tolist()))
    for col in ("c_name", "c_address", "c_comment"):
        assert np.unique(cat("customer", col)).size == customers
    assert list(cat("nation", "n_name")) == [n for n, _ in gen.NATIONS]
    # any customers' words can be made again without the others'
    some = np.array([3, 500, customers])
    again = gen.customer_text(PARAMS["data_seed"], some, nation[some - 1])
    assert again["c_comment"] == [cat("customer", "c_comment")[k - 1]
                                  for k in some]
    copy = gen.copy_columns(chunks[0])
    assert set(copy) == {"orders", "lineitem", "customer", "nation"}
    assert set(copy["customer"]) == {
        "c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
        "c_acctbal", "c_mktsegment", "c_comment"}


def brute_force(cat, params):
    """Q10 over every row of the four tables, in Python integers."""
    first = np.datetime64(params["DATE"], "D")
    lo = int(first.astype(int))
    hi = int((first.astype("datetime64[M]") + 3).astype("datetime64[D]")
             .astype(int))
    customer = {k: i for i, k in enumerate(cat("customer", "c_custkey")
                                           .tolist())}
    orders = {k: c for k, c, d in zip(
        cat("orders", "o_orderkey").tolist(),
        cat("orders", "o_custkey").tolist(),
        cat("orders", "o_orderdate").tolist()) if lo <= d < hi}
    revenue = {}
    for k, rf, price, disc in zip(
            cat("lineitem", "okey").tolist(), cat("lineitem", "rf").tolist(),
            cat("lineitem", "price").tolist(),
            cat("lineitem", "disc").tolist()):
        if rf == gen.RETURNED and k in orders and orders[k] in customer:
            c = orders[k]
            revenue[c] = revenue.get(c, 0) + price * (100 - disc)
    rows = sorted(revenue.items(), key=lambda kv: -kv[1])[:20]
    col = lambda name: cat("customer", name)
    dec = lambda v, s: decimal.Decimal(int(v)).scaleb(-s)
    return len(revenue), [
        (c, col("c_name")[customer[c]], dec(v, 4),
         dec(col("c_acctbal")[customer[c]], 2),
         gen.NATIONS[col("c_nationkey")[customer[c]]][0],
         col("c_address")[customer[c]], col("c_phone")[customer[c]],
         col("c_comment")[customer[c]]) for c, v in rows]


def test_reference_equals_a_brute_force_join_for_all_24_draws(tables):
    _, arrays, cat = tables
    dates = spec.load_json("queries", "q10.json")["parameters"]["DATE"]
    assert len(dates["choices"]) == 24
    for date in dates["choices"]:
        groups, rows = brute_force(cat, {"DATE": date})
        assert q10.expected(arrays, {"DATE": date}) == rows, date
        assert q10.groups(arrays, {"DATE": date})[0].size == groups > 20
    with pytest.raises(ValueError, match="not the first"):
        q10.expected(arrays, {"DATE": "1995-02-01"})


def test_reference_raises_on_a_planted_tie(tables):
    _, arrays, _ = tables
    params = {"DATE": "1993-10-01"}
    top = q10.expected(arrays, params)
    planted = {k: np.array(v) for k, v in arrays.items()}
    month = planted["q10_o_month"]
    mine = lambda c: np.flatnonzero(
        (planted["q10_o_custkey"] == c) & (month >= 8) & (month < 11))
    b = mine(top[1][0])
    planted["q10_o_revenue"][b] = 0
    planted["q10_o_revenue"][b[0]] = int(top[0][2].scaleb(4))
    with pytest.raises(ValueError, match="tie on"):
        q10.expected(planted, params)


def test_the_cuts_yardstick_is_the_query_files():
    query = spec.load_json("queries", "q10.json")
    assert group_top.group_bytes(query) == 32
    # 390,000 groups of 32 B at a v5e's 819 GB/s
    assert group_top.cut_floor_s(390_000, query, "TPU v5 lite", 1) \
        == pytest.approx(390_000 * 32 / 819e9)
    reader = spec.load_json("layer_metrics",
                            "q10_top_kernel_hbm_roofline.json")["reader"]
    assert reader == {"kind": "trace_group_top", "module": "top"}
    from types import SimpleNamespace as NS
    from benchmarks.sources import trace_group_top
    cell = spec.Cell(CELL)
    ctx = NS(cell=cell, n_queries=2, slice_queries=["q10"],
             counters={"hash_groups_out": 780_000}, chips=1,
             device_kind="TPU v5 lite",
             trace={"modules": {"jit_hash_top": {"seconds": 0.01,
                                                 "count": 1}}})
    assert trace_group_top.read(ctx, reader) == pytest.approx(
        100 * (390_000 * 32 / 819e9) / 0.01)
    # a program without the module, or one that counts no group: nothing
    ctx.trace = {"modules": {}}
    assert trace_group_top.read(ctx, reader) is None
    ctx.trace, ctx.counters = {"modules": {"jit_hash_top": {
        "seconds": 0.01, "count": 1}}}, {}
    assert trace_group_top.read(ctx, reader) is None


def test_cell_untraced(checkout):
    p, out = run(checkout, CELL, 0)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    rows = out["info"]["data"]["table_rows"]
    assert set(rows) == {"orders", "lineitem", "customer", "nation"}
    assert out["info"]["rows"] == sum(rows.values())


def test_cell_traced_gives_every_program_metric_a_number(checkout):
    p, out = run(checkout, CELL, 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    device = {m["name"] for m in bench(checkout)["per_layer"]
              if m["source"] == "device_trace"}
    want = expected_metrics(checkout, CELL, "per_layer") - device \
        - {"peak_hbm_gb", "idle_unattributed_ms"}
    assert METRICS - device <= want <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["q10_host_fallbacks_per_query"] == 0
    assert m["q10_dependent_keys_per_query"] == 6
    assert m["q10_key_lanes_per_query"] == 1
    assert m["compiles_in_window"] == 0
    assert m["kernel_compiles_in_window"] == 0
    assert m["q10_build_ms"] > 0 and m["q10_materialize_ms"] > 0
    counters = out["info"]["counters"]
    assert counters["join_queries"] == out["attempted"]
    assert "join_host_fallbacks" not in counters
    assert counters["group_keys"] == 7 * out["attempted"]
