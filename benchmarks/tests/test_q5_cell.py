"""TPC-H Q5's configuration ``tpch_sf10_q5_1chip`` and its cell
``tpch_sf10_q5_params`` (PR 53): the generator's six tables (four of
them ``tpch_q10_tables``' to the element), the plain reference against
a brute-force join written here for all 25 draws, the files and the
entries, and the cell end to end on the CPU at a few thousand orders (a
scratch checkout, as the other cells' rehearsals).  The reference's
case stands here and not in ``test_reference.py``: a PR that adds a
cell edits no file the benchmark has."""

import decimal
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import roofline, spec
from benchmarks.generators import tpch_lineitem as base
from benchmarks.generators import tpch_q10_tables as q10gen
from benchmarks.generators import tpch_q5_tables as gen
from benchmarks.generators.tpch_lineitem_supp import supplier_key
from benchmarks.references import q5
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import (  # noqa: F401
    bench, checkout, expected_metrics, run,
)
from test_spec import copies_of_one_reader

CELL = "tpch_sf10_q5_params"
CONFIG = "tpch_sf10_q5_1chip"
PARAMS = {"data_seed": 5, "orders": 30_000, "customers": 1_500_000,
          "parts": 200_000, "suppliers": 100_000, "chunk_orders": 8_000}
#: the twenty entries PR 53 brought, each this cell's alone, and the
#: accepted entry each is a copy of (None: a reader of its own)
METRICS = {
    "q5_join_kernel_ms": "join_kernel_ms",
    "q5_join_kernel_hbm_roofline": "join_kernel_hbm_roofline",
    "q5_join_probe_lookup_ms": "join_probe_lookup_ms",
    "q5_join_probe_block_ms": "join_probe_block_ms",
    "q5_join_probe_pack_ms": "join_probe_pack_ms",
    "q5_join_probe_filter_ms": None,
    "q5_join_agg_kernel_ms": None,
    "q5_join_build_ms": "join_build_ms",
    "q5_join_broadcast_ms": "join_broadcast_ms",
    "q5_decode_wait_ms": "decode_wait_ms",
    "q5_h2d_ms": "h2d_ms",
    "q5_kernel_unscoped_share": "kernel_unscoped_share",
    "q5_join_host_fallbacks_per_query": "join_host_fallbacks_per_query",
    "q5_join_rows_looked_up_per_query": "join_rows_looked_up_per_query",
    "q5_overflow_rounds_per_query": "q3_overflow_rounds_per_query",
    "q5_rows_matched_per_query": "q3_rows_matched_per_query",
    "q5_cycle_rows_in_per_query": None,
    "q5_cycle_rows_kept_per_query": None,
    "q5_cycle_filters_per_query": None,
    "q5_probe_children_per_query": None,
}
#: the kinds of reader the issue allows the new entries
KINDS = {"trace_module", "trace_scope", "trace_roofline", "counter_delta",
         "span_mean", "span_self"}
#: the rows of the six tables at SF10, as data seed 22 draws them
SF10_ROWS = {"lineitem": 59_998_987, "orders": 15_000_000,
             "customer": 1_500_000, "supplier": 100_000, "nation": 25,
             "region": 5}


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
    config = spec.load_json("configs", CONFIG + ".json")
    tables = spec.tables_of(config)
    q10 = spec.tables_of(spec.load_json("configs",
                                        "tpch_sf10_q10_1chip.json"))
    of = lambda ts: {t["name"]: t for t in ts}
    assert [t["name"] for t in tables] == [
        "orders", "lineitem", "customer", "supplier", "nation", "region"]
    for name in ("orders", "customer", "nation"):
        assert of(tables)[name] == of(q10)[name]
    line, theirs = of(tables)["lineitem"], of(q10)["lineitem"]
    assert line["distribution"] == theirs["distribution"] == {
        "kind": "hash", "column": "l_orderkey", "colocate_with": "orders"}
    assert line["ddl"] == theirs["ddl"][:-1] + ", l_suppkey bigint NOT NULL)"
    for name in ("customer", "supplier", "nation", "region"):
        assert of(tables)[name]["distribution"] == {"kind": "reference"}
    assert "s_suppkey bigint NOT NULL, s_name text, s_address text, " \
           "s_nationkey integer, s_phone text, s_acctbal decimal(15,2), " \
           "s_comment text" in of(tables)["supplier"]["ddl"]
    assert "r_regionkey integer NOT NULL, r_name text, r_comment text" \
        in of(tables)["region"]["ddl"]
    assert config["reduced"] == ["scale_factor"] and config["chips"] == 1
    assert config["shards_per_device"] == 8 and config["reduced_note"]
    assert {"exactness", "isolation", "replication_factor", "durability"} \
        == set(config["guarantees"])
    assert config["guarantees"]["replication_factor"] == 1
    assert "six relations" in config["guarantees"]["isolation"]
    assert config["assumed"]
    g = config["generator"]
    assert (g["name"], g["data_seed"], g["orders"], g["suppliers"]) \
        == ("tpch_q5_tables", 22, 15_000_000, 100_000)
    assert config["kernel_modules"] == {
        "join": "jit_join_probe", "join_build": "jit_join_build",
        "hash": "jit_hash_fused", "scan": "jit_fused"}
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["clients"] == 1
    assert cell.traffic["loop"] == "closed" \
        and cell.traffic["ordering"] == "cycle"
    assert cell.traffic["statements"] == [
        {"query": "q5", "parameters": "tpch", "weight": 1}]
    assert cell.query_tables == {"q5": list(SF10_ROWS)}
    query = cell.queries["q5"]
    assert [len(query["scanned_columns"][t])
            for t in cell.query_tables["q5"]] == [4, 3, 2, 2, 3, 2]
    # about 2.53 GB a statement for the roofline
    assert roofline.algorithmic_bytes(query, SF10_ROWS) == 2_527_363_957
    assert sum(SF10_ROWS.values()) == 76_599_017
    region, date = query["parameters"]["REGION"], query["parameters"]["DATE"]
    assert region["choices"] == list(q5.REGIONS) and region["fixed"] == "ASIA"
    assert date["choices"] == [f"{y}-01-01" for y in range(1993, 1998)] \
        and date["fixed"] == "1994-01-01"
    assert query["ordered"] is True and query["reference"] == "q5"
    assert "from customer, orders, lineitem, supplier, nation, region" \
        in query["sql"] and "c_nationkey = s_nationkey" in query["sql"]
    b = bench_json()
    entry, = [c for c in b["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == config["reduced"]
    mine, = [w for w in b["workloads"] if w["name"] == CELL]
    assert (mine["config"], mine["traffic"], mine["chips"]) \
        == (CONFIG, "q5_params", 1)
    assert len(mine["why"]) <= 200
    assert len(b["workloads"]) >= 14 and len(b["per_layer"]) <= 128
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 3


def test_the_cells_metric_set():
    b = bench_json()
    mine = [m for m in b["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} == set(METRICS)
    assert len(METRICS) == 20
    layers = {m["layer"] for m in b["per_layer"]
              if CELL not in m.get("workloads", [CELL])}
    for m in mine:
        assert m["workloads"] == [CELL] and m["name"].startswith("q5_")
        assert m["moves"] == "scan_rows_per_s" and m["layer"] in layers
        file = spec.load_json("layer_metrics", m["name"] + ".json")
        assert {k: file[k] for k in ("name", "unit", "better", "layer",
                                     "moves", "source")} \
            == {k: m[k] for k in m if k != "workloads"}
        assert file["reader"]["kind"] in KINDS
        spec.plugin("sources", file["reader"]["kind"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    cell = spec.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} \
        == {"scan_rows_per_s", "setup_s"}
    # what the cell reports besides: the entries every cell reports
    assert {m["name"] for m in cell.per_layer} - set(METRICS) == {
        m["name"] for m in b["per_layer"] if "workloads" not in m}
    # no accepted entry was edited for it
    assert all(CELL not in m["workloads"] for m in b["per_layer"]
               if "workloads" in m and not m["name"].startswith("q5_"))


def test_copies_of_one_reader_names_the_copies():
    """What the next ``benchmark`` issue folds: fourteen of the twenty
    read what an accepted entry reads; six have a reader of their own."""
    b = bench_json()
    families = [f for f in copies_of_one_reader(b["per_layer"])
                if any(n.startswith("q5_") for n in f)]
    assert sorted(families) == sorted(
        [theirs, name] for name, theirs in METRICS.items() if theirs)
    assert sum(theirs is None for theirs in METRICS.values()) == 6


def test_the_tables_are_q10s_and_the_specs_shapes(tables):
    chunks, arrays, cat = tables
    for i, c in enumerate(chunks):
        same = q10gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        assert set(c) == set(same) | ({"supplier", "region"} if i == 0
                                      else set())
        for t in same:
            assert set(c[t]) - {"supp"} == set(same[t])
            for k in same[t]:
                assert np.array_equal(np.asarray(c[t][k]),
                                      np.asarray(same[t][k])), (t, k)
        # l_suppkey: one of the part's four suppliers by the partsupp
        # formula, the part the one the price was drawn from
        part = gen._partkeys(PARAMS, PARAMS["data_seed"], i,
                             c["lineitem"]["lines_per_order"])
        line = c["lineitem"]
        assert np.array_equal(
            line["price"], (line["qty"] // 100)
            * base.retail_price_cents(part))
        S = gen.n_suppliers(PARAMS)
        four = np.stack([supplier_key(part, j, S) for j in range(4)])
        assert (four == line["supp"]).any(axis=0).all()
        assert line["supp"].min() >= 1 and line["supp"].max() <= S
    S = int(arrays["rows.supplier"])
    assert S == PARAMS["orders"] // 150 == 200
    assert arrays["rows.region"] == 5 and arrays["rows.nation"] == 25
    assert arrays["rows.customer"] == PARAMS["orders"] // 10
    assert list(cat("supplier", "s_suppkey")) == list(range(1, S + 1))
    assert cat("supplier", "s_name")[41] == "Supplier#000000042"
    nation = cat("supplier", "s_nationkey")
    assert nation.min() == 0 and nation.max() == 24
    length = lambda col: np.array([len(w) for w in cat("supplier", col)])
    assert 10 <= length("s_address").min() and length("s_address").max() <= 40
    assert 25 <= length("s_comment").min() \
        and length("s_comment").max() <= 100
    assert all(p.startswith(f"{n + 10}-") and len(p) == 15
               for p, n in zip(cat("supplier", "s_phone").tolist(),
                               nation.tolist()))
    assert list(cat("region", "r_name")) == list(gen.REGIONS) \
        == list(q5.REGIONS)
    # five nations a region
    assert np.bincount([r for _, r in gen.NATIONS]).tolist() == [5] * 5
    copy = gen.copy_columns(chunks[0])
    assert set(copy) == {"orders", "lineitem", "customer", "supplier",
                         "nation", "region"}
    assert set(copy["lineitem"]) == set(
        base.copy_columns(chunks[0]["lineitem"])) | {"l_suppkey"}
    assert set(copy["supplier"]) == {
        "s_suppkey", "s_name", "s_address", "s_nationkey", "s_phone",
        "s_acctbal", "s_comment"}
    assert set(copy["region"]) == {"r_regionkey", "r_name", "r_comment"}
    # the full scale's suppliers are the configuration's
    assert gen.n_suppliers(dict(PARAMS, orders=15_000_000)) == 100_000


def brute_force(cat, params):
    """Q5 over every row of the six tables, in Python integers."""
    first = np.datetime64(params["DATE"], "D")
    lo = int(first.astype(int))
    hi = int((first.astype("datetime64[Y]") + 1).astype("datetime64[D]")
             .astype(int))
    region = list(cat("region", "r_name")).index(params["REGION"])
    nations = {int(k): name for k, name, r in zip(
        cat("nation", "n_nationkey"), cat("nation", "n_name"),
        cat("nation", "n_regionkey")) if int(r) == region}
    supplier = {k: n for k, n in zip(cat("supplier", "s_suppkey").tolist(),
                                     cat("supplier", "s_nationkey").tolist())
                if n in nations}
    customer = dict(zip(cat("customer", "c_custkey").tolist(),
                        cat("customer", "c_nationkey").tolist()))
    orders = {k: c for k, c, d in zip(
        cat("orders", "o_orderkey").tolist(),
        cat("orders", "o_custkey").tolist(),
        cat("orders", "o_orderdate").tolist()) if lo <= d < hi}
    revenue, both = {}, 0
    for k, s, price, disc in zip(
            cat("lineitem", "okey").tolist(), cat("lineitem", "supp").tolist(),
            cat("lineitem", "price").tolist(),
            cat("lineitem", "disc").tolist()):
        if k in orders and s in supplier and orders[k] in customer:
            both += 1
            if customer[orders[k]] == supplier[s]:
                name = nations[supplier[s]]
                revenue[name] = revenue.get(name, 0) + price * (100 - disc)
    rows = sorted(revenue.items(), key=lambda kv: -kv[1])
    return both, [(n, decimal.Decimal(v).scaleb(-4)) for n, v in rows]


def test_reference_equals_a_brute_force_join_for_all_25_draws(tables):
    _, arrays, cat = tables
    query = spec.load_json("queries", "q5.json")["parameters"]
    seen = kept = 0
    for region in query["REGION"]["choices"]:
        for date in query["DATE"]["choices"]:
            params = {"REGION": region, "DATE": date}
            both, rows = brute_force(cat, params)
            assert q5.expected(arrays, params) == rows, params
            assert 1 <= len(rows) <= 5
            seen, kept = seen + both, kept + int(
                arrays["q5_rows"][:, int(date[:4]) - 1993][
                    [r == q5.REGIONS.index(region)
                     for _, r in gen.NATIONS]].sum())
    # every line of the five years reaches one region's filter, and one
    # in 25 of them shares its nation with its customer
    assert seen == int(arrays["q5_both"]) and kept == arrays["q5_rows"].sum()
    assert 1 / 40 < kept / seen < 1 / 16
    for bad in ("1998-01-01", "1994-02-01", "1992-01-01"):
        with pytest.raises(ValueError, match="not the first"):
            q5.expected(arrays, {"REGION": "ASIA", "DATE": bad})


def test_reference_raises_on_a_planted_tie(tables):
    _, arrays, _ = tables
    params = {"REGION": "ASIA", "DATE": "1994-01-01"}
    rows = q5.expected(arrays, params)
    planted = {k: np.array(v) for k, v in arrays.items()}
    nation = {n: i for i, (n, _) in enumerate(gen.NATIONS)}
    planted["q5_revenue"][nation[rows[1][0]], 1] \
        = planted["q5_revenue"][nation[rows[0][0]], 1]
    with pytest.raises(ValueError, match="tie on"):
        q5.expected(planted, params)


def test_a_program_without_the_graph_plan_stops_at_import(tmp_path):
    """The parent commit on the new cell: the generator reads the TEXT
    of the planner and exits at import, before anything is ingested."""
    planner = tmp_path / "citus_tpu" / "planner"
    for package in (planner, tmp_path / "citus_tpu" / "ops"):
        package.mkdir(parents=True)
        (package / "__init__.py").write_text("")
    (tmp_path / "citus_tpu" / "__init__.py").write_text("")
    # (the device join itself is there: ``tpch_q3_tables`` asks)
    (tmp_path / "citus_tpu" / "ops" / "join.py").write_text("")
    (planner / "join_planner.py").write_text(
        "def plan_device_join(bj, rel_rows):\n"
        "    return \"a step's keys name more than two relations\"\n")
    os.symlink(spec.HERE, tmp_path / "benchmarks")
    p = subprocess.run(
        [sys.executable, "-c",
         "import benchmarks.generators.tpch_q5_tables"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert "plans no join graph" in p.stderr \
        and "tpch_sf10_q5_1chip cannot run on it" in p.stderr
    assert not (tmp_path / "benchmarks" / ".data" / CONFIG).exists()


def test_cell_untraced(checkout):
    p, out = run(checkout, CELL, 0)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    rows = out["info"]["data"]["table_rows"]
    assert set(rows) == set(SF10_ROWS)
    assert out["info"]["rows"] == sum(rows.values())
    assert all(set(raw) == {"REGION", "DATE"}
               for _, raw in out["info"]["first_draws"])


def test_cell_traced_gives_every_program_metric_a_number(checkout):
    p, out = run(checkout, CELL, 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    device = {m["name"] for m in bench(checkout)["per_layer"]
              if m["source"] == "device_trace"}
    want = expected_metrics(checkout, CELL, "per_layer") - device \
        - {"peak_hbm_gb", "idle_unattributed_ms"}
    assert set(METRICS) - device <= want <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["q5_join_host_fallbacks_per_query"] == 0
    assert m["q5_cycle_filters_per_query"] == 1
    assert m["q5_probe_children_per_query"] == 2
    assert m["q5_cycle_rows_in_per_query"] \
        > m["q5_cycle_rows_kept_per_query"] > 0
    assert m["q5_rows_matched_per_query"] >= m["q5_cycle_rows_in_per_query"]
    assert m["compiles_in_window"] == 0
    assert m["kernel_compiles_in_window"] == 0
    assert m["q5_join_build_ms"] > 0 and m["q5_join_broadcast_ms"] > 0
    counters = out["info"]["counters"]
    assert counters["join_queries"] == out["attempted"]
    assert "join_host_fallbacks" not in counters
    assert counters["join_cycle_filters"] == out["attempted"]
