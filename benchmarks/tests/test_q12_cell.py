"""TPC-H Q12's configuration ``tpch_sf10_q12_4chip`` and its cell
``tpch_sf10x4_q12_repartition`` (PR 43): the files resolve, the
generator's two tables, the plain reference against a join of the
generated columns written beside it, the plan the deployment is for
(``join:repartition``, run on the device), and the cell end to end on
the CPU's four forced devices at a few thousand orders (a scratch
checkout, as the other cells' rehearsals)."""

import json
import os

import numpy as np
import pytest

from benchmarks import roofline, spec
from benchmarks.generators import tpch_lineitem as base
from benchmarks.generators import tpch_q12_tables as gen
from benchmarks.generators import tpch_q3_tables as q3gen
from benchmarks.references import q12
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import (  # noqa: F401
    bench, checkout, expected_metrics, run,
)

CELL = "tpch_sf10x4_q12_repartition"
CONFIG = "tpch_sf10_q12_4chip"
PARAMS = {"data_seed": 5, "orders": 24_000, "customers": 1_500_000,
          "parts": 200_000, "chunk_orders": 7_000}
QUERY = spec.load_json("queries", "q12.json")
PAIRS = QUERY["parameters"]["SHIPMODES"]["choices"]
DRAWS = [{"SHIPMODES": p, "DATE": y} for p in PAIRS for y in q12.YEARS]


@pytest.fixture(scope="module")
def tables():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        stats.add(c)
        chunks.append(c)
    cat = lambda t, col: np.concatenate([c[t][col] for c in chunks])
    return chunks, stats.arrays(), cat


def bench_json():
    with open(os.path.join(spec.HERE, os.pardir, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_files_resolve_and_the_configuration_says_what_it_is():
    config = spec.load_json("configs", CONFIG + ".json")
    tables = spec.tables_of(config)
    assert [(t["name"], t["distribution"]) for t in tables] == [
        ("lineitem", {"kind": "hash", "column": "l_orderkey"}),
        ("orders", {"kind": "hash", "column": "o_custkey"})]
    one = spec.load_json("configs", "tpch_sf10_1chip.json")
    q3 = spec.load_json("configs", "tpch_sf10_q3_1chip.json")
    assert tables[0]["ddl"] == one["ddl"][:-1] + \
        ", l_commitdate date, l_receiptdate date, l_shipmode text)"
    assert tables[1]["ddl"] == q3["tables"][0]["ddl"]
    assert config["chips"] == 4 and config["shards_per_device"] == 8
    assert config["reduced"] == ["scale_factor", "chips"]
    assert config["generator"]["orders"] == 15_000_000
    assert config["kernel_modules"] == {
        "exchange": "jit_join_exchange", "join": "jit_join_probe",
        "join_build": "jit_join_build", "hash": "jit_hash_fused"}
    assert set(config["guarantees"]) == {"exactness", "isolation",
                                         "replication_factor", "durability"}
    cell = spec.Cell(CELL)
    assert cell.chips == 4 and cell.query_tables == {
        "q12": ["lineitem", "orders"]}
    assert cell.traffic["warmup_cycles"] == 1 \
        and cell.traffic["traced_slice_cycles"] == 1
    b = bench_json()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert b["configs"][-1] is entry and b["workloads"][-1]["name"] == CELL
    assert len(b["workloads"]) == 12 \
        and sum(w["chips"] == 4 for w in b["workloads"]) == 3


def test_the_statement_is_q12_as_published_over_21_pairs_and_5_years():
    assert len(PAIRS) == 21 == len(set(PAIRS)) and len(DRAWS) == 105
    pairs = {frozenset(q12.modes_of({"SHIPMODES": p})) for p in PAIRS}
    assert len(pairs) == 21 and all(len(p) == 2 for p in pairs)
    assert QUERY["parameters"]["SHIPMODES"]["fixed"] == "MAIL', 'SHIP"
    assert QUERY["parameters"]["DATE"] == {
        "lo": 1993, "hi": 1997, "fixed": 1994, "format": "{}-01-01"}
    sql = QUERY["sql"].format(SHIPMODES="MAIL', 'SHIP", DATE="1994-01-01")
    assert "from orders, lineitem where o_orderkey = l_orderkey" in sql
    assert "l_shipmode in ('MAIL', 'SHIP')" in sql
    assert "l_receiptdate < date '1994-01-01' + interval '1' year" in sql
    assert sql.endswith("group by l_shipmode order by l_shipmode")
    assert QUERY["ordered"] is True and QUERY["reference"] == "q12"


#: the ten metrics PR 43 brought: four stay under the cell's prefix, six
#: read what another cell's copy read and were folded by PR 47 into one
#: entry with a ``workloads`` list each
OWN = {"q12x4_exchange_kernel_ms", "q12x4_exchange_kernel_hbm_roofline",
       "q12x4_exchange_ms", "q12x4_rows_exchanged_per_query"}
SHARED = {"join_kernel_ms", "join_kernel_hbm_roofline",
          "join_host_fallbacks_per_query", "mesh_collective_ms", "stack_ms",
          "decode_wait_ms"}


def test_the_cells_metric_set():
    b = bench_json()
    by_name = {m["name"]: m for m in b["per_layer"]}
    assert OWN | SHARED <= set(by_name)
    assert all(by_name[n]["workloads"] == [CELL] for n in OWN)
    assert all(CELL in by_name[n]["workloads"]
               and len(by_name[n]["workloads"]) > 1 for n in SHARED)
    # readers the benchmark had: since PR 47 trace_collectives finds the
    # exchange's all_to_all.N and the cell needs no reader of its own
    readers = {"trace_module", "trace_roofline", "trace_collectives",
               "span_self", "counter_delta"}
    for n in OWN | SHARED:
        m = by_name[n]
        f = spec.load_json("layer_metrics", n + ".json")
        assert f["reader"]["kind"] in readers and m["moves"] == "scan_rows_per_s"
        assert (f["unit"], f["better"], f["layer"], f["source"]) == (
            m["unit"], m["better"], m["layer"], m["source"])
    everywhere = {m["name"] for m in b["per_layer"] if "workloads" not in m}
    cell = spec.Cell(CELL)
    # PR 45 gave the cell decode_streams_per_query; a later PR may add more
    assert {m["name"] for m in cell.per_layer} \
        >= OWN | SHARED | everywhere | {"decode_streams_per_query"}
    assert {m["name"] for m in cell.end_to_end} \
        == {"scan_rows_per_s", "setup_s"}
    rooflines = {m["name"] for m in cell.per_layer if "roofline" in m["name"]}
    assert rooflines >= {"join_kernel_hbm_roofline",
                         "q12x4_exchange_kernel_hbm_roofline"}


def test_trace_collectives_sums_the_exchanges_lanes_and_is_silent_without():
    """What ``sources/trace_ops.py`` did for this cell until PR 47: the
    v5e trace names the exchange's ops ``all_to_all.N``."""
    import types
    from benchmarks import trace_reduce
    from benchmarks.sources import trace_collectives
    from benchmarks.tests.test_trace_reduce import Line, Plane, Profile, ev

    def reduced(*ops):
        host = Plane("/host:CPU", [Line("python", [
            ev("bench.execute.q12", 0, 10), ev("bench.execute.q12", 10, 10)])])
        chip = Plane("/device:TPU:0", [Line("XLA Ops", list(ops))])
        return trace_reduce.reduce_trace(Profile([host, chip]))

    ctx = types.SimpleNamespace(slice_queries=["q12", "q12"], trace=reduced(
        ev("all_to_all.27", 1, 4), ev("all_to_all.24", 5, 1),
        ev("fusion.9", 6, 3), ev("all-to-all-start", 9, 1),
        ev("while.20", 11, 5)))
    assert trace_collectives.read(ctx, {}) == pytest.approx(3.0)
    ctx.trace = reduced(ev("fusion.9", 1, 5))
    assert trace_collectives.read(ctx, {}) == 0.0   # a program without it
    ctx.trace = None
    assert trace_collectives.read(ctx, {}) is None  # a CPU rehearsal


def test_the_rooflines_bytes_come_from_the_query_file():
    rows = {"lineitem": 59_998_987, "orders": 15_000_000}
    # key 9, three dates and a mode's code 5 each; key 9 and a code 5
    assert roofline.row_bytes(QUERY["scanned_columns"]["lineitem"]) == 29
    assert roofline.row_bytes(QUERY["scanned_columns"]["orders"]) == 14
    assert roofline.algorithmic_bytes(QUERY, rows) \
        == 59_998_987 * 29 + 15_000_000 * 14 == 1_949_970_623


def test_the_shared_columns_are_the_other_generators_draw_for_draw(tables):
    chunks, arrays, _ = tables
    for i, c in enumerate(chunks):
        same = base.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        assert all((c["lineitem"][k] == same[k]).all() for k in same)
        q3 = q3gen.generate_chunk(PARAMS, PARAMS["data_seed"], i)
        assert c["orders"].keys() == q3["orders"].keys()
        assert all((c["orders"][k] == q3["orders"][k]).all()
                   for k in q3["orders"])
        columns = gen.copy_columns(c)
        assert set(columns) == {"orders", "lineitem"}
        assert set(columns["lineitem"]) == set(base.copy_columns(same)) | {
            "l_commitdate", "l_receiptdate", "l_shipmode"}
    assert arrays["rows.orders"] == PARAMS["orders"]
    assert arrays["rows.lineitem"] == sum(c["lineitem"]["okey"].size
                                          for c in chunks)


def test_the_new_columns_have_the_specs_shapes(tables):
    _, arrays, cat = tables
    date = dict(zip(cat("orders", "o_orderkey").tolist(),
                    cat("orders", "o_orderdate").tolist()))
    ordered = np.array([date[k] for k in cat("lineitem", "okey").tolist()])
    commit = cat("lineitem", "commit") - ordered
    assert commit.min() == 30 and commit.max() == 90
    lag = cat("lineitem", "receipt") - cat("lineitem", "ship")
    assert lag.min() == 1 and lag.max() == 30
    modes = np.bincount(cat("lineitem", "mode"), minlength=7)
    assert modes.size == 7 and modes.min() > 0.9 * modes.mean()
    priorities = np.bincount(cat("orders", "o_orderpriority"), minlength=5)
    assert priorities.size == 5 and priorities.min() > 0.9 * priorities.mean()
    # about one line in 190 counts for a draw: two modes of seven, a year
    # of 6.6, ship < commit < receipt
    kept = arrays["q12"].sum(axis=2)
    share = kept[:2].sum(axis=0) / arrays["rows.lineitem"]
    assert (share > 1 / 400).all() and (share < 1 / 100).all()


def test_reference_equals_a_join_of_the_columns_for_all_105_draws(tables):
    _, arrays, cat = tables
    orders = {c: cat("orders", c) for c in ("o_orderkey", "o_orderpriority")}
    lineitem = {c: cat("lineitem", c)
                for c in ("okey", "mode", "ship", "commit", "receipt")}
    for params in DRAWS[::5] + DRAWS[3::7]:
        got = q12.expected(arrays, params)
        assert got == q12.joined(orders, lineitem, params), params
        assert len(got) == 2 and got == sorted(got)
        assert all(h > 0 and l > h for _, h, l in got)


def test_reference_refuses_a_pair_that_is_no_pair(tables):
    _, arrays, _ = tables
    for bad in ("MAIL', 'MAIL", "MAIL", "MAIL', 'BOAT"):
        with pytest.raises(ValueError, match="two distinct modes"):
            q12.expected(arrays, {"SHIPMODES": bad, "DATE": 1994})


def test_a_program_without_the_exchange_is_refused_at_import(tmp_path):
    """The parent of PR 43 answers the statement through host frames,
    minutes a statement at SF10: the generator tells it so before
    anything is ingested."""
    import subprocess
    import sys
    (tmp_path / "citus_tpu" / "ops").mkdir(parents=True)
    for pkg in ("citus_tpu", "citus_tpu/ops"):
        (tmp_path / pkg / "__init__.py").write_text("")
    (tmp_path / "citus_tpu" / "ops" / "join.py").write_text(
        "def build_join_probe():\n    pass\n")
    os.symlink(os.path.join(os.path.dirname(spec.HERE), "benchmarks"),
               tmp_path / "benchmarks")
    p = subprocess.run(
        [sys.executable, "-c",
         "import benchmarks.generators.tpch_q12_tables"],
        cwd=tmp_path, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(tmp_path)})
    assert p.returncode != 0
    assert "has no exchange" in p.stderr and "tpch_sf10_q12_4chip" in p.stderr


def test_cell_untraced(checkout):
    p, out = run(checkout, CELL, 0)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    rows = out["info"]["data"]["table_rows"]
    assert set(rows) == {"orders", "lineitem"}
    assert out["info"]["rows"] == sum(rows.values())
    window = out["info"]["seconds"]
    assert out["metrics"]["scan_rows_per_s"]["value"] <= \
        out["info"]["rows"] * out["attempted"] / window * 1.001


def test_cell_traced_gives_every_program_metric_a_number(checkout):
    """... and the plan is the one the deployment is for: the window's
    statements ran ``join:repartition`` on the device, none fell back."""
    p, out = run(checkout, CELL, 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    device = {m["name"] for m in bench(checkout)["per_layer"]
              if m["source"] == "device_trace"}
    want = expected_metrics(checkout, CELL, "per_layer") - device \
        - {"peak_hbm_gb", "idle_unattributed_ms"}
    mine = (OWN | SHARED) - device
    assert len(mine) == 5 and mine <= want
    assert want <= set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    orders = out["info"]["data"]["table_rows"]["orders"]
    assert m["join_host_fallbacks_per_query"] == 0
    assert m["q12x4_rows_exchanged_per_query"] == orders
    assert m["compiles_in_window"] == 0
    assert m["q12x4_exchange_ms"] > 0 and m["stack_ms"] > 0
    # what the cell's metric list has no room for, from the counters
    n = out["attempted"]
    per_query = {k: v / n for k, v in out["info"]["counters"].items()}
    assert per_query["join_rows_exchanged"] == orders
    assert per_query["join_bytes_exchanged"] == orders * 15
    assert orders / 4 <= per_query["join_rows_received_max_device"] \
        < orders / 3
    assert "join_exchange_overflow_rounds" not in per_query
    assert per_query["join_rows_probed"] >= \
        out["info"]["data"]["table_rows"]["lineitem"]
    assert per_query["join_rows_matched"] \
        >= per_query["join_rows_out"] > 0
    assert per_query["join_table_bytes"] > 0
    counters = out["info"]["counters"]
    assert counters["join_queries"] == out["attempted"]
    assert "join_host_fallbacks" not in counters
    assert out["info"]["span_ms_per_query"]["join_exchange"] > 0


def test_explain_of_the_validation_statement_says_repartition(tmp_path):
    """The set-up's plan, held to its word once: over the
    configuration's two tables as ``dataset.create_tables`` makes them
    the validation statement plans ``join:repartition`` (``orders`` is
    not distributed on the join key) and the device runs it."""
    import citus_tpu as ct
    from benchmarks import dataset
    config = spec.load_json("configs", CONFIG + ".json")
    cl = ct.Cluster(str(tmp_path / "db"))
    try:
        dataset.create_tables(cl, spec.tables_of(config), 8)
        small = dict(PARAMS, orders=3000, chunk_orders=3000)
        chunk = gen.generate_chunk(small, 5, 0)
        for table, columns in gen.copy_columns(chunk).items():
            cl.copy_from(table, columns=columns)
        sql = QUERY["sql"].format(SHIPMODES="MAIL', 'SHIP", DATE="1994-01-01")
        plan = "\n".join(l for (l,) in cl.execute("EXPLAIN " + sql).rows)
        assert "repartition" in plan
        r = cl.execute(sql)
        assert r.explain["strategy"] == "join:repartition"
        assert r.explain["join"]["on"] == "device"
        stats = gen.Statistics(small)
        stats.add(chunk)
        assert [tuple(x) for x in r.rows] == q12.expected(
            stats.arrays(), {"SHIPMODES": "MAIL', 'SHIP", "DATE": 1994})
    finally:
        cl.close()
