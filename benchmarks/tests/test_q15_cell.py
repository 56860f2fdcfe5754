"""Q15's view ``revenue``: the generator's statistics and the plain
reference, and the two cells PR 31 added, end to end on the CPU.

The Q15 rehearsal takes 60,000 orders, not the 4,000 of the other
cells': the suppliers stay the configuration's 100,000 at any
``--orders`` (a key domain of 100,001 slots), and the planner keeps the
direct table over them only where the table's rows outnumber the slots
twice -- 240,000 rows do, 16,000 would take the device hash table."""

import datetime
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.generators import tpch_lineitem as base
from benchmarks.generators import tpch_lineitem_supp as gen
from benchmarks.references import q15_revenue
from benchmarks.references.common import dec
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import bench, checkout, expected_metrics  # noqa: F401

CELL = "tpch_sf10_q15_revenue_params"
CELL_8C = "tpch_sf10_q1_repeat_8c"
PARAMS = {"orders": 30_000, "parts": 200_000, "suppliers": 10_000,
          "chunk_orders": 8_000, "lookup_sample_orders": 64}
EPOCH = datetime.date(1970, 1, 1)


def test_supplier_key_is_the_partsupp_formula():
    # S = 10,000: part 1's four suppliers are 2, 2502, 5002, 7502
    assert [int(gen.supplier_key(np.int64(1), i, 10_000))
            for i in range(4)] == [2, 2502, 5002, 7502]
    # part 10,001 adds (partkey - 1) / S = 1 to each step
    assert [int(gen.supplier_key(np.int64(10_001), i, 10_000))
            for i in range(4)] == [2, 2503, 5004, 7505]
    keys = gen.supplier_key(np.arange(1, 200_001), 3, 10_000)
    assert keys.min() == 1 and keys.max() == 10_000


def test_month_axis_is_the_calendar_s():
    first = (datetime.date(1992, 1, 2) - EPOCH).days
    for date, month in ((datetime.date(1992, 1, 2), 0),
                        (datetime.date(1992, 1, 31), 0),
                        (datetime.date(1992, 2, 1), 1),
                        (datetime.date(1996, 2, 29), 49),
                        (datetime.date(1997, 12, 31), 71),
                        (datetime.date(1998, 12, 1), 83)):
        day = (date - EPOCH).days - first
        assert int(q15_revenue.month_of_ship_day(day)) == month
    assert int(q15_revenue.month_of_ship_day(base.SHIP_DAYS - 1)) == 83


@pytest.fixture(scope="module")
def table():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, 5, i)
        stats.add(c)
        chunks.append(c)
    return chunks, stats.arrays()


def test_same_draws_as_tpch_lineitem_and_its_statistics(table):
    chunks, arrays = table
    plain = base.Statistics(PARAMS)
    for i, c in enumerate(chunks):
        same = base.generate_chunk(PARAMS, 5, i)
        assert set(c) == set(same) | {"supp"}
        assert all((c[k] == same[k]).all() for k in same)
        assert c["supp"].min() >= 1 and c["supp"].max() <= 10_000
        plain.add(same)
        assert set(gen.copy_columns(c)) == set(base.copy_columns(same)) \
            | {"l_suppkey"}
    for k, v in plain.arrays().items():
        assert (arrays[k] == v).all(), k
    assert int(arrays["suppliers"]) == 10_000
    assert arrays["q15_revenue"].shape == arrays["q15_rows"].shape \
        == (10_001, 84)
    assert int(arrays["q15_rows"].sum()) == int(arrays["rows"])


def brute_force(chunks, date):
    """The view over the generator's own rows, in Python integers and the
    calendar's own month arithmetic."""
    y, m = int(date[:4]), int(date[5:7])
    lo = (datetime.date(y, m, 1) - EPOCH).days
    hi = (datetime.date(y + (m + 2) // 12, (m + 2) % 12 + 1, 1) - EPOCH).days
    total = {}
    for c in chunks:
        inside = (c["ship"] >= lo) & (c["ship"] < hi)
        for s, p, d in zip(c["supp"][inside].tolist(),
                           c["price"][inside].tolist(),
                           c["disc"][inside].tolist()):
            total[s] = total.get(s, 0) + p * (100 - d)
    return sorted((s, dec(t, 4)) for s, t in total.items())


@pytest.mark.parametrize("date", ["1993-01-01", "1996-01-01", "1996-02-01",
                                  "1994-11-01", "1997-10-01", "1992-01-01",
                                  "1998-10-01"])
def test_reference_equals_brute_force(table, date):
    chunks, arrays = table
    want = brute_force(chunks, date)
    assert sorted(q15_revenue.expected(arrays, {"DATE": date})) == want
    if date == "1996-01-01":
        assert len(want) > 3_000 and str(want[0][1]).count(".") == 1


def test_reference_refuses_what_it_does_not_hold(table):
    _, arrays = table
    with pytest.raises(ValueError):
        q15_revenue.expected(arrays, {"DATE": "1996-01-15"})
    with pytest.raises(ValueError):
        q15_revenue.expected(arrays, {"DATE": "1998-11-01"})
    with pytest.raises(ValueError):
        q15_revenue.expected(arrays, {"DATE": "1991-12-01"})


def test_query_file_holds_the_58_months():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "queries", "q15_revenue.json")) as fh:
        q = json.load(fh)
    months = [f"{y}-{m:02d}-01" for y in range(1993, 1998)
              for m in range(1, 13)][:58]
    assert q["parameters"]["DATE"]["choices"] == months
    assert months[-1] == "1997-10-01"
    assert q["sql"].startswith("select l_suppkey, sum(l_extendedprice * "
                               "(1 - l_discount))")


# ---- the cells, end to end on the CPU -------------------------------------


def run(checkout, cell, trace, orders):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
           "--seed", "2147483659", "--seconds", "1.5", "--trace", str(trace),
           "--rehearse-on-cpu", "--orders", str(orders)]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_q15_cell_untraced(checkout):
    out = run(checkout, CELL, 0, 60_000)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    assert out["info"]["rows"] > 235_000
    months = {d[1]["DATE"] for d in out["info"]["first_draws"]}
    assert all(m[-3:] == "-01" and "1993" <= m[:4] <= "1997" for m in months)


def test_q15_cell_traced_reports_the_grouping_layer(checkout):
    out = run(checkout, CELL, 1, 60_000)
    assert out["correct"] is True and out["failed"] == 0
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    # the CPU backend's trace has no device plane
    want = expected_metrics(checkout, CELL, "per_layer") - from_trace \
        - {"peak_hbm_gb"}
    assert set(out["metrics"]) == want
    assert {"q15_state_slots", "q15_group_rows_in_per_query",
            "q15_bytes_fetched_per_query", "group_finalize_ms",
            "q15_groups_out_per_query", "q15_state_init_ms",
            "result_fetch_ms", "decode_wait_ms"} <= want
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window"] == 0
    # the direct table over the suppliers' domain, three states of it
    # fetched (the sum, its count, the group rows: PR 36 proved the sum's
    # float64 shadow away)
    assert m["q15_state_slots"] == 100_001
    assert m["q15_bytes_fetched_per_query"] == 24 * 100_001
    assert m["q15_group_rows_in_per_query"] >= out["info"]["rows"]
    assert 5_000 < m["q15_groups_out_per_query"] < 12_000
    assert m["group_finalize_ms"] > 0 and m["q15_state_init_ms"] > 0
    counters, n = out["info"]["counters"], out["attempted"]
    assert counters["direct_groups"] == 100_001 * n
    assert counters["group_rows_kept"] < counters["group_rows_in"] // 20
    assert "hash_fused_dispatches" not in counters
    spans = out["info"]["span_ms_per_query"]
    assert {"init_acc", "fetch", "finalize_groups"} <= set(spans)


def test_q15_cell_on_the_hash_table_below_the_rule(checkout):
    """16,000 rows under 100,001 slots: the hash route answers, its table
    bounded by the rows; the same metrics read its counters and spans."""
    out = run(checkout, CELL, 1, 4_000)
    assert out["correct"] is True and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["q15_state_slots"] == 16_384
    assert m["q15_bytes_fetched_per_query"] == 16_384 * 33
    assert {"hash_init", "hash_merge", "hash_finalize"} \
        <= set(out["info"]["span_ms_per_query"])


def test_q1_repeat_8c_cell(checkout):
    out = run(checkout, CELL_8C, 0, 4_000)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 16 and out["info"]["by_query"] == {
        "q1": out["attempted"]}
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL_8C, "end_to_end")
    traffic = json.loads((checkout / "benchmarks" / "traffic"
                          / "q1_repeat_8c.json").read_text())
    one = json.loads((checkout / "benchmarks" / "traffic"
                      / "q1_repeat.json").read_text())
    assert traffic["clients"] == 8
    assert {k: v for k, v in traffic.items()
            if k not in ("name", "what", "clients")} \
        == {k: v for k, v in one.items()
            if k not in ("name", "what", "clients")}
    out = run(checkout, CELL_8C, 1, 4_000)
    assert out["correct"] is True and out["failed"] == 0
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    assert {"scan_kernel_ms", "scan_dispatches_per_query"} <= from_trace
    # the CPU backend's trace has no device plane
    spans = {"q1_8c_admission_wait_ms", "q1_8c_round_wait_ms",
             "result_fetch_ms", "q1_8c_cache_lookup_ms", "q1_8c_plan_ms",
             "q1_8c_dispatch_ms", "q1_8c_device_wait_ms"}
    assert set(out["metrics"]) == spans | {
        "cache_hit_share", "compiles_in_window", "untraced_host_ms",
        "partials_proved_away_per_query", "kernel_compiles_in_window"} \
        == expected_metrics(checkout, CELL_8C, "per_layer") - from_trace \
        - {"peak_hbm_gb"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["cache_hit_share"] == 100
    assert all(m[k] >= 0 for k in spans)
    assert m["q1_8c_round_wait_ms"] > 0 and m["result_fetch_ms"] > 0 \
        and m["q1_8c_dispatch_ms"] > 0


def test_the_grouping_module_is_read_on_either_route():
    """``q15_group_kernel_*`` read the scan module where the direct table
    answered and the hash module where the hash table did (the parent of
    PR 31), and nothing without a trace."""
    import types
    from benchmarks.sources import trace_first_module as reader
    cell = types.SimpleNamespace(
        config={"kernel_modules": {"scan": "jit_fused",
                                   "hash": "jit_hash_fused"}},
        queries={"q15_revenue": {"scanned_columns": {
            "l_suppkey": "bigint", "l_extendedprice": "decimal",
            "l_discount": "decimal", "l_shipdate": "date"}}})
    ctx = types.SimpleNamespace(
        cell=cell, slice_queries=["q15_revenue"] * 2, table_rows=60_000_000,
        rows_by_table={"lineitem": 60_000_000}, device_kind="TPU v5 lite", chips=1,
        trace={"modules": {"jit_fused": {"seconds": 1.6, "count": 32}}})
    ms = {"of": "trace_module", "modules": ["scan", "hash"],
          "field": "seconds", "scale": 1000}
    n = dict(ms, field="count", scale=1)
    share = {"of": "trace_roofline", "modules": ["scan", "hash"]}
    assert reader.read(ctx, ms) == pytest.approx(800.0)
    assert reader.read(ctx, n) == 16
    direct = reader.read(ctx, share)
    ctx.trace = {"modules": {"jit_hash_fused": {"seconds": 27.6,
                                                "count": 32}}}
    assert reader.read(ctx, ms) == pytest.approx(13_800.0)
    assert reader.read(ctx, n) == 16
    assert reader.read(ctx, share) == pytest.approx(direct * 1.6 / 27.6)
    ctx.trace = {"modules": {"jit_run": {"seconds": 1.0, "count": 1}}}
    assert reader.read(ctx, ms) is None and reader.read(ctx, share) is None
    ctx.trace = None
    assert reader.read(ctx, ms) is None
    # the files name this reader, and the configuration both roles
    for name in ("group_kernel_ms", "q15_group_dispatches_per_query",
                 "q15_group_kernel_hbm_roofline"):
        with open(os.path.join(os.path.dirname(__file__), "..",
                               "layer_metrics", name + ".json")) as fh:
            r = json.load(fh)["reader"]
        assert r["kind"] == "trace_first_module" \
            and r["modules"] == ["scan", "hash"]


def test_query_file_counts_the_planes_the_planner_counts(tmp_path):
    """``group_product.planes`` of the query file (the MXU roofline's
    operations) is what ``planner/physical.py`` ``product_planes`` counts
    for the published text; the kernel checks its own count against the
    same function when it is traced (``ops/scan_agg.py``)."""
    import citus_tpu as ct
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select, product_planes
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "queries", "q15_revenue.json")) as fh:
        query = json.load(fh)
    with open(os.path.join(root, "configs", "tpch_sf10_supp_1chip.json")) as fh:
        cfg = json.load(fh)
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute(cfg["ddl"])
    sql = query["sql"].format(DATE=query["parameters"]["DATE"]["fixed"])
    plan = plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]))
    assert product_planes(plan.partial_ops, plan.agg_args) \
        == query["group_product"]["planes"]
    cl.close()
