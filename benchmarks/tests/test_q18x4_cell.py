"""The configuration ``tpch_sf10_orders_4chip`` held to the layout it
says it takes, the kept cell's traffic file, and both cells PR 35 added,
end to end on the CPU (four forced host devices for the four-chip one).

The four-chip rehearsal takes 20,000 orders for the reason
``test_q18_cell.py`` gives: under 16,400 the keys' domain is small and
the direct-group-id kernel answers instead of the hash tables."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import bench, checkout, expected_metrics  # noqa: F401

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "tpch_sf10x4_q18_orders_params"
CELL_REPEAT = "tpch_sf10_q15_revenue_repeat"


def load(*parts):
    return json.loads(ROOT.joinpath("benchmarks", *parts).read_text())


# ---- the files ------------------------------------------------------------


def test_configuration_is_config_2s_layout_under_q18s_block():
    cfg = load("configs", "tpch_sf10_orders_4chip.json")
    layout = load("configs", "tpch_sf10_4chip.json")
    sf1 = load("configs", "tpch_sf1_1chip.json")
    for key in ("chips", "table", "ddl", "distribution_column",
                "shards_per_device"):
        assert cfg[key] == layout[key], key
    for key in ("isolation", "replication_factor", "durability"):
        assert cfg["guarantees"][key] == layout["guarantees"][key], key
    assert "HAVING comparison is made on scaled integers" \
        in cfg["guarantees"]["exactness"]
    # SF10's own scale and the SF1 cell's generator, seed and module
    assert cfg["generator"] == dict(
        layout["generator"], name=sf1["generator"]["name"])
    assert cfg["generator"]["orders"] == 15_000_000
    assert cfg["generator"]["data_seed"] == sf1["generator"]["data_seed"]
    assert cfg["kernel_modules"] == sf1["kernel_modules"] \
        == {"hash": "jit_hash_fused"}
    assert cfg["reduced"] == ["q18_outer_query"] and cfg["assumed"]
    entry, = [c for c in bench(ROOT)["configs"] if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]


def test_cells_name_the_traffic_the_issue_gave():
    cells = {w["name"]: w for w in bench(ROOT)["workloads"]}
    assert cells[CELL]["chips"] == 4 and cells[CELL_REPEAT]["chips"] == 1
    assert (cells[CELL]["config"], cells[CELL]["traffic"]) \
        == ("tpch_sf10_orders_4chip", "q18_orders_params")
    assert (cells[CELL_REPEAT]["config"], cells[CELL_REPEAT]["traffic"]) \
        == ("tpch_sf10_supp_1chip", "q15_revenue_repeat")
    # three since PR 43's Q12 cell; at most half of the cells may
    assert 2 <= sum(w["chips"] == 4 for w in cells.values()) \
        <= len(cells) // 2
    repeat = load("traffic", "q15_revenue_repeat.json")
    params = load("traffic", "q15_revenue_params.json")
    assert repeat["statements"] == [
        {"query": "q15_revenue", "parameters": "fixed", "weight": 1}]
    assert (repeat["warmup_cycles"], repeat["traced_slice_cycles"]) == (2, 4)
    assert {k: repeat[k] for k in ("loop", "clients", "ordering")} \
        == {k: params[k] for k in ("loop", "clients", "ordering")}
    assert load("queries", "q15_revenue.json")["parameters"]["DATE"][
        "fixed"] == "1996-01-01"


#: what PR 35's metrics are called since PR 47 folded the copies of one
#: reader: six keep the four-chip cell's prefix, nine are an entry the SF1
#: Q18 cell or the Q12 cell shares; the repeat cell's four all fold
Q18X4 = {
    "q18x4_hash_init_ms": "q18x4_hash_init_ms",
    "q18x4_fetch_ms": "q18x4_fetch_ms",
    "q18x4_tables_per_query": "q18x4_tables_per_query",
    "q18x4_tables_merged_per_query": "q18x4_tables_merged_per_query",
    "q18x4_rows_in_max_device_per_query": "q18x4_rows_in_max_device_per_query",
    "q18x4_hash_kernel_ms": "hash_kernel_ms",
    "q18x4_hash_kernel_hbm_roofline": "hash_kernel_hbm_roofline",
    "q18x4_hash_dispatches_per_query": "hash_dispatches_per_query",
    "q18x4_collective_ms": "mesh_collective_ms",
    "q18x4_stack_ms": "stack_ms",
    "q18x4_spill_drain_ms": "spill_drain_ms",
    "q18x4_hash_filter_ms": "hash_filter_ms",
    "q18x4_decode_wait_ms": "decode_wait_ms",
    "q18x4_spill_rows_per_query": "hash_spill_rows_per_query",
    "q18x4_entries_fetched_per_query": "hash_entries_fetched_per_query",
}
Q15R = {
    "q15r_group_kernel_ms": "group_kernel_ms",
    "q15r_group_kernel_mxu_roofline": "group_kernel_mxu_roofline",
    "q15r_finalize_ms": "group_finalize_ms",
    "q15r_fetch_ms": "result_fetch_ms",
}


@pytest.mark.parametrize("cell,names,n", [(CELL, Q18X4, 15),
                                          (CELL_REPEAT, Q15R, 4)])
def test_each_new_metric_lists_its_cell(cell, names, n):
    by_name = {m["name"]: m for m in bench(ROOT)["per_layer"]}
    assert len(names) == n
    for old, new in names.items():
        m = by_name[new]
        assert cell in m["workloads"] and m["moves"] == "scan_rows_per_s"
        # a name that kept the cell's prefix is the cell's alone
        assert (m["workloads"] == [cell]) == (old == new)
        assert old == new or old not in by_name


# ---- the cells, end to end on the CPU -------------------------------------


def run(checkout, cell, trace, orders, seconds="1.5"):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
           "--seed", "2147483659", "--seconds", seconds, "--trace", str(trace),
           "--rehearse-on-cpu", "--orders", str(orders)]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def host_metrics(checkout, cell):
    """The cell's per-layer metrics a CPU rehearsal can report: its
    trace has no device plane and its devices no memory statistics."""
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    return expected_metrics(checkout, cell, "per_layer") - from_trace \
        - {"peak_hbm_gb"}


# The repeat cell's query takes 1.3 s at 60,000 orders on this CPU with
# the cores to itself, and longer than the 1.5 s window while five other
# workers' rehearsals hold them: the window then closed on ONE query and
# ``attempted >= 2`` failed by the schedule (a run of the whole directory
# on six workers, PR 47).  What it shared was the cores, so it gets a
# window of its own: long enough for a second query under that load.
@pytest.mark.parametrize("cell,orders,seconds", [(CELL, 20_000, "1.5"),
                                                 (CELL_REPEAT, 60_000, "8")])
def test_cell_untraced(checkout, cell, orders, seconds):
    out = run(checkout, cell, 0, orders, seconds)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, cell, "end_to_end")
    assert out["device"]["count"] == (4 if cell == CELL else 1)


def test_four_chip_cell_traced_builds_a_table_a_device(checkout):
    out = run(checkout, CELL, 1, 20_000)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == host_metrics(checkout, CELL)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    rows = out["info"]["rows"]
    assert m["compiles_in_window"] == 0 and m["cache_hit_share"] == 0
    assert m["q18x4_tables_per_query"] == 4
    assert m["q18x4_tables_merged_per_query"] == 0
    # 32 shards by hash over four devices: a quarter each, nearly
    assert rows / 4 <= m["q18x4_rows_in_max_device_per_query"] <= 0.27 * rows
    assert m["hash_spill_rows_per_query"] < 0.05 * rows
    # HAVING on the chips: four tables' least blocks and host keys, not
    # the 4 x 32,768 slots
    assert m["hash_entries_fetched_per_query"] == 4 * (8 * 512 + 1024)
    assert m["q18x4_hash_init_ms"] > 0 and m["stack_ms"] > 0
    assert m["hash_filter_ms"] > 0 and m["q18x4_fetch_ms"] > 0
    counters, n = out["info"]["counters"], out["attempted"]
    assert counters["hash_groups_out"] == 20_000 * n
    assert counters["hash_slots"] == 4 * 32_768 * n
    assert counters["hash_rows_in"] == rows * n
    assert counters["hash_fused_dispatches"] == 8 * n   # 32 batches, 8 rounds
    assert "hash_tables_merged" not in counters


def test_repeat_cell_traced_answers_from_the_batch_cache(checkout):
    out = run(checkout, CELL_REPEAT, 1, 60_000)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == host_metrics(checkout, CELL_REPEAT)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window"] == 0 and m["cache_hit_share"] == 100
    assert m["group_finalize_ms"] > 0 and m["result_fetch_ms"] > 0
    assert {d[1]["DATE"] for d in out["info"]["first_draws"]} == {"1996-01-01"}
    counters, n = out["info"]["counters"], out["attempted"]
    assert counters["direct_groups"] == 100_001 * n
    assert counters["device_cache_hits"] == n
    assert "batch_rows_real" not in counters    # nothing was decoded
