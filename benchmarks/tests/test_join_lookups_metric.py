"""``join_rows_looked_up_per_query`` (PR 49): the accepted
``counter_delta`` reader over the program's ``join_rows_looked_up``
counter, in the three cells whose statements join on the device; the
reader's two ends -- 0 on a result line of a program that does not
count (the parent), the per-query count on one that does -- and the Q3
cell rehearsed on the CPU: the probe looks up fewer rows than it is
handed, because ``l_shipdate > DATE`` drops about half of them first."""

import json
import os
import types

import pytest

from benchmarks import spec
from benchmarks.sources import counter_delta
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import checkout, run  # noqa: F401

NAME = "join_rows_looked_up_per_query"
CELLS = ["tpch_sf10_q3_params", "tpch_sf10_q10_params",
         "tpch_sf10x4_q12_repartition"]


def bench_json():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_entry_and_its_file_agree():
    b = bench_json()
    entry, = [m for m in b["per_layer"] if m["name"] == NAME]
    assert b["per_layer"][-1] == entry          # appended, nothing moved
    f = spec.load_json("layer_metrics", NAME + ".json")
    assert entry["name"] == f["name"] == NAME
    assert (f["unit"], f["better"], f["layer"], f["source"], f["moves"]) == (
        entry["unit"], entry["better"], entry["layer"], entry["source"],
        entry["moves"]) == ("1/query", "lower", "join", "program_counter",
                            "scan_rows_per_s")
    assert entry["workloads"] == CELLS
    assert f["reader"] == {"kind": "counter_delta",
                           "counters": ["join_rows_looked_up"],
                           "per": "query"}
    assert len(b["per_layer"]) <= 128


@pytest.mark.parametrize("cell", CELLS)
def test_the_join_cells_report_it_and_the_metric_it_moves(cell):
    c = spec.Cell(cell)
    assert NAME in {m["name"] for m in c.per_layer}
    assert "scan_rows_per_s" in {m["name"] for m in c.end_to_end}


def test_a_cell_without_a_join_does_not_report_it():
    c = spec.Cell("tpch_sf10_q1_repeat")
    assert NAME not in {m["name"] for m in c.per_layer}


def reading(counters, n_queries):
    reader = spec.load_json("layer_metrics", NAME + ".json")["reader"]
    return counter_delta.read(
        types.SimpleNamespace(counters=counters, n_queries=n_queries), reader)


def test_a_line_without_the_counter_reads_zero():
    """The parent's window: it probes and matches, and does not say how
    many rows it looked up -- 0, not None, so the line holds the metric
    and says 'does not count'."""
    assert reading({"join_rows_probed": 67_108_864,
                    "join_rows_matched": 5_840_000}, 1) == 0


@pytest.mark.parametrize("counted,queries,want", [
    (32_000_000, 1, 32_000_000.0),      # Q3: about half of 67.1 M
    (66_000_000, 3, 22_000_000.0),      # Q10: 24 rounds of about 0.92 M
    (7, 2, 3.5),
])
def test_a_line_with_the_counter_reads_the_count_per_query(counted, queries,
                                                           want):
    assert reading({"join_rows_looked_up": counted, "join_rows_probed": 1},
                   queries) == want


def test_no_completed_query_reads_nothing():
    assert reading({"join_rows_looked_up": 3}, 0) is None


def test_the_q3_cell_looks_up_fewer_rows_than_it_probes(checkout):
    p, out = run(checkout, "tpch_sf10_q3_params", 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert 0 < m[NAME] < m["q3_rows_probed_per_query"]
    # what was looked up had passed the filter: a row found is a row out
    # but for the cross-relation conjuncts
    assert m["q3_rows_out_per_query"] <= m["q3_rows_matched_per_query"] \
        <= m[NAME]
    assert m["join_host_fallbacks_per_query"] == 0
