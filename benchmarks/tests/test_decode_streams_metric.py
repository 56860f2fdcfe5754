"""``decode_streams_per_query`` (PR 45): the accepted ``counter_delta``
reader over the program's ``decode_streams`` counter, in the three cells
whose window streams a scan past the cache; and the reader's two ends:
0 on a result line of a program that does not count (the parent), the
per-query count on one that does."""

import json
import os
import types

import pytest

from benchmarks import spec
from benchmarks.sources import counter_delta

NAME = "decode_streams_per_query"
CELLS = ["tpch_sf10_q1q6_params", "tpch_sf10_q15_revenue_params",
         "tpch_sf10x4_q12_repartition"]


def bench_json():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_entry_and_its_file_agree():
    b = bench_json()
    entry, = [m for m in b["per_layer"] if m["name"] == NAME]
    f = spec.load_json("layer_metrics", NAME + ".json")
    assert entry["name"] == f["name"] == NAME
    assert (f["unit"], f["better"], f["layer"], f["source"], f["moves"]) == (
        entry["unit"], entry["better"], entry["layer"], entry["source"],
        entry["moves"]) == ("1/query", "higher", "host stripe decode",
                            "program_counter", "scan_rows_per_s")
    assert entry["workloads"] == CELLS
    assert f["reader"] == {"kind": "counter_delta",
                           "counters": ["decode_streams"], "per": "query"}
    assert len(b["per_layer"]) <= 128
    layers = {m["layer"] for m in b["per_layer"] if m["name"] != NAME}
    assert entry["layer"] in layers        # a layer the benchmark names


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_that_stream_report_it_and_the_metric_it_moves(cell):
    c = spec.Cell(cell)
    assert NAME in {m["name"] for m in c.per_layer}
    assert "scan_rows_per_s" in {m["name"] for m in c.end_to_end}


def test_a_resident_cell_does_not_report_it():
    c = spec.Cell("tpch_sf10_q1_repeat")
    assert NAME not in {m["name"] for m in c.per_layer}


def reading(counters, n_queries):
    reader = spec.load_json("layer_metrics", NAME + ".json")["reader"]
    return counter_delta.read(
        types.SimpleNamespace(counters=counters, n_queries=n_queries), reader)


def test_a_line_without_the_counter_reads_zero():
    """The parent's window: every other counter moves, this one is not
    there -- 0, not None, so the line holds the metric and says 'does
    not count'."""
    assert reading({"footer_cache_hits": 4096, "fused_dispatches": 512}, 32) == 0


@pytest.mark.parametrize("counted,queries,want", [
    (96, 32, 3.0),      # three producers a query
    (128, 32, 4.0),     # the mesh: one a device stream
    (32, 32, 1.0),      # a machine of two cores: the one decode thread
    (7, 2, 3.5),
])
def test_a_line_with_the_counter_reads_the_count_per_query(counted, queries,
                                                           want):
    assert reading({"decode_streams": counted, "footer_cache_hits": 1},
                   queries) == want


def test_no_completed_query_reads_nothing():
    assert reading({"decode_streams": 3}, 0) is None
