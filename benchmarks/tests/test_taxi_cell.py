"""The taxi trips' generator and the hourly rollup's plain reference,
and the two cells PR 29 adds -- ``nyctaxi_hourly_repeat`` and
``tpch_sf10_orderkey_lookup`` -- end to end on the CPU."""

import datetime
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.generators import nyctaxi_trips as gen
from benchmarks.references import taxi_hourly
from benchmarks.references.common import avg_dec
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import ROOT, bench, checkout, expected_metrics  # noqa: F401

TAXI, LOOKUP = "nyctaxi_hourly_repeat", "tpch_sf10_orderkey_lookup"
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nyctaxi_hourly_1chip.json")) as fh:
    CONFIG = json.load(fh)
PARAMS = dict(CONFIG["generator"], orders=30_000, chunk_orders=8_000)


@pytest.fixture(scope="module")
def table():
    stats = gen.Statistics(PARAMS)
    chunks = []
    for i in range(gen.n_chunks(PARAMS)):
        c = gen.generate_chunk(PARAMS, 5, i)
        stats.add(c)
        chunks.append(c)
    return chunks, stats.arrays()


def test_same_seed_same_table(table):
    chunks, _ = table
    for i, c in enumerate(chunks):
        same = gen.generate_chunk(PARAMS, 5, i)
        assert all((c[k] == same[k]).all() for k in c)
    other = gen.generate_chunk(PARAMS, 6, 0)
    assert not (other["fare"] == chunks[0]["fare"]).all()


def test_shapes_of_the_table(table):
    chunks, arrays = table
    cat = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
    assert int(arrays["rows"]) == 30_000 == cat["trip_id"].size
    assert (cat["trip_id"] == np.arange(30_000)).all()
    first = gen.first_hour_us(PARAMS)
    assert first == 1_388_534_400_000_000          # 2014-01-01 00:00 UTC
    last = first + 181 * 24 * gen.HOUR_US
    assert cat["pickup"].min() >= first and cat["pickup"].max() < last
    assert (cat["dropoff"] > cat["pickup"]).all()
    # emitted in drop-off order inside a chunk: nearly, not exactly,
    # pickup order
    for c in chunks:
        assert (np.diff(c["dropoff"]) >= 0).all()
        assert (np.diff(c["pickup"]) < 0).any()
    assert set(cat["passengers"]) == {1, 2, 3, 4, 5, 6}
    assert (cat["fare"] >= 250).all() and (cat["fare"] % 50 == 0).all()
    assert (cat["total"] >= cat["fare"] + cat["tip"] + 50).all()
    assert (cat["tip"][cat["payment"] != 0] == 0).all()
    # the evening peak carries more trips than the night trough
    hour = (cat["pickup"] - first) // gen.HOUR_US % 24
    assert np.count_nonzero(hour == 19) > 3 * np.count_nonzero(hour == 4)


def test_every_hours_statistics_equal_a_recount(table):
    chunks, arrays = table
    want = {}
    for c in chunks:
        for p, f, t in zip(c["pickup"].tolist(), c["fare"].tolist(),
                           c["total"].tolist()):
            h = (p - int(arrays["hour_first_us"])) // gen.HOUR_US
            n, fs, ts = want.get(h, (0, 0, 0))
            want[h] = (n + 1, fs + f, ts + t)
    assert arrays["hourly"].shape == (181 * 24, 3)
    for h in range(181 * 24):
        assert tuple(arrays["hourly"][h]) == want.get(h, (0, 0, 0)), h
    rows = taxi_hourly.expected(arrays, {})
    assert len(rows) == len(want)
    epoch = datetime.datetime(2014, 1, 1)
    for hour, n, avg_fare, avg_total in rows:
        h = int((hour - epoch).total_seconds()) // 3600
        assert (n, avg_fare, avg_total) == (
            want[h][0], avg_dec(want[h][1], n, 2), avg_dec(want[h][2], n, 2))
        assert -avg_fare.as_tuple().exponent == 8


# ---- the cells, end to end on the CPU -----------------------------------


def run(checkout, cell, trace, seconds="1.5"):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
           "--seed", "2147483659", "--seconds", seconds, "--trace", str(trace),
           "--rehearse-on-cpu", "--orders", "4000"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell", [TAXI, LOOKUP])
def test_cell_untraced(checkout, cell):
    out, _ = run(checkout, cell, 0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, cell, "end_to_end")
    if cell == LOOKUP:
        keys = {json.dumps(d) for d in out["info"]["first_draws"]}
        assert len(keys) > 3                      # the key varies
    else:
        assert out["info"]["rows"] == 4000


def traced_metrics(checkout, cell):
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    # the CPU backend's trace has no device plane
    return expected_metrics(checkout, cell, "per_layer") - from_trace \
        - {"peak_hbm_gb"}


def test_taxi_cell_traced_reports_the_groupby_layer(checkout):
    out, _ = run(checkout, TAXI, 1)
    assert out["correct"] is True and out["failed"] == 0
    want = traced_metrics(checkout, TAXI)
    assert set(out["metrics"]) == want
    assert {"groupby_init_ms", "result_fetch_ms", "groupby_combine_ms",
            "groupby_groups_per_query"} <= want
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window"] == 0 and m["cache_hit_share"] == 100
    assert m["groupby_groups_per_query"] == 181 * 24 + 1
    assert m["groupby_combine_ms"] > 0 and m["groupby_init_ms"] > 0
    counters, n = out["info"]["counters"], out["attempted"]
    assert counters["direct_groups"] == (181 * 24 + 1) * n
    assert 1500 * n < counters["direct_groups_out"] < 4000 * n
    assert counters["fused_dispatches"] == 8 * n
    # of the eight metrics the taxi cell added, four are this cell's
    # alone; four read what another cell's copy read and, since PR 47,
    # are one entry that lists both cells
    by_name = {m["name"]: m for m in bench(checkout)["per_layer"]}
    mine = {"groupby_kernel_hbm_roofline", "groupby_init_ms",
            "groupby_combine_ms", "groupby_groups_per_query"}
    assert all(by_name[n]["workloads"] == [TAXI] for n in mine)
    shared = {"scan_kernel_ms", "scan_dispatches_per_query",
              "result_fetch_ms", "group_kernel_mxu_roofline"}
    assert all(TAXI in by_name[n]["workloads"]
               and len(by_name[n]["workloads"]) > 1 for n in shared)
    assert m["result_fetch_ms"] > 0


def test_lookup_cell_traced(checkout):
    out, _ = run(checkout, LOOKUP, 1)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == traced_metrics(checkout, LOOKUP)
    assert "compiles_in_window" in out["metrics"]
    assert out["info"]["span_ms_per_query"]["execute"] > 0


def test_a_wrong_count_in_the_reference_is_not_correct(checkout):
    ref = checkout / "benchmarks" / "references" / "taxi_hourly.py"
    good = ref.read_text()
    try:
        ref.write_text(good.replace("int(h)), n,", "int(h)), n + 1,"))
        assert ref.read_text() != good
        out, err = run(checkout, TAXI, 0)
    finally:
        ref.write_text(good)
    assert out["correct"] is False
    assert "wrong answer" in err


def test_the_group_product_reader_reads_nothing_without_its_counter():
    """On a program that counts no ``direct_groups`` (the parent of PR
    29) the MXU share is left out, not raised and not 0."""
    import types
    from benchmarks.sources import trace_group_product as reader
    cell = types.SimpleNamespace(
        config={"kernel_modules": {"scan": "jit_fused"}},
        queries={"taxi_hourly": {"group_product": {"planes": 19}}})
    ctx = types.SimpleNamespace(
        cell=cell, trace={"modules": {"jit_fused": {"seconds": 2.0,
                                                    "count": 48}}},
        slice_queries=["taxi_hourly"] * 2, n_queries=10, counters={},
        table_rows=85_000_000, device_kind="TPU v5 lite", chips=1)
    assert reader.read(ctx, {"module": "scan"}) is None
    ctx.counters = {"direct_groups": 4345 * 10}
    # 2 queries x 2 x 4,345 x 19 x 85 M operations at 197 TFLOP/s over 2 s
    want = 100 * (2 * 2 * 4345 * 19 * 85e6 / 197e12) / 2.0
    assert reader.read(ctx, {"module": "scan"}) == pytest.approx(want)
    ctx.trace = None
    assert reader.read(ctx, {"module": "scan"}) is None
