"""The Q18 block's generator statistics and plain reference, and the
cell that runs it, end to end on the CPU.

The rehearsal takes 20,000 orders, not the 4,000 of the other cells':
the first 4,000 orders have keys 1..16,000, a domain the planner proves
small, and would run the direct-group-id kernel; from 16,400 orders on
the keys pass ``direct_gid_limit`` and the statement takes the device
hash table, the path the cell exists for."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.generators import tpch_lineitem as base
from benchmarks.generators import tpch_lineitem_orders as gen
from benchmarks.references import q18_orders
from benchmarks.references.common import dec
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import bench, checkout, expected_metrics  # noqa: F401

CELL = "tpch_sf1_q18_orders_params"
PARAMS = {"orders": 30_000, "parts": 200_000, "chunk_orders": 8_000,
          "lookup_sample_orders": 64}


def hand_made_chunk():
    """Three orders: seven lines of 50 (350.00, the largest an order can
    hold), two lines of 50 (100.00), six lines of 50 (300.00 exactly)."""
    lines = np.array([7, 2, 6])
    of_order = np.repeat(np.arange(3), lines)
    n = of_order.size
    one = np.ones(n, np.int64)
    return {"lines_per_order": lines, "order_index": of_order,
            "okey": base.order_key(of_order), "qty": 5000 * one,
            "price": 90_000 * one, "disc": 0 * one, "tax": 0 * one,
            "rf": one, "ls": one, "ship": (base.SHIP_LO + one).astype(np.int32)}


@pytest.mark.parametrize("quantity,want", [
    (250, [(1, "350.00"), (3, "300.00")]),
    (299, [(1, "350.00"), (3, "300.00")]),
    (300, [(1, "350.00")]),         # HAVING is >: 300.00 does not pass 300
    (312, [(1, "350.00")]),
    (349, [(1, "350.00")]),
    (350, []),
])
def test_reference_on_a_hand_made_table(quantity, want):
    stats = gen.Statistics(PARAMS)
    stats.add(hand_made_chunk())
    arrays = stats.arrays()
    assert int(arrays["orders"]) == 3 and int(arrays["rows"]) == 15
    got = q18_orders.expected(arrays, {"QUANTITY": quantity})
    assert got == [(k, dec(int(float(s) * 100), 2)) for k, s in want]
    assert all(str(s) == w for (_, s), (_, w) in zip(got, want))


def test_reference_refuses_what_it_did_not_keep():
    stats = gen.Statistics(PARAMS)
    stats.add(hand_made_chunk())
    with pytest.raises(ValueError):
        q18_orders.expected(stats.arrays(), {"QUANTITY": 249})


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
        assert all((c[k] == same[k]).all() for k in c)
        plain.add(same)
    for k, v in plain.arrays().items():
        assert (arrays[k] == v).all(), k
    assert int(arrays["orders"]) == PARAMS["orders"]


@pytest.mark.parametrize("quantity", [250, 275, 300, 312, 313, 314, 315])
def test_reference_equals_brute_force(table, quantity):
    chunks, arrays = table
    okey = np.concatenate([c["okey"] for c in chunks])
    qty = np.concatenate([c["qty"] for c in chunks])
    totals = {}
    for k, q in zip(okey.tolist(), qty.tolist()):
        totals[k] = totals.get(k, 0) + q
    want = sorted((k, dec(t, 2)) for k, t in totals.items()
                  if t > quantity * 100)
    assert sorted(q18_orders.expected(arrays, {"QUANTITY": quantity})) == want
    if quantity == 250:
        assert len(want) > 50


# ---- the cell, end to end on the CPU ------------------------------------


def run(checkout, trace):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", CELL,
           "--seed", "2147483659", "--seconds", "1.5", "--trace", str(trace),
           "--rehearse-on-cpu", "--orders", "20000"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_untraced(checkout):
    out = run(checkout, 0)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2 and out["rehearsal"] is True
    assert set(out["metrics"]) == {"scan_rows_per_s", "setup_s"} \
        == expected_metrics(checkout, CELL, "end_to_end")
    assert out["info"]["rows"] > 79_000
    draws = {d[1]["QUANTITY"] for d in out["info"]["first_draws"]}
    assert draws <= {312, 313, 314, 315}


def test_cell_traced_reports_the_hash_layer(checkout):
    out = run(checkout, 1)
    assert out["correct"] is True and out["failed"] == 0
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    # the CPU backend's trace has no device plane
    want = expected_metrics(checkout, CELL, "per_layer") - from_trace - {"peak_hbm_gb"}
    assert set(out["metrics"]) == want
    assert {"hash_spill_rows_per_query", "spill_drain_ms", "hash_merge_ms",
            "hash_finalize_ms"} <= want
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compiles_in_window"] == 0 and m["cache_hit_share"] == 0
    assert m["hash_merge_ms"] > 0 and m["hash_finalize_ms"] > 0
    # defaults for every GUC: the derived table keeps the spill under 5 %
    assert m["hash_spill_rows_per_query"] < 0.05 * out["info"]["rows"]
    counters, n = out["info"]["counters"], out["attempted"]
    assert counters["hash_groups_out"] == 20_000 * n
    assert counters["hash_fused_dispatches"] == 8 * n
    assert counters["hash_table_bytes_fetched"] == (1 << 17) * 41 * n
    spans = out["info"]["span_ms_per_query"]
    assert {"hash_init", "spill_drain", "hash_merge", "hash_finalize"} \
        <= set(spans)
