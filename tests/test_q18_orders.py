"""The benchmark's Q18 block (``benchmarks/queries/q18_orders.json``)
through ``cl.execute`` against the benchmark's own plain reference
(``benchmarks/references/q18_orders.py``), on the benchmark's own
generator, on the CPU.

4,000 orders, as two chunks of 2,000 from the two ends of a 40,000-order
table: order keys then span 1..160,000, wider than ``direct_gid_limit``,
so the planner cannot prove the key domain small and takes the device
hash table -- as it does at SF1, where the cell runs.  (The first 4,000
orders alone have keys 1..16,000 and would take the direct-group-id
kernel.)

The tolerance is equality: ``l_quantity`` is a decimal held as a scaled
int64, sums of it are integer sums, and HAVING compares integers; there
is no float anywhere between the rows and the answer.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

# the driver runs ``python -m pytest tests/`` under xdist: the working
# directory is not to be trusted to hold the root of the repo
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import citus_tpu as ct  # noqa: E402
from benchmarks.generators import tpch_lineitem_orders as gen  # noqa: E402
from benchmarks.references import q18_orders  # noqa: E402
from benchmarks.references.common import dec  # noqa: E402

PARAMS = {"orders": 40_000, "parts": 200_000, "chunk_orders": 2_000,
          "lookup_sample_orders": 64}
CHUNKS = (0, 19)
DATA_SEED = 22
# 300 is the specification's validation value and 312..315 its range;
# at 4,000 orders few or no orders pass those, so 250 and 275 (the
# reference keeps every order from 250.00) make the answer non-empty
QUANTITIES = (250, 275, 300, 312, 313, 314, 315)
# "no_statistics": the planner is told nothing about the table, so the
# plan keeps every guard -- sum, count(l_quantity) and the float64
# overflow shadow, 41 bytes an entry, the layout before PR 36.  The
# other modes plan from the footers' statistics, which prove the sum
# fits and the quantity is never NULL: sum and count(*), 33 bytes
MODES = ("defaults", "forced_spill", "four_devices", "no_statistics")

with open(os.path.join(ROOT, "benchmarks", "queries", "q18_orders.json")) as fh:
    QUERY = json.load(fh)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "tpch_sf1_1chip.json")) as fh:
    CONFIG = json.load(fh)


@pytest.fixture(scope="module")
def table():
    stats = gen.Statistics(PARAMS)
    chunks = [gen.generate_chunk(PARAMS, DATA_SEED, i) for i in CHUNKS]
    for c in chunks:
        stats.add(c)
    return chunks, stats.arrays()


@pytest.fixture()
def cluster(tmp_path, table, limit_devices, request, monkeypatch):
    mode = request.param
    limit_devices(4 if mode == "four_devices" else 1)
    if mode == "no_statistics":
        from citus_tpu.planner import physical
        monkeypatch.setattr(physical, "table_facts", lambda cat, t: None)
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute(CONFIG["ddl"])
    cl.execute(f"SELECT create_distributed_table('{CONFIG['table']}', "
               f"'{CONFIG['distribution_column']}', 8)")
    for c in table[0]:
        cl.copy_from(CONFIG["table"], columns=gen.copy_columns(c))
    if mode == "forced_spill":
        cl.execute("SET citus.hash_agg_slots = 1024")
    yield cl, mode
    cl.close()


def _hash_line(cl, sql):
    """EXPLAIN ANALYZE's ``Hash:`` line -> [slots, occupancy, spilled
    rows, groups, bytes fetched, entries fetched with HAVING decided on
    the device (None where the whole table came home)]."""
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    m = re.search(r"Hash: hash slots (\d+), occupancy ([\d.]+)%, spilled "
                  r"(\d+) rows, groups (\d+), fetched (\d+) bytes", text)
    assert m, text
    on_device = re.search(r"having on device: (\d+) of (\d+) entries "
                          r"fetched", text)
    assert on_device is None or on_device.group(2) == m.group(1)
    return [int(m.group(1)), float(m.group(2))] \
        + [int(g) for g in m.group(3, 4, 5)] \
        + [int(on_device.group(1)) if on_device else None]


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("cluster", MODES, indirect=True)
def test_engine_equals_the_plain_reference(cluster, table, quantity):
    cl, mode = cluster
    stats = table[1]
    sql = QUERY["sql"].format(QUANTITY=quantity)
    want = sorted(q18_orders.expected(stats, {"QUANTITY": quantity}))
    got = sorted(tuple(r) for r in cl.execute(sql).rows)
    assert got == want          # every key and every decimal sum, equal
    if quantity == 250:
        assert len(want) >= 5   # the comparison is not of two empty lists
    rows, orders = int(stats["rows"]), int(stats["orders"])
    slots, _occupancy, spilled, groups, fetched, entries = _hash_line(cl, sql)
    assert groups == orders == 4_000
    if mode == "forced_spill":
        assert slots == 1024 and spilled > rows // 2
    else:
        # derived: the next power of two at or above the catalog's rows
        # (four devices: a table a device, each sized by the rows of the
        # fullest device's own shards, 4,096 slots, 16,384 in all)
        assert slots == 1 << (rows - 1).bit_length() == 16_384
        assert spilled < 0.05 * rows
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    assert ("tables 4 x 4096 slots, disjoint on l_orderkey" in text) \
        == (mode == "four_devices")
    # one int64 key + its int8 flag, sum / count, rows -- and the
    # float64 shadow where the statistics could not prove it away
    entry = 41 if mode == "no_statistics" else 33
    pa = cl.execute(sql).explain["partials"]
    assert (pa["computed"], pa["overflow_guards_proved_away"],
            pa["null_counts_proved_away"]) \
        == ((3, 0, 0) if mode == "no_statistics" else (2, 1, 1))
    if mode in ("defaults", "no_statistics") and quantity >= 312:
        # the cell's four QUANTITY values: HAVING is decided on the
        # table on the chip; the spilled keys' entries (1,024, their
        # power of two) and the survivors' blocks (8 of 512 slots, the
        # least) come home, not the 16,384 slots
        assert entries == 1024 + 8 * 512
        assert fetched == entries * entry
    elif entries is None:
        # also four tables of 4,096 slots: the least that comes home
        # filtered (8 blocks and 1,024 keys) is more than half a table
        assert fetched == slots * entry
    else:
        assert fetched == entries * entry <= slots * entry // 2


# what jit_hash_fused lowers to at PR 29 (780903a), for the statement's
# plan on a 1,024-slot state and a 2,048-row batch: the kernel's time
# and the bytes hash_kernel_hbm_roofline reckons are this module's
PARENT_HASH_FUSED_SHA1 = "27c07ec0b1b2462547fa7ffafb6fdfe98d9af9ac"
# ... and since PR 36 for the plan the table's statistics leave: the
# same kernel builder over two partials (sum, count(*)), no float64 lane
PROVED_HASH_FUSED_SHA1 = "a57a19761dbe5e6a16c4325200f96d2c87f36064"


@pytest.mark.parametrize("cluster", ["defaults", "no_statistics"],
                         indirect=True)
@pytest.mark.parametrize("quantity", (312, 313, 314, 315))
def test_hash_kernel_is_the_parents_module(cluster, quantity):
    """The filtered ending is its own kernel: ``jit_hash_fused`` lowers
    to the text it had before, byte for byte, whatever QUANTITY -- for
    the plan with every guard; the plan the statistics slim lowers to
    one pinned text of its own (``ops/hash_agg.py`` is not edited: the
    text differs by the partials alone)."""
    import hashlib

    import jax.numpy as jnp
    from citus_tpu.executor.executor import _hash_key_dtypes
    from citus_tpu.executor.kernel_cache import jit_compile
    from citus_tpu.ops.hash_agg import (
        build_fused_hash_worker, empty_hash_state,
    )
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    cl, mode = cluster
    sql = QUERY["sql"].format(QUANTITY=quantity)
    plan = plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]))
    assert plan.group_mode.kind == "hash_host"
    guarded = mode == "no_statistics"
    assert [(op.kind, op.dtype) for op in plan.partial_ops] == (
        [("sum", "int64"), ("count", "int64"), ("sum", "float64")] if guarded
        else [("sum", "int64"), ("count", "int64")])
    assert plan.partial_ops[1].arg_index == (0 if guarded else -1)
    key_dtypes = _hash_key_dtypes(plan, {})
    kernel = jit_compile(build_fused_hash_worker(plan, jnp, key_dtypes),
                         donate_argnums=0)
    schema = plan.bound.table.schema
    n = 2048
    cols = tuple(np.zeros(n, schema.scan_dtype(c, device=True))
                 for c in plan.scan_columns)
    valids = tuple(np.ones(n, bool) for _ in plan.scan_columns)
    text = kernel.lower(empty_hash_state(plan, 1024, key_dtypes), cols,
                        valids, np.ones(n, bool)).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() == (
        PARENT_HASH_FUSED_SHA1 if guarded else PROVED_HASH_FUSED_SHA1)


@pytest.mark.parametrize("cluster", MODES, indirect=True)
def test_every_order_sum_equals_numpy(cluster, table):
    """The block without its HAVING: all 4,000 groups, against a numpy
    sum over the generated rows (no statistics involved)."""
    cl, _ = cluster
    okey = np.concatenate([c["okey"] for c in table[0]])
    qty = np.concatenate([c["qty"] for c in table[0]])
    keys, inverse = np.unique(okey, return_inverse=True)
    sums = np.zeros(keys.size, np.int64)
    np.add.at(sums, inverse, qty)
    want = [(int(k), dec(s, 2)) for k, s in zip(keys, sums)]
    c0 = cl.counters.snapshot()
    got = sorted(cl.execute("SELECT l_orderkey, sum(l_quantity) FROM lineitem "
                            "GROUP BY l_orderkey").rows)
    c1 = cl.counters.snapshot()
    assert got == want
    assert c1["hash_groups_out"] - c0["hash_groups_out"] == keys.size
    assert c1["hash_fused_dispatches"] > c0["hash_fused_dispatches"]


def test_reference_refuses_a_quantity_under_what_it_keeps(table):
    with pytest.raises(ValueError):
        q18_orders.expected(table[1], {"QUANTITY": 249})
