"""Streaming fused device hash aggregation with pushable partials.

Covers the PR's acceptance surface:
- fused-vs-cpu oracle across cardinalities below / at / above the slot
  count plus a spill-heavy adversarial keyset (slots << groups);
- float group-key equality: -0.0 and 0.0 group together, every NaN
  payload is ONE group, on both the device and host-spill halves
  (sqlite oracle for mixed-sign zeros; NaN maps to sqlite's NULL);
- exactly ONE fused dispatch per batch (`hash_fused_dispatches`) and
  ZERO `jit_table_merge_*` / `jit_hash_worker` kernel slots;
- streaming peak device window stays ≤ 2× batch bytes with the HBM
  cache capped out of the way and depth 1;
- `citus.hash_agg_slots = auto` sizes from catalog row stats and the
  EXPLAIN ANALYZE `Hash:` line reports slots / occupancy / spill;
- the kernel's sort -> segment reduce -> chunked insert equals the cpu
  oracle for every shape of a batch (one to thousands of rows a key,
  all masked, NULL / float / two-column keys, count, decimal sum, min,
  max and float sum together), keeps groups apart under a forced
  fingerprint collision, and `hash_table_updates` counts the batches'
  distinct keys, not their rows;
- 2-host push: hash-table partials ship as TASK_VERSION 3 "hash"
  tasks (`hash_partials_pushed` rises, zero fallbacks, zero placement
  sync) byte-identical to the pull path, and a TASK_VERSION-2 peer
  falls back to pull cleanly.
"""

import math
import re

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS


@pytest.fixture()
def cl(tmp_path):
    return ct.Cluster(str(tmp_path / "db"))


@pytest.fixture()
def one_device(monkeypatch):
    """Pin the executor to the single-device path (conftest forces 8
    virtual host devices)."""
    import jax
    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real[:1])
    return real[0]


@pytest.fixture()
def pair(tmp_path):
    """Two coordinators, two data dirs, one logical cluster: A is the
    metadata authority hosting node 0; B attaches and hosts node 1."""
    a = ct.Cluster(str(tmp_path / "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    na = a.register_node()
    b = ct.Cluster(str(tmp_path / "b"), data_port=0, hosted_nodes=set(),
                   coordinator=("127.0.0.1", a.control_port), n_nodes=0)
    nb = b.register_node()
    a._maybe_reload_catalog(force_sync=True)
    yield a, b, na, nb
    b.close()
    a.close()


def _delta(c0, c1, name):
    return c1[name] - c0[name]


def _fill_groups(cl, n, groups, shards=4, table="t"):
    cl.execute(f"CREATE TABLE {table} "
               "(k bigint NOT NULL, g bigint, v bigint)")
    cl.execute(f"SELECT create_distributed_table('{table}', 'k', {shards})")
    rng = np.random.default_rng(groups)
    # key domain far wider than direct_gid_limit -> hash_host mode
    g = rng.integers(0, 10**12, groups)[rng.integers(0, groups, n)]
    v = rng.integers(0, 1000, n)
    cl.copy_from(table, columns={"k": np.arange(n, dtype=np.int64),
                                 "g": g, "v": v})
    return g, v


def _assert_hash_mode(cl, sql):
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    plan = plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]))
    assert plan.group_mode.kind == "hash_host"


SQL = "SELECT g, count(*), sum(v), min(v), max(v) FROM t GROUP BY g"


@pytest.mark.parametrize("slots,groups", [
    (4096, 700),      # cardinality below the slot count
    (1024, 1024),     # at the slot count
    (1024, 3000),     # above: second-chance probes + spills engaged
])
def test_fused_matches_cpu_oracle_across_cardinalities(
        cl, one_device, slots, groups):
    _fill_groups(cl, 30_000, groups)
    cl.execute(f"SET citus.hash_agg_slots = {slots}")
    _assert_hash_mode(cl, SQL)
    fused = sorted(cl.execute(SQL).rows)
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    cpu = sorted(cl.execute(SQL).rows)
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    assert fused == cpu
    assert len(fused) == groups


def test_spill_heavy_adversarial_keyset_stays_exact(cl, one_device):
    """slots=64 against ~20000 groups: nearly every row loses both
    probes — the exact host spill path carries the query."""
    import collections
    g, v = _fill_groups(cl, 40_000, 20_000)
    cl.execute("SET citus.hash_agg_slots = 64")
    c0 = cl.counters.snapshot()
    got = sorted(cl.execute("SELECT g, count(*), sum(v) FROM t GROUP BY g").rows)
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "hash_spill_rows") > 0
    truth = collections.defaultdict(lambda: [0, 0])
    for gi, vi in zip(g.tolist(), v.tolist()):
        truth[gi][0] += 1
        truth[gi][1] += vi
    assert got == sorted((gi, c, s) for gi, (c, s) in truth.items())


def test_one_dispatch_per_batch_zero_merge_slots(cl, one_device):
    _fill_groups(cl, 20_000, 2000)
    GLOBAL_KERNELS.clear()
    GLOBAL_CACHE.clear()
    c0 = cl.counters.snapshot()
    r = cl.execute(SQL)
    c1 = cl.counters.snapshot()
    batches = len(r.explain["tasks"])
    assert batches >= 1
    # ONE fused dispatch per batch: insert AND merge ride together
    assert _delta(c0, c1, "hash_fused_dispatches") == batches
    assert r.explain["pipeline"]["fused_dispatches"] == batches
    slots = {k[1] for k in GLOBAL_KERNELS._e}
    assert "jit_hash_fused" in slots
    assert not any(s == "jit_hash_worker" or s.startswith("jit_table_merge")
                   for s in slots)
    assert len(r.rows) == 2000


def test_streaming_peak_window_bounded(cl, one_device):
    _fill_groups(cl, 30_000, 500, shards=4)
    old_cap = GLOBAL_CACHE.capacity
    GLOBAL_CACHE.clear()
    GLOBAL_CACHE.capacity = 1  # force the streaming path
    cl.execute("SET citus.executor_prefetch_depth = 1")
    cl.execute("SET citus.max_tasks_in_flight = 1")
    try:
        r = cl.execute(f"EXPLAIN ANALYZE {SQL}")
        text = "\n".join(l for (l,) in r.rows)
        m = re.search(r"stream window peak (\d+) bytes", text)
        h = re.search(r"H2D (\d+) bytes", text)
        d = re.search(r"fused dispatches (\d+)", text)
        assert m and h and d, text
        peak, h2d, nd = int(m.group(1)), int(h.group(1)), int(d.group(1))
        assert nd >= 2
        # with depth 1 the un-synced device window never holds more
        # than 2× one batch's bytes (table slots are accounted apart)
        assert peak <= 2 * (h2d / nd)
        assert GLOBAL_CACHE.memory_view()["live_bytes"] == 0
    finally:
        GLOBAL_CACHE.capacity = old_cap


def test_auto_slots_and_explain_hash_line(cl, one_device):
    _fill_groups(cl, 25_000, 900)
    # auto is the default; a SET of a number and back restores it
    assert cl.execute("SHOW citus.hash_agg_slots").rows == [("0",)]
    cl.execute("SET citus.hash_agg_slots = 2048")
    assert cl.execute("SHOW citus.hash_agg_slots").rows == [("2048",)]
    cl.execute("SET citus.hash_agg_slots = auto")
    assert cl.execute("SHOW citus.hash_agg_slots").rows == [("0",)]
    r = cl.execute(f"EXPLAIN ANALYZE {SQL}")
    text = "\n".join(l for (l,) in r.rows)
    m = re.search(r"hash slots (\d+), occupancy ([\d.]+)%, "
                  r"spilled (\d+) rows, groups (\d+), fetched (\d+) bytes",
                  text)
    assert m, text
    # auto: the next power of two at or above the catalog's row count
    # (at least 1024, at most what the device's free memory holds)
    assert int(m.group(1)) == 32_768
    assert 0.0 <= float(m.group(2)) <= 100.0
    assert int(m.group(4)) == 900


def test_float_keys_negative_zero_and_nan_group_once(cl, one_device):
    """-0.0 groups with 0.0 and every NaN is ONE group, exact vs the
    sqlite oracle (sqlite stores NaN as NULL: our NaN group maps to its
    NULL group) and byte-identical across backends."""
    import sqlite3
    cl.execute("CREATE TABLE f (k bigint NOT NULL, f double, v bigint)")
    cl.execute("SELECT create_distributed_table('f', 'k', 2)")
    base = [0.0, -0.0, float("nan"), 1.5, -1.5, float("nan"), 0.0, -0.0,
            2.5, float("-inf")]
    n = 4000
    fs = np.array([base[i % len(base)] for i in range(n)])
    vs = np.arange(n, dtype=np.int64) % 13
    cl.copy_from("f", columns={"k": np.arange(n, dtype=np.int64),
                               "f": fs, "v": vs})
    sql = "SELECT f, count(*), sum(v) FROM f GROUP BY f"
    # small slot table forces some rows through the host spill half too
    cl.execute("SET citus.hash_agg_slots = 1024")
    ours = cl.execute(sql).rows
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    cpu = cl.execute(sql).rows
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    assert sorted(map(repr, ours)) == sorted(map(repr, cpu))

    sq = sqlite3.connect(":memory:")
    sq.execute("CREATE TABLE f (f REAL, v INTEGER)")
    sq.executemany("INSERT INTO f VALUES (?,?)",
                   list(zip(fs.tolist(), vs.tolist())))

    def norm(rows):
        out = []
        for key, c, s in rows:
            if key is not None and isinstance(key, float):
                if math.isnan(key):
                    key = None      # sqlite renders NaN as NULL
                elif key == 0.0:
                    key = 0.0       # fold -0.0 representatives
            out.append((key, c, s))
        return sorted(out, key=repr)

    theirs = [tuple(r) for r in sq.execute(sql).fetchall()]
    assert norm(ours) == norm(theirs)
    # one row per distinct canonical key: 0.0/-0.0 merged, NaNs merged
    assert len(ours) == 6


def test_hash_groupby_rides_megabatch(cl, one_device):
    """hash_host families coalesce under `batched:jit_hash_fused`:
    concurrent literal variants return exactly their serial rows."""
    import threading
    _fill_groups(cl, 12_000, 800)
    queries = [f"SELECT g, count(*), sum(v) FROM t WHERE v < {900 + i} "
               "GROUP BY g ORDER BY g" for i in range(4)]
    serial = [cl.execute(q).rows for q in queries]
    cl.execute("SET citus.megabatch_window_ms = 50")
    cl.execute("SET citus.megabatch_max_size = 4")
    try:
        c0 = cl.counters.snapshot()
        got = [None] * len(queries)
        bar = threading.Barrier(len(queries))

        def run(i):
            bar.wait()
            got[i] = cl.execute(queries[i]).rows
        ts = [threading.Thread(target=run, args=(i,))
              for i in range(len(queries))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        c1 = cl.counters.snapshot()
        assert got == serial
        assert _delta(c0, c1, "megabatch_queries") >= 2
        assert "batched:jit_hash_fused" in {k[1] for k in GLOBAL_KERNELS._e}
    finally:
        cl.execute("SET citus.megabatch_window_ms = 0")


# ------------------------------- sort -> segment reduce -> chunked insert


def _keyed_table(cl, shape, n=6000):
    """A table ``s`` whose GROUP BY exercises one shape of the kernel's
    sort / segment-reduce / chunked-insert path -> its GROUP BY sql."""
    rng = np.random.default_rng(len(shape))
    k = np.arange(n, dtype=np.int64)
    g = rng.integers(0, 10**12, n)
    f = rng.integers(-50, 50, n) / 4.0
    v = rng.integers(-500, 500, n)
    x = rng.random(n) * 100 - 50
    cols, where = "g", ""
    if shape == "one_row_a_key":
        pass                                  # g: n distinct keys
    elif shape == "seven_rows_a_key":
        g = np.repeat(g[:-(-n // 7)], 7)[:n]
        rng.shuffle(g)
    elif shape == "all_distinct_sorted":
        g = np.sort(g)
    elif shape == "one_key_whole_batch":
        cols, f = "f", np.full(n, 2.5)        # a float key is never direct
    elif shape == "all_masked":
        where = "WHERE v * 0 = 1"             # no statistic refutes it
    elif shape == "null_keys":
        g = np.where(rng.random(n) < 0.3, None,
                     g[rng.integers(0, 40, n)]).tolist()
    elif shape == "float_keys_zero_nan":
        cols = "f"
        f = np.array([0.0, -0.0, float("nan"), 1.5, -1.5, float("inf"),
                      float("-inf")])[rng.integers(0, 7, n)]
    elif shape == "two_key_columns":
        cols = "g, f"
        g = g[rng.integers(0, 60, n)]
    else:
        raise AssertionError(shape)
    cl.execute("CREATE TABLE s (k bigint NOT NULL, g bigint, f double, "
               "v bigint, d decimal(12,2), x double)")
    cl.execute("SELECT create_distributed_table('s', 'k', 2)")
    cl.copy_from("s", columns={"k": k, "g": g, "f": f, "v": v,
                               "d": v / 100.0, "x": x})
    return (f"SELECT {cols}, count(*), sum(d), min(v), max(v), count(x), "
            f"sum(x), min(x) FROM s {where} GROUP BY {cols}")


def _rows_equal(a, b):
    """Rows equal, a float sum within a few ulps (the device adds a
    segment's floats as a tree, the host in row order)."""
    assert len(a) == len(b)
    for ra, rb in zip(sorted(a, key=repr), sorted(b, key=repr)):
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                assert (va == vb or (math.isnan(va) and math.isnan(vb))
                        or math.isclose(va, vb, rel_tol=1e-9, abs_tol=1e-9)), (ra, rb)
            else:
                assert va == vb, (ra, rb)


@pytest.mark.parametrize("slots", [0, 64])
@pytest.mark.parametrize("shape", [
    "one_row_a_key", "seven_rows_a_key", "all_distinct_sorted",
    "one_key_whole_batch", "all_masked", "null_keys",
    "float_keys_zero_nan", "two_key_columns"])
def test_sorted_segments_match_cpu_oracle(cl, one_device, shape, slots):
    """Every shape of a batch — one to thousands of rows a key, nothing
    but masked rows, NULL and float keys, two key columns — with count,
    decimal sum, min, max and a float sum beside each other, in a table
    that holds the keys (auto) and in one that spills most (64 slots)."""
    sql = _keyed_table(cl, shape)
    _assert_hash_mode(cl, sql)
    cl.execute(f"SET citus.hash_agg_slots = {slots}")
    c0 = cl.counters.snapshot()
    fused = cl.execute(sql).rows
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "hash_fused_dispatches") >= 2
    spilled = _delta(c0, c1, "hash_spill_rows")
    if slots and shape in ("one_row_a_key", "seven_rows_a_key"):
        assert spilled > 3000
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    cpu = cl.execute(sql).rows
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    _rows_equal(fused, cpu)
    if shape == "all_masked":
        assert fused == []


def test_fingerprint_collision_keeps_groups_apart(cl, one_device,
                                                  monkeypatch):
    """Every key given ONE fingerprint: the sort brings nothing together
    and all entries contend for the same two slots, yet segments are cut
    on the key values and a slot takes only its stored key, so no two
    groups merge — the rest spill and stay exact."""
    import collections
    from citus_tpu.ops import hash_agg
    g, v = _fill_groups(cl, 6000, 300, shards=2)
    monkeypatch.setattr(
        hash_agg, "_fingerprint",
        lambda xp, keys, shape: xp.full(shape, np.uint64(12345), np.uint64))
    GLOBAL_KERNELS.clear()
    try:
        c0 = cl.counters.snapshot()
        got = sorted(cl.execute(
            "SELECT g, count(*), sum(v), min(v) FROM t GROUP BY g").rows)
        c1 = cl.counters.snapshot()
    finally:
        GLOBAL_KERNELS.clear()   # no other test gets the patched kernel
    truth = collections.defaultdict(lambda: [0, 0, 10**9])
    for gi, vi in zip(g.tolist(), v.tolist()):
        t = truth[gi]
        t[0] += 1
        t[1] += vi
        t[2] = min(t[2], vi)
    assert got == sorted((gi, *t) for gi, t in truth.items())
    assert len(got) == 300
    # one key holds each of the two slots; every other row spilled
    assert _delta(c0, c1, "hash_spill_rows") > 5000


@pytest.mark.parametrize("by,shards", [("g", 4), ("k", 1)])
def test_table_updates_count_distinct_keys_of_each_batch(
        cl, one_device, by, shards):
    """``hash_table_updates`` is the sum over batches of the batch's
    distinct keys — with every group in one shard (distributed by the
    group key, as Q18's block is) or one batch, the groups — not its
    rows, and still one dispatch a batch."""
    groups, n = 1500, 20_000
    cl.execute("CREATE TABLE t (k bigint NOT NULL, g bigint NOT NULL, "
               "v bigint)")
    cl.execute(f"SELECT create_distributed_table('t', '{by}', {shards})")
    rng = np.random.default_rng(9)
    g = rng.integers(0, 10**12, groups)[rng.integers(0, groups, n)]
    cl.copy_from("t", columns={"k": np.arange(n, dtype=np.int64), "g": g,
                               "v": rng.integers(0, 1000, n)})
    distinct = len(set(g.tolist()))
    sql = "SELECT g, count(*), sum(v) FROM t GROUP BY g"
    _assert_hash_mode(cl, sql)
    c0 = cl.counters.snapshot()
    r = cl.execute(sql)
    c1 = cl.counters.snapshot()
    batches = len(r.explain["tasks"])
    assert batches == shards
    assert len(r.rows) == distinct
    assert _delta(c0, c1, "hash_fused_dispatches") == batches
    assert _delta(c0, c1, "hash_table_updates") == distinct
    assert r.explain["pipeline"]["hash_table_updates"] == distinct
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    assert f"table updates {distinct} ({n} rows)" in text, text
    # the benchmark's layer metric reads this counter by name
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "layer_metrics",
                           "hash_table_updates_per_query.json")) as fh:
        assert json.load(fh)["reader"]["counters"] == ["hash_table_updates"]


# ------------------------------------------------------- 2-host push


def _load_pair(a, n=20_000, groups=3000):
    a.execute("CREATE TABLE t (k bigint NOT NULL, g bigint, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    rng = np.random.default_rng(5)
    g = rng.integers(0, 10**12, groups)[rng.integers(0, groups, n)]
    v = rng.integers(0, 1000, n)
    a.copy_from("t", columns={"k": np.arange(n, dtype=np.int64),
                              "g": g, "v": v})
    GLOBAL_CACHE.clear()
    GLOBAL_COUNTERS.reset()
    return g, v


def test_push_hash_partials_byte_identical_to_pull(pair):
    """A cross-host hash_host GROUP BY ships hash-table partials
    (TASK_VERSION 3 "hash" tasks): remote_tasks_pushed rises, zero
    fallbacks, zero placement sync — and the rows are byte-identical
    to the pull path's."""
    a, b, na, nb = pair
    _load_pair(a)
    sql = ("SELECT g, count(*), sum(v), min(v), max(v) FROM t "
           "GROUP BY g ORDER BY g")
    _assert_hash_mode(a, sql)
    pushed = a.execute(sql).rows
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_tasks_pushed"] >= 1
    assert snap["remote_task_fallbacks"] == 0
    assert snap["hash_partials_pushed"] >= 1
    assert snap["placement_sync_bytes"] == 0
    a.execute("SET citus.remote_task_execution = pull")
    GLOBAL_CACHE.clear()
    c0 = GLOBAL_COUNTERS.snapshot()
    pulled = a.execute(sql).rows
    c1 = GLOBAL_COUNTERS.snapshot()
    a.execute("SET citus.remote_task_execution = auto")
    assert _delta(c0, c1, "remote_tasks_pushed") == 0
    assert pushed == pulled


def test_task_version_2_peer_falls_back_to_pull(pair, monkeypatch):
    """A peer that still speaks TASK_VERSION 2 rejects the "hash" task
    server-side; the coordinator counts the fallback and rescans the
    shard through the pull path — rows stay correct."""
    import collections
    from citus_tpu.executor import worker_tasks
    a, b, na, nb = pair
    g, v = _load_pair(a)
    real = worker_tasks.encode_task

    def stale(plan, params=((), ())):
        t = real(plan, params)
        return dict(t, v=2) if t is not None else None
    monkeypatch.setattr(worker_tasks, "encode_task", stale)
    got = sorted(a.execute("SELECT g, count(*), sum(v) FROM t GROUP BY g").rows)
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_task_fallbacks"] >= 1
    assert snap["hash_partials_pushed"] == 0
    truth = collections.defaultdict(lambda: [0, 0])
    for gi, vi in zip(g.tolist(), v.tolist()):
        truth[gi][0] += 1
        truth[gi][1] += vi
    assert got == sorted((gi, c, s) for gi, (c, s) in truth.items())
