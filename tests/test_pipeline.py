"""Pipelined adaptive executor (executor/pipeline.py): remote
execute_task RPCs fan out on threads with per-node slow-start windows
(adaptive_executor.c's connection ramp-up analog) and overlap the local
shard scan; a background decode worker feeds a bounded read-ahead queue
so host stripe decode overlaps device compute.

Timing assertions use fault-injected delays (testing/faults.py), so
they measure scheduling structure, not machine speed: an injected
per-item delay makes "overlapped" vs "serial" differ by integer
multiples of the delay, far above scheduler noise.
"""

import threading
import time

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.testing.faults import FAULTS


@pytest.fixture()
def pair(tmp_path):
    """Authority + one attached worker (two data dirs, one logical
    cluster) — half of a table's shards land on the remote host."""
    a = ct.Cluster(str(tmp_path / "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    a.register_node()
    b = ct.Cluster(str(tmp_path / "b"), data_port=0, hosted_nodes=set(),
                   coordinator=("127.0.0.1", a.control_port), n_nodes=0)
    b.register_node()
    a._maybe_reload_catalog(force_sync=True)
    yield a
    FAULTS.disarm()
    b.close()
    a.close()


@pytest.fixture()
def quad(tmp_path):
    """Authority + three attached workers: a 4-shard table puts one
    shard on each host, so one scan issues three remote RPCs."""
    a = ct.Cluster(str(tmp_path / "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    a.register_node()
    workers = []
    try:
        for name in ("b", "c", "d"):
            w = ct.Cluster(str(tmp_path / name), data_port=0,
                           hosted_nodes=set(), n_nodes=0,
                           coordinator=("127.0.0.1", a.control_port))
            w.register_node()
            workers.append(w)
        a._maybe_reload_catalog(force_sync=True)
        yield a
    finally:
        FAULTS.disarm()
        for w in workers:
            w.close()
        a.close()


def _load(cl, n=20000, shards=4, table="t"):
    cl.execute(f"CREATE TABLE {table} (k bigint NOT NULL, v bigint)")
    cl.execute(f"SELECT create_distributed_table('{table}', 'k', {shards})")
    cl.copy_from(table, columns={"k": np.arange(n),
                                 "v": np.arange(n) * 3})
    GLOBAL_CACHE.clear()
    GLOBAL_COUNTERS.reset()
    return n


def test_parallel_dispatch_wall_is_max_not_sum(quad):
    """Three remote tasks, each delayed 0.5 s at the worker: parallel
    fan-out costs ~one delay, sequential dispatch would cost three."""
    a = quad
    n = _load(a)
    assert sum(1 for s in a.catalog.table("t").shards
               if a.catalog.is_remote_node(s.placements[0])) == 3
    FAULTS.arm("execute_task", delay_s=0.5)
    t0 = time.perf_counter()
    r = a.execute("SELECT count(*), sum(v) FROM t")
    wall = time.perf_counter() - t0
    FAULTS.disarm()
    assert r.rows == [(n, 3 * n * (n - 1) // 2)]
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_tasks_pushed"] == 3
    assert snap["remote_task_fallbacks"] == 0
    assert snap["remote_tasks_inflight_peak"] == 3
    # serial dispatch would need >= 1.5 s of injected delay alone
    assert wall < 1.2, wall


def test_remote_wait_overlaps_local_scan(quad):
    """The local shard scan runs while remote RPCs are in flight: the
    overlapped-wait gauge reports nonzero hidden wait."""
    a = quad
    n = _load(a)
    FAULTS.arm("execute_task", delay_s=0.2)
    r = a.execute("SELECT count(*), sum(v) FROM t")
    FAULTS.disarm()
    assert r.rows == [(n, 3 * n * (n - 1) // 2)]
    pl = r.explain.get("pipeline") or {}
    assert pl.get("remote_inflight_peak") == 3, pl
    # blocked wait + wait hidden behind local work covers the 0.2 s
    # the RPCs were in flight, however the local scan happened to pace
    assert pl.get("remote_wait_ms", 0) + pl.get("remote_overlapped_ms", 0) \
        >= 150, pl


def test_inflight_peak_respects_pool_cap(pair):
    """citus.max_adaptive_executor_pool_size caps the per-node RPC
    window: with 4 remote tasks on one worker and a cap of 2, the
    in-flight high-water mark never exceeds 2."""
    a = pair
    n = _load(a, shards=8)
    a.execute("SET citus.max_adaptive_executor_pool_size = 2")
    assert a.execute(
        "SHOW citus.max_adaptive_executor_pool_size").rows == [("2",)]
    GLOBAL_CACHE.clear()
    FAULTS.arm("execute_task", delay_s=0.05)
    r = a.execute("SELECT count(*), sum(v) FROM t")
    FAULTS.disarm()
    assert r.rows == [(n, 3 * n * (n - 1) // 2)]
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_tasks_pushed"] == 4
    assert 1 <= snap["remote_tasks_inflight_peak"] <= 2, snap


def test_per_task_failure_falls_back_mid_flight(quad):
    """One of three parallel RPCs dies: only that task falls back to
    the pull path; the other two pushes stand and the answer is
    exact."""
    a = quad
    n = _load(a)
    FAULTS.arm("execute_task", error=RuntimeError("mid-flight loss"),
               times=1)
    r = a.execute("SELECT count(*), sum(v) FROM t")
    FAULTS.disarm()
    assert r.rows == [(n, 3 * n * (n - 1) // 2)]
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_tasks_pushed"] == 2
    assert snap["remote_task_fallbacks"] == 1


def test_collect_creates_o1_threads_under_wide_fanout(pair, monkeypatch):
    """64 remote tasks dispatch through ONE selector-driven event loop:
    the coordinator's collect path creates no per-RPC thread (the old
    citus-remote-task-* dispatch threads), and total thread creation
    during the query stays far below the fan-out width — O(1)
    dispatcher threads per coordinator, not O(tasks) per query."""
    a = pair
    n = _load(a, shards=128)
    started = []
    orig_start = threading.Thread.start

    def record(self):
        started.append(self.name)
        return orig_start(self)

    monkeypatch.setattr(threading.Thread, "start", record)
    try:
        r = a.execute("SELECT count(*), sum(v) FROM t")
    finally:
        monkeypatch.undo()
    assert r.rows == [(n, 3 * n * (n - 1) // 2)]
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["remote_tasks_pushed"] == 64, snap
    assert not [nm for nm in started if "citus-remote-task" in nm], started
    assert sum("citus-rpc-loop" in nm for nm in started) <= 1, started
    # the only other creations are the local scan's decode workers and
    # the WORKER-side per-connection server handlers (unnamed
    # "Thread-N (_serve_conn)" threads) — the latter bounded by the
    # pool cap, not the 64-task fan-out
    conns = [nm for nm in started
             if nm.startswith("Thread-") or "_serve_conn" in nm]
    others = [nm for nm in started
              if nm not in conns and "citus-host-decode" not in nm
              and "citus-rpc-loop" not in nm]
    assert not others, others
    assert len(conns) < 32, conns


def test_prefetch_overlaps_decode_with_device(tmp_cluster):
    """A/B on the mesh path with injected per-batch decode delay and
    per-round device delay: depth-2 read-ahead hides decode behind
    device rounds, so pipelined wall must land well under serial."""
    cl = tmp_cluster
    n = 20000
    cl.execute("CREATE TABLE ov (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('ov', 'k', 32)")
    cl.copy_from("ov", columns={"k": np.arange(n),
                                "v": np.arange(n) * 3})
    q = "SELECT count(*), sum(v) FROM ov"
    exp = [(n, 3 * n * (n - 1) // 2)]
    GLOBAL_CACHE.clear()
    assert cl.execute(q).rows == exp  # warmup: compile kernels uncached

    def measured(depth):
        cl.execute(f"SET citus.executor_prefetch_depth = {depth}")
        try:
            FAULTS.arm("decode_batch", delay_s=0.02, match="ov")
            FAULTS.arm("device_round", delay_s=0.16, match="ov")
            GLOBAL_CACHE.clear()
            t0 = time.perf_counter()
            r = cl.execute(q)
            wall = time.perf_counter() - t0
        finally:
            FAULTS.disarm()
        assert r.rows == exp  # depth changes timing, never results
        return wall

    serial = measured(0)
    piped = measured(2)
    assert piped < 0.75 * serial, (piped, serial)
    snap = GLOBAL_COUNTERS.snapshot()
    # with decode 8x faster than a device round, the host side stalls
    # (queue full / consumer busy), the device side does not starve
    assert snap["pipeline_host_stalls"] + snap["pipeline_device_stalls"] > 0


def test_prefetch_decode_error_propagates(tmp_cluster):
    """An exception on the background decode thread surfaces as the
    query's error (no hang, no partial answer) and the cluster keeps
    answering afterwards."""
    cl = tmp_cluster
    n = 20000
    cl.execute("CREATE TABLE pe (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('pe', 'k', 32)")
    cl.copy_from("pe", columns={"k": np.arange(n),
                                "v": np.arange(n) * 3})
    GLOBAL_CACHE.clear()
    FAULTS.arm("decode_batch", error=RuntimeError("stripe rot"),
               match="pe", after=2)
    try:
        with pytest.raises(Exception, match="stripe rot"):
            cl.execute("SELECT count(*), sum(v) FROM pe")
    finally:
        FAULTS.disarm()
    GLOBAL_CACHE.clear()
    assert cl.execute("SELECT count(*), sum(v) FROM pe").rows == \
        [(n, 3 * n * (n - 1) // 2)]


def test_depth_zero_matches_piped_results_all_paths(tmp_cluster):
    """Inline decode (depth 0) and pipelined decode produce identical
    rows for scalar agg, GROUP BY, and filtered projection — on both
    the mesh (32-shard) and single-device (1-shard) layouts."""
    cl = tmp_cluster
    n = 12000
    for table, shards in (("m1", 32), ("s1", 1)):
        cl.execute(f"CREATE TABLE {table} (k bigint NOT NULL, v bigint,"
                   f" c text)")
        cl.execute(
            f"SELECT create_distributed_table('{table}', 'k', {shards})")
        cl.copy_from(table, columns={
            "k": np.arange(n), "v": np.arange(n) * 3,
            "c": [f"w{i % 5}" for i in range(n)]})
    queries = [
        "SELECT count(*), sum(v), min(k), max(v) FROM {t}",
        "SELECT c, count(*), sum(v) FROM {t} GROUP BY c ORDER BY c",
        "SELECT k, v FROM {t} WHERE k < 40 ORDER BY k",
    ]
    for table in ("m1", "s1"):
        for q in queries:
            sql = q.format(t=table)
            rows = {}
            for depth in (0, 3):
                cl.execute(f"SET citus.executor_prefetch_depth = {depth}")
                GLOBAL_CACHE.clear()
                rows[depth] = cl.execute(sql).rows
            assert rows[0] == rows[3], sql


def test_explain_analyze_pipeline_lines(pair):
    """EXPLAIN ANALYZE renders the pipeline block (decode/device halves,
    stalls) and split rpc/decode timings per pushed task."""
    a = pair
    _load(a)
    GLOBAL_CACHE.clear()
    r = a.execute("EXPLAIN ANALYZE SELECT count(*), sum(v) FROM t")
    txt = "\n".join(row[0] for row in r.rows)
    assert "Pipeline: host decode" in txt, txt
    assert "ms rpc" in txt and "ms decode" in txt, txt
    assert "Remote Wait:" in txt and "peak in-flight" in txt, txt


def test_prefetch_depth_guc_roundtrip(tmp_cluster):
    cl = tmp_cluster
    assert cl.execute("SHOW citus.executor_prefetch_depth").rows == [("2",)]
    cl.execute("SET citus.executor_prefetch_depth = 0")
    assert cl.execute("SHOW citus.executor_prefetch_depth").rows == [("0",)]
    assert cl.execute(
        "SHOW citus.max_adaptive_executor_pool_size").rows == [("16",)]
    cl.execute("SET citus.max_tasks_in_flight = 4")
    assert cl.execute("SHOW citus.max_tasks_in_flight").rows == [("4",)]


# ------------------------------------------------ spans of the decode thread
#
# Structure and counts only (no duration, no ordering of two threads).


def _streaming_table(cl, name, n=20000, shards=8):
    cl.execute(f"CREATE TABLE {name} (k bigint NOT NULL, v bigint)")
    cl.execute(f"SELECT create_distributed_table('{name}', 'k', {shards})")
    cl.copy_from(name, columns={"k": np.arange(n), "v": np.arange(n) * 3})
    return [(n, 3 * n * (n - 1) // 2)]


@pytest.mark.parametrize("n_producers", [1, 3])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_streaming_scan_spans_cover_both_threads(tmp_cluster, limit_devices,
                                                 producers, n_dev,
                                                 n_producers):
    """A streaming scan's trace: decode_batch spans from the decode
    threads hang under the query's execute, each with one stripe_read
    and one pad (the batch is assembled once: no concat); every
    device_round holds h2d + narrow (v fits 32 bits: the lane convert of
    tests/test_scan_lanes.py) + dispatch; the stall
    the consumer sat in is a wait:prefetch_stall span from the seam.
    One producer: the ONE thread ``citus-host-decode``; several: the
    threads ``citus-host-decode-<n>``, each on a line of its own."""
    from citus_tpu.observability import trace as T
    limit_devices(n_dev)
    producers(n_producers)
    cl = tmp_cluster
    exp = _streaming_table(cl, "sp")
    q = "SELECT count(*), sum(v) FROM sp"
    assert cl.execute(q).rows == exp                 # compile
    cl.execute("SET citus.executor_prefetch_depth = 2")
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    FAULTS.arm("decode_batch", delay_s=0.02, match="sp")
    try:
        assert cl.execute(q).rows == exp
    finally:
        FAULTS.disarm()
    tr = T.last_trace()
    by_id = {s.span_id: s for s in tr.spans}
    assert [s for s in tr.spans if s.parent_id not in by_id] == [tr.root()]
    assert all(s.t1 is not None for s in tr.spans)
    root, ex = tr.root(), tr.find("execute")
    kids = {}
    for s in tr.spans:
        kids.setdefault(s.parent_id, []).append(s.name)
    batches = [s for s in tr.find_all("decode_batch")
               if not s.attrs.get("eof")]
    assert len(batches) == 8                         # one per shard
    # and every shard's last pull, which finds it exhausted
    assert len(tr.find_all("decode_batch")) == 16
    for s in tr.find_all("decode_batch"):
        assert s.parent_id == ex.span_id
    for s in batches:
        assert kids[s.span_id] == ["stripe_read", "pad"]
        assert s.attrs["rows"] > 0 and s.attrs["bytes"] > 0
    other = [s for s in batches if s.tid != root.tid]
    # the mesh loop peeks two batches on its own thread before it
    # starts the decode thread; the single-device loop none
    assert len(other) == (8 if n_dev == 1 else 6)
    names = {s.attrs["thread"] for s in other}
    if n_producers == 1:
        assert names == {"citus-host-decode"}
    else:
        assert 2 <= len(names) <= n_producers and names <= {
            f"citus-host-decode-{k + 1}" for k in range(n_producers)}
        pl = ex.attrs["pipeline"]
        assert pl["decode_streams"] == len(names)
    # a thread, a line: every span of a batch on its batch's
    assert len({s.tid for s in other}) == len(names)
    for s in tr.spans:
        if by_id.get(s.parent_id) in batches:
            assert s.tid == by_id[s.parent_id].tid
    rounds = tr.find_all("device_round")
    assert len(rounds) == (8 if n_dev == 1 else 2)
    for r in rounds:
        assert r.parent_id == ex.span_id and r.attrs["resident"] is False
        assert kids[r.span_id][:4] == (
            ["h2d", "narrow", "dispatch"] if n_dev == 1 else
            ["stack", "h2d", "narrow", "dispatch"])[:4]
    stalls = tr.find_all("wait:prefetch_stall")
    assert stalls and all(s.parent_id == ex.span_id and s.tid == root.tid
                          for s in stalls)
    assert cl.counters.snapshot()["wait_prefetch_stall_ms"] > 0


def test_decode_thread_spans_are_closed_when_the_consumer_dies(
        tmp_cluster, limit_devices):
    """No span is held open across a generator's yield: when the
    consumer fails mid-scan and HostPrefetcher.close() has returned,
    every span the decode thread opened is closed, and the decode
    thread's next query starts from an empty span stack."""
    from citus_tpu.observability import trace as T
    limit_devices(1)
    cl = tmp_cluster
    exp = _streaming_table(cl, "sd")
    q = "SELECT count(*), sum(v) FROM sd"
    assert cl.execute(q).rows == exp
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    FAULTS.arm("device_round", error=RuntimeError("chip fell off"),
               match="sd", after=2)
    try:
        with pytest.raises(Exception, match="chip fell off"):
            cl.execute(q)
    finally:
        FAULTS.disarm()
    tr = T.last_trace()
    assert tr.find("decode_batch") is not None
    assert all(s.t1 is not None for s in tr.spans), \
        [s.name for s in tr.spans if s.t1 is None]
    assert tr.root().t1 == max(s.t1 for s in tr.spans)
    assert T.current() is None
    GLOBAL_CACHE.clear()
    assert cl.execute(q).rows == exp
    ids = {s.span_id for s in T.last_trace().spans}
    assert all(s.parent_id in ids for s in T.last_trace().spans
               if s.parent_id is not None)


def test_inline_decode_has_the_same_spans_on_the_callers_thread(
        tmp_cluster, limit_devices):
    from citus_tpu.observability import trace as T
    limit_devices(1)
    cl = tmp_cluster
    exp = _streaming_table(cl, "si")
    cl.execute("SET citus.executor_prefetch_depth = 0")
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    assert cl.execute("SELECT count(*), sum(v) FROM si").rows == exp
    tr = T.last_trace()
    batches = [s for s in tr.find_all("decode_batch")
               if not s.attrs.get("eof")]
    assert len(batches) == 8
    assert {s.tid for s in tr.spans} == {tr.root().tid}
    assert not tr.find_all("wait:prefetch_stall")


# --------------------------------- the decode thread's work, named from inside
#
# stripe_read's children (shard_open, footer_read, batch_layout,
# native_decode, stripe_fallback) and the producer's own wait
# (wait:prefetch_full).

STRIPE_READ_CHILDREN = {"shard_open", "footer_read", "batch_layout",
                        "native_decode", "stripe_fallback"}


def _mixed_table(cl, name, n=20000, shards=8):
    """k and v land in place; w (int, read as int64 on the device) casts
    and so takes the stripe reader in every batch."""
    cl.execute(f"CREATE TABLE {name} (k bigint NOT NULL, v bigint, w int)")
    cl.execute(f"SELECT create_distributed_table('{name}', 'k', {shards})")
    cl.copy_from(name, columns={"k": np.arange(n), "v": np.arange(n) * 3,
                                "w": np.arange(n) % 7})
    return [(n, n * (n - 1) // 2, 3 * n * (n - 1) // 2,
             int((np.arange(n) % 7).sum()))]


def _traced_stream(cl, q, exp):
    """Run ``q`` streaming under a sampled trace -> the trace."""
    from citus_tpu.observability import trace as T
    assert cl.execute(q).rows == exp                 # compile
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    assert cl.execute(q).rows == exp
    return T.last_trace()


def test_stripe_read_names_its_parts_on_the_decode_thread(tmp_cluster,
                                                          limit_devices):
    limit_devices(1)
    cl = tmp_cluster
    exp = _mixed_table(cl, "nm")
    tr = _traced_stream(cl, "SELECT count(*), sum(k), sum(v), sum(w) FROM nm",
                        exp)
    by_id = {s.span_id: s for s in tr.spans}
    reads = tr.find_all("stripe_read")
    assert len(reads) == 8        # one batch a shard
    # on the decode threads (how many: the cores decide), none on the caller's
    decode_tids = {s.tid for s in reads}
    assert decode_tids and tr.root().tid not in decode_tids
    for read in reads:
        assert by_id[read.parent_id].name == "decode_batch"
        kids = [s for s in tr.spans if s.parent_id == read.span_id]
        assert [k.name for k in kids] == [
            "shard_open", "footer_read", "batch_layout", "native_decode",
            "stripe_fallback"]
        for k in kids:
            # on the decode thread, closed, inside the parent: nothing
            # was held open across the generators' yields
            assert k.tid == read.tid and k.t1 is not None
            assert read.t0 <= k.t0 <= k.t1 <= read.t1
            assert not [s for s in tr.spans if s.parent_id == k.span_id]
        assert sum(k.duration_ms for k in kids) <= read.duration_ms
        opened, foot, lay, nat, slow = kids
        assert opened.attrs["stripes"] == 1
        # the compile run before the trace parsed every footer
        assert foot.attrs == {"chunks": foot.attrs["chunks"], "deletes": False,
                              "selected": foot.attrs["chunks"],
                              "cached": True}
        assert lay.attrs["streams"] == nat.attrs["streams"] == \
            2 * foot.attrs["chunks"]
        assert lay.attrs["files"] == nat.attrs["files"] == 1
        assert lay.attrs["bytes_alloc"] >= 3 * 8 * read.attrs["rows"]
        assert nat.attrs["bytes_raw"] == 16 * read.attrs["rows"]
        assert 0 < nat.attrs["bytes_comp"]
        assert 1 <= nat.attrs["threads"] <= 16
        assert nat.attrs["read_ms"] + nat.attrs["decompress_ms"] > 0
        assert nat.attrs["busy_max_ms"] > 0
        assert slow.attrs == {"chunks": foot.attrs["chunks"], "reason": "cast",
                              "bytes": 4 * read.attrs["rows"]}
    pads = tr.find_all("pad")
    assert len(pads) == 8
    assert {s.name for s in tr.spans
            if by_id.get(s.parent_id) in reads} == STRIPE_READ_CHILDREN


# id, the table's DDL tail / what is done to it, the statement, the reason
FALLBACKS = [
    ("deletes", "v bigint", "DELETE FROM {t} WHERE k % 1000 = 3", "deletes"),
    ("nulls", "v bigint", None, "nulls"),
    ("cast", "v int", None, "cast"),
    ("late_column", "v bigint", "ALTER", "late_column"),
]


@pytest.mark.parametrize("case", FALLBACKS, ids=[c[0] for c in FALLBACKS])
def test_stripe_fallback_says_why(tmp_cluster, limit_devices, case):
    """What cannot land in place takes the stripe reader under a
    stripe_fallback span that names the reason; a clean column of the
    same batch still goes through native_decode."""
    name, column, action, reason = case
    limit_devices(1)
    cl = tmp_cluster
    n, t = 8000, "fb_" + name
    cl.execute(f"CREATE TABLE {t} (k bigint NOT NULL, {column})")
    cl.execute(f"SELECT create_distributed_table('{t}', 'k', 2)")
    v = np.arange(n) * 3
    keep = np.ones(n, bool)
    if name == "nulls":
        cl.copy_from(t, rows=[(int(k), None if k % 50 == 0 else int(k) * 3)
                              for k in range(n)])
        v = np.where(np.arange(n) % 50 == 0, 0, v)
    else:
        cl.copy_from(t, columns={"k": np.arange(n), "v": v})
    extra, none = "", ()
    if action == "ALTER":
        cl.execute(f"ALTER TABLE {t} ADD COLUMN x bigint")
        extra, none = ", count(x)", (0,)
    elif action:
        cl.execute(action.format(t=t))
        keep = np.arange(n) % 1000 != 3
    q = f"SELECT count(*), sum(k), sum(v){extra} FROM {t}"
    exp = [(int(keep.sum()), int(np.arange(n)[keep].sum()),
            int(v[keep].sum())) + none]
    tr = _traced_stream(cl, q, exp)
    slow = tr.find_all("stripe_fallback")
    assert slow and {s.attrs["reason"] for s in slow} == {reason}
    assert all(s.attrs["chunks"] > 0 and s.attrs["bytes"] > 0 for s in slow)
    foots = tr.find_all("footer_read")
    assert {f.attrs["deletes"] for f in foots} == {name == "deletes"}
    if name == "deletes":
        assert not tr.find_all("native_decode")     # the whole stripe copies
    else:
        assert len(tr.find_all("native_decode")) == len(slow)


def test_a_cut_chunk_is_a_stripe_fallback_of_its_own(tmp_path, monkeypatch,
                                                     limit_devices):
    """The chunk a batch cut falls in is decoded alone, under
    stripe_fallback(reason=cut), inside the stripe_read that met it."""
    import functools
    from citus_tpu.config import ColumnarSettings, Settings
    from citus_tpu.executor import executor as ex
    from citus_tpu.executor.batches import load_padded_batches
    limit_devices(1)
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=128,
                                  stripe_row_limit=256)))
    try:
        cl.execute("CREATE TABLE cu (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('cu', 'k', 1)")
        n = 900
        cl.copy_from("cu", columns={"k": np.arange(n), "v": np.arange(n)})
        monkeypatch.setattr(ex, "load_padded_batches", functools.partial(
            load_padded_batches, max_batch_rows=200))
        cl.execute("SET citus.executor_min_batch_rows = 16")
        tr = _traced_stream(cl, "SELECT count(*), sum(v) FROM cu",
                            [(n, n * (n - 1) // 2)])
        cuts = [s for s in tr.find_all("stripe_fallback")
                if s.attrs["reason"] == "cut"]
        assert cuts and len(cuts) == len(tr.find_all("stripe_fallback"))
        by_id = {s.span_id: s for s in tr.spans}
        for s in cuts:
            assert by_id[s.parent_id].name == "stripe_read"
            assert s.attrs["chunks"] == 1 and s.attrs["bytes"] > 0
    finally:
        GLOBAL_CACHE.clear()
        cl.close()


@pytest.mark.parametrize("n_producers", [1, 3])
def test_a_slow_consumer_shows_as_prefetch_full_on_the_decode_thread(
        tmp_cluster, limit_devices, producers, n_producers):
    """Backpressure: the device is behind, the decode side holds a
    batch — a wait:prefetch_full span from the seam, on the thread that
    hands the batches on (with one producer: beside its decode_batch
    spans), booked into wait_prefetch_full_ms.  ONE span an interval
    however many producers the consumer holds back: the intervals
    never overlap, and their sum is wall time."""
    from citus_tpu.observability import trace as T
    limit_devices(1)
    producers(n_producers)
    cl = tmp_cluster
    exp = _streaming_table(cl, "pf")
    q = "SELECT count(*), sum(v) FROM pf"
    assert cl.execute(q).rows == exp
    cl.execute("SET citus.executor_prefetch_depth = 1")
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    before = cl.counters.snapshot().get("wait_prefetch_full_ms", 0)
    FAULTS.arm("device_round", delay_s=0.05, match="pf")
    try:
        assert cl.execute(q).rows == exp
    finally:
        FAULTS.disarm()
    tr = T.last_trace()
    ex = tr.find("execute")
    full = sorted(tr.find_all("wait:prefetch_full"), key=lambda s: s.t0)
    batches = tr.find_all("decode_batch")
    batch_tids = {s.tid for s in batches}
    assert full and len({s.tid for s in full}) == 1
    assert all(s.parent_id == ex.span_id and s.t1 is not None for s in full)
    assert full[0].tid != tr.root().tid
    if n_producers == 1:
        assert batch_tids == {full[0].tid}
        # it waits between batches, never inside one
        assert not [(w, b) for w in full for b in batches
                    if w.t0 < b.t1 and b.t0 < w.t1]
    else:
        assert len(batch_tids) >= 2 and full[0].tid not in batch_tids
    # one span an interval: at most one a batch handed on, none beside
    # another
    assert len(full) <= 8
    assert all(a.t1 <= b.t0 for a, b in zip(full, full[1:]))
    assert sum(s.duration_ms for s in full) >= 100     # of 8 rounds x 50 ms
    assert sum(s.duration_ms for s in full) <= ex.duration_ms
    assert cl.counters.snapshot()["wait_prefetch_full_ms"] - before >= 100


def test_an_unsampled_stream_asks_the_pool_nothing(tmp_cluster, limit_devices,
                                                   monkeypatch):
    """Tracing off: no Span is made and the native call gets NULL for
    its stats array (it then reads no clock); tracing on: it gets one."""
    import citus_tpu.native as nat
    from citus_tpu.observability import trace as T
    limit_devices(1)
    cl = tmp_cluster
    exp = _streaming_table(cl, "us")
    q = "SELECT count(*), sum(v) FROM us"
    assert cl.execute(q).rows == exp
    lib, asked = nat.get_lib(), []

    class Spy:
        def __getattr__(self, name):
            return getattr(lib, name)

        def ct_decode_batch(self, *args):
            asked.append(args[-1])
            return lib.ct_decode_batch(*args)

    spy = Spy()
    monkeypatch.setattr(nat, "get_lib", lambda: spy)
    cl.execute("SET citus.trace_sample_rate = 0")
    GLOBAL_CACHE.clear()
    before = T.span_allocations()
    assert cl.execute(q).rows == exp
    assert T.span_allocations() == before
    assert len(asked) == 8 and all(a is None for a in asked)
    del asked[:]
    cl.execute("SET citus.trace_sample_rate = 1.0")
    GLOBAL_CACHE.clear()
    assert cl.execute(q).rows == exp
    assert len(asked) == 8 and all(a is not None for a in asked)


@pytest.mark.parametrize("n_producers", [1, 3])
def test_explain_analyze_splits_the_decode(tmp_cluster, limit_devices,
                                           producers, n_producers):
    """The pipeline line reads the same spans: footers (all of them
    served by the footer cache: the statement ran once before), layout,
    the native call with its pool's busy share, the fallback, the time
    the decode side was blocked, and how many streams were decoded at
    once for how long."""
    import re
    limit_devices(1)
    producers(n_producers)
    cl = tmp_cluster
    exp = _mixed_table(cl, "ea")
    q = "SELECT count(*), sum(k), sum(v), sum(w) FROM ea"
    assert cl.execute(q).rows == exp
    GLOBAL_CACHE.clear()
    FAULTS.arm("decode_batch", delay_s=0.02, match="ea")
    try:
        text = "\n".join(r[0] for r in cl.execute("EXPLAIN ANALYZE " + q).rows)
    finally:
        FAULTS.disarm()
    (line,) = [ln for ln in text.splitlines() if "Pipeline:" in ln]
    m = re.search(
        r"decoded in place 0\.800, decode: footers (\d+\.\d\d) ms "
        r"\((\d+) of (\d+) cached\), "
        r"layout (\d+\.\d\d) ms, native (\d+\.\d\d) ms \(pool (\d+) % busy\), "
        r"fallback (\d+\.\d\d) ms, blocked (\d+\.\d\d) ms, "
        r"(\d+) streams at once, (\d+) ms overlapped, fused dispatches 8",
        line)
    assert m, line
    (footers, hits, stripes, layout, native, pool, fallback,
     _blocked, streams, overlapped) = map(float, m.groups())
    assert min(footers, layout, native, fallback) > 0 and 0 < pool <= 100
    assert hits == stripes > 0
    if n_producers == 1:
        assert (streams, overlapped) == (1, 0)
    else:
        # eight batches of 20 ms each by two or three threads
        assert 2 <= streams <= 3 and overlapped > 0
    # a resident scan decodes nothing: nothing to split
    again = "\n".join(r[0] for r in cl.execute("EXPLAIN ANALYZE " + q).rows)
    assert "decode:" not in again
