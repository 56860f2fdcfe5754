"""End-to-end distributed tracing (citus_tpu/observability/): span-tree
shape, cross-RPC trace_id propagation over a 2-host in-process cluster,
the allocation-free unsampled hot path, slow-query force-capture, the
Chrome-trace / Prometheus exporters, and the live-phase activity view.
"""

import json
import os

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.observability import trace as T
from citus_tpu.observability.slowlog import GLOBAL_SLOW_LOG


@pytest.fixture()
def cl(tmp_path):
    c = ct.Cluster(str(tmp_path / "db"))
    c.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    c.execute("SELECT create_distributed_table('t', 'k', 4)")
    c.copy_from("t", columns={"k": np.arange(2000),
                              "v": np.arange(2000) * 2})
    yield c
    c.close()


@pytest.fixture()
def pair(tmp_path):
    """Two coordinators, one logical cluster (same shape as the
    worker-tasks fixture): A hosts node 0, B attaches and hosts 1."""
    a = ct.Cluster(str(tmp_path / "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    a.register_node()
    b = ct.Cluster(str(tmp_path / "b"), data_port=0, hosted_nodes=set(),
                   coordinator=("127.0.0.1", a.control_port), n_nodes=0)
    b.register_node()
    a._maybe_reload_catalog(force_sync=True)
    yield a, b
    b.close()
    a.close()


# ------------------------------------------------------ tree correctness


def test_span_tree_single_rooted_no_orphans(cl):
    cl.execute("SET citus.trace_sample_rate = 1.0")
    cl.execute("SELECT count(*), sum(v) FROM t WHERE v < 3000")
    tr = T.last_trace()
    assert tr is not None
    root = tr.root()
    assert root is not None and root.name == "query"
    ids = {s.span_id for s in tr.spans}
    roots = [s for s in tr.spans
             if s.parent_id is None or s.parent_id not in ids]
    assert roots == [root], [s.name for s in roots]
    # the canonical phases hang off the tree
    names = {s.name for s in tr.spans}
    assert {"parse", "plan", "execute", "finalize"} <= names, names
    # every span closed, durations folded into counters
    assert all(s.t1 is not None for s in tr.spans)
    snap = cl.counters.snapshot()
    assert snap["trace_queries_sampled"] >= 1
    assert snap["trace_spans_recorded"] >= len(tr.spans)


def test_plan_span_reports_cache_hit(cl):
    cl.execute("SET citus.trace_sample_rate = 1.0")
    cl.execute("SELECT sum(v) FROM t WHERE v < 100")
    cl.execute("SELECT sum(v) FROM t WHERE v < 100")
    tr = T.last_trace()
    ps = tr.find("plan")
    assert ps is not None and ps.attrs.get("cache_hit") is True


def test_unsampled_path_is_allocation_free(cl):
    cl.execute("SET citus.trace_sample_rate = 0")
    cl.execute("SELECT count(*) FROM t")  # settle caches/compiles
    before = T.span_allocations()
    cl.execute("SELECT count(*) FROM t WHERE k = 7")
    cl.execute("SELECT sum(v) FROM t")
    assert T.span_allocations() == before


def test_sample_rate_validation(cl):
    from citus_tpu.errors import CatalogError
    with pytest.raises(CatalogError):
        cl.execute("SET citus.trace_sample_rate = 1.5")


# ------------------------------------------------------------ slow log


def test_slow_log_force_captures_at_threshold(cl):
    GLOBAL_SLOW_LOG.clear()
    cl.execute("SET citus.trace_sample_rate = 0")
    cl.execute("SET citus.log_min_duration_ms = 0")
    cl.execute("SELECT count(*) FROM t")
    assert len(GLOBAL_SLOW_LOG) >= 1
    ts, dur_ms, trace_id, phases, sql = GLOBAL_SLOW_LOG.rows_view()[0]
    assert "count(*)" in sql and dur_ms >= 0
    assert "execute=" in phases  # per-phase breakdown from the tree
    # threshold off -> no further capture
    GLOBAL_SLOW_LOG.clear()
    cl.execute("SET citus.log_min_duration_ms = -1")
    cl.execute("SELECT count(*) FROM t")
    assert len(GLOBAL_SLOW_LOG) == 0
    # a high threshold watches but does not capture fast queries
    cl.execute("SET citus.log_min_duration_ms = 60000")
    cl.execute("SELECT count(*) FROM t")
    assert len(GLOBAL_SLOW_LOG) == 0
    r = cl.execute("SELECT citus_slow_queries()")
    assert r.columns[1] == "duration_ms"


# ------------------------------------------------------- cross-host RPC


def test_remote_spans_share_trace_id_and_nest(pair, tmp_path):
    """The acceptance criterion: a sampled multi-shard aggregate over a
    2-host cluster exports ONE Chrome trace whose remote execute_task
    spans nest under the coordinator's query span, sharing trace_id."""
    a, b = pair
    n = 8000
    a.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    a.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n)})
    export = tmp_path / "traces"
    sql = "SELECT count(*), sum(v) FROM t"
    # A worker's first task compiles its kernels inside the RPC, and on
    # a loaded machine (the suite under several workers, cold compile
    # caches) that can outlast the RPC's time limit: the push then
    # falls back to a local scan -- the right answer, and no worker
    # span to graft.  What the program guarantees, and what is asserted
    # below, is the tree of a push that SUCCEEDED: warm the statement's
    # kernels on both hosts until a run pushes without a fallback.
    for _ in range(6):
        before = a.counters.snapshot()["remote_task_fallbacks"]
        assert a.execute(sql).rows == [(n, n * (n - 1) // 2)]
        if a.counters.snapshot()["remote_task_fallbacks"] == before:
            break
    a.execute("SET citus.trace_sample_rate = 1.0")
    a.execute(f"SET citus.trace_export_dir = '{export}'")
    r = a.execute(sql)
    assert r.rows == [(n, n * (n - 1) // 2)]
    tr = T.last_trace()
    root = tr.root()
    assert root.name == "query"
    rtasks = tr.find_all("remote_task")
    assert rtasks, [s.name for s in tr.spans]
    by_id = {s.span_id: s for s in tr.spans}
    # worker-recorded execute_task spans were grafted under remote_task
    # spans of the SAME trace (single tree, one trace_id)
    wspans = tr.find_all("execute_task")
    assert wspans, [s.name for s in tr.spans]
    for w in wspans:
        anchor = by_id[w.parent_id]
        assert anchor.name == "remote_task"
        # ancestry chains to the coordinator's query root
        cur = anchor
        while cur.parent_id is not None:
            cur = by_id[cur.parent_id]
        assert cur is root
        # grafted times are re-anchored inside the RPC window
        assert anchor.t0 <= w.t0 and w.t1 <= anchor.t1 + 1e-6
    # worker body spans came along too
    assert tr.find("worker_scan") is not None
    # exported Chrome trace: one file for this query, loadable JSON
    files = [f for f in os.listdir(export) if f.endswith(".json")]
    assert f"trace_{tr.trace_id}.json" in files
    doc = json.load(open(export / f"trace_{tr.trace_id}.json"))
    assert doc["otherData"]["trace_id"] == tr.trace_id
    evts = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in evts}
    assert {"query", "remote_task", "execute_task"} <= names
    # remote worker spans render on a different pid row than the
    # coordinator's
    pids = {e["pid"] for e in evts if e["name"] == "execute_task"}
    assert pids and 1 not in pids


def test_a_push_that_fails_falls_back_and_grafts_nothing(pair):
    """The other half of what the program guarantees: a pushed task
    that fails (here: the worker raises; on a loaded machine: the RPC
    outlasts its time limit while the worker compiles) rescans locally,
    the answer is right, its remote_task span says ok=False and no
    worker span hangs under it; the next push grafts again."""
    from citus_tpu.testing.faults import FAULTS
    a, b = pair
    n = 4000
    a.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    a.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n)})
    a.execute("SET citus.trace_sample_rate = 1.0")
    sql = "SELECT count(*), sum(v) FROM t"
    FAULTS.arm("execute_task", error=RuntimeError("worker down"))
    try:
        before = a.counters.snapshot()["remote_task_fallbacks"]
        assert a.execute(sql).rows == [(n, n * (n - 1) // 2)]
        tr = T.last_trace()
    finally:
        FAULTS.disarm()
    rtasks = tr.find_all("remote_task")
    assert rtasks and not any(s.attrs["ok"] for s in rtasks)
    assert a.counters.snapshot()["remote_task_fallbacks"] - before \
        == len(rtasks)
    assert tr.find_all("execute_task") == []
    assert a.execute(sql).rows == [(n, n * (n - 1) // 2)]
    tr = T.last_trace()
    assert all(s.attrs["ok"] for s in tr.find_all("remote_task"))
    assert len(tr.find_all("execute_task")) == len(rtasks)


def test_explain_analyze_renders_from_span_tree(pair):
    a, b = pair
    n = 4000
    a.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    a.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n)})
    a.execute("SET citus.trace_sample_rate = 0")  # forced trace anyway
    r = a.execute("EXPLAIN ANALYZE SELECT count(*) FROM t")
    txt = "\n".join(row[0] for row in r.rows)
    assert "Plan Cache:" in txt and "Elapsed:" in txt
    assert "Remote Tasks:" in txt and "pushed to node" in txt, txt
    # the lines came from the forced trace's tree
    tr = T.last_trace()
    assert tr.find("remote_task") is not None
    assert "forced" in tr.reasons


# ----------------------------------------------------------- exporters


def test_prometheus_text_exposition(cl):
    cl.execute("SELECT count(*) FROM t")
    r = cl.execute("SHOW citus.metrics")
    txt = "\n".join(row[0] for row in r.rows)
    assert "# TYPE citus_queries_executed_total counter" in txt
    assert "# HELP citus_queries_executed_total" in txt
    assert "citus_plan_cache_entries" in txt
    assert "citus_query_latency_ms_bucket" in txt
    assert 'le="+Inf"' in txt
    assert "citus_query_latency_ms_count" in txt
    # SQL-function spelling returns the same payload
    r2 = cl.execute("SELECT citus_metrics()")
    assert "\n".join(row[0] for row in r2.rows).splitlines()[0] \
        == txt.splitlines()[0]


def test_activity_reports_phase(cl):
    """ActivityTracker rows carry the live phase (and a wait_event
    column after it); a finished query leaves no rows, so drive the
    tracker directly."""
    gpid = cl.activity.enter("SELECT 1")
    T.push_phase_sink(lambda ph, _g=gpid: cl.activity.set_phase(_g, ph))
    try:
        T.set_phase("remote-wait")
        r = cl.execute("SELECT citus_stat_activity()")
        mine = [dict(zip(r.columns, row)) for row in r.rows
                if row[0] == gpid]
        assert mine and mine[0]["phase"] == "remote-wait"
        assert mine[0]["wait_event"] == ""
    finally:
        T.pop_phase_sink()
        cl.activity.exit(gpid)


def test_two_pc_spans_on_cross_host_write(pair):
    a, b = pair
    n = 1000
    a.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    a.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n)})
    a.execute("SET citus.trace_sample_rate = 1.0")
    a.execute("UPDATE t SET v = v + 1 WHERE v >= 0")
    # the multi-host modify recorded its 2PC phases in SOME sampled
    # trace this statement produced
    tr = T.last_trace()
    names = {s.name for s in tr.spans}
    assert "2pc_prepare" in names and "2pc_commit_point" in names, names
    assert "2pc_decide" in names, names


# ------------------------------------------- spans at every layer boundary
#
# Structure and counts only: no test here asserts a duration, a share or
# an ordering of two threads.


@pytest.fixture()
def cl8(tmp_path):
    """Eight shards with rows in each: 8 cached batches on one device,
    2 cached rounds on a mesh of 4."""
    c = ct.Cluster(str(tmp_path / "db8"))
    c.execute("CREATE TABLE t (k bigint NOT NULL, v bigint, c text)")
    c.execute("SELECT create_distributed_table('t', 'k', 8)")
    c.copy_from("t", columns={"k": np.arange(4000),
                              "v": np.arange(4000) * 2,
                              "c": ["x", "y", "z", "x"] * 1000})
    yield c
    c.close()


def tree(tr):
    """-> (by_id, children-by-parent-id), after checking that the trace
    has one root and no orphan and that every span is closed."""
    by_id = {s.span_id: s for s in tr.spans}
    roots = [s for s in tr.spans if s.parent_id not in by_id]
    assert roots == [tr.root()] and roots[0].name == "query", \
        [s.name for s in roots]
    assert all(s.t1 is not None for s in tr.spans)
    kids = {}
    for s in tr.spans:
        kids.setdefault(s.parent_id, []).append(s)
    return by_id, kids


@pytest.mark.parametrize("n_dev", [1, 4])
def test_resident_loops_have_a_span_per_round_and_a_named_tail(
        cl8, limit_devices, n_dev):
    limit_devices(n_dev)
    cl8.execute("SET citus.trace_sample_rate = 1.0")
    q = "SELECT c, count(*), sum(v) FROM t WHERE v < 6000 GROUP BY c"
    first = cl8.execute(q)
    hits = cl8.counters.snapshot()["device_cache_hits"]
    assert sorted(cl8.execute(q).rows) == sorted(first.rows)
    assert cl8.counters.snapshot()["device_cache_hits"] == hits + 1
    tr = T.last_trace()
    by_id, kids = tree(tr)
    ex = tr.find("execute")
    under_execute = [s.name for s in kids[ex.span_id]]
    rounds = tr.find_all("device_round")
    # one per cached batch on one device, one per cached round on the mesh
    assert len(rounds) == {1: 8, 4: 2}[n_dev]
    for r in rounds:
        assert r.parent_id == ex.span_id and r.attrs["resident"] is True
        assert [k.name for k in kids[r.span_id]] == ["dispatch"]
        slot = kids[r.span_id][0].attrs["slot"]
        assert slot == ("jit_fused" if n_dev == 1 else "mesh_run")
    tail = under_execute[under_execute.index("device_round") + len(rounds):]
    # every loop ends alike: the rounds fold into an accumulator on the
    # device (the mesh too, since PR 26), so one wait, one fetch of it,
    # and nothing for the host to combine
    assert tail[:2] == ["wait:device_round", "fetch"], under_execute
    assert under_execute.count("fetch") == 1
    assert "combine" not in under_execute
    for name in ("finalize_groups", "finalize", "book_stats", "admission",
                 "cache_lookup", "remote_dispatch", "init_acc"):
        assert name in under_execute, (name, under_execute)
    lookups = [s.attrs for s in tr.find_all("cache_lookup")]
    assert {"hit": True, "mesh": n_dev == 4} in lookups
    assert tr.find("finalize_groups").attrs["groups"] == 3
    assert by_id[tr.find("bind_params").parent_id].name == "query"


def test_root_closes_last_and_statement_stats_lie_under_it(cl):
    cl.execute("SET citus.trace_sample_rate = 1.0")
    before = cl.counters.snapshot()["trace_spans_recorded"]
    cl.execute("SELECT count(*), sum(v) FROM t WHERE v < 3000")
    tr = T.last_trace()
    root = tr.root()
    assert root.t1 == max(s.t1 for s in tr.spans)
    assert root.t0 == min(s.t0 for s in tr.spans)
    booked = [s for s in tr.find_all("book_stats")
              if s.parent_id == root.span_id]
    assert len(booked) == 1          # query_stats / tenant / scheduler
    # spans are booked once per trace, when it closes, by their number
    assert cl.counters.snapshot()["trace_spans_recorded"] \
        == before + len(tr.spans)


class AnnotationRecorder:
    """Stands in for jax.profiler.TraceAnnotation."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        import threading
        self.log.append(("enter", self.name, threading.get_ident()))

    def __exit__(self, *exc):
        import threading
        self.log.append(("exit", self.name, threading.get_ident()))


@pytest.mark.parametrize("n_producers", [1, 3])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_every_real_span_holds_one_annotation_on_its_own_thread(
        cl8, monkeypatch, limit_devices, producers, tmp_path, n_dev,
        n_producers):
    from collections import Counter
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    limit_devices(n_dev)
    producers(n_producers)
    q = "SELECT count(*), sum(v) FROM t"
    cl8.execute(q)                                   # compile unsampled
    AnnotationRecorder.log = log = []
    monkeypatch.setattr(T, "_annotation_cls", AnnotationRecorder)
    cl8.execute(q)
    assert log == []                                 # rate 0: none made
    cl8.execute("SET citus.trace_sample_rate = 1.0")
    del log[:]
    GLOBAL_CACHE.clear()                             # stream: two threads
    cl8.execute(q)                                   # or a producer more each
    tr = T.last_trace()
    tree(tr)
    threads = len({s.tid for s in tr.spans})
    assert threads == 2 if n_producers == 1 else 3 <= threads <= 5
    entered = Counter((n, t) for kind, n, t in log if kind == "enter")
    left = Counter((n, t) for kind, n, t in log if kind == "exit")
    assert entered == left
    assert entered == Counter(("citus." + s.name, s.tid) for s in tr.spans)
    # annotations nest per thread: each exit closes that thread's last
    open_ = {}
    for kind, name, tid in log:
        if kind == "enter":
            open_.setdefault(tid, []).append(name)
        else:
            assert open_[tid].pop() == name
    # the export after the root closed is a bare annotation of its own
    cl8.execute(f"SET citus.trace_export_dir = '{tmp_path}/traces'")
    del log[:]
    cl8.execute(q)
    names = [n for kind, n, _ in log if kind == "enter"]
    assert names[0] == "citus.query" and names[-1] == "citus.trace_export"
    assert names.count("citus.trace_export") == 1


def test_unsampled_resident_streaming_and_mesh_queries_allocate_no_span(
        cl8, limit_devices):
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    cl8.execute("SET citus.trace_sample_rate = 0")
    q = "SELECT c, count(*), sum(v) FROM t GROUP BY c"
    for n_dev in (1, 4):
        limit_devices(n_dev)
        GLOBAL_CACHE.clear()
        cl8.execute(q)                               # compiles, streams
        before = T.span_allocations()
        cl8.execute(q)                               # resident
        GLOBAL_CACHE.clear()
        cl8.execute(q)                               # streaming again
        assert T.span_allocations() == before, n_dev


@pytest.mark.parametrize("n_producers", [1, 3])
def test_chrome_export_puts_each_thread_on_its_own_row(cl8, limit_devices,
                                                       producers, tmp_path,
                                                       n_producers):
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    limit_devices(1)
    producers(n_producers)
    export = tmp_path / "traces"
    cl8.execute("SET citus.trace_sample_rate = 1.0")
    cl8.execute(f"SET citus.trace_export_dir = '{export}'")
    GLOBAL_CACHE.clear()
    cl8.execute("SELECT count(*), sum(v) FROM t")
    tr = T.last_trace()
    doc = json.load(open(export / f"trace_{tr.trace_id}.json"))
    rows = doc["otherData"]["thread_rows"]
    assert rows == 2 if n_producers == 1 else 3 <= rows <= 5
    evts = {e["args"]["span_id"]: e for e in doc["traceEvents"]
            if e["ph"] == "X"}
    assert len(evts) == len(tr.spans)
    root = tr.root()
    assert evts[root.span_id]["tid"] == 1
    for s in tr.spans:
        e = evts[s.span_id]
        assert (e["tid"] == 1) == (s.tid == root.tid)
        assert e["args"].get("parent_id") == s.parent_id
    decoders = {e["tid"] for e in evts.values() if e["name"] == "decode_batch"}
    if n_producers == 1:
        assert decoders == {2}
    else:
        assert 2 <= len(decoders) <= 3 and 1 not in decoders


def test_profile_traces_its_statement_whatever_the_sampling_rate(
        cl, monkeypatch, tmp_path):
    AnnotationRecorder.log = log = []
    monkeypatch.setattr(T, "_annotation_cls", AnnotationRecorder)
    cl.execute("SET citus.trace_sample_rate = 0")
    assert log == []
    r = cl.profile("SELECT count(*) FROM t", str(tmp_path / "prof"))
    assert r.rows == [(2000,)]
    names = [n for kind, n, _ in log if kind == "enter"]
    assert names[0] == "citus.query" and names.count("citus.query") == 1
    assert {"citus.execute", "citus.fetch", "citus.dispatch"} <= set(names)
    assert T.current() is None and "forced" in T.last_trace().reasons


# ------------------------------------------------- the hash-aggregation path


@pytest.fixture()
def clh(tmp_path):
    """6,000 rows in 1,500 groups whose key domain is far wider than
    ``direct_gid_limit``: the GROUP BY takes the device hash table."""
    c = ct.Cluster(str(tmp_path / "dbh"))
    c.execute("CREATE TABLE t (k bigint NOT NULL, g bigint, v bigint)")
    c.execute("SELECT create_distributed_table('t', 'k', 8)")
    g = np.random.default_rng(5).choice(10 ** 12, 1500, replace=False)
    c.copy_from("t", columns={"k": np.arange(6000), "g": np.tile(g, 4),
                              "v": np.arange(6000) % 7})
    yield c
    c.close()


HASH_Q = "SELECT g, sum(v) FROM t GROUP BY g"


@pytest.mark.parametrize("depth", [1, 3, None])
def test_hash_path_has_its_four_spans_on_the_callers_thread(
        clh, limit_devices, depth):
    from citus_tpu.executor.executor import _prefetch_depth
    limit_devices(1)
    if depth is not None:
        clh.execute(f"SET citus.executor_prefetch_depth = {depth}")
        clh.execute(f"SET citus.max_tasks_in_flight = {depth}")
    depth = _prefetch_depth(clh.settings)
    clh.execute("SET citus.trace_sample_rate = 1.0")
    assert len(clh.execute(HASH_Q).rows) == 1500
    tr = T.last_trace()
    by_id, kids = tree(tr)
    agg = tr.find("host_agg")
    under = [s.name for s in kids[agg.span_id]]
    rounds = tr.find_all("device_round")
    assert len(rounds) == 8                         # a batch per shard
    # one per query; a drain per window of ``depth`` batches, the last
    # one short
    for name, n in (("hash_init", 1), ("hash_merge", 1), ("hash_finalize", 1),
                    ("spill_drain", -(-len(rounds) // depth))):
        spans = tr.find_all(name)
        assert len(spans) == n, (name, under)
        for s in spans:
            assert s.parent_id == agg.span_id and s.tid == tr.root().tid
    assert under.index("hash_init") < under.index("device_round")
    assert under[-3:] == ["fetch", "hash_merge", "hash_finalize"], under
    assert sum(s.attrs["batches"] for s in tr.find_all("spill_drain")) == 8
    assert tr.find("hash_init").attrs["slots"] == 8192
    assert tr.find("hash_finalize").attrs == {"groups": 1500, "rows": 1500}


def test_unsampled_hash_query_allocates_no_span(clh, limit_devices):
    limit_devices(1)
    clh.execute("SET citus.trace_sample_rate = 0")
    clh.execute(HASH_Q)                                  # compiles
    before = T.span_allocations()
    clh.execute(HASH_Q)
    assert T.span_allocations() == before


@pytest.mark.parametrize("slots, provable", [
    (None, True), (1024, True), (64, True), (None, False)])
def test_hash_counters_for_a_known_table(clh, limit_devices, slots, provable):
    limit_devices(1)
    if not provable:
        # two rows of a group that is there: a NULL, so count(v) is not
        # count(*), and a value 6,002 rows of which would leave int64,
        # so the sum keeps its float64 overflow shadow
        g0 = clh.execute("SELECT g FROM t WHERE k = 0").rows[0][0]
        clh.execute(f"INSERT INTO t VALUES (6000, {g0}, NULL), "
                    f"(6001, {g0}, {1 << 61})")
    if slots is not None:
        clh.execute(f"SET citus.hash_agg_slots = {slots}")
    c0 = clh.counters.snapshot()
    r = clh.execute(HASH_Q)
    c1 = clh.counters.snapshot()
    pl = r.explain["pipeline"]
    # derived: the next power of two at or above the table's 6,000 rows
    assert pl["hash_slots"] == (slots or 8192)
    # groups after the merge of table and spills, however many spilled
    assert c1["hash_groups_out"] - c0["hash_groups_out"] == 1500
    assert pl["hash_groups_out"] == 1500
    assert (c1["hash_spill_rows"] - c0["hash_spill_rows"]
            == pl["hash_spilled_rows"])
    assert (pl["hash_spilled_rows"] > 3000) == (slots == 64)
    # a slot: int64 key + int8 flag, int64 sum + int64 count, int64 rows
    # -- and the float64 shadow sum where the table's statistics cannot
    # prove that the sum fits (planner/physical.py lower_aggregates)
    fetched = c1["hash_table_bytes_fetched"] - c0["hash_table_bytes_fetched"]
    assert fetched == pl["hash_table_bytes_fetched"] \
        == pl["hash_slots"] * (33 if provable else 41)
    assert r.explain["partials"] == {
        "computed": 2 if provable else 3,
        "overflow_guards_proved_away": int(provable),
        "null_counts_proved_away": int(provable)}
    assert (c1["agg_partials"] - c0["agg_partials"],
            c1["agg_partials_proved_away"] - c0["agg_partials_proved_away"]) \
        == ((2, 2) if provable else (3, 0))


# ------------------------------------- the raw reader and the index, named


def _spans_under(tr, parent):
    return [s for s in tr.spans if s.parent_id == parent.span_id]


def test_a_routed_lookup_names_footers_probes_and_chunk_reads(cl):
    """A point lookup's stripe_read holds the shard_open, one footer_read
    a stripe and a chunk_read for the stripe whose chunk is selected; with an index,
    one index_probe a stripe before them.  All closed before the rows
    are handed out, all on the caller's thread."""
    cl.execute("SET citus.trace_sample_rate = 1.0")
    q = "SELECT k, v FROM t WHERE k = 777"
    cl.execute(q)
    want = cl.execute(q).rows
    tr = T.last_trace()
    (read,) = tr.find_all("stripe_read")
    kids = _spans_under(tr, read)
    assert [k.name for k in kids][0] == "shard_open"
    assert {k.name for k in kids[1:]} == {"footer_read", "chunk_read"}
    foots = [k for k in kids if k.name == "footer_read"]
    assert sum(f.attrs["selected"] for f in foots) == 1
    (chunk,) = [k for k in kids if k.name == "chunk_read"]
    assert chunk.attrs["chunks"] == 1 and chunk.attrs["rows"] > 0
    assert all(k.tid == read.tid and read.t0 <= k.t0 <= k.t1 <= read.t1
               for k in kids)
    assert sum(k.duration_ms for k in kids) <= read.duration_ms
    cl.execute("CREATE INDEX t_k ON t (k)")
    assert cl.execute(q).rows == want
    tr = T.last_trace()
    (read,) = tr.find_all("stripe_read")
    names = [k.name for k in _spans_under(tr, read)]
    assert names.count("index_probe") == names.count("footer_read") >= 1
    assert names.count("chunk_read") == 1
    probes = tr.find_all("index_probe")
    assert sum(p.attrs["positions"] for p in probes) == len(want)


def test_kernel_compiles_counts_what_the_miss_counter_cannot(cl):
    """A kernel retraced for a new batch shape is no cache miss, but it
    is a compile: kernel_compiles moves with the kernel_compile spans."""
    def delta(c0, c1, name):
        return c1.get(name, 0) - c0.get(name, 0)

    from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
    GLOBAL_KERNELS.clear()       # shared by the process: start from a miss
    cl.execute("SET citus.trace_sample_rate = 1.0")
    q = "SELECT k, v FROM t WHERE k = 777"
    c0 = cl.counters.snapshot()
    cl.execute(q)
    c1 = cl.counters.snapshot()
    first = len(T.last_trace().find_all("kernel_compile"))
    assert delta(c0, c1, "kernel_cache_misses") >= 1
    assert delta(c0, c1, "kernel_compiles") == first >= 1
    cl.execute(q)
    c2 = cl.counters.snapshot()
    assert delta(c1, c2, "kernel_compiles") == 0
    # an index shrinks the batch handed to the same kernel: a new shape
    cl.execute("CREATE INDEX t_k2 ON t (k)")
    c3 = cl.counters.snapshot()
    cl.execute(q)
    c4 = cl.counters.snapshot()
    again = len(T.last_trace().find_all("kernel_compile"))
    assert delta(c3, c4, "kernel_compiles") == again >= 1
    assert delta(c3, c4, "kernel_cache_misses") == 0
    from citus_tpu.observability.export import METRIC_HELP
    from citus_tpu.stats import StatCounters
    for name in ("kernel_compiles", "wait_prefetch_full_ms"):
        assert name in StatCounters.COUNTERS and name in METRIC_HELP
