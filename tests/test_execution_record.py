"""A statement's run-time record (``executor/pipeline.py``
``PipelineStats``) is the execution's own: made where the run starts,
handed down by argument, read once by ``_finish_select``.  The cached
plan, which every caller of a statement family shares, keeps what was
compiled from it and nothing an execution counted.

Two executions of ONE cached plan that overlap each keep their own
figures, tasks and load-ledger booking; and route by route, a second
run shows exactly the figures that run set, none left over from the
run before it.
"""

import copy
import threading

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import executor as X
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.observability.load_attribution import GLOBAL_ATTRIBUTION
from test_join_device import DAY0, Data, Q3, iso, on_device
from test_worker_tasks import pair  # noqa: F401

ROWS, SHARDS = 4000, 4

# what a device scan loop writes, what only a streamed one does, and
# what the callers that close a streamed scan's timings add
SCAN = {"fused_dispatches", "scan_lanes", "scan_lanes_narrow"}
STREAMED = {"batch_rows_real", "batch_rows_padded", "decode_bytes_in_place",
            "decode_bytes_copied", "stream_window_peak_bytes",
            "decode_streams", "decode_overlap_ms"}
TIMINGS = {"host_decode_ms", "device_ms", "h2d_bytes", "host_stalls",
           "device_stalls"}
DIRECT = {"group_rows_in", "direct_groups", "direct_groups_out",
          "group_rows_kept", "direct_bytes_fetched", "direct_gid_keys",
          "direct_gid_keys_narrow", "direct_gid_divisions"}
HASH = {"hash_tables", "hash_slots", "hash_slots_from", "hash_disjoint_on",
        "hash_spilled_rows", "hash_table_updates", "hash_offer_slots",
        "hash_rows_in",
        "hash_rows_in_max_device", "group_rows_in", "hash_tables_merged",
        "hash_occupancy_pct", "group_rows_kept", "hash_groups_out",
        "hash_table_bytes_fetched", "hash_entries_fetched", "group_top"}
REMOTE = {"remote_wait_ms", "remote_overlapped_ms", "remote_inflight_peak",
          "wire_format"}


def _load(cl, rows=ROWS):
    cl.execute("CREATE TABLE t (k bigint NOT NULL, g integer, v bigint, "
               "w bigint)")
    cl.execute(f"SELECT create_distributed_table('t', 'k', {SHARDS})")
    k = np.arange(rows)
    cl.copy_from("t", columns={
        "k": k, "g": (k % 5).astype(np.int32), "v": k % 100,
        "w": np.random.default_rng(3).choice(10 ** 12, rows, replace=False)})
    GLOBAL_CACHE.clear()


@pytest.fixture()
def cl(tmp_path, limit_devices):
    limit_devices(1)        # one batch a round: a task a shard
    c = ct.Cluster(str(tmp_path / "db"))
    _load(c)
    yield c
    c.close()
    GLOBAL_CACHE.clear()


def test_overlapping_executions_of_one_cached_plan(cl, monkeypatch):
    """The first execution is held after its device scan, ahead of its
    fetch; the second, of the same cached plan with another parameter,
    runs to its end; then the first is let go."""
    sql = "SELECT g, count(*) FROM t WHERE v < $1 GROUP BY g"
    assert cl.execute(sql, params=[10]).explain["strategy"] == "direct"
    entered, release = threading.Event(), threading.Event()
    real_fetch = X._fetch_acc

    def held_fetch(acc):
        if threading.current_thread().name == "first":
            entered.set()
            assert release.wait(60)
        return real_fetch(acc)

    monkeypatch.setattr(X, "_fetch_acc", held_fetch)
    booked = {}
    real_book = GLOBAL_ATTRIBUTION.book_query

    def book_query(table, tenant, task_times, task_bytes, *a, **kw):
        booked[threading.current_thread().name] = (
            list(task_times), list(task_bytes))
        return real_book(table, tenant, task_times, task_bytes, *a, **kw)

    monkeypatch.setattr(GLOBAL_ATTRIBUTION, "book_query", book_query)
    hits = GLOBAL_COUNTERS.snapshot().get("plan_cache_hits", 0)
    out = {}
    first = threading.Thread(
        name="first",
        target=lambda: out.update(first=cl.execute(sql, params=[30])))
    first.start()
    try:
        assert entered.wait(60)
        second = cl.execute(sql, params=[70])
        taken = copy.deepcopy(second.explain)
    finally:
        release.set()
        first.join(60)
    first = out["first"]
    # ONE plan, from the cache, under both
    assert GLOBAL_COUNTERS.snapshot()["plan_cache_hits"] == hits + 2
    assert first.rows != second.rows

    # a Result taken earlier is as it was
    assert second.explain == taken
    # each Result carries its own figures and its own tasks
    p1, p2 = first.explain["pipeline"], second.explain["pipeline"]
    assert p1["group_rows_kept"] == ROWS * 30 // 100
    assert p2["group_rows_kept"] == ROWS * 70 // 100
    assert p1.keys() == p2.keys() == SCAN | STREAMED | TIMINGS | DIRECT
    assert p1["batch_rows_real"] == p2["batch_rows_real"] == ROWS
    for r in (first, second):
        assert sorted(si for si, _, _ in r.explain["tasks"]) == \
            list(range(SHARDS))
    assert p1 is not p2

    # the load ledger booked both executions, each its own device work
    main = threading.current_thread().name
    assert booked.keys() == {"first", main}
    for name, r in (("first", first), (main, second)):
        times, nbytes = booked[name]
        assert times == r.explain["tasks"]
        assert sorted(si for si, _ in nbytes) == list(range(SHARDS))
        assert sum(b for _, b in nbytes) == r.explain["pipeline"]["h2d_bytes"]

    # and the cached plan holds kernels, closures and its fingerprint
    entry = cl._plan_cache.lookup(
        ("$param", sql), cl.catalog,
        cl.settings.executor.task_executor_backend)
    assert not {"pipeline", "task_times", "task_bytes", "mesh_task_times",
                "remote_tasks"} & entry.plan.runtime_cache.keys()


# ------------------------------------------------------- route by route


def _direct(cl):
    sql = "SELECT g, count(*), sum(v) FROM t GROUP BY g"
    return [cl.execute(sql) for _ in range(2)], \
        [SCAN | STREAMED | TIMINGS | DIRECT, SCAN | DIRECT]


def _hash_one_device(cl):
    sql = "SELECT w, min(v) FROM t GROUP BY w"      # never cached
    runs = [cl.execute(sql) for _ in range(2)]
    assert runs[0].explain["strategy"] == "hash_host"
    return runs, [SCAN | STREAMED | TIMINGS | HASH] * 2


def _projection(cl):
    sql = "SELECT k, v FROM t WHERE k < 7"          # no device scan loop
    return [cl.execute(sql) for _ in range(2)], [set(), set()]


def _megabatched(cl):
    sql = "SELECT g, count(*) FROM t WHERE v < $1 GROUP BY g"
    cl.execute("SET citus.megabatch_window_ms = 1")
    runs = [cl.execute(sql, params=[30]) for _ in range(2)]
    assert [r.explain["megabatch"]["occupancy"] for r in runs] == [1, 1]
    return runs, [SCAN | STREAMED, SCAN]


def _device_join(cl):
    data = Data(11, orders=300, customers=40)
    data.load(cl, SHARDS)
    runs = [cl.execute(Q3.format(seg="BUILDING", date=iso(DAY0 + 60)))
            for _ in range(2)]
    assert all(on_device(r.explain) for r in runs)
    return runs, [SCAN | STREAMED | TIMINGS
                  | {"hash_occupancy_pct", "group_rows_kept", "group_top",
                     "group_keys", "group_key_lanes",
                     "group_keys_dependent"}] * 2


@pytest.mark.parametrize("route", [
    _direct, _hash_one_device, _projection, _megabatched, _device_join],
    ids=lambda f: f.__name__.strip("_"))
def test_a_second_run_shows_its_own_figures_alone(cl, route):
    """Each run's ``pipeline`` holds exactly the keys that run of the
    route sets -- a run served from the HBM cache none of the streamed
    run's before it -- and what a streamed run adds up batch by batch
    starts from nothing."""
    (first, second), (keys1, keys2) = route(cl)
    p1, p2 = first.explain["pipeline"], second.explain["pipeline"]
    assert set(p1) == keys1
    assert set(p2) == keys2
    assert p1 is not p2
    if "batch_rows_real" in keys2:
        assert p2["batch_rows_real"] == p1["batch_rows_real"] > 0
    assert first.rows and sorted(first.rows) == sorted(second.rows)


def test_a_pushed_worker_task_and_its_coordinator(pair, limit_devices):  # noqa: F811
    """Two coordinators: half the shards run as pushed tasks where they
    live.  The pushing statement's record holds its own remote task log
    and figures, run after run; each task's own record feeds the load
    ledger of the host that ran it, the second (served from that host's
    HBM cache) with no bytes."""
    limit_devices(1)
    a, b, na, nb = pair
    a.execute("CREATE TABLE t (k bigint NOT NULL, g integer, v bigint)")
    a.execute(f"SELECT create_distributed_table('t', 'k', {SHARDS})")
    k = np.arange(ROWS)
    a.copy_from("t", columns={"k": k, "g": (k % 5).astype(np.int32),
                              "v": k % 100})
    GLOBAL_CACHE.clear()
    GLOBAL_COUNTERS.reset()
    remote = [i for i, s in enumerate(a.catalog.table("t").shards)
              if a.catalog.is_remote_node(s.placements[0])]
    assert 0 < len(remote) < SHARDS
    sql = "SELECT g, count(*), sum(v) FROM t GROUP BY g"
    try:
        first = a.execute(sql)
        scanned = GLOBAL_ATTRIBUTION.totals()["bytes_scanned"]
        second = a.execute(sql)
        assert first.rows == second.rows
        assert GLOBAL_COUNTERS.snapshot()["remote_task_fallbacks"] == 0
        p1, p2 = first.explain["pipeline"], second.explain["pipeline"]
        assert set(p1) == SCAN | STREAMED | TIMINGS | DIRECT | REMOTE
        assert set(p2) == SCAN | DIRECT | REMOTE
        for r in (first, second):
            assert [t[0] for t in r.explain["remote_tasks"]] == remote
            assert len(r.explain["tasks"]) == SHARDS - len(remote)
        # ledger and counter balance over the local scans and the tasks
        tot = GLOBAL_ATTRIBUTION.totals()
        assert tot["bytes_scanned"] == scanned == \
            GLOBAL_COUNTERS.snapshot()["bytes_scanned"]
        assert tot["queries"] == 2
    finally:
        GLOBAL_CACHE.clear()
