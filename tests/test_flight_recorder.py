"""Cluster flight recorder (citus_tpu/observability/flight_recorder.py):
ring history + rates, disk segment rotation/retention, the health engine
(typed events, dedup, resolution, advisory shedding), counters-reset
coherence, HBM accounting invariants, and EXPLAIN ANALYZE's Memory line.
"""

import json
import threading
import time

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.config import ExecutorSettings, Settings, WorkloadSettings
from citus_tpu.errors import AdmissionShedError
from citus_tpu.executor.admission import SharedTaskPool
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.observability.flight_recorder import (
    ADVISORY, HEALTH_EVENT_KINDS, PAYLOAD_SAMPLES,
)
from citus_tpu.workload import TenantScheduler


@pytest.fixture()
def cl(tmp_path):
    c = ct.Cluster(str(tmp_path / "db"))
    c.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    c.execute("SELECT create_distributed_table('t', 'k', 4)")
    c.copy_from("t", columns={"k": np.arange(2000),
                              "v": np.arange(2000) * 2})
    yield c
    c.close()
    ADVISORY.pool_saturated = False  # process-global advisory: reset


# ----------------------------------------------------- ring + history


def test_ring_history_and_rates(cl):
    rec = cl.flight_recorder
    rec.run_once()
    cl.execute("SELECT count(*) FROM t")
    rec.run_once()
    rows = rec.history_rows(metric="queries_executed")
    assert len(rows) == 2
    ts = [r[0] for r in rows]
    assert ts == sorted(ts) and ts[0] < ts[1]
    assert rows[0][3] is None          # first sample has no rate base
    assert rows[1][3] is not None and rows[1][3] >= 0
    # the executed query moved the counter between the ticks
    assert rows[1][2] > rows[0][2]


def test_history_filter_limit_and_payload_bound(cl):
    rec = cl.flight_recorder
    for _ in range(3):
        rec.run_once()
    all_rows = rec.history_rows(metric="queries_executed")
    assert len(all_rows) == 3
    limited = rec.history_rows(metric="queries_executed", limit=1)
    assert len(limited) == 1
    assert limited[0][0] == all_rows[-1][0]
    # the dropped preceding sample still serves as the rate base
    assert limited[0][3] is not None
    # a generous lookback keeps everything; metric filter holds
    recent = rec.history_rows(metric="queries_executed", since_s=3600)
    assert len(recent) == 3
    assert all(r[1] == "queries_executed" for r in recent)
    payload = rec.export_payload()
    assert set(payload) == {"history", "health"}
    samples = {r[0] for r in payload["history"]}
    assert len(samples) <= PAYLOAD_SAMPLES


def test_sql_stat_history_single_node(cl):
    rec = cl.flight_recorder
    rec.run_once()
    cl.execute("SELECT sum(v) FROM t")
    rec.run_once()
    res = cl.execute("SELECT citus_stat_history('queries_executed')")
    assert res.columns == ["ts", "node", "metric", "value", "rate"]
    assert len(res.rows) == 2
    assert all(r[2] == "queries_executed" for r in res.rows)
    ts = [r[0] for r in res.rows]
    assert ts == sorted(ts)
    # the since_s window form parses and filters
    res2 = cl.execute(
        "SELECT citus_stat_history('queries_executed', 3600)")
    assert len(res2.rows) == 2


def test_guc_starts_and_stops_sampler_thread(cl):
    rec = cl.flight_recorder
    assert rec._thread is None  # off by default (interval 0)
    cl.execute("SET citus.flight_recorder_interval_ms = 10")
    assert rec._thread is not None and rec._thread.is_alive()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        if cl.counters.snapshot().get("flight_recorder_ticks", 0) >= 2:
            break
        time.sleep(0.01)
    assert cl.counters.snapshot()["flight_recorder_ticks"] >= 2
    assert rec.history_rows(metric="queries_executed")
    cl.execute("SET citus.flight_recorder_interval_ms = 0")
    assert rec._thread is None  # stop() joins before returning


# ------------------------------------------------------------ disk log


def test_segment_spill_rotation_and_retention(cl):
    rec = cl.flight_recorder
    # drive the spill path with synthetic timestamps: default retention
    # 3600s rotates every 900s and prunes segments older than 3600s
    rotations0 = cl.counters.snapshot()["flight_recorder_rotations"]
    rec._spill(1000.0, {"a": 1})
    rec._spill(1000.5, {"a": 2})
    segs = rec.segment_files()
    assert len(segs) == 1
    lines = open(segs[0]).read().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0]) == {"ts": 1000.0, "m": {"a": 1}}
    rec._spill(1000.0 + 901, {"a": 3})     # past retention/4: rotate
    assert len(rec.segment_files()) == 2
    rec._spill(1000.0 + 7200, {"a": 4})    # both old segments expired
    segs = rec.segment_files()
    assert len(segs) == 1
    assert segs[0].endswith(f"seg_{int((1000.0 + 7200) * 1000)}.jsonl")
    assert cl.counters.snapshot()["flight_recorder_rotations"] \
        - rotations0 == 3


# ------------------------------------------------------- health engine


def _feed(rec, metric_dicts, monkeypatch):
    """Run one tick per dict with _collect() stubbed to return it."""
    seq = iter(metric_dicts)
    monkeypatch.setattr(rec, "_collect", lambda: next(seq))
    for _ in metric_dicts:
        rec.run_once()


def test_forced_p99_regression_raises_exactly_one_event(cl, monkeypatch):
    rec = cl.flight_recorder
    # 6 warmup ticks at 1ms baseline, then a sustained 50ms spike
    _feed(rec, [{"query_p99_ms": 1.0}] * 6 + [{"query_p99_ms": 50.0}] * 3,
          monkeypatch)
    events = [e for e in rec.events_rows() if e[1] == "p99_regression"]
    assert len(events) == 1  # deduped while the condition is active
    assert events[0][6] is True
    assert rec.active_counts()["p99_regression"] == 1
    # recovery resolves the event; the log entry survives, inactive
    _feed(rec, [{"query_p99_ms": 1.0}], monkeypatch)
    assert rec.active_counts()["p99_regression"] == 0
    events = [e for e in rec.events_rows() if e[1] == "p99_regression"]
    assert len(events) == 1 and events[0][6] is False


def test_forced_pool_saturation_event_and_advisory(cl, monkeypatch):
    cl.execute("SET citus.max_shared_pool_size = 2")
    rec = cl.flight_recorder
    assert ADVISORY.pool_saturated is False
    _feed(rec, [{"pool_in_use": 2}] * 4, monkeypatch)
    events = [e for e in rec.events_rows() if e[1] == "pool_saturation"]
    assert len(events) == 1  # exactly one despite 4 pinned ticks
    assert ADVISORY.pool_saturated is True
    assert rec.active_counts()["pool_saturation"] == 1
    _feed(rec, [{"pool_in_use": 0}], monkeypatch)
    assert ADVISORY.pool_saturated is False
    assert rec.active_counts()["pool_saturation"] == 0


def test_shed_spike_and_catchup_stall_events(cl, monkeypatch):
    rec = cl.flight_recorder
    # sheds jump by 10 in one tick against a zero baseline
    _feed(rec, [{"tenant_shed": 0}, {"tenant_shed": 10}], monkeypatch)
    assert rec.active_counts()["shed_rate_spike"] == 1
    _feed(rec, [{"tenant_shed": 10}], monkeypatch)  # delta 0: resolved
    assert rec.active_counts()["shed_rate_spike"] == 0
    # catch-up rounds advancing 5 ticks in a row = a stalled move
    _feed(rec, [{"shard_move_catchup_rounds": n} for n in range(7)],
          monkeypatch)
    assert rec.active_counts()["catchup_stall"] == 1


def test_emit_event_rejects_unknown_kind(cl):
    with pytest.raises(ValueError, match="unknown health-event kind"):
        cl.flight_recorder.emit_event("made_up", "x", 1, 0, "detail")


def test_health_events_sql_surface(cl, monkeypatch):
    cl.execute("SET citus.max_shared_pool_size = 1")
    _feed(cl.flight_recorder, [{"pool_in_use": 1}] * 3, monkeypatch)
    res = cl.execute("SELECT citus_health_events()")
    assert res.columns == ["ts", "node", "kind", "severity", "subject",
                           "value", "baseline", "active", "detail"]
    sat = [r for r in res.rows if r[2] == "pool_saturation"]
    assert len(sat) == 1
    assert sat[0][3] == "critical" and sat[0][7] is True


def test_advisory_saturation_halves_shed_depth():
    """While the pool_saturation advisory is raised the scheduler sheds
    at half the configured queue depth (4 -> 2)."""
    sched = TenantScheduler(pool=SharedTaskPool())
    st = Settings(executor=ExecutorSettings(max_shared_pool_size=1),
                  workload=WorkloadSettings(tenant_queue_depth=4))
    sched.acquire(st, "a")  # hold the only slot
    threads = []
    try:
        for _ in range(2):
            th = threading.Thread(
                target=lambda: (sched.acquire(st, "a", timeout=10),
                                sched.release("a")),
                daemon=True)
            th.start()
            threads.append(th)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if any(r[0] == "a" and r[2] == 2 for r in sched.rows_view()):
                break
            time.sleep(0.001)
        ADVISORY.pool_saturated = True
        # 2 queued at effective depth 2: shed, where depth 4 would queue
        with pytest.raises(AdmissionShedError, match="2 waiters"):
            sched.acquire(st, "a")
    finally:
        ADVISORY.pool_saturated = False
        sched.release("a")
        for th in threads:
            th.join()


# ------------------------------------------------------ reset coherence


def test_counters_reset_clears_ring_and_histograms(cl):
    rec = cl.flight_recorder
    rec.run_once()
    cl.execute("SELECT count(*) FROM t")
    rec.run_once()
    assert rec.history_rows(metric="queries_executed")
    assert cl.query_stats.histograms_view()
    cl.execute("SELECT citus_stat_counters_reset()")
    # the reset hook dropped the ring atomically with the counters —
    # no post-reset sample can difference against a pre-reset value
    assert rec.history_rows() == []
    assert cl.counters.snapshot()["queries_executed"] == 0
    # the pre-reset query families are gone (the reset statement itself
    # records its own latency after the wipe — that one may remain)
    families = [q for q, _h in cl.query_stats.histograms_view()]
    assert not any("from t" in q for q in families), families
    rec.run_once()
    cl.execute("SELECT count(*) FROM t")
    rec.run_once()
    rows = rec.history_rows(metric="queries_executed")
    assert len(rows) == 2
    assert all(r[3] is None or r[3] >= 0 for r in rows), rows


def test_reset_during_tick_drops_sample(cl, monkeypatch):
    rec = cl.flight_recorder

    def racing_collect():
        rec.reset_baselines()  # a reset lands mid-collection
        return {"queries_executed": 5}

    monkeypatch.setattr(rec, "_collect", racing_collect)
    rec.run_once()
    assert rec.history_rows() == []  # torn sample was discarded


# ------------------------------------------------------- HBM accounting


def test_device_memory_attribution_invariant(cl):
    old_cap = GLOBAL_CACHE.capacity
    GLOBAL_CACHE.clear()
    # per-query entries under this workload are ~655KB: two fit, the
    # third put forces LRU eviction
    GLOBAL_CACHE.capacity = 1_400_000
    try:
        for hi in (100, 500, 900, 1300, 1700, 2000):
            cl.execute(f"SELECT count(*), sum(v) FROM t WHERE v < {hi}")
        mv = GLOBAL_CACHE.memory_view()
        assert mv["live_bytes"] > 0
        assert mv["live_bytes"] <= mv["capacity_bytes"]
        assert mv["high_water_bytes"] >= mv["live_bytes"]
        # the per-(table, tenant) ledger sums exactly to live bytes
        assert sum(b for _t, _n, b in mv["by_owner"]) == mv["live_bytes"]
        res = cl.execute("SELECT citus_device_memory()")
        assert res.columns == ["scope", "table", "tenant", "bytes"]
        by_scope = {}
        for scope, _table, _tenant, b in res.rows:
            by_scope.setdefault(scope, []).append(b)
        assert sum(by_scope["entry"]) == by_scope["total"][0]
        assert by_scope["total"][0] <= by_scope["capacity"][0]
    finally:
        GLOBAL_CACHE.capacity = old_cap
        GLOBAL_CACHE.clear()


def test_explain_analyze_memory_line_cached_and_streaming(cl):
    cl.execute("SELECT sum(v) FROM t WHERE v < 999")  # warm the cache
    r = cl.execute("EXPLAIN ANALYZE SELECT sum(v) FROM t WHERE v < 999")
    txt = "\n".join(row[0] for row in r.rows)
    assert "Memory:" in txt, txt
    mem = [ln for ln in txt.splitlines() if "Memory:" in ln][0]
    touched = int(mem.split("Memory:")[1].split()[0])
    assert touched > 0  # the cache hit replays resident bytes
    old_cap = GLOBAL_CACHE.capacity
    GLOBAL_CACHE.clear()
    GLOBAL_CACHE.capacity = 1  # nothing fits: pure streaming path
    try:
        r2 = cl.execute(
            "EXPLAIN ANALYZE SELECT sum(v) FROM t WHERE v < 999")
        txt2 = "\n".join(row[0] for row in r2.rows)
        mem2 = [ln for ln in txt2.splitlines() if "Memory:" in ln]
        assert mem2, txt2
        touched2 = int(mem2[0].split("Memory:")[1].split()[0])
        assert touched2 > 0  # streamed bytes are accounted too
        assert "cache-resident 0 bytes" in mem2[0]
    finally:
        GLOBAL_CACHE.capacity = old_cap
        GLOBAL_CACHE.clear()


# -------------------------------------------------------------- gauges


def test_pool_and_health_gauges_in_metrics(cl):
    from citus_tpu.observability.export import prometheus_text
    txt = prometheus_text(cl)
    assert "citus_pool_in_use 0" in txt
    assert "citus_pool_high_water" in txt
    assert "citus_tenant_queued" in txt
    for kind in HEALTH_EVENT_KINDS:
        assert f"citus_health_{kind} " in txt
    # running a query through the scheduler materializes the labeled
    # per-tenant queue-depth series
    cl.execute("SELECT count(*) FROM t")
    txt = prometheus_text(cl)
    assert 'citus_tenant_queue_depth{tenant="' in txt
