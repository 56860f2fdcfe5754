"""Query megabatching — same-family coalescing into one device dispatch
(executor/megabatch.py).

Covers the ISSUE-7 acceptance matrix: K threads coalesce (occupancy >
1 with per-query stat attribution), divergent shard pruning
sub-batches, window=0 is row-identical to the batched path across the
oracle suite, a mid-batch per-query error isolates to its caller, and
an injected per-dispatch delay proves batched throughput >= 2x serial.
"""

import threading
import time

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.testing.faults import FAULTS, FaultError


@pytest.fixture()
def db(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint, s text, d decimal(8,2))")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    cl.copy_from("t", columns={
        "k": np.arange(2000), "v": np.arange(2000) % 50,
        "s": [f"n{i % 5}" for i in range(2000)],
        "d": np.arange(2000) / 4})
    yield cl
    FAULTS.disarm()
    cl.close()


def _delta(c0, c1, key):
    return c1.get(key, 0) - c0.get(key, 0)


def _fanout(cl, sqls, n_threads=None):
    """Run one SQL per thread (or the same SQL K times), barrier-synced
    so they land inside one coalescing window.  -> (results, errors)."""
    if isinstance(sqls, str):
        sqls = [sqls] * n_threads
    results, errors = {}, {}
    bar = threading.Barrier(len(sqls))

    def run(i, sql):
        bar.wait()
        try:
            results[i] = cl.execute(sql).rows
        except Exception as e:  # noqa: BLE001 - recorded for assertions
            errors[i] = e
    ts = [threading.Thread(target=run, args=(i, s))
          for i, s in enumerate(sqls)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results, errors


def test_same_family_queries_coalesce(db):
    cl = db
    sql = "SELECT sum(v), count(*) FROM t WHERE k = 42"
    base = cl.execute(sql).rows           # serial baseline (window=0)
    cl.execute("SET citus.megabatch_window_ms = 1000")
    cl.execute("SET citus.megabatch_max_size = 6")
    cl.execute("SELECT citus_stat_statements_reset()")
    c0 = cl.counters.snapshot()
    results, errors = _fanout(cl, sql, 6)
    c1 = cl.counters.snapshot()
    assert errors == {}
    assert all(results[i] == base for i in range(6))
    # 6 queries rode strictly fewer dispatches (a full batch of 6 cuts
    # the window short, so normally exactly one)
    assert _delta(c0, c1, "megabatch_queries") == 6
    assert 1 <= _delta(c0, c1, "megabatch_batches") < 6
    assert _delta(c0, c1, "megabatch_fallbacks") == 0
    # coalescing waits book under megabatch_wait, never device_round
    assert _delta(c0, c1, "wait_megabatch_ms") > 0
    # per-QUERY stat attribution survives batching: the family books
    # one citus_stat_statements entry per issuing statement
    ss = {row[0]: row for row in cl.execute(
        "SELECT citus_stat_statements()").rows}
    fam = [row for q, row in ss.items() if "k = ?" in q or "k = 42" in q]
    assert fam and fam[0][3] == 6, fam       # calls column
    # occupancy accounting: the dispatcher saw one batch of 6 and the
    # admission pool served 5 of the 6 without a slot of their own
    mb = cl.execute("SELECT citus_megabatch_stats()").rows[0]
    assert mb[3] >= 6                        # queries
    pool = cl.execute("SELECT citus_stat_pool()").rows[0]
    assert pool[6] >= 5                      # coalesced column


def test_divergent_shards_sub_batch(db):
    cl = db
    # k=7, 13, 42 hash to distinct shards of 4 (deterministic); the
    # family coalesces into ONE queue but dispatches per shard set
    keys = (7, 13, 42)
    base = {k: cl.execute(
        f"SELECT sum(v), count(*) FROM t WHERE k = {k}").rows for k in keys}
    cl.execute("SET citus.megabatch_window_ms = 1000")
    cl.execute("SET citus.megabatch_max_size = 3")
    c0 = cl.counters.snapshot()
    results, errors = _fanout(
        cl, [f"SELECT sum(v), count(*) FROM t WHERE k = {k}" for k in keys])
    c1 = cl.counters.snapshot()
    assert errors == {}
    assert all(results[i] == base[k] for i, k in enumerate(keys))
    assert _delta(c0, c1, "megabatch_queries") == 3
    # sub-batched by placement: more than one dispatch, zero fallbacks,
    # and every query still returned ITS OWN shard's rows
    assert _delta(c0, c1, "megabatch_batches") >= 2
    assert _delta(c0, c1, "megabatch_fallbacks") == 0


ORACLE_SUITE = [
    "SELECT sum(v), count(*) FROM t WHERE k = 42",
    "SELECT v, s FROM t WHERE k = 13",
    "SELECT count(*) FROM t WHERE s = 'n3'",
    "SELECT sum(d), min(v) FROM t WHERE k BETWEEN 10 AND 20",
    "SELECT min(v), max(v) FROM t WHERE k >= 1990",
    "SELECT v, count(*) FROM t WHERE v < 5 AND k < 100 GROUP BY v ORDER BY v",
    "SELECT k, v FROM t WHERE k > 1995 ORDER BY k",
]


def test_window_zero_identical_to_batched_path(db):
    cl = db
    # window=0 (default): serial path, byte-identical to pre-megabatch
    serial = [cl.execute(q).rows for q in ORACLE_SUITE]
    # window>0 solo: every query rides the batched runners (occupancy
    # 1), including the interval-free shared scan — rows must match the
    # serial path row-for-row
    cl.execute("SET citus.megabatch_window_ms = 30")
    c0 = cl.counters.snapshot()
    batched = [cl.execute(q).rows for q in ORACLE_SUITE]
    c1 = cl.counters.snapshot()
    assert batched == serial
    # the suite really exercised the batched path
    assert _delta(c0, c1, "megabatch_queries") >= len(ORACLE_SUITE) - 1


def test_mid_batch_error_isolates_to_its_caller(db):
    cl = db
    keys = (7, 13, 42)
    base = {k: cl.execute(
        f"SELECT sum(v) FROM t WHERE k = {k}").rows for k in keys}
    cl.execute("SET citus.megabatch_window_ms = 1000")
    cl.execute("SET citus.megabatch_max_size = 3")
    # per-query failure injected at the caller-side scatter, keyed by
    # router key: only k=42's caller may see it
    FAULTS.arm("megabatch_finalize", error=FaultError("scatter boom"),
               match=":42", times=1)
    try:
        results, errors = _fanout(
            cl, [f"SELECT sum(v) FROM t WHERE k = {k}" for k in keys])
    finally:
        FAULTS.disarm("megabatch_finalize")
    assert list(errors) == [2], (errors, results)
    assert isinstance(errors[2], FaultError)
    for i, k in enumerate(keys[:2]):
        assert results[i] == base[k]


def test_batched_throughput_beats_serial_2x(db):
    cl = db
    sql = "SELECT sum(v), count(*) FROM t WHERE k = 42"
    K, R = 6, 3

    def storm():
        bar = threading.Barrier(K)

        def run():
            bar.wait()
            for _ in range(R):
                cl.execute(sql)
        ts = [threading.Thread(target=run) for _ in range(K)]
        t0 = time.monotonic()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return time.monotonic() - t0

    # warm both paths (compile + device cache) before arming the delay
    cl.execute(sql)
    cl.execute("SET citus.megabatch_window_ms = 300")
    cl.execute("SET citus.megabatch_max_size = 6")
    cl.execute(sql)
    cl.execute("SET citus.megabatch_window_ms = 0")
    # a fixed per-dispatch cost: hit under the kernel lock, so serial
    # same-family queries pay it K*R times end to end while coalesced
    # rounds pay it once per batch
    FAULTS.arm("kernel_dispatch", delay_s=0.03)
    try:
        serial_wall = storm()
        cl.execute("SET citus.megabatch_window_ms = 300")
        batched_wall = storm()
    finally:
        FAULTS.disarm("kernel_dispatch")
    assert batched_wall * 2 <= serial_wall, (batched_wall, serial_wall)


def test_explain_analyze_shows_batch_line(db):
    cl = db
    cl.execute("SET citus.megabatch_window_ms = 30")
    r = cl.execute("EXPLAIN ANALYZE SELECT sum(v) FROM t WHERE k = 7")
    lines = [row[0] for row in r.rows]
    batch = [ln for ln in lines if ln.strip().startswith("Batch:")]
    assert batch, lines
    assert "occupancy 1" in batch[0] and "window 30" in batch[0]


def test_megabatch_gucs_round_trip(db):
    cl = db
    cl.execute("SET citus.megabatch_window_ms = 12.5")
    cl.execute("SET citus.megabatch_max_size = 9")
    assert float(cl.execute("SHOW citus.megabatch_window_ms").rows[0][0]) \
        == 12.5
    assert int(cl.execute("SHOW citus.megabatch_max_size").rows[0][0]) == 9
    assert cl.settings.executor.megabatch_window_ms == 12.5
    assert cl.settings.executor.megabatch_max_size == 9
    r = cl.execute("SELECT citus_megabatch_stats()")
    assert r.columns[:5] == ["window_ms", "max_size", "batches", "queries",
                             "fallbacks"]
    cl.execute("SET citus.megabatch_window_ms = 0")
    assert cl.settings.executor.megabatch_window_ms == 0.0


# ------------------------------------------------- adaptive auto window


def _mb_stats(cl):
    r = cl.execute("SELECT citus_megabatch_stats()")
    return dict(zip(r.columns, r.rows[0]))


def test_auto_window_beats_fixed_under_bursty_arrivals(db):
    """SET citus.megabatch_window_ms = auto sizes the wait from the plan
    family's inter-arrival EWMA: under a bursty storm it still
    coalesces (occupancy > 1) but never parks a query for a whole
    oversized fixed window.  Held to what the megabatcher itself counts
    -- the window it chose for every query that rode a batch, and the
    batches' occupancy -- not to two wall clocks, which under the
    driver's six workers say more about the neighbours than about the
    window (ROADMAP C12)."""
    cl = db
    sql = "SELECT sum(v), count(*) FROM t WHERE k = 42"
    K, R = 6, 4
    cl.execute(sql)  # warm compile + device caches
    cl.execute("SET citus.megabatch_max_size = 32")

    def storm():
        """-> the megabatch note of every query that rode a batch."""
        bar = threading.Barrier(K)
        rode, mu = [], threading.Lock()

        def run():
            bar.wait()
            for _ in range(R):
                info = cl.execute(sql).explain.get("megabatch")
                if info:
                    with mu:
                        rode.append(info)
        ts = [threading.Thread(target=run) for _ in range(K)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return rode

    cl.execute("SET citus.megabatch_window_ms = auto")
    # a storm whose threads the scheduler spread past the sparseness
    # threshold coalesces nothing and says nothing: try again
    for _ in range(5):
        s0 = _mb_stats(cl)
        auto = storm()
        s1 = _mb_stats(cl)
        if s1["queries"] - s0["queries"] > s1["batches"] - s0["batches"]:
            break
    # the bursty family coalesced under auto: batched queries
    # outnumber batches (occupancy > 1 on average)
    assert s1["queries"] - s0["queries"] > s1["batches"] - s0["batches"], \
        (s0, s1)
    assert auto and max(i["occupancy"] for i in auto) > 1, auto
    # ... and every window auto chose is inside its 0.5-10 ms bound
    assert all(0.5 <= i["window_ms"] <= 10.0 for i in auto), auto
    # fixed oversized window: every round parks for the full window
    # (max_size 32 means the batch never fills early), four times the
    # longest auto ever waits
    cl.execute("SET citus.megabatch_window_ms = 40")
    fixed = storm()
    assert fixed and all(i["window_ms"] == 40.0 for i in fixed), fixed
    assert max(i["window_ms"] for i in auto) < min(
        i["window_ms"] for i in fixed)
    # a leader parks for the whole fixed window: its own wait says so
    assert max(i["wait_ms"] for i in fixed) >= 40.0


def test_auto_window_sparse_family_stays_serial(db):
    """A family arriving slower than the sparseness threshold pays no
    window at all under auto: maybe_megabatch bows out pre-queue, so
    megabatch counters do not move."""
    cl = db
    sql = "SELECT sum(v) FROM t WHERE k = 7"
    expected = cl.execute(sql).rows
    cl.execute("SET citus.megabatch_window_ms = auto")
    s0 = _mb_stats(cl)
    for _ in range(5):
        assert cl.execute(sql).rows == expected
        time.sleep(0.03)  # above _AUTO_SPARSE_S: the family is sparse
    s1 = _mb_stats(cl)
    assert s1["queries"] == s0["queries"], (s0, s1)
    assert s1["batches"] == s0["batches"], (s0, s1)
