"""Shared task-pool admission control.

Reference: citus.max_shared_pool_size backed by shared-memory counters
(connection/shared_connection_stats.c) — bounds the node-wide worker
connections; optional acquisitions fail fast, required ones wait."""

import threading
import time
import traceback

import pytest

import citus_tpu as ct
from citus_tpu.errors import ExecutionError
from citus_tpu.executor.admission import GLOBAL_POOL, SharedTaskPool


def test_required_waits_and_bounds_concurrency():
    pool = SharedTaskPool()
    peak = []

    def work(i):
        with pool.slot(2, timeout=10):
            peak.append(pool.in_use)
            time.sleep(0.02)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert pool.high_water <= 2
    assert pool.granted == 8
    assert pool.waits > 0
    assert pool.in_use == 0


def test_optional_denied_fast():
    pool = SharedTaskPool()
    assert pool.acquire(1) is True
    t0 = time.monotonic()
    assert pool.acquire(1, optional=True) is False
    assert time.monotonic() - t0 < 0.1  # never waited
    assert pool.stats()["denied_optional"] == 1
    pool.release()


def test_required_times_out():
    pool = SharedTaskPool()
    pool.acquire(1)
    with pytest.raises(ExecutionError, match="max_shared_pool_size"):
        pool.acquire(1, timeout=0.1)
    pool.release()


def test_unlimited_by_default():
    pool = SharedTaskPool()
    for _ in range(64):
        assert pool.acquire(0) is True
    assert pool.high_water == 64


def test_fifo_ticket_order():
    """Regression: a freed slot goes to the LONGEST-waiting required
    acquirer, not whichever thread the OS wakes first."""
    pool = SharedTaskPool()
    pool.acquire(1)
    order = []
    started = []
    threads = []

    def waiter(i):
        started.append(i)
        pool.acquire(1, timeout=10)
        order.append(i)
        time.sleep(0.01)
        pool.release()

    for i in range(4):
        t = threading.Thread(target=waiter, args=(i,))
        threads.append(t)
        t.start()
        # arrival order is the ticket order: wait until i is queued
        deadline = time.monotonic() + 5
        while len(pool._waiters) < i + 1 and time.monotonic() < deadline:
            time.sleep(0.001)
    pool.release()
    for t in threads:
        t.join()
    assert order == [0, 1, 2, 3]
    # waits counts waiters, not grants: the seed acquire never waited
    assert pool.waits == 4
    assert pool.granted == 5


def test_optional_never_barges_waiters():
    """Regression: with a required waiter queued, an optional acquire
    is denied even at the instant a slot frees — the freed slot belongs
    to the queue head."""
    pool = SharedTaskPool()
    pool.acquire(1)
    got = []
    t = threading.Thread(target=lambda: got.append(
        pool.acquire(1, timeout=10)))
    t.start()
    deadline = time.monotonic() + 5
    while not pool._waiters and time.monotonic() < deadline:
        time.sleep(0.001)
    pool.release()  # head ticket now owns the slot, maybe not yet awake
    assert pool.acquire(1, optional=True) is False
    t.join()
    assert got == [True]
    pool.release()


def test_timeout_counter_in_stats():
    pool = SharedTaskPool()
    pool.acquire(1)
    with pytest.raises(ExecutionError, match="max_shared_pool_size"):
        pool.acquire(1, timeout=0.05)
    assert pool.stats()["timeouts"] == 1
    pool.release()


def test_queries_bounded_end_to_end(tmp_path):
    """Concurrent queries through the SQL surface respect the cap and
    the citus_stat_pool view reports it."""
    import dataclasses
    from citus_tpu.config import ExecutorSettings, Settings
    st = Settings(executor=ExecutorSettings(max_shared_pool_size=2))
    cl = ct.Cluster(str(tmp_path / "db"), settings=st)
    cl.execute("CREATE TABLE t (k bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 8)")
    cl.copy_from("t", rows=[(i, i) for i in range(20000)])
    results, errors = [], []

    def q():
        # a failure in a thread would otherwise show only as a short
        # ``results``: keep it, so that the test can say what failed
        try:
            results.append(cl.execute("SELECT sum(v) FROM t").rows[0][0])
        except BaseException:
            errors.append(traceback.format_exc())

    threads = [threading.Thread(target=q) for _ in range(6)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 240
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    alive = sum(t.is_alive() for t in threads)
    assert not errors and not alive, (
        f"{len(errors)} of 6 queries raised, {alive} still running after "
        f"240 s; the first:\n{errors[0] if errors else '(none raised)'}")
    assert results == [sum(range(20000))] * 6
    view = cl.execute("SELECT citus_stat_pool()")
    row = dict(zip(view.columns, view.rows[0]))
    assert row["pool_size"] == 2
    assert row["in_use"] == 0
    assert row["granted"] >= 6