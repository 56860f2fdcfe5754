"""Runtime concurrency sanitizer (citussan dynamic half): lock-order
inversion detection across threads, self-deadlock, wait-under-lock and
loop-thread findings, the off-mode zero-cost passthrough, and the two
regression fixes the static rules drove — RemoteTaskDispatch submitting
outside its bookkeeping lock, and rollup refresh/drop executing with no
lock held (subprocess, CITUS_SANITIZE=1)."""

import os
import subprocess
import sys
import threading

import pytest

from citus_tpu.utils import sanitizer


@pytest.fixture
def san():
    """Activate the sanitizer's record mode for this test only (no
    threading patch: wrapped locks are constructed explicitly)."""
    old_active, old_mode = sanitizer._ACTIVE, sanitizer._MODE
    sanitizer._ACTIVE, sanitizer._MODE = True, "record"
    sanitizer.reset()
    yield sanitizer
    sanitizer.reset()
    sanitizer._ACTIVE, sanitizer._MODE = old_active, old_mode


def mklock(site, reentrant=False):
    make = sanitizer._real_RLock if reentrant else sanitizer._real_Lock
    return sanitizer._SanLock(make(), site, reentrant)


def kinds(report):
    return [f["kind"] for f in report]


# ------------------------------------------------------ order tracking


def test_ab_ba_inversion_on_two_threads_reports_cycle(san):
    a = mklock("t.py:A")
    b = mklock("t.py:B")

    def order(first, second):
        with first:
            with second:
                pass

    t1 = threading.Thread(target=order, args=(a, b))
    t1.start()
    t1.join()
    assert san.report() == []  # one order alone is fine
    t2 = threading.Thread(target=order, args=(b, a))
    t2.start()
    t2.join()
    found = [f for f in san.report() if f["kind"] == "lock-order-cycle"]
    assert len(found) == 1
    assert "t.py:A" in found[0]["detail"]
    assert "t.py:B" in found[0]["detail"]


def test_consistent_order_across_threads_is_clean(san):
    a = mklock("t.py:A")
    b = mklock("t.py:B")

    def ab():
        with a:
            with b:
                pass

    threads = [threading.Thread(target=ab) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert san.report() == []


def test_three_lock_rotation_reports_cycle(san):
    locks = {s: mklock(f"t.py:{s}") for s in "ABC"}

    def nest(x, y):
        with locks[x]:
            with locks[y]:
                pass

    for pair in ("AB", "BC", "CA"):  # A->B, B->C, then C->A closes it
        t = threading.Thread(target=nest, args=tuple(pair))
        t.start()
        t.join()
    assert "lock-order-cycle" in kinds(san.report())


def test_blocking_reacquire_always_raises(san):
    a = mklock("t.py:A")
    with a:
        with pytest.raises(sanitizer.SanitizerError):
            a.acquire()
    assert kinds(san.report()) == ["self-deadlock"]  # recorded AND raised
    san.reset()
    # an RLock re-acquire is legal and clean
    r = mklock("t.py:R", reentrant=True)
    with r:
        with r:
            pass
    assert san.report() == []


# --------------------------------------------------- begin_wait seam


def test_begin_wait_under_lock_is_reported(san):
    a = mklock("t.py:A")
    with a:
        san.on_begin_wait("remote_rpc")
    rep = san.report()
    assert kinds(rep) == ["wait-under-lock"]
    assert "t.py:A" in rep[0]["detail"]
    assert "remote_rpc" in rep[0]["detail"]


def test_condition_backing_lock_is_exempt(san):
    mu = mklock("t.py:MU")
    cv = sanitizer._condition_factory(mu)  # marks mu cv-backed
    with cv:
        san.on_begin_wait("admission_wait")
    assert san.report() == []


def test_begin_wait_routed_from_stats_seam(san):
    from citus_tpu.stats import begin_wait, end_wait
    a = mklock("t.py:A")
    with a:
        end_wait(begin_wait("remote_rpc"))
    assert "wait-under-lock" in kinds(san.report())


# ------------------------------------------------------- loop thread


def test_contended_acquire_on_loop_thread_is_reported(san):
    a = mklock("t.py:A")
    ready = threading.Event()

    def loop_thread():
        san.register_loop_thread()
        ready.wait(5)
        with a:  # contended: main holds it
            pass
        san.unregister_loop_thread()

    t = threading.Thread(target=loop_thread)
    import time as _time
    deadline = _time.monotonic() + 10
    with a:
        t.start()
        ready.set()
        # the loop thread records BEFORE parking on the contended lock
        while not any(k == "loop-thread-block"
                      for k in kinds(san.report())):
            assert _time.monotonic() < deadline, san.report()
            _time.sleep(0.005)
    t.join(5)
    rep = [f for f in san.report() if f["kind"] == "loop-thread-block"]
    assert rep and "t.py:A" in rep[0]["detail"]


def test_begin_wait_on_loop_thread_is_reported(san):
    out = []

    def loop_thread():
        san.register_loop_thread()
        san.on_begin_wait("remote_rpc")
        san.unregister_loop_thread()
        out.append(True)

    t = threading.Thread(target=loop_thread)
    t.start()
    t.join(5)
    assert out == [True]
    assert "loop-thread-block" in kinds(san.report())


# -------------------------------------------------- off-mode passthrough


@pytest.mark.skipif(sanitizer.enabled(),
                    reason="suite running under CITUS_SANITIZE")
def test_off_mode_is_zero_cost_passthrough():
    # no patch installed: threading.Lock is the real C factory and the
    # stats seam's guard flag is a single False attribute read
    assert threading.Lock is sanitizer._real_Lock
    assert threading.RLock is sanitizer._real_RLock
    assert threading.Condition is sanitizer._real_Condition
    assert sanitizer._ACTIVE is False
    sanitizer.on_begin_wait("remote_rpc")  # no-op, records nothing
    assert sanitizer.report() == []


# ------------------------------------- regression: dispatch fan-out fix


class _StubLoop:
    """Records submits and whether the dispatch bookkeeping lock was
    held at submit time (the old shape held it across JSON encode)."""

    def __init__(self):
        self.calls = []
        self.dispatch = None
        self.locked_during_submit = []

    def submit(self, ep, method, task, done_cb=None):
        if self.dispatch is not None:
            self.locked_during_submit.append(
                self.dispatch._mu.locked())
        self.calls.append((ep, method, task, done_cb))


class _Fut:
    def __init__(self, meta, blob):
        self._v = (meta, blob)

    def result(self):
        return self._v


def test_remote_dispatch_never_submits_under_its_lock():
    from collections import deque

    from citus_tpu.config import Settings
    from citus_tpu.executor.pipeline import RemoteTaskDispatch, _NodePool

    class _NS:
        runtime_cache = {}

    class _Cat:
        class remote_data:
            @staticmethod
            def event_loop():
                return None

    from citus_tpu.executor.admission import GLOBAL_POOL
    held = GLOBAL_POOL.in_use
    d = RemoteTaskDispatch(_Cat(), _NS(), Settings(), [], False)
    loop = _StubLoop()
    loop.dispatch = d
    d._loop = loop
    pool = _NodePool()
    pool.window = 2
    pool.pending = deque(
        [(0, 0, ("h", 1), {"t": 0}), (1, 0, ("h", 1), {"t": 1})])
    d._nodes[0] = pool
    d._total = 2

    d._launch()
    assert len(loop.calls) == 2  # window 2: both planned and submitted
    assert loop.locked_during_submit == [False, False]
    assert d._inflight_total == 2  # accounting committed at plan time

    # completion path (this runs on the event-loop thread in prod):
    # bookkeeping under the lock, relaunch AFTER releasing it
    pool.pending = deque([(2, 0, ("h", 1), {"t": 2})])
    d._total = 3
    cb = loop.calls[0][3]
    cb(_Fut({}, b"frame"))
    assert len(loop.calls) == 3  # completion relaunched the pending task
    assert loop.locked_during_submit == [False, False, False]
    assert d._settled == 1 and 0 in d._raw
    # settle what is still in flight: each such RPC holds an optional
    # slot of the PROCESS-WIDE pool, and a slot left behind here starves
    # the next test of this worker that caps the pool
    # (test_admission.py::test_queries_bounded_end_to_end, ROADMAP C12)
    for call in loop.calls[1:]:
        call[3](_Fut({}, b"frame"))
    assert d._inflight_total == 0 and GLOBAL_POOL.in_use == held


def test_remote_dispatch_abort_waits_out_planned_tasks():
    from collections import deque

    from citus_tpu.config import Settings
    from citus_tpu.executor.pipeline import RemoteTaskDispatch, _NodePool

    class _NS:
        runtime_cache = {}

    class _Cat:
        class remote_data:
            @staticmethod
            def event_loop():
                return None

    d = RemoteTaskDispatch(_Cat(), _NS(), Settings(), [], False)
    loop = _StubLoop()
    d._loop = loop
    pool = _NodePool()
    pool.pending = deque([(0, 0, ("h", 1), {"t": 0})])
    d._nodes[0] = pool
    d._total = 1
    d._launch()
    assert d._inflight_total == 1
    done = []

    def aborter():
        d.abort()
        done.append(True)

    t = threading.Thread(target=aborter)
    t.start()
    t.join(0.2)
    assert not done  # abort() blocks on the in-flight task...
    loop.calls[0][3](_Fut({}, b"x"))  # ...until its done_cb settles it
    t.join(5)
    assert done and d._inflight_total == 0


# ------------------------- regression: rollup refresh fix (subprocess)


_ROLLUP_CHILD = r"""
import sys
import citus_tpu as ct
from citus_tpu.config import Settings
from citus_tpu.utils import sanitizer
from citus_tpu import stats

assert sanitizer.enabled(), "CITUS_SANITIZE did not activate"

cl = ct.Cluster(sys.argv[1],
                settings=Settings(enable_change_data_capture=True,
                                  start_maintenance_daemon=False))
cl.execute("INSERT INTO ev VALUES (1, 'kx', 5.0, 3), (2, 'ky', 6.0, 4)")

orig = cl.execute
def parked_execute(sql, *a, **k):
    # simulate the admission controller parking this statement: under
    # the OLD refresh shape this bracket opens while _refresh_mu is
    # held and the sanitizer reports wait-under-lock
    tok = stats.begin_wait("admission_wait")
    try:
        return orig(sql, *a, **k)
    finally:
        stats.end_wait(tok)
cl.execute = parked_execute

folded = cl.rollup_manager.refresh_once("ev_r")
assert folded, "refresh folded nothing"
cl.execute = orig
cl.rollup_manager.drop_rollup("ev_r")

bad = [f for f in sanitizer.report()
       if f["kind"] in ("wait-under-lock", "lock-order-cycle")]
if bad:
    print("SANITIZER FINDINGS:", bad, file=sys.stderr)
    sys.exit(1)
cl.close()
print("OK")
"""


def test_rollup_refresh_holds_no_lock_across_execute(tmp_path):
    """Under CITUS_SANITIZE=1, a refresh whose execute() parks in
    admission must NOT be holding any rollup-manager lock (the old
    _refresh_mu-across-execute shape fails this)."""
    import numpy as np

    import citus_tpu as ct
    from citus_tpu.config import Settings

    db = str(tmp_path / "db")
    cl = ct.Cluster(db, n_nodes=1,
                    settings=Settings(enable_change_data_capture=True,
                                      start_maintenance_daemon=False))
    cl.execute("CREATE TABLE ev (tid bigint NOT NULL, kind text, "
               "v double, code bigint)")
    cl.execute("SELECT create_distributed_table('ev', 'tid', 4)")
    cl.copy_from("ev", columns={
        "tid": np.arange(40, dtype=np.int64) % 4,
        "kind": np.array(["k%d" % (i % 3) for i in range(40)], object),
        "v": np.linspace(1.0, 5.0, 40),
        "code": np.zeros(40, dtype=np.int64)})
    cl.execute("SELECT citus_create_rollup('ev_r', 'ev', 'tid', "
               "'count(*), sum(v)')")
    cl.close()

    env = dict(os.environ, JAX_PLATFORMS="cpu", CITUS_SANITIZE="1")
    r = subprocess.run([sys.executable, "-c", _ROLLUP_CHILD, db],
                       env=env, timeout=300, capture_output=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr.decode()[-3000:]
    assert b"OK" in r.stdout


# --------------------- representative stress run under CITUS_SANITIZE=1


_STRESS_CHILD = r"""
import sys, threading
import numpy as np
import citus_tpu as ct
from citus_tpu.config import Settings
from citus_tpu.utils import sanitizer

assert sanitizer.enabled()
cl = ct.Cluster(sys.argv[1], n_nodes=2,
                settings=Settings(start_maintenance_daemon=False))
cl.execute("CREATE TABLE t (k bigint NOT NULL, v double)")
cl.execute("SELECT create_distributed_table('t', 'k', 8)")
cl.copy_from("t", columns={
    "k": np.arange(400, dtype=np.int64) % 50,
    "v": np.linspace(0.0, 1.0, 400)})

errors = []
def worker(i):
    try:
        for q in range(4):
            res = cl.execute(
                "SELECT k, count(*), sum(v) FROM t "
                "WHERE k >= %d GROUP BY k" % (i % 5))
            assert res.rows
    except Exception as e:  # surfaced below; the thread must not die silently
        errors.append(repr(e))

threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
for t in threads: t.start()
for t in threads: t.join(120)
cl.close()
assert not errors, errors
findings = sanitizer.report()
if findings:
    print("SANITIZER FINDINGS:", findings, file=sys.stderr)
    sys.exit(1)
print("CLEAN")
"""


def test_multithreaded_stress_is_sanitizer_clean(tmp_path):
    """Six concurrent query threads over a 2-node cluster under
    CITUS_SANITIZE=1: the fan-out, scheduler, stats, and megabatch
    interplay must leave an empty citus_sanitizer_report()."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", CITUS_SANITIZE="1")
    r = subprocess.run(
        [sys.executable, "-c", _STRESS_CHILD, str(tmp_path / "db")],
        env=env, timeout=540, capture_output=True,
        cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, (r.stdout.decode()[-1000:],
                               r.stderr.decode()[-3000:])
    assert b"CLEAN" in r.stdout
