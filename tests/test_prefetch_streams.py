"""Several of a scan's streams decoded at once, the batches handed on
in the one-thread order (executor/pipeline.py ``one_after_another`` /
``in_rounds`` / ``_Producers`` under ``HostPrefetcher``).

Events and barriers decide every outcome here: a delay only makes the
interesting interleaving likely, no assertion reads a clock.
"""

import functools
import threading
import time

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import batches as B
from citus_tpu.executor import executor as X
from citus_tpu.executor import pipeline as P
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.storage import reader as R
from citus_tpu.testing.faults import FAULTS

WAIT = 30.0     # every wait of this file is bounded; none is expected to run out


def decode_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("citus-host-decode")]


@pytest.fixture(autouse=True)
def _no_thread_left_behind():
    yield
    FAULTS.disarm()
    deadline = time.monotonic() + WAIT
    while decode_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not decode_threads()


def numbered(stream, n, slow=0.0, fail_at=None, started=None):
    """Items ``(stream, 0..n-1)``; ``slow`` seconds before each."""
    def gen():
        if started is not None:
            started.append((stream, threading.current_thread().name))
        for k in range(n):
            if slow:
                time.sleep(slow)
            if fail_at == k:
                raise RuntimeError(f"stream {stream} rotted at {k}")
            yield (stream, k)
    return gen()


# ----------------------------------------------------- the budget's rule


@pytest.mark.parametrize("cores,streams,set_threads,want", [
    (13, 8, 0, (3, 4)),      # the one-chip machine: three producers of four
    (30, 4, 0, (4, 7)),      # the four-chip host: one a device stream
    (8, 8, 0, (2, 4)),
    (2, 8, 0, (1, 2)),       # two cores: today's one thread
    (4, 8, 0, (1, 4)),
    (64, 1, 0, (1, 8)),      # one stream: one producer whatever the cores
    (64, 8, 0, (8, 8)),
    (13, 8, 6, (2, 6)),      # citus.decode_threads keeps its meaning: a call's
    (13, 8, 13, (1, 13)),
])
def test_producers_and_native_threads_stay_inside_the_cores(
        monkeypatch, cores, streams, set_threads, want):
    monkeypatch.setattr(R, "usable_cores", lambda: cores)
    monkeypatch.setattr(R, "_DECODE_THREADS", set_threads)
    n = R.decode_producers(streams)
    with R.decode_pool_shared(n):
        per_call = R.decode_thread_count()
    assert (n, per_call) == want
    assert n == 1 or n * per_call <= cores
    assert R.decode_thread_count() == (set_threads or min(8, cores))


# ------------------------------------------------ the order, on its own


@pytest.mark.parametrize("n_streams", [1, 2, 5, 8])
@pytest.mark.parametrize("order", ["one_after_another", "in_rounds"])
def test_the_sequence_is_the_inline_sequence(producers, n_streams, order):
    """The stream due first is the slowest: every later one is done
    before it, and the consumer still gets them in turn."""
    producers(4)
    lengths = [3, 1, 0, 4, 2, 3, 1, 2][:n_streams]

    def source():
        streams = [numbered(s, n, slow=0.02 if s == 0 else 0.0)
                   for s, n in enumerate(lengths)]
        if order == "one_after_another":
            return P.one_after_another(streams)
        return (m for members in P.in_rounds(streams) for m in members)

    inline = list(P.prefetch_batches(source(), 0))
    stats = P.PipelineStats()
    pf = P.prefetch_batches(source(), 2, stats)
    try:
        piped = list(pf)
    finally:
        pf.close()
    assert piped == inline
    assert sum(m is not None for m in piped) == sum(lengths)
    assert stats.figures["decode_streams"] <= min(4, max(1, n_streams))
    if n_streams == 1:
        assert stats.figures["decode_streams"] == 1
        assert stats.figures["decode_overlap_ms"] == 0


def test_rounds_hold_device_i_at_place_i(producers):
    producers(3)        # fewer producers than streams: they take turns
    lengths = [4, 0, 2, 5]
    rounds = []
    pf = P.HostPrefetcher(
        (list(m) for m in P.in_rounds(
            [numbered(s, n, slow=0.005 * (4 - s))
             for s, n in enumerate(lengths)])), 2)
    try:
        rounds = list(pf)
    finally:
        pf.close()
    assert len(rounds) == max(lengths)
    for r, members in enumerate(rounds):
        assert members == [(s, r) if r < n else None
                           for s, n in enumerate(lengths)]


def test_an_error_surfaces_in_its_turn(producers):
    """Stream 3 fails at once while streams 1 and 2 are still slow:
    the consumer gets all of 1 and 2, then the error, nothing of 4."""
    producers(4)
    started = []
    streams = [numbered(1, 3, slow=0.02, started=started),
               numbered(2, 2, slow=0.02, started=started),
               numbered(3, 2, fail_at=0, started=started),
               numbered(4, 2, started=started)]
    pf = P.HostPrefetcher(P.one_after_another(streams), 2)
    got = []
    try:
        with pytest.raises(RuntimeError, match="stream 3 rotted at 0"):
            for item in pf:
                got.append(item)
    finally:
        pf.close()
    assert got == [(1, 0), (1, 1), (1, 2), (2, 0), (2, 1)]
    # several streams were opened beside stream 1, each by a producer
    assert len(started) >= 2
    assert {name.rsplit("-", 1)[0] for _, name in started} == \
        {"citus-host-decode"}
    assert all(name != "citus-host-decode" for _, name in started)


# --------------------------------------------------- memory and shutdown


class Counted:
    """Items that know how many of them are alive: made on ``next``,
    dropped when the consumer says so."""

    def __init__(self):
        self.mu = threading.Lock()
        self.alive = self.peak = self.made = 0

    def stream(self, s, n):
        for k in range(n):
            with self.mu:
                self.alive += 1
                self.made += 1
                self.peak = max(self.peak, self.alive)
            yield (s, k)

    def drop(self):
        with self.mu:
            self.alive -= 1


@pytest.mark.parametrize("n_producers,depth", [(2, 1), (4, 2), (3, 3)])
def test_batches_alive_at_once_are_bounded(producers, n_producers, depth):
    """The consumer takes ONE item and then sits still: the decode side
    fills what it may and stops -- at most 2 x producers in the
    producers' hands, ``depth`` in the queue, one in the pulling
    thread's and the one the consumer holds."""
    producers(n_producers)
    c = Counted()
    pf = P.HostPrefetcher(P.one_after_another(
        [c.stream(s, 50) for s in range(8)]), depth)
    try:
        first = next(pf)
        assert first == (0, 0)
        bound = 2 * n_producers + depth + 1 + 1
        # the decode side runs until every place is taken, and no further
        deadline = time.monotonic() + WAIT
        while c.made < bound and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)     # room for a producer that would overstep
        assert c.made == bound and c.peak == bound
        # every item the consumer lets go of makes room for one more
        for k in range(1, 40):
            c.drop()
            assert next(pf) == (0, k)
            assert c.peak <= bound
    finally:
        t0 = time.monotonic()
        pf.close()
        assert time.monotonic() - t0 < WAIT
    # close() returned with every producer held back by the budget
    assert not decode_threads()
    assert c.made < 8 * 50


def test_a_source_that_takes_its_streams_out_of_turn_still_goes_on(producers):
    """Two scans adopted at once and pulled alternately: the first
    fills every place it may, the second's items are still made the
    moment the pulling thread waits for them."""
    producers(2)

    def both():
        a = P.one_after_another([numbered("a", 30), numbered("a2", 30)])
        b = P.one_after_another([numbered("b", 5), numbered("b2", 5)])
        first = next(a)             # adopts a: its producers run ahead
        yield first
        yield from b                # while a sits on every place
        yield from a

    pf = P.HostPrefetcher(both(), 1)
    got = []
    worker = threading.Thread(target=lambda: got.extend(pf))
    worker.start()
    worker.join(WAIT)
    alive = worker.is_alive()
    pf.close()
    assert not alive
    assert got == [("a", 0)] + [(s, k) for s in ("b", "b2") for k in range(5)] \
        + [("a", k) for k in range(1, 30)] + [("a2", k) for k in range(30)]


def test_close_cancels_producers_inside_a_slow_batch(producers):
    producers(4)
    inside, release = threading.Event(), threading.Event()

    def slow(s):
        yield (s, 0)
        inside.set()
        release.wait(WAIT)
        yield (s, 1)

    pf = P.HostPrefetcher(P.one_after_another([slow(s) for s in range(4)]), 1)
    assert next(pf) == (0, 0)
    assert inside.wait(WAIT)
    closer = threading.Thread(target=pf.close)
    closer.start()
    release.set()
    closer.join(WAIT)
    assert not closer.is_alive() and not decode_threads()


def test_one_stream_or_depth_zero_start_no_second_thread(producers):
    producers(8)
    seen = []

    def watched(s, n):
        for k in range(n):
            seen.append({t.name for t in decode_threads()})
            yield (s, k)

    pf = P.prefetch_batches(P.one_after_another([watched(0, 4)]), 2)
    try:
        assert list(pf) == [(0, k) for k in range(4)]
    finally:
        pf.close()
    assert seen == [{"citus-host-decode"}] * 4
    del seen[:]
    inline = P.prefetch_batches(
        P.one_after_another([watched(s, 2) for s in range(3)]), 0)
    assert len(list(inline)) == 6
    inline.close()
    assert seen == [set()] * 6


def test_a_machine_of_two_cores_takes_the_one_thread_path(monkeypatch):
    monkeypatch.setattr(R, "usable_cores", lambda: 2)
    names = []

    def watched(s):
        names.append(threading.current_thread().name)
        yield (s, 0)

    pf = P.HostPrefetcher(P.one_after_another(
        [watched(s) for s in range(4)]), 2)
    try:
        assert list(pf) == [(s, 0) for s in range(4)]
    finally:
        pf.close()
    assert names == ["citus-host-decode"] * 4


def test_thread_time_is_summed_over_the_producers(producers):
    """host_decode_s: what the producers spent inside their streams,
    not what the pulling thread spent waiting for them (each of 4
    streams sleeps 4 x 50 ms: 0.8 s of thread-time whatever the wall
    time; the sleeps are floors, so the assertion is one-sided)."""
    producers(4)
    stats = P.PipelineStats()
    pf = P.HostPrefetcher(P.one_after_another(
        [numbered(s, 4, slow=0.05) for s in range(4)]), 2, stats)
    try:
        assert len(list(pf)) == 16
    finally:
        pf.close()
    assert stats.host_decode_s >= 16 * 0.05
    assert stats.figures["decode_streams"] >= 2
    assert stats.figures["decode_overlap_ms"] > 0


def test_sixteen_producers_on_eight_cores_lose_nothing(producers):
    """More producers than cores and a thread switch every 10 us: every
    item arrives once and in turn, the figures booked from the
    producers' threads add up (a lost update would show), nothing is
    left alive."""
    import sys
    producers(16)
    n_streams, n_items = 32, 150
    stats = P.PipelineStats()

    def stream(s):
        for k in range(n_items):
            stats.tally("batch_rows_real", 1, add=True)
            yield (s, k)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pf = P.HostPrefetcher(P.one_after_another(
            [stream(s) for s in range(n_streams)]), 2, stats)
        done = []
        worker = threading.Thread(target=lambda: done.append(list(pf)))
        worker.start()
        worker.join(WAIT * 4)
        assert not worker.is_alive()
        pf.close()
    finally:
        sys.setswitchinterval(before)
    assert done[0] == [(s, k) for s in range(n_streams)
                       for k in range(n_items)]
    assert stats.figures["batch_rows_real"] == n_streams * n_items
    assert stats.figures["decode_streams"] <= 16
    assert pf._producers._alive == 0 and pf._producers._inside == 0


# ------------------------------------------------------- through the SQL


def _table(cl, name, shards, n=24000):
    cl.execute(f"CREATE TABLE {name} (k bigint NOT NULL, v bigint, "
               f"f double precision)")
    cl.execute(f"SELECT create_distributed_table('{name}', 'k', {shards})")
    rng = np.random.default_rng(shards)
    cl.copy_from(name, columns={"k": np.arange(n), "v": np.arange(n) * 3,
                                "f": rng.standard_normal(n) * 1e6})
    return n


def _plan(cl, sql):
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    return plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]))


def _stream(cl, plan, depth):
    pf = P.prefetch_batches(
        X._iter_padded_batches(cl.catalog, plan, cl.settings,
                               P.PipelineStats()), depth)
    try:
        return list(pf)
    finally:
        pf.close()


@pytest.mark.parametrize("shards", [1, 2, 5, 8])
def test_a_scans_batches_arrive_as_inline_batch_for_batch(
        tmp_cluster, producers, monkeypatch, shards):
    """Several batches a shard, the first shard's decode slowed at the
    fault point: shard by shard, a shard's batches in file order, the
    arrays to the byte."""
    producers(4)
    cl = tmp_cluster
    _table(cl, "sq", shards)
    monkeypatch.setattr(X, "load_padded_batches", functools.partial(
        B.load_padded_batches, max_batch_rows=1000))
    plan = _plan(cl, "SELECT count(*), sum(v), sum(f) FROM sq")
    inline = _stream(cl, plan, 0)
    first = plan.bound.table.shards[plan.shard_indexes[0]].shard_id
    FAULTS.arm("decode_batch", delay_s=0.01, match=f"sq:{first}")
    piped = _stream(cl, plan, 2)
    FAULTS.disarm()
    assert len(piped) == len(inline) > shards
    order = [b.shard_index for b in inline]
    assert order == sorted(order) and len(set(order)) == shards
    for got, want in zip(piped, inline):
        assert (got.shard_index, got.n_rows, got.padded_rows) == \
            (want.shard_index, want.n_rows, want.padded_rows)
        for a, b in zip(got.cols + got.valids + (got.row_mask,),
                        want.cols + want.valids + (want.row_mask,)):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("n_dev", [1, 4])
def test_a_float_sum_has_the_same_bits_at_one_and_at_four_producers(
        tmp_cluster, producers, limit_devices, n_dev):
    limit_devices(n_dev)
    cl = tmp_cluster
    _table(cl, "fs", 8)
    q = "SELECT count(*), sum(f), avg(f), min(f), sum(v) FROM fs"
    rows = {}
    for n in (1, 4):
        producers(n)
        GLOBAL_CACHE.clear()
        GLOBAL_COUNTERS.reset()
        r = cl.execute(q)
        rows[n] = r.rows
        pl = r.explain["pipeline"]
        assert pl["decode_streams"] == (1 if n == 1 else
                                        GLOBAL_COUNTERS.snapshot()
                                        ["decode_streams"])
        if n == 1:
            assert pl["decode_overlap_ms"] == 0
    assert np.array(rows[1][0][1:4]).tobytes() == \
        np.array(rows[4][0][1:4]).tobytes()
    assert rows[1] == rows[4]


def test_every_producer_reads_the_transactions_staged_rows(tmp_cluster,
                                                           producers,
                                                           limit_devices):
    """A statement inside a transaction sees its own staged rows of
    every shard, whichever producer decodes the shard."""
    limit_devices(1)
    producers(4)
    cl = tmp_cluster
    n = _table(cl, "tx", 8)
    q = "SELECT count(*), sum(v) FROM tx"
    base = (n, 3 * n * (n - 1) // 2)
    assert cl.execute(q).rows == [base]
    s = cl.session()
    s.execute("BEGIN")
    extra = list(range(10 ** 6, 10 ** 6 + 64))      # lands in every shard
    for k in extra:
        s.execute(f"INSERT INTO tx VALUES ({k}, {k}, 0.0)")
    FAULTS.arm("decode_batch", delay_s=0.005, match="tx")
    seen = s.execute(q)
    FAULTS.disarm()
    assert seen.rows == [(n + len(extra), base[1] + sum(extra))]
    assert seen.explain["pipeline"]["decode_streams"] >= 2
    # nobody else sees them, and after the rollback neither does it
    assert cl.execute(q).rows == [base]
    s.execute("ROLLBACK")
    assert s.execute(q).rows == [base]


def test_the_affine_mesh_keeps_device_i_at_member_i(tmp_cluster, producers,
                                                    limit_devices,
                                                    monkeypatch):
    """GROUP BY the distribution column on four devices: one table a
    device, disjoint because a shard only ever meets its own device --
    with the device streams decoded side by side."""
    from citus_tpu.executor.scan_loop import AffineMeshPlacement
    limit_devices(4)
    producers(4)
    cl = tmp_cluster
    n, step = 16000, 10 ** 9     # keys too far apart for a direct table
    cl.execute("CREATE TABLE af (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('af', 'k', 8)")
    cl.copy_from("af", columns={"k": np.arange(n) * step,
                                "v": np.arange(n) * 3})
    monkeypatch.setattr(X, "load_padded_batches", functools.partial(
        B.load_padded_batches, max_batch_rows=500))
    rounds = []
    real = AffineMeshPlacement.book

    def spy(self, members, inputs, nbytes, round_s, dispatch_s):
        rounds.append([None if m is None else m.shard_index
                       for m in members])
        return real(self, members, inputs, nbytes, round_s, dispatch_s)

    monkeypatch.setattr(AffineMeshPlacement, "book", spy)
    q = "SELECT k, sum(v) FROM af GROUP BY k HAVING sum(v) > 47000"
    FAULTS.arm("decode_batch", delay_s=0.002, match="af")
    r = cl.execute(q)
    FAULTS.disarm()
    assert sorted(r.rows) == [(k * step, 3 * k) for k in range(n)
                              if 3 * k > 47000]
    pl = r.explain["pipeline"]
    assert pl["hash_tables"] == 4 and pl["hash_disjoint_on"] == "k"
    assert pl["decode_streams"] >= 2
    assert len(rounds) > 4
    for members in rounds:
        assert len(members) == 4
        for d, si in enumerate(members):
            assert si is None or si * 4 // 8 == d
    # each device's shards in order, a shard's batches together
    for d in range(4):
        mine = [m[d] for m in rounds if m[d] is not None]
        assert mine == sorted(mine) and set(mine) == {2 * d, 2 * d + 1}
