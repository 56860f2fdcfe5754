"""Pipelined adaptive executor: the two wall-clock overlaps the
reference's adaptive executor gets from connection-level concurrency.

Reference: AdaptiveExecutor (adaptive_executor.c:775) keeps one
connection pool PER WORKER NODE, growing each pool from one connection
toward citus.max_adaptive_executor_pool_size by slow-start (README:
1670-1688), all pools bounded globally by citus.max_shared_pool_size's
shared-memory counters — so a multi-host query costs the *max* of the
per-host times, not the sum.  SURVEY §2.4 maps "intra-node multi-core
parallelism / pipelined ingest" to XLA async streams; this module is
the host half of that lowering.

Two pieces:

- ``dispatch_remote_tasks`` / ``RemoteTaskDispatch``: fan out
  ``execute_task`` RPCs through the coordinator's single event loop
  (net/event_loop.py, the WaitEventSet analog — O(1) dispatcher
  threads no matter how wide the fan-out) with a per-node in-flight
  window (slow-start: each node starts at 1 and ramps toward
  ``citus.max_adaptive_executor_pool_size`` on successes), each extra
  concurrent RPC taking an OPTIONAL slot from the cross-query
  ``citus.max_shared_pool_size`` pool (denied = stay at the current
  width).  The caller dispatches first, scans local placements while
  the RPCs fly, and collects as they complete — result decode happens
  on the collecting thread, not the loop; per-task failures fall back
  to the local pull path exactly like the serial dispatcher did.
- ``prefetch_batches`` / ``HostPrefetcher``: a bounded read-ahead
  queue filled by a background thread with padded ``ShardBatch``es
  (chunk decompress, null decode, pad) while the device executes the
  previous round — backpressure at ``citus.executor_prefetch_depth``,
  errors from the decode side re-raised at the consumer, prompt
  cancellation when the consumer dies.  Depth 0 decodes inline (the
  pre-pipeline serial behavior).  A scan's shards (a mesh's devices)
  are independent streams (``one_after_another`` / ``in_rounds``):
  where there are several and the cores allow it, producer threads
  decode several at once and the background thread hands the batches
  on in exactly the order one thread would have made them.
"""

from __future__ import annotations

import contextlib
import queue
import threading
from collections import deque
from typing import Iterator, Optional

from citus_tpu.errors import ExecutionError
from citus_tpu.observability import trace as _trace
from citus_tpu.observability.trace import clock as _perf
from citus_tpu.stats import begin_wait, end_wait


class PipelineStats:
    """One execution's record: everything ONE run of a statement counted
    and logged, made where the run starts and handed down by argument
    (the cached plan is shared by every caller of its family and keeps
    compiled kernels alone).  The decode side owns host_decode_s /
    device_stalls (its pulling thread alone writes them), the consumer
    owns the rest; ``tally`` may be called from any thread (a mesh's
    device streams book their batches on the producers that decode
    them).  Read only after the pipeline is joined."""

    def __init__(self) -> None:
        self.host_decode_s = 0.0   # thread-time inside the host decode iterators
        self.device_s = 0.0        # H2D transfer + kernel dispatch + sync
        self.h2d_bytes = 0         # bytes shipped host -> device
        self.host_stalls = 0       # consumer found the queue empty
        self.device_stalls = 0     # producer found the queue full
        self.rounds = 0            # device rounds dispatched
        self.window_peak_bytes = 0  # most un-synced streamed bytes on device
        # what becomes Result.explain["pipeline"], EXPLAIN ANALYZE's
        # Pipeline: / Direct: / Hash: lines and the execute span's attr
        self.figures: dict = {}
        self._tally_mu = threading.Lock()
        # the load ledger's input (_finish_select, run_worker_task)
        self.task_times: list = []       # (shard index, rows, dispatch s)
        self.task_bytes: list = []       # (shard index, H2D bytes)
        self.mesh_task_times: list = []  # a mesh round's s, split by member
        self.remote_tasks: list = []     # (si, node, bytes, rpc s, decode s)

    def tally(self, name: str, amount, add: bool = False):
        """A figure that is also a process counter, booked once: the
        statement's ``figures[name]`` becomes ``amount`` (with ``add``,
        grows by it) and the counter ``name`` is bumped by the same."""
        from citus_tpu.executor.executor import GLOBAL_COUNTERS
        with self._tally_mu:
            self.figures[name] = amount + (self.figures.get(name, 0) if add
                                           else 0)
        GLOBAL_COUNTERS.bump(name, amount)
        return amount

    def book_timings(self) -> None:
        """The timings of a streamed scan into the figures, once its
        pipeline is joined, and its stalls into the process counters
        (what an earlier scan of this execution booked is in the
        figures already)."""
        from citus_tpu.executor.executor import GLOBAL_COUNTERS
        pl = self.figures
        GLOBAL_COUNTERS.bump("pipeline_host_stalls",
                             self.host_stalls - pl.get("host_stalls", 0))
        GLOBAL_COUNTERS.bump("pipeline_device_stalls",
                             self.device_stalls - pl.get("device_stalls", 0))
        pl.update(
            host_decode_ms=round(self.host_decode_s * 1000, 3),
            device_ms=round(self.device_s * 1000, 3),
            h2d_bytes=int(self.h2d_bytes),
            host_stalls=int(self.host_stalls),
            device_stalls=int(self.device_stalls))

    def book_streams(self, streams: int, overlap_s: float) -> None:
        """A streamed scan's decode side, once it is done: the threads
        that decoded its batches side by side (1: one thread did) and
        the wall time two or more of them were inside a batch."""
        self.tally("decode_streams", streams)
        self.tally("decode_overlap_ms", int(overlap_s * 1000), add=True)

    def rider(self) -> "PipelineStats":
        """A megabatched group's record as a rider other than the first
        takes it: the ONE device run's figures, its own copy (each rider
        adds its own ending's); no task log -- the group's device work
        is booked to the load ledger once, by the first."""
        r = PipelineStats()
        r.figures = dict(self.figures)
        return r


def read_ahead_depth(settings) -> int:
    """Host read-ahead queue depth (citus.executor_prefetch_depth);
    0 disables the decode thread entirely."""
    return max(0, settings.executor.executor_prefetch_depth)


# ------------------------------------------------- host/device overlap


def _close_iter(it) -> None:
    close = getattr(it, "close", None)
    if close is not None:
        close()


class _InlineHostIter:
    """Depth-0 degenerate prefetcher: decode inline on the consumer
    thread (the serial pre-pipeline behavior), still timing the host
    half so EXPLAIN stays comparable."""

    def __init__(self, source: Iterator, stats: Optional[PipelineStats]):
        self._source = iter(source)
        self._stats = stats
        if stats is not None:
            stats.book_streams(1, 0.0)      # the caller's thread, alone

    def __iter__(self):
        return self

    def __next__(self):
        t0 = _perf()
        try:
            return next(self._source)
        finally:
            if self._stats is not None:
                self._stats.host_decode_s += _perf() - t0

    def close(self) -> None:
        _close_iter(self._source)


class _Cancelled(BaseException):
    """The prefetcher was closed under a pull of its source: unwinds
    the pulling thread through the generators it was in."""


# .producers: the ``_Producers`` of the HostPrefetcher whose pulling
# thread this is (on every other thread: nothing)
_pulling = threading.local()

_ITEM, _DONE, _ERR = 0, 1, 2


class _Streams:
    """Iterators that share no state -- a scan's shards, a mesh's
    devices -- behind ``takers``, one a stream, that hand out what the
    stream would.  Whoever sequences the takers decides the order; this
    only needs to know which it is: stream after stream, or
    (``rounds``) one item of each in turn.  Pulled on a prefetcher's
    pulling thread where the cores allow it, the streams are decoded by
    that prefetcher's producers, several at once and ahead of their
    turn; anywhere else each ``next`` of a taker is the stream's own,
    on the caller's thread."""

    def __init__(self, streams: list, rounds: bool):
        self.streams = [iter(s) for s in streams]
        self.rounds = rounds
        self.takers = [_Taker(self, i) for i in range(len(self.streams))]
        self._by: Optional[_Producers] = None
        n = len(self.streams)
        # the producers' side, under their lock once adopted
        self.ready = [deque() for _ in range(n)]
        self.busy = [False] * n     # a producer is inside next(stream)
        self.ended = [False] * n    # the stream has nothing more to make
        self.made = [0] * n         # items asked of it

    def take(self, i: int):
        if self._by is None:
            pool = getattr(_pulling, "producers", None)
            if pool is None or not pool.adopt(self):
                return next(self.streams[i])
            self._by = pool
        return self._by.take(self, i)

    def close(self) -> None:
        if self._by is not None:
            self._by.release(self)
        for s in self.streams:
            _close_iter(s)


class _Taker:
    __slots__ = ("_of", "_i")

    def __init__(self, of: _Streams, i: int):
        self._of, self._i = of, i

    def __iter__(self):
        return self

    def __next__(self):
        return self._of.take(self._i)


def one_after_another(streams: list) -> Iterator:
    """Every item of every stream, stream after stream (a scan's shards
    in ``plan.shard_indexes`` order, a shard's batches in file order):
    the sequence whatever thread made the items."""
    turns = _Streams(streams, rounds=False)
    try:
        for taker in turns.takers:
            yield from taker
    finally:
        turns.close()


def in_rounds(streams: list) -> Iterator[list]:
    """Round after round of one item a stream, None for a stream that
    has ended, until every stream has: member ``i`` of round ``r`` is
    stream ``i``'s ``r``-th item, whatever thread made it."""
    turns = _Streams(streams, rounds=True)
    try:
        while True:
            members = [next(taker, None) for taker in turns.takers]
            if all(m is None for m in members):
                return
            yield members
    finally:
        turns.close()


class _Producers:
    """The threads ``citus-host-decode-<n>`` of one prefetcher: each
    asks the stream whose next item is due soonest, and that nobody is
    inside, for ONE item, then looks again -- so the streams advance in
    the order their items will be taken, whatever the ratio of streams
    to threads.  Started when the pulling thread first meets streams
    worth more than one thread (``decode_producers``: the streams and
    the usable cores decide), each under the statement's transaction
    overlay and trace context, each telling the native pool that it
    shares the cores.

    Memory: items made and not yet taken plus items in the making never
    exceed ``2 x threads`` -- one in the making and one waiting for its
    turn a thread; a producer that finds the budget spent waits until
    the pulling thread takes an item (or waits for one nobody has made:
    a source that takes its streams in another order than it said)."""

    def __init__(self, txn, trace_ctx):
        self._txn, self._trace_ctx = txn, trace_ctx
        self._cv = threading.Condition()
        self._threads: list[threading.Thread] = []
        self._adopted: list[_Streams] = []
        self._cancelled = False
        self._alive = 0          # items in the making or waiting
        self._wanted = None      # (streams, i) the pulling thread waits for
        self._inside = 0         # producers inside a next(stream)
        self._overlap_from = 0.0
        self._unsettled_s = 0.0  # producers' thread-time less the puller's waits
        self.overlap_s = 0.0     # wall time with two or more inside
        self.decoded: set = set()   # producers that made a batch

    # ---- the pulling thread ----
    def adopt(self, streams: _Streams) -> bool:
        from citus_tpu.storage.reader import decode_producers
        want = decode_producers(len(streams.streams))
        if want < 2:
            return False
        with self._cv:
            if self._cancelled:
                raise _Cancelled()
            self._adopted.append(streams)
            while len(self._threads) < want:
                t = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"citus-host-decode-{len(self._threads) + 1}")
                self._threads.append(t)
                t.start()
            self._cv.notify_all()
        return True

    def take(self, streams: _Streams, i: int):
        ready = streams.ready[i]
        with self._cv:
            if not ready:
                t0 = _perf()
                self._wanted = (streams, i)
                self._cv.notify_all()
                while not ready:
                    if self._cancelled:
                        raise _Cancelled()
                    self._cv.wait(0.5)
                self._wanted = None
                self._unsettled_s -= _perf() - t0
            kind, val = ready[0]
            if kind == _DONE:       # stays: every later take ends so
                raise StopIteration
            ready.popleft()
            if kind == _ERR:
                ready.append((_DONE, None))
                raise val
            self._alive -= 1
            self._cv.notify_all()
            return val

    def release(self, streams: _Streams) -> None:
        """Forget ``streams`` (exhausted, or closed under the
        producers): returns once no producer is inside one of them."""
        with self._cv:
            if streams in self._adopted:
                self._adopted.remove(streams)
            while any(streams.busy):
                self._cv.wait(0.5)
            for q in streams.ready:
                self._alive -= sum(kind == _ITEM for kind, _ in q)
                q.clear()
            self._cv.notify_all()

    def settle(self) -> float:
        """Thread-time the producers spent decoding since the last call,
        less what the pulling thread spent waiting for them."""
        with self._cv:
            s, self._unsettled_s = self._unsettled_s, 0.0
        return s

    def stop(self) -> None:
        with self._cv:
            self._cancelled = True
            self._cv.notify_all()

    def join(self) -> None:
        for t in self._threads:
            t.join()

    # ---- a producer ----
    def _due(self):
        """-> (streams, i) of the item due soonest among the streams
        nobody is inside, or None: nothing to make, or no room.  The
        item the pulling thread waits for is always due, room or not:
        whatever order a source takes its streams in, it goes on."""
        if self._wanted is not None:
            streams, i = self._wanted
            if not (streams.ready[i] or streams.busy[i] or streams.ended[i]):
                return self._wanted
        if self._alive >= 2 * len(self._threads):
            return None
        for streams in self._adopted:
            free = [i for i in range(len(streams.streams))
                    if not (streams.busy[i] or streams.ended[i])]
            if free:
                return streams, min(free, key=lambda i: (
                    streams.made[i] if streams.rounds else 0, i))
        return None

    def _run(self) -> None:
        from citus_tpu.storage.overlay import transaction_overlay
        from citus_tpu.storage.reader import decode_pool_shared
        with contextlib.ExitStack() as ctx:
            ctx.enter_context(transaction_overlay(self._txn))
            if self._trace_ctx is not None:
                ctx.enter_context(_trace.activate(*self._trace_ctx))
            while True:
                due = self._claim()
                if due is None:
                    return
                with decode_pool_shared(len(self._threads)):
                    self._make(*due)

    def _claim(self):
        """Wait for an item to be due and take it on -> (streams, i);
        None once the prefetcher is closed."""
        with self._cv:
            while not self._cancelled:
                due = self._due()
                if due is not None:
                    break
                self._cv.wait()
            else:
                return None
            streams, i = due
            streams.busy[i] = True
            streams.made[i] += 1
            self._alive += 1
            self._inside += 1
            if self._inside == 2:
                self._overlap_from = _perf()
        return due

    def _make(self, streams: _Streams, i: int) -> None:
        t0 = _perf()
        try:
            made = (_ITEM, next(streams.streams[i]))
        except StopIteration:
            made = (_DONE, None)
        except BaseException as e:  # surfaces where the item was due
            made = (_ERR, e)
        t1 = _perf()
        with self._cv:
            self._inside -= 1
            if self._inside == 1:
                self.overlap_s += t1 - self._overlap_from
            self._unsettled_s += t1 - t0
            streams.busy[i] = False
            if made[0] == _ITEM:
                self.decoded.add(threading.get_ident())
            else:
                streams.ended[i] = True
                self._alive -= 1
            streams.ready[i].append(made)
            self._cv.notify_all()


class HostPrefetcher:
    """Bounded read-ahead over a host batch iterator, pulled by one
    background thread (``citus-host-decode``).  The queue depth IS the
    backpressure: the pulling thread blocks when the device is
    ``depth`` batches behind, so host memory stays bounded no matter
    how large the scan.

    Where the source takes its items from several independent streams
    (``one_after_another`` / ``in_rounds``) and the cores allow it, the
    pulling thread only hands the items on in their order and
    ``_Producers`` decode them, several streams at once; a source of
    one stream is decoded on the pulling thread, with no other.  Host
    batches alive at once, made or in the making: at most ``2 x
    producers + depth`` and what the source itself holds in hand (a
    mesh round's members).

    Exceptions raised by the source (fault injections included) are
    re-raised at the consumer's next ``__next__``, after everything
    that was due before them.  ``close()`` cancels every thread
    promptly, also one blocked on backpressure (consumer died
    mid-scan)."""

    def __init__(self, source: Iterator, depth: int,
                 stats: Optional[PipelineStats] = None):
        from citus_tpu.storage.overlay import current_overlay
        self._source = iter(source)
        self._stats = stats
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._cancel = threading.Event()
        # the transaction overlay is thread-local: the decode threads
        # must see the consumer's staged writes, not a bare snapshot
        self._txn = current_overlay()
        # so is the trace context: the decode threads' spans hang under
        # the span the consumer had open (the query's ``execute``)
        self._trace_ctx = _trace.capture()
        self._producers = _Producers(self._txn, self._trace_ctx)
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="citus-host-decode")
        self._finished = False
        self._booked = False
        self._thread.start()

    # ---- pulling thread ----
    def _put(self, item) -> bool:
        try:
            self._q.put_nowait(item)
            return True
        except queue.Full:
            pass
        # device behind: backpressure holds the decode side, which says
        # so (wait:prefetch_full) from this first Full on -- ONE span
        # an interval, however many producers it holds back in turn
        wtok = begin_wait("prefetch_full")
        stalled = False
        try:
            while not self._cancel.is_set():
                try:
                    self._q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    if not stalled and self._stats is not None:
                        self._stats.device_stalls += 1
                        stalled = True
            return False
        finally:
            end_wait(wtok)

    def _produce(self) -> None:
        from citus_tpu.storage.overlay import transaction_overlay
        _pulling.producers = self._producers
        try:
            with transaction_overlay(self._txn):
                if self._trace_ctx is None:
                    self._produce_inner()
                else:
                    with _trace.activate(*self._trace_ctx):
                        self._produce_inner()
        finally:
            # nothing is left to make: no producer waits on for close()
            self._producers.stop()

    def _produce_inner(self) -> None:
        try:
            while not self._cancel.is_set():
                t0 = _perf()
                try:
                    batch = next(self._source)
                except StopIteration:
                    self._put((_DONE, None))
                    return
                finally:
                    if self._stats is not None:
                        # thread-time: this thread's own, and its
                        # producers' in place of its waits for them
                        self._stats.host_decode_s += \
                            _perf() - t0 + self._producers.settle()
                if not self._put((_ITEM, batch)):
                    return
        except _Cancelled:
            pass
        except BaseException as e:  # surfaces at the consumer
            self._put((_ERR, e))

    # ---- consumer ----
    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        try:
            kind, val = self._q.get_nowait()
        except queue.Empty:
            if self._stats is not None:
                # host behind: the device would starve here
                self._stats.host_stalls += 1
            wtok = begin_wait("prefetch_stall")
            try:
                while True:
                    try:
                        kind, val = self._q.get(timeout=0.5)
                        break
                    except queue.Empty:
                        if not self._thread.is_alive() and self._q.empty():
                            raise ExecutionError(
                                "host decode worker died without a result")
            finally:
                end_wait(wtok)
        if kind == _ITEM:
            return val
        self._finished = True
        if kind == _ERR:
            raise val
        raise StopIteration

    def close(self) -> None:
        """Cancel the decode threads and drain; idempotent, safe to call
        from a ``finally`` around the consumer loop.  No thread of this
        prefetcher is alive when it returns."""
        self._cancel.set()
        self._producers.stop()
        while self._thread.is_alive():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        self._producers.join()
        try:
            _close_iter(self._source)
        # lint: disable=SWL01 -- source close at shutdown is best-effort; batches already delivered
        except Exception:
            pass
        if self._stats is not None and not self._booked:
            self._booked = True
            self._stats.host_decode_s += self._producers.settle()
            self._stats.book_streams(max(1, len(self._producers.decoded)),
                                     self._producers.overlap_s)


def prefetch_batches(source: Iterator, depth: int,
                     stats: Optional[PipelineStats] = None):
    """Wrap a host batch iterator in the read-ahead pipeline (depth >=
    1) or the inline fallback (depth 0)."""
    if depth <= 0:
        return _InlineHostIter(source, stats)
    return HostPrefetcher(source, depth, stats)


# ------------------------------------------------ remote task dispatch


class _NodePool:
    """Per-worker-node dispatch window (the WorkerPool analog): starts
    at one in-flight RPC and ramps by one per success toward the
    citus.max_adaptive_executor_pool_size cap — slow start."""

    __slots__ = ("window", "inflight", "pending")

    def __init__(self):
        self.window = 1
        self.inflight = 0
        self.pending: deque = deque()


class RemoteTaskDispatch:
    """In-flight remote execute_task fan-out.  Construction starts the
    RPCs; ``collect()`` blocks until every task settled and returns
    ``(fallback_shard_indexes, results)`` — failed tasks fall back to
    the local pull path, successes carry decoded partials/batches.
    ``abort()`` (error path) drops undispatched tasks and waits out the
    in-flight ones so no thread outlives the query attempt."""

    def __init__(self, cat, record: PipelineStats, settings, tasks,
                 payload_kind: str):
        self.cat = cat
        self.record = record
        self.cap = max(1, settings.executor.max_adaptive_pool_size)
        self.shared_limit = settings.executor.max_shared_pool_size
        self.wire = settings.executor.wire_format
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._nodes: dict[int, _NodePool] = {}
        # "agg" -> decode_partials, "hash" -> decode_hash_partials,
        # anything else -> decode_batch (projection rows)
        self._payload_kind = payload_kind
        # si -> (node, meta, blob, rpc_s, rspan): raw response frames,
        # decoded on the COLLECTING thread so the event loop never
        # serializes decode work behind socket readiness
        self._raw: dict[int, tuple] = {}
        self._fallback: list[int] = []
        self._total = len(tasks)
        self._settled = 0
        self._inflight_total = 0
        self._inflight_peak = 0
        self._aborted = False
        # ONE dispatcher drives the whole fan-out (started lazily; a
        # local-only query never spins it up)
        self._loop = cat.remote_data.event_loop() if tasks else None
        # trace context captured BEFORE the RPCs start: spans opened
        # for them attach to the dispatching query's tree, and the
        # (trace_id, parent span_id) pair rides in each task payload
        self._trace_ctx = _trace.capture()
        self._t_start = _perf()
        self._t_last_done = self._t_start
        for si, node, ep, task in tasks:
            pool = self._nodes.setdefault(int(node), _NodePool())
            pool.pending.append((si, node, ep, task))
        self._launch()

    # ---- scheduling (caller holds self._mu) ----
    def _plan_locked(self) -> list:
        """Pick every launchable task and bump the in-flight
        accounting; returns fully-built submit descriptors.  The
        actual ``submit`` (JSON encode + wake) happens OUTSIDE the
        lock in ``_launch`` — under the old hold-``_mu``-across-submit
        shape, the event-loop thread's completion callback blocked on
        ``_mu`` for as long as a submitting caller spent encoding,
        stalling every other in-flight RPC behind one thread's CPU
        work (the citussan BLK01 loop-thread hazard)."""
        from citus_tpu.workload import GLOBAL_SCHEDULER
        batch = []
        progress = True
        while progress:
            progress = False
            for pool in self._nodes.values():
                if not pool.pending or pool.inflight >= pool.window:
                    continue
                if self._inflight_total == 0:
                    holds_slot = False  # rides the query's required slot
                elif GLOBAL_SCHEDULER.try_extra(self.shared_limit):
                    holds_slot = True
                else:
                    return batch  # pool saturated; retry on completion
                si, node, ep, task = pool.pending.popleft()
                pool.inflight += 1
                self._inflight_total += 1
                self._inflight_peak = max(self._inflight_peak,
                                          self._inflight_total)
                rspan = None
                if self._trace_ctx is not None:
                    tr, parent = self._trace_ctx
                    rspan = tr.open_span(
                        "remote_task", parent.span_id,
                        {"shard_index": int(si), "node": int(node)})
                    # span context rides in the payload; the worker
                    # records its half against the same trace_id and
                    # returns it in the meta
                    task = dict(task, trace={
                        "trace_id": tr.trace_id,
                        "parent_span_id": rspan.span_id})
                batch.append((ep, task, pool, si, node, rspan,
                              holds_slot))
                progress = True
        return batch

    def _launch(self) -> None:
        """Launch until no pool can accept more work: plan under the
        (bookkeeping-only) lock, submit outside it.  Safe concurrently
        from callers and the loop-thread done_cb: the accounting a plan
        bumps is committed before ``_mu`` is released, so a racing plan
        never double-launches a task."""
        while True:
            # lint: disable=BLK01 -- bookkeeping-only microsection: planning never encodes, submits, or blocks
            with self._mu:
                batch = self._plan_locked()
            if not batch:
                return
            for ep, task, pool, si, node, rspan, holds_slot in batch:
                t0 = _perf()
                # done_cb runs ON the loop thread (never inline here),
                # so a caller may hold its own locks across _launch
                self._loop.submit(
                    ep, "execute_task", task,
                    done_cb=lambda fut, pool=pool, si=si, node=node,
                    rspan=rspan, holds_slot=holds_slot, t0=t0:
                    # lint: disable=BLK01 -- done_cb fires post-settle; _on_done's result()/lock never block the loop
                    self._on_done(fut, pool, si, node, rspan,
                                  holds_slot, t0))

    # ---- one RPC settled (event-loop thread) ----
    def _on_done(self, fut, pool, si, node, rspan, holds_slot,
                 t0) -> None:
        from citus_tpu.executor.executor import GLOBAL_COUNTERS
        from citus_tpu.workload import GLOBAL_SCHEDULER
        rpc_s = _perf() - t0
        meta = blob = None
        ok = True
        try:
            # lint: disable=BLK01 -- done_cb fires after the future settles; result() returns immediately
            meta, blob = fut.result()
        # lint: disable=SWL01 -- failure is counted below as remote_task_fallbacks; shard rescans locally
        except Exception:
            # worker dead, version skew, codec refused server-side:
            # this shard scans locally through the pull path instead
            ok = False
        if blob is None:
            ok = False  # a pushed task must return a binary frame
        nbytes = len(blob) if blob is not None else 0
        if rspan is not None:
            tr, _parent = self._trace_ctx
            # dec_ms lands later, from the collecting thread's decode
            rspan.set(ok=ok, bytes=int(nbytes),
                      rpc_ms=round(rpc_s * 1000, 3), dec_ms=0.0)
            tr.close_span(rspan)
            if ok and isinstance(meta, dict) and meta.get("spans"):
                tr.graft(meta["spans"], rspan)
        if holds_slot:
            GLOBAL_SCHEDULER.release_extra()
        # lint: disable=BLK01 -- bookkeeping-only microsection on the loop thread; no holder blocks inside it
        with self._mu:
            pool.inflight -= 1
            self._inflight_total -= 1
            if ok:
                pool.window = min(self.cap, pool.window + 1)  # slow start
                self._raw[si] = (int(node), meta, blob, rpc_s, rspan)
                GLOBAL_COUNTERS.bump("remote_tasks_pushed")
                GLOBAL_COUNTERS.bump("remote_task_result_bytes", nbytes)
            else:
                self._fallback.append(si)
                GLOBAL_COUNTERS.bump("remote_task_fallbacks")
            self._settled += 1
            self._t_last_done = _perf()
            relaunch = not self._aborted
            if self._settled >= self._total and self._inflight_total == 0:
                self._cv.notify_all()
        if relaunch:
            self._launch()

    # ---- caller side ----
    def collect(self) -> tuple[list[int], list]:
        """Wait for every in-flight task; returns (fallback shard
        indexes, successful results in shard-index order) and books the
        task log and the overlap/peak figures to the execution's record.
        Decode runs here, on the caller — the event loop only moves
        bytes."""
        from citus_tpu.executor.executor import GLOBAL_COUNTERS
        from citus_tpu.net.data_plane import (decode_batch,
                                              decode_hash_partials,
                                              decode_partials)
        if self._total:
            _trace.set_phase("remote-wait")
        t_enter = _perf()
        with self._cv:
            if self._settled < self._total or self._inflight_total:
                # only a real block opens a wait bracket: a fan-out that
                # finished behind local work must not book phantom ms
                wtok = begin_wait("remote_rpc")
                try:
                    while self._settled < self._total or self._inflight_total:
                        self._cv.wait(0.5)
                finally:
                    end_wait(wtok)
            fallback = list(self._fallback)
            raw = dict(self._raw)
            peak = self._inflight_peak
            t_last = self._t_last_done
        wait_s = _perf() - t_enter
        results, tlog = [], []
        for si in sorted(raw):
            node, meta, blob, rpc_s, rspan = raw[si]
            t1 = _perf()
            try:
                if self._payload_kind == "agg":
                    payload = decode_partials(blob)
                elif self._payload_kind == "hash":
                    payload = decode_hash_partials(blob)
                else:
                    payload = decode_batch(blob)
            # lint: disable=SWL01 -- counted as remote_task_fallbacks below; shard rescans locally
            except Exception:
                # decode failed after a successful RPC (codec skew):
                # the shard rescans locally.  remote_tasks_pushed was
                # already bumped when the frame landed — an accepted
                # asymmetry for this rare path.
                fallback.append(si)
                GLOBAL_COUNTERS.bump("remote_task_fallbacks")
                continue
            dec_s = _perf() - t1
            if rspan is not None:
                rspan.set(dec_ms=round(dec_s * 1000, 3))
            results.append(payload)
            tlog.append((si, node, len(blob), rpc_s, dec_s))
        fallback = sorted(fallback)
        # the stretch of remote in-flight time the caller spent doing
        # local work instead of blocking — the overlap win itself
        overlapped_s = max(0.0, min(t_enter, t_last) - self._t_start)
        self.record.remote_tasks.extend(tlog)
        if self._total:
            pl = self.record.figures
            pl["remote_wait_ms"] = round(wait_s * 1000, 3)
            pl["remote_overlapped_ms"] = round(overlapped_s * 1000, 3)
            pl["remote_inflight_peak"] = peak
            pl["wire_format"] = self.wire
            GLOBAL_COUNTERS.bump_max("remote_tasks_inflight_peak", peak)
            GLOBAL_COUNTERS.bump("remote_task_wait_overlapped_ms",
                                 int(overlapped_s * 1000))
        return fallback, results

    def abort(self) -> None:
        """Error path: stop launching, count nothing, wait out the
        in-flight RPCs so no worker thread outlives the attempt."""
        with self._cv:
            self._aborted = True
            for pool in self._nodes.values():
                self._settled += len(pool.pending)
                pool.pending.clear()
            while self._inflight_total:
                self._cv.wait(0.5)


def dispatch_remote_tasks(cat, plan, settings, params, record: PipelineStats
                          ) -> tuple[list[int], RemoteTaskDispatch]:
    """Start the remote fan-out for every remote-only placement of
    ``plan`` and return immediately: ``(local_shard_indexes,
    dispatch)``.  The caller scans the local shards while the RPCs are
    in flight, then ``dispatch.collect()``s.  Inexpressible plans (or
    policy "pull") push nothing — everything stays local."""
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    from citus_tpu.executor.worker_tasks import encode_task, split_pushable
    local, remote = split_pushable(cat, plan, settings)
    if not remote:
        return list(local), RemoteTaskDispatch(cat, record, settings, [], "")
    template = encode_task(plan, params)
    if template is not None:
        # the coordinator's citus.wire_format decides how the WORKER
        # encodes its result; a worker that predates the key defaults
        # to npz, and decode always sniffs the magic — either way the
        # response decodes
        template = dict(template, wire=settings.executor.wire_format)
    if template is None:
        GLOBAL_COUNTERS.bump("remote_task_fallbacks", len(remote))
        return (sorted(local + [si for si, _, _ in remote]),
                RemoteTaskDispatch(cat, record, settings, [], ""))
    tasks = [(si, node,
              ep, dict(template,
                       shard_id=plan.bound.table.shards[si].shard_id,
                       node=node))
             for si, node, ep in remote]
    return list(local), RemoteTaskDispatch(
        cat, record, settings, tasks, template["kind"])
