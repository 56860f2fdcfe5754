"""The device scan loop: one driver, two placements, kernels as the step.

Every device scan takes a round of host batches, puts it on the
device(s), dispatches a kernel that takes the DONATED running state and
returns the next one, keeps a bounded number of rounds un-synced and may
keep the device inputs for the HBM batch cache.  ``drive`` is that loop,
and the only one.  What differs between the scans is data:

- a **placement** says where a round's inputs live: how many batches
  make a round, how they are put, how parameters ride along, the cache
  key's suffix, how a round's bytes and seconds are attributed (into
  the execution's record, ``pipeline.PipelineStats``, which it is made
  with).
  ``OneDevice``, ``MeshPlacement`` and its shard-affine sibling
  ``AffineMeshPlacement`` (per-device state: a shard always meets the
  same device); ``choose_placement`` / ``choose_affine_placement`` pick;
- a **step** is the compiled kernel, ``state' = fn(state, cols, valids,
  row_mask)`` — or ``(state', aux)`` beside a sync hook — with the slot
  and counter names its rounds are booked under;
- the first state and the tail (``_fetch_acc``) are the caller's.

**Lanes.**  The device form of a scan column is as wide as the table's
statistics prove it has to be (``PhysicalPlan.scan_lanes``): an int64
column they bound inside int32 is narrowed ON THE DEVICE right after
its put, by one ``jit_narrow`` dispatch a round that also reduces
"some valid row's value did not survive" into one flag the placement
carries from round to round.  The kernels widen where they read
(``ops/scan_agg.py`` ``scan_env_fn``); ``drive`` looks at the flag where
it blocks anyway and raises ``ScanLanesBelied`` before anything enters
the batch cache or a result leaves.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Iterator, NamedTuple, Optional

import numpy as np

from citus_tpu.executor.batches import ShardBatch, empty_batch
from citus_tpu.executor.device_cache import GLOBAL_CACHE, plan_cache_key
from citus_tpu.executor.pipeline import (
    PipelineStats, in_rounds, prefetch_batches, read_ahead_depth,
)
from citus_tpu.observability import trace as _trace
from citus_tpu.observability.trace import clock
from citus_tpu.stats import begin_wait, end_wait


def _block_ready(x) -> None:
    """block_until_ready under a device_round wait bracket: the stretch
    the backend spends blocked on device backpressure shows up in the
    activity view and the wait_device_round_ms counter.  On a TPU a
    wait costs a few tenths of a millisecond even when every array is
    ready (PERF.md, PR 23): where the arrays are the outputs of one
    dispatch, which become ready together, callers pass one of them."""
    import jax
    wtok = begin_wait("device_round")
    try:
        jax.block_until_ready(x)
    finally:
        end_wait(wtok)


def _nbytes(arrays) -> int:
    return int(sum(a.nbytes for a in arrays))


def _prefetch_depth(settings) -> int:
    """Device-side in-flight window: streaming mode keeps at most this
    many rounds un-synced ahead of the kernel consuming them.
    Governed by SET citus.executor_prefetch_depth (floor of 1 so the
    depth-0 'decode inline' setting still double-buffers the device);
    max_tasks_in_flight raises the window further."""
    return max(1, settings.executor.executor_prefetch_depth,
               settings.executor.max_tasks_in_flight)


class ScanLanesBelied(Exception):
    """A batch held a value its table's statistics rule out: the scan's
    narrowed lanes lost it.  Nothing was cached and no result returned;
    the statement runs again on a plan that takes nothing from the
    statistics (``executor.py`` ``_execute_select_traced``)."""


def _narrow_kernel(suffix: tuple, sharding):
    """The lane convert of a placement, one a process and placement
    kind: no plan shapes it (jit keys the column count and the bucket
    itself).  Module ``jit_narrow`` in a device trace."""
    import jax.numpy as jnp
    from citus_tpu.executor.kernel_cache import jit_compile, shared_kernel

    def narrow(wide, valids, row_mask, belied):
        out = tuple(c.astype(np.int32) for c in wide)
        for c, o, v in zip(wide, out, valids):
            # a NULL slot or a padding row may hold anything: the
            # validity bit and the row mask decide, not the value
            lost = (o.astype(c.dtype) != c) & v & row_mask
            belied = belied | jnp.any(lost, axis=-1)
        return out, belied
    # the wide arrays are donated: nothing reads them again
    return shared_kernel(
        "jit_narrow",
        lambda: jit_compile(narrow, donate_argnums=0, out_shardings=sharding),
        extra=suffix)


class Lanes:
    """What both placements do about a plan's narrow lanes: convert the
    marked columns of a round that is already on the device, carry the
    flag from round to round, say at the end what it reads."""

    sharding = None    # of a round's arrays (None: the default device)
    belied = None      # device flag(s): a scalar, or one a mesh device
    _marked: tuple = ()

    def prepare(self, plan) -> None:
        """Before a streamed scan's first round: the convert, where the
        plan has narrow lanes, and the flag it starts from."""
        self._marked = plan.narrow_lanes
        if self._marked and self.belied is None:
            import jax
            self._convert = _narrow_kernel(self.key_suffix, self.sharding)
            self.belied = jax.device_put(
                np.zeros(() if self.sharding is None else (self.round_size,),
                         bool), self.sharding)

    def _narrow(self, cols: tuple, valids: tuple, row_mask) -> tuple:
        """-> ``cols`` with the plan's narrow lanes as int32."""
        marked = self._marked
        with _trace.span("narrow") as sp:
            out, self.belied = self._convert(
                tuple(cols[i] for i in marked),
                tuple(valids[i] for i in marked), row_mask, self.belied)
            if sp.recording:
                sp.set(columns=len(marked))
        cols = list(cols)
        for i, c in zip(marked, out):
            cols[i] = c
        return tuple(cols)

    def lanes_belied(self) -> bool:
        """Whether any round's convert lost a valid value.  Waits for
        the last convert (the fetch of one flag a scan)."""
        if self.belied is None:
            return False
        import jax
        return bool(np.any(jax.device_get(self.belied)))


class Step(NamedTuple):
    fn: Callable
    slot: str       # on the round's ``dispatch`` span
    counter: str    # process counter of its rounds


# ------------------------------------------------------------ placements


class OneDevice(Lanes):
    """A round is one host batch, put on the default device as a
    ShardBatch of device arrays (which is also its cache entry: the
    plan's narrow lanes as int32); parameters ride along as the host
    arrays they are."""

    round_size = 1
    key_suffix: tuple = ()
    mesh = None

    def __init__(self, record: PipelineStats) -> None:
        self.record = record
        self.rows_padded = 0    # rows of every round, padding included

    def bind(self, params) -> None:
        self.pcols, self.pvalids = params

    def put(self, plan, members: list):
        import jax
        hb, = members
        nbytes = hb.nbytes
        with _trace.span("h2d") as sp:
            db = ShardBatch(tuple(jax.device_put(c) for c in hb.cols),
                            tuple(jax.device_put(v) for v in hb.valids),
                            jax.device_put(hb.row_mask), hb.n_rows,
                            hb.padded_rows, hb.shard_index)
            if sp.recording:
                sp.set(bytes=nbytes)
        if self._marked:
            db = dataclasses.replace(
                db, cols=self._narrow(db.cols, db.valids, db.row_mask))
        return db, nbytes

    def args(self, b: ShardBatch) -> tuple:
        return b.cols + self.pcols, b.valids + self.pvalids, b.row_mask

    @staticmethod
    def held_bytes(b: ShardBatch) -> int:
        """Device bytes of a round's inputs: what a cache entry holds."""
        return b.nbytes

    def describe(self, members, b: ShardBatch) -> dict:
        return {"shard_index": int(b.shard_index), "rows": int(b.n_rows),
                "bytes": b.nbytes}

    def book(self, members, b: ShardBatch, nbytes: int, round_s: float,
             dispatch_s: float) -> None:
        self.record.task_times.append((b.shard_index, b.n_rows, dispatch_s))
        self.rows_padded += b.padded_rows
        if members is not None:
            self.record.task_bytes.append((b.shard_index, nbytes))


def _repad_batch(b: ShardBatch, bucket: int) -> ShardBatch:
    """Grow a padded batch to a larger bucket (the members of a mesh
    round share one shape)."""
    pad = bucket - b.padded_rows
    if pad <= 0:
        return b
    cols = tuple(np.concatenate([c, np.zeros(pad, c.dtype)]) for c in b.cols)
    valids = tuple(np.concatenate([v, np.ones(pad, bool)]) for v in b.valids)
    mask = np.concatenate([b.row_mask, np.zeros(pad, bool)])
    return ShardBatch(cols, valids, mask, b.n_rows, bucket, b.shard_index)


class MeshPlacement(Lanes):
    """A round is up to ``n_dev`` host batches (a member may be None: a
    device with nothing this round).  It is never assembled on the
    host: member ``i``'s arrays go to the device that owns row ``i`` of
    the round's sharding as they stand, and the ``[n_dev, bucket]``
    arrays are made of those per-device pieces -- one ``(cols, valids,
    row_mask)`` triple of device-sharded arrays, a different structure
    than the one-device ShardBatch list, so its cache entries key
    apart.  The host copies only what it must: a member cut at a
    smaller bucket than the round's is re-padded, and a device that ran
    dry takes a filler of padding alone, made once a scan and bucket,
    kept here as HOST arrays and put afresh every round it is needed
    (a device array would be donated away by the first ``jit_narrow``
    that took it).  Parameters replicate across the shard axis as
    ``[n_dev]`` stacks, put on the mesh once a query (never cached:
    they change per execution).  The plan's narrow lanes are converted
    on the chips after the put, each device its own row of the round
    and its own flag: no collective."""

    def __init__(self, mesh, record: PipelineStats) -> None:
        from jax.sharding import NamedSharding, PartitionSpec
        from citus_tpu.parallel.mesh import shard_axis_size
        self.mesh = mesh
        self.record = record
        self.round_size = shard_axis_size(mesh)
        self.key_suffix = ("mesh", self.round_size)
        self.sharding = NamedSharding(mesh, PartitionSpec("shard"))
        self.rows_padded = 0    # rows of every round, padding included
        self._fillers: dict = {}    # (table, scan columns, bucket) -> batch

    def bind(self, params) -> None:
        import jax
        n = self.round_size
        with _trace.span("bind_params"):
            self.pcols, self.pvalids = jax.device_put(
                (tuple(np.stack([p] * n) for p in params[0]),
                 tuple(np.stack([v] * n) for v in params[1])),
                self.sharding)

    def _filler(self, plan, bucket: int) -> tuple:
        """-> (a batch of padding alone, the host bytes making it
        wrote: 0 where the scan has made this bucket's before)."""
        table = plan.bound.table
        key = (table.name, tuple(plan.scan_columns), bucket)
        filler = self._fillers.get(key)
        if filler is not None:
            return filler, 0
        filler = self._fillers[key] = empty_batch(table, plan, bucket, -1)
        return filler, filler.nbytes

    def _assemble(self, pieces: list):
        """``pieces[i]``: a ``[1, bucket]`` host array for row ``i`` of
        the round -> the ``[n_dev, bucket]`` device array, each piece
        on the device the sharding gives its row (one transfer a
        device, what ``device_put`` of the whole makes)."""
        import jax
        return jax.make_array_from_callback(
            (len(pieces), pieces[0].shape[1]), self.sharding,
            lambda index: pieces[index[0].start or 0])

    def put(self, plan, members: list):
        n_cols = range(len(plan.scan_columns))
        with _trace.span("stack") as sp:
            bucket = max(b.padded_rows for b in members if b is not None)
            copied = 0
            buf = []
            for b in members + [None] * (self.round_size - len(members)):
                if b is None:
                    b, fresh = self._filler(plan, bucket)
                    copied += fresh
                elif b.padded_rows < bucket:
                    b = _repad_batch(b, bucket)
                    copied += b.nbytes
                buf.append(b)
            cols = [[b.cols[i][None] for b in buf] for i in n_cols]
            valids = [[b.valids[i][None] for b in buf] for i in n_cols]
            mask = [b.row_mask[None] for b in buf]
            nbytes = sum(b.nbytes for b in buf)
            self.record.tally("mesh_round_bytes_copied", copied, add=True)
            if sp.recording:
                sp.set(bytes=nbytes, copied=copied)
        with _trace.span("h2d") as sp:
            dcols = tuple(self._assemble(c) for c in cols)
            dvalids = tuple(self._assemble(v) for v in valids)
            dmask = self._assemble(mask)
            if sp.recording:
                sp.set(bytes=nbytes)
        if self._marked:
            dcols = self._narrow(dcols, dvalids, dmask)
        return (dcols, dvalids, dmask), nbytes

    def args(self, inputs: tuple) -> tuple:
        dcols, dvalids, dmask = inputs
        return dcols + self.pcols, dvalids + self.pvalids, dmask

    @staticmethod
    def held_bytes(inputs: tuple) -> int:
        dcols, dvalids, dmask = inputs
        return _nbytes(dcols) + _nbytes(dvalids) + dmask.nbytes

    def describe(self, members, inputs: tuple) -> dict:
        return {"batches": len(members) if members else self.round_size,
                "bytes": self.held_bytes(inputs)}

    def book(self, members, inputs, nbytes: int, round_s: float,
             dispatch_s: float) -> None:
        """Split a streamed round's H2D bytes and device time across its
        shard members for attribution (the filler batches' padding
        belongs to the shards that forced the round).  The byte
        remainder lands on the first member so the ledger total stays
        exactly equal to the bytes_scanned counter bump.  The times are
        attribution-only (``mesh_task_times``, not the EXPLAIN Tasks
        section, which renders single-device dispatches)."""
        self.rows_padded += inputs[2].size
        if members is None:
            return
        share, rem = divmod(int(nbytes), len(members))
        for i, mb in enumerate(members):
            self.record.task_bytes.append(
                (mb.shard_index, share + (rem if i == 0 else 0)))
            self.record.mesh_task_times.append(
                (mb.shard_index, mb.n_rows, round_s / len(members)))


class AffineMeshPlacement(MeshPlacement):
    """The mesh placement for a step whose state is PER DEVICE (one
    hash table a chip): its rounds are shard-affine.  Member ``i`` of
    every round is a batch of a shard that device ``i`` owns, by a
    fixed map from the shard's index in its table to a device -- the
    contiguous ``n_shards / n_dev`` a device -- or None where that
    device has run dry.  So whatever a shard holds meets one device
    alone, scan after scan: tables of groups that live in one shard
    (a key that holds the distribution column) stay disjoint."""

    def __init__(self, mesh, n_shards: int, record: PipelineStats) -> None:
        super().__init__(mesh, record)
        self.n_shards = max(1, n_shards)
        self.key_suffix = ("mesh", self.round_size, "affine")
        self.device_rows = [0] * self.round_size    # table rows a device took

    def owner(self, shard_index: int, n_shards: Optional[int] = None) -> int:
        """The device of a shard of a table of ``n_shards`` shards (the
        placement's own table where not given)."""
        return shard_index * self.round_size // (n_shards or self.n_shards)

    def affine(self, shard_indexes: list, open_stream: Callable,
               n_shards: Optional[int] = None) -> Iterator:
        """The batches of ``shard_indexes`` in round order: each round
        is ``round_size`` items, item ``i`` the next batch of device
        ``i``'s own shards (``open_stream(its shards)``: one stream a
        device, decoded side by side where a prefetcher pulls this one:
        ``pipeline.in_rounds``) or None.  Ends with the last round that
        holds a batch.  ``n_shards``: of the
        table these shards are of, where a scan streams several."""
        owned = [[si for si in shard_indexes
                  if self.owner(si, n_shards) == d]
                 for d in range(self.round_size)]
        rounds = in_rounds([open_stream(mine) if mine else iter(())
                            for mine in owned])
        try:
            for members in rounds:
                yield from members
        finally:
            rounds.close()

    def deal(self, a):
        """A host array of entries, any of which may go to any device:
        padded with zeros to a multiple of the devices and put on the
        mesh a contiguous slice a device, ``[n_dev, len / n_dev]``."""
        import jax
        a = np.asarray(a)
        n = self.round_size
        a = np.concatenate([a, np.zeros(-len(a) % n, a.dtype)])
        return jax.device_put(a.reshape(n, -1), self.sharding)

    @staticmethod
    def _real(members):
        return members and [m for m in members if m is not None]

    def describe(self, members, inputs: tuple) -> dict:
        return super().describe(self._real(members), inputs)

    def book(self, members, inputs, nbytes: int, round_s: float,
             dispatch_s: float) -> None:
        for d, m in enumerate(members or ()):
            if m is not None:
                self.device_rows[d] += m.n_rows
        super().book(self._real(members), inputs, nbytes, round_s,
                     dispatch_s)


def _lookup(make_key: Callable[[], Optional[tuple]], mesh: bool):
    """-> (cache key | None, the device inputs cached under it | None)."""
    with _trace.span("cache_lookup") as sp:
        key = make_key()
        cached = None if key is None else GLOBAL_CACHE.get(key)
        if sp.recording:
            sp.set(hit=cached is not None, mesh=mesh)
    return key, cached


def choose_placement(plan, data_dir: str, use_cache: bool,
                     open_stream: Callable[[], Iterator],
                     record: PipelineStats):
    """Where this scan's rounds live, from what the code observes: the
    device count, a hit under the one-device key, a stream of a single
    batch.  -> (placement, cache key | None, cached device inputs |
    None, host stream | None); with ``use_cache`` false nothing is
    looked up and the key is None (nothing is kept either)."""
    from citus_tpu.parallel.mesh import default_mesh, executor_devices
    with _trace.span("scan_setup"):
        devices = executor_devices()
    key, cached = _lookup(
        lambda: plan_cache_key(plan, data_dir) if use_cache else None, False)
    # a single-batch table cached under the one-device key serves from
    # there without touching disk: the mesh is entered only when no such
    # entry exists
    if len(devices) == 1 or cached is not None:
        return (OneDevice(record), key, cached,
                None if cached is not None else open_stream())
    with _trace.span("scan_setup"):
        placement = MeshPlacement(default_mesh(), record)
    # the key holds the snapshot generation (three file reads): made once
    mkey, mcached = _lookup(lambda: key and key + placement.key_suffix, True)
    if mcached is not None:
        return placement, mkey, mcached, None
    stream = open_stream()
    t_peek = clock()
    head = list(itertools.islice(stream, 2))
    record.host_decode_s += clock() - t_peek
    if len(head) < 2:
        return OneDevice(record), key, None, iter(head)   # 0 or 1 batch
    return placement, mkey, None, itertools.chain(head, stream)


def choose_affine_placement(plan, open_stream: Callable[[list], Iterator],
                            record: PipelineStats):
    """Where the rounds of a scan with per-device state live, from what
    the code observes, as ``choose_placement`` does: one device, or a
    stream of a single batch -> ``OneDevice`` (and the stream as it
    always was); several devices -> ``AffineMeshPlacement`` and the
    stream in its round order.  ``open_stream(shard_indexes)`` opens
    the host batches of those shards.  -> (placement, host stream)."""
    from citus_tpu.parallel.mesh import default_mesh, executor_devices
    with _trace.span("scan_setup"):
        devices = executor_devices()
        if len(devices) == 1:
            return OneDevice(record), open_stream(plan.shard_indexes)
        placement = AffineMeshPlacement(default_mesh(),
                                        plan.bound.table.shard_count, record)
    stream = placement.affine(plan.shard_indexes, open_stream)
    t_peek = clock()
    head: list = []
    while sum(m is not None for m in head) < 2:
        try:
            head.append(next(stream))
        except StopIteration:
            break
    record.host_decode_s += clock() - t_peek
    real = [m for m in head if m is not None]
    if len(real) < 2:
        stream.close()
        return OneDevice(record), iter(real)      # 0 or 1 batch
    return placement, itertools.chain(head, stream)


# ---------------------------------------------------------------- driver


def _rounds(batches: Iterator, n: int) -> Iterator[list]:
    """Group a stream of host batches into rounds of ``n``; the last
    round holds what is left."""
    while True:
        members = list(itertools.islice(batches, n))
        if not members:
            return
        yield members


def drive(plan, settings, placement, step: Step, state, record: PipelineStats,
          *, cached: Optional[list] = None, stream: Optional[Iterator] = None,
          cache_key: Optional[tuple] = None, cache_tenant: Optional[str] = None,
          on_sync: Optional[Callable[[list], None]] = None):
    """Fold every round into ``state`` and return the last state.

    The rounds are ``cached`` (device inputs a previous scan kept:
    replayed as they are) or come from ``stream`` (host batches, pulled
    through the decode thread and grouped by the placement's round
    size).  A streamed scan keeps its device inputs (the plan's narrow
    lanes as int32: what they hold is what the cache books) and puts
    them in the HBM batch cache under ``cache_key`` when the whole
    working set fits it; past the capacity (or with no key) it streams:
    at most ``_prefetch_depth`` rounds are un-synced, and since the
    donated state chain orders the rounds, a wait for one output of the
    newest state retires every round admitted before it.

    With ``on_sync`` the step returns ``(state', aux)``; the hook gets
    the ``[(host members, aux)]`` of the rounds since the last sync at
    each such wait — beside the round, not inside it — and once at the
    end."""
    import jax
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    from citus_tpu.testing.faults import FAULTS

    streamed = cached is None
    if streamed:
        placement.prepare(plan)
        # host/device overlap: the decode thread prepares the next
        # rounds while the device executes the current one
        source = prefetch_batches(
            stream, read_ahead_depth(settings) * placement.round_size, record)
        todo = ((m, None) for m in _rounds(source, placement.round_size))
    else:
        todo = ((None, inputs) for inputs in cached)
    collect: Optional[list] = [] if streamed and cache_key is not None else None
    depth = _prefetch_depth(settings)
    table = plan.bound.table.name
    pending: list = []
    rounds = nbytes = held = since_sync = window_bytes = 0
    try:
        for members, inputs in todo:
            synced = False
            with _trace.span("device_round") as rsp:
                t_dev = clock()
                # delay injections here model device-side round latency
                # for the host/device overlap tests (the decode half is
                # decode_batch)
                FAULTS.hit("device_round", table)
                nb = 0
                if streamed:
                    inputs, nb = placement.put(plan, members)
                t0 = clock()
                with _trace.span("dispatch") as sp:
                    state = step.fn(state, *placement.args(inputs))
                    if sp.recording:
                        sp.set(slot=step.slot)
                t1 = clock()
                if on_sync is not None:
                    state, aux = state
                    pending.append((members, aux))
                rounds += 1
                nbytes += nb
                placement.book(members, inputs, nb, t1 - t_dev, t1 - t0)
                if collect is not None:
                    collect.append(inputs)
                    held += placement.held_bytes(inputs)
                    if held > GLOBAL_CACHE.capacity:
                        collect = None  # working set exceeds the HBM cache
                if streamed and collect is None:
                    window_bytes += nb
                    record.window_peak_bytes = max(record.window_peak_bytes,
                                                   window_bytes)
                    since_sync += 1
                    if since_sync >= depth:
                        _block_ready(jax.tree_util.tree_leaves(state)[-1:])
                        since_sync = window_bytes = 0
                        synced = True
                record.device_s += clock() - t_dev
                if rsp.recording:
                    rsp.set(resident=not streamed,
                            **placement.describe(members, inputs))
            if synced and on_sync is not None:
                on_sync(pending)
                pending = []
    finally:
        if streamed:
            source.close()
    if streamed and placement.lanes_belied():
        # the footers' bounds did not hold for a batch this scan put:
        # its narrowed values are not the table's.  Found where the
        # loop blocks anyway: before the cache's wait for the inputs,
        # and just ahead of the caller's fetch of the state
        GLOBAL_COUNTERS.bump("scan_lanes_belied")
        raise ScanLanesBelied(table)
    if on_sync is not None:
        on_sync(pending)
    if collect:
        _block_ready([placement.args(i)[0] for i in collect])
        with _trace.span("cache_put"):
            GLOBAL_CACHE.put(cache_key, collect, held, tenant=cache_tenant)
    record.rounds += rounds
    GLOBAL_COUNTERS.bump(step.counter, rounds)
    record.figures["fused_dispatches"] = record.rounds
    # the int64 scan columns of this scan, and those that rode at 32 bits
    record.tally("scan_lanes", plan.wide_lanes)
    record.tally("scan_lanes_narrow", len(plan.narrow_lanes))
    if streamed:
        record.h2d_bytes += nbytes
        GLOBAL_COUNTERS.bump("bytes_scanned", nbytes)
        GLOBAL_COUNTERS.bump("device_hbm_touched_bytes", nbytes)
        record.figures["stream_window_peak_bytes"] = record.window_peak_bytes
    return state
