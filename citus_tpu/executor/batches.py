"""Shard data -> padded device batches.

The host-side half of the scan: cut a shard's pruned chunks at exactly
``max_batch_rows`` rows and land each cut once in arrays padded to a
power-of-two row bucket, so XLA sees a small, stable set of shapes (the
recompile-pressure discipline the reference gets from prepared-statement
plan caching).  A full batch is its bucket (no padding); only a shard's
last batch is padded.  Padding rows carry ``row_mask=False`` and zeroed
values.

A device scan is cut from the footers, BEFORE anything is decompressed:
the batch's arrays are allocated first and ONE native call decodes every
value stream of the batch where the kernel will read it.  What cannot
arrive that way (see ``ShardReader.not_in_place``, and a column
whose stored dtype is not its device dtype) is read stripe by stripe
into fresh arrays and copied to the same place.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Iterator, NamedTuple, Optional

import numpy as np

from citus_tpu.catalog import Catalog, TableMeta
from citus_tpu.errors import StorageError
from citus_tpu.observability import trace as _trace
from citus_tpu.planner.physical import PhysicalPlan
from citus_tpu.storage import ShardReader
from citus_tpu.storage.reader import BatchDecode, StripeScan


@dataclass
class ShardBatch:
    cols: tuple[np.ndarray, ...]    # device dtypes, padded
    valids: tuple[np.ndarray, ...]
    row_mask: np.ndarray
    n_rows: int                      # real rows
    padded_rows: int
    shard_index: int
    # stored bytes of the real rows' values: decoded where they lie /
    # copied there from a decoded chunk
    bytes_in_place: int = 0
    bytes_copied: int = 0
    # what a stream of several relations' batches says of this one
    # (executor/join_device.py: relation, shard, first / last of its scan)
    tag: object = None

    @property
    def nbytes(self) -> int:
        """Bytes of the padded arrays: what one H2D copy of it ships."""
        return int(sum(c.nbytes for c in self.cols)
                   + sum(v.nbytes for v in self.valids)
                   + self.row_mask.nbytes)


class _Piece(NamedTuple):
    """A decoded chunk (of all scan columns, or of those a batch could
    not take in place), or the slice of one that a batch cut fell into
    (views, no copy)."""
    values: dict[str, np.ndarray]
    validity: dict[str, Optional[np.ndarray]]   # None = all valid
    rows: int

    def cut(self, lo: int, hi: int) -> "_Piece":
        return _Piece(
            {c: v[lo:hi] for c, v in self.values.items()},
            {c: None if m is None else m[lo:hi]
             for c, m in self.validity.items()}, hi - lo)


class _ChunkRef(NamedTuple):
    """A selected chunk still on disk: its rows are known from the
    footer (less the stripe's deletes), none of its bytes are read."""
    reader: ShardReader
    columns: list[str]
    st: StripeScan
    ci: int
    rows: int

    def cut(self, lo: int, hi: int) -> _Piece:
        """A batch cut falls inside this chunk (at most one a cut):
        decode it alone; both batches copy their part."""
        with _trace.span("stripe_fallback") as sp:
            (b,) = self.reader.stripe_chunks(self.st, self.columns, [self.ci])
            if sp.recording:
                sp.set(chunks=1, reason="cut",
                       bytes=int(sum(v.nbytes for v in b.values.values())))
        return _decoded(b, self.rows).cut(lo, hi)


def _decoded(b, rows: int) -> _Piece:
    if b.row_count != rows:
        raise StorageError(
            f"{b.stripe_file}: chunk {b.chunk_index} decoded to "
            f"{b.row_count} rows, planned {rows}")
    return _Piece(b.values, b.validity, rows)


def bucket_rows(n: int, min_rows: int, max_rows: int) -> int:
    """The power-of-two bucket (min_rows * 2**k) of a batch of
    n <= max_rows rows, never above max_rows: a full batch is its own
    bucket whatever min_rows is."""
    b = max(min_rows, 1)
    while b < n:
        b *= 2
    return min(b, max(max_rows, min_rows))


def _pull_placement_fallback(cat: Catalog, table: TableMeta, shard,
                             node: int) -> Optional[str]:
    """PULL path: mirror a remote placement's files into the local
    cache and scan them here — O(placement bytes) over DCN (reference:
    shard reads over libpq, executor/transmit.c).  This is the
    executor's ONLY sync_placement call site; the preferred PUSH path
    (executor/worker_tasks.py) ships the worker plan to the owning
    coordinator instead and only lands here on fallback, per the
    citus.remote_task_execution policy."""
    return cat.remote_data.sync_placement(
        table.name, shard.shard_id, node, cat.node_endpoint(node))


def _shard_chunks(cat: Catalog, plan: PhysicalPlan, shard_index: int,
                  node_override: Optional[int], prefer_secondary: bool,
                  decoded: bool):
    """Yield the pruned chunks of one shard placement, in order: as
    decoded ``_Piece``s, or (``decoded`` False, and no index to look a
    key up in) as ``_ChunkRef``s for a batch to be laid out from."""
    table = plan.bound.table
    shard = table.shards[shard_index]
    if node_override is not None:
        nodes = [node_override]
    else:
        # prefer active nodes (citus_disable_node semantics): a disabled
        # node's placement is only read when no active replica exists;
        # with prefer_secondary (citus.use_secondary_nodes='always'),
        # replica placements outrank the primary for reads
        def order(n):
            meta = cat.nodes.get(n)
            inactive = meta is not None and not meta.is_active
            is_primary = n == shard.placements[0]
            return (inactive, is_primary if prefer_secondary else False)
        nodes = sorted(shard.placements, key=order)
    # read tasks fail over to other placements, like the reference's
    # PlacementExecutionDone failover (adaptive_executor.c:96-100).  A
    # MISSING placement directory is a failed placement, not an empty
    # shard — only when no placement exists at all is the shard empty.
    # One shard_open span: the placement's directory, its visible
    # metadata and the reader, before the first footer.
    with _trace.span("shard_open") as sp:
        reader = _open_placement(cat, table, shard, nodes)
        if sp.recording:
            sp.set(shard_index=int(shard_index),
                   stripes=0 if reader is None else len(reader.stripe_files))
    if reader is None:
        return
    cols = plan.scan_columns
    if plan.index_eq is not None:
        col, value, _name = plan.index_eq
        chunks = reader.lookup_eq(cols, col, value, plan.intervals)
    elif decoded:
        chunks = reader.scan(cols, plan.intervals)
    else:
        for st in reader.scan_stripes(cols, plan.intervals):
            for ci in st.chunks:
                yield _ChunkRef(reader, cols, st, ci, st.live_rows(ci))
        return
    for b in chunks:
        yield _Piece(b.values, b.validity, b.row_count)


def _open_placement(cat: Catalog, table: TableMeta, shard,
                    nodes: list) -> Optional[ShardReader]:
    """The reader of the first placement of ``nodes`` that can be read;
    None for a shard that is empty (never written, or no visible row)."""
    from citus_tpu.testing.faults import FAULTS
    for attempt, node in enumerate(nodes):
        d = cat.shard_dir(table.name, shard.shard_id, node)
        try:
            FAULTS.hit("read_placement", f"{table.name}:{shard.shard_id}:{node}")
            if not os.path.isdir(d) and cat.is_remote_node(node) \
                    and cat.remote_data is not None:
                rd = _pull_placement_fallback(cat, table, shard, node)
                if rd is not None:
                    d = rd
            if not os.path.isdir(d):
                if attempt + 1 < len(nodes):
                    from citus_tpu.executor.executor import GLOBAL_COUNTERS
                    GLOBAL_COUNTERS.bump("connection_failovers")
                    continue
                return None  # never written on any placement: empty shard
            from citus_tpu.storage.overlay import visible_meta
            if visible_meta(d)["row_count"] == 0:
                return None  # authoritative: the shard is empty
            return ShardReader(d, table.schema)
        except Exception:
            if attempt + 1 < len(nodes):
                from citus_tpu.executor.executor import GLOBAL_COUNTERS
                GLOBAL_COUNTERS.bump("connection_failovers")
                continue
            raise
    return None


def _cut_batches(cat: Catalog, plan: PhysicalPlan, shard_index: int,
                 max_batch_rows: int, node_override: Optional[int],
                 prefer_secondary: bool, land=None):
    """Yield (parts, n_rows) with 0 < n_rows <= max_batch_rows: the
    shard's rows in order, cut at exactly max_batch_rows.  Chunk sizes
    are arbitrary (deletes shorten them), so a cut may fall anywhere in
    a chunk, and more than once in a large one.  Without ``land`` the
    parts are decoded ``_Piece``s; with it the cut is planned from the
    footers and ``land(parts, n_rows)`` — whose result is yielded in
    their place — brings the bytes in."""
    from citus_tpu.testing.faults import FAULTS
    table = plan.bound.table
    fault_key = f"{table.name}:{table.shards[shard_index].shard_id}"
    chunks = _shard_chunks(cat, plan, shard_index, node_override,
                           prefer_secondary, decoded=land is None)
    # NOTE: under the pipelined executor this generator runs on the
    # host decode thread (executor/pipeline.py HostPrefetcher), so the
    # decode_batch fault point below fires there — delays injected on
    # it model slow host-side decompression overlapping device compute.
    # Spans: one stripe_read per batch (footers, file read + decompress
    # of every chunk it takes), never one per chunk, and none held
    # across the yield.  Its children name the work from inside:
    # footer_read per stripe (the reader's generators close it before
    # they yield), batch_layout / native_decode / stripe_fallback per
    # batch (_Landing), stripe_fallback(reason=cut) for a cut chunk.
    rest = None            # what the last cut left of its chunk
    exhausted = False
    while not exhausted:
        parts, rows = [], 0
        with _trace.span("stripe_read") as sp:
            while rows < max_batch_rows:
                part = rest
                rest = None
                if part is None:
                    part = next(chunks, None)
                    if part is None:
                        exhausted = True
                        break
                room = max_batch_rows - rows
                if part.rows > room:
                    # a chunk still on disk is decoded here, once
                    whole = part.cut(0, part.rows)
                    rest, part = whole.cut(room, whole.rows), whole.cut(0, room)
                if part.rows:
                    parts.append(part)
                    rows += part.rows
            if sp.recording:
                sp.set(chunks=len(parts), rows=rows)
            if rows and land is not None:
                parts = land(parts, rows)
        if rows:
            FAULTS.hit("decode_batch", fault_key)
            yield parts, rows


def load_shard_batches(
    cat: Catalog, plan: PhysicalPlan, shard_index: int, *,
    max_batch_rows: int = 1 << 22,
    node_override: Optional[int] = None,
    prefer_secondary: bool = False,
) -> Iterator[tuple[dict[str, np.ndarray], dict[str, np.ndarray], int]]:
    """Yield (values, valids, n_rows) raw column groups of at most
    max_batch_rows rows for one shard placement: unpadded, in the
    stored dtypes (the host paths' input; the device paths take
    load_padded_batches)."""
    cols = plan.scan_columns
    for pieces, n in _cut_batches(cat, plan, shard_index, max_batch_rows,
                                  node_override, prefer_secondary):
        with _trace.span("concat") as sp:
            values = {c: _concat([p.values[c] for p in pieces]) for c in cols}
            masks = {c: _concat([np.ones(p.rows, bool) if p.validity[c] is None
                                 else p.validity[c] for p in pieces])
                     for c in cols}
            if sp.recording:
                sp.set(chunks=len(pieces),
                       bytes=int(sum(v.nbytes for v in values.values())
                                 + sum(m.nbytes for m in masks.values())))
        yield values, masks, n


def _concat(arrays: list) -> np.ndarray:
    return arrays[0] if len(arrays) == 1 else np.concatenate(arrays)


def load_padded_batches(
    cat: Catalog, plan: PhysicalPlan, shard_index: int, *,
    min_batch_rows: int = 8192, max_batch_rows: int = 1 << 22,
    prefer_secondary: bool = False,
) -> Iterator[ShardBatch]:
    """Yield one shard placement's rows as ShardBatches of at most
    max_batch_rows rows, each landed ONCE: per scan column one array of
    padded_rows elements in the device dtype, which the batch's chunks
    are decoded into, or copied into where they cannot be (the cast
    happens in that copy).  Full batches have n_rows == padded_rows; the
    shard's last batch is padded to its bucket with zeroed values,
    validity True and row_mask False."""
    def land(parts, n):
        return _Landing(plan, parts, n,
                        bucket_rows(n, min_batch_rows, max_batch_rows))

    for landing, _n in _cut_batches(cat, plan, shard_index, max_batch_rows,
                                    None, prefer_secondary, land):
        yield landing.finish(shard_index)


class _Landing:
    """One batch's arrays, from the planned cut to the ShardBatch.
    Making it (under the caller's stripe_read span) allocates them and
    brings every byte in: the chunks still on disk by one native call
    straight into place where the reader allows it and the dtypes
    agree, the others stripe by stripe into fresh arrays.  ``finish``
    (the pad span) copies what did not land in place and fills the
    validity, the row mask and the tail.

    Three spans say where the making's time goes: ``batch_layout``
    (allocation, what lands how, the native call's arguments),
    ``native_decode`` (``BatchDecode.run``: the thread asleep on its
    pool) and ``stripe_fallback`` (the stripe reader, with why)."""

    def __init__(self, plan: PhysicalPlan, parts: list, n_rows: int,
                 padded_rows: int):
        schema = plan.bound.table.schema
        self.columns = plan.scan_columns
        self._index = {c: k for k, c in enumerate(self.columns)}
        self.n_rows, self.padded_rows = n_rows, padded_rows
        self.copies: list[tuple[int, _Piece]] = []   # (first row, piece)
        self.bytes_in_place = 0
        with _trace.span("batch_layout") as sp:
            self.values = [
                np.empty(padded_rows, schema.scan_dtype(c, device=True))
                for c in self.columns]
            # only a column stored as the device reads it can land in place
            self._same_dtype = [c for c, v in zip(self.columns, self.values)
                                if schema.scan_dtype(c) == v.dtype]
            groups = []    # (first row, the refs of consecutive chunks of ONE stripe)
            at = 0
            for p in parts:
                if isinstance(p, _Piece):
                    self.copies.append((at, p))
                elif groups and groups[-1][1][-1].st is p.st:
                    groups[-1][1].append(p)
                else:
                    groups.append((at, [p]))
                at += p.rows
            decode, slow = self._lay_out(groups, in_place=True)
            decode.prepare()
            if sp.recording:
                sp.set(streams=decode.streams, files=decode.files,
                       bytes_alloc=int(sum(v.nbytes for v in self.values)))
        if decode.run():
            self.bytes_in_place = decode.bytes
        else:
            # a stream failed: the stripe readers name the fault, or
            # fall back to the Python codecs, as they always did
            _, slow = self._lay_out(groups, in_place=False)
        self._fall_back(slow)

    def _lay_out(self, groups: list, in_place: bool):
        """-> (the BatchDecode of every stream that lands in place, and
        what does not: ``[(reader, stripe, columns, refs, first rows,
        why)]`` for the stripe reader)."""
        decode = BatchDecode(self.values)
        slow = []
        for at, refs in groups:
            reader, st = refs[0].reader, refs[0].st
            chunks = [r.ci for r in refs]
            rows = np.array([r.rows for r in refs], np.int64)
            starts = at + np.cumsum(rows) - rows
            if in_place:
                why = {c: "cast" for c in self.columns
                       if c not in self._same_dtype}
                why.update(reader.not_in_place(st, chunks, self._same_dtype))
            else:
                why = dict.fromkeys(self.columns, "codec")
            for c in self.columns:
                if c in why:
                    continue
                k = self._index[c]
                stats = st.footer.columns[reader.schema.scan_storage_name(c)]
                decode.add(st, [stats[ci] for ci in chunks], k,
                           starts * self.values[k].itemsize)
            if why:
                slow.append((reader, st, [c for c in self.columns if c in why],
                             refs, starts, sorted(set(why.values()))))
        return decode, slow

    def _fall_back(self, slow: list) -> None:
        """Read what could not land in place stripe by stripe; ``pad``
        copies it in."""
        if not slow:
            return
        with _trace.span("stripe_fallback") as sp:
            chunks = nbytes = 0
            reasons = set()
            for reader, st, columns, refs, starts, why in slow:
                reasons.update(why)
                for s, r, b in zip(starts, refs, reader.stripe_chunks(
                        st, columns, [r.ci for r in refs])):
                    self.copies.append((int(s), _decoded(b, r.rows)))
                    chunks += 1
                    nbytes += sum(v.nbytes for v in b.values.values())
            if sp.recording:
                sp.set(chunks=chunks, bytes=int(nbytes),
                       reason=",".join(sorted(reasons)))

    def finish(self, shard_index: int) -> ShardBatch:
        n_rows, padded_rows = self.n_rows, self.padded_rows
        with _trace.span("pad") as sp:
            # shared and read-only until a column shows a NULL
            valids = [_all_true(padded_rows)] * len(self.columns)
            copied = 0
            for at, p in self.copies:
                for c, v in p.values.items():
                    k = self._index[c]
                    self.values[k][at:at + p.rows] = v
                    if p.validity[c] is not None:
                        if not valids[k].flags.writeable:
                            valids[k] = np.ones(padded_rows, bool)
                        valids[k][at:at + p.rows] = p.validity[c]
                    copied += v.nbytes
            for v in self.values:
                v[n_rows:] = 0
            row_mask = _all_true(padded_rows)
            if n_rows < padded_rows:
                row_mask = np.ones(padded_rows, bool)
                row_mask[n_rows:] = False
            out = ShardBatch(tuple(self.values), tuple(valids), row_mask,
                             n_rows, padded_rows, shard_index,
                             self.bytes_in_place, copied)
            if sp.recording:
                sp.set(bytes_in=copied, bytes_in_place=self.bytes_in_place,
                       bytes_out=out.nbytes)
        return out


@functools.lru_cache(maxsize=32)
def _all_true(n: int) -> np.ndarray:
    """THE read-only array of ``n`` True: the validity of every column
    without a NULL in the batch and the row mask of every full batch of
    that bucket, whoever decodes it.  A bucket is a power of two up to
    the batch limit, so a handful are ever made; a fresh one a column
    and batch was 32 MB of new pages a batch for ``pad`` to fault in
    (PERF.md section 7, PR 45)."""
    a = np.ones(n, bool)
    a.flags.writeable = False
    return a


def empty_batch(table: TableMeta, plan: PhysicalPlan, padded_rows: int,
                shard_index: int) -> ShardBatch:
    cols, valids = [], []
    for c in plan.scan_columns:
        dt = table.schema.scan_dtype(c, device=True)
        cols.append(np.zeros(padded_rows, dt))
        valids.append(np.ones(padded_rows, bool))
    return ShardBatch(tuple(cols), tuple(valids), np.zeros(padded_rows, bool),
                      0, padded_rows, shard_index)
