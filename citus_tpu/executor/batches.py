"""Shard data -> padded device batches.

The host-side half of the scan: read pruned chunks (decompressed on the
host), cut the stream at exactly ``max_batch_rows`` rows, and assemble
each cut once into a buffer padded to a power-of-two row bucket, so XLA
sees a small, stable set of shapes (the recompile-pressure discipline
the reference gets from prepared-statement plan caching).  A full batch
is its bucket (no padding); only a shard's last batch is padded.
Padding rows carry ``row_mask=False`` and zeroed values.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, NamedTuple, Optional

import numpy as np

from citus_tpu.catalog import Catalog, TableMeta
from citus_tpu.observability import trace as _trace
from citus_tpu.planner.physical import PhysicalPlan
from citus_tpu.storage import ShardReader
from citus_tpu.storage.writer import _load_meta
import os


@dataclass
class ShardBatch:
    cols: tuple[np.ndarray, ...]    # device dtypes, padded
    valids: tuple[np.ndarray, ...]
    row_mask: np.ndarray
    n_rows: int                      # real rows
    padded_rows: int
    shard_index: int

    @property
    def nbytes(self) -> int:
        """Bytes of the padded arrays: what one H2D copy of it ships."""
        return int(sum(c.nbytes for c in self.cols)
                   + sum(v.nbytes for v in self.valids)
                   + self.row_mask.nbytes)


class _Piece(NamedTuple):
    """A chunk, or the slice of one that a batch cut fell into (views,
    no copy)."""
    values: dict[str, np.ndarray]
    validity: dict[str, Optional[np.ndarray]]   # None = all valid
    rows: int

    def cut(self, lo: int, hi: int) -> "_Piece":
        return _Piece(
            {c: v[lo:hi] for c, v in self.values.items()},
            {c: None if m is None else m[lo:hi]
             for c, m in self.validity.items()}, hi - lo)


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
                  node_override: Optional[int], prefer_secondary: bool):
    """Yield the pruned chunks of one shard placement, in order."""
    table = plan.bound.table
    shard = table.shards[shard_index]
    from citus_tpu.testing.faults import FAULTS
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
    reader = None
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
                return  # never written on any placement: empty shard
            from citus_tpu.storage.overlay import visible_meta
            if visible_meta(d)["row_count"] == 0:
                return  # authoritative: the shard is empty
            reader = ShardReader(d, table.schema)
            break
        except Exception:
            if attempt + 1 < len(nodes):
                from citus_tpu.executor.executor import GLOBAL_COUNTERS
                GLOBAL_COUNTERS.bump("connection_failovers")
                continue
            raise
    if reader is None:
        return
    cols = plan.scan_columns
    if plan.index_eq is not None:
        col, value, _name = plan.index_eq
        yield from reader.lookup_eq(cols, col, value, plan.intervals)
    else:
        yield from reader.scan(cols, plan.intervals)


def _cut_batches(cat: Catalog, plan: PhysicalPlan, shard_index: int,
                 max_batch_rows: int, node_override: Optional[int],
                 prefer_secondary: bool):
    """Yield (pieces, n_rows) with 0 < n_rows <= max_batch_rows: the
    shard's rows in order, cut at exactly max_batch_rows.  Chunk sizes
    are arbitrary (deletes shorten them), so a cut may fall anywhere in
    a chunk, and more than once in a large one."""
    from citus_tpu.testing.faults import FAULTS
    table = plan.bound.table
    fault_key = f"{table.name}:{table.shards[shard_index].shard_id}"
    chunks = _shard_chunks(cat, plan, shard_index, node_override,
                           prefer_secondary)
    # NOTE: under the pipelined executor this generator runs on the
    # host decode thread (executor/pipeline.py HostPrefetcher), so the
    # decode_batch fault point below fires there — delays injected on
    # it model slow host-side decompression overlapping device compute.
    # Spans: one stripe_read per batch (file read + decompress of every
    # chunk it pulls), never one per chunk, and none held across the
    # yield.
    rest = None            # what the last cut left of its chunk
    exhausted = False
    while not exhausted:
        pieces, rows = [], 0
        with _trace.span("stripe_read") as sp:
            while rows < max_batch_rows:
                piece = rest
                rest = None
                if piece is None:
                    b = next(chunks, None)
                    if b is None:
                        exhausted = True
                        break
                    piece = _Piece(b.values, b.validity, b.row_count)
                room = max_batch_rows - rows
                if piece.rows > room:
                    rest = piece.cut(room, piece.rows)
                    piece = piece.cut(0, room)
                if piece.rows:
                    pieces.append(piece)
                    rows += piece.rows
            if sp.recording:
                sp.set(chunks=len(pieces), rows=rows)
        if rows:
            FAULTS.hit("decode_batch", fault_key)
            yield pieces, rows


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
    max_batch_rows rows, each assembled ONCE: per scan column one array
    of padded_rows elements in the device dtype that the chunk slices
    are written into (the cast happens in that write).  Full batches
    have n_rows == padded_rows; the shard's last batch is padded to its
    bucket with zeroed values, validity True and row_mask False."""
    for pieces, n in _cut_batches(cat, plan, shard_index, max_batch_rows,
                                  None, prefer_secondary):
        yield _assemble(plan, pieces, n,
                        bucket_rows(n, min_batch_rows, max_batch_rows),
                        shard_index)


def _assemble(plan: PhysicalPlan, pieces: list, n_rows: int,
              padded_rows: int, shard_index: int) -> ShardBatch:
    schema = plan.bound.table.schema
    cols_out, valids_out = [], []
    with _trace.span("pad") as sp:
        for c in plan.scan_columns:
            dt = schema.scan_dtype(c, device=True)
            if len(pieces) == 1 and padded_rows == n_rows:
                # one chunk that fills its bucket: no copy unless it casts
                m = pieces[0].validity[c]
                cols_out.append(pieces[0].values[c].astype(dt, copy=False))
                valids_out.append(np.ones(n_rows, bool) if m is None else m)
                continue
            v = np.empty(padded_rows, dt)
            m = np.empty(padded_rows, bool)
            at = 0
            for p in pieces:
                v[at:at + p.rows] = p.values[c]
                m[at:at + p.rows] = \
                    True if p.validity[c] is None else p.validity[c]
                at += p.rows
            v[n_rows:] = 0
            m[n_rows:] = True
            cols_out.append(v)
            valids_out.append(m)
        row_mask = np.ones(padded_rows, bool)
        row_mask[n_rows:] = False
        out = ShardBatch(tuple(cols_out), tuple(valids_out), row_mask,
                         n_rows, padded_rows, shard_index)
        if sp.recording:
            sp.set(bytes_in=int(sum(
                p.values[c].nbytes
                + (0 if p.validity[c] is None else p.validity[c].nbytes)
                for p in pieces for c in plan.scan_columns)),
                   bytes_out=out.nbytes)
    return out


def empty_batch(table: TableMeta, plan: PhysicalPlan, padded_rows: int,
                shard_index: int) -> ShardBatch:
    cols, valids = [], []
    for c in plan.scan_columns:
        dt = table.schema.scan_dtype(c, device=True)
        cols.append(np.zeros(padded_rows, dt))
        valids.append(np.ones(padded_rows, bool))
    return ShardBatch(tuple(cols), tuple(valids), np.zeros(padded_rows, bool),
                      0, padded_rows, shard_index)
