"""Shard reader: stripe/chunk iteration with skip-list pruning.

Reference analog: ColumnarBeginRead/ColumnarReadNextRow and chunk skipping
(src/backend/columnar/columnar_reader.c:148-180,323) — but instead of
materializing one row per call, the unit of delivery is a whole chunk
batch (values + validity per projected column), ready to be padded and
shipped to a device kernel.  Pruning happens on the host from footer
min/max stats before any stream bytes are read or decompressed, like
SelectedChunkMask/BuildBaseConstraint in the reference.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np

from citus_tpu.errors import AnalysisError, StorageError
from citus_tpu.observability import trace as _trace
from citus_tpu.schema import Schema
from citus_tpu.storage.format import (
    StripeFooter, read_chunk, read_stripe_footer,
)
from citus_tpu.storage.writer import _load_meta


# SET citus.decode_threads pushes here (the process-wide native pool has
# no cluster handle, like kernel_cache's set_capacity); None = read the
# ambient settings
_DECODE_THREADS: Optional[int] = None

#: threads a native call gets at least, where nobody set a number, before
#: a scan decodes a further stream beside it (PERF.md section 6, PR 45)
_THREADS_A_PRODUCER = 4

# .ways: how many threads decode batches at once beside this one, itself
# included (executor/pipeline.py's producers say so of themselves)
_sharing = threading.local()


def set_decode_threads(n: int) -> None:
    global _DECODE_THREADS
    _DECODE_THREADS = int(n)


def _configured_threads() -> int:
    """citus.decode_threads as set: 0 = auto."""
    n = _DECODE_THREADS
    if n is None:
        from citus_tpu.config import current_settings
        n = current_settings().executor.decode_threads
    return n


def usable_cores() -> int:
    """Cores this process may run on: its affinity mask where the
    platform has one (a container's share, not the host's count)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:
        return os.cpu_count() or 1


def decode_thread_count() -> int:
    """Threads of ONE call of the native read+decompress pool --
    citus.decode_threads; 0 = auto: the usable cores, at most 8,
    divided by the threads that make such calls at once (1 unless the
    calling thread is one of ``decode_pool_shared``'s)."""
    n = _configured_threads()
    if n > 0:
        return n
    return max(1, min(8, usable_cores() // getattr(_sharing, "ways", 1)))


def decode_producers(streams: int) -> int:
    """How many of a scan's ``streams`` may be decoded at once, each by
    a thread of its own, so that the producers' native calls together
    stay inside the usable cores: one a stream, and no more than leave
    every call ``citus.decode_threads`` threads (auto: four)."""
    per_call = _configured_threads()
    if per_call <= 0:
        per_call = _THREADS_A_PRODUCER
    return max(1, min(streams, usable_cores() // per_call))


@contextlib.contextmanager
def decode_pool_shared(ways: int):
    """This thread is one of ``ways`` that decode batches at once: its
    native calls take their share of the cores, not all of them."""
    _sharing.ways = max(1, ways)
    try:
        yield
    finally:
        del _sharing.ways


@dataclass(frozen=True)
class Interval:
    """Closed/open numeric interval constraint on a column's physical
    values — the pruning currency (analog of the reference's base
    constraint over the skip list's min/max)."""

    column: str
    lo: Optional[float] = None
    hi: Optional[float] = None
    lo_inclusive: bool = True
    hi_inclusive: bool = True

    def admits(self, cmin, cmax) -> bool:
        """Could any value in [cmin, cmax] satisfy this constraint?"""
        if cmin is None or cmax is None:
            return True  # no stats -> cannot prune
        if self.lo is not None:
            if cmax < self.lo or (cmax == self.lo and not self.lo_inclusive):
                return False
        if self.hi is not None:
            if cmin > self.hi or (cmin == self.hi and not self.hi_inclusive):
                return False
        return True


@dataclass
class ChunkBatch:
    """One chunk group's worth of projected columns."""

    values: dict[str, np.ndarray]
    validity: dict[str, Optional[np.ndarray]]  # None = all valid
    row_count: int
    stripe_file: str
    chunk_index: int
    # first row's offset within the stripe (position addressing for DML)
    chunk_row_offset: int = 0


@dataclass
class StripeScan:
    """What a scan takes from one stripe, known from its footer and its
    deletion bitmap before any stream byte is read."""

    file: str
    path: str
    footer: StripeFooter
    chunks: list[int]                  # the selected chunk groups
    offsets: np.ndarray                # first row of every chunk group
    del_mask: Optional[np.ndarray]     # deleted rows of the stripe, if any

    def live_rows(self, ci: int) -> int:
        """Rows chunk group ``ci`` yields once deletes are subtracted."""
        n = self.footer.chunk_row_counts[ci]
        if self.del_mask is None:
            return n
        at = int(self.offsets[ci])
        return n - int(self.del_mask[at:at + n].sum())


class ShardReader:
    """Reads one shard directory written by ShardWriter."""

    def __init__(self, directory: str, schema: Schema):
        from citus_tpu.storage.overlay import visible_meta
        self.directory = directory
        self.schema = schema
        self.meta = visible_meta(directory)

    @property
    def row_count(self) -> int:
        return self.meta["row_count"]

    @property
    def stripe_files(self) -> list[str]:
        return [s["file"] for s in self.meta["stripes"]]

    def scan(
        self,
        columns: list[str],
        constraints: Optional[list[Interval]] = None,
        apply_deletes: bool = True,
        only_stripes: Optional[set] = None,
    ) -> Iterator[ChunkBatch]:
        """Yield chunk batches for the projected ``columns``, skipping
        chunks refuted by ``constraints`` (conjunctive semantics) and
        subtracting deletion bitmaps (unless ``apply_deletes=False``,
        used by DML that needs original row positions).  ``only_stripes``
        restricts to a stripe-file subset (index-lookup fallback)."""
        for st in self.scan_stripes(columns, constraints, apply_deletes,
                                    only_stripes):
            # as lazy as ``yield from``, and one span a stripe, never one
            # a chunk: chunk_read is the reader's first step, under which
            # the native reader brings the stripe's selected chunks in
            # (the Python reader its first; its later chunks are the
            # caller's own time), closed before a batch is handed out
            batches = self.stripe_chunks(st, columns, st.chunks)
            with _trace.span("chunk_read") as sp:
                first = next(batches, None)
                if sp.recording:
                    sp.set(chunks=len(st.chunks),
                           rows=sum(st.footer.chunk_row_counts[ci]
                                    for ci in st.chunks))
            if first is not None:
                yield first
                yield from batches

    def scan_stripes(
        self,
        columns: list[str],
        constraints: Optional[list[Interval]] = None,
        apply_deletes: bool = True,
        only_stripes: Optional[set] = None,
    ) -> Iterator[StripeScan]:
        """The first half of a scan, per stripe with a selected chunk:
        footer, chunk pruning, deletion bitmap — everything known before
        a stream byte is read, so a caller can lay a batch out first."""
        from citus_tpu.storage.deletes import deleted_mask
        from citus_tpu.storage.overlay import visible_deletes
        constraints = constraints or []
        for col in columns:
            self.schema.scan_column(col)  # validate projection
        delete_cache = None if apply_deletes else {}
        try:
            from citus_tpu.executor.executor import GLOBAL_COUNTERS
        except ImportError:
            GLOBAL_COUNTERS = None
        # the footers' files are named from the directory as this scan
        # found it, like the stripe list its reader holds
        dir_fd = None
        try:
            for stripe in self.meta["stripes"]:
                if only_stripes is not None and stripe["file"] not in only_stripes:
                    continue
                path = os.path.join(self.directory, stripe["file"])
                # one footer_read per stripe, closed before the yield: the
                # footer (decoded once a file: attr cached), the chunk pruning
                # and the deletion bitmap (the shard's map of deletes and
                # the directory are opened under the first one)
                with _trace.span("footer_read") as sp:
                    if delete_cache is None:
                        delete_cache = visible_deletes(self.directory)
                    if dir_fd is None:
                        dir_fd = os.open(self.directory,
                                         os.O_RDONLY | os.O_DIRECTORY)
                    footer = read_stripe_footer(path, sp, dir_fd)
                    selected = self._selected_chunks(footer, constraints)
                    n_selected = int(np.count_nonzero(selected))
                    if GLOBAL_COUNTERS is not None:
                        GLOBAL_COUNTERS.bump("chunks_total", footer.chunk_count)
                        GLOBAL_COUNTERS.bump("chunks_selected", n_selected)
                        # rows refuted by footer min/max BEFORE any stream bytes
                        # of theirs are read or decompressed — the fused hot
                        # loop's admission win
                        if n_selected < footer.chunk_count:
                            skipped = int(np.diff(
                                footer.chunk_bounds)[~selected].sum())
                            if skipped:
                                GLOBAL_COUNTERS.bump("fused_rows_skipped", skipped)
                    st = None
                    if n_selected:
                        offsets = footer.chunk_bounds[:-1]
                        del_mask = None
                        if apply_deletes and stripe["file"] in delete_cache:
                            del_mask = deleted_mask(self.directory, stripe["file"],
                                                    footer.row_count, delete_cache)
                        st = StripeScan(stripe["file"], path, footer,
                                        [int(i) for i in np.nonzero(selected)[0]],
                                        offsets, del_mask)
                    if sp.recording:
                        sp.set(chunks=int(footer.chunk_count),
                               selected=n_selected,
                               deletes=st is not None and st.del_mask is not None)
                if st is not None:
                    yield st
        finally:
            if dir_fd is not None:
                os.close(dir_fd)

    def stripe_chunks(self, st: StripeScan, columns: list[str],
                      chunks: list[int]) -> Iterator[ChunkBatch]:
        """The second half: read and decompress ``chunks`` of one
        stripe into fresh arrays (one native call a stripe, else the
        Python reader) and subtract the stripe's deletes."""
        footer = st.footer
        native = self._scan_stripe_native(st.path, footer, columns, chunks)
        if native is not None:
            for b in native:
                b.chunk_row_offset = int(st.offsets[b.chunk_index])
                yield self._subtract_deletes(b, st.del_mask)
            return
        with open(st.path, "rb") as fh:
            for ci in chunks:
                vals, valid = {}, {}
                for col in columns:
                    c = self.schema.scan_column(col)
                    stream = footer.columns.get(
                        self.schema.scan_storage_name(col))
                    if stream is None:
                        # column added after this stripe: all NULL
                        n_ = footer.chunk_row_counts[ci]
                        vals[col] = np.zeros(n_, c.type.storage_dtype)
                        valid[col] = np.zeros(n_, bool)
                        continue
                    v, m = read_chunk(fh, footer, stream[ci], c.type.storage_dtype)
                    vals[col], valid[col] = v, m
                b = ChunkBatch(
                    values=vals, validity=valid,
                    row_count=footer.chunk_row_counts[ci],
                    stripe_file=st.file, chunk_index=ci,
                    chunk_row_offset=int(st.offsets[ci]))
                yield self._subtract_deletes(b, st.del_mask)

    def lookup_eq(
        self,
        columns: list[str],
        column: str,
        value,
        constraints: Optional[list[Interval]] = None,
    ) -> Iterator[ChunkBatch]:
        """Index-driven point lookup: yield batches holding ONLY the rows
        whose ``column`` equals ``value`` (live rows; deletes applied).
        Stripes without a segment fall back to a pruned full scan —
        never wrong, just slower (reference analog: an index scan over
        columnar random row access, columnar_reader.c:370-391)."""
        from citus_tpu.storage.deletes import deleted_mask
        from citus_tpu.storage.index import positions_eq
        from citus_tpu.storage.overlay import visible_deletes
        try:
            from citus_tpu.executor.executor import GLOBAL_COUNTERS
        except ImportError:
            GLOBAL_COUNTERS = None
        delete_cache = visible_deletes(self.directory)
        fallback: set = set()
        # per stripe: index_probe, footer_read and (where a row is
        # left) chunk_read, each closed before the stripe's rows are
        # yielded
        for stripe in self.meta["stripes"]:
            with _trace.span("index_probe") as sp:
                pos = positions_eq(self.directory, stripe["file"], column,
                                   value)
                if sp.recording:
                    sp.set(positions=-1 if pos is None else int(pos.size))
            if pos is None:
                fallback.add(stripe["file"])
                continue
            path = os.path.join(self.directory, stripe["file"])
            with _trace.span("footer_read") as sp:
                footer = read_stripe_footer(path, sp)
                if GLOBAL_COUNTERS is not None:
                    GLOBAL_COUNTERS.bump("index_lookups")
                    GLOBAL_COUNTERS.bump("chunks_total", footer.chunk_count)
                deletes = False
                if pos.size and stripe["file"] in delete_cache:
                    dm = deleted_mask(self.directory, stripe["file"],
                                      footer.row_count, delete_cache)
                    if dm is not None:
                        deletes = True
                        pos = pos[~dm[pos]]
                needed = ()
                if pos.size:
                    bounds = footer.chunk_bounds
                    chunk_of = np.searchsorted(bounds, pos, "right") - 1
                    needed = np.unique(chunk_of)
                    if GLOBAL_COUNTERS is not None:
                        GLOBAL_COUNTERS.bump("chunks_selected",
                                             int(needed.size))
                if sp.recording:
                    sp.set(chunks=int(footer.chunk_count),
                           selected=len(needed), deletes=deletes)
            if not len(needed):
                continue
            found = []
            with _trace.span("chunk_read") as sp, open(path, "rb") as fh:
                for ci in needed:
                    local = np.sort(pos[chunk_of == ci]) - bounds[ci]
                    vals, valid = {}, {}
                    for col in columns:
                        c = self.schema.scan_column(col)
                        stream = footer.columns.get(
                            self.schema.scan_storage_name(col))
                        if stream is None:
                            # column added after this stripe: all NULL
                            vals[col] = np.zeros(local.size, c.type.storage_dtype)
                            valid[col] = np.zeros(local.size, bool)
                            continue
                        v, m = read_chunk(fh, footer, stream[int(ci)],
                                          c.type.storage_dtype)
                        vals[col] = v[local]
                        valid[col] = None if m is None else m[local]
                    found.append(ChunkBatch(values=vals, validity=valid,
                                            row_count=int(local.size),
                                            stripe_file=stripe["file"],
                                            chunk_index=int(ci)))
                if sp.recording:
                    sp.set(chunks=len(found),
                           rows=sum(b.row_count for b in found))
            yield from found
        if fallback:
            yield from self.scan(columns, constraints,
                                 only_stripes=fallback)

    @staticmethod
    def _subtract_deletes(b: ChunkBatch, del_mask) -> ChunkBatch:
        if del_mask is None:
            return b
        sl = del_mask[b.chunk_row_offset:b.chunk_row_offset + b.row_count]
        if not sl.any():
            return b
        keep = ~sl
        b.values = {c: v[keep] for c, v in b.values.items()}
        b.validity = {c: (m[keep] if m is not None else None)
                      for c, m in b.validity.items()}
        b.row_count = int(keep.sum())
        return b

    def _scan_stripe_native(self, path, footer, columns, sel_idx):
        """Batched read+decompress of all selected streams of one stripe
        through the C++ runtime (one call per column); None = unavailable."""
        from citus_tpu.native import CODEC_IDS, get_lib
        lib = get_lib()
        if lib is None or footer.codec not in CODEC_IDS:
            return None
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        cid = CODEC_IDS[footer.codec]
        # one native call per stripe: every (column, chunk) value stream
        streams = []  # (col, k, stats)
        missing = []  # columns added after this stripe was written
        for col in columns:
            sname = self.schema.scan_storage_name(col)
            if sname not in footer.columns:
                missing.append(col)
                continue
            for k, ci in enumerate(sel_idx):
                streams.append((col, k, footer.columns[sname][ci]))
        offs = np.array([s.value_offset for _, _, s in streams], np.int64)
        clens = np.array([s.value_length for _, _, s in streams], np.int64)
        rlens = np.array([s.value_raw_length for _, _, s in streams], np.int64)
        dsts = np.concatenate([[0], np.cumsum(rlens)[:-1]]).astype(np.int64)
        total = int(rlens.sum())
        out = np.empty(max(total, 1), np.uint8)
        if len(streams) >= 8:
            # thread-pooled read+decompress (each worker owns a file
            # handle + scratch) — saturates cold-scan bandwidth
            nt = decode_thread_count()
            rc = lib.ct_read_streams_mt(
                path.encode(), cid, len(streams),
                offs.ctypes.data_as(i64p), clens.ctypes.data_as(i64p),
                rlens.ctypes.data_as(i64p), dsts.ctypes.data_as(i64p),
                out.ctypes.data_as(u8p), max(total, 1), nt)
        else:
            scratch = np.empty(max(int(clens.max(initial=0)), 1), np.uint8)
            rc = lib.ct_read_streams(
                path.encode(), cid, len(streams),
                offs.ctypes.data_as(i64p), clens.ctypes.data_as(i64p),
                rlens.ctypes.data_as(i64p), dsts.ctypes.data_as(i64p),
                out.ctypes.data_as(u8p), max(total, 1),
                scratch.ctypes.data_as(u8p), len(scratch))
        if rc != 0:
            return None  # fall back to the python reader
        per_col_vals: dict[str, list] = {c: [None] * len(sel_idx) for c in columns}
        per_col_valid: dict[str, list] = {c: [None] * len(sel_idx) for c in columns}
        for si, (col, k, s) in enumerate(streams):
            dt = self.schema.scan_column(col).type.storage_dtype
            arr = out[dsts[si]:dsts[si] + rlens[si]].view(dt)
            if arr.shape[0] != s.row_count:
                return None
            per_col_vals[col][k] = arr
        for col in missing:
            dt = self.schema.scan_column(col).type.storage_dtype
            for k, ci in enumerate(sel_idx):
                n_ = footer.chunk_row_counts[ci]
                per_col_vals[col][k] = np.zeros(n_, dt)
                per_col_valid[col][k] = np.zeros(n_, bool)
        # validity streams (usually few; read individually)
        null_streams = [(col, k, footer.columns[self.schema.scan_storage_name(col)][ci])
                        for col in columns if col not in missing
                        for k, ci in enumerate(sel_idx)
                        if footer.columns[self.schema.scan_storage_name(col)][ci].has_nulls]
        if null_streams:
            from citus_tpu.storage import compression as comp
            with open(path, "rb") as fh:
                for col, k, s in null_streams:
                    fh.seek(s.exists_offset)
                    braw = comp.decompress(fh.read(s.exists_length),
                                           footer.codec, s.exists_raw_length)
                    bits = np.frombuffer(braw, np.uint8)
                    unpacked = np.empty(s.row_count, np.uint8)
                    lib.ct_unpack_bits(
                        bits.ctypes.data_as(u8p), s.row_count,
                        unpacked.ctypes.data_as(u8p))
                    per_col_valid[col][k] = unpacked.astype(bool)
        out_batches = []
        for k, ci in enumerate(sel_idx):
            out_batches.append(ChunkBatch(
                values={c: per_col_vals[c][k] for c in columns},
                validity={c: per_col_valid[c][k] for c in columns},
                row_count=footer.chunk_row_counts[ci],
                stripe_file=os.path.basename(path), chunk_index=ci))
        return out_batches

    def not_in_place(self, st: StripeScan, chunks: list[int],
                     columns: list[str]) -> dict[str, str]:
        """``{column: why}`` for the ``columns`` whose value streams of
        ``chunks`` cannot be decoded where a caller wants them
        (``BatchDecode``) and arrive through ``stripe_chunks``, decided
        from what the footer shows: ``codec`` (the native library lacks
        the stripe's codec or is missing, or a stream is not exactly its
        rows long), ``deletes`` (a row of the stripe is deleted),
        ``late_column`` (the column was added after the stripe was
        written), ``nulls`` (a stream has a validity bitmap, which needs
        unpacking)."""
        from citus_tpu.native import CODEC_IDS, get_lib
        if get_lib() is None or st.footer.codec not in CODEC_IDS:
            return dict.fromkeys(columns, "codec")
        if st.del_mask is not None:
            return dict.fromkeys(columns, "deletes")
        out = {}
        for col in columns:
            stats = st.footer.columns.get(self.schema.scan_storage_name(col))
            if stats is None:
                out[col] = "late_column"
                continue
            width = self.schema.scan_dtype(col).itemsize
            if any(stats[ci].has_nulls for ci in chunks):
                out[col] = "nulls"
            elif not all(
                    stats[ci].row_count == st.footer.chunk_row_counts[ci]
                    and stats[ci].value_raw_length == stats[ci].row_count * width
                    for ci in chunks):
                out[col] = "codec"
        return out

    def chunk_counts(self, constraints: Optional[list[Interval]] = None) -> tuple[int, int]:
        """(selected_chunks, total_chunks) — for EXPLAIN/statistics."""
        sel = tot = 0
        for stripe in self.meta["stripes"]:
            footer = read_stripe_footer(os.path.join(self.directory, stripe["file"]))
            mask = self._selected_chunks(footer, constraints or [])
            sel += int(mask.sum())
            tot += footer.chunk_count
        return sel, tot

    def _selected_chunks(self, footer, constraints: list[Interval]) -> np.ndarray:
        keep = [True] * footer.chunk_count
        for c in constraints:
            try:
                sname = self.schema.scan_storage_name(c.column)
            except AnalysisError:
                sname = c.column
            chunks = footer.columns.get(sname)
            if chunks is None:
                # column added after this stripe: every row is NULL there,
                # so no range constraint can match
                return np.zeros(footer.chunk_count, dtype=bool)
            for ci, stats in enumerate(chunks):
                # all null: no row can match a range
                if keep[ci] and (stats.row_count == stats.null_count or
                                 not c.admits(stats.minimum, stats.maximum)):
                    keep[ci] = False
        return np.array(keep, dtype=bool)


class BatchDecode:
    """The value streams of one scan batch, gathered stripe by stripe,
    then read and decompressed by ONE native call on the pool
    (``decode_thread_count()`` threads over every stream of the batch),
    each stream straight to its place in the caller's arrays."""

    def __init__(self, dst: list[np.ndarray]):
        self._dst = dst
        self._paths: list[bytes] = []
        self._codecs: list[int] = []
        self._file: list[int] = []
        self._col: list[int] = []
        self._stats: list = []
        self._at: list[np.ndarray] = []
        self._args = None                 # prepare()'s, and the arrays
        self._keep: list = []             # their pointers borrow
        self.bytes = 0                    # decompressed bytes gathered

    @property
    def streams(self) -> int:
        return len(self._stats)

    @property
    def files(self) -> int:
        return len(self._paths)

    def add(self, st: StripeScan, stats: list, col: int,
            byte_offsets: np.ndarray) -> None:
        """Queue ``stats`` (a column's ChunkStats of consecutive chunks
        of ``st``) for ``dst[col]``, stream i at ``byte_offsets[i]``."""
        from citus_tpu.native import CODEC_IDS
        path = st.path.encode()
        if not self._paths or self._paths[-1] != path:
            self._paths.append(path)
            self._codecs.append(CODEC_IDS[st.footer.codec])
        self._file += [len(self._paths) - 1] * len(stats)
        self._col += [col] * len(stats)
        self._stats += stats
        self._at.append(byte_offsets)

    def prepare(self) -> None:
        """Marshal what was queued into the native call's argument
        arrays (once): the last of the batch's layout, apart from
        ``run`` so that a tracing caller can time it there."""
        if self._args is not None or not self._stats:
            return
        import ctypes
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)

        def arr(values, dt, ptr):
            a = np.ascontiguousarray(values, dt)
            self._keep.append(a)
            return a.ctypes.data_as(ptr)

        rlens = np.array([s.value_raw_length for s in self._stats], np.int64)
        self.bytes = int(rlens.sum())
        paths = (ctypes.c_char_p * len(self._paths))(*self._paths)
        ptrs = (ctypes.c_void_p * len(self._dst))(
            *[d.ctypes.data for d in self._dst])
        self._args = (
            len(self._paths), paths, arr(self._codecs, np.int32, i32p),
            len(self._stats), arr(self._file, np.int32, i32p),
            arr([s.value_offset for s in self._stats], np.int64, i64p),
            arr([s.value_length for s in self._stats], np.int64, i64p),
            arr(rlens, np.int64, i64p), arr(self._col, np.int32, i32p),
            arr(np.concatenate(self._at), np.int64, i64p), len(self._dst),
            ptrs, arr([d.nbytes for d in self._dst], np.int64, i64p),
            decode_thread_count())

    def run(self) -> bool:
        """Decode everything queued; False = a stream failed (the caller
        reads those chunks the slow way, which names the fault).  The
        native call alone is the ``native_decode`` span; only while that
        span records is the pool asked to time itself."""
        if not self._stats:
            return True
        if not all(d.flags.c_contiguous and d.flags.writeable
                   for d in self._dst):
            return False
        self.prepare()
        import ctypes
        from citus_tpu.native import DECODE_STATS, get_lib
        with _trace.span("native_decode") as sp:
            pool = (ctypes.c_double * len(DECODE_STATS))() \
                if sp.recording else None
            rc = get_lib().ct_decode_batch(*self._args, pool)
            if pool is not None:
                sp.set(streams=self.streams, files=self.files,
                       bytes_comp=int(sum(s.value_length
                                          for s in self._stats)),
                       bytes_raw=self.bytes,
                       **{k: int(v) if k == "threads" else round(v, 3)
                          for k, v in zip(DECODE_STATS, pool)})
        return rc == 0
