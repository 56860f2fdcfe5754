"""A device scan's batch is laid out from the footers and decoded by ONE
native call straight into its bucket-shaped arrays
(executor/batches.py ``_Landing``, storage/reader.py ``BatchDecode``).

The reference here is the chunk-by-chunk assembly: ``load_shard_batches``
(the raw host path: ``ShardReader.scan`` and ``np.concatenate``, which
this mechanism does not touch) cast and padded by hand.  Whatever the
geometry and whatever a stripe holds, the two must agree byte for byte.
"""

import contextlib
import decimal
import os
import threading
import time

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.config import ColumnarSettings, Settings
from citus_tpu.executor import executor as ex
from citus_tpu.executor.batches import (
    bucket_rows, load_padded_batches, load_shard_batches,
)
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.pipeline import HostPrefetcher
from citus_tpu.planner import parse_sql
from citus_tpu.planner.bind import bind_select
from citus_tpu.planner.physical import plan_select
from citus_tpu.storage import reader as rd

MIN_ROWS = 16
DDL = ("CREATE TABLE t (k bigint NOT NULL, q int, flag boolean, "
       "price decimal(12,2), d date, tag text)")
SELECT = "SELECT k, q, flag, price, d, tag FROM t"


def _rows(n, nulls, start=0, seed=0):
    rng = np.random.default_rng(seed + n)
    out = []
    for i in range(start, start + n):
        null = nulls and rng.random() < 0.15
        out.append((i,
                    None if null else int(rng.integers(-2**31, 2**31)),
                    None if null and i % 2 else bool(rng.integers(0, 2)),
                    None if null and i % 3 == 0 else
                    decimal.Decimal(int(rng.integers(0, 10**7))) / 100,
                    None if null and i % 5 == 0 else
                    int(rng.integers(8000, 11000)),
                    "AFNORX"[int(rng.integers(0, 6))]))
    return out


def _plan(cl, sql=SELECT):
    return plan_select(cl.catalog, bind_select(cl.catalog, parse_sql(sql)[0]))


def _cluster(tmp_path, chunk, stripe, codec="zstd"):
    return ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=chunk,
                                  stripe_row_limit=stripe, compression=codec)))


def _reference(cl, plan, max_rows):
    """Chunk-by-chunk assembly, by hand, from the raw host path."""
    schema = plan.bound.table.schema
    out = []
    for values, masks, n in load_shard_batches(cl.catalog, plan, 0,
                                               max_batch_rows=max_rows):
        padded = bucket_rows(n, MIN_ROWS, max_rows)
        cols, valids = [], []
        for c in plan.scan_columns:
            v = np.zeros(padded, schema.scan_dtype(c, device=True))
            v[:n] = values[c]
            m = np.ones(padded, bool)
            m[:n] = masks[c]
            cols.append(v)
            valids.append(m)
        row_mask = np.zeros(padded, bool)
        row_mask[:n] = True
        stored = sum(values[c].nbytes for c in plan.scan_columns)
        out.append((cols, valids, row_mask, n, padded, stored))
    return out


def _assert_identical(cl, plan, max_rows):
    """-> (bytes in place, bytes copied) over the shard's batches."""
    got = list(load_padded_batches(cl.catalog, plan, 0, min_batch_rows=MIN_ROWS,
                                   max_batch_rows=max_rows))
    want = _reference(cl, plan, max_rows)
    assert len(got) == len(want)
    for b, (cols, valids, row_mask, n, padded, stored) in zip(got, want):
        assert (b.n_rows, b.padded_rows) == (n, padded)
        for have, ref in zip(b.cols + b.valids + (b.row_mask,),
                             cols + valids + [row_mask]):
            assert have.dtype == ref.dtype and have.shape == ref.shape
            assert have.tobytes() == ref.tobytes()
        # every stored byte of the scan columns arrived one way or the other
        assert b.bytes_in_place + b.bytes_copied == stored
    return (sum(b.bytes_in_place for b in got),
            sum(b.bytes_copied for b in got))


# id, what the table holds, codec, decode_threads, native library present
CONTENTS = [
    ("clean_zstd_1_thread", "clean", "zstd", 1, True),
    ("clean_zstd_8_threads", "clean", "zstd", 8, True),
    ("clean_lz4", "clean", "lz4", 8, True),
    ("clean_zlib", "clean", "zlib", 2, True),
    ("clean_uncompressed", "clean", "none", 8, True),
    ("nulls", "nulls", "zstd", 8, True),
    ("deletes", "deletes", "zstd", 8, True),
    ("nulls_and_deletes", "nulls+deletes", "lz4", 1, True),
    ("column_added_after_a_stripe", "added", "zstd", 8, True),
    ("no_native_library", "nulls+deletes", "zstd", 8, False),
    ("no_native_library_uncompressed", "clean", "none", 1, False),
]


def _geometry(seed):
    """Random (rows, chunk rows, stripe rows, max_batch_rows): cuts fall
    inside chunks, several in one stripe, and the last stripe is partial."""
    rng = np.random.default_rng(1000 + seed)
    chunk = int(rng.integers(16, 160))
    stripe = chunk * int(rng.integers(1, 6))      # the writer wants a multiple
    max_rows = int(rng.integers(max(8, chunk // 3), 3 * stripe))
    rows = int(rng.integers(2 * stripe, 6 * stripe)) + 1
    return rows, chunk, stripe, max_rows


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("content", CONTENTS, ids=[c[0] for c in CONTENTS])
def test_padded_batches_equal_the_chunk_by_chunk_assembly(
        tmp_path, monkeypatch, content, seed):
    _id, holds, codec, threads, native = content
    if not native:
        import citus_tpu.native as nat
        monkeypatch.setattr(nat, "get_lib", lambda: None)
    monkeypatch.setattr(rd, "_DECODE_THREADS", threads)
    n, chunk, stripe, max_rows = _geometry(seed + 7 * CONTENTS.index(content))
    cl = _cluster(tmp_path, chunk, stripe, codec)
    try:
        if holds == "added":
            cl.execute("CREATE TABLE t (k bigint NOT NULL, q int, flag boolean,"
                       " price decimal(12,2))")
            cl.execute("SELECT create_distributed_table('t', 'k', 1)")
            cl.copy_from("t", rows=[r[:4] for r in _rows(n // 2, False)])
            cl.execute("ALTER TABLE t ADD COLUMN d date")
            cl.execute("ALTER TABLE t ADD COLUMN tag text")
            cl.copy_from("t", rows=_rows(n - n // 2, False, start=n // 2))
        else:
            cl.execute(DDL)
            cl.execute("SELECT create_distributed_table('t', 'k', 1)")
            cl.copy_from("t", rows=_rows(n, "nulls" in holds, seed=seed))
        if "deletes" in holds:
            # a run of rows inside one stripe, and a sprinkle over all
            cl.execute(f"DELETE FROM t WHERE k BETWEEN {stripe + 3} AND "
                       f"{stripe + chunk + 9} OR k % 11 = 5")
        in_place, copied = _assert_identical(cl, _plan(cl), max_rows)
        assert in_place + copied > 0
        if not native or holds in ("deletes", "nulls+deletes"):
            assert in_place == 0        # every stripe has a deleted row
        elif max_rows >= 2 * chunk:     # some chunk lies whole in a batch
            assert in_place > 0
    finally:
        cl.close()


# id, rows, chunk rows, stripe rows, max_batch_rows
SHAPES = [
    ("cut_inside_a_chunk", 900, 128, 256, 200),
    ("several_cuts_in_one_chunk", 700, 512, 512, 96),
    ("several_cuts_in_one_stripe", 2000, 32, 1024, 160),
    ("cuts_on_chunk_borders", 1024, 64, 256, 128),
    ("partial_last_stripe", 1000, 64, 256, 4096),
    ("shard_smaller_than_min_batch_rows", 11, 64, 256, 4096),
    ("one_row", 1, 64, 256, 64),
    ("batch_of_many_stripes", 3000, 16, 64, 2048),
]


@pytest.mark.parametrize("holds", ["clean", "nulls"])
@pytest.mark.parametrize("shape", SHAPES, ids=[s[0] for s in SHAPES])
def test_cuts_land_where_the_assembly_put_them(tmp_path, shape, holds):
    _id, n, chunk, stripe, max_rows = shape
    cl = _cluster(tmp_path, chunk, stripe)
    try:
        cl.execute(DDL)
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", rows=_rows(n, holds == "nulls"))
        plan = _plan(cl)
        in_place, copied = _assert_identical(cl, plan, max_rows)
        schema = plan.bound.table.schema
        stored = {c: schema.scan_dtype(c).itemsize for c in plan.scan_columns}
        assert in_place + copied == n * sum(stored.values())
        if holds == "clean" and _id == "cuts_on_chunk_borders":
            # no chunk is cut, so only the casting columns are copied
            cast = [c for c in plan.scan_columns
                    if schema.scan_dtype(c) != schema.scan_dtype(c, device=True)]
            assert sorted(cast) == ["flag", "q"]
            assert copied == n * sum(stored[c] for c in cast)
    finally:
        cl.close()


def test_pruned_chunks_are_neither_read_nor_laid_out(tmp_path):
    """Chunk pruning happens before the layout: a WHERE that refutes
    most chunks yields the same batches as the raw path, all in place."""
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", columns={"k": np.arange(4000), "v": np.arange(4000) * 7})
        plan = _plan(cl, "SELECT k, v FROM t WHERE k >= 1000 AND k < 1300")
        in_place, copied = _assert_identical(cl, plan, 128)
        assert copied == 0 and 300 * 16 <= in_place <= (300 + 2 * 64) * 16
    finally:
        cl.close()


# ------------------------------------------------------------ the native call


def _decode(dst, streams, path, codec="none"):
    """Run BatchDecode over hand-made (offset, comp len, raw len, col,
    byte offset) streams of one file."""
    from citus_tpu.storage.format import ChunkStats, StripeFooter
    st = rd.StripeScan("f", path, StripeFooter(0, 0, [], codec), [],
                       np.zeros(0, np.int64), None)
    d = rd.BatchDecode(dst)
    for off, clen, rlen, col, at in streams:
        d.add(st, [ChunkStats(value_offset=off, value_length=clen,
                              value_raw_length=rlen)], col,
              np.array([at], np.int64))
    return d.run(), d


def test_native_call_writes_each_stream_at_its_absolute_place(tmp_path):
    path = str(tmp_path / "f")
    payload = np.arange(64, dtype=np.int64)
    with open(path, "wb") as fh:
        fh.write(payload.tobytes())
    a, b = np.zeros(32, np.int64), np.zeros(48, np.int64)
    ok, d = _decode([a, b], [(0, 128, 128, 0, 128), (128, 256, 256, 1, 64),
                             (384, 128, 128, 0, 0)], path)
    assert ok and d.bytes == 512
    assert np.array_equal(a, np.r_[payload[48:64], payload[0:16]])
    assert np.array_equal(b[8:40], payload[16:48])
    assert not b[:8].any() and not b[40:].any()


@pytest.mark.parametrize("fault", ["past_the_end_of_its_array", "negative_place",
                                   "no_such_column", "past_the_end_of_the_file",
                                   "lengths_disagree", "no_such_file"])
def test_native_call_refuses_a_stream_it_cannot_place(tmp_path, fault):
    path = str(tmp_path / "f")
    with open(path, "wb") as fh:
        fh.write(bytes(256))
    guard = np.full(48, 7, np.int64)          # dst is its middle third
    dst = guard[16:32]
    stream = {"past_the_end_of_its_array": (0, 128, 128, 0, 8),
              "negative_place": (0, 64, 64, 0, -8),
              "no_such_column": (0, 64, 64, 3, 0),
              "past_the_end_of_the_file": (200, 128, 128, 0, 0),
              "lengths_disagree": (0, 64, 128, 0, 0),
              "no_such_file": (0, 64, 64, 0, 0)}[fault]
    if fault == "no_such_file":
        path += ".gone"
    ok, _d = _decode([dst], [stream], path)
    assert not ok
    assert (guard[:16] == 7).all() and (guard[32:] == 7).all()


def _first_stripe(cl):
    shard = cl.catalog.table("t").shards[0]
    d = cl.catalog.shard_dir("t", shard.shard_id, shard.placements[0])
    return os.path.join(d, sorted(f for f in os.listdir(d)
                                  if f.endswith(".cts"))[0])


@pytest.mark.parametrize("codec", ["zstd", "none"])
def test_a_corrupt_stream_fails_as_the_raw_path_does(tmp_path, codec):
    """The native call refuses the batch, the stripe readers take over
    and the fault surfaces from where it always did: the padded path
    and the raw path raise the same error (or, uncompressed, both read
    the same flipped bytes)."""
    cl = _cluster(tmp_path, 64, 256, codec)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        rng = np.random.default_rng(3)
        cl.copy_from("t", columns={"k": np.arange(1000),
                                   "v": rng.integers(0, 2**40, 1000)})
        path = _first_stripe(cl)
        with open(path, "r+b") as fh:
            fh.seek(8)                  # the first value stream's header
            head = fh.read(12)
            fh.seek(8)
            fh.write(bytes(b ^ 0xFF for b in head))
        plan = _plan(cl, "SELECT k, v FROM t")

        def outcome(load):
            try:
                return [hb for hb in load()]
            except Exception as e:                # noqa: BLE001 - compared below
                return type(e), str(e)

        raw = outcome(lambda: load_shard_batches(cl.catalog, plan, 0,
                                                 max_batch_rows=512))
        padded = outcome(lambda: load_padded_batches(
            cl.catalog, plan, 0, min_batch_rows=MIN_ROWS, max_batch_rows=512))
        if codec == "zstd":
            assert isinstance(raw, tuple) and raw == padded
        else:
            assert not isinstance(raw, tuple)
            _assert_identical(cl, plan, 512)
    finally:
        cl.close()


def test_a_footer_that_lies_about_a_length_is_not_decoded_in_place(tmp_path):
    """value_raw_length != rows x width: the reader keeps the stream off
    the in-place list, and the stripe reader refuses it as before."""
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", columns={"k": np.arange(600), "v": np.arange(600)})
        plan = _plan(cl, "SELECT k, v FROM t")
        shard = cl.catalog.table("t").shards[0]
        r = rd.ShardReader(cl.catalog.shard_dir(
            "t", shard.shard_id, shard.placements[0]), plan.bound.table.schema)
        st = next(r.scan_stripes(["k", "v"]))
        assert list(r.not_in_place(st, st.chunks, ["k", "v"])) == []
        st.footer.columns["v"][1].value_raw_length -= 8
        assert list(r.not_in_place(st, st.chunks, ["k", "v"])) == ["v"]
        assert list(r.not_in_place(st, [0, 2], ["k", "v"])) == []
        st.footer.columns["k"][0].has_nulls = True
        assert list(r.not_in_place(st, st.chunks, ["k", "v"])) == ["k", "v"]
        st.del_mask = np.zeros(st.footer.row_count, bool)
        assert list(r.not_in_place(st, [2], ["k", "v"])) == ["k", "v"]
    finally:
        cl.close()


# ------------------------------------------------------- the decode thread


def test_prefetcher_close_during_a_native_call_returns_promptly(
        tmp_path, monkeypatch):
    """close() while the decode thread is inside the batch's native
    call: the call runs to its end (one batch at most), the thread
    exits, nothing further is decoded and close() does not hang."""
    import citus_tpu.native as nat
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", columns={"k": np.arange(4000), "v": np.arange(4000)})
        plan = _plan(cl, "SELECT k, v FROM t")
        lib = nat.get_lib()
        assert lib is not None
        inside, calls = threading.Event(), []

        class Slow:
            """The library with a native call that takes 0.4 s."""
            def __getattr__(self, name):
                return getattr(lib, name)

            def ct_decode_batch(self, *args):
                calls.append(time.monotonic())
                if len(calls) == 2:
                    inside.set()
                    time.sleep(0.4)
                return lib.ct_decode_batch(*args)

        slow = Slow()
        monkeypatch.setattr(nat, "get_lib", lambda: slow)
        pf = HostPrefetcher(load_padded_batches(
            cl.catalog, plan, 0, min_batch_rows=MIN_ROWS, max_batch_rows=256),
            depth=1)
        first = next(pf)
        assert first.n_rows == 256 and first.bytes_in_place == 256 * 16
        assert inside.wait(timeout=10)
        t0 = time.monotonic()
        pf.close()
        took = time.monotonic() - t0
        assert not pf._thread.is_alive()
        assert took < 5.0
        assert len(calls) <= 3          # of the 16 batches the shard holds
    finally:
        cl.close()


# ------------------------------------------- counters and EXPLAIN ANALYZE


def _explain_counts(cl, monkeypatch, sql, max_rows):
    import functools
    monkeypatch.setattr(ex, "load_padded_batches", functools.partial(
        load_padded_batches, max_batch_rows=max_rows))
    cl.execute(f"SET citus.executor_min_batch_rows = {MIN_ROWS}")
    GLOBAL_CACHE.clear()
    before = cl.counters.snapshot()
    text = "\n".join(r[0] for r in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    after = cl.counters.snapshot()
    return text, {k: after.get(k, 0) - before.get(k, 0)
                  for k in ("decode_bytes_in_place", "decode_bytes_copied")}


@pytest.mark.parametrize("n_dev", [1, 4])
def test_a_clean_table_is_decoded_wholly_in_place(tmp_path, monkeypatch,
                                                  limit_devices, n_dev):
    limit_devices(n_dev)
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint, "
                   "p decimal(12,2))")
        cl.execute("SELECT create_distributed_table('t', 'k', 2)")
        n = 3000
        cl.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n) * 3,
                                   "p": np.arange(n) / 4})
        sql = "SELECT count(*), sum(v), sum(p) FROM t"
        text, d = _explain_counts(cl, monkeypatch, sql, 128)
        assert d == {"decode_bytes_in_place": n * 16, "decode_bytes_copied": 0}
        assert "decoded in place 1.000" in text, text
        # a resident scan decodes nothing: nothing to report
        again = "\n".join(r[0] for r in cl.execute("EXPLAIN ANALYZE " + sql).rows)
        assert "decoded in place" not in again
        assert cl.execute(sql).rows == [(n, sum(range(n)) * 3, decimal.Decimal(
            sum(range(n))) / 4)]
    finally:
        GLOBAL_CACHE.clear()
        cl.close()


def test_one_deleted_row_makes_its_stripe_a_copy(tmp_path, monkeypatch,
                                                 limit_devices):
    limit_devices(1)
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        n = 1024                                  # four stripes of 256 rows
        cl.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n)})
        # in the last stripe, so the cuts before it stay on chunk borders
        cl.execute("DELETE FROM t WHERE k = 800")
        text, d = _explain_counts(cl, monkeypatch,
                                  "SELECT count(*), sum(v), min(k) FROM t", 128)
        assert d == {"decode_bytes_in_place": 3 * 256 * 16,
                     "decode_bytes_copied": 255 * 16}
        share = 3 * 256 / (n - 1)
        assert f"decoded in place {share:.3f}" in text, text
        assert cl.execute("SELECT count(*), sum(v), min(k) FROM t").rows == \
            [(n - 1, sum(range(n)) - 800, 0)]
    finally:
        GLOBAL_CACHE.clear()
        cl.close()


def test_the_counters_are_exported(tmp_path):
    from citus_tpu.observability.export import METRIC_HELP
    from citus_tpu.stats import StatCounters
    for name in ("decode_bytes_in_place", "decode_bytes_copied"):
        assert name in StatCounters.COUNTERS and name in METRIC_HELP


# ------------------------------------------- what the pool says of itself


def _under_a_trace(cl, work):
    """Run ``work()`` under a forced trace on this thread -> (its result,
    the trace)."""
    from citus_tpu.observability import trace as T
    qt = T.begin_query("test", cl.settings.observability, force=True)
    try:
        out = work()
    finally:
        qt.finish()
    return out, qt.trace


@pytest.mark.parametrize("codec,threads", [("zstd", 1), ("zstd", 8),
                                           ("none", 4)])
def test_the_stats_array_changes_no_decoded_byte(tmp_path, monkeypatch,
                                                 codec, threads):
    """The native call with its stats array (a recording native_decode
    span) lands the same bytes as without, and what it reports adds up:
    bytes_raw is BatchDecode.bytes, the pool's read + decompress time is
    positive, its workers are at most the threads asked for."""
    monkeypatch.setattr(rd, "_DECODE_THREADS", threads)
    cl = _cluster(tmp_path, 64, 256, codec)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        rng = np.random.default_rng(11)
        cl.copy_from("t", columns={"k": np.arange(3000),
                                   "v": rng.integers(0, 2**40, 3000)})
        plan = _plan(cl, "SELECT k, v FROM t")

        def load():
            return list(load_padded_batches(
                cl.catalog, plan, 0, min_batch_rows=MIN_ROWS,
                max_batch_rows=1024))

        plain = load()
        traced, tr = _under_a_trace(cl, load)
        assert len(plain) == len(traced) == 3
        for a, b in zip(plain, traced):
            assert a.bytes_in_place == b.bytes_in_place > 0
            for x, y in zip(a.cols + a.valids + (a.row_mask,),
                            b.cols + b.valids + (b.row_mask,)):
                assert x.tobytes() == y.tobytes()
        calls = tr.find_all("native_decode")
        assert len(calls) == 3
        assert [s.attrs["bytes_raw"] for s in calls] == \
            [b.bytes_in_place for b in traced]
        for s, b in zip(calls, traced):
            assert s.attrs["read_ms"] + s.attrs["decompress_ms"] > 0
            assert (s.attrs["decompress_ms"] == 0) == (codec == "none")
            assert 1 <= s.attrs["threads"] <= threads
            assert s.attrs["busy_max_ms"] <= s.duration_ms
            assert s.attrs["streams"] == 2 * -(-b.n_rows // 64)
            assert s.attrs["files"] == -(-b.n_rows // 256)
            assert s.attrs["bytes_comp"] > 0
    finally:
        cl.close()


def test_batchdecode_reports_bytes_raw_as_its_own_count(tmp_path):
    """The hand-made streams of ``_decode``, under a trace: bytes_raw of
    the span is ``BatchDecode.bytes``; a refused stream still closes the
    span and reports nothing decoded in place."""
    path = str(tmp_path / "f")
    payload = np.arange(64, dtype=np.int64)
    with open(path, "wb") as fh:
        fh.write(payload.tobytes())
    cl = _cluster(tmp_path, 64, 256)
    try:
        a = np.zeros(64, np.int64)
        (ok, d), tr = _under_a_trace(cl, lambda: _decode(
            [a], [(0, 256, 256, 0, 256), (256, 256, 256, 0, 0)], path))
        assert ok and np.array_equal(a, np.r_[payload[32:], payload[:32]])
        (span,) = tr.find_all("native_decode")
        assert span.attrs["bytes_raw"] == d.bytes == 512
        assert span.attrs["streams"] == 2 and span.attrs["files"] == 1
        assert span.attrs["read_ms"] > 0 and span.attrs["decompress_ms"] == 0
        (ok, _d), tr = _under_a_trace(cl, lambda: _decode(
            [a], [(0, 256, 256, 0, 512)], path))
        assert not ok and tr.find("native_decode").t1 is not None
    finally:
        cl.close()


@pytest.mark.parametrize("traced", [False, True])
def test_the_raw_scan_reads_only_as_far_as_it_is_asked(tmp_path, monkeypatch,
                                                       traced):
    """``ShardReader.scan`` is as lazy traced as untraced: a caller that
    takes one chunk of the Python reader pays for one, and the stripe's
    chunk_read span is closed by the time the chunk is handed out."""
    from citus_tpu.observability import trace as T
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", columns={"k": np.arange(600), "v": np.arange(600)})
        plan = _plan(cl, "SELECT k, v FROM t")
        shard = cl.catalog.table("t").shards[0]
        r = rd.ShardReader(cl.catalog.shard_dir(
            "t", shard.shard_id, shard.placements[0]), plan.bound.table.schema)
        read = []
        monkeypatch.setattr(r, "_scan_stripe_native", lambda *a: None)
        monkeypatch.setattr(rd, "read_chunk", lambda *a, _f=rd.read_chunk:
                            read.append(1) or _f(*a))
        tr = T.Trace()
        root = tr.open_span("query", None, {})
        with T.activate(tr, root) if traced else contextlib.nullcontext():
            rows = r.scan(["k", "v"])
            first = next(rows)
            assert first.row_count == 64 and len(read) == 2
            if traced:
                (span,) = tr.find_all("chunk_read")
                assert span.t1 is not None and span.attrs == {
                    "chunks": 4, "rows": 256}
            assert sum(b.row_count for b in rows) == 600 - 64
        assert len(tr.find_all("chunk_read")) == (3 if traced else 0)
    finally:
        cl.close()


def test_not_in_place_names_the_reason(tmp_path):
    """Each column that cannot land in place comes with why."""
    cl = _cluster(tmp_path, 64, 256)
    try:
        cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        cl.copy_from("t", columns={"k": np.arange(600), "v": np.arange(600)})
        cl.execute("ALTER TABLE t ADD COLUMN late bigint")
        plan = _plan(cl, "SELECT k, v, late FROM t")
        shard = cl.catalog.table("t").shards[0]
        r = rd.ShardReader(cl.catalog.shard_dir(
            "t", shard.shard_id, shard.placements[0]), plan.bound.table.schema)
        cols = ["k", "v", "late"]
        st = next(r.scan_stripes(cols))
        assert r.not_in_place(st, st.chunks, cols) == {"late": "late_column"}
        st.footer.columns["v"][1].value_raw_length -= 8
        assert r.not_in_place(st, st.chunks, ["k", "v"]) == {"v": "codec"}
        st.footer.columns["k"][0].has_nulls = True
        assert r.not_in_place(st, st.chunks, ["k", "v"]) == \
            {"k": "nulls", "v": "codec"}
        st.del_mask = np.zeros(st.footer.row_count, bool)
        assert r.not_in_place(st, [2], cols) == dict.fromkeys(cols, "deletes")
        st.footer.codec = "brotli"
        assert r.not_in_place(st, [2], cols) == dict.fromkeys(cols, "codec")
    finally:
        cl.close()
