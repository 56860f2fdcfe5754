"""How a scan batch is formed (executor/batches.py): the chunk stream of
a shard is cut at exactly ``max_batch_rows`` rows and each cut is
assembled once into a bucket-shaped buffer in the device dtypes.

Every case builds a one-shard table whose stored order is the insertion
order, so "the concatenation of all batches" has one right answer: the
inserted rows, minus the deleted ones, in order.
"""

import decimal
import functools

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.config import ColumnarSettings, Settings
from citus_tpu.executor import executor as ex
from citus_tpu.executor.batches import (
    bucket_rows, load_padded_batches, load_shard_batches,
)
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.planner import parse_sql
from citus_tpu.planner.bind import bind_select
from citus_tpu.planner.physical import plan_select

MIN_ROWS = 16

# (id, rows, chunk rows, stripe rows, max_batch_rows, delete predicate)
CASES = [
    ("chunks_divide_limit", 1000, 64, 256, 256, None),
    ("chunks_do_not_divide_limit", 1000, 48, 192, 128, None),
    ("chunk_larger_than_limit", 1300, 512, 512, 128, None),
    ("deletes_shorten_chunks", 1000, 64, 256, 128, "k % 7 = 3"),
    ("deletes_inside_a_large_chunk", 1300, 512, 512, 128, "k % 5 = 1"),
    ("rows_a_multiple_of_limit", 512, 64, 256, 128, None),
    ("limit_not_a_power_of_two", 1000, 64, 256, 100, None),
    ("one_chunk_fills_its_bucket", 64, 64, 64, 64, None),
    ("one_short_chunk", 40, 64, 64, 64, None),
    ("every_row_deleted", 300, 64, 256, 128, "k >= 0"),
]


def _rows(n):
    """k bigint, q int (stored int32, device int64), flag boolean
    (stored int8, device int32), price decimal(12,2).  NULLs in q, flag
    and price fall on both sides of every multiple of 32, 50 and 64 —
    wherever a case's cut lands, a NULL sits next to it."""
    rng = np.random.default_rng(n)
    near_cut = {i + d for step in (32, 50, 64) for i in range(0, n + step, step)
                for d in (-1, 0)}
    out = []
    for i in range(n):
        null = i in near_cut or rng.random() < 0.1
        out.append((i,
                    None if null else int(rng.integers(-2**31, 2**31)),
                    None if null and i % 2 else bool(rng.integers(0, 2)),
                    None if null and i % 3 == 0 else
                    decimal.Decimal(int(rng.integers(0, 10**7))) / 100))
    return out


def _expected(rows):
    """Physical (values, valid) per column, device dtypes."""
    def col(j, dt, conv):
        valid = np.array([r[j] is not None for r in rows], bool)
        vals = np.array([conv(r[j]) if r[j] is not None else 0 for r in rows],
                        dt)
        return vals, valid
    return {"k": col(0, np.int64, int), "q": col(1, np.int64, int),
            "flag": col(2, np.int32, int),
            "price": col(3, np.int64, lambda d: int(d * 100))}


@pytest.fixture()
def shard(tmp_path, request):
    _id, n, chunk, stripe, max_rows, delete = request.param
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=chunk,
                                  stripe_row_limit=stripe)))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, q int, flag boolean, "
               "price decimal(12,2))")
    cl.execute("SELECT create_distributed_table('t', 'k', 1)")
    rows = _rows(n)
    cl.copy_from("t", rows=rows)
    if delete:
        cl.execute(f"DELETE FROM t WHERE {delete}")
        keep = {r[0] for r in cl.execute("SELECT k FROM t").rows}
        rows = [r for r in rows if r[0] in keep]
        assert len(rows) < n
    bound = bind_select(cl.catalog,
                        parse_sql("SELECT k, q, flag, price FROM t")[0])
    plan = plan_select(cl.catalog, bound)
    yield cl, plan, _expected(rows), len(rows), max_rows
    cl.close()


def _case_params():
    return pytest.mark.parametrize("shard", CASES, indirect=True,
                                   ids=[c[0] for c in CASES])


@_case_params()
def test_padded_batches_cut_exactly_and_assembled_once(shard):
    cl, plan, expected, n_rows, max_rows = shard
    batches = list(load_padded_batches(
        cl.catalog, plan, 0, min_batch_rows=MIN_ROWS, max_batch_rows=max_rows))
    assert sum(b.n_rows for b in batches) == n_rows
    assert len(batches) == -(-n_rows // max_rows)
    for b in batches[:-1]:
        # a full batch is its bucket: no padding at all
        assert b.n_rows == b.padded_rows == max_rows
    schema = plan.bound.table.schema
    for b in batches:
        assert 0 < b.n_rows <= max_rows
        assert b.padded_rows == bucket_rows(b.n_rows, MIN_ROWS, max_rows)
        assert b.n_rows <= b.padded_rows <= max_rows
        assert b.row_mask.dtype == bool and b.row_mask.shape == (b.padded_rows,)
        assert b.row_mask[:b.n_rows].all() and not b.row_mask[b.n_rows:].any()
        for c, v, m in zip(plan.scan_columns, b.cols, b.valids):
            assert v.dtype == schema.scan_dtype(c, device=True)
            assert v.shape == m.shape == (b.padded_rows,)
            assert m.dtype == bool
            # padding rows: zero values (the kernels clamp group codes
            # computed from them on that assumption), validity True
            assert not v[b.n_rows:].any() and m[b.n_rows:].all()
    for i, c in enumerate(plan.scan_columns):
        vals, valid = expected[c]
        got_valid = np.concatenate(
            [b.valids[i][:b.n_rows] for b in batches] or [np.zeros(0, bool)])
        got = np.concatenate(
            [b.cols[i][:b.n_rows] for b in batches] or [np.zeros(0, vals.dtype)])
        assert np.array_equal(got_valid, valid), c
        assert np.array_equal(got[valid], vals[valid]), c


@_case_params()
def test_raw_batches_hold_at_most_max_batch_rows(shard):
    """The host paths' input (projection, host hash aggregation,
    COPY TO's frames): the same exact cut, unpadded, stored dtypes."""
    cl, plan, expected, n_rows, max_rows = shard
    raw = list(load_shard_batches(cl.catalog, plan, 0,
                                  max_batch_rows=max_rows))
    full, rest = divmod(n_rows, max_rows)
    assert [n for _v, _m, n in raw] == [max_rows] * full + [rest] * (rest > 0)
    schema = plan.bound.table.schema
    for c in plan.scan_columns:
        vals, valid = expected[c]
        for v, m, n in raw:
            assert v[c].dtype == schema.scan_dtype(c) and m[c].dtype == bool
            assert v[c].shape == m[c].shape == (n,)
        got_valid = np.concatenate([m[c] for _v, m, _n in raw]
                                   or [np.zeros(0, bool)])
        got = np.concatenate([v[c] for v, _m, _n in raw]
                             or [np.zeros(0, vals.dtype)])
        assert np.array_equal(got_valid, valid), c
        assert np.array_equal(got[valid], vals[valid]), c


def test_a_chunk_is_decoded_where_the_kernel_reads_it_unless_it_casts(tmp_path):
    """No deletes, no NULLs: ``k`` (bigint, int64 on both sides) is
    decompressed straight into the batch's own array; ``q`` (int,
    stored int32, device int64) is decoded apart and cast in the copy."""
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=64,
                                  stripe_row_limit=64)))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, q int)")
    cl.execute("SELECT create_distributed_table('t', 'k', 1)")
    cl.copy_from("t", columns={"k": np.arange(128), "q": np.arange(128)})
    plan = plan_select(cl.catalog, bind_select(
        cl.catalog, parse_sql("SELECT k, q FROM t")[0]))
    for hb, (values, _masks, n) in zip(
            load_padded_batches(cl.catalog, plan, 0, min_batch_rows=MIN_ROWS,
                                max_batch_rows=64),
            load_shard_batches(cl.catalog, plan, 0, max_batch_rows=64)):
        assert n == hb.n_rows == hb.padded_rows == 64
        k, q = (hb.cols[plan.scan_columns.index(c)] for c in ("k", "q"))
        assert k.base is None and k.dtype == values["k"].dtype
        assert q.base is None and q.dtype == np.int64
        assert np.array_equal(k, values["k"]) and np.array_equal(q, values["q"])
        assert hb.bytes_in_place == values["k"].nbytes == 64 * 8
        assert hb.bytes_copied == values["q"].nbytes == 64 * 4
    cl.close()


# ------------------------------------------------- through cl.execute


Q1_SHAPED = ("SELECT flag, g, count(*), sum(q), sum(price), avg(price), "
             "sum(price * (1 - disc)), min(q), max(price) FROM li "
             "WHERE k < 3900 GROUP BY flag, g ORDER BY flag, g")


def _small_batches(monkeypatch, cl, max_rows):
    """Cut the executor's batches at max_rows instead of 1 << 22."""
    monkeypatch.setattr(ex, "load_padded_batches", functools.partial(
        load_padded_batches, max_batch_rows=max_rows))
    cl.execute(f"SET citus.executor_min_batch_rows = {MIN_ROWS}")


def _lineitem_like(cl, n=4000, shards=2):
    cl.execute("CREATE TABLE li (k bigint NOT NULL, q int, flag boolean, "
               "g text, price decimal(12,2), disc decimal(12,2))")
    cl.execute(f"SELECT create_distributed_table('li', 'k', {shards})")
    rng = np.random.default_rng(5)
    rows = []
    for i in range(n):
        rows.append((i,
                     None if i % 11 == 0 else int(rng.integers(1, 51)),
                     None if i % 13 == 0 else bool(rng.integers(0, 2)),
                     "ANR"[int(rng.integers(0, 3))],
                     None if i % 17 == 0 else
                     decimal.Decimal(int(rng.integers(90000, 10**7))) / 100,
                     decimal.Decimal(int(rng.integers(0, 11))) / 100))
    cl.copy_from("li", rows=rows)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("max_rows", [128, 500])
def test_q1_shaped_answers_equal_the_numpy_arm(tmp_path, monkeypatch,
                                               limit_devices, n_dev, max_rows):
    """Aggregates over batches cut in mid-chunk (and, at 500, capped at
    a bucket that is no power of two) equal the numpy arm's, which
    takes the raw path: exact decimals, NULLs, both scan loops."""
    limit_devices(n_dev)
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=192,
                                  stripe_row_limit=384)))
    _lineitem_like(cl)
    cl.execute("DELETE FROM li WHERE k % 9 = 4")
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    oracle = cl.execute(Q1_SHAPED).rows
    assert len(oracle) == 9                    # (true, false, NULL) x A, N, R
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    _small_batches(monkeypatch, cl, max_rows)
    GLOBAL_CACHE.clear()
    seen = []
    real = ex._iter_padded_batches
    monkeypatch.setattr(ex, "_iter_padded_batches", lambda *a: (
        seen.append((hb.n_rows, hb.padded_rows)) or hb for hb in real(*a)))
    try:
        assert cl.execute(Q1_SHAPED).rows == oracle       # streamed
        assert cl.execute(Q1_SHAPED).rows == oracle       # resident
    finally:
        GLOBAL_CACHE.clear()
        cl.close()
    assert len(seen) > 2 * (2000 * 8 // 9) // max_rows - 2
    assert all(n <= p <= max_rows for n, p in seen)
    assert sum(n == p == max_rows for n, p in seen) >= len(seen) - 2


@pytest.mark.parametrize("n_dev", [1, 4])
def test_pad_share_counts_buckets_of_the_cut_not_of_the_overshoot(
        tmp_path, monkeypatch, limit_devices, n_dev):
    """A shard of 2.5 batches and a bit: 2 full batches and the bucket
    of the rest.  (Stopping AFTER the chunk that crosses the limit made
    every batch 1,100 rows in a 2,048-row bucket: 2.36.)"""
    limit_devices(n_dev)
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=100,
                                  stripe_row_limit=400)))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 1)")
    n = 2600
    cl.copy_from("t", columns={"k": np.arange(n), "v": np.arange(n) * 3})
    _small_batches(monkeypatch, cl, 1024)
    GLOBAL_CACHE.clear()
    before = cl.counters.snapshot()
    try:
        r = cl.execute("EXPLAIN ANALYZE SELECT count(*), sum(v) FROM t")
        after = cl.counters.snapshot()
        resident = cl.execute("EXPLAIN ANALYZE SELECT count(*), sum(v) FROM t")
    finally:
        GLOBAL_CACHE.clear()
        cl.close()
    padded = 2 * 1024 + bucket_rows(n - 2 * 1024, MIN_ROWS, 1024)
    assert padded == 3072
    assert after["batch_rows_real"] - before["batch_rows_real"] == n
    assert after["batch_rows_padded"] - before["batch_rows_padded"] == padded
    text = "\n".join(row[0] for row in r.rows)
    assert f"pad_share {padded / n:.3f}" in text, text
    # a resident scan makes no batch: nothing to report
    assert "pad_share" not in "\n".join(row[0] for row in resident.rows)
    assert cl.counters.snapshot()["batch_rows_real"] == after["batch_rows_real"]


# ------------------------------------- validity arrays nobody has to write


def test_columns_without_a_null_share_one_read_only_validity_array(tmp_path):
    """A batch's all-True validity arrays (and a full batch's row mask)
    are ONE read-only array a bucket, shared by every column and batch;
    a column that shows a NULL gets an array of its own, as does the row
    mask of a padded batch -- and every one reads as it always did."""
    from citus_tpu.executor import batches as B
    cl = ct.Cluster(str(tmp_path / "db"), settings=Settings(
        columnar=ColumnarSettings(chunk_group_row_limit=64,
                                  stripe_row_limit=256)))
    try:
        n = 1000
        cl.execute("CREATE TABLE sh (k bigint NOT NULL, v bigint, w bigint)")
        cl.execute("SELECT create_distributed_table('sh', 'k', 1)")
        cl.copy_from("sh", rows=[(i, None if i % 9 == 0 else i * 3, i * 5)
                                 for i in range(n)])
        bound = bind_select(cl.catalog,
                            parse_sql("SELECT k, v, w FROM sh")[0])
        plan = plan_select(cl.catalog, bound)
        at = {c: i for i, c in enumerate(plan.scan_columns)}
        batches = list(load_padded_batches(
            cl.catalog, plan, 0, min_batch_rows=MIN_ROWS, max_batch_rows=256))
        assert [b.n_rows for b in batches] == [256, 256, 256, 232]
        shared = B._all_true(256)
        assert shared.all() and not shared.flags.writeable
        lo = 0
        for b in batches:
            assert b.padded_rows == 256
            for c in ("k", "w"):                    # never a NULL
                assert b.valids[at[c]] is shared
            own = b.valids[at["v"]]
            assert own is not shared and own.flags.writeable
            want = np.ones(256, bool)
            want[:b.n_rows] = np.arange(lo, lo + b.n_rows) % 9 != 0
            assert (own == want).all()
            if b.n_rows == b.padded_rows:
                assert b.row_mask is shared
            else:
                assert b.row_mask is not shared
                assert b.row_mask[:b.n_rows].all() \
                    and not b.row_mask[b.n_rows:].any()
            lo += b.n_rows
        # what is shared stays what it was after the scan that read it
        GLOBAL_CACHE.clear()
        assert cl.execute("SELECT count(*), count(v), sum(w) FROM sh").rows \
            == [(n, n - len(range(0, n, 9)), 5 * n * (n - 1) // 2)]
        assert B._all_true(256) is shared and shared.all()
        with pytest.raises(ValueError):
            shared[0] = False
    finally:
        GLOBAL_CACHE.clear()
        cl.close()
