"""The decoded-footer cache behind ``read_stripe_footer``
(citus_tpu/storage/format.py): an entry is served only while one
``os.stat`` returns the identity of the file it was parsed from.  A
stripe file never changes once visible, but its NAME comes back with
other bytes — VACUUM, TRUNCATE + COPY, DROP + CREATE, a shard move, a
rolled-back transaction's staged stripe — and each of those is pinned
here, with the sharing between threads, the bound, and the chunk
selection over cached footers.
"""

import glob
import os
import shutil
import threading

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.schema import Schema
from citus_tpu.storage import Interval, ShardReader, ShardWriter
from citus_tpu.storage import format as F
from citus_tpu.storage.format import read_stripe_footer

SCHEMA = Schema.of(("a", "bigint"), ("b", "double"), ("c", "bigint"))
FOOTER_COUNTERS = ("footer_cache_hits", "footer_parses",
                   "footer_cache_evictions")


class Counted:
    """Deltas of the footer counters since it was made."""

    def __init__(self):
        self.base = GLOBAL_COUNTERS.snapshot()

    def __call__(self) -> tuple:
        now = GLOBAL_COUNTERS.snapshot()
        return tuple(now[k] - self.base[k] for k in FOOTER_COUNTERS)


def fresh(path: str) -> F.StripeFooter:
    """The footer as the file holds it now, parsed past the cache."""
    return F._parse_stripe_footer(path).footer


def write_shard(directory, a, codec="none", chunk=64, stripe=256, valid=None):
    w = ShardWriter(str(directory), SCHEMA, chunk_row_limit=chunk,
                    stripe_row_limit=stripe, codec=codec)
    n = len(a)
    w.append_batch({"a": np.asarray(a, np.int64), "b": np.arange(n) / 4.0,
                    "c": np.arange(n, dtype=np.int64)}, valid)
    w.flush()
    return ShardReader(str(directory), SCHEMA)


def stripe_paths(reader):
    return [os.path.join(reader.directory, f) for f in reader.stripe_files]


def placement_stripes(cl, table):
    return sorted(glob.glob(os.path.join(
        cl.catalog.data_dir, "**", table, "**", "*.cts"), recursive=True))


def test_second_read_is_a_hit_and_parses_nothing(tmp_path):
    r = write_shard(tmp_path / "s", np.arange(600))
    paths = stripe_paths(r)
    assert len(paths) == 3
    seen = Counted()
    first = [read_stripe_footer(p) for p in paths]
    assert seen() == (0, 3, 0)
    again = [read_stripe_footer(p) for p in paths]
    assert seen() == (3, 3, 0)
    # the very objects: nothing was decoded twice, the derived offsets
    # neither
    assert all(x is y for x, y in zip(first, again))
    assert first[0].chunk_bounds is again[0].chunk_bounds
    assert first[0].chunk_bounds.tolist() == [0, 64, 128, 192, 256]
    assert not first[0].chunk_bounds.flags.writeable
    assert first[0] == fresh(paths[0])
    # a scan, a lookup and chunk_counts go through the same door
    got = np.concatenate([b.values["a"] for b in r.scan(["a"])])
    np.testing.assert_array_equal(got, np.arange(600))
    assert r.chunk_counts([Interval("a", lo=70, hi=70)]) == (1, 10)
    assert seen() == (9, 3, 0)


@pytest.mark.parametrize("through", ["path", "dir_fd"])
def test_same_name_size_and_mtime_with_other_bytes_is_parsed_again(
        tmp_path, through):
    """What a shard move does to a name (operations/shard_transfer.py
    ``_copy_atomic``: ``shutil.copy2`` + ``os.replace``) at its most
    adverse: the file that arrives has the size AND the mtime of the one
    it replaces.  The inode and the ctime are its own — whether the stat
    walks the path or names the file from its directory's descriptor, as
    a scan does."""
    (old,) = stripe_paths(write_shard(tmp_path / "old",
                                      np.arange(200) + 100))
    (new,) = stripe_paths(write_shard(tmp_path / "new",
                                      np.arange(200) + 500))
    assert os.path.getsize(old) == os.path.getsize(new)
    st = os.stat(old)
    os.utime(new, ns=(st.st_atime_ns, st.st_mtime_ns))
    dir_fd = None
    if through == "dir_fd":
        dir_fd = os.open(os.path.dirname(old), os.O_RDONLY | os.O_DIRECTORY)
    try:
        def read():
            return read_stripe_footer(old, dir_fd=dir_fd)

        assert read().columns["a"][0].minimum == 100
        seen = Counted()
        assert read() is read_stripe_footer(old)   # one entry, either way
        assert seen() == (2, 0, 0)
        shutil.copy2(new, old + ".copy")
        os.replace(old + ".copy", old)
        now = os.stat(old)
        assert (now.st_size, now.st_mtime_ns) == (st.st_size, st.st_mtime_ns)
        footer = read()
        assert footer.columns["a"][0].minimum == 500
        assert footer == fresh(new)
        assert seen() == (2, 1, 0)
        assert read() is footer
        assert seen() == (3, 1, 0)
        os.remove(old)
        with pytest.raises(FileNotFoundError):
            read()
    finally:
        if dir_fd is not None:
            os.close(dir_fd)


def test_missing_file_raises_as_before_and_drops_its_entry(tmp_path):
    (path,) = stripe_paths(write_shard(tmp_path / "s", np.arange(100)))
    read_stripe_footer(path)
    os.remove(path)
    with pytest.raises(FileNotFoundError):
        read_stripe_footer(path)
    assert path not in F._FOOTERS._entries
    with open(path, "wb") as fh:
        fh.write(b"short")
    with pytest.raises(ct.errors.StorageError, match="too small"):
        read_stripe_footer(path)


@pytest.fixture()
def db(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"), n_nodes=2)
    cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 2)")
    cl.copy_from("t", columns={"k": np.arange(4000, dtype=np.int64),
                               "v": np.arange(4000, dtype=np.int64) % 10})
    yield cl
    cl.close()


def table_schema(cl):
    return cl.catalog.table("t").schema


def assert_footers_current(cl, table="t"):
    paths = placement_stripes(cl, table)
    assert paths
    for p in paths:
        assert read_stripe_footer(p) == fresh(p), p


def test_vacuum_rewrites_under_the_same_names(db):
    cl = db
    cl.execute("DELETE FROM t WHERE v < 3")
    cl.execute("VACUUM t")
    cl.execute("SELECT citus_cleanup_orphaned_resources()")
    assert cl.execute("SELECT count(*), sum(k) FROM t").rows == [
        (2800, sum(k for k in range(4000) if k % 10 >= 3))]
    names = [os.path.basename(p) for p in placement_stripes(cl, "t")]
    assert_footers_current(cl)         # every rewritten footer is cached
    cl.execute("DELETE FROM t WHERE v < 6")
    cl.execute("VACUUM t")
    cl.execute("SELECT citus_cleanup_orphaned_resources()")
    # the second rewrite reused the first one's names
    assert [os.path.basename(p) for p in placement_stripes(cl, "t")] == names
    left = [k for k in range(4000) if k % 10 >= 6]
    # a scan, a routed lookup and EXPLAIN's chunk_counts, all from the
    # rewritten stripes
    assert cl.execute("SELECT count(*), sum(k) FROM t").rows == [
        (len(left), sum(left))]
    assert cl.execute("SELECT k, v FROM t WHERE k = 3999").rows == [(3999, 9)]
    assert cl.execute("SELECT count(*) FROM t WHERE k = 3995").rows == [(0,)]
    t = cl.catalog.table("t")
    rows = chunks = 0
    for shard in t.shards:
        r = ShardReader(cl.catalog.shard_dir(
            "t", shard.shard_id, shard.placements[0]), t.schema)
        rows += sum(read_stripe_footer(p).row_count for p in stripe_paths(r))
        chunks += r.chunk_counts()[1]
        assert r.chunk_counts([Interval("k", lo=10**9)])[0] == 0
    assert rows == len(left) and chunks > 0
    assert_footers_current(cl)


def test_truncate_then_copy_serves_the_new_footers(db):
    cl = db
    assert cl.execute("SELECT max(k) FROM t").rows == [(3999,)]
    cl.execute("TRUNCATE t")
    assert cl.execute("SELECT count(*) FROM t").rows == [(0,)]
    cl.copy_from("t", columns={"k": np.arange(4000, dtype=np.int64) + 10000,
                               "v": np.arange(4000, dtype=np.int64) % 7})
    assert cl.execute("SELECT min(k), max(k), sum(v) FROM t").rows == [
        (10000, 13999, int((np.arange(4000) % 7).sum()))]
    assert cl.execute("SELECT count(*) FROM t WHERE k = 3999").rows == [(0,)]
    assert cl.execute("SELECT v FROM t WHERE k = 13999").rows == [(3999 % 7,)]
    assert_footers_current(cl)


def test_drop_then_create_of_the_same_name(db):
    cl = db
    assert cl.execute("SELECT sum(v) FROM t").rows == [(18000,)]
    cl.execute("DROP TABLE t")
    cl.execute("SELECT citus_cleanup_orphaned_resources()")
    cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 2)")
    cl.copy_from("t", columns={"k": np.arange(500, dtype=np.int64),
                               "v": np.full(500, 2, np.int64)})
    assert cl.execute("SELECT count(*), sum(v), max(k) FROM t").rows == [
        (500, 1000, 499)]
    assert cl.execute("SELECT count(*) FROM t WHERE k = 3000").rows == [(0,)]
    assert_footers_current(cl)


def test_a_shard_moved_away_changed_and_moved_back(db):
    """The placement directory of node ``src`` is read (and cached),
    left, and later filled again by a move back — after a VACUUM at the
    other node gave the same stripe names other bytes."""
    cl = db
    shard = cl.catalog.table("t").shards[0]
    src = shard.placements[0]
    dst = 1 - src
    cl.execute("DELETE FROM t WHERE v = 0")
    cl.execute("VACUUM t")                 # plain names from here on
    home = cl.catalog.shard_dir("t", shard.shard_id, src)
    before = {p: read_stripe_footer(p)
              for p in stripe_paths(ShardReader(home, table_schema(cl)))}
    assert before
    cl.execute(f"SELECT citus_move_shard_placement({shard.shard_id}, "
               f"{src}, {dst})")
    cl.execute("DELETE FROM t WHERE v = 1")
    cl.execute("VACUUM t")
    cl.execute("SELECT citus_cleanup_orphaned_resources()")
    cl.execute(f"SELECT citus_move_shard_placement({shard.shard_id}, "
               f"{dst}, {src})")
    left = [k for k in range(4000) if k % 10 >= 2]
    assert cl.execute("SELECT count(*), sum(k) FROM t").rows == [
        (len(left), sum(left))]
    after = stripe_paths(ShardReader(home, table_schema(cl)))
    assert set(after) & set(before)        # names that came back
    for p in after:
        footer = read_stripe_footer(p)
        assert footer == fresh(p)
        if p in before:
            assert footer.row_count < before[p].row_count


def test_staged_stripe_is_read_by_its_statement_and_gone_after_rollback(db):
    cl = db
    committed = set(placement_stripes(cl, "t"))
    cl.execute("BEGIN")
    cl.execute("INSERT INTO t VALUES (9000, 4), (9001, 5)")
    staged = set(placement_stripes(cl, "t")) - committed
    assert staged
    # read-your-writes: the block's own statements pass the staged
    # stripes' footers; another session does not see them
    assert cl.execute("SELECT count(*), max(k) FROM t").rows == [(4002, 9001)]
    assert cl.execute("SELECT v FROM t WHERE k = 9001").rows == [(5,)]
    assert cl.session().execute("SELECT count(*) FROM t").rows == [(4000,)]
    for p in staged:
        assert read_stripe_footer(p) == fresh(p)
    cl.execute("ROLLBACK")
    assert cl.execute("SELECT count(*), max(k) FROM t").rows == [(4000, 3999)]
    for p in staged:
        with pytest.raises(FileNotFoundError):
            read_stripe_footer(p)


def test_column_added_after_the_stripes_selects_as_before(db):
    cl = db
    assert cl.execute("SELECT count(*) FROM t WHERE v = 3").rows == [(400,)]
    cl.execute("ALTER TABLE t ADD COLUMN w bigint")
    cl.execute("INSERT INTO t VALUES (5000, 1, 77)")
    # old stripes lack w: every row is NULL there and no range admits it
    assert cl.execute("SELECT count(*), min(k) FROM t WHERE w >= 0").rows == [
        (1, 5000)]
    assert cl.execute("SELECT count(*) FROM t WHERE w IS NULL").rows == [
        (4000,)]
    assert cl.execute("SELECT k, w FROM t WHERE k = 5000").rows == [(5000, 77)]
    t = cl.catalog.table("t")
    selected = total = 0
    for shard in t.shards:
        r = ShardReader(cl.catalog.shard_dir(
            "t", shard.shard_id, shard.placements[0]), t.schema)
        s, n = r.chunk_counts([Interval("w", lo=0)])
        selected, total = selected + s, total + n
    assert selected == 1 and total > 2
    assert_footers_current(cl)


def test_eight_threads_share_the_footers(tmp_path):
    r = write_shard(tmp_path / "s", np.arange(2048), chunk=32, stripe=128)
    paths = stripe_paths(r)
    assert len(paths) == 16
    seen = Counted()
    rounds, got, errors = 5, [None] * 8, []
    gate = threading.Barrier(8)

    def reader(i):
        try:
            gate.wait()
            for _ in range(rounds):
                got[i] = [read_stripe_footer(p) for p in paths]
        except Exception as e:      # pragma: no cover - the assert below
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    want = [fresh(p) for p in paths]
    assert all(g == want for g in got)
    hits, parses, evicted = seen()
    # every read is one or the other; a file is parsed at least once and
    # at most once a thread (the parse runs outside the lock)
    assert hits + parses == 8 * rounds * len(paths) and evicted == 0
    assert len(paths) <= parses <= 8 * len(paths)
    # and from then on one object a file, whoever asks
    assert all(read_stripe_footer(p) is read_stripe_footer(p) for p in paths)


def test_the_bound_evicts_least_recently_used_and_counts(tmp_path, monkeypatch):
    r = write_shard(tmp_path / "s", np.arange(1280), chunk=64, stripe=256)
    paths = stripe_paths(r)
    assert len(paths) == 5
    sizes = [F._parse_stripe_footer(p).json_bytes for p in paths]
    cache = F._FooterCache()
    monkeypatch.setattr(F, "_FOOTERS", cache)
    # room for any three of the five, for no four
    assert 3 * max(sizes) < 4 * min(sizes)
    monkeypatch.setattr(F, "FOOTER_CACHE_JSON_BYTES", 3 * max(sizes))
    seen = Counted()
    for p in paths[:3]:
        read_stripe_footer(p)
    read_stripe_footer(paths[0])                  # 0 is now the newest
    assert seen() == (1, 3, 0)
    read_stripe_footer(paths[3])                  # pushes 1 out
    assert seen() == (1, 4, 1)
    assert list(cache._entries) == [paths[2], paths[0], paths[3]]
    read_stripe_footer(paths[0])                  # still a hit
    read_stripe_footer(paths[1])                  # parsed again, 2 goes
    assert seen() == (2, 5, 2)
    assert list(cache._entries) == [paths[3], paths[0], paths[1]]
    assert cache._bytes == sizes[3] + sizes[0] + sizes[1]
    # an entry larger than the bound is still served once it is read
    monkeypatch.setattr(F, "FOOTER_CACHE_JSON_BYTES", 1)
    big = read_stripe_footer(paths[4])
    assert list(cache._entries) == [paths[4]] and big == fresh(paths[4])
    assert seen() == (2, 6, 5)


def _selection_shard(directory):
    """Six chunks of 64 rows over ``a``: 0..63, 64..127, all NULL,
    128..191 with NULLs among them, a chunk written without statistics
    (None: cannot prune), 320..383."""
    n = 384
    a = np.arange(n, dtype=np.int64)
    a[256:320] = 10**6                    # the chunk whose stats go away
    valid_a = np.ones(n, bool)
    valid_a[128:192] = False              # chunk 2: all NULL
    valid_a[192:256:3] = False            # chunk 3: some NULLs
    a[192:256] = np.arange(128, 192)
    valid = {"a": valid_a, "b": np.ones(n, bool), "c": np.ones(n, bool)}
    r = write_shard(directory, a, chunk=64, stripe=n, valid=valid)
    return r


SELECTIONS = [
    ("none", []),
    ("point", [("a", 70, 70, True, True)]),
    ("point_in_gap", [("a", 250, 250, True, True)]),
    ("closed", [("a", 63, 128, True, True)]),
    ("open_both", [("a", 63, 128, False, False)]),
    ("lo_only", [("a", 191, None, True, True)]),
    ("lo_only_exclusive", [("a", 191, None, False, True)]),
    ("hi_only", [("a", None, 64, True, True)]),
    ("hi_only_exclusive", [("a", None, 64, True, False)]),
    ("empty_range", [("a", 10**7, None, True, True)]),
    ("float_bounds", [("a", 63.5, 127.5, True, True)]),
    ("other_column_float", [("b", 16.0, 16.0, True, True)]),
    ("two_columns", [("a", 0, 200, True, True), ("c", 100, 330, True, False)]),
    ("unknown_column", [("zz", 0, None, True, True)]),
]


@pytest.mark.parametrize("name,spec", SELECTIONS, ids=[s[0] for s in SELECTIONS])
def test_selection_over_cached_footers_is_the_uncached_decision(
        tmp_path, name, spec):
    r = _selection_shard(tmp_path / "s")
    (path,) = stripe_paths(r)
    # the stripe is written with statistics everywhere; take chunk 4's
    # away in a copy of the footer's JSON, as a no-stats column has them
    plain = fresh(path)
    doc = plain.to_json()
    doc["columns"]["a"][4]["mn"] = doc["columns"]["a"][4]["mx"] = None
    holes = F.StripeFooter.from_json(doc)
    constraints = [Interval(*c) for c in spec]

    def by_admits(footer):
        """The decision, chunk by chunk, from Interval.admits alone."""
        keep = np.ones(footer.chunk_count, bool)
        for c in constraints:
            stats = footer.columns.get(c.column)
            if stats is None:
                return np.zeros(footer.chunk_count, bool)
            for ci, s in enumerate(stats):
                if s.row_count == s.null_count or not c.admits(s.minimum,
                                                               s.maximum):
                    keep[ci] = False
        return keep

    read_stripe_footer(path)
    seen = Counted()
    cached = read_stripe_footer(path)
    assert seen() == (1, 0, 0) and cached == plain
    for footer in (cached, plain, holes):
        got = r._selected_chunks(footer, constraints)
        assert got.dtype == bool
        np.testing.assert_array_equal(got, by_admits(footer))
    sel = r._selected_chunks(cached, constraints)
    if name == "none":
        assert sel.all()
    else:
        assert not sel[2]                 # all NULL: refuted by any range
    if name in ("point", "closed", "float_bounds"):
        assert sel.tolist() == [name == "closed", True] + [False] * 4
    if name == "empty_range":
        assert not sel.any()
        assert r._selected_chunks(holes, constraints).tolist() == \
            [False] * 4 + [True, False]
    assert r.chunk_counts(constraints) == (int(sel.sum()), 6)
