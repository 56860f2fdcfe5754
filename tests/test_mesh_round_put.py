"""A mesh round is never assembled on the host (``scan_loop.py``
``MeshPlacement.put``).

Member ``i``'s host arrays go to the device that owns row ``i`` of the
round's sharding as they stand, and the ``[n_dev, bucket]`` arrays are
made of the per-device pieces.  Held here to what the parent built --
``device_put(np.stack(members), sharding)`` over re-padded members and
fillers -- for every kind of round: values, dtype, shape, sharding,
committedness, which device holds which member; and to what the host
may still copy: a member cut at a smaller bucket, a filler once a scan
and bucket, nothing else.  4 and 8 of the harness's virtual CPU devices;
never a time.
"""

import re
import tracemalloc
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import citus_tpu as ct
from citus_tpu.executor import scan_loop as L
from citus_tpu.executor.batches import ShardBatch, empty_batch
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.executor.pipeline import PipelineStats
from citus_tpu.observability import trace as T
from citus_tpu.parallel.mesh import SHARD_AXIS

DTYPES = {"k": np.dtype(np.int64), "c": np.dtype(np.int32),
          "f": np.dtype(np.float64)}
BUCKET = 1 << 12


def _plan(narrow=()):
    schema = SimpleNamespace(scan_dtype=lambda c, device=False: DTYPES[c])
    return SimpleNamespace(
        bound=SimpleNamespace(table=SimpleNamespace(name="t", schema=schema)),
        scan_columns=list(DTYPES), narrow_lanes=tuple(narrow))


def _batch(seed: int, bucket: int = BUCKET, si: int = 0) -> ShardBatch:
    """What the decode thread hands over: ``n_rows`` real rows, zeros
    behind them, a NULL here and there."""
    rng = np.random.default_rng(seed)
    n_rows = int(rng.integers(bucket // 2, bucket))
    cols, valids = [], []
    for dt in DTYPES.values():
        c = rng.integers(-1000, 1000, bucket).astype(dt)
        c[n_rows:] = 0
        cols.append(c)
        valids.append(rng.random(bucket) < 0.9)
    mask = np.arange(bucket) < n_rows
    return ShardBatch(tuple(cols), tuple(valids), mask, n_rows, bucket, si)


def _placement(n_dev: int, kind: str = "mesh"):
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (SHARD_AXIS,))
    record = PipelineStats()
    if kind == "affine":
        return L.AffineMeshPlacement(mesh, 2 * n_dev, record)
    return L.MeshPlacement(mesh, record)


def _round(kind: str, n_dev: int) -> list:
    if kind == "full":
        return [_batch(d, si=d) for d in range(n_dev)]
    if kind == "dry_device":        # the affine stream's None
        return [None if d == 1 else _batch(d, si=d) for d in range(n_dev)]
    if kind == "last_round":        # the plain stream's tail: fewer members
        return [_batch(d, si=d) for d in range(n_dev - 2)]
    if kind == "short_bucket":      # a device's short last batch
        return [_batch(d, BUCKET // 4 if d == 2 else BUCKET, si=d)
                for d in range(n_dev)]
    if kind == "replicated":        # the one batch for every device
        return [_batch(7)] * n_dev
    if kind == "short_and_dry":
        return [_batch(9, BUCKET // 2), _batch(1), None] \
            + [_batch(d, BUCKET // 8, si=d) for d in range(3, n_dev)]
    raise AssertionError(kind)


KINDS = ["full", "dry_device", "last_round", "short_bucket", "replicated",
         "short_and_dry"]


def _parent_round(placement, plan, members: list):
    """The parent's ``put``, body for body: re-pad every member to the
    round's bucket, fill up with an empty batch, stack on the host and
    let ``device_put`` slice the stack apart again.  -> (the round on
    the devices, its host members after the re-pad and the fill)."""
    bucket = max(b.padded_rows for b in members if b is not None)
    filler = empty_batch(plan.bound.table, plan, bucket, -1)
    buf = [filler if b is None else L._repad_batch(b, bucket)
           for b in members]
    buf += [filler] * (placement.round_size - len(buf))
    n_cols = range(len(plan.scan_columns))
    put = lambda arrays: jax.device_put(np.stack(arrays), placement.sharding)
    return ((tuple(put([b.cols[i] for b in buf]) for i in n_cols),
             tuple(put([b.valids[i] for b in buf]) for i in n_cols),
             put([b.row_mask for b in buf])), buf)


def _flat(inputs) -> list:
    dcols, dvalids, dmask = inputs
    return [*dcols, *dvalids, dmask]


def _bytes_the_host_must_copy(members: list, n_dev: int) -> int:
    """Each short member grown to the round's bucket, and a filler where
    a device has no member."""
    real = [b for b in members if b is not None]
    bucket = max(b.padded_rows for b in real)
    row = sum(dt.itemsize + 1 for dt in DTYPES.values()) + 1
    short = sum(b.padded_rows < bucket for b in real)
    return (short + (len(real) < n_dev)) * bucket * row


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("placement_kind", ["mesh", "affine"])
@pytest.mark.parametrize("n_dev", [4, 8])
def test_the_round_is_what_the_host_stack_was(n_dev, placement_kind, kind):
    placement, plan = _placement(n_dev, placement_kind), _plan()
    members = _round(kind, n_dev)
    want, buf = _parent_round(placement, plan, members)
    got, nbytes = placement.put(plan, members)
    assert nbytes == sum(a.nbytes for a in _flat(want))
    assert placement.held_bytes(got) == placement.held_bytes(want)
    assert [type(x) for x in got] == [tuple, tuple, type(want[2])]
    devices = list(placement.mesh.devices.flat)
    for k, (g, w) in enumerate(zip(_flat(got), _flat(want), strict=True)):
        assert g.shape == w.shape == (n_dev, BUCKET) and g.dtype == w.dtype
        assert g.sharding == w.sharding == placement.sharding
        assert g.committed and w.committed
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        shards = sorted(g.addressable_shards, key=lambda s: s.index[0].start)
        assert len(shards) == n_dev
        for d, s in enumerate(shards):
            # shard d: on the device of row d, member d's array
            assert s.device == devices[d] and s.index[0] == slice(d, d + 1)
            host = [*buf[d].cols, *buf[d].valids, buf[d].row_mask][k]
            np.testing.assert_array_equal(np.asarray(s.data)[0], host)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n_dev", [4, 8])
def test_the_host_copies_only_short_members_and_a_filler(n_dev, kind):
    """``copied`` on the ``stack`` span, ``mesh_round_bytes_copied`` in
    the execution's record and among the process counters: 0 for a
    full round of one bucket, else exactly the re-padded members' and
    the filler's bytes -- the filler's once a scan and bucket."""
    placement, plan = _placement(n_dev), _plan()
    members = _round(kind, n_dev)
    want = _bytes_the_host_must_copy(members, n_dev)
    assert (want == 0) == (kind in ("full", "replicated"))
    tr = T.Trace()
    c0 = GLOBAL_COUNTERS.snapshot().get("mesh_round_bytes_copied", 0)
    with T.activate(tr, tr.open_span("test", None)):
        _, nbytes = placement.put(plan, members)
        placement.put(plan, members)
    first, second = tr.find_all("stack")
    assert first.attrs == {"bytes": nbytes, "copied": want}
    # the same round again: the filler is kept, a short member is not
    dry = len([b for b in members if b is not None]) < n_dev
    again = want - dry * empty_batch(plan.bound.table, plan, BUCKET, -1).nbytes
    assert second.attrs == {"bytes": nbytes, "copied": again}
    assert [s.attrs for s in tr.find_all("h2d")] == [{"bytes": nbytes}] * 2
    assert placement.record.figures["mesh_round_bytes_copied"] == want + again
    c1 = GLOBAL_COUNTERS.snapshot()["mesh_round_bytes_copied"]
    assert c1 - c0 == want + again


def test_a_filler_is_made_once_a_scan_and_bucket():
    placement, plan = _placement(4), _plan()
    other = _plan()
    other.scan_columns = ["k"]
    a, made = placement._filler(plan, BUCKET)
    assert made == a.nbytes and placement._filler(plan, BUCKET) == (a, 0)
    b, made = placement._filler(plan, BUCKET * 2)
    assert made == b.nbytes == 2 * a.nbytes and b is not a
    c, made = placement._filler(other, BUCKET)
    assert made == c.nbytes and len(c.cols) == 1
    assert placement._filler(plan, BUCKET)[0] is a


@pytest.mark.parametrize("n_dev", [4, 8])
def test_narrowed_rounds_with_a_dry_device_do_not_trip_the_donation(n_dev):
    """``jit_narrow`` takes the wide arrays DONATED.  The filler lives
    on the host and is put afresh every round, so the second round's is
    not an array the first round's convert gave away -- and nothing the
    convert did reached the host arrays."""
    placement, plan = _placement(n_dev, "affine"), _plan(narrow=(0,))
    placement.prepare(plan)
    for r in range(3):
        members = [None if d == (r + 1) % n_dev else _batch(10 * r + d, si=d)
                   for d in range(n_dev)]
        (wcols, wvalids, wmask), buf = _parent_round(placement, plan, members)
        (dcols, dvalids, dmask), _ = placement.put(plan, members)
        assert dcols[0].dtype == np.int32 and dcols[1].dtype == np.int32
        assert dcols[2].dtype == np.float64
        assert dcols[0].sharding == placement.sharding
        for g, w in zip(dcols + dvalids + (dmask,), wcols + wvalids + (wmask,),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not placement.lanes_belied()
    (filler,) = placement._fillers.values()
    assert not any(c.any() for c in filler.cols) and not filler.row_mask.any()
    assert all(v.all() for v in filler.valids)


def _peak_inside(fn) -> int:
    """The most host memory numpy and Python held above where they stood
    when ``fn`` started (numpy reports its arrays to tracemalloc)."""
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        fn()
        return tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()


@pytest.mark.parametrize("n_dev", [4, 8])
def test_a_full_round_allocates_no_host_array_of_its_size(n_dev):
    placement, plan = _placement(n_dev), _plan()
    bucket = 1 << 16
    members = [_batch(d, bucket, si=d) for d in range(n_dev)]
    member_bytes = members[0].nbytes
    placement.put(plan, members)       # whatever the first call imports
    kept = []
    new = _peak_inside(lambda: kept.append(placement.put(plan, members)))
    assert new < member_bytes, (new, member_bytes)
    # the yardstick sees the parent's stacks: the int64 column's alone
    # is an array of the round's size
    old = _peak_inside(
        lambda: kept.append(_parent_round(placement, plan, members)))
    assert old >= n_dev * bucket * 8 > member_bytes, (old, member_bytes)


def test_np_stack_of_the_members_is_gone_from_put():
    import inspect
    assert "np.stack" not in inspect.getsource(L.MeshPlacement.put)
    assert "np.stack" not in inspect.getsource(L.MeshPlacement._assemble)


# ------------------------------------------------- through the executor


@pytest.fixture()
def mesh_cluster(tmp_path, limit_devices):
    limit_devices(4)
    GLOBAL_CACHE.clear()
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE m (k bigint NOT NULL, v bigint, g int)")
    cl.execute("SELECT create_distributed_table('m', 'k', 10)")
    k = np.arange(6000)
    cl.copy_from("m", columns={"k": k, "v": (k * 7919) % 1013 - 500,
                               "g": k % 5})
    yield cl
    cl.close()
    GLOBAL_CACHE.clear()


QUERIES = {
    # MeshPlacement: 10 batches in rounds of 4, the last of 2 (a filler)
    "scan": "SELECT g, count(*), sum(v) FROM m WHERE v < 300 GROUP BY g "
            "ORDER BY g",
    # AffineMeshPlacement: a hash table a device, dry devices at the tail
    "hash": "SELECT k, sum(v), min(v) FROM m GROUP BY k HAVING sum(v) > 400 "
            "ORDER BY k",
}


@pytest.mark.parametrize("query", list(QUERIES))
def test_a_statement_counts_what_its_rounds_copied(mesh_cluster, query):
    cl, sql = mesh_cluster, QUERIES[query]
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    oracle = cl.execute(sql).rows
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    cl.execute("SET citus.trace_sample_rate = 1.0")
    c0 = cl.counters.snapshot()
    r = cl.execute(sql)
    c1 = cl.counters.snapshot()
    assert r.rows == oracle
    tr = T.last_trace()
    stacks = tr.find_all("stack")
    assert stacks and len(stacks) == len(tr.find_all("h2d"))
    copied = sum(s.attrs["copied"] for s in stacks)
    nbytes = sum(s.attrs["bytes"] for s in stacks)
    pl = r.explain["pipeline"]
    assert pl["mesh_round_bytes_copied"] == copied
    assert c1.get("mesh_round_bytes_copied", 0) \
        - c0.get("mesh_round_bytes_copied", 0) == copied
    assert c1["bytes_scanned"] - c0["bytes_scanned"] == nbytes \
        == pl["h2d_bytes"]
    # a dry device's filler is the only copy: every member here is cut
    # at the round's bucket
    fillers = [s for s in stacks if s.attrs["copied"]]
    assert 0 < copied < nbytes and len(fillers) <= 2
    GLOBAL_CACHE.clear()
    text = "\n".join(row[0] for row in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    m = re.search(r"Pipeline: .*stacked: (\d+) of (\d+) bytes copied on the "
                  r"host", text)
    assert m and (int(m[1]), int(m[2])) == (copied, nbytes), text


def test_a_replayed_round_stacks_nothing(mesh_cluster):
    """The HBM batch cache replays device rounds: no ``put``, no figure,
    nothing on the line."""
    cl, sql = mesh_cluster, QUERIES["scan"]
    cl.execute(sql)
    cl.execute("SET citus.trace_sample_rate = 1.0")
    r = cl.execute(sql)
    assert not T.last_trace().find_all("stack")
    assert "mesh_round_bytes_copied" not in r.explain["pipeline"]
    text = "\n".join(row[0] for row in cl.execute("EXPLAIN ANALYZE " + sql).rows)
    assert "Pipeline:" in text and "stacked:" not in text
