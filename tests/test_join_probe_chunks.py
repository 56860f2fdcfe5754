"""The probe's one path (``ops/join.py`` ``build_join_probe``): the rows
the probe relation's own filter keeps are packed WITH what their lookup
reads -- one sort, the lanes its further operands -- and only they are
looked up, a chunk a trip, however many there are.

A two-table join in small: ``fact`` (one shard: one batch, bucket 8,192,
chunks of 1,024) probes ``dim`` on a key a third of whose values have no
partner and a few are NULL; ``f_keep * 2 < X * 2`` keeps the first X rows
(arithmetic, so that no chunk's statistics prune the batch away).  The
answer is the host arm's, the counts are numpy's.
"""

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import join_device as JD
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.ops import join as J
from citus_tpu.planner.bound import BBinOp, BColumn, BLiteral
from citus_tpu.types import BOOL_T, INT64_T

ROWS, BUCKET, BLOCK = 5000, 8192, 64
SQL = ("select count(*), sum(f_val + d_val), min(d_val) from fact, dim "
       "where f_key = d_key and f_keep * 2 < {keep} * 2")

#: rows ``f_keep < X`` keeps: none, under a block, a block and one row,
#: several chunks of the lookup loop (1,024 rows each), every row
KEPT = {"none": 0, "under_a_block": 40, "a_block_and_one": 113,
        "several_chunks": 3000, "every_row": ROWS}


class Data:
    def __init__(self):
        rng = np.random.default_rng(49)
        self.d_key = np.arange(0, 3 * 2000, 3) + 7          # 7, 10, 13, ...
        self.d_val = rng.integers(1, 10 ** 6, self.d_key.size)
        key = rng.integers(0, 3 * 2000 + 40, ROWS) + 7      # a third match
        self.null = rng.random(ROWS) < 0.03
        self.f_key = [None if n else int(k) for k, n in zip(key, self.null)]
        self.key = key
        self.f_keep = np.arange(ROWS)
        self.f_val = rng.integers(1, 10 ** 6, ROWS)

    def load(self, cl):
        cl.execute("CREATE TABLE dim (d_key bigint NOT NULL, d_val bigint)")
        cl.execute("SELECT create_reference_table('dim')")
        cl.execute("CREATE TABLE fact (f_key bigint, f_keep bigint, "
                   "f_val bigint)")
        cl.execute("SELECT create_distributed_table('fact', 'f_keep', 1)")
        cl.copy_from("dim", columns={"d_key": self.d_key,
                                     "d_val": self.d_val})
        cl.copy_from("fact", columns={"f_key": self.f_key,
                                      "f_keep": self.f_keep,
                                      "f_val": self.f_val})

    def matched(self, keep):
        """Of the first ``keep`` rows, those with a partner in ``dim``."""
        return int((np.isin(self.key, self.d_key) & ~self.null)[:keep].sum())


@pytest.fixture(scope="module")
def data():
    return Data()


@pytest.fixture(scope="module")
def cl(data, tmp_path_factory):
    cluster = ct.Cluster(str(tmp_path_factory.mktemp("chunks") / "db"))
    data.load(cluster)
    return cluster


def host_arm(cl, sql):
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        return cl.execute(sql)
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")


@pytest.mark.parametrize("kind", ["direct", "hash"])
@pytest.mark.parametrize("share", list(KEPT))
def test_the_lookups_follow_the_rows_the_filter_keeps(
        cl, data, monkeypatch, share, kind):
    keep = KEPT[share]
    monkeypatch.setattr(JD._DeviceJoin, "block_rows", BLOCK)
    if kind == "hash":
        # no index fits: the open-addressing table
        monkeypatch.setattr(JD, "DIRECT_MEMORY_SHARE", 1e-9)
    sql = SQL.format(keep=keep)
    c0 = GLOBAL_COUNTERS.snapshot()
    r = cl.execute(sql)
    c1 = GLOBAL_COUNTERS.snapshot()
    j = r.explain["join"]
    assert j["on"] == "device" and j["tables"]["dim"]["table"] == kind
    assert r.rows == host_arm(cl, sql).rows
    matched = data.matched(keep)
    assert r.rows[0][0] == matched
    # a block of 64: the rows that matched take ceil(matched / 64) rounds,
    # and every round looks the kept rows up, no more and no fewer
    # (a key whose pair of slots is taken twice over is packed too, for
    # the later pairs: an open-addressing table may take a round more)
    rounds = 1 + j["overflow_rounds"]
    assert rounds == max(1, -(-matched // BLOCK)) if kind == "direct" \
        else 0 <= rounds - max(1, -(-matched // BLOCK)) <= 1
    assert j["rows_probed"] == BUCKET * rounds
    assert j["rows_looked_up"] == keep * rounds
    assert j["rows_out"] == matched
    # MATCHED counts the first pair of slots: a few keys of an
    # open-addressing table sit in a later pair
    assert j["rows_matched"] == matched if kind == "direct" \
        else 0.95 * matched <= j["rows_matched"] <= matched
    assert c1["join_rows_looked_up"] - c0.get("join_rows_looked_up", 0) \
        == j["rows_looked_up"]


def test_explain_analyze_says_how_many_rows_were_looked_up(cl):
    lines = "\n".join(l for (l,) in cl.execute(
        "EXPLAIN ANALYZE " + SQL.format(keep=3000)).rows)
    assert f"probed {BUCKET}, looked up 3000, matched " in lines


def test_no_filter_on_the_probe_relation_looks_the_bucket_up(cl, data):
    sql = "select count(*) from fact, dim where f_key = d_key"
    r = cl.execute(sql)
    j = r.explain["join"]
    assert j["on"] == "device" and r.rows == host_arm(cl, sql).rows
    assert j["rows_looked_up"] == j["rows_probed"] \
        == BUCKET * (1 + j["overflow_rounds"])
    assert j["rows_matched"] == j["rows_out"] == data.matched(ROWS)


@pytest.mark.parametrize("n,chunk", [
    (4_194_304, 65_536), (65_536, 1024), (8192, 1024), (2048, 1024),
    (1024, 1024), (512, 512), (3 * 4096, 1536), (1000, 1000)])
def test_a_chunk_divides_its_bucket(n, chunk):
    assert J.lookup_chunk(n) == chunk and n % chunk == 0


# ------------------------------------------------- the kernel's own parts


def direct_node(filtered=True):
    keep = BBinOp("<", BColumn("f.keep", INT64_T), BLiteral(0, INT64_T),
                  BOOL_T)
    return J.JoinNode(
        alias="f", names=("f.key", "f.keep"),
        filter=keep if filtered else None,
        children=(J.ChildProbe("d", (BColumn("f.key", INT64_T),),
                               (("d.val", "int64"),), "direct"),),
        out=("f.key", "d.val"))


def direct_table(lo, keys, vals, slots):
    """``index[key - lo]`` = 1 + the key's place in the payload lanes."""
    import jax.numpy as jnp
    index = np.zeros(slots, np.int32)
    index[np.asarray(keys) - lo] = np.arange(len(keys)) + 1
    lanes = (jnp.asarray(vals, np.int64), jnp.ones(len(keys), np.int8))
    return (jnp.asarray(index), lanes, jnp.asarray(lo, np.int64)), \
        jnp.zeros(J.COUNTS, np.int32)


def test_the_packed_lanes_are_the_kept_rows_keys():
    """The one sort carries each kept row's lane to its packed place:
    lane[j] is what a gather ``key[positions[j]]`` would have fetched,
    0 for a NULL key and for one outside the table's span."""
    import jax.numpy as jnp
    n, lo, slots = 2048, 100, 1024
    rng = np.random.default_rng(3)
    key = rng.integers(lo - 50, lo + slots + 50, n)
    valid = rng.random(n) > 0.1
    keep = np.where(rng.random(n) < 0.4, -1, 1)
    row_mask = np.arange(n) < n - 100
    table = direct_table(lo, [lo, lo + 5], [11, 22], slots)
    pre = J._Prefix(direct_node(), (), jnp)
    env = pre.env((jnp.asarray(key), jnp.asarray(keep)),
                  (jnp.asarray(valid), jnp.ones(n, bool)))
    own = pre.own_filter(env, jnp.asarray(row_mask))
    (lane,), = pre.probe_lanes(env, own, (table,))
    order, (packed,) = J._pack_with(jnp, own, [lane])
    own, order, packed = map(np.asarray, (own, order, packed))
    positions = np.flatnonzero(row_mask & (keep < 0))
    K = positions.size
    assert own.sum() == K and (order[:K] == positions).all()
    assert (order[K:] >= n).all()
    inside = valid & (key >= lo) & (key < lo + slots)
    want = np.where(inside, key - lo + 1, 0)
    assert (packed[:K] == want[positions]).all()
    assert (want[positions] == 0).any() and (want[positions] > 0).any()
    # a row the filter drops carries nothing
    assert (np.asarray(lane)[~own] == 0).all()


@pytest.mark.parametrize("filtered", [True, False])
def test_the_probe_kernel_against_numpy(filtered):
    """A batch of 4,096 rows, chunks of 1,024, a block of 16: every
    round's block holds the next 16 rows that matched, in batch order,
    with the payload of the build row their key addresses."""
    import jax
    import jax.numpy as jnp
    n, lo, slots, C = 4096, 1000, 2048, 16
    rng = np.random.default_rng(5)
    build = lo + rng.choice(slots, 700, replace=False)
    vals = rng.integers(1, 10 ** 9, build.size)
    table = direct_table(lo, build, vals, slots)
    key = rng.integers(lo - 100, lo + slots + 100, n)
    valid = rng.random(n) > 0.05
    keep = np.where(rng.random(n) < 0.3, -1, 1)
    row_mask = np.arange(n) < n - 300
    probe = jax.jit(J.build_join_probe(direct_node(filtered), (), jnp, C))
    args = ((table,), (jnp.asarray(key), jnp.asarray(keep)),
            (jnp.asarray(valid), jnp.ones(n, bool)), jnp.asarray(row_mask))
    kept = row_mask & (keep < 0) if filtered else row_mask
    payload = dict(zip(build.tolist(), vals.tolist()))
    hit = kept & valid & np.isin(key, build)
    want = [(int(key[i]), payload[int(key[i])]) for i in np.flatnonzero(hit)]
    got = []
    for rnd in range(-(-len(want) // C)):
        (k, v), (km, vm), live, counts = jax.device_get(
            probe(*args, np.int32(rnd)))
        assert counts[J.PACKED] == len(want)
        assert counts[J.LOOKED] == (kept.sum() if filtered else n)
        assert counts[J.MATCHED] == (len(want) if rnd == 0 else 0)
        assert counts[J.OUT] == live.sum()
        assert km[live].all() and vm[live].all()
        got += list(zip(k[live].tolist(), v[live].tolist()))
    assert got == want and len(want) > 3 * C
