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
    # a block of 64: the rows that matched take ceil(matched / 64) rounds
    # (a key whose pair of slots is taken twice over is packed too, for
    # the later pairs: an open-addressing table may take a round more),
    # and the kept rows are looked up ONCE, no more and no fewer,
    # whatever the rounds: a further round cuts its block from what
    # round 0 left on the device
    rounds = 1 + j["overflow_rounds"]
    assert rounds == max(1, -(-matched // BLOCK)) if kind == "direct" \
        else 0 <= rounds - max(1, -(-matched // BLOCK)) <= 1
    assert j["rows_probed"] == BUCKET
    assert j["rows_looked_up"] == keep
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
    assert j["overflow_rounds"] >= 1
    assert j["rows_looked_up"] == j["rows_probed"] == BUCKET
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
    with the payload of the build row their key addresses -- round 0 by
    the probe kernel, every further one by the round kernel on the
    carry round 0 returned, which looks nothing up."""
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
    node = direct_node(filtered)
    probe = jax.jit(J.build_join_probe(node, (), jnp, C))
    further = jax.jit(J.build_join_probe_round(node, (), jnp, C))
    args = ((table,), (jnp.asarray(key), jnp.asarray(keep)),
            (jnp.asarray(valid), jnp.ones(n, bool)), jnp.asarray(row_mask))
    kept = row_mask & (keep < 0) if filtered else row_mask
    payload = dict(zip(build.tolist(), vals.tolist()))
    hit = kept & valid & np.isin(key, build)
    want = [(int(key[i]), payload[int(key[i])]) for i in np.flatnonzero(hit)]
    got = []
    *block, carry = probe(*args)
    for rnd in range(-(-len(want) // C)):
        if rnd:
            block = further(*args, carry, np.int32(rnd))
        (k, v), (km, vm), live, counts = jax.device_get(block)
        assert counts[J.PACKED] == len(want)
        looked = kept.sum() if filtered else n
        assert counts[J.LOOKED] == (looked if rnd == 0 else 0)
        assert counts[J.MATCHED] == (len(want) if rnd == 0 else 0)
        assert counts[J.OUT] == counts[J.SEEN] == live.sum()
        assert km[live].all() and vm[live].all()
        got += list(zip(k[live].tolist(), v[live].tolist()))
    assert got == want and len(want) > 3 * C


# ----------------------------- a further round against the probe as it stood


def probe_node(kind, filtered):
    """``direct_node`` with a child of either kind and a conjunct over
    both relations."""
    from dataclasses import replace
    node = direct_node(filtered)
    ch, = node.children
    both = BBinOp("<", BColumn("f.key", INT64_T), BColumn("d.val", INT64_T),
                  BOOL_T)
    return replace(node, children=(replace(ch, kind=kind),),
                   post_filter=both)


def hash_table(keys, vals, slots):
    """The build's own table of ``keys`` -> ``vals``."""
    import jax
    import jax.numpy as jnp
    node = J.JoinNode(alias="d", names=("d.key", "d.val"), filter=None,
                      children=(), key=(BColumn("d.key", INT64_T),),
                      payload=(("d.val", "int64"),))
    build = jax.jit(J.build_join_build(node, (), jnp))
    ones = jnp.ones(len(keys), bool)
    return build(J.empty_join_table(node, slots, jnp), (),
                 (jnp.asarray(keys), jnp.asarray(vals)), (ones, ones), ones)


def rounds_as_they_stood(kind, filtered, table, batch, C):
    """Every round's ``(cols, valids, live)`` by the probe of before the
    carry, in numpy: EVERY round packs the kept rows, looks all of them
    up and packs the rows that came through again, and only then cuts
    the round's block.  The table is read by its own primitive
    (``_probe_slots``, numpy's arm)."""
    key, valid, keep, row_mask = batch
    state, counts = table
    n = row_mask.size
    ones = lambda a: np.ones(a.shape, bool)
    own = row_mask & (keep < 0) if filtered else row_mask
    pos = np.arange(n, dtype=np.int32)
    marked = np.where(own, pos, pos + n)
    if kind == "direct":
        index, lanes, lo = state
        S = index.size
        inside = own & valid & (key >= lo) & (key < lo + S)
        lane = np.where(inside, key - lo + 1, 0).astype(np.int32)
    else:
        lanes, S = state[1], state[2].size
        lane = np.where(valid, key, 0)
        lane_ok = own & valid
    # the one sort: positions, and the lanes its further operands
    perm = np.argsort(marked, kind="stable") if filtered else pos
    order, lane = marked[perm], lane[perm]
    K = int(own.sum()) if filtered else n
    CH = J.lookup_chunk(n)
    mask = (order < n) & (pos < -(-K // CH) * CH)
    if kind == "direct":
        place = index[np.maximum(lane - 1, 0)]
        found = mask & (lane > 0) & (place > 0)
        slot = np.where(found, place - 1, S).astype(np.int32)
        through = found
    else:
        slot, crowded = J._probe_slots(
            np, [(lane, ones(lane))], mask & lane_ok[perm], state[0],
            crowded=True)
        through = mask & ((slot < S)
                          | (crowded & (counts[J.LATER_LEVEL] > 0)))
    # (the loop writes the chunks it ran: the rest stays zero)
    slot = np.where(pos < -(-K // CH) * CH, slot, 0)
    D = int(through.sum())
    again = np.concatenate([np.flatnonzero(through),
                            np.zeros(n - D + -n % C, np.int64)])
    out = []
    for rnd in range(max(1, -(-D // C))):
        at_block = rnd * C + np.arange(C)
        if K <= C:
            among, live = np.arange(C), through[:C] & (rnd == 0)
        else:
            among, live = again[at_block], at_block < D
        s = slot[among]
        if kind == "hash":
            keys = [(lane[among], ones(among))]
            h = J._fingerprint(np, keys, (C,))
            for level in range(1, J.JOIN_LEVELS):
                need = live & (s == S)
                if need.any():
                    s = np.minimum(s, J._probe_slots(
                        np, keys, need, state[0],
                        h=J._level_hash(np, h, level)))
        live = live & (s < S)
        if kind == "hash":
            s = np.minimum(s, S - 1)
        at = order[among]
        at = np.where(at >= n, 0, at)
        d_val = np.take(lanes[0], s, mode="clip")
        d_ok = np.take(lanes[1], s, mode="clip") != 0
        live = live & valid[at] & (key[at] < d_val) & d_ok
        out.append(((key[at], d_val), (valid[at], d_ok), live))
    return out


@pytest.mark.parametrize("n", [4096, 1000])
@pytest.mark.parametrize("filtered", [True, False],
                         ids=["filtered", "unfiltered"])
@pytest.mark.parametrize("kind", ["direct", "hash"])
def test_a_further_round_is_the_block_the_whole_probe_gave(kind, filtered, n):
    """A block of 16 over a bucket of 4,096 (chunks of 1,024) and one of
    1,000 (one chunk, and no whole number of blocks): every further
    round's ``(cols, valids, live)`` by the round kernel on round 0's
    carry equals, array for array, what the probe gave before there was
    a carry -- dead lanes too; round 0's equals it wherever a lane is
    live."""
    import jax
    import jax.numpy as jnp
    lo, slots, C = 1000, 2048, 16
    rng = np.random.default_rng(54)
    build = lo + rng.choice(slots, 900, replace=False)
    # the conjunct over both relations (f.key < d.val) drops a fifth
    vals = np.where(rng.random(build.size) < 0.2, 5,
                    rng.integers(10 ** 6, 10 ** 9, build.size))
    if kind == "direct":
        table = direct_table(lo, build, vals, slots)
    else:
        # a crowded table: some keys sit in a later pair of slots
        table = hash_table(build, vals, slots)
        built = np.asarray(table[1])
        assert built[J.BUILT] == build.size and built[J.LATER_LEVEL] > 0 \
            and built[J.UNPLACED] == built[J.REPEATED] == 0
    key = rng.integers(lo - 100, lo + slots + 100, n)
    valid = rng.random(n) > 0.05
    keep = np.where(rng.random(n) < 0.4, -1, 1)
    row_mask = np.arange(n) < n - n // 10
    node = probe_node(kind, filtered)
    probe = jax.jit(J.build_join_probe(node, (), jnp, C))
    further = jax.jit(J.build_join_probe_round(node, (), jnp, C))
    args = ((table,), (jnp.asarray(key), jnp.asarray(keep)),
            (jnp.asarray(valid), jnp.ones(n, bool)), jnp.asarray(row_mask))
    want = rounds_as_they_stood(
        kind, filtered, jax.device_get(table), (key, valid, keep, row_mask),
        C)
    assert len(want) > 3
    *block, carry = probe(*args)
    (k, v), (km, vm), live, counts = jax.device_get(block)
    (wk, wv), (wkm, wvm), wlive = want[0]
    assert (live == wlive).all() and live.any()
    for got, ref in ((k, wk), (v, wv), (km, wkm), (vm, wvm)):
        assert (got[live] == ref[live]).all()
    assert -(-counts[J.PACKED] // C) == len(want)
    dropped = 0
    for rnd in range(1, len(want)):
        (k, v), (km, vm), live, counts = jax.device_get(
            further(*args, carry, np.int32(rnd)))
        (wk, wv), (wkm, wvm), wlive = want[rnd]
        for got, ref in ((k, wk), (v, wv), (km, wkm), (vm, wvm),
                         (live, wlive)):
            assert got.dtype == ref.dtype and (got == ref).all(), rnd
        assert counts[J.LOOKED] == counts[J.MATCHED] == 0
        assert counts[J.OUT] == live.sum() <= counts[J.SEEN]
        dropped += counts[J.SEEN] - counts[J.OUT]
    assert dropped > 0          # the conjunct decided something
    assert not want[-1][2].all()        # the last block is not full


def _gathers(text):
    """(rows of the operand, indices) of every gather of a lowered
    module."""
    import re
    found = re.findall(
        r"stablehlo\.gather.*?:\s*\(tensor<(\d+)x[^>]*>, "
        r"tensor<(\d+)x1xi32>\)", text)
    return [(int(a), int(b)) for a, b in found]


@pytest.mark.parametrize("kind", ["direct", "hash"])
@pytest.mark.parametrize("filtered", [True, False],
                         ids=["filtered", "unfiltered"])
def test_a_further_round_holds_no_lookup_no_sort_and_no_long_gather(
        kind, filtered):
    """The mechanism, in the lowered text: round 0 holds the lookup loop
    and gathers a chunk of 1,024 a trip; the round kernel holds no
    ``while`` (a hash child: the one loop over its later pairs, a
    block's rows a trip), no ``sort`` and no gather of more than its
    block's 16 rows."""
    import jax
    import jax.numpy as jnp
    n, lo, slots, C = 4096, 1000, 2048, 16
    build = lo + np.arange(0, slots, 3)
    table = direct_table(lo, build, build, slots) if kind == "direct" \
        else hash_table(build, build, slots)
    node = probe_node(kind, filtered)
    args = ((table,), (jnp.zeros(n, np.int64), jnp.zeros(n, np.int64)),
            (jnp.ones(n, bool), jnp.ones(n, bool)), jnp.ones(n, bool))
    probe = jax.jit(J.build_join_probe(node, (), jnp, C))
    text = probe.lower(*args).as_text()
    assert "stablehlo.while" in text and "stablehlo.sort" in text
    assert max(i for _, i in _gathers(text)) == J.lookup_chunk(n) == 1024
    carry = jax.eval_shape(probe, *args)[-1]
    text = jax.jit(J.build_join_probe_round(node, (), jnp, C)).lower(
        *args, carry, np.int32(1)).as_text()
    assert "stablehlo.sort" not in text
    assert text.count("stablehlo.while") == (kind == "hash")
    gathers = _gathers(text)
    assert gathers and all(i <= C for _, i in gathers)
    # ... some of them out of the batch and the carry, row for row
    assert any(rows == n for rows, _ in gathers)
