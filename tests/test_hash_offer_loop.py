"""The hash module's offer loop (``ops/hash_agg.py``
``build_fused_hash_worker``, scope ``hash.offer``), on the CPU, through
the module itself:

- whatever the batch's distinct keys D against the chunk C (none, one,
  a chunk less one, a chunk, a chunk and one, three chunks and seven),
  whatever the keys (one bigint; a bigint, a NULL-able int and a float)
  and whether the table has room or must spill, table and spills merged
  through ``merge_hash_tables_into`` equal a numpy group-by exactly, no
  key sits in two slots, ``hash_table_updates`` advances by D a batch
  and ``hash_offer_slots`` by ceil(D / C) * C;
- a key whose rows interleave with another's -- several entries of one
  key, in one chunk and across chunks -- still ends in ONE slot;
- of two keys that race for one slot in one chunk the lower entry index
  holds it, also in a later trip;
- the chunk is a matter of the table's size alone (``entry_chunk``):
  ``ENTRY_CHUNK`` where the table's lanes fit the chip's fast memory,
  ``WIDE_CHUNK`` past ``FAST_TABLE_SLOTS``, the batch's length where that
  is shorter; a table past the limit gives the same answers and counts
  its entry slots by the wide chunk.
"""

import collections

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor.executor import (
    GLOBAL_COUNTERS, _SpillDrain, _hash_key_dtypes,
)
from citus_tpu.executor.host_agg import HostGroupAccumulator
from citus_tpu.ops import hash_agg
from citus_tpu.planner import parse_sql
from citus_tpu.planner.bind import bind_select
from citus_tpu.planner.physical import plan_select

C = hash_agg.ENTRY_CHUNK
N = 4 * C
#: a slot count no other case has (one trace a table size), for the
#: cases that lower ``FAST_TABLE_SLOTS`` under it
WIDE = 1 << 15

SHAPES = {
    "one_key": (
        "CREATE TABLE t (k bigint NOT NULL, v bigint NOT NULL)",
        "SELECT k, count(*), sum(v), min(v) FROM t GROUP BY k"),
    "three_keys": (
        "CREATE TABLE t (k bigint NOT NULL, n int, f double precision, "
        "v bigint NOT NULL)",
        "SELECT k, n, f, count(*), sum(v), max(v) FROM t GROUP BY k, n, f"),
}


@pytest.fixture(scope="module")
def plans(tmp_path_factory):
    """shape -> (plan, key dtypes): planned once against a table of two
    rows far apart, so no key domain is proved and the hash table it is."""
    out = {}
    for shape, (ddl, sql) in SHAPES.items():
        cl = ct.Cluster(str(tmp_path_factory.mktemp(shape) / "db"))
        cl.execute(ddl)
        cl.execute("SELECT create_distributed_table('t', 'k', 1)")
        rows = {"k": np.array([1, 10**13]), "v": np.array([1, 2])}
        if shape == "three_keys":
            rows.update(n=np.array([1, 2], np.int32),
                        f=np.array([0.5, 1.5]))
        cl.copy_from("t", columns=rows)
        plan = plan_select(cl.catalog,
                           bind_select(cl.catalog, parse_sql(sql)[0]))
        assert plan.group_mode.kind == "hash_host"
        out[shape] = (plan, _hash_key_dtypes(plan, {}))
        cl.close()
    return out


@pytest.fixture(scope="module")
def kernels(plans):
    """shape -> the jitted module (one compile a shape and table size)."""
    import jax
    import jax.numpy as jnp
    return {shape: jax.jit(hash_agg.build_fused_hash_worker(plan, jnp, kd))
            for shape, (plan, kd) in plans.items()}


def _batch(plan, shape, D, rng):
    """A batch of N rows holding exactly D distinct keys, a third of
    them on two rows, the rest of the batch masked out -> (cols, valids,
    row_mask, the live rows as python tuples (key..., v))."""
    live = min(N, D + D // 3)
    at = np.concatenate([np.arange(D), np.arange(D // 3)])[:live]
    at = at[rng.permutation(live)]
    k = rng.choice(10**12, D, replace=False)[at]
    v = rng.integers(-10**6, 10**6, live)
    cols = {"k": k, "v": v}
    valid = {"k": np.ones(live, bool), "v": np.ones(live, bool)}
    if shape == "three_keys":
        # n: NULL for a quarter of the keys; f: a function of the key
        # with -0.0 / 0.0 and NaN among its values
        cols["n"] = (k % 1000).astype(np.int32)
        valid["n"] = k % 4 != 0
        f = (k % 7).astype(np.float64) / 2
        f[k % 7 == 3] = np.nan
        f[(k % 7 == 0) & (v % 2 == 0)] = -0.0
        cols["f"], valid["f"] = f, np.ones(live, bool)
    lanes = plan.lanes
    pad = lambda a, dt: np.concatenate(
        [a.astype(dt), np.zeros(N - live, dt)])
    out_cols = tuple(pad(cols[c], dt)
                     for c, dt in zip(plan.scan_columns, lanes))
    out_valids = tuple(pad(valid[c], bool) for c in plan.scan_columns)
    rows = []
    for i in range(live):
        key = [int(k[i])]
        if shape == "three_keys":
            fi = float(cols["f"][i])
            key += [int(cols["n"][i]) if valid["n"][i] else None,
                    "nan" if np.isnan(fi) else fi + 0.0]
        rows.append((tuple(key), int(v[i])))
    return out_cols, out_valids, np.arange(N) < live, rows


def _groups_of(acc, shape):
    """The accumulator's groups as {key tuple: partial states}."""
    out = {}
    for kvs, accs in zip(acc._key_vals, acc._accs):
        key = [int(kvs[0][0])]
        if shape == "three_keys":
            fv = float(kvs[2][0])
            key += [int(kvs[1][0]) if kvs[1][1] else None,
                    "nan" if np.isnan(fv) else fv + 0.0]
        assert tuple(key) not in out
        out[tuple(key)] = tuple(int(a) for a in accs)
    return out


def _one_slot_a_key(state):
    """No key (values and flags of every lane) sits in two slots."""
    key_tables, _, rows = state
    occ = np.asarray(rows) > 0
    lanes = []
    for kvt, kft in key_tables:
        kvt = np.asarray(kvt)[occ]
        if np.issubdtype(kvt.dtype, np.floating):
            kvt = kvt.view(np.int64)
        lanes += [kvt.astype(np.int64), np.asarray(kft)[occ].astype(np.int64)]
        assert (np.asarray(kft)[occ] != 0).all()
        assert (np.asarray(kft)[~occ] == 0).all()
    stored = np.stack(lanes, axis=1)
    assert len(np.unique(stored, axis=0)) == len(stored)


@pytest.mark.parametrize("slots", [1 << 16, 1024, WIDE],
                         ids=["roomy", "spills", "wide"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("D", [0, 1, C - 1, C, C + 1, 3 * C + 7])
def test_table_and_spills_equal_a_numpy_group_by(plans, kernels, monkeypatch,
                                                 D, shape, slots):
    """The same batch offered TWICE (the second time every placed key
    matches its slot), drained the executor's way.  ``wide``: a table
    past the limit of the small chunk (the limit lowered under it: the
    real one is 4 M slots) runs the wide chunk, here the whole batch."""
    if slots == WIDE:
        monkeypatch.setattr(hash_agg, "FAST_TABLE_SLOTS", WIDE // 2)
    C = hash_agg.entry_chunk(slots, N)
    assert C == (N if slots == WIDE else hash_agg.ENTRY_CHUNK)
    plan, key_dtypes = plans[shape]
    cols, valids, mask, rows = _batch(
        plan, shape, D, np.random.default_rng(D + slots))
    acc = HostGroupAccumulator(len(key_dtypes), plan.partial_ops)
    drain = _SpillDrain(plan, [acc], slots)
    state = hash_agg.empty_hash_state(plan, slots, key_dtypes)
    c0 = GLOBAL_COUNTERS.snapshot()
    for _ in range(2):
        state, spill = kernels[shape](state, cols, valids, mask)
        assert int(spill[0]) == D
        assert spill[2].shape == (N,)
        drain([(None, spill)])
    c1 = GLOBAL_COUNTERS.snapshot()
    assert c1["hash_table_updates"] - c0["hash_table_updates"] == 2 * D
    assert c1["hash_offer_slots"] - c0["hash_offer_slots"] \
        == 2 * -(-D // C) * C
    assert (drain.updates, drain.slots) == (2 * D, 2 * -(-D // C) * C)
    if D > slots:
        assert drain.rows > 0
    if slots > 8 * D:
        # a table this empty loses an entry to its two probes rarely
        assert drain.rows <= D // 10 + 2
    _one_slot_a_key(state)
    hash_agg.merge_hash_tables_into(acc, plan, *state)

    kind = "min" if shape == "one_key" else "max"
    want = collections.defaultdict(lambda: [0, 0, None])
    for key, v in rows:
        w = want[key]
        w[0] += 2
        w[1] += 2 * v
        w[2] = v if w[2] is None else (min if kind == "min" else max)(w[2], v)
    assert len(want) == D
    assert _groups_of(acc, shape) == {k: tuple(w) for k, w in want.items()}


def _paired_fingerprint(real):
    """Keys 2i and 2i + 1 given ONE fingerprint (that of i): they share
    the sort's bits, so their rows stay in the batch's order, and both
    candidate slots."""
    def fingerprint(xp, keys, shape):
        (kv, kvm), = keys
        return real(xp, [(kv >> 1, kvm)], shape)
    return fingerprint


def _offer(plan, key_dtypes, k, slots=1 << 16):
    """The module over a batch whose live rows hold the keys ``k`` in
    that order (v = 1 a row) -> (state, spill)."""
    import jax
    import jax.numpy as jnp
    lanes = plan.lanes
    vals = {"k": k, "v": np.ones(len(k), np.int64)}
    cols = tuple(np.concatenate([vals[c].astype(dt),
                                 np.zeros(N - len(k), dt)])
                 for c, dt in zip(plan.scan_columns, lanes))
    valids = tuple(np.ones(N, bool) for _ in cols)
    kernel = jax.jit(hash_agg.build_fused_hash_worker(plan, jnp, key_dtypes))
    return kernel(hash_agg.empty_hash_state(plan, slots, key_dtypes), cols,
                  valids, np.arange(N) < len(k))


@pytest.mark.parametrize("pairs", [40, C // 2],
                         ids=["one_chunk", "across_chunks"])
def test_a_key_whose_rows_interleave_ends_in_one_slot(plans, monkeypatch,
                                                      pairs):
    """Rows 2i, 2i + 1, 2i, 2i + 1, 2i of every pair, the pairs one after
    another: five entries a pair, three of one key.  The first entry of a
    key takes a slot, its repeats spill at once; nothing is lost."""
    plan, key_dtypes = plans["one_key"]
    monkeypatch.setattr(hash_agg, "_fingerprint",
                        _paired_fingerprint(hash_agg._fingerprint))
    base = 2 * np.arange(pairs, dtype=np.int64) + 10**9
    k = (base[:, None] + np.array([0, 1, 0, 1, 0])).reshape(-1)
    state, spill = _offer(plan, key_dtypes, k)
    D, n_spilled, lost = int(spill[0]), int(spill[1]), np.asarray(spill[2])
    assert D == 5 * pairs
    # the three repeats of a pair, whatever else its slots cost it
    assert n_spilled >= 3 * pairs
    _one_slot_a_key(state)
    acc = HostGroupAccumulator(1, plan.partial_ops)
    hash_agg.merge_hash_tables_into(acc, plan, *state)
    hash_agg.merge_hash_tables_into(acc, plan, *spill[3:], entry_mask=lost)
    got = _groups_of(acc, "one_key")
    assert got == {**{(int(b),): (3, 3, 1) for b in base},
                   **{(int(b) + 1,): (2, 2, 1) for b in base}}


@pytest.mark.parametrize("first", [0, 2 * C + 10],
                         ids=["first_trip", "third_trip"])
def test_the_lower_entry_index_holds_the_raced_slot(plans, monkeypatch,
                                                    first):
    """Two keys of one fingerprint are entries ``first`` and ``first`` +
    1 of the batch: the earlier one holds their first candidate slot, the
    later one their second -- in the first trip and in the third, when
    the race lane holds what two trips left in it."""
    plan, key_dtypes = plans["one_key"]
    real = hash_agg._fingerprint
    monkeypatch.setattr(hash_agg, "_fingerprint", _paired_fingerprint(real))
    S = 1 << 20
    # entries come in the order of the sort's 31 bits: rank the other
    # keys by them and find a pair whose bits fall where the test wants it
    bits = lambda keys: np.asarray(real(
        np, [(keys >> 1, np.ones(len(keys), bool))],
        (len(keys),)) >> np.uint64(33)).astype(np.int64)
    others = 2 * np.arange(1, 3 * C, dtype=np.int64)
    ranked = np.sort(bits(others))
    below = ranked[first - 1] if first else -1
    pool = 2 * np.arange(10**6, 2 * 10**6, dtype=np.int64)
    pool_bits = bits(pool)
    fits = pool[(pool_bits > below) & (pool_bits < ranked[first])]
    assert fits.size
    pair = np.array([fits[0] + 1, fits[0]], np.int64)   # the odd key first
    k = np.concatenate([pair, others])
    state, spill = _offer(plan, key_dtypes, k, S)
    assert int(spill[0]) == len(k)
    h = real(np, [(pair >> 1, np.ones(2, bool))], (2,))
    assert h[0] == h[1]
    s1 = int(h[0] % np.uint64(S))
    s2 = int(hash_agg._mix(np, h, hash_agg._GOLD)[0] % np.uint64(S))
    kvt = np.asarray(state[0][0][0])
    assert kvt[s1] == pair[0]
    # the later key lost the race and holds the pair's second slot --
    # unless an earlier entry took that one, and then it spilled
    lost = np.asarray(spill[2])
    at = first + 1
    assert (kvt[s2] == pair[1]) != bool(lost[at])
    assert np.asarray(spill[3][0][0])[at] == pair[1]


@pytest.mark.parametrize("slots, n, chunk", [
    (1024, 32768, 8192),            # Q12's table over a block of the join
    (1 << 20, 65536, 8192),         # Q3's
    (1 << 22, 65536, 8192),         # Q10's: the largest fast table
    ((1 << 22) + 1, 65536, 65536),
    (1 << 23, 1 << 20, 65536),      # Q18's, one chip
    (1 << 24, 1 << 21, 65536),      # ... and four
    (1 << 20, 2048, 2048),          # a batch shorter than the chunk
    (1 << 24, 2048, 2048),
])
def test_the_chunk_follows_the_tables_size(slots, n, chunk):
    assert hash_agg.entry_chunk(slots, n) == chunk
    assert hash_agg.offer_slots(0, slots, n) == 0
    assert hash_agg.offer_slots(1, slots, n) == chunk
    assert hash_agg.offer_slots(np.array([chunk, chunk + 1]), slots,
                                n).tolist() == [chunk, 2 * chunk]
