"""Hash aggregation on every device of the host (``executor.py``
``_run_hash_device`` over ``scan_loop.AffineMeshPlacement``): one table a
device, each fed its own shards' batches, against a plain group-by
written here in Python integers (no engine code) and against the
one-device path, on the CPU platform's virtual devices.

The table is Q18's block in small: ``k`` the distribution column (a
group lives in ONE shard), ``q`` a decimal quantity with NULLs, ``s`` a
nullable column that is NOT the distribution column (a group meets
every shard).  The tolerance is equality: decimals are scaled int64.
"""

import decimal
import re

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import executor as X

ROWS, ORDERS, SUPPLIERS = 60_000, 15_000, 9_000


def make_rows(seed):
    rng = np.random.default_rng(seed)
    # sparse keys far wider than direct_gid_limit: the hash table answers
    keys = rng.choice(10 ** 12, ORDERS, replace=False)
    k = keys[rng.integers(0, ORDERS, ROWS)]
    q = rng.integers(100, 5001, ROWS)               # 1.00 .. 50.00
    q_null = rng.random(ROWS) < 0.03
    s = rng.choice(10 ** 11, SUPPLIERS, replace=False)[
        rng.integers(0, SUPPLIERS, ROWS)]
    s_null = rng.random(ROWS) < 0.02
    return k, q, q_null, s, s_null


def load(cl, rows, shards):
    k, q, q_null, s, s_null = rows
    cl.execute("CREATE TABLE t (k bigint NOT NULL, q decimal(12,2), s bigint)")
    cl.execute(f"SELECT create_distributed_table('t', 'k', {shards})")
    half = len(k) // 2
    for part in (slice(0, half), slice(half, None)):
        cl.copy_from("t", columns={
            "k": k[part],
            "q": [None if n else decimal.Decimal(int(v)).scaleb(-2)
                  for v, n in zip(q[part], q_null[part])],
            "s": [None if n else int(v)
                  for v, n in zip(s[part], s_null[part])]})


def group_sums(rows, by):
    """{key or None: (scaled sum or None, rows)}: the plain reference."""
    k, q, q_null, s, s_null = rows
    out = {}
    for i in range(len(k)):
        key = int(k[i]) if by == "k" else (None if s_null[i] else int(s[i]))
        total, n = out.get(key, (None, 0))
        if not q_null[i]:
            total = int(q[i]) + (total or 0)
        out[key] = (total, n + 1)
    return out


def dec(v):
    return None if v is None else decimal.Decimal(v).scaleb(-2)


def expected(rows, by, above=None):
    """Rows of ``SELECT by, sum(q), count(*) ... GROUP BY by [HAVING
    sum(q) > above]`` (``above`` a scaled integer)."""
    return sorted(
        ((key, dec(total), n) for key, (total, n)
         in group_sums(rows, by).items()
         if above is None or (total is not None and total > above)),
        key=lambda r: (r[0] is None, r[0]))


def nth_largest_sum(rows, by, n):
    sums = sorted((t for t, _ in group_sums(rows, by).values()
                   if t is not None), reverse=True)
    return sums[n]


def run(cl, sql):
    c0 = cl.counters.snapshot()
    r = cl.execute(sql)
    c1 = cl.counters.snapshot()
    got = sorted(r.rows, key=lambda r: (r[0] is None, r[0]))
    return got, r.explain["pipeline"], {k: c1[k] - c0.get(k, 0) for k in c1}


@pytest.fixture(scope="module")
def rows():
    return make_rows(35)


@pytest.fixture(scope="module")
def cl8(tmp_path_factory, rows):
    """8 shards: two a device on four devices, four on two."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("hm8") / "db"))
    load(cl, rows, 8)
    yield cl
    cl.close()


@pytest.fixture(scope="module")
def cl2(tmp_path_factory, rows):
    """2 shards: on four devices two of them never get a batch."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("hm2") / "db"))
    load(cl, rows, 2)
    yield cl
    cl.close()


def sql_for(by, above):
    having = "" if above is None else \
        f" HAVING sum(q) > {decimal.Decimal(above).scaleb(-2)}"
    return f"SELECT {by}, sum(q), count(*) FROM t GROUP BY {by}{having}"


# (case, key, HAVING keeps the n largest | None = no HAVING, devices,
#  SET hash_agg_slots | None)
CASES = [
    ("block_1dev", "k", 5, 1, None),
    ("block_2dev", "k", 5, 2, None),
    ("block_4dev", "k", 5, 4, None),
    ("block_8dev", "k", 5, 8, None),
    ("spill_2dev", "k", 5, 2, 64),
    ("spill_4dev", "k", 5, 4, 64),
    ("keeps_most_4dev", "k", 12_000, 4, None),
    ("no_having_4dev", "k", None, 4, None),
    ("other_key_2dev", "s", 5, 2, None),
    ("other_key_4dev", "s", 5, 4, None),
    ("other_key_spill_4dev", "s", 5, 4, 64),
    ("other_key_no_having_4dev", "s", None, 4, None),
]


@pytest.mark.parametrize("case,by,keep,n_dev,slots", CASES,
                         ids=[c[0] for c in CASES])
def test_equals_the_plain_group_by_and_the_one_device_path(
        cl8, rows, limit_devices, case, by, keep, n_dev, slots):
    above = None if keep is None else nth_largest_sum(rows, by, keep)
    want = expected(rows, by, above)
    assert len(want) == (keep if keep is not None
                         else len(group_sums(rows, by)))
    sql = sql_for(by, above)
    cl8.execute(f"SET citus.hash_agg_slots = {slots or 'auto'}")
    try:
        limit_devices(1)
        one, pl1, _ = run(cl8, sql)
        limit_devices(n_dev)
        got, pl, delta = run(cl8, sql)
        text = "\n".join(
            l for (l,) in cl8.execute(f"EXPLAIN ANALYZE {sql}").rows)
    finally:
        cl8.execute("SET citus.hash_agg_slots = auto")
    assert got == want          # every key, decimal sum and count, equal
    assert one == want
    assert pl1["hash_tables"] == 1 and "tables " not in pl1
    assert pl["hash_tables"] == delta["hash_tables"] == n_dev
    assert pl["hash_rows_in"] == delta["hash_rows_in"] == ROWS
    assert delta["hash_groups_out"] == len(group_sums(rows, by))
    S = pl["hash_slots"] // n_dev
    assert delta["hash_slots"] == pl["hash_slots"] == n_dev * S
    if slots:
        assert S == slots and pl["hash_slots_from"] == "setting"
        assert pl["hash_spilled_rows"] > ROWS // 2
    else:
        # each table is sized by the rows of the fullest device's shards
        assert S == 1 << (pl["hash_rows_in_max_device"] - 1).bit_length()
        assert pl["hash_slots_from"] == "row count"
        assert pl["hash_spilled_rows"] < 0.1 * ROWS
    if n_dev == 1:
        assert "tables" not in re.search(r"Hash: .*", text).group(0)
        return
    # the balance of the shard-to-device map
    assert ROWS / n_dev <= pl["hash_rows_in_max_device"] \
        == delta["hash_rows_in_max_device"] <= 1.1 * ROWS / n_dev
    if by == "k":
        # the keys hold the distribution column: tables apart, none merged
        assert f"tables {n_dev} x {S} slots, disjoint on k" in text
        assert delta["hash_tables_merged"] == pl["hash_tables_merged"] == 0
    else:
        assert f"tables {n_dev} x {S} slots, merged" in text
        assert delta["hash_tables_merged"] == pl["hash_tables_merged"] == n_dev
        assert "hash_having_on_device" not in pl
        assert delta["hash_entries_fetched"] == n_dev * S
    if case in ("block_2dev", "block_4dev"):
        # HAVING was decided on the chips: the survivors' blocks and the
        # spilled keys' entries came home, not the tables
        assert pl["hash_having_on_device"] is True
        assert delta["hash_entries_fetched"] <= n_dev * S // 2
        assert f"of {n_dev * S} entries fetched" in text
    if case in ("keeps_most_4dev", "no_having_4dev", "spill_4dev"):
        # nothing the chips could thin: whole tables home, still apart
        assert "hash_having_on_device" not in pl
        assert delta["hash_entries_fetched"] == n_dev * S


def test_fewer_shards_than_devices_leaves_devices_without_a_batch(
        cl2, rows, limit_devices, monkeypatch):
    above = nth_largest_sum(rows, "k", 5)
    sql = sql_for("k", above)
    placements = []
    real = X.choose_affine_placement

    def spy(*a, **kw):
        placements.append(real(*a, **kw))
        return placements[-1]

    monkeypatch.setattr(X, "choose_affine_placement", spy)
    limit_devices(4)
    got, pl, delta = run(cl2, sql)
    assert got == expected(rows, "k", above)
    (placement, _stream), = placements
    # shard 0 -> device 0, shard 1 -> device 2; 1 and 3 stay dry
    taken = placement.device_rows
    assert taken[1] == taken[3] == 0 and taken[0] + taken[2] == ROWS
    assert min(taken[0], taken[2]) > 0.4 * ROWS
    assert pl["hash_tables"] == 4 and pl["hash_tables_merged"] == 0
    assert pl["hash_rows_in_max_device"] == max(taken)


def test_a_single_batch_falls_to_one_device(tmp_path, limit_devices):
    limit_devices(4)
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, q decimal(12,2), s bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 1)")
    k = np.arange(3000, dtype=np.int64) * 10 ** 9
    cl.copy_from("t", columns={"k": np.repeat(k, 2),
                               "q": np.tile([1.25, 2.5], 3000),
                               "s": np.arange(6000)})
    got, pl, delta = run(cl, "SELECT k, sum(q), count(*) FROM t GROUP BY k "
                             "HAVING sum(q) > 3")
    assert got == [(int(v), decimal.Decimal("3.75"), 2) for v in k]
    assert pl["hash_tables"] == delta["hash_tables"] == 1
    assert pl["hash_disjoint_on"] is None and delta["hash_tables_merged"] == 0
    assert delta["hash_fused_dispatches"] == 1
    cl.close()


def test_the_devices_tables_are_apart_and_add_up_to_the_whole(
        cl8, rows, limit_devices, monkeypatch):
    """What ties the share to the whole: on the distribution-column key
    no two devices' tables hold the same key, and the tables' groups
    and the spilled ones add up to the reference's, sum for sum."""
    seen = []
    real = X._run_hash_device

    def spy(cat, plan, settings, params, acc, *a, **kw):
        tables = real(cat, plan, settings, params, acc, *a, **kw)
        seen.append((tables, acc))
        return tables

    monkeypatch.setattr(X, "_run_hash_device", spy)
    limit_devices(4)
    got, pl, _ = run(cl8, sql_for("k", None))
    want = group_sums(rows, "k")
    assert got == expected(rows, "k")
    (tables, acc), = seen
    assert tables.tables == 4 and tables.disjoint == "k"
    (keys, flags), = tables.state[0]
    sums, n_rows = np.asarray(tables.state[1][0]), np.asarray(tables.state[2])
    keys = np.asarray(keys)
    per_device = [set(keys[d][n_rows[d] > 0].tolist()) for d in range(4)]
    for a in range(4):
        assert len(per_device[a]) > 0.2 * ORDERS
        for b in range(a + 1, 4):
            assert not per_device[a] & per_device[b]
    assert set().union(*per_device) <= set(want)
    # a key's rows are in its one entry or were spilled: entry + spilled
    # part = the reference's sum and count
    total = {}
    for d in range(4):
        live = n_rows[d] > 0
        for key, v, n in zip(keys[d][live].tolist(), sums[d][live].tolist(),
                             n_rows[d][live].tolist()):
            total[key] = (v, n)
    assert sum(n for _, n in total.values()) + pl["hash_spilled_rows"] == ROWS
    unspilled = {key for key in total if total[key][1] == want[key][1]}
    assert len(unspilled) > 0.9 * ORDERS
    for key in unspilled:
        # count(*) says no row of the key went elsewhere
        assert total[key][0] == (want[key][0] or 0)


def test_hash_init_and_the_endings_say_what_they_did(cl8, rows,
                                                     limit_devices):
    """Spans of the per-device path: ``hash_init`` (devices, slots per
    table, what bounded them), the mesh round's ``stack`` / ``h2d`` /
    ``dispatch``, ``spill_drain`` (devices that spilled), ``hash_filter``
    and ``fetch`` (tables, entries)."""
    from citus_tpu.observability import trace as T
    limit_devices(4)
    above = nth_largest_sum(rows, "k", 5)
    cl8.execute("SET citus.trace_sample_rate = 1.0")
    try:
        _, pl, _ = run(cl8, sql_for("k", above))
        tr = T.last_trace()
    finally:
        cl8.execute("SET citus.trace_sample_rate = 0")
    init = tr.find("hash_init")
    S = pl["hash_slots"] // 4
    assert (init.attrs["devices"], init.attrs["slots"],
            init.attrs["slots_from"]) == (4, S, "row count")
    rounds = tr.find_all("device_round")
    assert len(rounds) == pl["fused_dispatches"] >= 2
    for name in ("stack", "h2d", "dispatch"):
        assert len(tr.find_all(name)) == len(rounds)
    assert {s.attrs["slot"] for s in tr.find_all("dispatch")} == \
        {"jit_hash_fused"}
    drains = tr.find_all("spill_drain")
    assert drains and all(0 <= s.attrs["devices"] <= 4 for s in drains)
    assert sum(s.attrs["rows"] for s in drains) == pl["hash_spilled_rows"]
    flt = tr.find("hash_filter")
    assert flt.attrs["tables"] == 4 and flt.attrs["slots"] == S
    fetch = tr.find("fetch")
    assert fetch.attrs["tables"] == 4
    assert fetch.attrs["entries"] == pl["hash_entries_fetched"]


def test_remote_partials_meet_the_tables_as_they_come_and_merge_exactly(
        tmp_path, limit_devices):
    """Two hosts, the coordinator on four devices: the worker's table
    partials are dealt across the coordinator's tables through the
    device merge door, so even on the distribution-column key the
    tables are no longer proven apart -- they come home whole and merge
    on the host, HAVING after the merge, and every sum is equal."""
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    limit_devices(4)
    a = ct.Cluster(str(tmp_path / "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    a.register_node()
    b = ct.Cluster(str(tmp_path / "b"), data_port=0, hosted_nodes=set(),
                   coordinator=("127.0.0.1", a.control_port), n_nodes=0)
    b.register_node()
    a._maybe_reload_catalog(force_sync=True)
    try:
        rows = make_rows(36)
        load(a, rows, 8)
        above = nth_largest_sum(rows, "k", 5)
        c0 = GLOBAL_COUNTERS.snapshot()
        got, pl, _ = run(a, sql_for("k", above))
        c1 = GLOBAL_COUNTERS.snapshot()
        assert got == expected(rows, "k", above)
        assert c1["hash_partials_pushed"] > c0["hash_partials_pushed"]
        assert c1["remote_task_fallbacks"] == c0["remote_task_fallbacks"]
        assert pl["hash_tables"] == 4 and pl["hash_disjoint_on"] is None
        assert pl["hash_tables_merged"] == 4
        assert "hash_having_on_device" not in pl
    finally:
        b.close()
        a.close()
