"""HAVING decided on the device hash table: the filtered ending of the
coordinator's hash scan (executor.py ``_fetch_hash_survivors``, kernel
slots ``jit_hash_having`` / ``jit_hash_take``), on the CPU platform's
``jnp`` path, each answer held to the cpu oracle backend.

- a matrix of HAVING shapes (sum / count / min / max, >, <=, BETWEEN,
  AND / OR / NOT, a sum over all-NULL arguments, a literal and a ``$n``
  parameter, a key) x table sizes (1,024 slots against 24 K groups, so
  most keys spill and the whole table comes home; ``auto``, where the
  survivors' blocks and the spilled keys' entries come alone);
- keys whose rows interleave in a batch (the "repeats spill" case of
  ops/hash_agg.py): a group's table part passes HAVING alone and the
  merged group fails, and the reverse; no group lost, none merged twice;
- where it does not engage (a HAVING that keeps everything, ``avg`` of
  a decimal, ``count(DISTINCT)``) the old tail answers, and EXPLAIN
  ANALYZE's ``Hash:`` line says which ending ran;
- the sum-overflow error still comes for a group HAVING drops;
- two HAVING literals compile the filter ONCE (XLA compilations of the
  jitted function are counted, not ``kernel_cache_misses``);
- ``hash_entries_fetched`` and ``hash_table_bytes_fetched`` fall far
  under the table's;
- pushed remote partials + HAVING equal the pull path, and the worker
  still ships its whole table.
"""

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.errors import ExecutionError
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.executor.executor import GLOBAL_COUNTERS
from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
from citus_tpu.ops.hash_agg import FILTER_BLOCK

from test_hash_agg_fused import _assert_hash_mode, _delta, one_device, pair  # noqa: F401

KEYS, ROWS = 24_000, 80_000
GROUPS = 23_175         # of the 24,000 keys, those some row drew


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """23 K groups of signed values; ``n`` is NULL in every row of two
    groups in three, so their ``sum(n)`` is NULL and ``count(n)`` 0."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("having") / "db"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, g bigint, v bigint, "
               "d decimal(12,2), n bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    rng = np.random.default_rng(30)
    keys = rng.integers(0, 10**12, KEYS)
    at = rng.integers(0, KEYS, ROWS)
    v = rng.integers(-500, 1000, ROWS)
    n = np.where(at % 3 == 0, rng.integers(0, 900, ROWS), None).tolist()
    cl.copy_from("t", columns={"k": np.arange(ROWS, dtype=np.int64),
                               "g": keys[at], "v": v, "d": v / 100.0,
                               "n": n})
    yield cl
    cl.close()


def _both(cl, sql, params=None):
    """(rows on the device path, its pipeline counters, rows of the cpu
    oracle backend), the rows sorted."""
    r = cl.execute(sql, params=params)
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    try:
        oracle = cl.execute(sql, params=params).rows
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    return sorted(r.rows, key=repr), r.explain["pipeline"], \
        sorted(oracle, key=repr)


SELECT = ("SELECT g, count(*), sum(v), min(v), max(v), sum(d), sum(n), "
          "count(n) FROM t GROUP BY g HAVING ")
# (HAVING, parameters, the groups it keeps): each keeps few
HAVINGS = [
    ("sum(v) > 4000", None, 65),
    ("count(*) >= 10", None, 50),
    ("min(v) > 980", None, 32),
    ("max(v) <= -480", None, 45),
    ("sum(d) BETWEEN 30.00 AND 30.50", None, 49),
    ("sum(v) > 3300 AND count(*) < 5", None, 9),
    ("sum(v) > 5000 OR min(v) > 985", None, 31),
    ("NOT (sum(v) <= 4200)", None, 41),
    ("sum(n) > 4500", None, 39),
    ("sum(n) IS NULL AND count(*) >= 10", None, 34),
    ("count(n) = 0 AND sum(v) < -1200", None, 20),
    ("sum(v) > $1", [4000], 65),
    ("g < 100000000000 AND sum(v) - 100 * count(*) > 2500", None, 36),
]


@pytest.mark.parametrize("slots", ["auto", "1024"])
@pytest.mark.parametrize("having,params,kept", HAVINGS,
                         ids=[h for h, _, _ in HAVINGS])
def test_having_shapes_equal_the_cpu_oracle(loaded, one_device, having,
                                            params, kept, slots):
    cl = loaded
    cl.execute(f"SET citus.hash_agg_slots = {slots}")
    try:
        _assert_hash_mode(cl, (SELECT + having).replace("$1", "4000"))
        c0 = cl.counters.snapshot()
        got, pl, want = _both(cl, SELECT + having, params)
        c1 = cl.counters.snapshot()
    finally:
        cl.execute("SET citus.hash_agg_slots = auto")
    assert got == want
    assert len(got) == kept
    assert pl["hash_groups_out"] == GROUPS     # before HAVING, as ever
    S = pl["hash_slots"]
    if slots == "auto":
        # the survivors' blocks and the spilled keys' entries alone
        assert pl.get("hash_having_on_device") is True
        assert S == 131_072
        assert pl["hash_entries_fetched"] <= S // 2
        assert _delta(c0, c1, "hash_entries_fetched") \
            == pl["hash_entries_fetched"]
        assert pl["hash_table_bytes_fetched"] < S * 73 // 2
        if kept <= 16:
            assert pl["hash_entries_fetched"] < S // 8
    else:
        # most keys spilled: what HAVING could leave on the chip is less
        # than what the host must see anyway, so the table comes whole
        assert "hash_having_on_device" not in pl
        assert pl["hash_spilled_rows"] > ROWS // 2
        assert pl["hash_entries_fetched"] == S == 1024


def test_entries_fetched_follow_the_survivors_and_the_spilled_keys(
        loaded, one_device):
    """``hash_entries_fetched`` <= the survivors' blocks + the spilled
    keys (each in its power-of-two count), far under the slots."""
    cl = loaded
    sql = SELECT + "sum(v) > 4000"
    r = cl.execute(sql)
    pl = r.explain["pipeline"]
    S = pl["hash_slots"]
    survivors = len(r.rows)
    spilled_keys = pl["hash_spilled_rows"]      # at most a key a row
    pow2 = lambda n, least: max(least, 1 << (max(1, n) - 1).bit_length())
    assert pl["hash_entries_fetched"] <= \
        pow2(survivors, 8) * FILTER_BLOCK + pow2(spilled_keys, 1024)
    assert pl["hash_entries_fetched"] <= S // 2
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    assert (f"having on device: {pl['hash_entries_fetched']} of {S} "
            "entries fetched") in text, text


@pytest.mark.parametrize("having,why", [
    ("count(*) >= 1", "keeps every group: its blocks pass half the table"),
    ("avg(d) > 9.5", "avg of a decimal: a division the host makes"),
    ("avg(v) > 950", "avg of an integer: a float"),
    ("CAST(sum(v) AS double precision) > 4000.5", "a float comparison"),
])
def test_old_tail_where_the_filter_does_not_engage(loaded, one_device,
                                                   having, why):
    cl = loaded
    sql = "SELECT g, count(*), sum(v), avg(d) FROM t GROUP BY g HAVING " \
        + having
    got, pl, want = _both(cl, sql)
    assert got == want and got
    assert "hash_having_on_device" not in pl, why
    assert pl["hash_entries_fetched"] == pl["hash_slots"]
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    assert "Hash: hash slots" in text and "having on device" not in text


def test_count_distinct_in_having_stays_on_the_host(loaded, one_device):
    """An exact value-set partial never reaches the device table."""
    cl = loaded
    sql = ("SELECT g, count(DISTINCT v) FROM t GROUP BY g "
           "HAVING count(DISTINCT v) >= 9")
    got, pl, want = _both(cl, sql)
    assert got == want and got
    assert "hash_slots" not in pl
    text = "\n".join(l for (l,) in cl.execute(f"EXPLAIN ANALYZE {sql}").rows)
    assert "having on device" not in text


def test_two_having_literals_compile_the_filter_once(loaded, one_device):
    """HAVING's literals are runtime operands of ``jit_hash_having``:
    a second literal neither misses the kernel cache nor retraces."""
    cl = loaded
    GLOBAL_KERNELS.clear()
    sql = "SELECT g, sum(d), count(*) FROM t GROUP BY g HAVING sum(d) > {}"
    got1, pl1, want1 = _both(cl, sql.format("61.50"))
    kernels = [k for key, k in GLOBAL_KERNELS._e.items()
               if key[1] == "jit_hash_having"]
    assert len(kernels) == 1
    compiled = kernels[0]._cache_size()
    c0 = cl.counters.snapshot()
    got2, pl2, want2 = _both(cl, sql.format("64.25"))
    c1 = cl.counters.snapshot()
    assert got1 == want1 and got2 == want2 and len(got2) < len(got1)
    assert pl1.get("hash_having_on_device") and pl2.get("hash_having_on_device")
    assert _delta(c0, c1, "kernel_cache_misses") == 0
    assert [k for key, k in GLOBAL_KERNELS._e.items()
            if key[1] == "jit_hash_having"] == kernels
    assert kernels[0]._cache_size() == compiled == 1
    # another STRUCTURE is another program
    cl.execute(sql.format("61.50").replace(">", "<="))
    assert len([1 for key in GLOBAL_KERNELS._e
                if key[1] == "jit_hash_having"]) == 2


# -------------------------------------- keys with a part on either side


def _interleaved(cl, monkeypatch):
    """One batch in which, for 36 pairs, keys 4b and 4b + 1 share a
    fingerprint and their rows interleave as A, B, A: the sort cannot
    bring A's rows together, so A's first segment takes the slot and its
    repeat spills to the host -- A's final state is table entry + host
    part.  17,000 other keys (two rows each, alone in their fingerprint)
    stay on the chip.  Returns {key: (count, sum)}."""
    from citus_tpu.ops import hash_agg
    real = hash_agg._fingerprint
    monkeypatch.setattr(
        hash_agg, "_fingerprint",
        lambda xp, keys, shape: real(
            xp, [(kv // 4, kvm) for kv, kvm in keys], shape))
    cl.execute("CREATE TABLE w (k bigint NOT NULL, g bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('w', 'k', 1)")
    rng = np.random.default_rng(4)
    base = 4 * rng.choice(10**11, 17_036, replace=False)
    g, v = [], []
    for i, b in enumerate(base[:36].tolist()):
        # table part, the neighbour, host part
        first, last = [(900, -800), (100, 800), (-900, 800), (-100, -800),
                       (300, 300), (-300, -300)][i % 6]
        g += [b, b + 1, b]
        v += [first, 1, last]
    for i, b in enumerate(base[36:].tolist()):
        g += [b + 2, b + 2]
        v += [400, 400] if i % 4000 == 0 else \
            [-400, -400] if i % 4000 == 1 else [1, 1]
    cl.copy_from("w", columns={"k": np.arange(len(g), dtype=np.int64),
                               "g": np.array(g), "v": np.array(v)})
    truth = {}
    for gi, vi in zip(g, v):
        c, s = truth.get(gi, (0, 0))
        truth[gi] = (c + 1, s + vi)
    return truth


@pytest.mark.parametrize("having,keep", [
    # (900, -800): the table part passes alone, the group (100) fails;
    # (100, 800): the table part fails alone, the group (900) passes
    ("sum(v) > 500", lambda c, s: s > 500),
    # (-900, 800) and (-100, -800), the same with the sign turned
    ("sum(v) < -500", lambda c, s: s < -500),
    # (300, 300): neither part passes alone
    ("sum(v) BETWEEN 550 AND 650 AND count(*) = 2", lambda c, s:
     550 <= s <= 650 and c == 2),
])
def test_table_part_and_host_part_decide_together(tmp_path, one_device,
                                                  monkeypatch, having, keep):
    cl = ct.Cluster(str(tmp_path / "db"))
    truth = _interleaved(cl, monkeypatch)
    GLOBAL_KERNELS.clear()
    try:
        sql = "SELECT g, count(*), sum(v) FROM w GROUP BY g HAVING " + having
        _assert_hash_mode(cl, sql)
        r = cl.execute(sql)
        pl = r.explain["pipeline"]
        cl.execute("SET citus.task_executor_backend = 'cpu'")
        oracle = cl.execute(sql).rows
    finally:
        GLOBAL_KERNELS.clear()   # no other test gets the patched kernels
        cl.close()
    want = sorted((g, c, s) for g, (c, s) in truth.items() if keep(c, s))
    assert sorted(oracle) == want
    # no group lost, none merged twice: every count and sum, equal
    assert sorted(r.rows) == want and 6 <= len(want) <= 17
    assert pl.get("hash_having_on_device") is True
    # the 36 repeats spilled (and the keys that lost both probes): the
    # host held a part of those keys
    assert pl["hash_spilled_rows"] >= 36
    assert pl["hash_groups_out"] == len(truth) == 17_072
    assert pl["hash_entries_fetched"] <= pl["hash_slots"] // 2


# ---------------------------------------------------- the overflow error


def test_sum_overflow_still_raises_for_a_group_having_drops(tmp_path,
                                                            one_device):
    """The group whose sum leaves int64 fails HAVING (its count is 4):
    the error comes all the same, as it does from the whole-table tail
    and from the cpu backend."""
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE o (k bigint NOT NULL, g bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('o', 'k', 2)")
    rng = np.random.default_rng(8)
    n = 40_000                                  # 65,536 slots
    g = rng.integers(0, 10**12, n)
    v = rng.integers(0, 100, n)
    g[:4] = 77                                  # one group of four rows
    v[:4] = (1 << 62) - 1                       # 4 x that wraps int64
    cl.copy_from("o", columns={"k": np.arange(n, dtype=np.int64),
                               "g": g, "v": v})
    sql = "SELECT g, sum(v) FROM o GROUP BY g HAVING count(*) > {}"
    _assert_hash_mode(cl, sql.format(4))
    for backend in ("tpu", "cpu"):
        cl.execute(f"SET citus.task_executor_backend = '{backend}'")
        with pytest.raises(ExecutionError, match="out of range"):
            cl.execute(sql.format(4))           # drops every group
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    with pytest.raises(ExecutionError, match="out of range"):
        cl.execute(sql.format(0))               # keeps all: whole table
    # without the wrapped group the filtered ending answers
    r = cl.execute("SELECT g, sum(v) FROM o WHERE g <> 77 GROUP BY g "
                   "HAVING count(*) > 4")
    assert r.rows == [] and r.explain["pipeline"]["hash_having_on_device"]
    cl.close()


# ------------------------------------------------------- 2-host push


def test_pushed_partials_with_having_equal_the_pull_path(pair,
                                                         limit_devices):
    """The coordinator decides HAVING on its one table after the remote
    partials merged into it; the worker ships its whole table (a worker
    plan has no HAVING).  One device: across several, a peer's entries
    are dealt to the tables as they come and the tables merge on the
    host (tests/test_hash_agg_mesh.py)."""
    from citus_tpu.executor import executor
    limit_devices(1)
    a, b, na, nb = pair
    a.execute("CREATE TABLE t (k bigint NOT NULL, g bigint, v bigint)")
    a.execute("SELECT create_distributed_table('t', 'k', 4)")
    rng = np.random.default_rng(5)
    n, groups = 20_000, 3000
    g = rng.integers(0, 10**12, groups)[rng.integers(0, groups, n)]
    a.copy_from("t", columns={"k": np.arange(n, dtype=np.int64), "g": g,
                              "v": rng.integers(-500, 1000, n)})
    GLOBAL_CACHE.clear()
    GLOBAL_COUNTERS.reset()
    sql = ("SELECT g, count(*), sum(v), min(v) FROM t GROUP BY g "
           "HAVING sum(v) > 6000 ORDER BY g")
    _assert_hash_mode(a, sql)
    r = a.execute(sql)
    snap = GLOBAL_COUNTERS.snapshot()
    assert snap["hash_partials_pushed"] >= 1
    assert snap["remote_task_fallbacks"] == 0
    pl = r.explain["pipeline"]
    assert pl.get("hash_having_on_device") is True
    S = pl["hash_slots"]
    assert pl["hash_entries_fetched"] < S
    # both tables of the query (a worker's and the coordinator's, in one
    # process here) came through _finish_hash_agg or the wire: the
    # coordinator's entries are the filtered ones, the worker's the table
    a.execute("SET citus.remote_task_execution = pull")
    GLOBAL_CACHE.clear()
    c0 = GLOBAL_COUNTERS.snapshot()
    pulled = a.execute(sql)
    c1 = GLOBAL_COUNTERS.snapshot()
    a.execute("SET citus.remote_task_execution = auto")
    assert _delta(c0, c1, "remote_tasks_pushed") == 0
    assert r.rows == pulled.rows and len(r.rows) >= 1
    a.execute("SET citus.task_executor_backend = 'cpu'")
    assert a.execute(sql).rows == r.rows
    # the worker half: a decoded task plan carries no HAVING and its
    # table comes home whole
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    from citus_tpu.executor.worker_tasks import _decode_plan, encode_task
    plan = plan_select(a.catalog, bind_select(a.catalog, parse_sql(sql)[0]))
    assert executor._device_having(plan) is not None
    task = encode_task(plan)
    assert task is not None and task["kind"] == "hash"
    worker_plan, _params = _decode_plan(a.catalog.table("t"), task,
                                        plan.shard_indexes[0])
    assert worker_plan.bound.having is None
    assert executor._device_having(worker_plan) is None
