"""The direct mode's group id, in the lanes and with the division that
the key's domain proves (ops/scan_agg.py ``direct_id_lanes`` /
``direct_group_id_fn``).

The oracle is the formula the kernel ran before: every key widened to
int64, ``//`` by the step, a clip a key and one at the end.  A row
inside its keys' domains must get the same id bit for bit, on the numpy
and on the jax arm, under ``vmap`` (megabatch's shape) and under
``shard_map``; a masked or padding row any slot with neutral updates.
Everything here is a count or an answer on the CPU backend, never a
time.
"""

import collections
import datetime

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.ops import scan_agg
from citus_tpu.planner.bind import bind_select
from citus_tpu.planner.bound import _as_mask, compile_expr
from citus_tpu.planner.parser import parse_sql
from citus_tpu.planner.physical import DIRECT_MAX_SLOTS, plan_select

N_ROWS = 4096
HOUR, DAY = 3_600_000_000, 86_400_000_000
T0 = 1_420_070_400_000_000          # 2015-01-01 00:00, microseconds
I32_MIN = -(1 << 31)

# name -> (column definitions, {column: (lowest, highest)} or the words of
# a text column, the GROUP BY keys, lanes expected as (sub, divide), slots)
CASES = {
    "text": ("c text", {"c": list("abcd")}, "c",
             [("int32", "none")], 5),
    "boolean": ("b boolean", {"b": (False, True)}, "b",
                [("int32", "none")], 3),
    "date": ("d date", {"d": (9000, 9400)}, "d",
             [("int32", "none")], 402),
    "integer_negative_lo": ("i integer", {"i": (-500, 700)}, "i",
                            [("int32", "none")], 1202),
    # a zeroed padding row: 0 - (-2**31) wraps in 32-bit lanes
    "integer_padding_wraps": ("i integer", {"i": (I32_MIN, I32_MIN + 900)},
                              "i", [("int32", "none")], 902),
    "bigint_lo_above_int32": ("k bigint", {"k": (1 << 40, (1 << 40) + 3000)},
                              "k", [("int32", "none")], 3002),
    "bigint_lo_below_int32": ("k bigint", {"k": (-(1 << 40) - 3000,
                                                 -(1 << 40))},
                              "k", [("int32", "none")], 3002),
    "bigint_at_the_direct_limit": (
        "k bigint", {"k": (7, 7 + DIRECT_MAX_SLOTS - 2)}, "k",
        [("int32", "none")], DIRECT_MAX_SLOTS),
    "two_keys": ("c text, b boolean", {"c": list("abcd"), "b": (False, True)},
                 "c, b", [("int32", "none")] * 2, 15),
    "three_keys": ("c text, b boolean, d date",
                   {"c": list("abcd"), "b": (False, True), "d": (9000, 9400)},
                   "c, b, d", [("int32", "none")] * 3, 15 * 402),
    # the wrapped code of a padding row times a stride wraps again
    "two_keys_padding_wraps": (
        "i integer, c text", {"i": (I32_MIN, I32_MIN + 900),
                              "c": list("abcd")},
        "i, c", [("int32", "none")] * 2, 902 * 5),
    "trunc_day": ("d date", {"d": (9000, 9400)}, "date_trunc('day', d)",
                  [("int32", "none")], 402),
    "trunc_week": ("d date", {"d": (9000, 9400)}, "date_trunc('week', d)",
                   [("int32", "estimate")], 59),
    "trunc_week_before_epoch": (
        "d date", {"d": (-40000, -30000)}, "date_trunc('week', d)",
        [("int32", "estimate")], 1430),
    "trunc_hour": ("ts timestamp", {"ts": (T0, T0 + 181 * DAY - 1)},
                   "date_trunc('hour', ts)", [("int64", "estimate")],
                   181 * 24 + 1),
    "trunc_week_of_timestamp": (
        "ts timestamp", {"ts": (T0, T0 + 181 * DAY - 1)},
        "date_trunc('week', ts)", [("int64", "estimate")], 28),
    "trunc_hour_and_text": (
        "ts timestamp, c text", {"ts": (T0, T0 + 30 * DAY - 1),
                                 "c": list("abcd")},
        "date_trunc('hour', ts), c",
        [("int64", "estimate"), ("int32", "none")], (30 * 24 + 1) * 5),
    # 2**17 quotients and more: the estimate no longer settles it
    "trunc_minute_wide": ("ts timestamp", {"ts": (T0, T0 + 100 * DAY - 1)},
                          "date_trunc('minute', ts)",
                          [("int64", "floor_div")], 100 * 1440 + 1),
    "trunc_week_wide": ("d date", {"d": (-400_000, 600_000)},
                        "date_trunc('week', d)", [("int32", "floor_div")],
                        142_859),
}


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    """plan(case) -> (plan, {column: (lo, hi) in physical values})."""
    cl = ct.Cluster(str(tmp_path_factory.mktemp("gid") / "db"))
    made = {}

    def plan(case, aggs="count(*), sum(v)", where=""):
        cols, spec, keys, _, slots = CASES[case]
        t = f"t_{case}"
        if case not in made:
            cl.execute(f"CREATE TABLE {t} (id bigint, {cols}, v bigint)")
            cl.execute(f"SELECT create_distributed_table('{t}', 'id', 2)")
            n = max(len(s) for s in spec.values())
            data = {"id": np.arange(n), "v": np.arange(n)}
            for c, s in spec.items():
                vals = list(s) + [s[-1]] * (n - len(s))
                ctype = cl.catalog.table(t).schema.column(c).type
                if isinstance(vals[0], (str, bool)):
                    data[c] = vals if isinstance(vals[0], str) \
                        else np.array(vals)
                else:
                    data[c] = np.array(vals, ctype.storage_dtype)
            cl.copy_from(t, columns=data)
            made[case] = {
                c: ((0, len(s) - 1) if isinstance(s, list)
                    else (int(s[0]), int(s[1]))) for c, s in spec.items()}
        p = plan_select(
            cl.catalog, bind_select(cl.catalog, parse_sql(
                f"SELECT {keys}, {aggs} FROM {t} {where} "
                f"GROUP BY {keys}")[0], param_count=where.count("$")),
            direct_limit=1 << 20 if slots > DIRECT_MAX_SLOTS else 0)
        assert p.group_mode.kind == "direct", p.group_mode
        return p, made[case]
    yield plan
    cl.close()


def batch(plan, ranges, seed):
    """One padded batch as the executor hands it over: device dtypes,
    NULL keys and a padding tail zeroed, some real rows filtered."""
    rng = np.random.default_rng([seed, plan.group_mode.n_groups])
    schema = plan.bound.table.schema
    row_mask = np.arange(N_ROWS) < N_ROWS - 500
    row_mask &= rng.random(N_ROWS) < 0.95
    cols, valids = [], []
    for c in plan.scan_columns:
        lo, hi = ranges.get(c, (-10 ** 12, 10 ** 12))
        v = rng.integers(lo, hi, N_ROWS, dtype=np.int64, endpoint=True)
        v[:2] = lo, hi                         # the domain's two ends
        valid = (rng.random(N_ROWS) < 0.85) | (c == "v")
        valid[:2] = True
        keep = valid & (np.arange(N_ROWS) < N_ROWS - 500)
        cols.append(np.where(keep, v, 0).astype(
            schema.scan_dtype(c, device=True)))
        valids.append(valid)
    return tuple(cols), tuple(valids), row_mask


def old_group_id(plan, cols, valids, mask):
    """The id as the kernel made it before this file: int64, ``//``, the
    two clips."""
    env = {c: (a, v) for c, a, v in zip(plan.scan_columns, cols, valids)}
    mode = plan.group_mode
    gid = None
    for key, d, stride in zip(plan.bound.group_keys, mode.domains,
                              mode.strides):
        kv, kvalid = compile_expr(key, np)(env)
        code = (kv.astype(np.int64) - d.lo) // d.step
        code = np.where(_as_mask(np, kvalid, kv), code + 1, 0)
        code = np.clip(code, 0, None)
        gid = code * stride if gid is None else gid + code * stride
    return np.clip(np.where(mask, gid, 0), 0,
                   mode.n_groups - 1).astype(np.int32)


def new_group_id(plan, xp, cols, valids, mask):
    fn = scan_agg.direct_group_id_fn(plan, xp)

    def run(cols, valids, mask):
        return fn({c: (a, v) for c, a, v in
                   zip(plan.scan_columns, cols, valids)}, mask)
    got = jax.jit(run)(cols, valids, mask) if xp is jnp \
        else run(cols, valids, mask)
    assert got.dtype == np.int32
    return np.asarray(got)


def assert_states_equal(got, want):
    if want.dtype == np.float64:
        # the float64 shadow of an int64 sum: a guard, held to float error
        scale = max(np.abs(want).max(), 1.0)
        assert np.allclose(got, want, rtol=0, atol=1e-9 * scale)
    else:
        assert got.dtype == want.dtype and (got == want).all()


@pytest.mark.parametrize("arm", ["numpy", "jax"])
@pytest.mark.parametrize("case", CASES)
def test_id_equals_the_old_formula(db, case, arm):
    plan, ranges = db(case)
    _, _, _, lanes, slots = CASES[case]
    assert plan.group_mode.n_groups == slots
    assert [(str(l.sub), l.divide)
            for l in scan_agg.direct_id_lanes(plan)] == lanes
    for seed in (1, 2):
        cols, valids, mask = batch(plan, ranges, seed)
        want = old_group_id(plan, cols, valids, mask)
        got = new_group_id(plan, np if arm == "numpy" else jnp,
                           cols, valids, mask)
        # every row equal: a masked row reads slot 0 in both
        assert (got == want).all(), np.nonzero(got != want)[0][:5]
        assert want[mask].max() > plan.group_mode.n_groups // 2


@pytest.mark.parametrize("case", ["two_keys", "integer_padding_wraps",
                                  "trunc_hour", "trunc_week_wide"])
def test_null_keys_take_slot_zero_and_padding_is_neutral(db, case):
    """A NULL key is code 0 of its key; a padding row adds to no slot
    (the worker's group-row counts hold the masked-in rows alone)."""
    plan, ranges = db(case)
    cols, valids, mask = batch(plan, ranges, 3)
    gid = new_group_id(plan, jnp, cols, valids, mask)
    key = plan.bound.group_keys[0]
    first = plan.scan_columns.index(getattr(key, "operand", key).name)
    stride = plan.group_mode.strides[0]
    null_first = mask & ~valids[first]
    assert null_first.any() and (gid[null_first] // stride == 0).all()
    assert (gid[~mask] == 0).all()
    for xp in (np, jnp):
        worker = scan_agg.build_worker_fn(plan, xp)
        out = (jax.jit(worker) if xp is jnp else worker)(cols, valids, mask)
        rows = np.asarray(out[-1])
        assert rows.sum() == mask.sum()
        assert (rows == np.bincount(gid[mask], minlength=rows.size)).all()


@pytest.mark.parametrize("case", ["two_keys", "bigint_lo_above_int32",
                                  "trunc_hour"])
def test_id_under_vmap_is_each_riders_own(db, case):
    """megabatch's shape: the fused worker lifted over a query axis, the
    riders differing in ``$1``; each rider's states equal its own serial
    numpy run."""
    plan, ranges = db(case, where="WHERE v < $1")
    cols, valids, mask = batch(plan, ranges, 4)
    lims = np.array([-10 ** 11, 0, 5 * 10 ** 11, 10 ** 13])
    from citus_tpu.executor.executor import _empty_partials
    n = len(plan.scan_columns)
    axes = (None,) * n + (0,)
    fused = jax.jit(jax.vmap(scan_agg.build_fused_worker_fn(plan, jnp),
                             in_axes=(0, axes, axes, None)))
    acc = tuple(np.stack([p] * len(lims)) for p in _empty_partials(plan, np))
    got = fused(acc, cols + (lims,), valids + (np.ones(len(lims), bool),),
                mask)
    serial = scan_agg.build_worker_fn(plan, np)
    for q, lim in enumerate(lims):
        want = serial(cols + (np.int64(lim),), valids + (np.bool_(True),),
                      mask)
        for g, w in zip(got, want):
            assert_states_equal(np.asarray(g)[q], w)
        v = cols[plan.scan_columns.index("v")]
        assert want[-1].sum() == (mask & (v < lim)).sum()


@pytest.mark.parametrize("case", ["three_keys", "bigint_lo_below_int32",
                                  "trunc_week"])
def test_id_under_shard_map_sums_the_shards(db, case):
    """The mesh round (``sharded_partial_agg``) on four of the CPU
    devices: its states are the sum of the shards' numpy states."""
    from jax.sharding import Mesh
    from citus_tpu.executor.executor import _empty_partials
    from citus_tpu.parallel.mesh import SHARD_AXIS, sharded_partial_agg
    plan, ranges = db(case)
    n_dev = 4
    batches = [batch(plan, ranges, 10 + i) for i in range(n_dev)]
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (SHARD_AXIS,))
    run = sharded_partial_agg(scan_agg.build_worker_fn(plan, jnp),
                              scan_agg.combine_kinds(plan), mesh)
    stack = lambda i: tuple(np.stack(x) for x in zip(*(b[i] for b in batches)))
    got = run(tuple(jnp.asarray(p) for p in _empty_partials(plan, np)),
              stack(0), stack(1), np.stack([b[2] for b in batches]))
    serial = scan_agg.build_worker_fn(plan, np)
    want = [serial(*b) for b in batches]
    for i, g in enumerate(got):
        assert_states_equal(np.asarray(g), sum(w[i] for w in want))


# ---- what the lowered module holds -----------------------------------------


def lowered(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text()


def test_a_step_one_plan_over_int32_keys_lowers_without_64_bit_lanes(db):
    """Q1's shape: two dictionary keys.  The id is made in int32 alone —
    no 64-bit value, no division, no remainder — and the whole worker
    (decimal sums and products, int64) divides nowhere."""
    plan, ranges = db("two_keys", aggs="count(*), sum(v), sum(v * (1 - v))")
    cols, valids, mask = batch(plan, ranges, 5)
    fn = scan_agg.direct_group_id_fn(plan, jnp)
    keys_only = [i for i, c in enumerate(plan.scan_columns) if c != "v"]

    def ids(kcols, kvalids, mask):
        env = {plan.scan_columns[i]: (a, v)
               for i, a, v in zip(keys_only, kcols, kvalids)}
        return fn(env, mask)
    text = lowered(ids, tuple(cols[i] for i in keys_only),
                   tuple(valids[i] for i in keys_only), mask)
    assert "i64" not in text and "i32" in text
    assert "divide" not in text and "remainder" not in text
    whole = lowered(scan_agg.build_worker_fn(plan, jnp), cols, valids, mask)
    assert "i64" in whole                       # the sums stay int64
    assert "divide" not in whole and "remainder" not in whole


def test_a_bigint_key_narrows_before_anything_else(db):
    """A 64-bit key with ``step == 1``: one convert to int32, then int32
    alone (the low words give the code exactly)."""
    plan, ranges = db("bigint_lo_above_int32")
    cols, valids, mask = batch(plan, ranges, 6)
    fn = scan_agg.direct_group_id_fn(plan, jnp)
    k = plan.scan_columns.index("k")
    text = lowered(lambda kv, kvalid, mask: fn({"k": (kv, kvalid)}, mask),
                   cols[k], valids[k], mask)
    i64_ops = [ln for ln in text.splitlines()
               if "i64" in ln and "stablehlo." in ln]
    assert len(i64_ops) == 1 and "stablehlo.convert" in i64_ops[0], i64_ops
    assert "divide" not in text and "remainder" not in text


def test_the_hourly_key_keeps_the_float32_estimate(db, monkeypatch):
    plan, ranges = db("trunc_hour")
    seen = []
    real = scan_agg._floor_div_small_quotient

    def spy(xp, d, step, q_max):
        seen.append((xp.__name__, str(d.dtype), step, q_max))
        return real(xp, d, step, q_max)
    monkeypatch.setattr(scan_agg, "_floor_div_small_quotient", spy)
    cols, valids, mask = batch(plan, ranges, 7)
    for xp in (jnp, np):
        new_group_id(plan, xp, cols, valids, mask)
    assert seen == [("jax.numpy", "int64", HOUR, 181 * 24 + 1),
                    ("numpy", "int64", HOUR, 181 * 24 + 1)]


def test_the_estimate_is_exact_in_32_bit_lanes():
    """``_floor_div_small_quotient`` over int32 differences (a week of
    dates), at both edges of every bucket."""
    n = (1 << 17) - 1
    q = np.repeat(np.arange(n, dtype=np.int32), 3)
    d = q * np.int32(7) + np.tile(np.array([0, 3, 6], np.int32), n)
    for xp in (np, jnp):
        got = np.asarray(scan_agg._floor_div_small_quotient(xp, xp.asarray(d),
                                                            7, n))
        assert got.dtype == np.int32 and (got == q).all()


def test_a_group_table_past_int32_is_refused(db):
    import dataclasses
    plan, _ = db("text")
    wide = dataclasses.replace(plan, group_mode=dataclasses.replace(
        plan.group_mode, n_groups=1 << 31))
    with pytest.raises(AssertionError):
        scan_agg.direct_id_lanes(wide)


# ---- statements, counters and EXPLAIN ANALYZE -------------------------------

ROWS = 3000


def _every_columns():
    rng = np.random.default_rng(39)
    return {
        "id": np.arange(ROWS),
        "c": [str(w) for w in rng.choice(["ash", "elm", "oak", "yew"], ROWS)],
        "b": rng.random(ROWS) < 0.4,
        "d": rng.integers(9000, 9200, ROWS).astype(np.int32),
        "i": rng.integers(-300, 300, ROWS).astype(np.int32),
        "k": rng.integers(0, 500, ROWS) + (1 << 41),
        "ts": T0 + rng.integers(0, 20 * DAY, ROWS),
        "v": rng.integers(-10 ** 6, 10 ** 6, ROWS),
    }


@pytest.fixture(scope="module")
def every(tmp_path_factory):
    cl = ct.Cluster(str(tmp_path_factory.mktemp("every") / "db"))
    cl.execute("CREATE TABLE e (id bigint, c text, b boolean, d date, "
               "i integer, k bigint, ts timestamp, v bigint)")
    cl.execute("SELECT create_distributed_table('e', 'id', 4)")
    cols = _every_columns()
    cl.copy_from("e", columns=cols)
    # NULL keys beside the loaded rows
    cl.execute("INSERT INTO e (id, v) VALUES (900001, 5), (900002, 7)")
    yield cl, cols
    cl.close()


def _epoch_date(n):
    return datetime.date(1970, 1, 1) + datetime.timedelta(days=int(n))


def _ts(us):
    return datetime.datetime(1970, 1, 1) + datetime.timedelta(
        microseconds=int(us))


KEYS = {
    "c": ("c", lambda r: r["c"]),
    "b": ("b", lambda r: bool(r["b"])),
    "d": ("d", lambda r: _epoch_date(r["d"])),
    "i": ("i", lambda r: int(r["i"])),
    "k": ("k", lambda r: int(r["k"])),
    "day": ("date_trunc('day', d)", lambda r: _epoch_date(r["d"])),
    "week": ("date_trunc('week', d)",
             lambda r: _epoch_date((r["d"] + 3) // 7 * 7 - 3)),
    "hour": ("date_trunc('hour', ts)", lambda r: _ts(r["ts"] // HOUR * HOUR)),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("key", KEYS)
def test_grouped_statement_equals_the_reference_row_for_row(every, key,
                                                            backend):
    cl, cols = every
    sql, of = KEYS[key]
    want = collections.defaultdict(lambda: [0, 0])
    for j in range(ROWS):
        g = want[of({c: a[j] for c, a in cols.items()})]
        g[0] += 1
        g[1] += int(cols["v"][j])
    want[None] = [2, 12]
    cl.execute(f"SET citus.task_executor_backend = '{backend}'")
    try:
        r = cl.execute(f"SELECT {sql}, count(*), sum(v) FROM e GROUP BY 1")
    finally:
        cl.execute("SET citus.task_executor_backend = 'tpu'")
    assert r.explain["strategy"] == "direct"
    got = {row[0]: [row[1], int(row[2])] for row in r.rows}
    assert len(got) == len(r.rows) and got == dict(want)


@pytest.mark.parametrize("keys,line,narrow,total", [
    ("c, b", "id lanes: 2 of 2 keys 32-bit, 0 divisions", 2, 2),
    ("date_trunc('hour', ts)", "id lanes: 1 of 1 keys 32-bit, 1 divisions",
     1, 1),
    ("b, date_trunc('week', d), c",
     "id lanes: 3 of 3 keys 32-bit, 1 divisions", 3, 3),
])
def test_counters_and_the_direct_line_say_how_the_id_was_made(
        every, keys, line, narrow, total):
    cl, _ = every
    sql = (f"SELECT {keys}, count(*), sum(v), avg(v) FROM e "
           f"GROUP BY {keys}")
    before = cl.counters.snapshot()
    lines = [row[0] for row in cl.execute("EXPLAIN ANALYZE " + sql).rows]
    after = cl.counters.snapshot()
    direct = [ln for ln in lines if ln.strip().startswith("Direct:")]
    assert len(direct) == 1 and direct[0].endswith(line), lines
    assert after["direct_gid_keys"] - before.get("direct_gid_keys", 0) \
        == total
    assert after["direct_gid_keys_narrow"] \
        - before.get("direct_gid_keys_narrow", 0) == narrow
    pl = cl.execute(sql).explain["pipeline"]
    assert (pl["direct_gid_keys_narrow"], pl["direct_gid_keys"]) \
        == (narrow, total)


def test_the_counters_are_exported_with_a_description():
    from citus_tpu.observability.export import METRIC_HELP
    from citus_tpu.stats import StatCounters
    for name in ("direct_gid_keys", "direct_gid_keys_narrow"):
        assert StatCounters().snapshot()[name] == 0
        assert METRIC_HELP[name]


# ---- rows the statistics do not cover ---------------------------------------


@pytest.mark.parametrize("key,rows,new", [
    ("i", "(910001, 'oak', 9100, 999, 1), (910002, 'oak', 9100, -999, 1)",
     [999, -999]),
    ("d", "(910001, 'oak', 12000, 5, 1), (910002, 'oak', 100, 5, 1)",
     [_epoch_date(12000), _epoch_date(100)]),
    ("c", "(910001, 'a new word', 9100, 5, 1)", ["a new word"]),
])
def test_staged_rows_outside_the_statistics_get_their_own_groups(
        every, key, rows, new):
    """A transaction's own writes bump no version and sit in no footer:
    a plan whose group table was sized by the statistics is re-made (the
    guard that re-makes a plan with partial states proved away), and a
    key whose domain they bounded goes to the hash path."""
    cl, _ = every
    sql = f"SELECT {key}, count(*) FROM e GROUP BY {key}"
    base = dict(cl.execute(sql).rows)
    assert cl.execute(sql).explain["strategy"] == "direct"
    cl.execute("BEGIN")
    try:
        cl.execute(f"INSERT INTO e (id, c, d, i, v) VALUES {rows}")
        r = cl.execute(sql)
        got = dict(r.rows)
    finally:
        cl.execute("ROLLBACK")
    for g in new:
        assert got.pop(g) == 1
    moved = {k: n for k, n in got.items() if base.get(k) != n}
    # the other columns of the staged rows are NULL or existing values
    assert all(n == base.get(k, 0) + len(new) or n == base.get(k, 0) + 1
               for k, n in moved.items()), moved
    assert dict(cl.execute(sql).rows) == base
