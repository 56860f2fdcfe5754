"""The mesh scan loops keep their partial states on the devices.

A mesh round is ``run(acc, inputs) -> acc'`` (parallel/mesh.py
``sharded_partial_agg``): the replicated, donated accumulator stays on
the devices from round to round, and a query ends with one wait and one
fetch, as on one device.  Checked here on 4 of the harness's 8 virtual
CPU devices, for programs, spans, counters and answers — never a time.

The table has 10 shards, so a scan is 3 rounds of 4 batches with 2
empty filler batches in the last one: min / max must not see them.
"""

import decimal

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import executor as X
from citus_tpu.executor.device_cache import GLOBAL_CACHE
from citus_tpu.observability import trace as T

N_DEV, SHARDS, ROUNDS, ROWS = 4, 10, 3, 6000


def _columns():
    k = np.arange(ROWS)
    return {"k": k, "v": (k * 7919) % 1013 - 500, "g": k % 5,
            "d": ((k * 31) % 99991) / 100}


@pytest.fixture()
def cl(tmp_path, limit_devices):
    limit_devices(N_DEV)
    GLOBAL_CACHE.clear()
    c = ct.Cluster(str(tmp_path / "db"))
    c.execute("CREATE TABLE m (k bigint NOT NULL, v bigint, g int, "
              "d decimal(12,2))")
    c.execute(f"SELECT create_distributed_table('m', 'k', {SHARDS})")
    c.copy_from("m", columns=_columns())
    yield c
    c.close()
    GLOBAL_CACHE.clear()


@pytest.fixture()
def mesh_calls(monkeypatch):
    """Every call of the device scan loop: (cat, plan, settings, params,
    the partial states it returned)."""
    calls = []
    real = X._run_partials_jax

    def spy(cat, plan, settings, params, record):
        out = real(cat, plan, settings, params, record)
        calls.append((cat, plan, settings, params, out))
        return out

    monkeypatch.setattr(X, "_run_partials_jax", spy)
    return calls


def _reference(grouped, lim):
    c = _columns()
    keep = c["v"] < lim
    cents = np.round(c["d"] * 100).astype(np.int64)
    rows = []
    for g in (range(5) if grouped else [None]):
        m = keep if g is None else keep & (c["g"] == g)
        v, n = c["v"][m], int(m.sum())
        D = decimal.Decimal
        row = (n, int(v.sum()), int(v.min()), int(v.max()),
               D(int(cents[m].sum())) / 100,
               D(int(cents[m].min())) / 100, float(v.mean()))
        rows.append(row if g is None else (g,) + row)
    return rows


# every (grouped, with_params, mode) on the table whose statistics prove
# every overflow guard and NULL count away, and the grouped dollar case
# of each mode on a table they cannot: one more row whose ``v`` 6,002
# rows of would leave int64 (sum(v) keeps its float64 shadow) and one
# whose ``v`` is NULL (count(v) is not count(*)); ``v < 300`` keeps
# neither, so the answers stand
_FOLD_CASES = [(g, p, m, True) for g in (False, True) for p in (False, True)
               for m in ("resident", "streaming")] \
    + [(True, True, m, False) for m in ("resident", "streaming")]


@pytest.mark.parametrize(
    "grouped, with_params, mode, provable", _FOLD_CASES,
    ids=["-".join(("direct" if g else "scalar", "dollar" if p else "literal",
                   m) + (() if ok else ("unprovable",)))
         for g, p, m, ok in _FOLD_CASES])
def test_mesh_rounds_fold_on_the_devices(cl, mesh_calls, monkeypatch, mode,
                                         with_params, grouped, provable):
    lim = 300
    if not provable:
        cl.execute(f"INSERT INTO m VALUES (6000, {1 << 61}, 0, 1.00), "
                   "(6001, NULL, 0, 1.00)")
    sql = ("SELECT {k}count(*), sum(v), min(v), max(v), sum(d), min(d), avg(v) "
           "FROM m WHERE v < {lim}{g}").format(
        k="g, " if grouped else "", lim="$1" if with_params else lim,
        g=" GROUP BY g ORDER BY g" if grouped else "")
    params = [lim] if with_params else None
    if mode == "streaming":
        # the cache holds less than the working set: every query streams
        monkeypatch.setattr(GLOBAL_CACHE, "capacity", 1 << 10)
        cl.execute("SET citus.executor_prefetch_depth = 1")
    cl.execute(sql, params=params)                   # compiles; fills the cache
    cl.execute("SET citus.trace_sample_rate = 1.0")
    del mesh_calls[:]
    c0 = cl.counters.snapshot()
    r = cl.execute(sql, params=params)
    c1 = cl.counters.snapshot()

    want = _reference(grouped, lim)
    assert [row[:-1] for row in r.rows] == [row[:-1] for row in want]
    assert [float(row[-1]) for row in r.rows] == pytest.approx(
        [row[-1] for row in want], abs=1e-6)         # avg: six decimals

    # the device loop's partial states are the numpy arm's, state for state
    (cat, plan, settings, prm, got), = mesh_calls
    assert "mesh_run" in plan.runtime_cache
    oracle = X._run_partials_cpu(cat, plan, settings, prm)
    assert len(got) == len(oracle) == len(X.combine_kinds(plan))
    # count(*), sum / min / max of v, sum / min of d (+ the group rows):
    # no other state on the provable table; count(v), the shadow of
    # sum(v) and count(d)'s stand-in stay where nothing is proved of v
    assert plan.proved_away == ((2, 2) if provable else (1, 1))
    assert sum(a.dtype.kind == "f" for a in got) == (0 if provable else 1)
    for a, b in zip(got, oracle):
        assert isinstance(a, np.ndarray) and a.dtype == np.asarray(b).dtype
        if a.dtype.kind == "f":
            # the float64 shadow of a decimal sum (the overflow guard):
            # the order of a float sum is the loop's own
            assert np.allclose(a, b, rtol=1e-12, atol=0)
        else:
            assert np.array_equal(a, b)

    # one round a dispatch, folded on the devices; one wait-and-fetch
    assert c1["fused_dispatches"] - c0["fused_dispatches"] == ROUNDS
    assert r.explain["pipeline"]["fused_dispatches"] == ROUNDS
    tr = T.last_trace()
    rounds = tr.find_all("device_round")
    assert len(rounds) == ROUNDS
    assert all(s.attrs["resident"] is (mode == "resident") for s in rounds)
    assert [s.attrs["batches"] for s in rounds] == (
        [N_DEV] * ROUNDS if mode == "resident" else [4, 4, SHARDS - 8])
    slots = [s.attrs["slot"] for s in tr.find_all("dispatch")]
    assert slots == ["mesh_run"] * ROUNDS
    fetch, = tr.find_all("fetch")
    assert fetch.attrs["arrays"] == len(got)
    assert len(tr.find_all("init_acc")) == 1
    assert not tr.find_all("combine")
    ex = tr.find("execute")
    under = [s.name for s in tr.spans if s.parent_id == ex.span_id]
    tail = under[len(under) - under[::-1].index("device_round"):]
    assert tail[:2] == ["wait:device_round", "fetch"], under
    hits = c1["device_cache_hits"] - c0["device_cache_hits"]
    assert hits == (1 if mode == "resident" else 0)


def test_the_round_is_a_program_named_run_over_a_replicated_donated_state(
        cl, mesh_calls):
    cl.execute("SELECT count(*), min(v), max(d) FROM m")
    plan = mesh_calls[0][1]
    run, zero = plan.runtime_cache["mesh_run"], plan.runtime_cache["mesh_zero"]
    # the device trace is read by the module's name, jit_<function>
    assert run.__name__ == "run" and zero.__name__ == "zero_acc"
    acc = zero()
    empty = X._empty_partials(plan, np)
    assert len(acc) == len(empty) == len(X.combine_kinds(plan))
    for a, e in zip(acc, empty):
        assert a.sharding.is_fully_replicated
        assert len(a.sharding.device_set) == N_DEV
        assert a.dtype == e.dtype and np.array_equal(np.asarray(a), e)
    key = next(k for k in GLOBAL_CACHE._entries if k[-2:] == ("mesh", N_DEV))
    dcols, dvalids, dmask = GLOBAL_CACHE.get(key)[0]       # the first round
    out = run(acc, dcols, dvalids, dmask)
    assert all(o.sharding.is_fully_replicated for o in out)
    assert [(o.shape, o.dtype) for o in out] == [(e.shape, e.dtype)
                                                 for e in empty]
    assert int(out[0]) == int(np.asarray(dmask).sum())
    assert all(a.is_deleted() for a in acc)          # donated


def test_a_state_without_an_elementwise_fold_is_refused():
    from citus_tpu.parallel.mesh import default_mesh, sharded_partial_agg
    with pytest.raises(ValueError, match="cannot be folded"):
        sharded_partial_agg(lambda c, v, m: (), ["sum", "none"],
                            default_mesh(N_DEV))
