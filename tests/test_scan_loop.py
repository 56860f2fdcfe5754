"""The one device scan loop (executor/scan_loop.py ``drive``).

Every device scan — the aggregate paths on one device and on the mesh,
the hash path, the megabatched lifts — is one driver over a placement, a
step and a first state.  Checked here for what the benchmark's sources
read (``benchmarks/tests`` are not tier-1): the partial states against
the numpy arm's, the span sequence under ``execute``, the dispatch
counter and the cache entry, for every placement x source — never a
time.  The mesh is 4 of the harness's 8 virtual CPU devices.
"""

import inspect
import os
import re
import threading

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.executor import executor as X
from citus_tpu.executor import scan_loop as L
from citus_tpu.executor.device_cache import GLOBAL_CACHE, SHARED_TENANT
from citus_tpu.observability import trace as T
from citus_tpu.testing.faults import FAULTS

N_DEV, SHARDS, ROWS = 4, 10, 6000
Q = "SELECT g, count(*), sum(v), min(v), max(d) FROM m WHERE v < $1 GROUP BY g"


def _cluster(tmp_path, shards, rows):
    GLOBAL_CACHE.clear()
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE m (k bigint NOT NULL, v bigint, g int, "
               "d decimal(12,2), w bigint)")
    cl.execute(f"SELECT create_distributed_table('m', 'k', {shards})")
    if rows:
        k = np.arange(rows)
        cl.copy_from("m", columns={"k": k, "v": (k * 7919) % 1013 - 500,
                                   "g": k % 5, "d": ((k * 31) % 99991) / 100,
                                   "w": k * 1_000_003_017})
    return cl


@pytest.fixture()
def scans(monkeypatch):
    """Every call of the device scan: (cat, plan, settings, params, the
    partial states it returned)."""
    calls = []
    real = X._run_partials_jax

    def spy(cat, plan, settings, params, record):
        out = real(cat, plan, settings, params, record)
        calls.append((cat, plan, settings, params, out))
        return out

    monkeypatch.setattr(X, "_run_partials_jax", spy)
    return calls


#: source -> (shards, rows, queries run before the measured one)
SOURCES = {"cold": (SHARDS, ROWS, 0), "warm": (SHARDS, ROWS, 1),
           "past_capacity": (SHARDS, ROWS, 1), "single_batch": (1, 600, 0),
           "empty": (SHARDS, 0, 0)}


@pytest.mark.parametrize("source", list(SOURCES))
@pytest.mark.parametrize("n_dev", [1, N_DEV], ids=["one_device", "mesh"])
def test_one_loop_for_every_placement_and_source(
        tmp_path, monkeypatch, limit_devices, scans, n_dev, source):
    limit_devices(n_dev)
    shards, rows, before = SOURCES[source]
    cl = _cluster(tmp_path, shards, rows)
    try:
        if source == "past_capacity":
            monkeypatch.setattr(GLOBAL_CACHE, "capacity", 1 << 10)
        for _ in range(before):
            cl.execute(Q, params=[300])
        cl.execute("SET citus.trace_sample_rate = 1.0")
        del scans[:]
        keys0 = set(GLOBAL_CACHE._entries)
        c0 = cl.counters.snapshot()
        r = cl.execute(Q, params=[300])
        c1 = cl.counters.snapshot()
        tr = T.last_trace()

        # which placement, how many rounds, what a round holds
        on_mesh = n_dev > 1 and shards > 1 and rows > 0
        n_batches = shards if rows else 0
        n_rounds = -(-n_batches // n_dev) if on_mesh else n_batches
        resident = source == "warm"
        put = source in ("cold", "single_batch")

        # the partial states are the numpy arm's, state for state
        (cat, plan, settings, prm, got), = scans
        oracle = X._run_partials_cpu(cat, plan, settings, prm)
        assert len(got) == len(oracle) == len(X.combine_kinds(plan))
        for a, b in zip(got, oracle):
            assert isinstance(a, np.ndarray) and a.dtype == np.asarray(b).dtype
            if a.dtype.kind == "f":        # the order of a float sum
                assert np.allclose(a, b, rtol=1e-12, atol=0)
            else:
                assert np.array_equal(a, b)
        cl.execute("SET citus.task_executor_backend = 'cpu'")
        assert sorted(r.rows) == sorted(cl.execute(Q, params=[300]).rows)

        # one dispatch a round, booked once
        assert c1["fused_dispatches"] - c0["fused_dispatches"] == n_rounds
        assert r.explain["pipeline"]["fused_dispatches"] == n_rounds
        assert ("h2d_bytes" in r.explain["pipeline"]) is not resident
        hits = c1["device_cache_hits"] - c0["device_cache_hits"]
        assert hits == (1 if resident else 0)

        # under execute, on the caller's thread: the first state, the
        # rounds, (the cache entry,) one wait and one fetch
        ex = tr.find("execute")
        loop = {"init_acc", "device_round", "wait:device_round", "cache_put",
                "fetch", "combine", "bind_params"}
        under = [s.name for s in tr.spans
                 if s.parent_id == ex.span_id and s.name in loop]
        assert under == (
            (["bind_params"] if on_mesh else []) + ["init_acc"]
            + ["device_round"] * n_rounds
            + (["wait:device_round", "cache_put"] if put else [])
            + ["wait:device_round", "fetch"]), under
        assert tr.find("fetch").attrs["arrays"] == len(got)
        # the cache key reads the snapshot generation (files): made once,
        # however many keys are looked up
        lookups = {s.span_id for s in tr.find_all("cache_lookup")}
        assert len(lookups) == (2 if n_dev > 1 else 1)
        assert len([s for s in tr.find_all("snapshot_check")
                    if s.parent_id in lookups]) == 1

        # a round's children and attributes
        depth = L._prefetch_depth(cl.settings)
        kids = {}
        for s in tr.spans:
            kids.setdefault(s.parent_id, []).append(s.name)
        rounds = tr.find_all("device_round")
        for i, s in enumerate(rounds, 1):
            want = [] if resident else (["stack"] if on_mesh else []) + ["h2d"]
            if want and plan.narrow_lanes:
                # v and d ride at 32 bits: the lane convert follows the
                # put (tests/test_scan_lanes.py)
                want.append("narrow")
            want.append("dispatch")
            if source == "past_capacity" and i % depth == 0:
                want.append("wait:device_round")    # the window's sync
            assert kids[s.span_id] == want, (i, kids[s.span_id])
            assert s.attrs["resident"] is resident and s.attrs["bytes"] > 0
        slots = [s.attrs["slot"] for s in tr.find_all("dispatch")]
        assert slots == ["mesh_run" if on_mesh else "jit_fused"] * n_rounds
        if on_mesh:
            full, rest = divmod(n_batches, n_dev)
            assert [s.attrs["batches"] for s in rounds] == (
                [n_dev] * n_rounds if resident
                else [n_dev] * full + [rest] * (rest > 0))
        else:
            assert sorted(s.attrs["shard_index"] for s in rounds) == \
                list(range(n_batches))
            assert sum(s.attrs["rows"] for s in rounds) == rows

        # the cache entry: put once when the stream fitted, under the
        # placement's key
        new = set(GLOBAL_CACHE._entries) - keys0
        assert len(new) == (1 if put else 0)
        for key in new:
            assert (key[-2:] == ("mesh", n_dev)) is on_mesh
            assert len(GLOBAL_CACHE._entries[key][0]) == n_rounds
    finally:
        cl.close()
        GLOBAL_CACHE.clear()


@pytest.mark.parametrize("depth", [1, 3])
def test_the_hash_step_drains_at_every_window_and_once_at_the_end(
        tmp_path, monkeypatch, limit_devices, depth):
    """The hash scan is the same driver with a sync hook: every row is in
    the fetched table or was drained, exactly, at a sync point."""
    limit_devices(1)
    GLOBAL_CACHE.clear()
    cl = ct.Cluster(str(tmp_path / "dbh"))
    cl.execute("CREATE TABLE h (k bigint NOT NULL, g bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('h', 'k', 8)")
    # 1,500 groups whose key domain is far wider than direct_gid_limit
    g = np.random.default_rng(5).choice(10 ** 12, 1500, replace=False)
    k = np.arange(ROWS)
    cl.copy_from("h", columns={"k": k, "g": np.tile(g, 4), "v": k % 7})
    tables = []
    real = X._run_hash_device

    def spy(*a, **kw):
        tables.append(real(*a, **kw))
        return tables[-1]

    monkeypatch.setattr(X, "_run_hash_device", spy)
    try:
        cl.execute("SET citus.hash_agg_slots = 64")     # most rows spill
        cl.execute(f"SET citus.executor_prefetch_depth = {depth}")
        cl.execute(f"SET citus.max_tasks_in_flight = {depth}")
        cl.execute("SET citus.trace_sample_rate = 1.0")
        c0 = cl.counters.snapshot()
        r = cl.execute("SELECT g, sum(v), count(*) FROM h GROUP BY g")
        c1 = cl.counters.snapshot()
        sums = np.zeros(1500, np.int64)
        np.add.at(sums, k % 1500, k % 7)
        assert sorted(r.rows) == sorted(zip(g.tolist(), sums.tolist(),
                                            [4] * 1500))
        (_keys, _partials, h_rows) = tables[0].state
        assert len(tables) == 1 and tables[0].tables == 1
        spilled = c1["hash_spill_rows"] - c0["hash_spill_rows"]
        assert spilled == ROWS - int(h_rows.sum()) > ROWS // 2
        assert r.explain["pipeline"]["hash_spilled_rows"] == spilled
        assert c1["hash_fused_dispatches"] - c0["hash_fused_dispatches"] == 8
        assert c1["fused_dispatches"] == c0["fused_dispatches"]
        assert r.explain["pipeline"]["fused_dispatches"] == 8
        tr = T.last_trace()
        agg = tr.find("host_agg")
        names = [s.name for s in tr.spans if s.parent_id == agg.span_id
                 and s.name in ("device_round", "spill_drain")]
        # a drain beside every ``depth``-th round, and one for the rest
        want = []
        for i in range(1, 9):
            want.append("device_round")
            if i % depth == 0:
                want.append("spill_drain")
        if 8 % depth:
            want.append("spill_drain")
        assert names == want
        drains = tr.find_all("spill_drain")
        assert sum(s.attrs["batches"] for s in drains) == 8
        assert sum(s.attrs["rows"] for s in drains) == spilled
        assert {s.attrs["slot"] for s in tr.find_all("dispatch")} == \
            {"jit_hash_fused"}
    finally:
        cl.close()
        GLOBAL_CACHE.clear()


@pytest.mark.parametrize("kind", ["agg", "hash"])
def test_the_megabatched_lifts_ride_the_same_driver(tmp_path, limit_devices,
                                                    kind):
    """A coalesced group is a step and a first state like any other: it
    keeps its fault point (on a replay too), its family-wide cache entry
    in the shared tenant bucket and its per-rider spill handling."""
    limit_devices(1)
    cl = _cluster(tmp_path, 4, 2000)
    try:
        if kind == "hash":
            cl.execute("SET citus.hash_agg_slots = 64")
            # w's domain is far wider than direct_gid_limit
            sql = "SELECT w, sum(v) FROM m WHERE v < {} GROUP BY w"
        else:
            sql = "SELECT count(*), sum(v) FROM m WHERE v < {}"
        lims = (100, 300, 450)
        base = [sorted(cl.execute(sql.format(v)).rows) for v in lims]
        GLOBAL_CACHE.clear()
        cl.execute("SET citus.megabatch_window_ms = 2000")
        cl.execute("SET citus.megabatch_max_size = 3")

        def fan_out():
            out, bar = {}, threading.Barrier(len(lims))

            def run(i, v):
                bar.wait()
                try:
                    out[i] = sorted(cl.execute(sql.format(v)).rows)
                except Exception as e:  # noqa: BLE001 - asserted below
                    out[i] = e
            ts = [threading.Thread(target=run, args=(i, v))
                  for i, v in enumerate(lims)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(120)
            return [out.get(i) for i in range(len(lims))]

        counter = "hash_fused_dispatches" if kind == "hash" \
            else "fused_dispatches"
        for attempt in ("streamed", "replayed"):
            c0 = cl.counters.snapshot()
            FAULTS.arm("device_round", delay_s=0.0, match="m")
            try:
                assert fan_out() == base, attempt
                hits = FAULTS._arms["device_round"].hits
            finally:
                FAULTS.disarm()
            c1 = cl.counters.snapshot()
            assert c1["megabatch_queries"] - c0["megabatch_queries"] == 3
            assert c1["megabatch_fallbacks"] == c0["megabatch_fallbacks"]
            batches = c1["megabatch_batches"] - c0["megabatch_batches"]
            # a round per shard batch and dispatched group, each one
            # through the fault point
            assert c1[counter] - c0[counter] == 4 * batches == hits
            if kind == "hash":
                assert c1["hash_spill_rows"] > c0["hash_spill_rows"]
        view = GLOBAL_CACHE.memory_view()
        if kind == "agg":
            assert [(t, n) for t, n, _b in view["by_owner"]] == \
                [("m", SHARED_TENANT)]
            assert c1["device_cache_hits"] > c0["device_cache_hits"]
        else:
            assert view["entries"] == 0        # the hash scan always streams
    finally:
        FAULTS.disarm()
        cl.close()
        GLOBAL_CACHE.clear()


def test_the_driver_takes_any_placement_step_and_state():
    """The driver alone, over a placement and a step made here: nothing
    in it names a caller.  Two "batches" of integers fold into a sum; the
    hook sees every round's aux once."""
    import jax.numpy as jnp
    from citus_tpu.config import Settings
    from citus_tpu.executor.pipeline import PipelineStats

    class Plain(L.Lanes):       # (what a placement does about narrow lanes)
        round_size = 2

        def __init__(self):
            self.booked = []

        def put(self, plan, members):
            return jnp.asarray(members), 8 * len(members)

        def args(self, inputs):
            return ((inputs,), (), None)

        def describe(self, members, inputs):
            return {"bytes": 8}

        def book(self, members, inputs, nbytes, round_s, dispatch_s):
            self.booked.append((members, nbytes))

    plan = type("P", (), {"narrow_lanes": (), "wide_lanes": 0,
                          "bound": type("B", (), {
        "table": type("Tb", (), {"name": "plain"})})})()
    seen = []
    step = L.Step(lambda s, cols, valids, mask: (s + cols[0].sum(),
                                                 cols[0].max()),
                  "plain", "fused_dispatches")
    pstats, placement = PipelineStats(), Plain()
    out = L.drive(plan, Settings(), placement, step, jnp.int64(0), pstats,
                  stream=iter([1, 2, 3, 4, 5]),
                  on_sync=lambda pending: seen.extend(
                      (m, int(a)) for m, a in pending))
    assert int(out) == 15 and pstats.rounds == 3
    assert seen == [([1, 2], 2), ([3, 4], 4), ([5], 5)]
    assert placement.booked == [([1, 2], 16), ([3, 4], 16), ([5], 8)]
    assert pstats.h2d_bytes == 40
    assert pstats.figures["fused_dispatches"] == 3
    names = set(inspect.signature(L.drive).parameters)
    assert not [n for n in names if re.search("mesh|hash|mega", n)]


def test_one_loop_in_the_package():
    """The literal of the one-device H2D copy occurs once, and the old
    bodies are gone."""
    root = os.path.dirname(os.path.abspath(ct.__file__))
    text = ""
    for d, _dirs, files in os.walk(os.path.join(root, "executor")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    text += fh.read()
    assert text.count("ShardBatch(tuple(jax.device_put(") == 1
    for gone in ("_run_mesh_round", "_mesh_rounds", "_book_mesh_round",
                 "_stream_hash_batches"):
        assert gone not in text and not hasattr(X, gone)
