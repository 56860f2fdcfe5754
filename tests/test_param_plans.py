"""Parameterized-plan cache with deferred pruning — VERDICT item #7.

Reference: prepared statements keep a generic plan with shard pruning
deferred to bind time (Job->deferredPruning, fast_path_router_planner.c
README:307-311).  Here: one bind+plan per SQL text, $N values arrive as
0-d traced arrays, so the jitted kernel compiles once and every later
execution is zero replan / zero recompile."""

import numpy as np
import pytest

import citus_tpu as ct


@pytest.fixture()
def db(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE t (k bigint NOT NULL, v bigint, s text, d decimal(8,2))")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    cl.copy_from("t", columns={
        "k": np.arange(2000), "v": np.arange(2000) % 50,
        "s": [f"n{i % 5}" for i in range(2000)],
        "d": np.arange(2000) / 4})
    yield cl
    cl.close()


def _delta(c0, c1, key):
    return c1.get(key, 0) - c0.get(key, 0)


def test_router_query_zero_replan(db):
    cl = db
    c0 = cl.counters.snapshot()
    for kv in (5, 77, 400, 913, 1999):
        r = cl.execute("SELECT v, s FROM t WHERE k = $1", params=[kv])
        assert r.rows == [(kv % 50, f"n{kv % 5}")]
        assert r.explain["router"] is True
        assert r.explain["shards"] == 1
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "plan_cache_misses") == 1
    assert _delta(c0, c1, "plan_cache_hits") == 4
    assert _delta(c0, c1, "router_queries") == 5


def test_jit_kernel_reused_across_values(db):
    cl = db
    sql = "SELECT count(*), sum(v) FROM t WHERE v < $1"
    for lim in (10, 25, 40, 49):
        cl.execute(sql, params=[lim])
    plan = cl._plan_cache[("$param", sql)][1]
    # one plan object; its jitted worker was traced exactly once even
    # though four different parameter values executed
    assert "mesh_run" in plan.runtime_cache or "jit_fused" in plan.runtime_cache
    jitted = plan.runtime_cache.get("jit_fused")
    if jitted is not None and hasattr(jitted, "_cache_size"):
        assert jitted._cache_size() == 1


def test_param_results_match_literal_path(db):
    cl = db
    v = np.arange(2000) % 50
    for lim in (7, 33):
        a = cl.execute("SELECT s, count(*), sum(v) FROM t WHERE v < $1 "
                       "GROUP BY s ORDER BY s", params=[lim])
        b = cl.execute(f"SELECT s, count(*), sum(v) FROM t WHERE v < {lim} "
                       "GROUP BY s ORDER BY s")
        assert a.rows == b.rows


def test_text_and_null_and_decimal_params(db):
    cl = db
    assert cl.execute("SELECT count(*) FROM t WHERE s = $1",
                      params=["n3"]).rows == [(400,)]
    assert cl.execute("SELECT count(*) FROM t WHERE s = $1",
                      params=["missing"]).rows == [(0,)]
    assert cl.execute("SELECT count(*) FROM t WHERE k = $1",
                      params=[None]).rows == [(0,)]
    assert cl.execute("SELECT count(*) FROM t WHERE d <= $1",
                      params=[2.5]).rows == [(11,)]


def test_params_in_select_list_and_between(db):
    cl = db
    r = cl.execute("SELECT v + $2 FROM t WHERE k = $1", params=[3, 100])
    assert r.rows == [(103,)]
    r = cl.execute("SELECT count(*) FROM t WHERE v BETWEEN $1 AND $2",
                   params=[10, 19])
    assert r.rows == [(400,)]
    r = cl.execute("SELECT count(*) FROM t WHERE v IN ($1, $2, $3)",
                   params=[1, 2, 3])
    assert r.rows == [(120,)]


def test_plan_invalidated_on_ddl(db):
    cl = db
    sql = "SELECT count(*) FROM t WHERE v < $1"
    cl.execute(sql, params=[5])
    cl.execute("ALTER TABLE t ADD COLUMN extra bigint")
    c0 = cl.counters.snapshot()
    r = cl.execute(sql, params=[5])
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "plan_cache_misses") == 1  # replanned after DDL
    assert r.rows == [(200,)]


def test_fallback_for_subquery_params(db):
    """Shapes outside the generic-plan subset still execute correctly
    through literal substitution."""
    cl = db
    r = cl.execute(
        "SELECT count(*) FROM t WHERE v < (SELECT max(v) FROM t WHERE k < $1)",
        params=[100])
    lit = cl.execute(
        "SELECT count(*) FROM t WHERE v < (SELECT max(v) FROM t WHERE k < 100)")
    assert r.rows == lit.rows


def test_missing_params_error(db):
    cl = db
    from citus_tpu.errors import AnalysisError
    with pytest.raises(AnalysisError):
        cl.execute("SELECT count(*) FROM t WHERE v < $2", params=[1])


# ---- query-family kernel cache (auto-parameterization) --------------------


def test_literal_variants_share_kernels(db):
    """Two textually different ad-hoc queries that differ only in their
    comparison literals hoist to one structural fingerprint: the second
    variant reuses the first's compiled kernels — zero new XLA compiles
    — and still answers correctly (sqlite oracle)."""
    import sqlite3
    cl = db
    cl.execute("SELECT s, count(*), sum(v) FROM t WHERE v < 100 "
               "GROUP BY s ORDER BY s")
    c0 = cl.counters.snapshot()
    r = cl.execute("SELECT s, count(*), sum(v) FROM t WHERE v < 200 "
                   "GROUP BY s ORDER BY s")
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "kernel_cache_hits") >= 1
    assert _delta(c0, c1, "kernel_cache_misses") == 0
    assert _delta(c0, c1, "kernel_compile_ms") == 0  # compile amortized
    sq = sqlite3.connect(":memory:")
    sq.execute("CREATE TABLE t (k INTEGER, v INTEGER, s TEXT)")
    sq.executemany("INSERT INTO t VALUES (?,?,?)",
                   [(i, i % 50, f"n{i % 5}") for i in range(2000)])
    exp = sq.execute("SELECT s, count(*), sum(v) FROM t WHERE v < 200 "
                     "GROUP BY s ORDER BY s").fetchall()
    assert [tuple(row) for row in r.rows] == [tuple(e) for e in exp]


def test_kernels_shared_between_adhoc_and_param_paths(db):
    """The $N prepared path and the auto-parameterized literal path
    produce the same generic structure, so they share kernels too."""
    cl = db
    cl.execute("SELECT count(*), sum(v) FROM t WHERE v < $1", params=[10])
    c0 = cl.counters.snapshot()
    r = cl.execute("SELECT count(*), sum(v) FROM t WHERE v < 25")
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "kernel_cache_misses") == 0
    assert _delta(c0, c1, "kernel_compile_ms") == 0
    assert r.rows == [(1000, sum(i % 50 for i in range(2000)
                                 if i % 50 < 25))]


def test_plan_cache_mode_guc(db):
    """citus.plan_cache_mode: force_custom bypasses the plan cache for
    ad-hoc SQL (replan every execution, no counter traffic); auto
    caches by text."""
    cl = db
    cl.execute("SET citus.plan_cache_mode = force_custom")
    r = cl.execute("SHOW citus.plan_cache_mode")
    assert r.rows == [("force_custom",)]
    c0 = cl.counters.snapshot()
    a = cl.execute("SELECT count(*) FROM t WHERE v < 10")
    b = cl.execute("SELECT count(*) FROM t WHERE v < 10")
    c1 = cl.counters.snapshot()
    assert a.rows == b.rows == [(400,)]
    assert _delta(c0, c1, "plan_cache_hits") == 0
    assert _delta(c0, c1, "plan_cache_misses") == 0
    cl.execute("SET citus.plan_cache_mode = auto")
    c0 = cl.counters.snapshot()
    cl.execute("SELECT count(*) FROM t WHERE v < 11")
    cl.execute("SELECT count(*) FROM t WHERE v < 11")
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "plan_cache_misses") == 1
    assert _delta(c0, c1, "plan_cache_hits") == 1
    from citus_tpu.errors import CatalogError
    with pytest.raises(CatalogError):
        cl.execute("SET citus.plan_cache_mode = bogus")


def test_kernel_cache_gucs(db):
    cl = db
    assert cl.execute("SHOW citus.kernel_cache_size").rows == [("512",)]
    cl.execute("SET citus.kernel_cache_size = 256")
    assert cl.execute("SHOW citus.kernel_cache_size").rows == [("256",)]
    from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
    assert GLOBAL_KERNELS.capacity == 256
    cl.execute("SET citus.kernel_cache_size = 512")
    # the on-disk compile cache is placed from outside the process
    # (JAX_COMPILATION_CACHE_DIR) or at the checkout's fixed default —
    # there is no setting for it
    from citus_tpu.errors import CatalogError
    with pytest.raises(CatalogError):
        cl.execute("SHOW citus.jit_cache_dir")


def test_explain_analyze_shows_cache_lines(db):
    cl = db
    r1 = cl.execute("EXPLAIN ANALYZE SELECT count(*) FROM t WHERE v < 30")
    txt1 = "\n".join(row[0] for row in r1.rows)
    assert "Plan Cache: miss" in txt1, txt1
    assert "Device Cache:" in txt1, txt1
    r2 = cl.execute("EXPLAIN ANALYZE SELECT count(*) FROM t WHERE v < 30")
    txt2 = "\n".join(row[0] for row in r2.rows)
    assert "Plan Cache: hit" in txt2, txt2


# ---- surgical invalidation ------------------------------------------------


def test_ddl_on_other_table_keeps_plan(db):
    """DDL against table B must not evict A's cached plans: the DDL
    epoch bump is disarmed by the object-state token compare and the
    entry re-arms in place."""
    cl = db
    cl.execute("CREATE TABLE other (x bigint, y bigint)")
    sql = "SELECT count(*) FROM t WHERE v < $1"
    assert cl.execute(sql, params=[5]).rows == [(200,)]
    cl.execute("ALTER TABLE other ADD COLUMN z bigint")
    cl.execute("CREATE INDEX other_x ON other (x)")
    c0 = cl.counters.snapshot()
    r = cl.execute(sql, params=[7])
    c1 = cl.counters.snapshot()
    assert r.rows == [(280,)]
    assert _delta(c0, c1, "plan_cache_hits") == 1
    assert _delta(c0, c1, "plan_cache_misses") == 0
    assert _delta(c0, c1, "plan_cache_invalidations") == 0


def test_ddl_on_own_table_still_invalidates(db):
    """ALTER / TRUNCATE against the referenced table itself must keep
    invalidating — surgical, not absent."""
    cl = db
    sql = "SELECT count(*) FROM t WHERE v < $1"
    cl.execute(sql, params=[5])
    cl.execute("ALTER TABLE t ADD COLUMN e1 bigint")
    c0 = cl.counters.snapshot()
    cl.execute(sql, params=[5])
    c1 = cl.counters.snapshot()
    assert _delta(c0, c1, "plan_cache_misses") == 1
    cl.execute("TRUNCATE t")
    c0 = cl.counters.snapshot()
    r = cl.execute(sql, params=[5])
    c1 = cl.counters.snapshot()
    assert r.rows == [(0,)]
    assert _delta(c0, c1, "plan_cache_misses") == 1


def test_ingest_flip_invalidates_cached_plan(db):
    """The ingest-flip window: an INSERT bumps the table version, so a
    plan cached before the flip is detected stale at its next lookup
    and replanned — results include the new row."""
    cl = db
    sql = "SELECT count(*) FROM t WHERE v < $1"
    assert cl.execute(sql, params=[1]).rows == [(40,)]
    cl.execute("INSERT INTO t VALUES (5000, 0, 'n0', 1.0)")
    c0 = cl.counters.snapshot()
    r = cl.execute(sql, params=[1])
    c1 = cl.counters.snapshot()
    assert r.rows == [(41,)]
    assert _delta(c0, c1, "plan_cache_misses") == 1
    assert _delta(c0, c1, "plan_cache_hits") == 0
