"""End-to-end correctness vs two oracles.

1. sqlite3 executes the same SQL over the same rows (SQL semantics
   oracle) — the analog of the reference's pg_regress golden outputs.
2. The numpy cpu backend must produce *identical* rows to the jax
   backend (mesh path included) — the bit-exactness invariant that makes
   the psum combine trustworthy.
"""

import decimal
import sqlite3

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.config import ExecutorSettings, settings_override

N = 5000


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    cl = ct.Cluster(str(tmp_path_factory.mktemp("db")))
    cl.execute("""CREATE TABLE events (
        id bigint NOT NULL, device bigint, kind text, qty decimal(12,2),
        score double, d date)""")
    cl.execute("SELECT create_distributed_table('events', 'id', 4)")
    rng = np.random.default_rng(11)
    kinds = ["click", "view", "buy", None]
    rows = []
    for i in range(N):
        rows.append((
            i,
            int(rng.integers(0, 50)) if rng.random() > 0.05 else None,
            kinds[int(rng.integers(0, 4))],
            round(float(rng.integers(0, 10000)) / 100, 2) if rng.random() > 0.1 else None,
            float(np.round(rng.random() * 100, 6)),
            f"202{int(rng.integers(0,4))}-0{int(rng.integers(1,10))}-1{int(rng.integers(0,10))}",
        ))
    cl.copy_from("events", rows=rows)

    sq = sqlite3.connect(":memory:")
    sq.execute("CREATE TABLE events (id INTEGER, device INTEGER, kind TEXT, qty REAL, score REAL, d TEXT)")
    sq.executemany("INSERT INTO events VALUES (?,?,?,?,?,?)", rows)
    return cl, sq


QUERIES = [
    "SELECT count(*) FROM events",
    "SELECT count(device), count(kind), count(qty) FROM events",
    "SELECT sum(qty), min(qty), max(qty) FROM events",
    "SELECT avg(score) FROM events",
    "SELECT kind, count(*) FROM events GROUP BY kind ORDER BY kind NULLS LAST",
    "SELECT kind, sum(qty), avg(qty), min(score), max(score) FROM events GROUP BY kind ORDER BY kind NULLS LAST",
    "SELECT device, count(*) FROM events WHERE device IS NOT NULL GROUP BY device ORDER BY device LIMIT 10",
    "SELECT count(*) FROM events WHERE qty > 50 AND score < 40",
    "SELECT count(*) FROM events WHERE kind = 'click' OR kind = 'buy'",
    "SELECT count(*) FROM events WHERE d >= '2021-01-01' AND d < '2023-01-01'",
    "SELECT kind, count(*) FROM events WHERE device BETWEEN 10 AND 20 GROUP BY kind ORDER BY kind NULLS LAST",
    "SELECT device, kind, count(*), sum(qty) FROM events GROUP BY device, kind "
    "HAVING count(*) > 10 ORDER BY device NULLS LAST, kind NULLS LAST LIMIT 25",
    "SELECT count(*) FROM events WHERE kind IN ('click', 'view')",
    "SELECT count(*) FROM events WHERE kind LIKE 'c%'",
    "SELECT id, qty FROM events WHERE id = 777",
    "SELECT sum(qty * 2 + 1) FROM events WHERE device = 7",
    "SELECT count(*) FROM events WHERE NOT (score > 50)",
    "SELECT min(d), max(d) FROM events",
    "SELECT device FROM events WHERE id < 20 ORDER BY device NULLS FIRST LIMIT 5",
    "SELECT DISTINCT kind FROM events ORDER BY kind NULLS LAST",
]


def canon(rows):
    out = []
    for r in rows:
        row = []
        for v in r:
            if isinstance(v, decimal.Decimal):
                row.append(round(float(v), 4))
            elif isinstance(v, float):
                row.append(round(v, 4))
            elif hasattr(v, "isoformat"):
                row.append(v.isoformat())
            else:
                row.append(v)
        out.append(tuple(row))
    return out


@pytest.mark.parametrize("sql", QUERIES)
def test_vs_sqlite(loaded, sql):
    cl, sq = loaded
    ours = canon(cl.execute(sql).rows)
    theirs = canon(sq.execute(sql).fetchall())
    if "ORDER BY" not in sql:
        ours, theirs = sorted(ours, key=repr), sorted(theirs, key=repr)
    assert ours == pytest.approx(theirs, rel=1e-6, abs=1e-4) if _all_numeric(ours) \
        else ours == theirs


def _all_numeric(rows):
    return all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for r in rows for v in r if v is not None)


@pytest.mark.parametrize("sql", QUERIES)
def test_jax_vs_cpu_identical(loaded, sql):
    cl, sq = loaded
    jax_rows = cl.execute(sql).rows
    with settings_override(executor=ExecutorSettings(task_executor_backend="cpu")):
        cpu_rows = cl.execute(sql).rows
    assert jax_rows == cpu_rows


def test_mesh_path_is_used(loaded):
    """The 8-device CPU mesh must actually take the shard_map branch."""
    import jax
    assert len(jax.devices()) == 8
    cl, _ = loaded
    from citus_tpu.planner import parse_sql
    from citus_tpu.planner.bind import bind_select
    from citus_tpu.planner.physical import plan_select
    bound = bind_select(cl.catalog, parse_sql("SELECT kind, count(*) FROM events GROUP BY kind")[0])
    plan = plan_select(cl.catalog, bound)
    from citus_tpu.executor.executor import _iter_padded_batches
    from citus_tpu.executor.pipeline import PipelineStats
    batches = list(_iter_padded_batches(cl.catalog, plan, cl.settings,
                                        PipelineStats()))
    assert len(batches) > 1  # multi-batch -> shard_map + psum path


def test_order_by_non_output_column(loaded):
    cl, sq = loaded
    sql = "SELECT kind FROM events WHERE id < 30 ORDER BY score LIMIT 10"
    ours = cl.execute(sql)
    theirs = sq.execute(sql).fetchall()
    assert ours.columns == ["kind"]
    assert ours.rows == [tuple(r) for r in theirs]
    # grouped query ordering by an aggregate not in the output
    sql2 = "SELECT kind FROM events GROUP BY kind ORDER BY count(*) DESC, kind NULLS LAST"
    ours2 = cl.execute(sql2).rows
    theirs2 = sq.execute(
        "SELECT kind FROM events GROUP BY kind "
        "ORDER BY count(*) DESC, kind IS NULL, kind").fetchall()
    assert ours2 == [tuple(r) for r in theirs2]


def test_coalesce_nullif_group_ordinals(loaded):
    cl, sq = loaded
    for sql in [
        "SELECT count(*) FROM events WHERE coalesce(device, 99) = 99",
        "SELECT coalesce(kind, 'none'), count(*) FROM events GROUP BY 1",
        "SELECT count(*) FROM events WHERE nullif(device, 7) IS NULL",
        "SELECT device, count(*) FROM events GROUP BY 1 ORDER BY 2 DESC LIMIT 5",
    ]:
        ours = sorted(canon(cl.execute(sql).rows), key=repr)
        theirs = sorted(canon(sq.execute(sql).fetchall()), key=repr)
        assert ours == theirs, sql


def test_having_without_group_by(loaded):
    cl, sq = loaded
    import sqlite3 as _sq3
    for sql, thresh in [
        ("SELECT count(*) FROM events HAVING count(*) > 10", 10),
        ("SELECT count(*) FROM events HAVING count(*) > 1000000", 1000000),
    ]:
        ours = cl.execute(sql).rows
        if _sq3.sqlite_version_info >= (3, 39):
            theirs = sq.execute(sql).fetchall()
        else:  # old sqlite rejects bare HAVING: apply the filter by hand
            n = sq.execute("SELECT count(*) FROM events").fetchall()[0][0]
            theirs = [(n,)] if n > thresh else []
        assert ours == [tuple(r) for r in theirs], sql


def test_boolean_column_end_to_end(tmp_path_factory):
    import citus_tpu as ct
    cl = ct.Cluster(str(tmp_path_factory.mktemp("booldb")), n_nodes=2)
    cl.execute("CREATE TABLE b (k bigint NOT NULL, flag boolean, v bigint)")
    cl.execute("SELECT create_distributed_table('b', 'k', 2)")
    cl.execute("INSERT INTO b VALUES (1, true, 10), (2, false, 20), (3, true, 30), (4, NULL, 40)")
    assert cl.execute("SELECT count(*) FROM b WHERE flag").rows == [(2,)]
    assert cl.execute("SELECT count(*) FROM b WHERE NOT flag").rows == [(1,)]
    rows = sorted(cl.execute("SELECT flag, sum(v) FROM b GROUP BY flag").rows, key=repr)
    assert rows == sorted([(True, 40), (False, 20), (None, 40)], key=repr)
