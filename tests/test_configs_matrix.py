"""Arbitrary-configs matrix.

Reference: src/test/regress/citus_tests/arbitrary_configs/ — one common
SQL suite executed across cluster shapes (shard counts, executors,
metadata modes).  Here the battery runs over shard counts x executor
backends x compression codecs x chunk sizes x planner toggles and must
produce identical results everywhere.
"""

import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.config import (
    ColumnarSettings, ExecutorSettings, PlannerSettings, Settings,
)

CONFIGS = [
    {"shards": 1, "codec": "zstd", "chunk": 8192, "backend": "tpu"},
    {"shards": 4, "codec": "zstd", "chunk": 8192, "backend": "tpu"},
    {"shards": 8, "codec": "lz4", "chunk": 1024, "backend": "tpu"},
    {"shards": 3, "codec": "zlib", "chunk": 512, "backend": "tpu"},
    {"shards": 4, "codec": "none", "chunk": 8192, "backend": "cpu"},
    {"shards": 16, "codec": "zstd", "chunk": 256, "backend": "cpu"},
    # repartition joins disabled: non-colocated joins take the pull path
    {"shards": 4, "codec": "zstd", "chunk": 8192, "backend": "tpu",
     "repartition": False},
    # tiny hash-agg table: heavy spill through the exact host path
    {"shards": 4, "codec": "zstd", "chunk": 2048, "backend": "tpu",
     "hash_slots": 16},
    # tiny direct-gid budget: GROUP BY forced onto the hash path
    {"shards": 4, "codec": "zstd", "chunk": 8192, "backend": "tpu",
     "direct_limit": 4},
]

BATTERY = [
    "SELECT count(*), sum(v), min(v), max(v) FROM t",
    "SELECT g, count(*), avg(v) FROM t GROUP BY g ORDER BY g",
    "SELECT count(*) FROM t WHERE v BETWEEN 100 AND 400",
    "SELECT s, sum(v) FROM t WHERE g < 5 GROUP BY s ORDER BY s",
    "SELECT k, v FROM t WHERE k = 37",
    "SELECT count(*) FROM t a JOIN t b ON a.k = b.k",
    # non-colocated equi-join (repartition or pull depending on config)
    "SELECT count(*), sum(a.v) FROM t a JOIN t b ON a.v = b.g",
    "SELECT g, stddev(v) FROM t GROUP BY g ORDER BY g",
    "SELECT v % 97 AS m, count(*) FROM t GROUP BY v % 97 ORDER BY m LIMIT 5",
]


def run_battery(tmp_path, cfg):
    st = Settings(
        columnar=ColumnarSettings(
            chunk_group_row_limit=cfg["chunk"],
            stripe_row_limit=cfg["chunk"] * 4,
            compression=cfg["codec"]),
        executor=ExecutorSettings(task_executor_backend=cfg["backend"]),
        planner=PlannerSettings(
            enable_repartition_joins=cfg.get("repartition", True),
            hash_agg_slots=cfg.get("hash_slots", 0),   # 0: derived
            direct_gid_limit=cfg.get("direct_limit", 0)),   # 0: auto
    )
    tag = "_".join(str(v) for v in cfg.values())
    cl = ct.Cluster(str(tmp_path / f"db_{tag}"), n_nodes=2, settings=st)
    cl.execute("CREATE TABLE t (k bigint NOT NULL, g bigint, v bigint, s text)")
    cl.execute(f"SELECT create_distributed_table('t', 'k', {cfg['shards']})")
    rng = np.random.default_rng(99)
    n = 5000
    cl.copy_from("t", columns={
        "k": np.arange(n, dtype=np.int64),
        "g": rng.integers(0, 10, n),
        "v": rng.integers(0, 500, n),
        "s": np.array(["x", "y", "z"])[rng.integers(0, 3, n)].tolist()})
    out = []
    for sql in BATTERY:
        r = cl.execute(sql)
        if sql == BATTERY[1]:
            # 11 slots: direct unless the setting bounds it under them
            want = "hash_host" if "direct_limit" in cfg else "direct"
            assert r.explain["strategy"] == want, (cfg, r.explain)
        out.append(sorted(r.rows, key=repr))
    cl.close()
    return out


def _canon(rows):
    import decimal
    out = []
    for r in rows:
        out.append(tuple(round(float(v), 6)
                         if isinstance(v, (float, decimal.Decimal)) else v
                         for v in r))
    return out


def test_configs_matrix(tmp_path):
    baseline = run_battery(tmp_path, CONFIGS[0])
    for cfg in CONFIGS[1:]:
        got = run_battery(tmp_path, cfg)
        for sql, want, have in zip(BATTERY, baseline, got):
            assert _canon(want) == _canon(have), (cfg, sql)
