"""Bring-up contracts: the executor never computes on the CPU unasked,
the compile cache can be placed from outside the process, and
chip_smoke.py refuses to pass without a chip."""

import contextlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import citus_tpu as ct
from citus_tpu.errors import ExecutionError
from citus_tpu.executor import kernel_cache
from citus_tpu.parallel.mesh import executor_devices

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------- device guard


@contextlib.contextmanager
def platform_left_to_jax():
    """The state of a process that named no platform and got the CPU:
    the backends are up (conftest pinned them), the request is gone."""
    jax.config.update("jax_platforms", "")
    try:
        yield
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_guard_passes_when_cpu_was_named():
    assert len(executor_devices()) == 8


def test_guard_raises_when_jax_fell_back_to_cpu(tmp_path):
    with platform_left_to_jax():
        with pytest.raises(ExecutionError, match="no accelerator found"):
            executor_devices()
        with pytest.raises(ExecutionError, match="no accelerator found"):
            ct.Cluster(str(tmp_path / "db"))


def test_queries_raise_instead_of_computing_on_the_cpu(tmp_path):
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE t (k bigint, v bigint)")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    cl.copy_from("t", columns={"k": np.arange(100), "v": np.arange(100)})
    cl.execute("CREATE TABLE u (k bigint, w bigint)")
    cl.execute("SELECT create_distributed_table('u', 'w', 4)")
    cl.copy_from("u", columns={"k": np.arange(100), "w": np.arange(100)})
    agg = "SELECT count(*), sum(v) FROM t"
    join = "SELECT count(*) FROM t JOIN u ON t.k = u.k"
    want = cl.execute(agg).rows
    with platform_left_to_jax():
        with pytest.raises(ExecutionError, match="no accelerator found"):
            cl.execute(agg)
        with pytest.raises(ExecutionError, match="no accelerator found"):
            cl.execute(join)
        # the numpy arm needs no device
        cl.execute("SET citus.task_executor_backend = 'cpu'")
        assert cl.execute(agg).rows == want


def test_every_kernel_slot_asks_the_guard(tmp_path):
    """A Cluster opened with an explicit node count skips the guard at
    open; its projection (``jit_filter``) and float-key hash GROUP BY
    (``jit_hash_fused``) never meet the scan loop's check.  Every
    kernel compiles through ``jit_compile``, which asks.  The
    column names are this test's own, so no other test has left a
    compiled kernel of the same fingerprint behind."""
    cl = ct.Cluster(str(tmp_path / "db"), n_nodes=2)
    cl.execute("CREATE TABLE g (guard_k bigint, guard_x double precision)")
    cl.execute("SELECT create_distributed_table('g', 'guard_k', 4)")
    cl.copy_from("g", columns={"guard_k": np.arange(100),
                               "guard_x": np.arange(100) / 2.0})
    with platform_left_to_jax():
        for sql in ("SELECT guard_k, guard_x FROM g WHERE guard_k = 5",
                    "SELECT guard_x, count(*) FROM g GROUP BY guard_x",
                    "SELECT count(*), sum(guard_x) FROM g"):
            with pytest.raises(ExecutionError, match="no accelerator found"):
                cl.execute(sql)
        with pytest.raises(ExecutionError, match="no accelerator found"):
            kernel_cache.jit_compile(lambda a: a + 1)
    assert cl.execute("SELECT guard_x, count(*) FROM g GROUP BY guard_x "
                      "ORDER BY 1 LIMIT 2").rows == [(0.0, 1), (0.5, 1)]


# --------------------------------------------------------- compile cache


def test_default_cache_dir_is_a_fixed_path_of_the_checkout(tmp_path,
                                                           monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert kernel_cache.DEFAULT_PERSISTENT_CACHE_DIR == str(REPO / ".jax_cache")
    ct.Cluster(str(tmp_path / "db"))
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_env_placed_cache_is_never_set_in_code(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    updates = []
    real = jax.config.update
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: (updates.append(k), real(k, v))[1])
    kernel_cache.configure_persistent_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert "jax_persistent_cache_min_compile_time_secs" in updates


_CACHE_CHILD = """
import sys, numpy as np
import citus_tpu as ct
cl = ct.Cluster(sys.argv[1])
if not cl.catalog.has_table("t"):
    cl.execute("CREATE TABLE t (k bigint, v decimal(10,2))")
    cl.execute("SELECT create_distributed_table('t', 'k', 4)")
    cl.copy_from("t", columns={"k": np.arange(5000), "v": np.arange(5000) / 7})
print(cl.execute("SELECT k % 5, count(*), sum(v) FROM t WHERE v > 3 "
                 "GROUP BY k % 5 ORDER BY 1").rows)
cl.close()
"""


def test_second_process_adds_no_cache_file(tmp_path):
    cache = tmp_path / "cc"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(REPO))
    outs = []
    for _ in range(2):
        p = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD, str(tmp_path / "db")],
            env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append((p.stdout, sorted(os.listdir(cache))))
    (rows1, files1), (rows2, files2) = outs
    assert rows1 == rows2
    assert files1, "the first process persisted nothing"
    assert files2 == files1, "the second process compiled something anew"


# ------------------------------------------------------------ chip_smoke


def _smoke(args, env_extra, tmp_path):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"),
         "--out", str(tmp_path / "out"), *args],
        env=env, capture_output=True, text=True, timeout=600)


def test_smoke_fails_without_a_chip(tmp_path):
    """JAX_PLATFORMS=cpu in the environment is not the rehearsal flag."""
    p = _smoke(["--rows", "2000"], {"JAX_PLATFORMS": "cpu"}, tmp_path)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"ok"' not in p.stdout


@pytest.mark.parametrize("n_dev", [1, 8])
def test_smoke_rehearsal_passes_every_leg(tmp_path, n_dev):
    flags = f"--xla_force_host_platform_device_count={n_dev}"
    p = _smoke(["--rows", "12000", "--rehearse-on-cpu"],
               {"XLA_FLAGS": flags}, tmp_path)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": n_dev}}
    report = json.loads((tmp_path / "out" / "report.json").read_text())
    assert report["rehearsal"] is True and report["platform"] == "cpu"
    legs = {leg["leg"].split()[0]: leg for leg in report["legs"]}
    single = {"1", "2", "3", "3b", "4", "5", "5b", "5c", "5d", "5e", "5f",
              "6", "7a", "7c", "7f", "7g"}
    assert set(legs) == (single | {"7b", "7d", "7e"} if n_dev > 1
                         else single)
    assert all(leg["ok"] for leg in legs.values())
    scan_slot = "mesh_run" if n_dev > 1 else "jit_fused"
    assert legs["1"]["kernel_slot"] == scan_slot
    # leg 5 keeps the hash table by its partials (a min), sized here by
    # the 12,000 rows; the same keys over a count and a sum follow the
    # rule (fewer rows than twice the 303,240 slots: the hash table) and,
    # in 5e, the operator's bound
    assert legs["5"]["kernel_slot"] == "jit_hash_fused"
    assert (legs["5"]["hash_slots"], legs["5"]["hash_slots_from"]) \
        == (16_384, "row count")
    assert legs["5d"]["kernel_slot"] == legs["5e"]["kernel_slot"] \
        == "jit_hash_fused"
    assert "the plan's route" in legs["5d"]["leg"] \
        and "direct_gid_limit 65536" in legs["5e"]["leg"]
    # Q18's block: a hash table on every device, apart on the
    # distribution column
    assert legs["5f"]["kernel_slot"] == "jit_hash_fused"
    assert legs["5f"]["hash_tables"] == n_dev and legs["5f"]["groups"] >= 1
    assert legs["5f"]["hash_disjoint_on"] == \
        ("l_orderkey" if n_dev > 1 else None)
    assert legs["6"]["kernel_slot"] == "jit_filter"
    # the single-hash repartition join runs on the device too: every
    # order exchanged once between several devices, none on one
    assert legs["7a"]["shuffle"] == \
        ("all_to_all:device" if n_dev > 1 else "local")
    assert legs["7a"]["kernel_slot"] == \
        ("jit_join_exchange" if n_dev > 1 else "jit_join_probe")
    assert legs["7a"]["rows_built"] == 3_000
    assert legs["7a"]["rows_exchanged"] == (3_000 if n_dev > 1 else 0)
    assert "explain Join: on device, probe l;" in p.stdout \
        and f"exchange: o on o_orderkey, {3_000 if n_dev > 1 else 0} " \
            f"rows" in p.stdout
    # the colocated join runs on the device whatever the device count
    assert legs["7c"]["kernel_slot"] == "jit_join_probe"
    assert legs["7c"]["rows_probed"] >= 12_000 > legs["7c"]["rows_out"] > 0
    # Q5's shape: a join graph with a cycle, its filter decided on the
    # device over the rows with a partner in both children
    assert legs["7g"]["kernel_slot"] == "jit_join_probe"
    assert legs["7g"]["rows_matched"] == legs["7g"]["cycle_rows_in"] \
        > legs["7g"]["cycle_rows_kept"] > 0


# ------------------------------------- what the chip refused or overflowed


def test_decimal_product_scale_is_the_sum_of_scales(tmp_path):
    """Q1's sum_charge came out at scale 8 (operands were aligned before
    the multiply) and tripped the int64 overflow guard past ~6 M rows."""
    import decimal
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE p (k bigint, price decimal(12,2), "
               "disc decimal(12,2), tax decimal(12,2), w decimal(10,3))")
    cl.execute("SELECT create_distributed_table('p', 'k', 2)")
    D = decimal.Decimal
    cl.copy_from("p", rows=[(1, D("104999.99"), D("0.10"), D("0.08"),
                             D("1.125"))])
    r = cl.execute("SELECT price * (1 - disc) * (1 + tax), price * w, "
                   "price * 2, sum(price * (1 - disc) * (1 + tax)) FROM p "
                   "GROUP BY 1, 2, 3")
    charge, pw, p2, total = r.rows[0]
    assert str(charge) == "102059.990280" and total == charge
    assert str(pw) == "118124.98875" and str(p2) == "209999.98"


def test_float_bits_need_no_64_bit_float_bitcast():
    """The TPU holds float64 as a float32 pair; XLA refuses
    bitcast-convert on it.  The hash lanes must be the same on numpy
    and under jit, one lane per distinct value, one lane for all NaNs."""
    import jax.numpy as jnp
    from citus_tpu.planner.aggregates import float_bits, hll_value_bits
    rng = np.random.default_rng(3)
    v = np.concatenate([rng.normal(size=4096) * 1e6, rng.random(4096),
                        [0.0, 0.5, 0.1, 1e30, -1e-30, np.inf, -np.inf]])
    host = float_bits(np, v)
    dev = np.asarray(jax.jit(lambda a: float_bits(jnp, a))(v))
    assert host.dtype == np.uint64 and np.array_equal(host, dev)
    assert len(np.unique(host)) == len(np.unique(v))
    nans = np.array([np.nan, -np.nan,
                     np.frombuffer(np.uint64(0x7FF0000000000001).tobytes(),
                                   np.float64)[0]])
    assert len(np.unique(float_bits(np, nans))) == 1
    jaxpr = str(jax.make_jaxpr(lambda a: hll_value_bits(jnp, a))(v))
    for line in jaxpr.splitlines():
        if "bitcast_convert_type" in line:
            assert "f64" not in line, line
    # float32 columns take the same lanes as their float64 widening
    f32 = v[:100].astype(np.float32)
    assert np.array_equal(float_bits(np, f32),
                          float_bits(np, f32.astype(np.float64)))


def test_float_lanes_end_at_float32_range(tmp_path):
    """The stated limit of ``float_bits``: doubles outside float32's
    range share lanes.  GROUP BY stays exact (stored keys verify each
    claim); approx_count_distinct counts each class once."""
    cl = ct.Cluster(str(tmp_path / "db"))
    cl.execute("CREATE TABLE f (k bigint, x double precision)")
    cl.execute("SELECT create_distributed_table('f', 'k', 2)")
    x = np.array([1e300, 2e300, 1e300, 1e-40, 3e-40, 0.0, 1.5] * 10)
    cl.copy_from("f", columns={"k": np.arange(len(x)), "x": x})
    for backend in ("tpu", "cpu"):
        cl.execute(f"SET citus.task_executor_backend = '{backend}'")
        assert cl.execute("SELECT x, count(*) FROM f GROUP BY x ORDER BY x"
                          ).rows == [(0.0, 10), (1e-40, 10), (3e-40, 10),
                                     (1.5, 10), (1e300, 20), (2e300, 10)]
        # {0, 1e-40, 3e-40}, {1.5}, {1e300, 2e300}
        assert cl.execute("SELECT approx_count_distinct(x) FROM f"
                          ).rows == [(3,)]
