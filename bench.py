#!/usr/bin/env python
"""Benchmark: TPC-H Q1 + Q6 + repartition join on columnar lineitem.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
The headline metric stays Q1 rows scanned/sec/chip; "extra" carries Q6
(BASELINE config 1) and the repartition-join rate (config 5, exercising
parallel/shuffle.py's build_repartition_join).

Baseline (BASELINE.md): the reference's columnar scan + GROUP BY SUM runs
75 M rows in 16 s on its microbench box = 4.6875 M rows/s.  vs_baseline
is our warm Q1 rows/s divided by that.  The join compares against the
reference's ~10 M rows/s repartition INSERT..SELECT throughput
(distributed/README.md:1761).

Data persists in .bench_data/ across runs (ingest is skipped when the
table already exists at the right scale).

BENCH_SWEEP=1 additionally measures Q1 at 2x and 4x the configured row
count (the throughput-vs-size curve past the HBM batch cache; the
streaming pipeline should degrade smoothly, not collapse) and reports it
under "sweep".
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_HERE = os.path.dirname(os.path.abspath(__file__))

BASELINE_ROWS_PER_SEC = 75_000_000 / 16.0
# 24M rows ≈ TPC-H SF4 lineitem: the working set fits the 6 GB HBM
# batch cache
N_ROWS = int(os.environ.get("BENCH_ROWS", 24_000_000))
SHARDS = 8
# BENCH_PLATFORM=cpu is the explicit CPU rehearsal: it pins JAX to the
# host backend and the record says platform=cpu.  Unset, the run needs
# an accelerator and fails without one.
PLATFORM = os.environ.get("BENCH_PLATFORM")

Q1 = """SELECT l_returnflag, l_linestatus,
  sum(l_quantity) AS sum_qty,
  sum(l_extendedprice) AS sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
  avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
  avg(l_discount) AS avg_disc, count(*) AS count_order
FROM lineitem WHERE l_shipdate <= date '1998-12-01' - interval '90' day
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""

Q6 = """SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= date '1994-01-01'
  AND l_shipdate < date '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""

# config 5: equi-join on the NON-distribution key of the probe side —
# forces the repartition (all_to_all) path; orders_b is distributed on
# o_custkey, lineitem on l_orderkey
QJOIN = """SELECT count(*), sum(l.l_quantity)
FROM lineitem l JOIN orders_b o ON l.l_orderkey = o.o_orderkey
WHERE o.o_flag = 'H'"""

#: reference repartition INSERT..SELECT throughput (README:1761)
JOIN_BASELINE_ROWS_PER_SEC = 10_000_000.0

#: device-side bytes Q1 processes per row: scanned columns' device
#: dtypes (l_returnflag/l_linestatus/l_shipdate int32; l_quantity/
#: l_extendedprice/l_discount/l_tax int64 scaled decimals) plus one
#: validity byte per column — the numerator of the roofline fraction
Q1_BYTES_PER_ROW = 3 * 4 + 4 * 8 + 7

#: HBM peak bandwidth by device kind (GB/s; public chip specs) — the
#: denominator of the roofline fraction BASELINE.md's north star asks
#: for.  A scan→filter→partial-agg pipeline is bandwidth-bound, so
#: bytes-scanned/s over HBM peak is the scan analog of MFU.
HBM_PEAK_GBPS = {
    "v2": 700.0, "v3": 900.0, "v4": 1228.0,
    "v5e": 819.0, "v5 lite": 819.0, "v5p": 2765.0,
    "v6e": 1640.0, "v6 lite": 1640.0,
}


def _hbm_peak_for(device_kind: str) -> float:
    dk = device_kind.lower()
    for key in sorted(HBM_PEAK_GBPS, key=len, reverse=True):
        if key in dk:
            return HBM_PEAK_GBPS[key] * 1e9
    raise KeyError(f"no HBM peak on record for device kind "
                   f"{device_kind!r}; add it to HBM_PEAK_GBPS with its "
                   "source")


def bench_concurrency(cl, extra: dict) -> None:
    """N parallel clients through the admission pool (VERDICT #9): the
    citus.max_shared_pool_size machinery has to be shown under load.
    Mixed Q1/Q6 stream; reports queries/s and latency percentiles."""
    import threading
    n_clients = int(os.environ.get("BENCH_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_QUERIES_PER_CLIENT", "6"))
    lat: list = []
    errs: list = []
    mu = threading.Lock()

    def worker(ci: int) -> None:
        for j in range(per_client):
            q = Q6 if (ci + j) % 2 else Q1
            t0 = time.perf_counter()
            try:
                cl.execute(q)
            except Exception as e:  # recorded, not fatal to the bench
                with mu:
                    errs.append(str(e))
                return
            with mu:
                lat.append(time.perf_counter() - t0)

    cl.execute(Q1)
    cl.execute(Q6)  # both plans warm/compiled before the clock starts
    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    if not lat:
        extra["concurrency_error"] = errs[:1]
        return
    extra["concurrency"] = {
        "clients": n_clients,
        "queries": len(lat),
        "queries_per_sec": round(len(lat) / wall, 2),
        "p50_ms": round(lat[len(lat) // 2] * 1000, 1),
        "p99_ms": round(lat[min(len(lat) - 1,
                                int(len(lat) * 0.99))] * 1000, 1),
        "max_shared_pool_size": cl.settings.executor.max_shared_pool_size,
        "errors": len(errs),
    }


def bench_plan_cache(cl, extra: dict) -> None:
    """Query-family compile amortization (executor/kernel_cache.py +
    planner/auto_param.py): cold compile cost, warm plan-cache hit
    latency, and the kernel hit rate across a Q6 literal family —
    textually distinct SQL that hoists to one structural fingerprint."""
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
    GLOBAL_KERNELS.clear()
    cl._plan_cache.invalidate_all()
    c0 = GLOBAL_COUNTERS.snapshot()
    t0 = time.perf_counter()
    cl.execute(Q6)
    cold_s = time.perf_counter() - t0
    c1 = GLOBAL_COUNTERS.snapshot()
    t0 = time.perf_counter()
    cl.execute(Q6)
    warm_s = time.perf_counter() - t0
    c2 = GLOBAL_COUNTERS.snapshot()
    variants = [Q6.replace("< 24", f"< {24 + i}") for i in (1, 2, 3, 4)]
    v0 = GLOBAL_COUNTERS.snapshot()
    t0 = time.perf_counter()
    for q in variants:
        cl.execute(q)
    fam_s = (time.perf_counter() - t0) / len(variants)
    v1 = GLOBAL_COUNTERS.snapshot()
    hits = v1["kernel_cache_hits"] - v0["kernel_cache_hits"]
    misses = v1["kernel_cache_misses"] - v0["kernel_cache_misses"]
    extra["plan_cache"] = {
        "cold_ms": round(cold_s * 1000, 1),
        "cold_compile_ms":
            c1["kernel_compile_ms"] - c0["kernel_compile_ms"],
        "warm_hit_ms": round(warm_s * 1000, 1),
        "warm_plan_cache_hit": bool(
            c2["plan_cache_hits"] - c1["plan_cache_hits"]),
        "literal_variant_avg_ms": round(fam_s * 1000, 1),
        "literal_variant_kernel_hit_rate": round(
            hits / max(1, hits + misses), 3),
        "literal_variant_compile_ms":
            v1["kernel_compile_ms"] - v0["kernel_compile_ms"],
    }


def bench_megabatch(cl, extra: dict) -> None:
    """Same-family query coalescing (executor/megabatch.py): K clients
    hammering ONE router point-lookup family, serial (window=0) vs
    coalesced (window>0) QPS, plus the dispatch occupancy histogram —
    the high-QPS lever ROADMAP open item 1 names."""
    import threading
    from citus_tpu.executor.megabatch import GLOBAL_MEGABATCH
    n_clients = int(os.environ.get("BENCH_MB_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_MB_QUERIES", "8"))
    window_ms = float(os.environ.get("BENCH_MB_WINDOW_MS", "5"))
    sql = ("SELECT sum(l_quantity), count(*) FROM lineitem "
           "WHERE l_orderkey = 4242")

    def storm() -> float:
        bar = threading.Barrier(n_clients)

        def run() -> None:
            bar.wait()
            for _ in range(per_client):
                cl.execute(sql)
        ts = [threading.Thread(target=run) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return time.perf_counter() - t0

    cl.execute(sql)  # serial plan + kernels warm
    cl.execute(f"SET citus.megabatch_window_ms = {window_ms}")
    cl.execute(f"SET citus.megabatch_max_size = {n_clients}")
    cl.execute(sql)  # batched: kernels warm
    cl.execute("SET citus.megabatch_window_ms = 0")
    serial_wall = storm()
    st0 = GLOBAL_MEGABATCH.stats()
    cl.execute(f"SET citus.megabatch_window_ms = {window_ms}")
    batched_wall = storm()
    st1 = GLOBAL_MEGABATCH.stats()
    cl.execute("SET citus.megabatch_window_ms = 0")
    n = n_clients * per_client
    batches = st1["batches"] - st0["batches"]
    queries = st1["queries"] - st0["queries"]
    hist = {k: st1["occupancy_hist"].get(k, 0)
            - st0["occupancy_hist"].get(k, 0)
            for k in st1["occupancy_hist"]}
    extra["megabatch"] = {
        "clients": n_clients,
        "queries": n,
        "window_ms": window_ms,
        "serial_qps": round(n / serial_wall, 1),
        "batched_qps": round(n / batched_wall, 1),
        "speedup": round(serial_wall / batched_wall, 2),
        "avg_occupancy": round(queries / max(1, batches), 2),
        "occupancy_hist": {k: v for k, v in sorted(hist.items()) if v},
    }


def bench_scan_fuse(cl, extra: dict) -> None:
    """Fused single-dispatch hot loop A/B (ops/scan_agg.py
    build_fused_worker_fn + the executor's donated-accumulator loop):
    uncached Q1 through the fused path vs the staged host worker
    (task_executor_backend = 'cpu') — rows/s, dispatch counts, and
    pipeline stall counters per arm — plus a uuid vs text
    high-cardinality ingest A/B: the uuid lane encoding keeps the
    dictionary flat at zero entries while text grows linearly."""
    import uuid as _uuid
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    from citus_tpu.executor.device_cache import GLOBAL_CACHE

    def measure():
        GLOBAL_CACHE.clear()
        c0 = GLOBAL_COUNTERS.snapshot()
        t0 = time.perf_counter()
        cl.execute(Q1)
        wall = time.perf_counter() - t0
        c1 = GLOBAL_COUNTERS.snapshot()
        return wall, {k: c1[k] - c0[k] for k in (
            "fused_dispatches", "pipeline_host_stalls",
            "pipeline_device_stalls")}

    cl.execute(Q1)  # fused arm: plan + kernels warm
    fused_wall, fused_c = measure()
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    cl.execute(Q1)  # staged arm warm
    staged_wall, staged_c = measure()
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    fuse = {
        "fused_rows_per_sec": round(N_ROWS / fused_wall, 1),
        "staged_cpu_rows_per_sec": round(N_ROWS / staged_wall, 1),
        "speedup_vs_staged": round(staged_wall / fused_wall, 2),
        "fused_dispatches": fused_c["fused_dispatches"],
        "fused_host_stalls": fused_c["pipeline_host_stalls"],
        "fused_device_stalls": fused_c["pipeline_device_stalls"],
        "staged_fused_dispatches": staged_c["fused_dispatches"],
        "staged_host_stalls": staged_c["pipeline_host_stalls"],
    }
    n = int(os.environ.get("BENCH_FUSE_UUIDS", "300000"))
    words = [str(_uuid.UUID(int=(i * 2654435761) % (1 << 128)))
             for i in range(n)]
    cl.execute("DROP TABLE IF EXISTS fuse_uuid_ab")
    cl.execute("DROP TABLE IF EXISTS fuse_text_ab")
    cl.execute("CREATE TABLE fuse_uuid_ab (k bigint NOT NULL, u uuid)")
    cl.execute("SELECT create_distributed_table('fuse_uuid_ab', 'k', 4)")
    t0 = time.perf_counter()
    cl.copy_from("fuse_uuid_ab", columns={"k": np.arange(n), "u": words})
    uuid_wall = time.perf_counter() - t0
    cl.execute("CREATE TABLE fuse_text_ab (k bigint NOT NULL, u text)")
    cl.execute("SELECT create_distributed_table('fuse_text_ab', 'k', 4)")
    t0 = time.perf_counter()
    cl.copy_from("fuse_text_ab", columns={"k": np.arange(n), "u": words})
    text_wall = time.perf_counter() - t0
    cat = cl.catalog
    cat._ensure_dict("fuse_text_ab", "u")
    fuse["uuid_ingest"] = {
        "distinct_values": n,
        "uuid_rows_per_sec": round(n / uuid_wall, 1),
        "text_rows_per_sec": round(n / text_wall, 1),
        "uuid_dict_entries": len(cat._dicts.get(("fuse_uuid_ab", "u"), ())),
        "text_dict_entries": len(cat._dicts[("fuse_text_ab", "u")]),
    }
    extra["scan_fuse"] = fuse


def bench_hash_agg(cl, extra: dict) -> None:
    """Streaming fused hash aggregation A/B (ops/hash_agg.py
    build_fused_hash_worker + the executor's donated HBM-resident
    table): high-cardinality GROUP BY through the fused device path vs
    the staged host accumulator (task_executor_backend = 'cpu') —
    rows/s plus the dispatch/spill counters — then a 2-host loopback
    push-vs-pull A/B: shipped hash-table partials (TASK_VERSION 3
    "hash" tasks) against the pull path's raw-placement bytes."""
    import shutil
    import tempfile

    import citus_tpu as ct
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    from citus_tpu.executor.executor import GLOBAL_COUNTERS

    # l_orderkey spans ~N_ROWS/4 distinct values: unprovable domain ->
    # the hash_host group mode, the path under test
    sql = ("SELECT l_orderkey, count(*), sum(l_quantity) "
           "FROM lineitem GROUP BY l_orderkey")

    def measure():
        GLOBAL_CACHE.clear()
        c0 = GLOBAL_COUNTERS.snapshot()
        t0 = time.perf_counter()
        cl.execute(sql)
        wall = time.perf_counter() - t0
        c1 = GLOBAL_COUNTERS.snapshot()
        return wall, {k: c1[k] - c0[k] for k in (
            "hash_fused_dispatches", "hash_spill_rows")}

    cl.execute("SET citus.hash_agg_slots = auto")
    cl.execute(sql)  # fused arm: plan + kernels warm
    fused_wall, fused_c = measure()
    cl.execute("SET citus.task_executor_backend = 'cpu'")
    cl.execute(sql)  # staged arm warm
    staged_wall, _ = measure()
    cl.execute("SET citus.task_executor_backend = 'tpu'")
    cl.execute("SET citus.hash_agg_slots = 8192")
    hagg = {
        "fused_rows_per_sec": round(N_ROWS / fused_wall, 1),
        "staged_cpu_rows_per_sec": round(N_ROWS / staged_wall, 1),
        # acceptance bar: >= 2x the staged host accumulator
        "speedup_vs_staged": round(staged_wall / fused_wall, 2),
        "hash_fused_dispatches": fused_c["hash_fused_dispatches"],
        "hash_spill_rows": fused_c["hash_spill_rows"],
    }

    root = tempfile.mkdtemp(prefix="bench_hashagg_", dir=_HERE)
    a = ct.Cluster(os.path.join(root, "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    b = None
    try:
        a.register_node()
        b = ct.Cluster(os.path.join(root, "b"), data_port=0,
                       hosted_nodes=set(), n_nodes=0,
                       coordinator=("127.0.0.1", a.control_port))
        b.register_node()
        a._maybe_reload_catalog(force_sync=True)
        n = int(os.environ.get("BENCH_HASH_AGG_ROWS", "400000"))
        a.execute("CREATE TABLE hb (k bigint NOT NULL, v bigint)")
        a.execute("SELECT create_distributed_table('hb', 'k', 8)")
        # spread ~50k distinct keys over a > direct_gid_limit domain so
        # the planner picks hash_host (the pushable-partials path)
        a.copy_from("hb", columns={"k": (np.arange(n) % 50_000) * 20_000_003,
                                   "v": np.arange(n)})
        q = "SELECT k, count(*), sum(v) FROM hb GROUP BY k"
        runs = {}
        for mode in ("push", "pull"):
            a.execute(f"SET citus.remote_task_execution = {mode}")
            GLOBAL_CACHE.clear()
            a.execute(q)  # plans + kernels warm under this mode
            GLOBAL_CACHE.clear()
            c0 = GLOBAL_COUNTERS.snapshot()
            t0 = time.perf_counter()
            a.execute(q)
            wall = time.perf_counter() - t0
            c1 = GLOBAL_COUNTERS.snapshot()
            runs[mode] = {
                "ms": round(wall * 1000, 2),
                "remote_tasks_pushed":
                    c1["remote_tasks_pushed"] - c0["remote_tasks_pushed"],
                "hash_partials_pushed":
                    c1["hash_partials_pushed"]
                    - c0["hash_partials_pushed"],
                "remote_task_fallbacks":
                    c1["remote_task_fallbacks"]
                    - c0["remote_task_fallbacks"],
                "remote_task_result_bytes":
                    c1["remote_task_result_bytes"]
                    - c0["remote_task_result_bytes"],
            }
        a.execute("SET citus.remote_task_execution = auto")
        hagg["push_vs_pull"] = runs
    finally:
        if b is not None:
            b.close()
        a.close()
        shutil.rmtree(root, ignore_errors=True)
    extra["hash_agg"] = hagg


def bench_trace_overhead(cl, extra: dict) -> None:
    """Tracing cost (observability/): warm Q1 wall time with sampling
    off (the allocation-free no-op recorder) vs sample_rate=1.0 (every
    span recorded).  The acceptance bar is < 3% overhead at rate 0
    relative to this build's own untraced baseline — measured here as
    rate-0 vs rate-0 jitter-adjusted by taking the best of several
    reps, the same protocol the headline metric uses."""
    reps = int(os.environ.get("BENCH_TRACE_REPS", "3"))

    def best_of(sql: str) -> float:
        cl.execute(sql)  # warm
        return min(_t_wall(cl, sql) for _ in range(reps))

    def _t_wall(cl, sql):
        t0 = time.perf_counter()
        cl.execute(sql)
        return time.perf_counter() - t0

    cl.execute("SET citus.trace_sample_rate = 0")
    off_s = best_of(Q1)
    cl.execute("SET citus.trace_sample_rate = 1.0")
    on_s = best_of(Q1)
    cl.execute("SET citus.trace_sample_rate = 0")
    extra["trace_overhead"] = {
        "q1_rate0_ms": round(off_s * 1000, 2),
        "q1_rate1_ms": round(on_s * 1000, 2),
        "sampled_overhead_fraction": round(max(0.0, on_s / off_s - 1.0), 4),
    }


def bench_recorder_overhead(cl, extra: dict) -> None:
    """Flight-recorder cost (observability/flight_recorder.py): warm Q1
    wall time with the sampler off vs ticking at interval=100ms (ring
    append + health checks + one segment line per tick, all off the
    query path).  The acceptance bar is < 3% overhead — the sampler
    runs on its own thread and only takes subsystem snapshot locks."""
    reps = int(os.environ.get("BENCH_RECORDER_REPS", "3"))

    def best_of(sql: str) -> float:
        cl.execute(sql)  # warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            cl.execute(sql)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    cl.execute("SET citus.flight_recorder_interval_ms = 0")
    off_s = best_of(Q1)
    cl.execute("SET citus.flight_recorder_interval_ms = 100")
    on_s = best_of(Q1)
    cl.execute("SET citus.flight_recorder_interval_ms = 0")
    extra["recorder_overhead"] = {
        "q1_recorder_off_ms": round(off_s * 1000, 2),
        "q1_recorder_100ms_ms": round(on_s * 1000, 2),
        "recorder_overhead_fraction": round(
            max(0.0, on_s / off_s - 1.0), 4),
    }


def bench_wait_overhead(cl, extra: dict) -> None:
    """Wait-event seam cost (stats.begin_wait/end_wait): warm Q1 wall
    time with the brackets live vs stubbed to no-ops at every
    instrumented call site.  The seam only opens brackets on genuinely
    blocking branches, so a warm local scan should measure within
    noise — the acceptance bar for 'near-free when idle'."""
    import citus_tpu.commands.dml as _dml
    import citus_tpu.executor.executor as _ex
    import citus_tpu.executor.pipeline as _pl
    import citus_tpu.transaction.locks as _lk
    reps = int(os.environ.get("BENCH_WAIT_REPS", "3"))

    def best_of() -> float:
        cl.execute(Q1)  # warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            cl.execute(Q1)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    on_s = best_of()
    sites = [(m, m.begin_wait, m.end_wait) for m in (_dml, _ex, _pl, _lk)]
    try:
        for m, _, _ in sites:
            m.begin_wait = lambda event: (event, 0.0)
            m.end_wait = lambda token: 0.0
        off_s = best_of()
    finally:
        for m, bw, ew in sites:
            m.begin_wait, m.end_wait = bw, ew
    extra["wait_event_overhead"] = {
        "q1_instrumented_ms": round(on_s * 1000, 2),
        "q1_stubbed_ms": round(off_s * 1000, 2),
        "overhead_fraction": round(max(0.0, on_s / off_s - 1.0), 4),
    }


_SANITIZE_CHILD = r"""
import json, sys, time
import numpy as np
import citus_tpu as ct
from citus_tpu.config import Settings

cl = ct.Cluster(sys.argv[1],
                settings=Settings(start_maintenance_daemon=False))
cl.execute("CREATE TABLE b (k bigint NOT NULL, v double)")
cl.execute("SELECT create_distributed_table('b', 'k', 8)")
n = int(sys.argv[2])
cl.copy_from("b", columns={"k": np.arange(n, dtype=np.int64) % 97,
                           "v": np.linspace(0.0, 1.0, n)})
q = "SELECT k, count(*), sum(v) FROM b GROUP BY k"
cl.execute(q)  # warm: compile + cache
ts = []
for _ in range(int(sys.argv[3])):
    t0 = time.perf_counter()
    cl.execute(q)
    ts.append(time.perf_counter() - t0)
cl.close()
print(json.dumps({"best_ms": min(ts) * 1000}))
"""


def bench_sanitize_overhead(extra: dict) -> None:
    """Concurrency-sanitizer cost (utils/sanitizer.py): warm Q1-shape
    wall time in a fresh process with CITUS_SANITIZE unset vs =1
    (every package lock wrapped, order graph + begin_wait hook live).
    Also asserts the off-mode zero-cost contract in THIS process:
    threading.Lock is still the raw C factory and the stats-seam guard
    is one False attribute read — off mode must be a passthrough, not
    merely cheap."""
    import subprocess
    import sys as _sys
    import tempfile
    import threading as _th

    from citus_tpu.utils import sanitizer as _san
    assert _th.Lock is _san._real_Lock and not _san._ACTIVE, \
        "sanitizer must be an exact passthrough when CITUS_SANITIZE is unset"
    rows = int(os.environ.get("BENCH_SANITIZE_ROWS", "200000"))
    reps = int(os.environ.get("BENCH_SANITIZE_REPS", "3"))

    def run(sanitize: bool) -> float:
        with tempfile.TemporaryDirectory() as td:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("CITUS_SANITIZE", None)
            if sanitize:
                env["CITUS_SANITIZE"] = "1"
            out = subprocess.run(
                [_sys.executable, "-c", _SANITIZE_CHILD,
                 os.path.join(td, "db"), str(rows), str(reps)],
                env=env, capture_output=True, timeout=600, check=True)
            return json.loads(out.stdout)["best_ms"]

    off_ms = run(False)
    on_ms = run(True)
    extra["sanitizer_overhead"] = {
        "q1_sanitize_off_ms": round(off_ms, 2),
        "q1_sanitize_on_ms": round(on_ms, 2),
        "overhead_fraction": round(max(0.0, on_ms / off_ms - 1.0), 4),
        "off_mode_passthrough": True,  # asserted above
    }


def bench_stat_fanout(extra: dict) -> None:
    """citus_cluster_metrics fan-out latency on a 3-node cluster
    (authority + two attached workers, all loopback): the wall cost of
    one merged scrape — probe threads + per-node get_node_stats round
    trips + Prometheus rendering."""
    import shutil
    import tempfile

    import citus_tpu as ct
    reps = int(os.environ.get("BENCH_FANOUT_REPS", "5"))
    root = tempfile.mkdtemp(prefix="bench_fanout_", dir=_HERE)
    a = ct.Cluster(os.path.join(root, "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    workers = []
    try:
        a.register_node()
        for name in ("b", "c"):
            w = ct.Cluster(os.path.join(root, name), data_port=0,
                           hosted_nodes=set(), n_nodes=0,
                           coordinator=("127.0.0.1", a.control_port))
            w.register_node()
            workers.append(w)
        a._maybe_reload_catalog(force_sync=True)
        a.execute("SELECT citus_cluster_metrics()")  # warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            r = a.execute("SELECT citus_cluster_metrics()")
            ts.append(time.perf_counter() - t0)
        txt = "\n".join(row[0] for row in r.rows)
        extra["stat_fanout"] = {
            "nodes": 3,
            "cluster_metrics_best_ms": round(min(ts) * 1000, 2),
            "cluster_metrics_avg_ms": round(
                sum(ts) / len(ts) * 1000, 2),
            "series_lines": sum(
                1 for ln in txt.splitlines()
                if ln and not ln.startswith("#")),
        }
    finally:
        for w in workers:
            w.close()
        a.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_wire(extra: dict) -> None:
    """Wire-format A/B (net/data_plane.py CTFR frame vs legacy npz):
    host decode of a ~32 MB task result (micro A/B — the zero-copy
    frombuffer view vs the zip-container copy), then the same remote
    fan-out queries — a distributed agg and a repartition join — on a
    3-host loopback cluster under each citus.wire_format, with the
    remote-RPC wait and per-codec byte counters for both runs."""
    import shutil
    import tempfile

    import citus_tpu as ct
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    from citus_tpu.net.data_plane import (
        _npz_bytes, _npz_load, decode_frame, encode_frame,
    )

    arrays = {f"c{i}": np.arange(1_000_000, dtype=np.int64)
              for i in range(4)}
    frame_blob, npz_blob = encode_frame(arrays), _npz_bytes(arrays)

    def best_decode(fn, blob) -> float:
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn(blob)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    f_ms = best_decode(decode_frame, frame_blob) * 1000
    z_ms = best_decode(_npz_load, npz_blob) * 1000

    root = tempfile.mkdtemp(prefix="bench_wire_", dir=_HERE)
    a = ct.Cluster(os.path.join(root, "a"), serve_port=0, data_port=0,
                   hosted_nodes=set(), n_nodes=0)
    workers = []
    try:
        a.register_node()
        for name in ("b", "c"):
            w = ct.Cluster(os.path.join(root, name), data_port=0,
                           hosted_nodes=set(), n_nodes=0,
                           coordinator=("127.0.0.1", a.control_port))
            w.register_node()
            workers.append(w)
        a._maybe_reload_catalog(force_sync=True)
        n = int(os.environ.get("BENCH_WIRE_ROWS", "400000"))
        a.execute("CREATE TABLE lw (k bigint NOT NULL, v bigint)")
        a.execute("SELECT create_distributed_table('lw', 'k', 8)")
        a.copy_from("lw", columns={"k": np.arange(n),
                                   "v": np.arange(n) % 97})
        # ow distributed on g, joined on o: forces the repartition path
        a.execute("CREATE TABLE ow (o bigint NOT NULL, g bigint)")
        a.execute("SELECT create_distributed_table('ow', 'g', 8)")
        a.copy_from("ow", columns={"o": np.arange(n // 4),
                                   "g": np.arange(n // 4) % 31})
        agg = "SELECT count(*), sum(v) FROM lw"
        join = "SELECT count(*) FROM lw l JOIN ow o ON l.k = o.o"
        runs = {}
        for fmt in ("frame", "npz"):
            a.execute(f"SET citus.wire_format = {fmt}")
            GLOBAL_CACHE.clear()
            a.execute(agg)
            a.execute(join)  # plans + kernels warm under this format
            GLOBAL_CACHE.clear()
            c0 = GLOBAL_COUNTERS.snapshot()
            t0 = time.perf_counter()
            a.execute(agg)
            agg_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            a.execute(join)
            join_s = time.perf_counter() - t0
            c1 = GLOBAL_COUNTERS.snapshot()
            runs[fmt] = {
                "agg_ms": round(agg_s * 1000, 2),
                "repartition_join_ms": round(join_s * 1000, 2),
                "wait_remote_rpc_ms": round(
                    c1["wait_remote_rpc_ms"] - c0["wait_remote_rpc_ms"],
                    2),
                "wire_frame_bytes":
                    c1["wire_frame_bytes"] - c0["wire_frame_bytes"],
                "wire_npz_bytes":
                    c1["wire_npz_bytes"] - c0["wire_npz_bytes"],
            }
        a.execute("SET citus.wire_format = frame")
        extra["wire"] = {
            "decode_frame_ms": round(f_ms, 3),
            "decode_npz_ms": round(z_ms, 3),
            # the acceptance bar: frame cuts host decode by >= 30 %
            "decode_cut_fraction": round(1.0 - f_ms / max(z_ms, 1e-9), 4),
            "frame": runs["frame"],
            "npz": runs["npz"],
        }
    finally:
        for w in workers:
            w.close()
        a.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_workload(extra: dict) -> None:
    """Closed-loop multi-tenant harness (workload/scheduler.py): mixed
    router + analytic traffic from N client threads in EACH of two
    coordinator OS processes sharing one cluster, admission squeezed
    through a small shared pool so the stride scheduler is the choke
    point.  Reports sustained QPS and per-tenant p50/p99."""
    import shutil
    import subprocess as sp
    import tempfile
    import textwrap
    import threading

    import citus_tpu as ct
    clients = int(os.environ.get("BENCH_WL_CLIENTS", "6"))
    seconds = float(os.environ.get("BENCH_WL_SECONDS", "6"))
    pool = int(os.environ.get("BENCH_WL_POOL", "4"))
    root = tempfile.mkdtemp(prefix="bench_workload_", dir=_HERE)
    d = os.path.join(root, "db")

    # one client thread's closed loop: router lookups on its own tenant
    # key, every 8th query the shared-bucket analytic scan
    driver = textwrap.dedent("""
        def _drive(cl, clients, seconds, out):
            import threading, time

            def loop(ci):
                tenant = str(ci % 4)
                lat = out.setdefault(tenant, [])
                alat = out.setdefault("*", [])
                router = f"SELECT sum(v) FROM wt WHERE k = {ci % 4}"
                analytic = "SELECT count(*), sum(v) FROM wt"
                i = 0
                deadline = time.monotonic() + seconds
                while time.monotonic() < deadline:
                    sql, dst = ((analytic, alat) if i % 8 == 7
                                else (router, lat))
                    t0 = time.perf_counter()
                    try:
                        cl.execute(sql)
                    except Exception:
                        i += 1
                        continue
                    dst.append(time.perf_counter() - t0)
                    i += 1
            ts = [threading.Thread(target=loop, args=(ci,))
                  for ci in range(clients)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    """)
    child_code = driver + textwrap.dedent(f"""
        import json, sys
        import citus_tpu as ct
        cl = ct.Cluster({d!r}, coordinator=("127.0.0.1", PORT))
        cl.execute("SET citus.max_shared_pool_size = {pool}")
        cl.execute("SELECT sum(v) FROM wt WHERE k = 1")  # warm
        print("READY", flush=True)
        sys.stdin.readline()  # GO
        out = {{}}
        _drive(cl, {clients}, {seconds}, out)
        cl.close()
        print("RESULT " + json.dumps(out), flush=True)
    """)

    a = ct.Cluster(d, serve_port=0)
    child = None
    try:
        a.execute("CREATE TABLE wt (k bigint NOT NULL, v bigint)")
        a.execute("SELECT create_distributed_table('wt', 'k', 8)")
        n = 200_000
        a.copy_from("wt", columns={"k": np.arange(n) % 64,
                                   "v": np.arange(n)})
        a.execute("SET citus.max_shared_pool_size = %d" % pool)
        for t in range(4):
            a.execute(f"SELECT citus_add_tenant_quota('{t}', 1.0)")
        a.execute("SELECT sum(v) FROM wt WHERE k = 1")  # warm
        # the second coordinator always runs the cpu backend: a second
        # OS process cannot share the TPU, and admission behavior (the
        # thing under test) is device-independent
        code = ("import jax\njax.config.update('jax_platforms','cpu')\n"
                + child_code.replace("PORT", str(a.control_port)))
        child = sp.Popen([sys.executable, "-c", code], stdin=sp.PIPE,
                         stdout=sp.PIPE, text=True)
        assert child.stdout.readline().strip() == "READY"
        ns = {}
        exec(compile(driver, "<bench_workload>", "exec"), ns)
        out = {}
        child.stdin.write("GO\n")
        child.stdin.flush()
        t0 = time.perf_counter()
        ns["_drive"](a, clients, seconds, out)
        line = child.stdout.readline()
        wall = time.perf_counter() - t0
        assert line.startswith("RESULT "), line
        for tenant, lats in json.loads(line[len("RESULT "):]).items():
            out.setdefault(tenant, []).extend(lats)
        total = sum(len(v) for v in out.values())
        tenants = {
            t: {"queries": len(v),
                "p50_ms": round(float(np.percentile(v, 50)) * 1000, 2),
                "p99_ms": round(float(np.percentile(v, 99)) * 1000, 2)}
            for t, v in sorted(out.items()) if v
        }
        extra["workload"] = {
            "coordinators": 2,
            "clients_per_coordinator": clients,
            "shared_pool_size": pool,
            "duration_s": seconds,
            "sustained_qps": round(total / wall, 1),
            "tenants": tenants,
        }
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        a.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_multi_coordinator(extra: dict) -> None:
    """Query-from-any-node scaling (citus_tpu/metadata/): aggregate QPS
    as 1 -> 2 -> 4 coordinator OS processes serve zipfian mixed traffic
    from a ~1M-tenant namespace against one shared cluster.  Every
    coordinator admits from the SAME catalog-persisted quota table (64
    registered heavy hitters in two priority classes, the long tail on
    GUC defaults), so the run also proves zero divergent admission
    decisions: each process reports a fingerprint over the effective
    admission inputs of fixed probe tenants, and all must match.

    Scaling is only meaningful when host cores >= coordinator count;
    the record carries host_cores so a 1-core container's flat curve
    reads as saturation, not a sync-engine bottleneck."""
    import shutil
    import subprocess as sp
    import tempfile
    import textwrap

    import citus_tpu as ct
    clients = int(os.environ.get("BENCH_MC_CLIENTS", "4"))
    seconds = float(os.environ.get("BENCH_MC_SECONDS", "3"))
    tenant_space = int(os.environ.get("BENCH_MC_TENANTS", "1000000"))
    counts = [1, 2, 4]
    root = tempfile.mkdtemp(prefix="bench_multicoord_", dir=_HERE)
    d = os.path.join(root, "db")

    child_code = textwrap.dedent(f"""
        import hashlib, json, sys, threading, time
        import numpy as np
        import citus_tpu as ct
        from citus_tpu.workload import GLOBAL_TENANTS
        seat = int(sys.argv[1])
        cl = ct.Cluster({d!r}, coordinator=("127.0.0.1", PORT))
        cl.metadata_sync.sync_once()
        # admission fingerprint over fixed probe tenants: registered
        # heavy hitters AND defaulted long-tail ids; any divergence in
        # quotas, classes, or GUC fallbacks changes the digest
        wl = cl.settings.workload
        probe = []
        for t in [str(i) for i in range(1, 65)] + ["999983", "717171"]:
            q = GLOBAL_TENANTS.get(t)
            pclass = (q.priority_class if q and q.priority_class
                      else wl.tenant_default_priority_class)
            probe.append((t, q.weight if q else wl.tenant_default_weight,
                          q.max_concurrency if q else 0,
                          q.rate_limit_qps if q else wl.tenant_rate_limit_qps,
                          q.queue_depth if q else wl.tenant_queue_depth,
                          pclass, GLOBAL_TENANTS.class_weight(pclass)))
        fp = hashlib.sha1(json.dumps(probe).encode()).hexdigest()[:16]
        cl.execute("SELECT sum(v) FROM mt WHERE k = 1")  # warm
        print("READY", flush=True)
        sys.stdin.readline()  # GO
        counts = [0] * {clients}

        def loop(ci):
            rng = np.random.default_rng(1000 * seat + ci)
            i = 0
            deadline = time.monotonic() + {seconds}
            while time.monotonic() < deadline:
                # zipfian tenant draw over the ~{tenant_space} namespace
                t = int(min(rng.zipf(1.2), {tenant_space}))
                sql = ("SELECT count(*), sum(v) FROM mt" if i % 8 == 7
                       else f"SELECT sum(v) FROM mt WHERE k = {{t}}")
                try:
                    cl.execute(sql)
                    counts[ci] += 1
                except Exception:
                    pass
                i += 1
        ts = [threading.Thread(target=loop, args=(ci,))
              for ci in range({clients})]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        wall = time.perf_counter() - t0
        cl.close()
        print("RESULT " + json.dumps(
            {{"count": sum(counts), "wall": wall, "fingerprint": fp}}),
            flush=True)
    """)

    a = ct.Cluster(d, serve_port=0)
    procs = []
    try:
        a.execute("CREATE TABLE mt (k bigint NOT NULL, v bigint)")
        a.execute("SELECT create_distributed_table('mt', 'k', 8)")
        n = 200_000
        rng = np.random.default_rng(7)
        keys = np.minimum(rng.zipf(1.2, size=n), tenant_space).astype(np.int64)
        a.copy_from("mt", columns={"k": keys, "v": np.arange(n)})
        # replicated control plane: two priority classes, 64 registered
        # heavy hitters, the other ~1M tenants on GUC defaults
        a.execute("SELECT citus_add_priority_class('gold', 4.0)")
        a.execute("SELECT citus_add_priority_class('basic', 1.0)")
        for t in range(1, 65):
            pc = "gold" if t <= 8 else "basic"
            a.execute(f"SELECT citus_add_tenant_quota('{t}', 2.0, 0, 0.0,"
                      f" 0, '{pc}')")
        qps_by_count = {}
        fingerprints = set()
        code = ("import jax\njax.config.update('jax_platforms','cpu')\n"
                + child_code.replace("PORT", str(a.control_port)))
        for k in counts:
            procs = [sp.Popen([sys.executable, "-c", code, str(seat)],
                              stdin=sp.PIPE, stdout=sp.PIPE, text=True)
                     for seat in range(k)]
            for p in procs:
                assert p.stdout.readline().strip() == "READY"
            for p in procs:
                p.stdin.write("GO\n")
                p.stdin.flush()
            total = 0
            for p in procs:
                line = p.stdout.readline()
                assert line.startswith("RESULT "), line
                r = json.loads(line[len("RESULT "):])
                total += r["count"] / max(r["wall"], 1e-9)
                fingerprints.add(r["fingerprint"])
                p.wait()
            procs = []
            qps_by_count[str(k)] = round(total, 1)
        q1 = qps_by_count["1"]
        extra["multi_coordinator"] = {
            "host_cores": os.cpu_count() or 1,
            "clients_per_coordinator": clients,
            "duration_s": seconds,
            "tenant_namespace": tenant_space,
            "registered_quotas": 64,
            "qps_by_coordinators": qps_by_count,
            "scaling_x2": round(qps_by_count["2"] / max(q1, 1e-9), 2),
            "scaling_x4": round(qps_by_count["4"] / max(q1, 1e-9), 2),
            # one distinct fingerprint across every coordinator = zero
            # divergent admission decisions
            "admission_fingerprints": len(fingerprints),
            "divergent_admission_decisions": len(fingerprints) - 1,
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        a.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_rollup(extra: dict) -> None:
    """Continuous-aggregation A/B (rollup/): a dashboard closed loop
    runs against a wide event table while writer threads keep heavy
    ingest flowing and the background refresh loop folds CDC deltas.
    The A arm re-scans raw events (citus.enable_rollup_routing = off);
    the B arm serves the same query from the rollup table.  Reports
    QPS + p99 per arm, the steady-state refresh lag sampled during the
    run, and how long the lag takes to converge once ingest stops."""
    import shutil
    import tempfile
    import threading

    import citus_tpu as ct
    from citus_tpu.config import Settings

    n = int(os.environ.get("BENCH_ROLLUP_ROWS", "300000"))
    seconds = float(os.environ.get("BENCH_ROLLUP_SECONDS", "5"))
    batch = int(os.environ.get("BENCH_ROLLUP_INGEST_BATCH", "2000"))
    tenants = 16
    root = tempfile.mkdtemp(prefix="bench_rollup_", dir=_HERE)
    dash_q = ("SELECT tid, count(*), sum(v), "
              "approx_count_distinct(kind), "
              "approx_percentile(0.5) WITHIN GROUP (ORDER BY v) "
              "FROM ev GROUP BY tid")

    def make_batch(rng, rows):
        return {
            "tid": rng.integers(0, tenants, rows).astype(np.int64),
            "kind": np.array([f"k{int(x)}" for x in
                              rng.integers(0, 64, rows)], object),
            "v": rng.uniform(1.0, 100.0, rows),
            "code": rng.integers(0, 32, rows).astype(np.int64),
        }

    cl = ct.Cluster(os.path.join(root, "db"),
                    settings=Settings(enable_change_data_capture=True))
    try:
        cl.execute("CREATE TABLE ev (tid bigint NOT NULL, kind text, "
                   "v double, code bigint)")
        cl.execute("SELECT create_distributed_table('ev', 'tid', 8)")
        rng = np.random.default_rng(0)
        done = 0
        while done < n:
            m = min(200_000, n - done)
            cl.copy_from("ev", columns=make_batch(rng, m))
            done += m
        cl.execute("SELECT citus_create_rollup('ev_r', 'ev', 'tid', "
                   "'count(*), sum(v), approx_count_distinct(kind), "
                   "approx_percentile(v), approx_top_k(code)')")
        cl.execute("SET citus.rollup_refresh_interval_ms = 100")

        stop = threading.Event()
        ingested = [0]

        def pound():
            wrng = np.random.default_rng(1)
            while not stop.is_set():
                cl.copy_from("ev", columns=make_batch(wrng, batch))
                ingested[0] += batch

        def arm(route_on):
            cl.execute("SET citus.enable_rollup_routing = "
                       + ("on" if route_on else "off"))
            cl.execute(dash_q)  # warm compile outside the window
            lats, lags = [], []
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                t0 = time.perf_counter()
                cl.execute(dash_q)
                lats.append(time.perf_counter() - t0)
                if route_on and len(lats) % 10 == 0:
                    lags.append(
                        cl.execute("SELECT citus_rollups()").rows[0][6])
            return {
                "queries": len(lats),
                "qps": round(len(lats) / seconds, 1),
                "p50_ms": round(float(np.percentile(lats, 50)) * 1000, 2),
                "p99_ms": round(float(np.percentile(lats, 99)) * 1000, 2),
            }, lags

        th = threading.Thread(target=pound)
        th.start()
        try:
            raw, _ = arm(route_on=False)
            rolled, lags = arm(route_on=True)
        finally:
            stop.set()
            th.join()
        # lag convergence: once ingest stops the watermark must reach
        # the CDC head and stay there
        t0 = time.monotonic()
        converged = None
        while time.monotonic() - t0 < 60:
            if cl.execute("SELECT citus_rollups()").rows[0][6] == 0:
                converged = round(time.monotonic() - t0, 2)
                break
            time.sleep(0.05)
        cl.execute("SET citus.rollup_refresh_interval_ms = 0")
        extra["rollup"] = {
            "source_rows": n + ingested[0],
            "ingested_during_run": ingested[0],
            "raw_scan": raw,
            "rollup": rolled,
            "speedup_p50": round(raw["p50_ms"] / max(rolled["p50_ms"],
                                                     1e-6), 1),
            "steady_state_lag_changes": {
                "mean": round(float(np.mean(lags)), 1) if lags else 0,
                "max": int(max(lags)) if lags else 0,
            },
            "lag_converged_after_ingest_s": converged,
        }
    finally:
        cl.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_rebalance(extra: dict) -> None:
    """Online rebalancing (operations/shard_transfer.py): N writer
    threads hammer the table for the whole life of a background shard
    move; the contract is zero failed writes with the blocked-write
    window (the locked final catch-up + metadata flip) a tiny fraction
    of the total move time.  Reports sustained write QPS under the
    move, blocked-write ms, and CDC catch-up rounds."""
    import shutil
    import tempfile
    import threading

    import citus_tpu as ct
    from citus_tpu.config import Settings
    from citus_tpu.testing.faults import FAULTS

    writers = int(os.environ.get("BENCH_RB_WRITERS", "4"))
    n = int(os.environ.get("BENCH_RB_ROWS", "200000"))
    root = tempfile.mkdtemp(prefix="bench_rebalance_", dir=_HERE)
    cl = ct.Cluster(os.path.join(root, "db"), n_nodes=2,
                    settings=Settings(enable_change_data_capture=True))
    try:
        cl.execute("CREATE TABLE rb (k bigint NOT NULL, v bigint)")
        cl.execute("SELECT create_distributed_table('rb', 'k', 4)")
        cl.copy_from("rb", columns={"k": np.arange(n, dtype=np.int64),
                                    "v": np.arange(n, dtype=np.int64) % 97})
        shard = cl.catalog.table("rb").shards[0]
        src = shard.placements[0]
        # stretch the bulk pass so the writers demonstrably overlap it
        FAULTS.arm("shard_move_copy", delay_s=0.3, times=1)
        jid = cl.background_jobs.create_job("bench move")
        cl.background_jobs.add_task(
            jid, "move_shard", {"shard_id": shard.shard_id,
                                "source": src, "target": 1 - src})
        stop = threading.Event()
        wrote, failed = [], []

        def hammer(base):
            i = 0
            while not stop.is_set():
                k = base + i * writers
                try:
                    cl.execute(f"INSERT INTO rb VALUES ({k}, {k % 97})")
                    wrote.append(k)
                except Exception:
                    failed.append(k)
                i += 1

        ts = [threading.Thread(target=hammer, args=(10 * n + w,))
              for w in range(writers)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        status = cl.background_jobs.wait_for_job(jid)
        stop.set()
        for t in ts:
            t.join()
        wall = time.perf_counter() - t0
        FAULTS.disarm()
        r = cl.execute("SELECT citus_shard_move_stats()")
        d = [dict(zip(r.columns, row)) for row in r.rows
             if row[0] == "move" and row[1] == shard.shard_id][-1]
        extra["rebalance"] = {
            "move_status": status,
            "writer_threads": writers,
            "writes_total": len(wrote),
            "writes_failed": len(failed),
            "sustained_write_qps": round(len(wrote) / wall, 1),
            "catchup_rounds": d["catchup_rounds"],
            "blocked_write_ms": d["blocked_write_ms"],
            "move_total_ms": d["total_ms"],
            "blocked_fraction": round(
                d["blocked_write_ms"] / max(d["total_ms"], 1), 4),
        }
    finally:
        FAULTS.disarm()
        cl.close()
        shutil.rmtree(root, ignore_errors=True)


def bench_autopilot(extra: dict) -> None:
    """Self-driving rebalancing A/B (services/autopilot.py): the same
    zipfian hot-shard storm under citus.autopilot = off | observe | on.
    Per arm: hot-query p99 before/after the autopilot's decision
    window, actions executed/observed/declined, and failed writes while
    a move ran (the contract: zero).  The observe arm's decision log is
    the dry-run instrument — same decisions as 'on', no moves."""
    import shutil
    import tempfile
    import threading

    import citus_tpu as ct

    n = int(os.environ.get("BENCH_AP_ROWS", "100000"))
    probes = int(os.environ.get("BENCH_AP_PROBES", "150"))
    arms = {}
    for arm in ("off", "observe", "on"):
        root = tempfile.mkdtemp(prefix=f"bench_autopilot_{arm}_", dir=_HERE)
        cl = ct.Cluster(os.path.join(root, "db"), n_nodes=2)
        try:
            cl.execute("CREATE TABLE ap (k bigint NOT NULL, v bigint)")
            cl.execute("SELECT create_distributed_table('ap', 'k', 4)")
            cl.copy_from("ap", columns={
                "k": np.arange(n, dtype=np.int64),
                "v": np.arange(n, dtype=np.int64) % 97})
            cl.execute(f"SET citus.autopilot = {arm}")
            cl.execute("SET citus.autopilot_sustain_ticks = 2")
            cl.execute("SET citus.autopilot_cooldown_s = 3600")
            cl.counters.reset()  # re-zeros the attribution ledger too
            s = cl.session()
            s.execute("PREPARE appt AS SELECT sum(v) FROM ap WHERE k = $1")
            # hot-tenant storm: every probe routes to a shard placed on
            # node 0, so node 0's placements run away in the attribution
            # ledger while node 1 idles — the shape the autopilot fixes
            from citus_tpu.catalog.hashing import hash_int64_scalar
            t = cl.catalog.table("ap")
            keys, k = [], 0
            while len(keys) < 8 and k < n:
                sidx = t.route_hash(hash_int64_scalar(k))
                if t.shards[sidx].placements[0] == 0:
                    keys.append(k)
                k += 1
            keys = (keys * (2 * probes // len(keys) + 1))[:2 * probes]

            def storm(ks):
                lat = []
                for k in ks:
                    t0 = time.perf_counter()
                    s.execute(f"EXECUTE appt ({int(k)})")
                    lat.append(time.perf_counter() - t0)
                lat.sort()
                return round(lat[int(0.99 * (len(lat) - 1))] * 1000, 3)

            before = [tuple(s.placements)
                      for s in cl.catalog.table("ap").shards]
            p99_storm = storm(keys[:probes])
            # decision window: the storm KEEPS RUNNING (the EWMA rates
            # the planner reads are live rates, not history) and a
            # writer hammers ingest while the duty evaluates — and, in
            # the 'on' arm, executes its one move under both
            stop = threading.Event()
            wrote, failed = [], []

            def hammer():
                i = 0
                while not stop.is_set():
                    k = 10 * n + i
                    try:
                        cl.execute(f"INSERT INTO ap VALUES ({k}, {k % 97})")
                        wrote.append(k)
                    except Exception:
                        failed.append(k)
                    i += 1

            s2 = cl.session()
            s2.execute("PREPARE hot AS SELECT sum(v) FROM ap WHERE k = $1")

            def hot_loop():
                i = 0
                while not stop.is_set():
                    s2.execute(f"EXECUTE hot ({int(keys[i % probes])})")
                    i += 1

            threads = [threading.Thread(target=hammer),
                       threading.Thread(target=hot_loop)]
            for th in threads:
                th.start()
            for _ in range(6):
                cl.autopilot.duty()
                time.sleep(0.25)
            stop.set()
            for th in threads:
                th.join()
            p99_after = storm(keys[probes:])
            after = [tuple(s.placements)
                     for s in cl.catalog.table("ap").shards]
            snap = cl.counters.snapshot()
            arms[arm] = {
                "p99_storm_ms": p99_storm,
                "p99_after_ms": p99_after,
                "placements_moved": sum(b != a
                                        for b, a in zip(before, after)),
                "actions_executed": snap["autopilot_actions_executed"],
                "actions_observed": snap["autopilot_actions_observed"],
                "actions_declined": snap["autopilot_actions_declined"],
                "decision_log_rows": len(cl.autopilot.log_rows()),
                "writes_total": len(wrote),
                "writes_failed": len(failed),
            }
        finally:
            cl.close()
            shutil.rmtree(root, ignore_errors=True)
    extra["autopilot"] = arms


def ensure_join_data(cl: "ct.Cluster", n_orders: int) -> None:
    """orders_b: the build side of the repartition join, distributed on
    o_custkey so the l_orderkey = o_orderkey join must reshuffle."""
    if cl.catalog.has_table("orders_b"):
        from citus_tpu.catalog.stats import table_row_count
        if table_row_count(cl.catalog, cl.catalog.table("orders_b")) == n_orders:
            return
        cl.drop_table("orders_b")
    cl.execute("""CREATE TABLE orders_b (
        o_orderkey bigint NOT NULL, o_custkey bigint NOT NULL,
        o_flag text)""")
    cl.execute(f"SELECT create_distributed_table('orders_b', 'o_custkey', {SHARDS})")
    rng = np.random.default_rng(11)
    flags = np.array(["H", "L", "M"])
    chunk = 1_000_000
    for start in range(0, n_orders, chunk):
        n = min(chunk, n_orders - start)
        cl.copy_from("orders_b", columns={
            "o_orderkey": np.arange(start, start + n, dtype=np.int64),
            "o_custkey": rng.integers(0, n_orders // 8 + 1, n),
            "o_flag": flags[rng.integers(0, 3, n)].tolist(),
        })


def ensure_data(cl: "ct.Cluster", n_rows: int = None) -> None:
    n_rows = N_ROWS if n_rows is None else n_rows
    if cl.catalog.has_table("lineitem"):
        from citus_tpu.catalog.stats import table_row_count
        if table_row_count(cl.catalog, cl.catalog.table("lineitem")) == n_rows:
            return
        cl.drop_table("lineitem")
    cl.execute("""CREATE TABLE lineitem (
        l_orderkey bigint NOT NULL, l_quantity decimal(12,2),
        l_extendedprice decimal(12,2), l_discount decimal(12,2),
        l_tax decimal(12,2), l_returnflag text, l_linestatus text,
        l_shipdate date)""")
    cl.execute(f"SELECT create_distributed_table('lineitem', 'l_orderkey', {SHARDS})")
    rng = np.random.default_rng(7)
    chunk = 1_000_000
    rf = np.array(["A", "N", "R"])
    ls = np.array(["F", "O"])
    for start in range(0, n_rows, chunk):
        n = min(chunk, n_rows - start)
        cl.copy_from("lineitem", columns={
            "l_orderkey": rng.integers(0, n_rows // 4, n),
            "l_quantity": (rng.integers(100, 5100, n) / 100.0),
            "l_extendedprice": (rng.integers(90_000, 10_500_000, n) / 100.0),
            "l_discount": (rng.integers(0, 11, n) / 100.0),
            "l_tax": (rng.integers(0, 9, n) / 100.0),
            "l_returnflag": rf[rng.integers(0, 3, n)].tolist(),
            "l_linestatus": ls[rng.integers(0, 2, n)].tolist(),
            "l_shipdate": (rng.integers(0, 2526, n) + 8036).astype(np.int32),
        })


def main() -> None:
    import jax
    if PLATFORM:
        jax.config.update("jax_platforms", PLATFORM)
    import citus_tpu as ct
    from citus_tpu.parallel.mesh import executor_devices
    # raises when JAX found no accelerator and BENCH_PLATFORM did not
    # ask for the cpu by name: no record is printed without a device
    devices = executor_devices()
    data_dir = os.path.join(_HERE, ".bench_data")
    cl = ct.Cluster(data_dir)
    ensure_data(cl)

    def timed(sql, warm=1, reps=3):
        for _ in range(warm):
            cl.execute(sql)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            cl.execute(sql)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    best = timed(Q1)
    rows_per_sec = N_ROWS / best
    q6_rate = N_ROWS / timed(Q6)
    extra = {
        "q6_rows_per_sec": round(q6_rate, 1),
        "q6_vs_baseline": round(q6_rate / BASELINE_ROWS_PER_SEC, 3),
    }
    # roofline (VERDICT weak #4): bytes the warm Q1 scan pushes per
    # second vs the chip's HBM peak — rows/s cannot say how close the
    # engine runs to what the memory system permits
    bytes_per_sec = rows_per_sec * Q1_BYTES_PER_ROW
    extra["q1_bytes_scanned_per_sec"] = round(bytes_per_sec, 1)
    if devices[0].platform != "cpu":
        peak = _hbm_peak_for(devices[0].device_kind)
        extra["hbm_peak_bytes_per_sec"] = peak
        extra["q1_fraction_of_hbm_peak"] = round(bytes_per_sec / peak, 4)
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        # host-decode/device-compute overlap on an uncached Q1 scan
        # (executor/pipeline.py): busy fractions near 1.0 on both
        # halves mean the read-ahead queue is hiding decode behind
        # device rounds; a low device fraction = host-bound pipeline
        from citus_tpu.executor.device_cache import GLOBAL_CACHE
        GLOBAL_CACHE.clear()
        t0 = time.perf_counter()
        r = cl.execute(Q1)
        wall = time.perf_counter() - t0
        pl = (r.explain or {}).get("pipeline") or {}
        if pl and wall > 0:
            extra["pipeline"] = {
                "host_decode_ms": pl.get("host_decode_ms", 0),
                "device_ms": pl.get("device_ms", 0),
                "h2d_bytes": pl.get("h2d_bytes", 0),
                "host_stalls": pl.get("host_stalls", 0),
                "device_stalls": pl.get("device_stalls", 0),
                "host_decode_busy_fraction": round(
                    pl.get("host_decode_ms", 0) / (wall * 1000), 4),
                "device_busy_fraction": round(
                    pl.get("device_ms", 0) / (wall * 1000), 4),
                # lower bound on overlapped work: both halves cannot
                # sum past the wall unless they ran concurrently
                "overlap_fraction": round(max(
                    0.0, (pl.get("host_decode_ms", 0)
                          + pl.get("device_ms", 0)) / (wall * 1000) - 1.0),
                    4),
            }
    if os.environ.get("BENCH_CONCURRENCY", "1") != "0":
        bench_concurrency(cl, extra)
    if os.environ.get("BENCH_PLAN_CACHE", "1") != "0":
        bench_plan_cache(cl, extra)
    if os.environ.get("BENCH_MEGABATCH", "1") != "0":
        bench_megabatch(cl, extra)
    if os.environ.get("BENCH_SCAN_FUSE", "1") != "0":
        bench_scan_fuse(cl, extra)
    if os.environ.get("BENCH_HASH_AGG", "1") != "0":
        bench_hash_agg(cl, extra)
    if os.environ.get("BENCH_TRACE", "1") != "0":
        bench_trace_overhead(cl, extra)
    if os.environ.get("BENCH_RECORDER", "1") != "0":
        bench_recorder_overhead(cl, extra)
    if os.environ.get("BENCH_WAIT", "1") != "0":
        bench_wait_overhead(cl, extra)
    if os.environ.get("BENCH_SANITIZE", "0") == "1":
        bench_sanitize_overhead(extra)
    if os.environ.get("BENCH_FANOUT", "1") != "0":
        bench_stat_fanout(extra)
    if os.environ.get("BENCH_WIRE", "1") != "0":
        bench_wire(extra)
    if os.environ.get("BENCH_WORKLOAD", "1") != "0":
        bench_workload(extra)
    if os.environ.get("BENCH_MULTICOORD", "1") != "0":
        bench_multi_coordinator(extra)
    if os.environ.get("BENCH_REBALANCE", "1") != "0":
        bench_rebalance(extra)
    if os.environ.get("BENCH_AUTOPILOT", "1") != "0":
        bench_autopilot(extra)
    if os.environ.get("BENCH_ROLLUP", "1") != "0":
        bench_rollup(extra)
    if os.environ.get("BENCH_JOIN", "1") != "0":
        n_orders = N_ROWS // 4
        ensure_join_data(cl, n_orders)
        join_rate = (N_ROWS + n_orders) / timed(QJOIN, reps=2)
        extra["repartition_join_rows_per_sec"] = round(join_rate, 1)
        extra["join_vs_repartition_baseline"] = round(
            join_rate / JOIN_BASELINE_ROWS_PER_SEC, 3)
    if os.environ.get("BENCH_SWEEP") == "1":
        # throughput-vs-size curve past the HBM batch cache: the
        # streaming pipeline should degrade smoothly, not collapse
        sweep = {str(N_ROWS): round(rows_per_sec, 1)}
        for mult in (2, 4):
            n_sweep = N_ROWS * mult
            ensure_data(cl, n_sweep)
            sweep[str(n_sweep)] = round(n_sweep / timed(Q1), 1)
        ensure_data(cl, N_ROWS)  # restore the standard scale
        extra["sweep_rows_per_sec_by_table_rows"] = sweep
    rec = {
        "metric": "tpch_q1_rows_scanned_per_sec_per_chip",
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / BASELINE_ROWS_PER_SEC, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "extra": extra,
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
