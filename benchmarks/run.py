#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, queries and per-layer metrics
are data files found by the names ``BENCHMARK.json`` gives (see
``spec.py``).  Set-up builds the native library, opens the chip, opens
the configuration's table (generating and ingesting it on a checkout's
first run), and warms the cell's own statements; then the traffic runs
for ``--seconds`` and every answer is held to the plain reference.  The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``).  Without a TPU, or with another number of chips than
the cell names, it exits non-zero and prints no result.

``--rehearse-on-cpu`` (with ``--orders`` for a tiny table) debugs the
harness where there is no chip; its line is stamped ``"rehearsal":
true`` and is never chosen automatically.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(HERE, ".data", "trace")
SPANS_DIR = os.path.join(TRACE_DIR, "spans")


def log(message):
    print(message, file=sys.stderr, flush=True)


def build_native():
    """Build the native columnar IO library before JAX is imported, so
    this process starts no child once it can hold the chip."""
    subprocess.run(["make", "-C", os.path.join(ROOT, "citus_tpu", "native")],
                   check=True, stdout=subprocess.DEVNULL)


def memory_peak_bytes(devices) -> int:
    stats = [d.memory_stats() for d in devices]
    return max((int(s["peak_bytes_in_use"]) for s in stats if s), default=0)


class Profiler:
    """The traced run's instrumentation: the program samples and exports
    every statement's spans, and the JAX profiler records the first
    ``slice_cycles`` cycles of the window."""

    def __init__(self, cl, slice_cycles):
        import jax
        self.slice_cycles = slice_cycles
        self.lock = threading.Lock()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(SPANS_DIR)
        cl.execute("SET citus.trace_sample_rate = 1")
        cl.execute(f"SET citus.trace_export_dir = '{SPANS_DIR}'")
        for name in os.listdir(SPANS_DIR):      # the SETs' own traces
            os.remove(os.path.join(SPANS_DIR, name))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2           # TraceAnnotation spans
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        self.on = True

    def stop_after(self, cycles_done=None):
        """Called after every cycle, and once more after the window."""
        with self.lock:
            if self.on and (cycles_done is None
                            or cycles_done >= self.slice_cycles):
                import jax
                jax.profiler.stop_trace()
                self.on = False


def program_spans():
    """-> ({span name: total ms}, traces) over the traces the program
    exported during the window, one file per statement."""
    total, traces = {}, 0
    for name in os.listdir(SPANS_DIR):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(SPANS_DIR, name)) as fh:
            events = json.load(fh)["traceEvents"]
        traces += 1
        for e in events:
            if e.get("ph") == "X":
                total[e["name"]] = total.get(e["name"], 0.0) + e["dur"] / 1e3
    return total, traces


def per_layer(cell, ctx):
    """-> {metric: {"value", "unit"}} for the cell's per-layer metrics
    whose reader found something to read in ``ctx``: the window's
    records and counter deltas, the program's exported spans, the
    reduced device trace and the traced slice's queries."""
    from benchmarks.spec import load_json, plugin
    out = {}
    for m in cell.per_layer:
        reader = load_json("layer_metrics", m["name"] + ".json")["reader"]
        value = plugin("sources", reader["kind"]).read(ctx, reader)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="debug on the CPU backend; the line is stamped as "
                         "a rehearsal and proves nothing about the chip")
    ap.add_argument("--orders", type=int,
                    help="rehearsal only: orders to generate instead of the "
                         "configuration's")
    args = ap.parse_args(argv)
    if args.orders is not None and not args.rehearse_on_cpu:
        ap.error("--orders cuts the table and is for --rehearse-on-cpu only")

    from benchmarks import dataset, metrics, trace_reduce, traffic
    from benchmarks.spec import Cell
    cell = Cell(args.workload)
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell.chips}")
    build_native()

    import jax
    import citus_tpu as ct
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse_on_cpu:
        log(f"benchmark: no TPU found (jax.devices() -> {devices}); "
            "refusing to measure on another platform")
        return 2
    if len(devices) != cell.chips:
        log(f"benchmark: {cell.name} is laid out for {cell.chips} chip(s), "
            f"JAX found {len(devices)}")
        return 2

    # ---- set-up: the table, the statements, the warm-up ---------------
    cl, stats, data = dataset.prepare(cell.config, len(devices), ct.Cluster,
                                      orders=args.orders, log=log)
    try:
        statements = traffic.build_statements(cell.traffic, cell.queries, stats)
        warm = traffic.run_cycles(cl, statements, cell.traffic, args.seed,
                                  int(cell.traffic.get("warmup_cycles", 1)))
        slice_cycles = int(cell.traffic.get("traced_slice_cycles", 1))
        profiler = Profiler(cl, slice_cycles) if args.trace else None
        counters_before = cl.counters.snapshot()
        setup_s = time.perf_counter() - T_PROCESS

        # ---- the measured window --------------------------------------
        records, t_start = traffic.run_window(
            cl, statements, cell.traffic, args.seed, args.seconds,
            annotate=bool(args.trace),
            on_cycle=profiler.stop_after if profiler else None)
        if profiler:
            profiler.stop_after()
        counters_after = cl.counters.snapshot()
        peak_bytes = memory_peak_bytes(devices)
    finally:
        cl.close()

    # ---- after the window: check every answer, reduce, report ---------
    checker = metrics.Checker(cell.queries, stats)
    correct = all([checker.check(warm), checker.check(records),
                   not any(r.error for r in warm)])
    failed = [r for r in records if r.error is not None]
    for r in warm + records:
        if r.error is not None:
            log(f"benchmark: {r.name} {r.raw} failed: {r.error}")
    for w in checker.wrong[:5]:
        log(f"benchmark: wrong answer {json.dumps(w)}")
    names = [m["name"] for m in cell.end_to_end]
    e2e = metrics.end_to_end(names, records, t_start, data["rows"])
    e2e["setup_s"] = setup_s
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    info = {
        "rows": data["rows"], "queries": len(records) - len(failed),
        "data": data, "seed": args.seed, "seconds": args.seconds,
        "generator_lateness_max_s": max((r.sent - r.due for r in records),
                                        default=0.0),
        "by_query": {q: sum(r.name == q for r in records)
                     for q in cell.queries},
        "first_draws": [[r.name, r.raw] for r in records[:8]],
        "first_latencies_ms": [round(r.latency_s * 1e3, 3)
                               for r in records[:16]],
    }
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed)}
    if not args.trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {n: {"value": e2e[n], "unit": units[n]}
                             for n in names if n in e2e}
    else:
        profile = trace_reduce.load(TRACE_DIR)
        reduced = trace_reduce.reduce_trace(profile) if profile else None
        if reduced is None and not args.rehearse_on_cpu:
            log("benchmark: the profiler's trace holds no device operation")
            return 3
        span_ms, span_traces = program_spans()
        counters = {k: counters_after[k] - counters_before.get(k, 0)
                    for k in counters_after}
        # one client: records are in completion order, a cycle after a cycle
        n_slice = slice_cycles * sum(w for _, w in statements)
        result["metrics"] = per_layer(cell, types.SimpleNamespace(
            cell=cell, records=records, n_queries=len(records) - len(failed),
            counters=counters,
            span_ms=span_ms, span_traces=span_traces, trace=reduced,
            slice_queries=[r.name for r in records[:n_slice]],
            table_rows=data["rows"], chips=cell.chips,
            device_kind=devices[0].device_kind, memory_peak_bytes=peak_bytes))
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": trace_reduce.top(reduced["ops"]),
                "idle_gaps": trace_reduce.top(reduced["gaps"])}
            info["trace"] = {"modules": reduced["modules"],
                             "spans": reduced["n_spans"]}
        else:
            log("benchmark: the CPU backend's trace has no device plane; "
                "trace metrics are left out of a rehearsal")
        # the same client-side numbers under tracing: the difference from
        # the --trace 0 run is what the instrumentation costs
        info["traced_end_to_end"] = e2e
        info["counters"] = {k: v for k, v in counters.items() if v}
        info["span_ms_per_query"] = {k: v / span_traces
                                     for k, v in sorted(span_ms.items())}
    result["device"] = device
    result["info"] = info
    if args.rehearse_on_cpu:
        result["rehearsal"] = True
    print(json.dumps(result, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
