"""Trace + metrics exporters.

- Chrome trace-event JSON: one file per sampled query under
  ``citus.trace_export_dir``; loads directly in Perfetto / chrome://
  tracing.  Coordinator spans render as process 1, every remote host's
  grafted ``execute_task`` subtree as its own process row, each thread
  (the caller's, the decode thread's) as its own row within it, and
  each event's args carry span_id/parent_id so the tree survives the
  format.
- Prometheus text exposition: all StatCounters as counters, cache
  occupancy as gauges, and per-query-family latency histograms from
  ``QueryStats`` (scripts/metrics_exporter.py + SHOW citus.metrics).
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

#: coordinator pid in the trace-event timeline; remote hosts offset
#: their node id from here
COORD_PID = 1
_REMOTE_PID_BASE = 1000


def chrome_trace_events(trace) -> dict:
    """Render a finished Trace as a Chrome trace-event document
    ("X" complete events, ts/dur in microseconds)."""
    events = []
    pids = {COORD_PID: "coordinator"}
    # small stable thread rows: 1 is the thread that opened the trace
    tids: dict = {}
    for s in trace.spans:
        t1 = s.t1 if s.t1 is not None else s.t0
        host = s.attrs.get("host")
        if host is None:
            pid = COORD_PID
        else:
            pid = _REMOTE_PID_BASE + int(host)
            pids[pid] = f"worker node {host}"
        args = {k: v for k, v in s.attrs.items()
                if isinstance(v, (int, float, str, bool))}
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        events.append({
            "name": s.name,
            "cat": "citus",
            "ph": "X",
            "ts": round((trace.t0_wall + (s.t0 - trace.t0)) * 1e6, 3),
            "dur": round(max(0.0, t1 - s.t0) * 1e6, 3),
            "pid": pid,
            "tid": tids.setdefault((pid, s.tid), len(tids) + 1),
            "args": args,
        })
    for pid, name in sorted(pids.items()):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 1, "args": {"name": name}})
    return {"traceEvents": events,
            "otherData": {"trace_id": trace.trace_id,
                          "thread_rows": len(tids)}}


def write_chrome_trace(trace, export_dir: str) -> str:
    """Write one Perfetto-loadable JSON per trace; returns the path."""
    os.makedirs(export_dir, exist_ok=True)
    path = os.path.join(export_dir, f"trace_{trace.trace_id}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(chrome_trace_events(trace), f)
    os.replace(tmp, path)
    return path


# ----------------------------------------------------------- prometheus


_LABEL_BAD = re.compile(r"[\\\"\n]")


def _label(v: str) -> str:
    return _LABEL_BAD.sub("_", v)[:200]


#: families with fewer calls than the busiest N are dropped from the
#: histogram section (label cardinality bound)
TOP_FAMILIES = 20

#: curated HELP docs (counter/gauge name -> text); names not listed get
#: generated text.  Every HELP line quotes the bare internal name, so a
#: reader of SHOW citus.metrics can still find the pre-_total series
#: names the counters are known by inside the process.
METRIC_HELP = {
    "queries_executed": "SQL statements executed by this process",
    "bytes_scanned": "columnar bytes staged for device scans",
    "batch_rows_real": "table rows in the padded scan batches made",
    "batch_rows_padded": "rows of those batches' power-of-two buckets",
    "decode_bytes_in_place": "value bytes of those batches decoded straight into the batch's arrays",
    "decode_bytes_copied": "value bytes of those batches decoded apart and copied in",
    "decode_streams": "threads that decoded a streamed scan's batches side by side, summed over the scans (1 a scan that one thread decoded)",
    "decode_overlap_ms": "wall milliseconds of those scans during which two or more batches were being decoded at once",
    "footer_cache_hits": "stripe footers served decoded, the file's identity unchanged",
    "footer_parses": "stripe files opened and their footers parsed",
    "footer_cache_evictions": "decoded footers the cache's bound pushed out",
    "direct_groups": "slots of the group domains of direct-group-id aggregations",
    "direct_groups_out": "groups those aggregations returned",
    "direct_bytes_fetched": "bytes of their partial states fetched",
    "direct_gid_keys": "group keys of the direct-group-id aggregations that ran",
    "direct_gid_keys_narrow": "those keys whose group code took no 64-bit division and rode 32-bit lanes",
    "scan_lanes": "int64 scan columns of the device scans that ran",
    "scan_lanes_narrow": "those the table statistics bound inside int32 and the device held at 32 bits",
    "scan_lanes_belied": "device scans a batch of which held a value outside those bounds (re-run at full width)",
    "mesh_round_bytes_copied": "host bytes the streamed mesh rounds copied before the put (re-padded short members, new fillers)",
    "agg_partials": "partial states the plans of aggregate queries computed",
    "agg_partials_proved_away": "overflow guards and null counts the table statistics proved redundant",
    "hash_slots": "slots of the device hash tables made",
    "hash_tables": "device hash tables made (one a device where a host has several)",
    "hash_tables_merged": "per-device hash tables fetched whole and merged (a group may sit in several)",
    "hash_rows_in": "table rows the hash scans took",
    "hash_rows_in_max_device": "of those, the rows of each scan's fullest device",
    "group_rows_in": "padded rows the grouping stages ran over",
    "group_rows_kept": "rows of grouped scans that passed the WHERE",
    "hash_groups_out": "groups of hash aggregations, before HAVING",
    "hash_table_updates": "entries (a batch's distinct keys) offered to device hash tables",
    "hash_offer_slots": "entry slots the hash kernels' offer loops ran over for them (whole chunks a batch)",
    "hash_table_bytes_fetched": "bytes of device hash tables fetched",
    "hash_entries_fetched": "entries (slots) of device hash tables fetched",
    "group_keys": "group keys of the device joins that ran",
    "group_keys_dependent": "of those, keys a join proved functions of another: looked up for the returned groups",
    "group_key_lanes": "key lanes the device joins' group tables held",
    "group_top_cuts": "statements whose ORDER BY ... LIMIT was cut on the chip",
    "group_top_entries": "entries of device hash tables fetched by those cuts",
    "wait_remote_rpc_ms": "ms blocked on remote RPC round trips",
    "wait_lock_ms": "ms blocked acquiring advisory locks",
    "wait_prefetch_stall_ms": "ms the device starved for host decode",
    "wait_prefetch_full_ms": "ms the decode thread held a batch the device was not ready for",
    "kernel_compiles": "jitted calls that traced and compiled (cache misses and retraces for a new shape)",
    "wait_device_round_ms": "ms blocked on device round backpressure",
    "wait_2pc_decision_ms": "ms blocked on 2PC decision round trips",
    "stat_fanout_probes": "get_node_stats probes issued by this node",
    "stat_fanout_unreachable":
        "stat fan-out probes degraded to node_unreachable",
    "live_queries": "statements currently executing",
    "slow_log_entries": "entries in the in-memory slow-query ring",
    "pool_in_use": "admission-pool slots held right now",
    "pool_high_water": "peak concurrent admission-pool slots",
    "tenant_queued": "queries waiting in tenant admission queues",
    "device_cache_high_water_bytes":
        "peak HBM bytes the device batch cache ever held",
    "device_hbm_touched_bytes":
        "HBM bytes touched by device scans (hits + streams)",
    "health_p99_regression": "active p99-regression health events",
    "health_shed_rate_spike": "active shed-rate-spike health events",
    "health_catchup_stall": "active catch-up-stall health events",
    "health_pool_saturation": "active pool-saturation health events",
    "health_dead_node": "active dead-node health events",
    "health_metadata_sync_lag": "active metadata-sync-lag health events",
    "health_autopilot_action": "active autopilot-action health events",
    "autopilot_ticks": "autopilot evaluation ticks run",
    "autopilot_actions_executed": "rebalance actions the autopilot ran",
    "autopilot_actions_observed":
        "actions the autopilot would have run (observe mode)",
    "autopilot_actions_declined":
        "actions the autopilot evaluated and declined",
    "placement_sync_elided":
        "pull-path placement syncs skipped via the invalidation epoch",
    "metadata_sync_bytes":
        "catalog bytes shipped to this coordinator as CTFR frames",
    "metadata_sync_rounds": "metadata pull-on-mismatch rounds run",
    "metadata_stale_reads":
        "statements that observed a stale catalog before converging",
    "wait_metadata_sync_ms": "ms blocked on metadata sync round trips",
}


def _help_line(name: str, series: str) -> str:
    doc = METRIC_HELP.get(name, name.replace("_", " "))
    return f"# HELP {series} {doc} (internal name: {name})"


def prometheus_text(cluster) -> str:
    """Text-format exposition of the cluster's metrics: every
    StatCounters name, cache-occupancy gauges, and per-query-family
    latency histograms (log-scale buckets from QueryStats).  Counter
    series carry the conventional _total suffix; HELP lines keep the
    bare internal names discoverable."""
    out = []

    counters = cluster.counters.snapshot()
    for name in sorted(counters):
        series = f"citus_{name}_total"
        out.append(_help_line(name, series))
        out.append(f"# TYPE {series} counter")
        out.append(f"{series} {counters[name]}")

    gauges = _gauges(cluster)
    for name in sorted(gauges):
        series = f"citus_{name}"
        out.append(_help_line(name, series))
        out.append(f"# TYPE {series} gauge")
        out.append(f"{series} {gauges[name]}")

    # per-tenant queue depth, labeled (the flat citus_tenant_queued
    # gauge above is the sum; cardinality is bounded by the scheduler's
    # own tenant table)
    from citus_tpu.workload.scheduler import GLOBAL_SCHEDULER
    sched_rows = GLOBAL_SCHEDULER.rows_view()
    if sched_rows:
        out.append("# HELP citus_tenant_queue_depth queries waiting in "
                   "this tenant's admission queue")
        out.append("# TYPE citus_tenant_queue_depth gauge")
        for r in sched_rows:
            out.append(f'citus_tenant_queue_depth'
                       f'{{tenant="{_label(str(r[0]))}"}} {int(r[2])}')

    # per-placement load attribution, labeled; cardinality bounded by
    # the ledger's top-K sampler cap (same cap as the flight-recorder
    # shard_load: ring series)
    from citus_tpu.observability.load_attribution import (
        GLOBAL_ATTRIBUTION, RING_TOP_K,
    )
    att = GLOBAL_ATTRIBUTION.rows_view()[:RING_TOP_K]
    if att:
        for series, idx, doc in (
                ("citus_shard_load_device_ms_total", 5,
                 "device ms attributed to this placement"),
                ("citus_shard_load_bytes_total", 6,
                 "bytes scanned attributed to this placement")):
            out.append(f"# HELP {series} {doc} "
                       "(internal view: citus_shard_load)")
            out.append(f"# TYPE {series} counter")
            for r in att:
                out.append(
                    f'{series}{{table="{_label(str(r[0]))}",'
                    f'shard="{int(r[1])}",node="{int(r[2])}",'
                    f'tenant="{_label(str(r[3]))}"}} {r[idx]}')

    # autopilot decisions by outcome (the per-outcome flat counters
    # above remain for SHOW citus.metrics discoverability)
    out.append("# HELP citus_autopilot_actions_total autopilot "
               "decisions by outcome (services/autopilot.py)")
    out.append("# TYPE citus_autopilot_actions_total counter")
    for outcome in ("executed", "observed", "declined"):
        out.append(f'citus_autopilot_actions_total'
                   f'{{outcome="{outcome}"}} '
                   f'{counters.get("autopilot_actions_" + outcome, 0)}')

    fams = _family_histograms(cluster)
    if fams:
        out.append("# HELP citus_query_latency_ms per-query-family "
                   "latency (internal name: query_latency_ms)")
        out.append("# TYPE citus_query_latency_ms histogram")
        for family, hist in fams:
            lab = _label(family)
            cum = 0
            for bound, n in zip(hist.BOUNDS_MS, hist.counts):
                cum += n
                out.append(f'citus_query_latency_ms_bucket'
                           f'{{family="{lab}",le="{bound:g}"}} {cum}')
            out.append(f'citus_query_latency_ms_bucket'
                       f'{{family="{lab}",le="+Inf"}} {hist.count}')
            out.append(f'citus_query_latency_ms_sum{{family="{lab}"}} '
                       f'{hist.sum_ms:.3f}')
            out.append(f'citus_query_latency_ms_count{{family="{lab}"}} '
                       f'{hist.count}')
    return "\n".join(out) + "\n"


def prometheus_cluster_text(cluster, payloads=None) -> str:
    """Cluster-wide exposition: the stat fan-out's merged payloads as
    node-labeled series (SELECT citus_cluster_metrics(), and what
    scripts/metrics_exporter.py serves in cluster mode).  Unreachable
    peers surface as citus_node_unreachable{node=...} 1 — the scrape
    itself never fails on a dead node."""
    from citus_tpu.observability.cluster_stats import (
        cluster_node_stats, payload_node,
    )
    if payloads is None:
        payloads = cluster_node_stats(cluster)
    out = []
    reachable = [p for p in payloads if not p.get("unreachable")]

    counter_names = sorted({n for p in reachable
                            for n in p.get("counters", {})})
    for name in counter_names:
        series = f"citus_{name}_total"
        out.append(_help_line(name, series))
        out.append(f"# TYPE {series} counter")
        for p in reachable:
            if name in p.get("counters", {}):
                out.append(f'{series}{{node="{payload_node(p)}"}} '
                           f'{p["counters"][name]}')

    gauge_names = sorted({n for p in reachable for n in p.get("gauges", {})})
    for name in gauge_names:
        series = f"citus_{name}"
        out.append(_help_line(name, series))
        out.append(f"# TYPE {series} gauge")
        for p in reachable:
            if name in p.get("gauges", {}):
                out.append(f'{series}{{node="{payload_node(p)}"}} '
                           f'{p["gauges"][name]}')

    out.append("# HELP citus_node_unreachable 1 when the stat fan-out "
               "could not reach the node within citus.stat_fanout_timeout_s")
    out.append("# TYPE citus_node_unreachable gauge")
    for p in payloads:
        out.append(f'citus_node_unreachable{{node="{payload_node(p)}"}} '
                   f'{1 if p.get("unreachable") else 0}')

    # in-flight background-task byte progress, node-attributed (the
    # Prometheus face of get_rebalance_progress)
    prog = [(payload_node(p), t) for p in reachable
            for t in p.get("progress", []) if t.get("status") == "running"]
    if prog:
        for series, key in (("citus_task_bytes_done", "bytes_done"),
                            ("citus_task_bytes_total", "bytes_total")):
            out.append(f"# HELP {series} background task progress "
                       f"({key} of the running move/split)")
            out.append(f"# TYPE {series} gauge")
            for node, t in prog:
                out.append(
                    f'{series}{{node="{node}",task_id="{t["task_id"]}",'
                    f'op="{_label(str(t.get("op", "")))}",'
                    f'phase="{_label(str(t.get("phase", "")))}"}} '
                    f'{int(t.get(key) or 0)}')
    return "\n".join(out) + "\n"


def _gauges(cluster) -> dict:
    from citus_tpu.executor.admission import GLOBAL_POOL
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
    from citus_tpu.observability.slowlog import GLOBAL_SLOW_LOG
    from citus_tpu.workload.scheduler import GLOBAL_SCHEDULER
    mv = GLOBAL_CACHE.memory_view()
    pool = GLOBAL_POOL.stats()
    sched = GLOBAL_SCHEDULER.rows_view()
    g = {
        "kernel_cache_entries": len(GLOBAL_KERNELS),
        "plan_cache_entries": len(cluster._plan_cache),
        "device_cache_bytes": int(mv["live_bytes"]),
        "device_cache_high_water_bytes": int(mv["high_water_bytes"]),
        "device_cache_capacity_bytes": int(GLOBAL_CACHE.capacity),
        "slow_log_entries": len(GLOBAL_SLOW_LOG),
        "live_queries": len(cluster.activity.rows_view()),
        # admission saturation as proper gauges (the counters above are
        # cumulative; operators watching a scrape need the level)
        "pool_in_use": int(pool["in_use"]),
        "pool_high_water": int(pool["high_water"]),
        "tenant_queued": int(sum(r[2] for r in sched)),
    }
    # health engine: one 0/1-or-more gauge per declared event kind
    # (each kind spelled out — the CNT04 contract with the declaration
    # in observability/flight_recorder.py)
    rec = getattr(cluster, "flight_recorder", None)
    active = rec.active_counts() if rec is not None else {}
    g["health_p99_regression"] = active.get("p99_regression", 0)
    g["health_shed_rate_spike"] = active.get("shed_rate_spike", 0)
    g["health_catchup_stall"] = active.get("catchup_stall", 0)
    g["health_pool_saturation"] = active.get("pool_saturation", 0)
    g["health_dead_node"] = active.get("dead_node", 0)
    g["health_metadata_sync_lag"] = active.get("metadata_sync_lag", 0)
    g["health_autopilot_action"] = active.get("autopilot_action", 0)
    return g


def _family_histograms(cluster) -> list[tuple]:
    stats = cluster.query_stats.histograms_view()
    stats.sort(key=lambda kv: -kv[1].count)
    return stats[:TOP_FAMILIES]
