"""Cluster flight recorder: continuous metric history + health events.

Every observability surface built so far (counters, wait events, the
``citus_stat_*`` views, node-labeled Prometheus) answers "what is the
value NOW".  This module adds the time axis: a per-node background
sampler that every ``citus.flight_recorder_interval_ms`` snapshots the
whole counter plane — counter values, wait-event ms, admission-pool
occupancy, tenant queue depths/shed counts, device-cache residency and
the merged query p99 — into

  * a fixed-size in-memory ring (the working set behind
    ``citus_stat_history(metric [, since_s])``), and
  * a bounded, segment-rotated on-disk log under
    ``<data_dir>/flight_recorder/`` (retention
    ``citus.flight_recorder_retention_s``) for post-mortems that
    outlive the process.

On top of the ring sits a small health engine: EWMA baselines per
watched signal and typed, deduplicated events (``citus_health_events()``
and per-kind Prometheus gauges).  Saturation events double as an
advisory signal — the tenant scheduler sheds earlier while
``ADVISORY.pool_saturated`` is raised (workload/scheduler.py).

Threading: one sampler thread per Cluster, started/stopped with the
GUC (``apply()``) and joined on ``Cluster.close()``.  ``run_once()`` is
the synchronous test hook, exactly like services/maintenance.py.  Lock
order: the sampler reads StatCounters/pool/scheduler snapshots (their
own locks) BEFORE taking ``self._mu``; the counters-reset hook
(``reset_baselines``) is invoked by StatCounters.reset() after the
counter lock is released, so the two locks never nest in either order.
"""

from __future__ import annotations

import collections
import json
import os
import threading

from citus_tpu.utils.clock import now as wall_now

# Typed health-event kinds (the CNT03-style single declaration; lint
# rule CNT04 checks each kind has a Prometheus gauge in export.py, a
# row type in commands/utility.py, and a real emit site).
HEALTH_EVENT_KINDS = {
    "p99_regression": "merged query p99 far above its EWMA baseline",
    "shed_rate_spike": "tenant sheds per tick far above baseline",
    "catchup_stall": "shard-move CDC catch-up looping without converging",
    "pool_saturation": "admission pool pinned at its configured limit",
    "dead_node": "stat fan-out probe found an unreachable endpoint",
    "metadata_sync_lag": "coordinator's catalog trailing the authority "
                         "across consecutive sync rounds",
    "autopilot_action": "autopilot executed (or observed) a rebalance "
                        "action for a sustained hot placement",
}

RING_SAMPLES = 512        # in-memory history ring (per node)
EVENTS_MAX = 256          # retained health-event log entries
PAYLOAD_SAMPLES = 60      # ring tail shipped per get_node_stats payload

# Health-engine thresholds (engine constants, not GUCs: they describe
# what "anomalous" means, not per-deployment policy).
EWMA_ALPHA = 0.3
P99_WARMUP_TICKS = 5      # baseline ticks before p99 alerts can fire
P99_FACTOR = 3.0          # alert when p99 > factor * baseline ...
P99_FLOOR_MS = 5.0        # ... and above an absolute floor
SHED_SPIKE_MIN = 5        # sheds in one tick before a spike can fire
SHED_SPIKE_FACTOR = 4.0   # vs the EWMA of per-tick sheds
CATCHUP_STALL_TICKS = 5   # consecutive ticks with catch-up rounds
SATURATION_TICKS = 3      # consecutive ticks pinned at the pool limit


def _counters():
    from citus_tpu.executor.executor import GLOBAL_COUNTERS
    return GLOBAL_COUNTERS


class _Advisory:
    """Process-wide advisory flags the health engine raises for other
    subsystems (plain bool attributes: single-writer, torn reads are
    impossible for bools, and readers only ever branch on them)."""

    def __init__(self) -> None:
        self.pool_saturated = False


ADVISORY = _Advisory()


class FlightRecorder:
    """Per-node sampler ring + segment-rotated disk log + health engine."""

    def __init__(self, cluster, data_dir: str) -> None:
        self._cluster = cluster
        self._dir = os.path.join(data_dir, "flight_recorder")
        self._mu = threading.Lock()
        self._io_mu = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        # sampled state (under _mu)
        self._ring = collections.deque(maxlen=RING_SAMPLES)
        self._epoch = 0
        # health state (under _mu)
        self._events = collections.deque(maxlen=EVENTS_MAX)
        self._active = {}          # (kind, subject) -> first-seen ts
        self._ewma = {}            # signal -> EWMA baseline
        self._warm = {}            # signal -> ticks observed
        self._consec = {}          # signal -> consecutive anomalous ticks
        self._prev_counters = {}   # last tick's counter snapshot
        # disk segment state (under _io_mu)
        self._seg_path = None
        self._seg_ts = 0.0

    # ------------------------------------------------------- lifecycle

    def apply(self) -> None:
        """Start or stop the sampler to match the current GUC value
        (the SET citus.flight_recorder_interval_ms side-effect hook)."""
        if self._interval_ms() > 0:
            self.start()
        else:
            self.stop()

    def _interval_ms(self) -> float:
        obs = self._cluster.settings.observability
        return float(obs.flight_recorder_interval_ms)

    def _retention_s(self) -> float:
        obs = self._cluster.settings.observability
        return max(1.0, float(obs.flight_recorder_retention_s))

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="citus-flight-recorder")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            interval = self._interval_ms()
            if interval <= 0:
                break
            try:
                self.run_once()
            except Exception:  # lint: disable=SWL01 -- a failed tick must not kill the sampler; the error counter is the signal
                _counters().bump("flight_recorder_errors", 1)
            self._stop.wait(timeout=interval / 1000.0)

    # -------------------------------------------------------- sampling

    def run_once(self) -> None:
        """One sampler tick: collect, ring-append, health-check, spill."""
        ts = wall_now()
        with self._mu:
            epoch = self._epoch
        metrics = self._collect()
        with self._mu:
            if self._epoch != epoch:
                return  # counters were reset mid-tick; drop the sample
            self._ring.append((ts, metrics))
            self._health_tick_locked(ts, metrics)
        self._spill(ts, metrics)
        _counters().bump("flight_recorder_ticks", 1)

    def _collect(self) -> dict:
        """Snapshot every watched plane into one flat {metric: number}
        dict.  Reads each subsystem under ITS lock, never ours."""
        from citus_tpu.executor.admission import GLOBAL_POOL
        from citus_tpu.executor.device_cache import GLOBAL_CACHE
        from citus_tpu.stats import LatencyHistogram
        from citus_tpu.workload.scheduler import GLOBAL_SCHEDULER
        cl = self._cluster
        m = dict(cl.counters.snapshot())
        pool = GLOBAL_POOL.stats()
        m["pool_in_use"] = pool["in_use"]
        m["pool_high_water"] = pool["high_water"]
        rows = GLOBAL_SCHEDULER.rows_view()
        m["tenant_queued"] = sum(r[2] for r in rows)
        m["tenant_shed_total"] = sum(r[4] for r in rows)
        mv = GLOBAL_CACHE.memory_view()
        m["device_cache_bytes"] = mv["live_bytes"]
        m["device_cache_high_water_bytes"] = mv["high_water_bytes"]
        m["live_queries"] = len(cl.activity.rows_view())
        agg = LatencyHistogram()
        for _q, h in cl.query_stats.histograms_view():
            agg.count += h.count
            agg.sum_ms += h.sum_ms
            for i, c in enumerate(h.counts):
                agg.counts[i] += c
        m["query_p99_ms"] = round(agg.percentile(0.99), 3) if agg.count \
            else 0.0
        # per-placement attribution: advance the EWMA rates on the
        # sampler's cadence and ring the top placements so
        # citus_stat_history('shard_load:...') rates work like any
        # other counter series
        from citus_tpu.observability.load_attribution import (
            GLOBAL_ATTRIBUTION,
        )
        GLOBAL_ATTRIBUTION.tick()
        m.update(GLOBAL_ATTRIBUTION.ring_metrics())
        return m

    # --------------------------------------------------- health engine

    def _health_tick_locked(self, ts: float, m: dict) -> None:
        self._check_p99_locked(ts, m)
        self._check_shed_locked(ts, m)
        self._check_catchup_locked(ts, m)
        self._check_saturation_locked(ts, m)
        self._prev_counters = m

    def _check_p99_locked(self, ts: float, m: dict) -> None:
        v = float(m.get("query_p99_ms", 0.0))
        base = self._ewma.get("p99", 0.0)
        warm = self._warm.get("p99", 0)
        active = ("p99_regression", "cluster") in self._active
        if warm >= P99_WARMUP_TICKS and v > P99_FACTOR * max(base, 0.001) \
                and v > P99_FLOOR_MS:
            self._emit_locked("p99_regression", "cluster", v, base, ts,
                              f"p99 {v:.1f}ms vs baseline {base:.1f}ms")
            return  # freeze the baseline while the regression is live
        if active:
            if v <= P99_FACTOR * max(base, 0.001) or v <= P99_FLOOR_MS:
                self._resolve_locked("p99_regression", "cluster")
            else:
                return
        self._ewma["p99"] = v if warm == 0 \
            else base + EWMA_ALPHA * (v - base)
        self._warm["p99"] = warm + 1

    def _check_shed_locked(self, ts: float, m: dict) -> None:
        prev = self._prev_counters.get("tenant_shed")
        if prev is None:
            return
        delta = max(0, int(m.get("tenant_shed", 0)) - int(prev))
        base = self._ewma.get("shed", 0.0)
        if delta >= SHED_SPIKE_MIN and delta > SHED_SPIKE_FACTOR * base:
            self._emit_locked(
                "shed_rate_spike", "cluster", delta, base, ts,
                f"{delta} sheds this tick vs EWMA {base:.2f}")
        elif delta == 0:
            self._resolve_locked("shed_rate_spike", "cluster")
        self._ewma["shed"] = base + EWMA_ALPHA * (delta - base)

    def _check_catchup_locked(self, ts: float, m: dict) -> None:
        prev = self._prev_counters.get("shard_move_catchup_rounds")
        delta = 0 if prev is None \
            else int(m.get("shard_move_catchup_rounds", 0)) - int(prev)
        n = self._consec.get("catchup", 0) + 1 if delta > 0 else 0
        self._consec["catchup"] = n
        if n >= CATCHUP_STALL_TICKS:
            self._emit_locked(
                "catchup_stall", "cluster", n, CATCHUP_STALL_TICKS, ts,
                f"catch-up rounds advanced {n} ticks in a row")
        elif n == 0:
            self._resolve_locked("catchup_stall", "cluster")

    def _check_saturation_locked(self, ts: float, m: dict) -> None:
        limit = int(self._cluster.settings.executor.max_shared_pool_size)
        in_use = int(m.get("pool_in_use", 0))
        pinned = limit > 0 and in_use >= limit
        n = self._consec.get("saturation", 0) + 1 if pinned else 0
        self._consec["saturation"] = n
        if n >= SATURATION_TICKS:
            self._emit_locked(
                "pool_saturation", "admission_pool", in_use, limit, ts,
                f"pool pinned at {in_use}/{limit} for {n} ticks")
            ADVISORY.pool_saturated = True
        elif n == 0:
            self._resolve_locked("pool_saturation", "admission_pool")
            ADVISORY.pool_saturated = False

    def note_dead_node(self, endpoint: str) -> None:
        """Stat fan-out observed an unreachable endpoint (called from
        observability/cluster_stats.py on probe failure)."""
        with self._mu:
            self._emit_locked("dead_node", endpoint, 1, 0, wall_now(),
                              "get_node_stats probe failed")

    def clear_dead_node(self, endpoint: str) -> None:
        with self._mu:
            self._resolve_locked("dead_node", endpoint)

    def resolve_event(self, kind: str, subject: str) -> None:
        """Public resolve door for externally-raised kinds (the metadata
        sync engine clears its own metadata_sync_lag once a round
        converges; dead_node has its dedicated pair above)."""
        with self._mu:
            self._resolve_locked(kind, subject)

    def emit_event(self, kind: str, subject: str, value, baseline,
                   detail: str) -> None:
        """Public emit door (deduplicated: one event per (kind, subject)
        until the condition resolves)."""
        with self._mu:
            self._emit_locked(kind, subject, value, baseline, wall_now(),
                              detail)

    def _emit_locked(self, kind, subject, value, baseline, ts, detail):
        if kind not in HEALTH_EVENT_KINDS:
            raise ValueError(f"unknown health-event kind: {kind}")
        if (kind, subject) in self._active:
            return
        self._active[(kind, subject)] = ts
        self._events.append({
            "ts": round(float(ts), 3), "kind": kind, "subject": subject,
            "value": value, "baseline": baseline, "detail": detail,
        })
        # bump via a daemon thread-safe counter; StatCounters locks
        # internally and never calls back into the recorder
        _counters().bump("health_events_emitted", 1)

    def _resolve_locked(self, kind, subject):
        self._active.pop((kind, subject), None)

    # ----------------------------------------------------------- views

    def history_rows(self, metric=None, since_s=None, limit=None):
        """(ts, metric, value, rate) rows from the ring; ``rate`` is the
        per-second delta vs the previous tick (None on the first)."""
        with self._mu:
            samples = list(self._ring)
        rate_base_only = False
        if limit is not None and len(samples) > limit:
            samples = samples[-(limit + 1):]  # extra one is the rate base
            rate_base_only = True
        cutoff = None if since_s is None else wall_now() - float(since_s)
        rows = []
        prev_ts, prev_m = None, None
        for idx, (ts, m) in enumerate(samples):
            dt = None if prev_ts is None else max(ts - prev_ts, 1e-9)
            emit = not (rate_base_only and idx == 0) \
                and (cutoff is None or ts >= cutoff)
            if emit:
                for name in sorted(m):
                    if metric is not None and name != metric:
                        continue
                    rate = None
                    if dt is not None and name in prev_m:
                        rate = round((m[name] - prev_m[name]) / dt, 3)
                    rows.append([round(ts, 3), name, m[name], rate])
            prev_ts, prev_m = ts, m
        return rows

    def events_rows(self):
        """[ts, kind, subject, value, baseline, detail, active] rows,
        oldest first."""
        with self._mu:
            return [[e["ts"], e["kind"], e["subject"], e["value"],
                     e["baseline"], e["detail"],
                     (e["kind"], e["subject"]) in self._active]
                    for e in self._events]

    def active_counts(self) -> dict:
        """{kind: number of currently-active events} for the Prometheus
        health gauges (zero-filled over every declared kind)."""
        out = {k: 0 for k in HEALTH_EVENT_KINDS}
        with self._mu:
            for kind, _subject in self._active:
                out[kind] += 1
        return out

    def export_payload(self) -> dict:
        """JSON-safe slice for the get_node_stats fan-out: the ring tail
        plus the health-event log."""
        return {
            "history": self.history_rows(limit=PAYLOAD_SAMPLES),
            "health": self.events_rows(),
        }

    # ---------------------------------------------------- reset seam

    def reset_baselines(self) -> None:
        """Counters-reset hook (StatCounters.add_reset_hook): drop the
        ring and every EWMA/consecutive-tick baseline so post-reset
        samples never difference against pre-reset values (no huge
        negative rates).  The health-event LOG survives — events are
        history, not derived state."""
        with self._mu:
            self._epoch += 1
            self._ring.clear()
            self._ewma.clear()
            self._warm.clear()
            self._consec.clear()
            self._prev_counters = {}

    # ------------------------------------------------------ disk spill

    def _spill(self, ts: float, metrics: dict) -> None:
        """Append this tick to the current on-disk segment, rotating and
        pruning by retention.  All recorder disk writes funnel through
        append_segment_line (CONF01-confined to this module)."""
        line = json.dumps({"ts": round(ts, 3), "m": metrics},
                          separators=(",", ":"))
        with self._io_mu:
            retention = self._retention_s()
            seg_age = ts - self._seg_ts
            if self._seg_path is None or seg_age > max(retention / 4, 1.0):
                self._rotate_io_locked(ts, retention)
            self.append_segment_line(line)

    def _rotate_io_locked(self, ts: float, retention: float) -> None:
        os.makedirs(self._dir, exist_ok=True)
        self._seg_path = os.path.join(
            self._dir, f"seg_{int(ts * 1000)}.jsonl")
        self._seg_ts = ts
        for name in sorted(os.listdir(self._dir)):
            if not (name.startswith("seg_") and name.endswith(".jsonl")):
                continue
            try:
                start_ms = int(name[4:-6])
            except ValueError:
                continue
            if ts - start_ms / 1000.0 > retention:
                try:
                    os.unlink(os.path.join(self._dir, name))
                except OSError:
                    break  # segment vanished or dir mutated under us
        _counters().bump("flight_recorder_rotations", 1)

    def append_segment_line(self, line: str) -> None:
        """The single disk-write door for recorder segments (the
        confined-method table in tools/cituslint pins all recorder disk
        writes to this module)."""
        with open(self._seg_path, "a", encoding="utf-8") as fh:
            fh.write(line + "\n")

    def segment_files(self):
        """Sorted on-disk segment paths (test/inspection helper)."""
        if not os.path.isdir(self._dir):
            return []
        return [os.path.join(self._dir, n)
                for n in sorted(os.listdir(self._dir))
                if n.startswith("seg_") and n.endswith(".jsonl")]
