"""SET/SHOW (GUC analog), ANALYZE, REINDEX, RETURNING evaluation, and
extended-statistics ndistinct computation.

Reference: the ~139 citus.* GUCs (shared_library_init.c:980+) with PG
unit parsing and transactional SET rollback; commands/vacuum.c ANALYZE;
commands/index.c REINDEX.
"""

from __future__ import annotations

from typing import Optional

from citus_tpu.errors import CatalogError
from citus_tpu.executor import Result
from citus_tpu.planner import ast as A

from citus_tpu.cluster import _eval_const, _expand_returning_items  # noqa: E402


def _remote_task_mode(v) -> str:
    """citus.remote_task_execution = push | pull | auto."""
    s = str(v).lower()
    if s not in ("push", "pull", "auto"):
        raise ValueError(s)
    return s


def _autopilot_mode(v) -> str:
    """citus.autopilot = off | observe | on.  The SET parser coerces
    bare on/off to booleans before coercion sees them."""
    if isinstance(v, bool):
        return "on" if v else "off"
    s = str(v).lower()
    if s not in ("off", "observe", "on"):
        raise ValueError(s)
    return s


def _wire_format(v) -> str:
    """citus.wire_format = frame | npz (net/data_plane.py codecs)."""
    s = str(v).lower()
    if s not in ("frame", "npz"):
        raise ValueError(s)
    return s


def _plan_cache_mode(v) -> str:
    """citus.plan_cache_mode = auto | force_generic | force_custom
    (reference: the plancache.c GUC of the same name)."""
    s = str(v).lower()
    if s not in ("auto", "force_generic", "force_custom"):
        raise ValueError(s)
    return s


def _window_ms(v) -> float:
    """citus.megabatch_window_ms = <ms> | auto (stored as -1)."""
    if str(v).lower() == "auto":
        return -1.0
    return float(v)


def _slots_or_auto(v) -> int:
    """citus.hash_agg_slots / citus.direct_gid_limit = <slots> | auto
    (the default, stored as 0: the hash table is sized at execution from
    the plan's key domain, catalog row-count stats and the device's free
    memory; the direct table's bound is left to the plan,
    planner/physical.py choose_group_mode)."""
    if str(v).lower() == "auto":
        return 0
    n = int(v)
    if n < 0:
        raise ValueError(v)
    return n


def _sample_rate(v) -> float:
    """citus.trace_sample_rate = 0.0 .. 1.0."""
    f = float(v)
    if not 0.0 <= f <= 1.0:
        raise ValueError(v)
    return f


def _percentile_backend(v) -> str:
    """citus.percentile_backend = ddsketch | tdigest (the sketch kind
    approx_percentile rollup columns store, rollup/sketches.py)."""
    s = str(v).lower()
    if s not in ("ddsketch", "tdigest"):
        raise ValueError(s)
    return s


def _compute_ndistinct(cl, table: str, columns: list) -> int:
    """count(DISTINCT (cols)) — the extended-statistics ndistinct."""
    sel = A.Select(
        [A.SelectItem(A.FuncCall("count", (A.Star(),)))],
        A.SubqueryRef(A.Select(
            [A.SelectItem(A.ColumnRef(c)) for c in columns],
            A.TableRef(table), distinct=True), "d"))
    return int(cl._execute_stmt(sel).rows[0][0])

#: SET/SHOW surface: GUC name -> (settings section, field, coercion)
#: (reference: the citus.* GUCs, shared_library_init.c:980+).
#: Settings apply to this Cluster handle (every session of it).
_GUCS = {
    "citus.task_executor_backend": ("executor", "task_executor_backend", str),
    "citus.max_shared_pool_size": ("executor", "max_shared_pool_size", int),
    # per-node remote-task RPC window cap (slow-start ramp target,
    # executor/pipeline.py); formerly aliased the device in-flight
    # window, which now has its own name below
    "citus.max_adaptive_executor_pool_size": ("executor", "max_adaptive_pool_size", int),
    "citus.max_tasks_in_flight": ("executor", "max_tasks_in_flight", int),
    # host read-ahead queue depth for the decode thread; 0 = inline
    "citus.executor_prefetch_depth": ("executor", "executor_prefetch_depth", int),
    # native stripe read+decompress pool width; 0 = auto
    # (min(8, cpu_count), storage/reader.py)
    "citus.decode_threads": ("executor", "decode_threads", int),
    "citus.use_secondary_nodes": ("executor", "use_secondary_nodes", "secondary"),
    "citus.remote_task_execution": ("executor", "remote_task_execution", _remote_task_mode),
    # wire codec for execute_task results / placement bundles: the
    # zero-copy columnar frame (default) or the legacy npz container
    "citus.wire_format": ("executor", "wire_format", _wire_format),
    # query-family compile amortization (executor/kernel_cache.py,
    # planner/auto_param.py)
    "citus.plan_cache_mode": ("planner", "plan_cache_mode", _plan_cache_mode),
    "citus.kernel_cache_size": ("executor", "kernel_cache_size", int),
    # same-family query coalescing (executor/megabatch.py): dispatch
    # window (ms; 0 = off, byte-identical serial path) and per-batch
    # occupancy bound
    "citus.megabatch_window_ms": ("executor", "megabatch_window_ms", _window_ms),
    "citus.megabatch_max_size": ("executor", "megabatch_max_size", int),
    # multi-tenant admission defaults (workload/scheduler.py): fair-
    # share weight for unregistered tenants, per-tenant queue bound
    # (0 = unbounded) and sustained-QPS token bucket (0 = unlimited)
    "citus.tenant_default_weight": ("workload", "tenant_default_weight", float),
    "citus.tenant_queue_depth": ("workload", "tenant_queue_depth", int),
    "citus.tenant_rate_limit_qps": ("workload", "tenant_rate_limit_qps", float),
    # priority class for tenants without an explicit class (the
    # two-level stride tree's fallback node, workload/scheduler.py)
    "citus.tenant_default_priority_class": ("workload",
                                            "tenant_default_priority_class",
                                            str),
    # multi-coordinator metadata sync (metadata/sync.py): background
    # pull-on-mismatch cadence (ms; 0 = loop off, sync still runs at
    # invalidation + citus_sync_metadata()) and the incremental-sync
    # master switch (off = full-document fetch per invalidation)
    "citus.metadata_sync_interval_ms": ("metadata",
                                        "metadata_sync_interval_ms",
                                        float),
    "citus.enable_metadata_sync": ("metadata", "enable_metadata_sync",
                                   "bool"),
    # distributed tracing (observability/): span-tree sampling rate,
    # slow-query force-capture threshold (ms; -1 off), Chrome-trace
    # export directory ("" off)
    "citus.trace_sample_rate": ("observability", "trace_sample_rate", _sample_rate),
    "citus.log_min_duration_ms": ("observability", "log_min_duration_ms", float),
    "citus.trace_export_dir": ("observability", "trace_export_dir", str),
    "citus.stat_fanout_timeout_s": ("observability", "stat_fanout_timeout_s",
                                    float),
    # cluster flight recorder (observability/flight_recorder.py):
    # background sampling cadence (ms; 0 = recorder off) and on-disk
    # segment retention (seconds)
    "citus.flight_recorder_interval_ms": ("observability",
                                          "flight_recorder_interval_ms",
                                          float),
    "citus.flight_recorder_retention_s": ("observability",
                                          "flight_recorder_retention_s",
                                          float),
    # autopilot control loop (services/autopilot.py): mode switch plus
    # its hysteresis knobs — evaluation cadence, consecutive-tick
    # sustain requirement, post-action cooldown, and the greedy
    # balance trigger threshold
    "citus.autopilot": ("autopilot", "mode", _autopilot_mode),
    "citus.autopilot_interval_s": ("autopilot", "interval_s", float),
    "citus.autopilot_sustain_ticks": ("autopilot", "sustain_ticks", int),
    "citus.autopilot_cooldown_s": ("autopilot", "cooldown_s", float),
    "citus.autopilot_threshold": ("autopilot", "threshold", float),
    # continuous aggregation (rollup/): refresh-loop cadence (ms; 0 =
    # loop off, refresh via citus_refresh_rollups()), percentile sketch
    # backend for NEW rollups, and the per-batch source-row bound
    "citus.rollup_refresh_interval_ms": ("rollup",
                                         "rollup_refresh_interval_ms",
                                         float),
    "citus.percentile_backend": ("rollup", "percentile_backend",
                                 _percentile_backend),
    "citus.rollup_max_batch_rows": ("rollup", "rollup_max_batch_rows",
                                    int),
    "citus.enable_rollup_routing": ("rollup", "enable_rollup_routing",
                                    "bool"),
    "citus.enable_repartition_joins": ("planner", "enable_repartition_joins", "bool"),
    "citus.shard_count": ("sharding", "shard_count", int),
    "citus.shard_replication_factor": ("sharding", "shard_replication_factor", int),
    # non-blocking shard moves (operations/shard_transfer.py): lag bar
    # the catch-up loop must get under before taking the write lock,
    # the bound on catch-up rounds, and whether the source placement
    # drop is deferred to the cleaner or done inline after the flip
    "citus.shard_move_catchup_threshold": ("sharding", "shard_move_catchup_threshold", int),
    "citus.shard_move_max_catchup_rounds": ("sharding", "shard_move_max_catchup_rounds", int),
    "citus.defer_drop_after_shard_move": ("sharding", "defer_drop_after_shard_move", "bool"),
    "citus.enable_change_data_capture": (None, "enable_change_data_capture", "bool"),
    "citus.distributed_deadlock_detection_interval": (None, "deadlock_detection_interval_s", float),
    # every settings field the code reads is SET/SHOW-reachable
    # (cituslint GUC01): batch floor below which shards merge into one
    # device dispatch, router fast-path shard cap, GROUP BY hash-slot
    # budget, repartition-join fanout, and the maintenance/authority
    # daemon knobs
    "citus.executor_min_batch_rows": ("executor", "min_batch_rows", int),
    "citus.direct_gid_limit": ("planner", "direct_gid_limit", _slots_or_auto),
    "citus.hash_agg_slots": ("planner", "hash_agg_slots", _slots_or_auto),
    "citus.repartition_bucket_count_per_device": ("planner", "repartition_bucket_count_per_device", int),
    "citus.start_maintenance_daemon": (None, "start_maintenance_daemon", "bool"),
    "citus.authority_watch_interval": (None, "authority_watch_interval_s", float),
    # PostgreSQL spelling: bare numbers are MILLISECONDS; unit
    # suffixes ('3s', '500ms') accepted
    "lock_timeout": ("executor", "lock_timeout_s", "ms_duration"),
}

def _guc_key(cl, name: str) -> str:
    name = name.lower()
    if name in _GUCS:
        return name
    if f"citus.{name}" in _GUCS:
        return f"citus.{name}"
    raise CatalogError(f'unrecognized configuration parameter "{name}"')

def _execute_set(cl, stmt: A.SetConfig) -> Result:
    import dataclasses as _dc
    key = _guc_key(cl, stmt.name)
    section, field_, coerce = _GUCS[key]
    v = stmt.value
    if coerce == "bool":
        if not isinstance(v, bool):
            s = str(v).lower()
            if s in ("true", "on", "1", "yes"):
                v = True
            elif s in ("false", "off", "0", "no"):
                v = False
            else:
                raise CatalogError(
                    f'parameter "{stmt.name}" requires a Boolean '
                    f"value (got {stmt.value!r})")
    elif coerce == "secondary":
        # PostgreSQL spelling: citus.use_secondary_nodes = always|never
        if isinstance(v, bool):
            pass
        elif str(v).lower() in ("always", "never"):
            v = str(v).lower() == "always"
        else:
            raise CatalogError(
                f'invalid value for parameter "{stmt.name}": '
                f"{stmt.value!r} (expected always or never)")
    elif coerce == "ms_duration":
        # bare numbers are milliseconds (PostgreSQL); 's'/'ms'
        # suffixes accepted
        s = str(v).strip().lower()
        try:
            if s.endswith("ms"):
                v = float(s[:-2]) / 1000.0
            elif s.endswith("s"):
                v = float(s[:-1])
            else:
                v = float(s) / 1000.0
        except ValueError:
            raise CatalogError(
                f'invalid value for parameter "{stmt.name}": '
                f"{stmt.value!r}")
    else:
        try:
            v = coerce(v)
        except (TypeError, ValueError):
            raise CatalogError(
                f'invalid value for parameter "{stmt.name}": {stmt.value!r}')
    from citus_tpu.storage.overlay import current_overlay
    txn = current_overlay()
    if txn is not None:
        # PostgreSQL: a non-LOCAL SET is undone if the transaction
        # aborts
        prev_settings, prev_cdc = cl.settings, cl.cdc.enabled

        def _restore(prev_settings=prev_settings, prev_cdc=prev_cdc):
            cl.settings = prev_settings
            cl.cdc.enabled = prev_cdc
            cl._plan_cache.clear()
        txn.on_rollback.append(_restore)
    if section is None:
        cl.settings = _dc.replace(cl.settings, **{field_: v})
    else:
        sec = _dc.replace(getattr(cl.settings, section), **{field_: v})
        cl.settings = _dc.replace(cl.settings, **{section: sec})
    if key == "citus.enable_change_data_capture":
        cl.cdc.enabled = bool(v)
    elif key == "citus.kernel_cache_size":
        from citus_tpu.executor.kernel_cache import GLOBAL_KERNELS
        GLOBAL_KERNELS.set_capacity(int(v))
    elif key == "citus.trace_export_dir":
        # the kernels' instruction -> scope maps go BESIDE the span
        # directory: its readers list it, and who empties it removes
        # files
        from citus_tpu.executor.kernel_cache import follow_kernel_scopes
        follow_kernel_scopes(v + ".kernels" if v else None)
    elif key == "citus.decode_threads":
        from citus_tpu.storage.reader import set_decode_threads
        set_decode_threads(int(v))
    elif key == "citus.flight_recorder_interval_ms":
        cl.flight_recorder.apply()  # start/stop the sampler to match
    elif key == "citus.rollup_refresh_interval_ms":
        cl.rollup_manager.apply()  # start/stop the refresh loop
    elif key in ("citus.metadata_sync_interval_ms",
                 "citus.enable_metadata_sync"):
        cl.metadata_sync.apply()  # start/stop the sync loop to match
    cl._plan_cache.clear()  # backend/knob changes invalidate plans
    return Result(columns=[], rows=[])

def _guc_value(cl, key: str) -> str:
    section, field_, coerce = _GUCS[key]
    v = getattr(cl.settings, field_) if section is None \
        else getattr(getattr(cl.settings, section), field_)
    if coerce == "secondary":
        return "always" if v else "never"
    if isinstance(v, bool):
        return "on" if v else "off"  # PostgreSQL boolean rendering
    if coerce == "ms_duration":
        return f"{v * 1000:g}ms"
    return str(v)

def _execute_show(cl, stmt: A.ShowConfig) -> Result:
    if stmt.name.lower() == "citus.metrics":
        # SHOW citus.metrics: the Prometheus text exposition, one row
        # per line (scripts/metrics_exporter.py serves the same text)
        from citus_tpu.observability.export import prometheus_text
        return Result(columns=["metrics"],
                      rows=[(line,) for line in
                            prometheus_text(cl).splitlines()])
    if stmt.name == "all":
        rows = [(k, _guc_value(cl, k)) for k in sorted(_GUCS)]
        return Result(columns=["name", "setting"], rows=rows)
    key = _guc_key(cl, stmt.name)
    return Result(columns=[stmt.name], rows=[(_guc_value(cl, key),)])

def _execute_analyze(cl, table: Optional[str]) -> Result:
    """ANALYZE [table]: recompute extended-statistics ndistinct
    (column min/max stats are always skip-list-live here, so there
    is no per-column histogram pass to run)."""
    if table is not None:
        cl.catalog.table(table)  # PostgreSQL: unknown relation errors
    refreshed = 0
    for name, st in cl.catalog.statistics.items():
        if table is not None and st["table"] != table:
            continue
        if not cl.catalog.has_table(st["table"]):
            continue
        st["ndistinct"] = _compute_ndistinct(cl, st["table"],
                                                  st["columns"])
        refreshed += 1
    if refreshed:
        cl.catalog.commit()
    return Result(columns=[], rows=[],
                  explain={"statistics_refreshed": refreshed})

def _execute_reindex(cl, stmt: A.Reindex) -> Result:
    """REINDEX INDEX name | REINDEX TABLE name: rebuild segment
    files from the stripe data (recovers from lost/corrupted
    segments; a missing segment is only a slow path, never wrong)."""
    from citus_tpu.storage.index import backfill_index
    from citus_tpu.transaction.locks import EXCLUSIVE
    if stmt.kind == "index":
        t, ix = cl._find_index(stmt.name)
        if ix is None:
            raise CatalogError(f'index "{stmt.name}" does not exist')
        targets = [(t, [ix["column"]])]
    else:
        t = cl.catalog.table(stmt.name)
        if t.is_partitioned:
            targets = [(p, p.index_columns)
                       for p in cl.catalog.partitions_of(t.name)
                       if p.indexes]
        else:
            targets = [(t, t.index_columns)] if t.indexes else []
    rebuilt = 0
    for tt, cols in targets:
        with cl._write_lock(tt, EXCLUSIVE):
            for col in cols:
                cl._drop_index_segments(tt, col)
            rebuilt += backfill_index(cl.catalog, tt, list(cols))
            tt.version += 1
    if targets:
        cl.catalog.ddl_epoch += 1
        cl.catalog.commit()
        for tt, _cols in targets:
            cl._plan_cache.invalidate_table(tt.name)
    return Result(columns=[], rows=[],
                  explain={"segments_rebuilt": rebuilt})

def _returning_result(cl, table_name, where, items, subst=None):
    """Evaluate a RETURNING clause as a distributed SELECT over the
    affected rows (pre-image WHERE); for UPDATE, assignment
    expressions are substituted into the items so the NEW values are
    returned (reference: adaptive_executor.c DML RETURNING tuples)."""
    t = cl.catalog.table(table_name)
    expanded = _expand_returning_items(t, items, subst)
    # constant items (e.g. SET c = 'z' substituted into RETURNING c)
    # cannot ride the distributed select: fold them on the host and
    # splice one copy per affected row
    consts, sel_items = {}, []
    for idx, (e, alias) in enumerate(expanded):
        try:
            consts[idx] = _eval_const(e)
        except Exception:
            sel_items.append((idx, A.SelectItem(e, alias)))
    if sel_items:
        inner = cl._execute_stmt(A.Select(
            [si for _, si in sel_items], A.TableRef(table_name), where))
        nrows, inner_rows = len(inner.rows), inner.rows
    else:
        cnt = A.Select([A.SelectItem(A.FuncCall("count", (A.Star(),)))],
                       A.TableRef(table_name), where)
        nrows = int(cl._execute_stmt(cnt).rows[0][0] or 0)
        inner_rows = [()] * nrows
    rows = []
    for r in inner_rows:
        full, j = [None] * len(expanded), 0
        for idx in range(len(expanded)):
            if idx in consts:
                full[idx] = consts[idx]
            else:
                full[idx] = r[j]
                j += 1
        rows.append(tuple(full))
    return Result(columns=[a for _, a in expanded], rows=rows)
