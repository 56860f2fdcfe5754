"""Distributed EXPLAIN [ANALYZE].

Reference: planner/multi_explain.c — distributed plan rendering with
"Tasks Shown: One of N" per-shard representative plans, EXPLAIN ANALYZE
piggybacking timings on execution, and strategy display for
INSERT..SELECT / set operations / grouping sets / joins.
"""

from __future__ import annotations

from citus_tpu.errors import UnsupportedFeatureError
from citus_tpu.executor import Result, execute_select
from citus_tpu.planner import ast as A
from citus_tpu.planner.bind import bind_select


def _execute_explain(cl, stmt: A.Explain) -> Result:
    if isinstance(stmt.statement, A.SetOp):
        so = stmt.statement
        lines = [f"Set Operation: {so.op.upper()}{' ALL' if so.all else ''}"]
        for side, sub in (("left", so.left), ("right", so.right)):
            r = _execute_explain(cl, A.Explain(sub, analyze=stmt.analyze))
            lines.append(f"  -> {side}:")
            lines.extend("     " + row[0] for row in r.rows)
        return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
    if isinstance(stmt.statement, (A.Update, A.Delete)):
        # modify-plan display (reference: EXPLAIN on the router /
        # multi-shard modify path shows the task distribution)
        m = stmt.statement
        t = cl.catalog.table(m.table)
        op = "Update" if isinstance(m, A.Update) else "Delete"
        if t.is_partitioned:
            from citus_tpu.partitioning import prune_partitions
            surv = prune_partitions(cl.catalog, t, m.where)
            lines = [f"{op} on {m.table} "
                     f"(partitions: {len(surv)}/"
                     f"{len(cl.catalog.partitions_of(m.table))})"]
            return Result(columns=["QUERY PLAN"],
                          rows=[(l,) for l in lines])
        from citus_tpu.planner.bind import Binder
        from citus_tpu.planner.physical import extract_intervals, prune_shards
        where = Binder(cl.catalog, t).bind_scalar(m.where) \
            if m.where is not None else None
        sis = prune_shards(t, where)
        lines = [f"{op} on {m.table} (shards: {len(sis)}/{len(t.shards)})"]
        ivs = [c.column for c in extract_intervals(where)] if where is not None else []
        if ivs:
            lines.append(f"  Shard/Chunk Pruning: {', '.join(sorted(set(ivs)))}")
        owners = {t.shards[si].placements[0] for si in sis}
        remote = {o for o in owners if cl.catalog.is_remote_node(o)}
        if remote and owners == remote and len(
                {cl.catalog.node_endpoint(o) for o in remote}) == 1:
            lines.append("  Strategy: forward to remote owner "
                         "(router, statement shipped as SQL)")
        elif remote:
            lines.append(f"  Strategy: cross-host two-phase commit "
                         f"({len(remote)} remote node(s))")
        else:
            lines.append("  Strategy: local (deletion bitmaps"
                         + (" + re-insert)" if op == "Update" else ")"))
        return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
    if isinstance(stmt.statement, A.Insert) \
            and stmt.statement.select is not None:
        ins = stmt.statement
        t = cl.catalog.table(ins.table)
        names = list(ins.columns or t.schema.names)
        strategy = "pull"
        sel = ins.select
        if isinstance(sel, A.Select) and isinstance(sel.from_, A.TableRef) \
                and not (sel.group_by or sel.having or sel.order_by
                         or sel.limit or sel.distinct):
            from citus_tpu.commands.insert import _insert_select_strategy
            try:
                bound = bind_select(cl.catalog, sel)
                if not bound.has_aggs and len(bound.final_exprs) == len(names):
                    strategy = _insert_select_strategy(
                        cl, t, bound, list(bound.final_exprs), names)
            # lint: disable=SWL01 -- EXPLAIN-only strategy probe; a bind failure falls back to the generic label
            except Exception:
                pass
        lines = [f"Insert into {ins.table} ({', '.join(names)})",
                 f"  Strategy: {strategy}"
                 + {"colocated": "  (per-shard pushdown, no re-hash)",
                    "repartition": "  (array-streaming re-hash)",
                    "pull": "  (coordinator row materialization)"}[strategy]]
        if isinstance(sel, (A.Select, A.SetOp)):
            sub = _execute_explain(cl, A.Explain(sel, analyze=False))
            lines.append("  -> source:")
            lines.extend("     " + row[0] for row in sub.rows)
        return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
    if not isinstance(stmt.statement, A.Select):
        raise UnsupportedFeatureError(
            "EXPLAIN supports SELECT, set operations, UPDATE/DELETE, "
            "and INSERT..SELECT")
    sel = stmt.statement
    if len(sel.group_by) == 1 and isinstance(sel.group_by[0],
                                             A.GroupingSetsSpec):
        spec = sel.group_by[0]
        full = max(spec.sets, key=len)
        lines = [f"Grouping Sets: {len(spec.sets)} grouped executions"]
        inner = A.Select(
            [i for i in sel.items
             if not (isinstance(i.expr, A.FuncCall)
                     and i.expr.name == "grouping")],
            sel.from_, sel.where, list(full))
        sub = _execute_explain(cl, A.Explain(inner, analyze=stmt.analyze))
        lines.extend("  " + row[0] for row in sub.rows)
        return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
    if isinstance(stmt.statement.from_, A.Join):
        return _explain_join(cl, stmt)
    sel0 = stmt.statement
    if isinstance(sel0.from_, A.TableRef) \
            and cl.catalog.has_table(sel0.from_.name) \
            and cl.catalog.table(sel0.from_.name).is_partitioned:
        from citus_tpu.partitioning import prune_partitions
        pt = cl.catalog.table(sel0.from_.name)
        parts = cl.catalog.partitions_of(pt.name)
        surv = prune_partitions(cl.catalog, pt, sel0.where)
        lines = [f"Append on {pt.name} "
                 f"(partitions: {len(surv)}/{len(parts)})"]
        if surv:
            import dataclasses as _dc
            rep = _dc.replace(sel0, from_=A.TableRef(
                surv[0].name, sel0.from_.alias or pt.name))
            sub = _execute_explain(cl, A.Explain(rep, analyze=False))
            lines.append(f"  Partitions Shown: One of {len(surv)}")
            lines.extend("  " + r[0] for r in sub.rows)
        return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
    if cl.catalog.rollups:
        from citus_tpu.rollup.routing import match_rollup
        m = match_rollup(cl, sel0)
        if m is not None:
            rname, rspec, rplan = m
            lines = [f"Rollup Scan on {rspec['table']} "
                     f"(rollup: {rname}, source: {rspec['source']})"]
            aggs = [f"{k}->{o}" for k, o, _p in rplan["items"]
                    if k != "group"]
            lines.append("  Finalize From Stored Sketches: "
                         + ", ".join(aggs))
            if rplan["groups"]:
                lines.append("  Re-merge GroupBy: "
                             + ", ".join(rplan["groups"]))
            if stmt.analyze:
                lines.extend(_run_analyze(cl, stmt))
            return Result(columns=["QUERY PLAN"],
                          rows=[(l,) for l in lines])
    bound = bind_select(cl.catalog, stmt.statement)
    from citus_tpu.planner.physical import plan_select
    plan = plan_select(cl.catalog, bound,
                       direct_limit=cl.settings.planner.direct_gid_limit)
    t = bound.table
    lines = []
    kind = ("Router" if plan.is_router else "Distributed") if t.is_distributed else "Local"
    lines.append(f"{kind} Scan on {t.name} "
                 f"(shards: {len(plan.shard_indexes)}/{t.shard_count})")
    if plan.index_eq is not None:
        icol, ival, iname = plan.index_eq
        if t.schema.scan_column(icol).type.is_text:
            # literal was bound to its dictionary id; show the string
            decoded = cl.catalog.decode_strings(t.name, icol, [int(ival)])
            ival = decoded[0] if decoded else ival
        lines.append(f"  Index Lookup: {icol} = {ival!r} using {iname}")
    if plan.intervals:
        lines.append("  Chunk Pruning: " +
                     ", ".join(sorted({c.column for c in plan.intervals})))
    if bound.has_aggs:
        mode = plan.group_mode
        # the group reduction follows from the group count and the arm
        # (ops/scan_agg.py): named here so the plan says which one runs
        from citus_tpu.ops.scan_agg import direct_reduction
        reduce_ = direct_reduction(
            mode.n_groups,
            cl.settings.executor.task_executor_backend == "cpu")
        desc = {"scalar": "Global Aggregate",
                "direct": f"Direct GroupBy (groups: {mode.n_groups}, "
                          f"reduce: {reduce_}, combine: psum)",
                "hash_host": "Hash GroupBy (host combine)"}[mode.kind]
        lines.append(f"  Partial Aggregate per shard -> {desc}")
        lines.append(f"    Partials: " + ", ".join(
            f"{op.kind}[{op.dtype}]" for op in plan.partial_ops))
    if stmt.analyze:
        lines.extend(_run_analyze(cl, stmt))
    return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])


def _run_analyze(cl, stmt: A.Explain) -> list[str]:
    """Execute the statement under a FORCED trace and render every
    timing line from the resulting span tree (the same tree the
    Chrome-trace exporter and slow-query ring see), so EXPLAIN ANALYZE
    can never drift from the tracing instrumentation.

    Executes through the plan cache (keyed by the statement's AST repr,
    never the surrounding EXPLAIN text) so repeated ANALYZE shows real
    hit/miss + compile-amortization behavior."""
    from citus_tpu.executor.kernel_cache import plan_fingerprint
    from citus_tpu.observability import trace as _trace
    c0 = cl.counters.snapshot()
    qt = _trace.begin_query(f"explain analyze {stmt.statement!r:.80}",
                            cl.settings.observability, force=True)
    try:
        xbound, xplan, values, cache_hit = cl._cached_select_plan(
            stmt.statement, ("$explain", repr(stmt.statement)))
        r = execute_select(cl.catalog, xbound, cl.settings, plan=xplan,
                           param_values=values)
    finally:
        qt.finish()
    c1 = cl.counters.snapshot()
    tr = qt.trace
    _trace.set_last(tr)
    lines = []
    ex = tr.find("execute")
    elapsed_ms = ex.duration_ms if ex is not None \
        else r.explain["elapsed_s"] * 1000
    lines.append(f"  Rows: {r.rowcount}  Elapsed: {elapsed_ms:.2f} ms")
    ps = tr.find("plan")
    hit = ps.attrs.get("cache_hit", cache_hit) if ps is not None \
        else cache_hit
    fp = (ps.attrs.get("fingerprint") if ps is not None else None) \
        or plan_fingerprint(xplan)[:12]
    compile_ms = int(sum(s.duration_ms
                         for s in tr.find_all("kernel_compile")))
    lines.append(f"  Plan Cache: {'hit' if hit else 'miss'}  "
                 f"fingerprint {fp}  compile {compile_ms} ms")
    pa = r.explain.get("partials")
    if pa:
        g, n = pa["overflow_guards_proved_away"], pa["null_counts_proved_away"]
        lines.append(f"  Partials: {pa['computed']} computed, {g + n} proved "
                     f"away: {g} overflow guards, {n} null counts")
    dh = c1.get("device_cache_hits", 0) - c0.get("device_cache_hits", 0)
    dm = c1.get("device_cache_misses", 0) - c0.get("device_cache_misses", 0)
    lines.append(f"  Device Cache: {dh} hit(s), {dm} miss(es)")
    # HBM odometer for THIS statement (hits replay resident bytes,
    # streams book the transfer) + what the cache holds resident now
    from citus_tpu.executor.device_cache import GLOBAL_CACHE
    hbm = (c1.get("device_hbm_touched_bytes", 0)
           - c0.get("device_hbm_touched_bytes", 0))
    mv = GLOBAL_CACHE.memory_view()
    lines.append(f"  Memory: {hbm} HBM bytes touched, "
                 f"cache-resident {mv['live_bytes']} bytes "
                 f"(high water {mv['high_water_bytes']})")
    mb = (ex.attrs.get("megabatch") if ex is not None else None) \
        or r.explain.get("megabatch")
    if mb:
        lines.append(
            f"  Batch: occupancy {mb.get('occupancy')}/"
            f"window {mb.get('window_ms', 0):g} ms  "
            f"(wait {mb.get('wait_ms', 0):.2f} ms)")
    rounds = tr.find_all("device_round")
    tasks = r.explain.get("tasks") or []
    if tasks:
        lines.append(f"  Tasks: {len(tasks)}  "
                     f"Tasks Shown: One of {len(tasks)}")
        si, nrows, dt = tasks[0]
        lines.append(f"    -> Task (shard index {si}): {nrows} rows, "
                     f"{dt*1000:.2f} ms device dispatch")
    elif rounds:
        lines.append(f"  Device Rounds: {len(rounds)}  "
                     f"({sum(s.duration_ms for s in rounds):.2f} ms)")
    rtasks = tr.find_all("remote_task")
    if rtasks:
        lines.append(f"  Remote Tasks: {len(rtasks)}")
        for s in rtasks:
            lines.append(
                f"    -> Task (shard index {s.attrs.get('shard_index')}): "
                f"pushed to node {s.attrs.get('node')}, "
                f"{s.attrs.get('bytes', 0)} result bytes, "
                f"{s.attrs.get('rpc_ms', 0):.2f} ms rpc, "
                f"{s.attrs.get('dec_ms', 0):.2f} ms decode")
    pl = (ex.attrs.get("pipeline") if ex is not None else None) \
        or r.explain.get("pipeline") or {}
    if pl:
        line = (
            f"  Pipeline: host decode {pl.get('host_decode_ms', 0):.2f}"
            f" ms, device {pl.get('device_ms', 0):.2f} ms, "
            f"H2D {pl.get('h2d_bytes', 0)} bytes, "
            f"stalls host={pl.get('host_stalls', 0)} "
            f"device={pl.get('device_stalls', 0)}")
        if pl.get("batch_rows_real"):
            # rows shipped and scanned per table row (1.0 = no padding)
            line += (", pad_share "
                     f"{pl['batch_rows_padded'] / pl['batch_rows_real']:.3f}")
        decoded = pl.get("decode_bytes_in_place", 0) \
            + pl.get("decode_bytes_copied", 0)
        if decoded:
            # share of the scan columns' bytes that the batch's one
            # native call decoded where the kernel reads them
            line += (", decoded in place "
                     f"{pl['decode_bytes_in_place'] / decoded:.3f}")
        if tr.find("stripe_read") is not None:
            line += ", " + _decode_split(tr)
            if "decode_streams" in pl:
                # batches of different shards (a mesh's devices) decoded
                # side by side, and the wall time two or more were
                line += (f", {pl['decode_streams']} streams at once, "
                         f"{pl.get('decode_overlap_ms', 0)} ms overlapped")
        if "fused_dispatches" in pl:
            # the 1-dispatch-per-batch claim, visible per statement
            line += f", fused dispatches {pl['fused_dispatches']}"
        if "stream_window_peak_bytes" in pl:
            line += (f", stream window peak "
                     f"{pl['stream_window_peak_bytes']} bytes")
        if "mesh_round_bytes_copied" in pl:
            # a mesh round's members go to their devices as they stand:
            # what the host still copied (re-pads, fillers) of the bytes
            line += (f", stacked: {pl['mesh_round_bytes_copied']} of "
                     f"{pl.get('h2d_bytes', 0)} bytes copied on the host")
        if "scan_lanes" in pl:
            # int64 scan columns the table's statistics bound inside
            # int32: the device holds them, and reads them, at 32 bits
            line += (f", lanes: {pl.get('scan_lanes_narrow', 0)} of "
                     f"{pl['scan_lanes']} 64-bit columns at 32 bits")
        lines.append(line)
        if "direct_groups" in pl:
            lines.append(f"    Direct: group slots {pl['direct_groups']}, "
                         f"groups {pl['direct_groups_out']}, "
                         f"rows in {pl.get('group_rows_in', 0)} "
                         f"(kept {pl.get('group_rows_kept', 0)}), "
                         f"fetched {pl.get('direct_bytes_fetched', 0)} bytes, "
                         f"id lanes: {pl.get('direct_gid_keys_narrow', 0)} of "
                         f"{pl.get('direct_gid_keys', 0)} keys 32-bit, "
                         f"{pl.get('direct_gid_divisions', 0)} divisions")
        if "hash_slots" in pl:
            # each batch is sorted by key and segment-reduced on the
            # device, then offered to the table in chunks: U is the sum
            # of the batches' distinct keys, R the rows they held
            line = (
                f"    Hash: hash slots {pl['hash_slots']}, "
                f"occupancy {pl.get('hash_occupancy_pct', 0):g}%, "
                f"spilled {pl.get('hash_spilled_rows', 0)} rows, "
                f"groups {pl.get('hash_groups_out', 0)}, "
                f"fetched {pl.get('hash_table_bytes_fetched', 0)} bytes, "
                f"table updates {pl.get('hash_table_updates', 0)} "
                f"({pl.get('hash_rows_in', 0)} rows), "
                f"rows in {pl.get('group_rows_in', 0)}")
            if "group_rows_kept" in pl:
                line += f" (kept {pl['group_rows_kept']})"
            if "hash_slots_from" in pl:
                # what bounded the derived table (executor.py _hash_slots)
                line += f", slots from {pl['hash_slots_from']}"
            if pl.get("hash_offer_slots"):
                # the kernel's offer loop runs whole chunks of entry
                # slots: the share of them that carried an entry
                live = pl.get("hash_table_updates", 0) / pl["hash_offer_slots"]
                line += (f", offer slots {pl['hash_offer_slots']} "
                         f"({100 * live:.0f}% live)")
            if pl.get("hash_tables", 1) > 1:
                # one table a device, each fed its own shards: apart
                # where the keys hold the distribution column, else
                # fetched whole and merged
                line += (f", tables {pl['hash_tables']} x "
                         f"{pl['hash_slots'] // pl['hash_tables']} slots, "
                         + (f"disjoint on {pl['hash_disjoint_on']}"
                            if pl.get("hash_disjoint_on") else "merged"))
            if pl.get("hash_having_on_device"):
                # the chip decided HAVING on the table: the survivors'
                # blocks and the spilled keys' entries came home
                line += (f", having on device: "
                         f"{pl.get('hash_entries_fetched', 0)} of "
                         f"{pl['hash_slots']} entries fetched")
            if pl.get("group_top_cuts"):
                # ORDER BY ... LIMIT was cut on the table
                line += (f", {pl['group_top']}: "
                         f"{pl['group_top_entries']} of {pl['hash_slots']} "
                         f"entries fetched")
            lines.append(line)
        if "remote_wait_ms" in pl:
            wire = f", wire {pl['wire_format']}" \
                if pl.get("wire_format") else ""
            lines.append(
                f"    Remote Wait: {pl['remote_wait_ms']:.2f} ms "
                f"(overlapped {pl['remote_overlapped_ms']:.2f} ms, "
                f"peak in-flight {pl['remote_inflight_peak']}{wire})")
    return lines


def _decode_split(tr) -> str:
    """Where the batches' decode went, from the statement's own spans
    (the children of stripe_read and the producer's wait): the shards'
    metadata and the footers (and how many of them the footer cache
    served decoded), the batch layout, the ONE native call a
    batch — with the share of that call's threads x time its pool spent
    reading and decompressing — the stripe reader, and the time
    backpressure held the decode."""
    def ms(*names):
        return sum(s.duration_ms for n in names for s in tr.find_all(n))

    native = tr.find_all("native_decode")
    offered = sum(s.attrs.get("threads", 0) * s.duration_ms for s in native)
    worked = sum(s.attrs.get("read_ms", 0.0) + s.attrs.get("decompress_ms", 0.0)
                 for s in native)
    busy = f" (pool {100 * worked / offered:.0f} % busy)" if offered else ""
    footers = tr.find_all("footer_read")
    cached = sum(bool(s.attrs.get("cached")) for s in footers)
    return (f"decode: footers {ms('shard_open', 'footer_read'):.2f} ms "
            f"({cached} of {len(footers)} cached), "
            f"layout {ms('batch_layout'):.2f} ms, "
            f"native {ms('native_decode'):.2f} ms{busy}, "
            f"fallback {ms('stripe_fallback', 'chunk_read'):.2f} ms, "
            f"blocked {ms('wait:prefetch_full'):.2f} ms")


def _explain_join(cl, stmt: A.Explain) -> Result:
    from citus_tpu.executor.join_executor import execute_join_select
    from citus_tpu.planner.join_planner import bind_join_select
    bj = bind_join_select(cl.catalog, stmt.statement)
    lines = [f"Join ({bj.strategy}) over {len(bj.rels)} relations"]
    for s_ in bj.steps:
        keys = ", ".join(f"{l} = {r}" for l, r in
                         zip(s_.left_keys, s_.right_keys)) or "(cross)"
        lines.append(f"  {s_.kind.upper()} JOIN {s_.right_alias} ON {keys}")
    for alias, _t in bj.rels:
        rp = bj.rel_plans[alias]
        f = f" filter: {rp.filter}" if rp.filter is not None else ""
        lines.append(f"  Scan {alias} [{', '.join(rp.columns)}]{f}")
    if bj.has_aggs:
        lines.append(f"  GroupBy keys={len(bj.group_keys)} "
                     f"partials={len(bj.partial_ops)} (host combine)")
    if stmt.analyze:
        r = execute_join_select(cl.catalog, bj, cl.settings)
        lines.append(f"  Rows: {r.rowcount}  Tasks: {r.explain['tasks']}  "
                     f"Elapsed: {r.explain['elapsed_s']*1000:.2f} ms")
        j = r.explain.get("join")
        if j and j["on"] == "device":
            tables = ", ".join(
                f"{a} {t['table']} {t['slots']} slots a {t['built_per']}"
                for a, t in j["tables"].items())
            lines.append(
                f"  Join: on device, probe {j['probe']}; tables: {tables} "
                f"({j['table_bytes']} bytes); rows built {j['rows_built']}, "
                f"probed {j['rows_probed']}, looked up "
                f"{j['rows_looked_up']}, matched {j['rows_matched']}, "
                f"out {j['rows_out']}; overflow rounds "
                f"{j['overflow_rounds']}; groups {j['groups']}")
            top = j["top"]
            lines[-1] += (
                f"; keys: {j['group_key_lanes']} of {j['group_keys']} "
                f"grouped, {j['group_keys_dependent']} looked up for "
                f"{j['groups_looked_up']} groups; "
                + (f"top {top['rows']} on device: {top['entries']} entries "
                   f"fetched" if isinstance(top, dict)
                   else f"top not cut on device: {top}"))
            if j.get("cycle_filters"):
                edges = ", ".join(f"{a} under {b}"
                                  for a, b in j["tree"].items())
                lines[-1] += (
                    f"; tree: {edges}; cycle filters: "
                    f"{' and '.join(j['cycle_filters'])} "
                    f"({j['cycle_rows_kept']} of {j['cycle_rows_in']} rows "
                    f"kept)")
            x = j.get("exchange")
            if x:
                lines[-1] += (
                    f"; exchange: {x['relation']} on {x['key']}, "
                    f"{x['rows']} rows, {x['bytes']} bytes over "
                    f"{x['devices']} devices, fullest device "
                    f"{x['rows_received_max_device']}")
        elif j:
            lines.append(f"  Join: on host ({j['why']})")
    return Result(columns=["QUERY PLAN"], rows=[(l,) for l in lines])
