"""INSERT / upsert / INSERT..SELECT handlers.

Reference: multi-row INSERT routing (multi_router_planner.c
BuildRoutesForInsert), ON CONFLICT within one shard group, and the
3-strategy INSERT..SELECT ladder (insert_select_planner.c:
colocated-pushdown / repartition / pull-to-coordinator) — here the
direct strategies move arrays shard-to-shard without materializing
rows through the coordinator.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from citus_tpu.errors import (
    AnalysisError, CatalogError, ExecutionError, UnsupportedFeatureError,
)
from citus_tpu.executor import Result
from citus_tpu.ingest import TableIngestor, rows_to_columns
from citus_tpu.planner import ast as A
from citus_tpu.planner.bind import bind_select

from citus_tpu.cluster import (  # noqa: E402  (loaded post-cluster)
    _eval_const, _expand_returning_items, _pylit, _subst_excluded,
)


def execute_insert(cl, stmt: A.Insert) -> Result:
    t = cl.catalog.table(stmt.table)
    if stmt.select is not None:
        if stmt.returning:
            raise UnsupportedFeatureError(
                "RETURNING on INSERT..SELECT is not supported")
        if stmt.on_conflict is not None:
            # pull the source rows, then run the same upsert machinery
            # row literals take (reference: INSERT..SELECT ON CONFLICT
            # goes through the pull / colocated-intermediate-results
            # strategy, insert_select_executor.c README:1223-1238)
            inner = cl._execute_stmt(stmt.select)
            rows = [list(r) for r in inner.rows]
            r = _execute_upsert(cl, t, stmt, rows)
            r.explain["strategy"] = "insert_select:upsert_pull"
            return r
        names = stmt.columns or t.schema.names
        # FK-constrained, unique-indexed, and partitioned targets —
        # and partitioned sources — take the pull path: copy_from's
        # probes and partition routing only run there, and a
        # partitioned source must expand through _execute_stmt
        def _refs_partitioned(item) -> bool:
            if isinstance(item, A.Join):
                return _refs_partitioned(item.left) \
                    or _refs_partitioned(item.right)
            return (isinstance(item, A.TableRef)
                    and cl.catalog.has_table(item.name)
                    and cl.catalog.table(item.name).is_partitioned)
        direct_ok = not (t.foreign_keys or t.unique_indexes
                         or t.is_partitioned
                         or cl._domain_columns_of(t))
        if direct_ok and isinstance(stmt.select, A.Select) \
                and stmt.select.from_ is not None:
            direct_ok = not _refs_partitioned(stmt.select.from_)
        res = None if not direct_ok \
            else _insert_select_arrays(cl, t, stmt.select, list(names))
        if res is None:
            # general path: materialize rows through the coordinator
            # (reference: the pull-to-coordinator INSERT..SELECT
            # strategy, insert_select_executor.c)
            inner = cl._execute_stmt(stmt.select)
            n = cl.copy_from(stmt.table, rows=inner.rows,
                               column_names=list(names))
            strategy = "pull"
        else:
            n, strategy = res
        return Result(columns=[], rows=[],
                      explain={"inserted": n,
                               "strategy": f"insert_select:{strategy}"})
    rows = []
    for row_exprs in stmt.rows:
        row = []
        for e in row_exprs:
            if not isinstance(e, A.Literal):
                if isinstance(e, A.UnOp) and e.op == "-" and isinstance(e.operand, A.Literal):
                    row.append(-e.operand.value)
                    continue
                if isinstance(e, A.FuncCall) and e.name in ("nextval", "currval") \
                        and e.args and isinstance(e.args[0], A.Literal):
                    seq = str(e.args[0].value)
                    row.append(cl.catalog.nextval(seq) if e.name == "nextval"
                               else cl.catalog.currval(seq))
                    continue
                raise UnsupportedFeatureError("INSERT VALUES must be literals")
            row.append(e.value)
        rows.append(row)
    # resolve DEFAULTs up front (serial ids included) so ON CONFLICT
    # and RETURNING see exactly what gets stored — copy_from then
    # receives the complete batch and never draws defaults again
    names = list(t.schema.names if stmt.columns is None else stmt.columns)
    has_defaults = any(c.default_sql and c.name not in names
                       for c in t.schema)
    if has_defaults and rows:
        from citus_tpu.ingest import rows_to_columns
        listed = set(names)
        columns = {c: v for c, v in
                   rows_to_columns(t.schema.names, rows, names).items()
                   if c in listed
                   or not t.schema.column(c).default_sql}
        columns = cl._fill_defaults(t, columns)
        names = [c for c in t.schema.names if c in columns]
        rows = [tuple(columns[c][i] for c in names)
                for i in range(len(rows))]
        stmt = __import__("dataclasses").replace(stmt, columns=names)
    if stmt.on_conflict is not None:
        return _execute_upsert(cl, t, stmt, rows)
    n = cl.copy_from(stmt.table, rows=rows, column_names=names)
    if stmt.returning:
        out_rows = []
        for row in rows:
            m = {}
            for cn, v in zip(names, row):
                typ = t.schema.column(cn).type
                if v is not None and not typ.is_text:
                    # what a subsequent SELECT would read back
                    v = typ.from_physical(typ.to_physical(v))
                lit = A.Literal(v, "null" if v is None else
                                "string" if isinstance(v, str) else "int")
                m[A.ColumnRef(cn)] = lit
                m[A.ColumnRef(cn, stmt.table)] = lit
            for cn in t.schema.names:
                m.setdefault(A.ColumnRef(cn), A.Literal(None, "null"))
                m.setdefault(A.ColumnRef(cn, stmt.table),
                             A.Literal(None, "null"))
            exp = _expand_returning_items(t, stmt.returning, m)
            out_rows.append(tuple(_eval_const(e) for e, _ in exp))
        cols = [a for _, a in _expand_returning_items(t, stmt.returning)]
        return Result(columns=cols, rows=out_rows,
                      explain={"inserted": n})
    return Result(columns=[], rows=[], explain={"inserted": n})

def _execute_upsert(cl, t, stmt: A.Insert, rows: list) -> Result:
    """INSERT ... ON CONFLICT: the conflict target is the declared
    key (the reference requires it to include the distribution
    column so conflicts resolve within one shard group —
    multi_router_planner.c rejects others).  Runs under the
    colocation group's EXCLUSIVE write lock so check+write is atomic
    against concurrent writers and shard moves."""
    oc = stmt.on_conflict
    if stmt.returning:
        raise UnsupportedFeatureError(
            "RETURNING with ON CONFLICT is not supported")
    if not oc.targets:
        raise UnsupportedFeatureError(
            "ON CONFLICT requires an explicit (column, ...) target")
    names = list(t.schema.names if stmt.columns is None else stmt.columns)
    for c in oc.targets:
        if not t.schema.has(c):
            raise AnalysisError(f"column {c!r} does not exist")
        if c not in names:
            raise AnalysisError(
                "ON CONFLICT target columns must be inserted columns")
    if t.is_distributed and t.dist_column not in oc.targets:
        raise UnsupportedFeatureError(
            "ON CONFLICT target must include the distribution column")
    for c, _e in oc.assignments:
        if not t.schema.has(c):
            raise AnalysisError(f"column {c!r} does not exist")
        if t.is_distributed and c == t.dist_column:
            raise UnsupportedFeatureError(
                "ON CONFLICT DO UPDATE cannot modify the distribution "
                "column")
    # sketch_merge(col, excluded.col) assignments merge serialized
    # sketch states host-side before the UPDATE runs: the batched probe
    # below fetches the stored word alongside the conflict key, the
    # rollup codec merges it with the proposed row's word, and the
    # assignment collapses to a plain string literal (which the UPDATE
    # path dictionary-encodes like any other text-routed value)
    merge_cols: list = []
    for c, e in oc.assignments:
        if isinstance(e, A.FuncCall) and e.name == "sketch_merge":
            if t.schema.column(c).type.kind != "sketch":
                raise AnalysisError(
                    f"sketch_merge() target column {c!r} is not a "
                    f"sketch column")
            if len(e.args) != 2 \
                    or not (isinstance(e.args[0], A.ColumnRef)
                            and e.args[0].name == c
                            and e.args[0].table in (None, t.name)) \
                    or not (isinstance(e.args[1], A.ColumnRef)
                            and e.args[1].table == "excluded"
                            and e.args[1].name == c):
                raise AnalysisError(
                    "sketch_merge() must be written as "
                    "sketch_merge(col, excluded.col) on the assigned "
                    "column")
            merge_cols.append(c)
    key_idx = [names.index(c) for c in oc.targets]

    def norm_key(vals) -> tuple:
        """Canonicalize proposed key values to what a SELECT reads
        back (physical round-trip), so they compare equal to probed
        rows: 5.0 -> Decimal('5.00'), '2020-01-01' -> date."""
        out = []
        for c, v in zip(oc.targets, vals):
            typ = t.schema.column(c).type
            if v is None:
                out.append(v)
            elif typ.is_text:
                if typ.kind != "text":
                    # uuid/bytea/array: a non-canonical spelling must
                    # collide with the stored canonical word, then read
                    # back the way a SELECT renders it
                    out.append(typ.render_word(typ.normalize_word(v)))
                else:
                    out.append(v)
            else:
                out.append(typ.from_physical(typ.to_physical(v)))
        return tuple(out)

    if oc.action == "update":
        # PostgreSQL raises error 21000 whenever two proposed rows
        # would affect the same target row; checking up front keeps
        # the statement all-or-nothing (no partially applied updates)
        dup_check: set = set()
        for row in rows:
            raw = tuple(row[i] for i in key_idx)
            if any(v is None for v in raw):
                continue
            key = norm_key(raw)
            if key in dup_check:
                raise ExecutionError(
                    "ON CONFLICT DO UPDATE command cannot affect row "
                    "a second time")
            dup_check.add(key)
    inserted = updated = skipped = 0
    from citus_tpu.transaction.locks import EXCLUSIVE
    with cl._write_lock(t, EXCLUSIVE):
        # one batched probe instead of a per-row count(*) under the
        # lock: fetch the conflict-target columns of candidate rows
        # (pruned by the distribution-column IN-list) into a set
        probe_rows = [row for row in rows
                      if not any(row[i] is None for i in key_idx)]
        # conflict key -> stored values of the sketch-merge source
        # columns (an empty tuple when none are requested)
        existing: dict = {}
        if probe_rows:
            where = None
            if t.is_distributed and t.dist_column in names:
                di = names.index(t.dist_column)
                dvals = sorted({row[di] for row in probe_rows})
                where = A.InList(A.ColumnRef(t.dist_column),
                                 tuple(_pylit(v) for v in dvals), False)
            chk = A.Select([A.SelectItem(A.ColumnRef(c))
                            for c in list(oc.targets) + merge_cols],
                           A.TableRef(t.name), where)
            nk = len(oc.targets)
            existing = {tuple(r[:nk]): tuple(r[nk:])
                        for r in cl._execute_stmt(chk).rows}
        to_insert: list = []
        affected: set = set()  # keys inserted/updated by this command
        for row in rows:
            raw = tuple(row[i] for i in key_idx)
            if any(v is None for v in raw):
                # NULL never equals NULL: no conflict possible
                to_insert.append(row)
                inserted += 1
                continue
            key = norm_key(raw)
            if key in affected:
                # only reachable for DO NOTHING (DO UPDATE duplicate
                # keys were rejected before any mutation)
                skipped += 1
                continue
            if key not in existing:
                affected.add(key)
                to_insert.append(row)
                inserted += 1
                continue
            if oc.action == "nothing":
                skipped += 1
                continue
            affected.add(key)
            cond = None
            for c, v in zip(oc.targets, raw):
                eq = A.BinOp("=", A.ColumnRef(c), _pylit(v))
                cond = eq if cond is None else A.BinOp("and", cond, eq)
            excl = {c: _pylit(v) for c, v in zip(names, row)}
            stored = dict(zip(merge_cols, existing.get(key, ())))
            assignments = []
            for c, e in oc.assignments:
                e2 = _subst_excluded(e, excl)
                if c in stored and isinstance(e2, A.FuncCall) \
                        and e2.name == "sketch_merge":
                    from citus_tpu.rollup.sketches import (
                        merge_sketch_words,
                    )
                    cur = stored[c]
                    new = e2.args[1].value \
                        if isinstance(e2.args[1], A.Literal) else None
                    if cur is None or new is None:
                        merged = new if cur is None else cur
                    else:
                        merged = merge_sketch_words(str(cur), str(new))
                    e2 = A.Literal(merged,
                                   "null" if merged is None else "string")
                assignments.append((c, e2))
            where = cond
            if oc.where is not None:
                where = A.BinOp("and", cond,
                                _subst_excluded(oc.where, excl))
            upd: A.Statement = A.Update(t.name, assignments, where)
            import threading as _threading
            exec_role = cl._exec_roles.get(_threading.get_ident())
            if exec_role is not None:
                # the conflicting row must pass the role's UPDATE
                # policies regardless of the conflict WHERE clause
                # (PostgreSQL raises the RLS violation whenever the
                # existing row fails USING)
                pol = cl._policy_predicate(exec_role, t.name,
                                             "update")
                if pol is not None:
                    vis = A.Select(
                        [A.SelectItem(A.FuncCall("count", (A.Star(),)))],
                        A.TableRef(t.name), A.BinOp("and", cond, pol))
                    if not cl._execute_stmt(vis).rows[0][0]:
                        raise AnalysisError(
                            f'new row violates row-level security '
                            f'policy for table "{t.name}"')
                upd, _ = cl._apply_rls(exec_role, upd)
            r = cl._execute_stmt(upd)
            n_upd = r.explain.get("updated", 0)
            updated += n_upd
            skipped += 0 if n_upd else 1  # DO UPDATE ... WHERE filtered
        if to_insert:
            cl.copy_from(t.name, rows=to_insert,
                           column_names=stmt.columns)
    if oc.action == "update":
        # PostgreSQL fires statement-level UPDATE triggers whenever
        # DO UPDATE is specified (INSERT triggers fire at execute())
        cl._fire_triggers_for(t.name, "update", 0)
    return Result(columns=[], rows=[],
                  explain={"inserted": inserted, "updated": updated,
                           "skipped": skipped, "strategy": "upsert"})

def _insert_select_arrays(cl, target, sel: A.Select,
                          names: list[str]) -> Optional[int]:
    """Array-streaming INSERT..SELECT (the repartition strategy,
    reference: insert_select_planner.c IsRedistributablePlan): when
    the SELECT is a plain single-table projection whose output types
    match the target physically, move numpy columns straight from
    the scan into the hash-routing ingest — no Python row
    materialization.  Returns None when ineligible."""
    if not isinstance(sel, A.Select) or not isinstance(sel.from_, A.TableRef):
        return None
    if sel.group_by or sel.having or sel.order_by or sel.limit or sel.distinct:
        return None
    if cl.catalog.remote_data is not None and any(
            cl.catalog.is_remote_node(nd)
            for s in target.shards for nd in s.placements):
        # remote-hosted target shards: only the pull path routes rows
        # over the data plane (copy_from's _route_remote_batch); the
        # array strategies write placements directly and would drop or
        # misplace rows for foreign hosts
        return None
    try:
        bound = bind_select(cl.catalog, sel)
    except Exception:
        return None
    if bound.has_aggs or len(bound.final_exprs) != len(names):
        return None
    from citus_tpu.planner.bound import (
        BColumn, BDictRemap, compile_expr, predicate_mask,
    )
    from citus_tpu.planner.physical import plan_select
    final_exprs = list(bound.final_exprs)
    for i, (e, cname) in enumerate(zip(final_exprs, names)):
        tgt = target.schema.column(cname).type
        if e.type != tgt:
            return None
        if tgt.kind == "uuid":
            # uuid lanes travel in pairs; the pull path rematerializes
            # canonical strings and re-encodes both lanes on ingest
            return None
        if tgt.is_text:
            if not isinstance(e, BColumn):
                return None
            if bound.table.name != target.name or e.name != cname:
                # re-encode source dictionary ids into the target's
                # dictionary space (grows the target dictionary)
                src_words = cl.catalog.dictionary(bound.table.name, e.name)
                mapping = tuple(int(x) for x in cl.catalog.encode_strings(
                    target.name, cname, src_words))
                final_exprs[i] = BDictRemap(e, mapping)
    plan = plan_select(cl.catalog, bound,
                       direct_limit=cl.settings.planner.direct_gid_limit)
    from citus_tpu.transaction.locks import SHARED
    fns = [compile_expr(e, np) for e in final_exprs]
    ffn = compile_expr(bound.filter, np) if bound.filter is not None else None
    strategy = _insert_select_strategy(cl, target, bound, final_exprs, names)
    with cl._write_lock(target, SHARED):
        n = _run_insert_select_arrays(cl, 
            target, bound, plan, fns, ffn, names, strategy)
    return n, strategy

def _insert_select_strategy(cl, target, bound, final_exprs, names) -> str:
    """The reference's INSERT..SELECT strategy ladder
    (insert_select_planner.c, README:1187-1238): *colocated pushdown*
    when source and target share a colocation group and the target's
    distribution column is fed directly by the source's distribution
    column (rows already live on the right shard — no re-hash, no
    routing); else *repartition* (array-streaming re-hash through the
    hash-routing ingest).  The caller falls back to *pull* (row
    materialization) when the arrays path is ineligible entirely."""
    from citus_tpu.planner.bound import BColumn
    src = bound.table
    if not (src.is_distributed and target.is_distributed):
        return "repartition"
    if src.colocation_id != target.colocation_id:
        return "repartition"
    if target.dist_column is None or target.dist_column not in names:
        return "repartition"
    i = names.index(target.dist_column)
    e = final_exprs[i]
    # plain column (no dict remap / cast) referencing the source's
    # distribution column: hash(source row) == hash(target row)
    if isinstance(e, BColumn) and e.name == src.dist_column:
        return "colocated"
    return "repartition"

def _run_insert_select_arrays(cl, target, bound, plan, fns, ffn,
                              names, strategy) -> int:
    from citus_tpu.storage.overlay import current_overlay
    txn = current_overlay()
    if txn is not None:
        # inside BEGIN..COMMIT: stage under the transaction's xid.
        # On failure, register staged dirs (never abort the xid —
        # that would destroy earlier statements' staged rows)
        ing = TableIngestor(cl.catalog, target, txlog=None)
        ing.xid = txn.xid
        try:
            total = _stream_insert_select(cl, ing, target, bound, plan,
                                               fns, ffn, names, strategy)
            for w in ing._writers.values():
                w.flush()
        finally:
            txn.record_ingest(
                target.name,
                [w.directory for w in ing._writers.values()])
        cl.counters.bump("rows_ingested", total)
        return total
    ing = TableIngestor(cl.catalog, target, txlog=cl.txlog)
    try:
        total = _stream_insert_select(cl, ing, target, bound, plan,
                                           fns, ffn, names, strategy)
    except BaseException:
        ing.abort()  # failure during scan/append: staged files dropped
        raise
    # finish() manages its own failure path (releases the xid so
    # recovery decides; aborting here could roll back a logged COMMIT)
    ing.finish()
    cl.counters.bump("rows_ingested", total)
    return total

def _stream_insert_select(cl, ing, target, bound, plan, fns, ffn,
                          names, strategy) -> int:
    from citus_tpu.executor.batches import load_shard_batches
    from citus_tpu.planner.bound import predicate_mask
    total = 0
    for si in plan.shard_indexes:
        for values, masks, n in load_shard_batches(cl.catalog, plan, si):
            env = {c: (values[c].astype(
                        bound.table.schema.scan_dtype(c, device=True), copy=False),
                       masks[c]) for c in plan.scan_columns}
            if ffn is not None:
                m = np.asarray(predicate_mask(np, ffn, env, np.ones(n, bool)))
                if m.shape == ():
                    m = np.full(n, bool(m))
            else:
                m = np.ones(n, bool)
            idx = np.nonzero(m)[0]
            if idx.size == 0:
                continue
            out_v, out_m = {}, {}
            for fn, cname in zip(fns, names):
                v, valid = fn(env)
                v = np.asarray(v)
                if v.ndim == 0:
                    v = np.broadcast_to(v, (n,))
                if valid is True:
                    valid = np.ones(n, bool)
                elif valid is False:
                    valid = np.zeros(n, bool)
                st = target.schema.column(cname).type.storage_dtype
                out_v[cname] = v[idx].astype(st)
                out_m[cname] = np.asarray(valid)[idx]
            for cname in target.schema.names:
                if cname not in out_v:
                    out_v[cname] = np.zeros(idx.size, target.schema.column(cname).type.storage_dtype)
                    out_m[cname] = np.zeros(idx.size, bool)
            if strategy == "colocated":
                # pushdown: rows of source shard si belong to target
                # shard si by construction — write straight to its
                # placements, skipping hash + scatter entirely
                shard = target.shards[si]
                for node in shard.placements:
                    ing._writer(shard.shard_id, node).append_batch(out_v, out_m)
            else:
                ing.append(out_v, out_m)
            total += idx.size
    return total
