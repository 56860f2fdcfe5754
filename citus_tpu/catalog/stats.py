"""Table statistics from stripe footers.

The columnar skip list already stores per-chunk min/max (reference:
ColumnChunkSkipNode, src/include/columnar/columnar.h:85-111); aggregating
it per table gives free global column bounds.  The planner uses these to
prove a GROUP BY key domain small enough for the exact direct-gid
aggregation strategy (the TPU analog of choosing a hash-agg vs sort-agg
plan from relation statistics).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

from citus_tpu.catalog.catalog import Catalog, TableMeta
from citus_tpu.storage.format import read_stripe_footer
from citus_tpu.storage.writer import _load_meta

# cache key: (data_dir, table, version) — version bumps on every ingest
# and DDL, which is exactly the invalidation we want; data_dir isolates
# distinct clusters in one process
_CACHE: dict[tuple, tuple] = {}


def shard_row_counts(cat: Catalog, table: TableMeta) -> list[int]:
    """Rows of each shard, in the order of ``table.shards`` (0 for a
    shard with no local directory)."""
    counts = []
    for shard in table.shards:
        node = shard.placements[0]
        d = cat.shard_dir(table.name, shard.shard_id, node)
        counts.append(_load_meta(d)["row_count"] if os.path.isdir(d) else 0)
    return counts


def table_row_count(cat: Catalog, table: TableMeta) -> int:
    return sum(shard_row_counts(cat, table))


@dataclass(frozen=True)
class TableFacts:
    """What the stripe footers prove of every row a scan of one
    ``table.version`` can return: an upper bound on their number and,
    per stored column, ``(min, max, has_nulls)`` over ALL of them.  A
    column some stripe lacks (added after it was written: those rows
    read NULL) has nulls; a column with a chunk of values and no
    min/max is absent."""
    rows: int
    columns: dict[str, tuple]


def _collect(cat: Catalog, table: TableMeta) -> tuple[dict, Optional[TableFacts]]:
    key = (cat.data_dir, table.name, table.version)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    out: dict[str, list] = {}
    nulls: dict[str, bool] = {}
    unbounded: set[str] = set()
    # every shard's rows are covered: nothing is a fact once a shard
    # contributed no footer (no local directory: never written, or
    # hosted on another coordinator)
    covered = bool(table.shards)
    rows = 0
    stored = [c.storage_name for c in table.schema]
    for shard in table.shards:
        node = shard.placements[0]
        d = cat.shard_dir(table.name, shard.shard_id, node)
        if not os.path.isdir(d):
            covered = False
            continue
        covered = covered and not cat.is_remote_node(node)
        for stripe in _load_meta(d)["stripes"]:
            footer = read_stripe_footer(os.path.join(d, stripe["file"]))
            rows += footer.row_count
            for col in stored:
                if col not in footer.columns:
                    nulls[col] = True  # added later: these rows read NULL
            for col, chunks in footer.columns.items():
                for cs in chunks:
                    nulls[col] = nulls.get(col, False) or cs.has_nulls
                    if cs.minimum is None:
                        if cs.null_count < cs.row_count:
                            unbounded.add(col)
                        continue
                    cur = out.get(col)
                    if cur is None:
                        out[col] = [cs.minimum, cs.maximum]
                    else:
                        cur[0] = min(cur[0], cs.minimum)
                        cur[1] = max(cur[1], cs.maximum)
    bounds = {col: (v[0], v[1], nulls.get(col, False)) for col, v in out.items()}
    facts = None
    if covered and rows:
        facts = TableFacts(rows, {c: b for c, b in bounds.items()
                                  if c not in unbounded})
    _CACHE[key] = (bounds, facts)
    return bounds, facts


def column_bounds(cat: Catalog, table: TableMeta) -> dict[str, tuple]:
    """{column: (min, max, has_nulls)} over all shards (physical values);
    columns with no stats (all-null or empty table) are absent."""
    return _collect(cat, table)[0]


def table_facts(cat: Catalog, table: TableMeta) -> Optional[TableFacts]:
    """The footers' facts about ``table`` at its version, or None where
    some shard contributed none (then nothing is proved).  Sound for as
    long as ``table.version`` stands, like ``column_bounds``."""
    return _collect(cat, table)[1]


def column_minmax(cat: Catalog, table: TableMeta, column: str) -> Optional[tuple]:
    return column_bounds(cat, table).get(column)
