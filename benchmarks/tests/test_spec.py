"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names."""

import collections
import json
import os
import re
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as fh:
        return json.load(fh)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_run_length(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(bench):
    sources = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmarks/")
        f = load(os.path.relpath(c["file"], "benchmarks"))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert f["reduced"] == c["reduced"]
        assert f["guarantees"] and f["assumed"]
        sources.add(c["source"])
    assert len(sources) == len(bench["configs"])      # sources differ
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"]: load("configs", c["name"] + ".json")
               for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert configs[w["config"]]["chips"] == w["chips"]
        traffic = load("traffic", w["traffic"] + ".json")
        for s in traffic["statements"]:
            q = load("queries", s["query"] + ".json")
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", "references", q["reference"] + ".py"))
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    reported = {c: set() for c in cells}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for c in m.get("workloads", cells):
            assert c in cells
            reported[c].add(m["name"])
    for c in cells:
        assert "setup_s" in reported[c] and len(reported[c]) >= 2
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        f = load("layer_metrics", m["name"] + ".json")
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert f[k] == m[k], (m["name"], k)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "sources", f["reader"]["kind"] + ".py"))
        assert line(m["layer"])
        # a per-layer metric is reported only where the metric it moves is
        for c in m.get("workloads", cells):
            assert m["moves"] in reported[c], (m["name"], c)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    assert all(layer in perf for layer in layers)


# ---- one metric a reader: the list of per-layer metrics ------------------

PER_LAYER_MAX = 128             # the contract's


def reader_of(name):
    return load("layer_metrics", name + ".json")["reader"]


def copies_of_one_reader(per_layer, reader_of=reader_of):
    """-> [[names]]: the families of entries that share ``(reader, unit,
    better, moves, layer)``, everything but the name, the ``what`` and
    the cells they list.  One entry with the union of their
    ``workloads`` reads in each cell what the copy read there
    (``spec.Cell._has``; every reader resolves the cell's own from the
    cell), so folding a family frees all but one of its places.  A PR
    that adds a cell may not append it to an accepted metric's
    ``workloads`` (an edit) and brings copies under its prefix; the next
    ``benchmark`` PR folds them as PR 47 did.  So this is NOT pinned
    empty: it is what to do when the list is full."""
    families = collections.defaultdict(list)
    for m in per_layer:
        families[json.dumps(reader_of(m["name"]), sort_keys=True), m["unit"],
                 m["better"], m["moves"], m["layer"]].append(m["name"])
    return sorted(v for v in families.values() if len(v) > 1)


def test_per_layer_fits_and_says_what_a_fold_would_free(bench):
    copies = copies_of_one_reader(bench["per_layer"])
    n = len(bench["per_layer"])
    assert n <= PER_LAYER_MAX, (
        f"per_layer holds {n} entries, {PER_LAYER_MAX} allowed; folding "
        f"these copies of one reader frees "
        f"{sum(len(f) - 1 for f in copies)}: {copies}")


def test_copies_of_one_reader_finds_a_later_cells_prefixed_copy(bench):
    """The helper on the list as a later cell's PR leaves it."""
    assert copies_of_one_reader(bench["per_layer"]) == []      # after PR 47
    entry, = [m for m in bench["per_layer"] if m["name"] == "decode_wait_ms"]
    copy = dict(entry, name="q10_decode_wait_ms", workloads=["tpch_sf10_q10"])
    assert copies_of_one_reader(
        bench["per_layer"] + [copy],
        lambda name: reader_of(name.removeprefix("q10_"))) \
        == [["decode_wait_ms", "q10_decode_wait_ms"]]


def test_every_entry_has_one_file_and_every_file_one_entry(bench):
    entries = [m["name"] for m in bench["per_layer"]]
    files = os.listdir(os.path.join(ROOT, "benchmarks", "layer_metrics"))
    assert len(set(entries)) == len(entries)
    assert sorted(n + ".json" for n in entries) == sorted(files)
    # ... and every reader under sources/ is one some file names
    kinds = set()
    for n in entries:
        reader = reader_of(n)
        kinds |= {reader["kind"], reader.get("of", reader["kind"])}
    assert kinds == {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "sources"))
        if f.endswith(".py") and f != "__init__.py"}


# ---- PR 47's fold: no cell lost a reading ---------------------------------

#: ``per_layer`` as the parent of PR 47 had it, each entry with its
#: file's reader: {name: {reader, unit, better, moves, layer, workloads}}
BEFORE = load("tests", "fixtures", "per_layer_before_pr47.json")
#: old name -> the entry that reads the same thing since PR 47
FOLDED = load("tests", "fixtures", "folded_by_pr47.json")
#: the twelve cells the parent had
CELLS = sorted({c for e in BEFORE.values() for c in e["workloads"] or ()})
#: the two readers PR 47 changed.  The Q12 cell's collective came through
#: ``trace_ops`` by name prefix because ``trace_collectives`` could not
#: see an ``all_to_all.N``; now it can, and ``trace_ops`` is gone.  The Q3
#: cell's ``q3_h2d_ms`` read the ``h2d`` spans alone and is ``h2d_ms``
#: now, which also names ``stack``: a span only a mesh round writes, so on
#: that cell's one chip the reading is the same (the reading test below)
TRACE_OPS = {"kind": "trace_ops", "prefixes": ["all_to_all", "all-to-all"]}
H2D_ALONE = {"kind": "span_self", "spans": ["h2d"]}
READS_NOW = [(TRACE_OPS, {"kind": "trace_collectives"}),
             (H2D_ALONE, {"kind": "span_self", "spans": ["h2d", "stack"]})]


def reads_now(reader):
    return next((now for then, now in READS_NOW if then == reader), reader)


def listed(entry, cell):
    return entry["workloads"] is None or cell in entry["workloads"]


def test_the_fixture_table_names_what_went_and_what_stands(bench):
    now = {m["name"]: m for m in bench["per_layer"]}
    assert len(BEFORE) == 127 and len(FOLDED) == 38
    assert not set(FOLDED) & set(now)
    after = set(BEFORE) - set(FOLDED) | set(FOLDED.values())
    assert len(after) == 100 and after <= set(now)
    for old, new in FOLDED.items():
        was, m = BEFORE[old], now[new]
        assert (was["unit"], was["better"], was["moves"], was["layer"]) \
            == (m["unit"], m["better"], m["moves"], m["layer"])
        assert set(was["workloads"]) <= set(m["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_resolves_to_the_readers_it_resolved_to_before(bench, cell):
    def readings(entries):
        return collections.Counter(
            (json.dumps(reads_now(reader), sort_keys=True), unit, better, moves)
            for reader, unit, better, moves in entries)

    before = readings((e["reader"], e["unit"], e["better"], e["moves"])
                      for e in BEFORE.values() if listed(e, cell))
    now = readings(
        (reader_of(m["name"]), m["unit"], m["better"], m["moves"])
        for m in bench["per_layer"]
        if cell in m.get("workloads", [cell]))
    assert not before - now, f"{cell} lost {before - now}"
    # ... and every cell reads kernel_compiles_in_window since PR 47
    compiles = (json.dumps(reader_of("kernel_compiles_in_window"),
                           sort_keys=True), "count", "lower", "setup_s")
    assert now[compiles] == 1


def family_cases():
    """(old name, its reader then, cell) for every member of a family
    PR 47 folded, the member whose name the family kept among them."""
    families = set(FOLDED.values())
    return [pytest.param(name, e["reader"], cell, id=f"{name}-{cell}")
            for name, e in BEFORE.items()
            if FOLDED.get(name, name) in families
            for cell in e["workloads"]]


def hand_built_ctx(cell_name, spans_dir):
    """A traced run's ``ctx`` for the cell, by hand: the cell's own
    configuration and queries (``kernel_modules``, ``scanned_columns``,
    ``group_product``), a device trace reduced from a hand-made profile
    with a module for each of the cell's roles and an ``all_to_all``,
    counters, and one exported statement whose spans are the names the
    folded readers look for -- ``stack`` only where there are four
    chips, as in a real run."""
    from benchmarks import trace_reduce
    from benchmarks.spec import Cell
    from benchmarks.tests.test_span_readers import event as span
    from benchmarks.tests.test_trace_reduce import Line, Plane, Profile, ev
    cell = Cell(cell_name)
    query = next(iter(cell.queries))
    modules = sorted(set(cell.config["kernel_modules"].values()))
    chips = [Plane(f"/device:TPU:{d}", [
        Line("XLA Ops", [ev("fusion.1", 1, 2 + d), ev("all_to_all.27", 5, 1),
                         ev("all-to-all.3", 6, 0.5), ev("fusion.2", 12, 3)]),
        Line("XLA Modules", [ev(f"{m}(77)", 1 + 2 * i, 1.5 + i + d)
                             for i, m in enumerate(modules)]
             + [ev(f"{modules[0]}(77)", 12, 3)])])
        for d in range(cell.chips)]
    host = Plane("/host:CPU", [Line("python", [
        ev(f"bench.execute.{query}", 0, 10),
        ev(f"bench.execute.{query}", 10, 10)])])
    events = [span("query", "q", None, 0, 40),
              span("execute", "x", "q", 1, 38),
              span("device_round", "r", "x", 2, 9),
              span("h2d", "h", "r", 3, 4),
              span("wait:prefetch_stall", "w", "x", 12, 5),
              span("hash_filter", "hf", "x", 18, 2),
              span("spill_drain", "sd", "x", 20, 1.5),
              span("fetch", "f", "x", 22, 3),
              span("hash_merge", "hm", "x", 26, 1),
              span("hash_finalize", "hz", "x", 27, 2),
              span("finalize_groups", "fg", "x", 30, 6),
              span("decode_batch", "b", "x", 2, 9, tid=2)]
    if cell.chips > 1:
        events.append(span("stack", "s", "r", 2.2, 0.7))
    with open(os.path.join(spans_dir, "trace_0.json"), "w") as fh:
        json.dump({"traceEvents": events,
                   "otherData": {"trace_id": "0", "thread_rows": 2}}, fh)
    rows = {t["name"]: 1_000_000 * (i + 1)
            for i, t in enumerate(cell.tables)}
    return types.SimpleNamespace(
        cell=cell, n_queries=8, slice_queries=[query, query],
        counters={"direct_groups": 8 * 4345, "hash_spill_rows": 96,
                  "hash_entries_fetched": 8 * 5120, "join_host_fallbacks": 0},
        trace=trace_reduce.reduce_trace(Profile([host] + chips)),
        table_rows=sum(rows.values()), rows_by_table=rows, chips=cell.chips,
        device_kind="TPU v5 lite")


@pytest.mark.parametrize("old,reader_then,cell", family_cases())
def test_a_folded_entry_reads_what_the_cells_copy_read(
        old, reader_then, cell, tmp_path, monkeypatch):
    from benchmarks.sources import span_self
    from benchmarks.spec import plugin
    monkeypatch.setattr(span_self, "SPANS_DIR", str(tmp_path))
    ctx = hand_built_ctx(cell, str(tmp_path))
    reader = reader_of(FOLDED.get(old, old))
    got = plugin("sources", reader["kind"]).read(ctx, reader)
    assert got is not None
    if reader_then == TRACE_OPS:
        # the reader that went, by hand: the all_to_all ops per query
        ops = ctx.trace["ops"]
        want = (ops["all_to_all.27"] + ops["all-to-all.3"]) * 1e3 / 2
        assert want > 0
    else:
        want = plugin("sources", reader_then["kind"]).read(ctx, reader_then)
    assert got == pytest.approx(want, rel=1e-12)
    if old == "q3_h2d_ms":
        # h2d_ms also names stack, which a one-chip run never writes
        assert ctx.chips == 1 and got == pytest.approx(4.0)
    if reader["kind"] == "span_self" and "stack" in reader["spans"] \
            and ctx.chips == 4:
        assert got >= 0.7


def test_files_under_paths_are_named_from_the_allowed_characters():
    """Every file a commit would hold.  A checkout is no git repository,
    so the tree is walked; what ``.gitignore`` keeps out is left out by
    name."""
    ignored = {".data", "__pycache__", ".pytest_cache"}
    files = []
    for d, dirs, names in os.walk(os.path.join(ROOT, "benchmarks")):
        dirs[:] = [x for x in dirs if x not in ignored]
        files += [os.path.relpath(os.path.join(d, n), ROOT) for n in names
                  if not n.endswith(".pyc")]
    assert len(files) > 100
    assert all(re.match(r"^[A-Za-z0-9_./-]+$", f) for f in files)


# ---- a configuration of several tables -----------------------------------

FIXTURE = os.path.join(ROOT, "benchmarks", "tests", "fixtures", "two_tables")


def fixture_config():
    with open(os.path.join(FIXTURE, "configs",
                           "fixture_orders_lineitem.json")) as fh:
        return json.load(fh)


def test_a_configuration_of_several_tables_is_files_and_entries(bench):
    """What ``spec.py`` promises: the fixture's configuration resolves
    from its files alone, through the same ``Cell`` as the shipped ones."""
    from benchmarks.spec import query_tables, tables_of
    tables = tables_of(fixture_config())
    assert [t["name"] for t in tables] == ["orders", "lineitem", "customer"]
    assert [t["distribution"]["kind"] for t in tables] \
        == ["hash", "hash", "reference"]
    assert tables[1]["distribution"]["colocate_with"] == "orders"
    with open(os.path.join(FIXTURE, "entries.json")) as fh:
        entries = json.load(fh)
    for group in ("configs", "workloads"):
        assert all(set(e) == set(bench[group][0]) for e in entries[group])
    for name, want in (("fixture_q3", ["orders", "lineitem", "customer"]),
                       ("fixture_lineitem_count", ["lineitem"])):
        with open(os.path.join(FIXTURE, "queries", name + ".json")) as fh:
            assert query_tables(json.load(fh), tables) == want


def test_every_shipped_configuration_is_a_list_of_one_table(bench):
    """... where it is written ``table`` / ``ddl``; one that lists
    ``tables`` (Q3's, Q12's) resolves to what it lists, and its queries
    to the tables each names."""
    from benchmarks.spec import Cell
    one_table = 0
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        if "tables" in cell.config:
            assert cell.tables == cell.config["tables"]
            have = {t["name"] for t in cell.tables}
            assert all(set(named) <= have
                       for named in cell.query_tables.values())
            continue
        one_table += 1
        assert len(cell.tables) == 1
        assert cell.query_tables == {q: [cell.tables[0]["name"]]
                                     for q in cell.queries}
    assert one_table >= 10


def broken(**change):
    config = fixture_config()
    for path, value in change.items():
        at = config
        *keys, last = [int(k) if k.isdigit() else k for k in path.split("__")]
        for k in keys:
            at = at[k]
        if value is None:
            del at[last]
        else:
            at[last] = value
    return config


@pytest.mark.parametrize("config,message", [
    (broken(table="lineitem"), "one form or the other"),
    (broken(ddl="CREATE TABLE t (a bigint)"), "one form or the other"),
    (broken(tables=None), "names no table"),
    (broken(tables=[]), "lists no table"),
    (broken(tables__1__distribution__colocate_with="customer"),
     "no earlier table"),
    (broken(tables__1__distribution__colocate_with="lineitem"),
     "no earlier table"),
    (broken(tables__0__distribution__kind="range"), "unknown distribution"),
    (broken(tables__2__distribution__column="c_custkey"),
     "unknown distribution"),
    (broken(tables__0__distribution__column=None), "unknown distribution"),
    (broken(tables__2__name="orders"), "listed twice"),
    (broken(tables__0__rows=3000), "a table is"),
])
def test_a_configuration_the_harness_cannot_run_is_a_spec_error(config,
                                                                message):
    from benchmarks.spec import SpecError, tables_of
    with pytest.raises(SpecError, match=message):
        tables_of(config)


@pytest.mark.parametrize("query,message", [
    ({"tables": ["supplier"]}, "scans"),
    ({"tables": []}, "scans"),
    ({"scanned_columns": {"l_orderkey": "bigint"}}, "scanned_columns"),
    ({"scanned_columns": {"supplier": {"s_suppkey": "bigint"}}},
     "scanned_columns"),
    ({"scanned_columns": {"orders": {"o_orderkey": "bigint"},
                          "l_orderkey": "bigint"}}, "scanned_columns"),
    ({"tables": ["lineitem"],
      "scanned_columns": {"orders": {"o_orderkey": "bigint"}}},
     "scanned_columns"),
])
def test_a_query_over_tables_it_does_not_have_is_a_spec_error(query, message):
    from benchmarks.spec import SpecError, query_tables, tables_of
    with pytest.raises(SpecError, match=message):
        query_tables(dict(query, name="q"), tables_of(fixture_config()))
