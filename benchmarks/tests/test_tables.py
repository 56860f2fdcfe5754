"""A configuration of several tables through the whole harness, on the
CPU: ``fixtures/two_tables`` holds ``orders`` and ``lineitem``
hash-distributed and colocated on the order key and ``customer`` as a
reference table, a Q3-shaped ``JOIN ... GROUP BY`` and a statement over
``lineitem`` alone.  The fixture enters the scratch checkout as a later
PR's cell would: files copied beside the ones that are there, two
entries appended, nothing edited.  Beside it, the pins that hold the
shipped cells where they were: the data directory's name and the
one-table arithmetic of ``scan_rows_per_s`` and the roofline's bytes
(since PR 47 also over the two configurations that list ``tables``)."""

import json
import os
import shutil
import types

import pytest

from benchmarks import dataset, metrics, roofline
from benchmarks.sources import trace_roofline
from benchmarks.spec import SpecError, plugin, query_tables, tables_of
from benchmarks.traffic import Record
# the scratch checkout of the other cells' rehearsal: what a checkout holds
from test_rehearsal import ROOT, checkout, run  # noqa: F401

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "two_tables")
CELL = "fixture_q3_params"
KINDS = ("configs", "queries", "traffic", "generators", "references")
TPCH_ROWS = {"lineitem": 60_000_000, "orders": 15_000_000,
             "customer": 1_500_000}


def load(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def with_fixture(checkout):  # noqa: F811
    """The checkout with the fixture's cell added as data."""
    before = {os.path.join(d, f) for d, _, files in os.walk(checkout)
              for f in files}
    for kind in KINDS:
        for name in os.listdir(os.path.join(FIXTURE, kind)):
            target = checkout / "benchmarks" / kind / name
            assert not target.exists()
            shutil.copy(os.path.join(FIXTURE, kind, name), target)
    bench = load(checkout, "BENCHMARK.json")
    entries = load(FIXTURE, "entries.json")
    bench["configs"] += entries["configs"]
    bench["workloads"] += entries["workloads"]
    with open(checkout / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)
    added = {os.path.join(d, f) for d, _, files in os.walk(checkout)
             for f in files} - before
    assert len(added) == sum(len(os.listdir(os.path.join(FIXTURE, k)))
                             for k in KINDS)
    return checkout


def bench_run(checkout, trace):  # noqa: F811
    p, out = run(checkout, CELL, trace, "--rehearse-on-cpu", rehearse=False)
    assert p.returncode == 0, p.stderr[-2000:]
    return out


def test_first_run_ingests_second_reopens_a_cut_run_is_made_again(with_fixture):
    first = bench_run(with_fixture, 0)
    assert first["correct"] is True and first["failed"] == 0
    assert set(first["metrics"]) == {"scan_rows_per_s", "setup_s"}
    data = first["info"]["data"]
    assert data["ingested"] is True
    rows = data["table_rows"]
    assert rows["orders"] == 3000 and rows["customer"] == 300
    assert 3000 <= rows["lineitem"] <= 21_000
    assert first["info"]["rows"] == data["rows"] == sum(rows.values())
    # the join names all three tables, the count lineitem alone
    by_query = first["info"]["by_query"]
    assert by_query["fixture_q3"] == 2 * by_query["fixture_lineitem_count"] > 0
    scanned = by_query["fixture_q3"] * sum(rows.values()) \
        + by_query["fixture_lineitem_count"] * rows["lineitem"]
    rate = first["metrics"]["scan_rows_per_s"]["value"]
    assert 0 < rate <= scanned / first["info"]["seconds"]
    assert {d[0] for d in first["info"]["first_draws"]} \
        == {"fixture_q3", "fixture_lineitem_count"}

    second = bench_run(with_fixture, 0)
    assert second["correct"] is True
    assert second["info"]["data"]["ingested"] is False
    assert second["info"]["data"]["data_dir"] == data["data_dir"]
    assert second["info"]["data"]["table_rows"] == rows

    os.remove(with_fixture / "benchmarks" / data["data_dir"] / "READY")
    again = bench_run(with_fixture, 0)
    assert again["correct"] is True
    assert again["info"]["data"]["ingested"] is True
    assert again["info"]["data"]["table_rows"] == rows


def test_traced_run_reports_the_metrics_every_cell_has(with_fixture):
    out = bench_run(with_fixture, 1)
    assert out["correct"] is True and out["failed"] == 0
    assert {"cache_hit_share", "compiles_in_window", "untraced_host_ms"} \
        <= set(out["metrics"])
    assert out["info"]["span_ms_per_query"]["execute"] > 0


def test_a_wrong_join_reference_is_not_correct(with_fixture):
    ref = with_fixture / "benchmarks" / "references" / "fixture_q3.py"
    good = ref.read_text()
    try:
        ref.write_text(good.replace("(100 - stats", "(101 - stats"))
        assert ref.read_text() != good
        out = bench_run(with_fixture, 0)
    finally:
        ref.write_text(good)
    assert out["correct"] is False and out["failed"] == 0


def test_tables_that_are_not_colocated_fail_the_set_up(tmp_path):
    """The program colocates by equal shard count and key type: an
    ``integer`` key beside a ``bigint`` one lands in another group, and
    the harness refuses to measure a join over them."""
    import citus_tpu as ct
    tables = tables_of(load(FIXTURE, "configs", "fixture_orders_lineitem.json"))
    cl = ct.Cluster(str(tmp_path / "ok"))
    try:
        dataset.create_tables(cl, tables, 8)
        view = cl.execute("SELECT citus_tables()")
        kinds = {r[0]: r[1] for r in view.rows}
        assert kinds == {"orders": "hash", "lineitem": "hash",
                         "customer": "reference"}
    finally:
        cl.close()
    apart = json.loads(json.dumps(tables))
    apart[1]["ddl"] = apart[1]["ddl"].replace("l_orderkey bigint",
                                              "l_orderkey integer")
    cl = ct.Cluster(str(tmp_path / "apart"))
    try:
        with pytest.raises(SpecError, match="lineitem .* is not colocated "
                                            "with orders"):
            dataset.create_tables(cl, apart, 8)
    finally:
        cl.close()


# ---- the roofline's bytes ------------------------------------------------


def test_bytes_of_columns_listed_by_table():
    q3 = load(FIXTURE, "queries", "fixture_q3.json")
    # Q3's columns at SF10: 32 B x 60 M + 28 B x 15 M + 14 B x 1.5 M
    assert roofline.algorithmic_bytes(q3, TPCH_ROWS) \
        == 32 * 60_000_000 + 28 * 15_000_000 + 14 * 1_500_000
    count = load(FIXTURE, "queries", "fixture_lineitem_count.json")
    assert roofline.algorithmic_bytes(count, TPCH_ROWS) == 5 * 60_000_000
    assert roofline.TYPE_BYTES["integer"] == 4


def test_trace_roofline_reads_the_tables_each_traced_query_names():
    queries = {n: load(FIXTURE, "queries", n + ".json")
               for n in ("fixture_q3", "fixture_lineitem_count")}
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(
            config={"kernel_modules": {"scan": "jit_fused"}}, queries=queries),
        slice_queries=["fixture_q3", "fixture_q3", "fixture_lineitem_count"],
        rows_by_table=TPCH_ROWS, device_kind="TPU v5 lite", chips=1,
        trace={"modules": {"jit_fused": {"seconds": 2.0, "count": 3}}})
    n_bytes = 2 * (32 * 60e6 + 28 * 15e6 + 14 * 1.5e6) + 5 * 60e6
    assert trace_roofline.read(ctx, {"module": "scan"}) \
        == pytest.approx(100 * n_bytes / 819e9 / 2.0)
    ctx.trace = None
    assert trace_roofline.read(ctx, {"module": "scan"}) is None


QUERY_FILES = sorted(f[:-5] for f in os.listdir(
    os.path.join(ROOT, "benchmarks", "queries")))


@pytest.mark.parametrize("name", QUERY_FILES)
def test_flat_columns_over_one_table_are_the_old_arithmetic(name):
    """A query file written before PR 37 lists its columns flat; one
    over several tables (Q3, Q12) lists them by table, and its bytes are
    that arithmetic table by table."""
    query = load(ROOT, "benchmarks", "queries", name + ".json")
    columns = query["scanned_columns"]

    def per_row(of_table):
        return sum(roofline.TYPE_BYTES[t] + roofline.VALIDITY_BYTES
                   for t in of_table.values())

    table_rows = 59_998_987
    if all(isinstance(t, str) for t in columns.values()):
        assert roofline.algorithmic_bytes(query, {"lineitem": table_rows}) \
            == table_rows * per_row(columns)
    else:
        assert set(columns) == set(query["tables"]) and len(columns) > 1
        rows = {t: table_rows // (i + 1) for i, t in enumerate(columns)}
        assert roofline.algorithmic_bytes(query, rows) \
            == sum(rows[t] * per_row(columns[t]) for t in columns)


# ---- the shipped cells stay where they were ------------------------------


def shipped():
    bench = load(ROOT, "BENCHMARK.json")
    return [pytest.param(
        c["name"], load(ROOT, c["file"]),
        [w for w in bench["workloads"] if w["config"] == c["name"]],
        id=c["name"]) for c in bench["configs"]]


SHIPPED = shipped()


DATA_DIRS = {
    "tpch_sf10_1chip": "tpch_sf10_1chip-seed22-g1-orders15000000",
    "tpch_sf10_4chip": "tpch_sf10_4chip-seed22-g1-orders15000000",
    "tpch_sf1_1chip": "tpch_sf1_1chip-seed22-g1-orders1500000",
    "nyctaxi_hourly_1chip": "nyctaxi_hourly_1chip-seed29-g1-orders85000000",
    "tpch_sf10_supp_1chip": "tpch_sf10_supp_1chip-seed22-g1-orders15000000",
    "tpch_sf10_orders_4chip": "tpch_sf10_orders_4chip-seed22-g1-orders15000000",
    "tpch_sf10_q3_1chip": "tpch_sf10_q3_1chip-seed22-g1-orders15000000",
    "tpch_sf10_q12_4chip": "tpch_sf10_q12_4chip-seed22-g1-orders15000000",
}


@pytest.mark.parametrize("name,config,cells", SHIPPED)
def test_a_shipped_configuration_keeps_its_directory_and_its_form(
        name, config, cells):
    """The chip machines hold these directories: a character's change
    and every one is ingested again (107-213 s; the join cells' three
    and two tables longer).  A configuration written ``table`` / ``ddl``
    reads as a list of one; one that lists ``tables`` as what it lists."""
    generator = plugin("generators", config["generator"]["name"])
    assert dataset.data_dir(config, generator, config["generator"]["orders"]) \
        == os.path.join(ROOT, "benchmarks", ".data", DATA_DIRS[name])
    if "tables" in config:
        assert not {"table", "ddl", "distribution_column"} & set(config)
        assert tables_of(config) == config["tables"]
        arrays = {f"rows.{t['name']}": 7 + i
                  for i, t in enumerate(config["tables"])}
        assert dataset.table_rows(config, arrays) == {
            t["name"]: 7 + i for i, t in enumerate(config["tables"])}
        return
    assert tables_of(config) == [{
        "name": config["table"], "ddl": config["ddl"],
        "distribution": {"kind": "hash",
                         "column": config["distribution_column"]}}]
    assert dataset.table_rows(config, {"rows": 7}) == {config["table"]: 7}


@pytest.mark.parametrize("name,config,cells", SHIPPED)
def test_over_one_table_scan_rows_per_s_is_the_old_formula(
        name, config, cells):
    """Over one table the rate is table rows x queries / time, to the
    digit; over several a query counts the rows of the tables it names
    (``old`` with their sum)."""
    def old(records, t_start, table_rows):      # the parent's body
        return table_rows * len(records) / (max(r.done for r in records)
                                            - t_start)

    tables = tables_of(config)
    rows_of = {t["name"]: 59_998_987 // (i + 1) for i, t in enumerate(tables)}
    t_start = 1234.567
    for cell in cells:
        traffic = load(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")
        queries = {s["query"]: load(ROOT, "benchmarks", "queries",
                                    s["query"] + ".json")
                   for s in traffic["statements"]}
        rows_scanned = {
            q: sum(rows_of[t] for t in query_tables(queries[q], tables))
            for q in queries}
        if "tables" not in config:
            assert set(rows_scanned.values()) == {59_998_987}
        records = []
        for i in range(37):
            r = Record(list(queries)[i % len(queries)], {}, None)
            r.sent = r.due = t_start + 1.3 * i
            r.done = r.sent + 1.2345
            records.append(r)
        records[5].error = "refused"        # a failed record is not counted
        done = [r for r in records if r.error is None]
        got = metrics.end_to_end(["scan_rows_per_s", "query_p50_ms"],
                                 records, t_start, rows_scanned)
        # every statement of a shipped cell scans the same tables
        scanned, = set(rows_scanned.values())
        assert got["scan_rows_per_s"] == old(done, t_start, scanned)
        assert got["query_p50_ms"] == pytest.approx(1234.5)
