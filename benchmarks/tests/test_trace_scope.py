"""Device time by the kernels' named steps (``sources/trace_scope.py``)
on a hand-made profile and hand-made maps whose answers can be checked
on paper."""

import json
import types

import pytest

from benchmarks.sources import trace_scope as S
from benchmarks.tests.test_trace_reduce import Line, Plane, Profile, ev

MS = 1e-3


def op(scope=None, inside=(), calls=()):
    return {"scope": scope, "inside": list(inside), "calls": list(calls)}


#: Q1's ``jit_fused`` and Q6's: both number a ``fusion.1``, under
#: different scopes
Q1_FUSED = {"fusion.1": op("scan.filter"), "fusion.2": op("scan.reduce"),
            "copy.3": op()}
Q6_FUSED = {"fusion.1": op("scan.reduce"), "fusion.9": op("scan.fold")}
HASH = {"sort.4": op("hash.sort"),
        "while.8": op("hash.offer", calls=["cond.1", "body.2"]),
        "fusion.7": op("hash.offer"), "copy.9": op(),
        "fusion.3": op("hash.segments", inside=["hash.gather",
                                                "hash.segments"])}


def write_maps(directory, entry=None, **maps):
    directory.mkdir(exist_ok=True)
    for i, (name, ops) in enumerate(maps.items()):
        module = name.rsplit("_v", 1)[0]
        doc = {"module": module, "signature": "", "ops": ops}
        if entry is not None:
            doc["entry"] = entry[name]
        (directory / f"{module}.{i}.scopes.json").write_text(json.dumps(doc))
    return S.load_maps(str(directory))


@pytest.fixture
def profile():
    """One chip, two queries (spans 0..30 and 40..60).

    query 1: ``jit_hash_fused`` 2..22 -- sort.4 2..6; while.8 6..20 over
    two trips of its body (fusion.7 7..10, copy.9 10..11; fusion.7
    12..16, copy.9 16..17); fusion.3 20..21; the last ms under no op.
    Then Q1's ``jit_fused`` 24..29: fusion.1 24..26, fusion.2 26..28,
    copy.3 28..29.
    query 2: Q6's ``jit_fused`` 42..46 (fusion.1 42..45, fusion.9
    45..46), an execution no map names 50..53 (fusion.77), and a module
    without a map, ``jit_narrow`` 55..56.
    A warm-up execution before the first span must not count.
    """
    host = Plane("/host:CPU", [Line("python", [
        ev("bench.execute.q1", 0, 30), ev("bench.execute.q6", 40, 20)])])
    chip = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            ev("sort.4", -8, 2),
            ev("%sort.4 = (u32[8]{0}, s32[8]{0}) sort(u32[8]{0} %a)", 2, 4),
            ev("while.8", 6, 14),
            ev("fusion.7", 7, 3), ev("copy.9", 10, 1),
            ev("fusion.7", 12, 4), ev("copy.9", 16, 1),
            ev("fusion.3", 20, 1),
            ev("fusion.1", 24, 2), ev("fusion.2", 26, 2), ev("copy.3", 28, 1),
            ev("fusion.1", 42, 3), ev("fusion.9", 45, 1),
            ev("fusion.77", 50, 3),
            ev("fusion.1", 55, 1)]),
        Line("XLA Modules", [
            ev("jit_hash_fused(5)", -8, 2),
            ev("jit_hash_fused(5)", 2, 20),
            ev("jit_fused(11)", 24, 5),
            ev("jit_fused(12)", 42, 4),
            ev("jit_fused(13)", 50, 3),
            ev("jit_narrow(2)", 55, 1)])])
    return Profile([host, chip])


@pytest.fixture
def maps(tmp_path):
    return write_maps(tmp_path / "spans.kernels", jit_fused_v1=Q1_FUSED,
                      jit_fused_v6=Q6_FUSED, jit_hash_fused=HASH)


def seconds(table, module):
    return {s: pytest.approx(v) for (m, s), v in table.scopes.items()
            if m == module}


def test_a_while_over_its_body_counts_once(profile, maps):
    t = S.scope_table(profile, maps)
    assert t.n_devices == 1
    # while.8: 14 ms, of which its body's ops cover 9: all of it is
    # hash.offer (the copies take the loop's scope), once
    assert seconds(t, "jit_hash_fused") == {
        "hash.sort": 4 * MS, "hash.offer": 14 * MS,
        "hash.segments": 1 * MS, S.UNSCOPED: 1 * MS}
    # the fusion that holds two steps counts whole under its root's
    assert t.mixed[("jit_hash_fused", "hash.segments")] == [
        pytest.approx(1 * MS), {"hash.gather", "hash.segments"}]
    assert t.unscoped_ops[("jit_hash_fused", "(between the ops)")] \
        == pytest.approx(1 * MS)


def test_two_variants_of_one_name_are_kept_apart(profile, maps):
    t = S.scope_table(profile, maps)
    # fusion.1 is Q1's filter in one execution and Q6's reduce in the
    # other; copy.3 has no scope and no enclosing op; jit_fused(13)
    # runs a fusion.77 that no map names
    assert seconds(t, "jit_fused") == {
        "scan.filter": 2 * MS, "scan.reduce": (2 + 3) * MS,
        "scan.fold": 1 * MS, S.UNSCOPED: (1 + 3) * MS}
    assert t.unscoped_ops[("jit_fused", "copy.3")] == pytest.approx(1 * MS)
    assert t.unscoped_ops[
        ("jit_fused", "(no map names this execution's ops)")] \
        == pytest.approx(3 * MS)


def test_variants_that_disagree_on_a_covered_op_name_nothing(tmp_path):
    chip = Plane("/device:TPU:0", [
        Line("XLA Ops", [ev("fusion.1", 1, 2)]),
        Line("XLA Modules", [ev("jit_fused(1)", 1, 2)])])
    maps = write_maps(tmp_path / "k", jit_fused_v1=Q1_FUSED,
                      jit_fused_v6=Q6_FUSED)
    t = S.scope_table(Profile([chip]), maps)
    assert seconds(t, "jit_fused") == {S.UNSCOPED: 2 * MS}
    # ... unless the entry ops say which variant ran: Q1's runs a
    # fusion.2 this execution lacks
    told = write_maps(tmp_path / "e", jit_fused_v1=Q1_FUSED,
                      jit_fused_v6=Q6_FUSED, entry={
                          "jit_fused_v1": ["fusion.1", "fusion.2"],
                          "jit_fused_v6": ["fusion.1"]})
    t = S.scope_table(Profile([chip]), told)
    assert seconds(t, "jit_fused") == {"scan.reduce": 2 * MS}
    # two maps that agree are one answer
    same = write_maps(tmp_path / "s", jit_fused_v1=Q1_FUSED,
                      jit_fused_v2=dict(Q1_FUSED, extra=op("scan.env")))
    t = S.scope_table(Profile([chip]), same)
    assert seconds(t, "jit_fused") == {"scan.filter": 2 * MS}


def test_ops_that_overlap_without_nesting_share_no_instant(tmp_path):
    """An asynchronous copy runs on under the op that follows it: the
    overlap is the later op's, the rest of the copy its own, and the
    execution's 10 ms are counted once."""
    chip = Plane("/device:TPU:0", [
        Line("XLA Ops", [ev("copy-start.1", 0, 6), ev("fusion.2", 2, 2),
                         ev("fusion.1", 5, 4)]),
        Line("XLA Modules", [ev("jit_fused(1)", 0, 10)])])
    maps = write_maps(tmp_path / "k", jit_fused=dict(
        Q1_FUSED, **{"copy-start.1": op("scan.env")}))
    t = S.scope_table(Profile([chip]), maps)
    # copy-start 0..2 and 4..5; fusion.2 2..4; fusion.1 5..9; 9..10 none
    assert seconds(t, "jit_fused") == {
        "scan.env": 3 * MS, "scan.reduce": 2 * MS, "scan.filter": 4 * MS,
        S.UNSCOPED: 1 * MS}


def test_scopes_and_unscoped_add_up_to_the_modules_seconds(profile, maps):
    from benchmarks import trace_reduce
    t = S.scope_table(profile, maps)
    reduced = trace_reduce.reduce_trace(profile)
    for module in ("jit_hash_fused", "jit_fused"):
        total = sum(v for (m, _), v in t.scopes.items() if m == module)
        assert total == pytest.approx(reduced["modules"][module]["seconds"])
        assert t.modules[module][0] == pytest.approx(total)
    # a module without a map is counted, and split by nothing
    assert t.modules["jit_narrow"] == [pytest.approx(1 * MS), 1]
    assert t.mapped_modules() == ["jit_fused", "jit_hash_fused"]


def test_the_metrics(profile, maps):
    t = S.scope_table(profile, maps)
    roles = {"hash": "jit_hash_fused", "scan": "jit_fused",
             "join": "jit_join_probe"}
    read = lambda args: S.read_table(t, args, roles, 2)
    # ms per query: two traced queries, one chip
    assert read({"module": "hash", "scopes": ["hash.offer"]}) \
        == pytest.approx(7.0)
    assert read({"module": "hash", "scopes": ["hash.keys", "hash.sort"]}) \
        == pytest.approx(2.0)
    assert read({"module": "scan", "scopes": ["scan.reduce", "scan.fold"]}) \
        == pytest.approx(3.0)
    # a module that did not run, a role the cell does not have
    assert read({"module": "join", "scopes": ["probe.lookup"]}) is None
    assert read({"module": "top", "scopes": ["x"]}) is None
    # unscoped: (1 + 4) of the (20 + 12) ms of the modules with a map
    assert read({"scopes": None}) == pytest.approx(100 * 5 / 32)
    assert S.read_table(None, {"scopes": None}, roles, 2) is None
    lines = []
    S.print_table(t, 2, lines.append)
    text = "\n".join(lines)
    assert "jit_hash_fused: 10.000 ms, 0.5 executions a query" in text
    assert "mixed 0.500 ms: fusions that also hold hash.gather" in text
    assert "unscoped: copy.3" in text


def test_the_time_is_averaged_over_the_chips(profile, maps):
    second = Plane("/device:TPU:1", [
        Line("XLA Ops", [ev("sort.4", 2, 2)]),
        Line("XLA Modules", [ev("jit_hash_fused(5)", 2, 2)])])
    idle = Plane("/device:TPU:2", [Line("XLA Ops", [])])
    t = S.scope_table(Profile(profile.planes + [second, idle]), maps)
    assert t.n_devices == 2
    assert S.read_table(t, {"module": "hash", "scopes": ["hash.sort"]},
                        {"hash": "jit_hash_fused"}, 2) \
        == pytest.approx((4 + 2) / 2 / 2)


def test_no_maps_reads_nothing(profile, tmp_path, monkeypatch):
    """The parent's side of the driver's pair writes no map: None, not 0."""
    ctx = types.SimpleNamespace(
        trace={"modules": {}}, slice_queries=["q1", "q6"],
        cell=types.SimpleNamespace(config={"kernel_modules": {
            "hash": "jit_hash_fused"}}))
    args = {"module": "hash", "scopes": ["hash.sort"]}
    monkeypatch.setattr(S.trace_reduce, "load", lambda d: profile)
    for directory in (tmp_path / "missing", tmp_path / "empty"):
        monkeypatch.setattr(S, "MAPS_DIR", str(directory))
        monkeypatch.setattr(S, "_last", (None, None))
        assert S.read(ctx, args) is None
        assert S.read(ctx, {"scopes": None}) is None
        (tmp_path / "empty").mkdir(exist_ok=True)
    # ... and with maps the same call reads, and prints its table once
    write_maps(tmp_path / "empty", jit_hash_fused=HASH)
    monkeypatch.setattr(S, "_last", (None, None))
    printed = []
    monkeypatch.setattr(S, "_log", printed.append)
    assert S.read(ctx, args) == pytest.approx(2.0)
    n = len(printed)
    assert n and S.read(ctx, {"scopes": None}) == pytest.approx(5.0)
    assert len(printed) == n
    assert S.read(types.SimpleNamespace(trace=None, slice_queries=["q"]),
                  args) is None


def test_the_entries_and_their_files_agree():
    """Every metric this reader serves: its entry lists cells whose
    configuration gives the module's role, and its scopes are ones the
    program names (``tests/test_kernel_scopes.py`` holds that list to the
    kernels)."""
    import os

    from benchmarks.spec import HERE, ROOT, Cell, load_json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entries = {m["name"]: m for m in json.load(fh)["per_layer"]}
    named = {"hash": {"keys", "sort", "gather", "segments", "ends", "offer"},
             "probe": {"lanes", "pack", "lookup", "block", "payload"},
             "scan": {"env", "filter", "group_id", "reduce", "fold"}}
    served, split = [], set()
    for path in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        doc = load_json("layer_metrics", path)
        if doc["reader"]["kind"] != "trace_scope":
            continue
        entry = entries[doc["name"]]
        assert {k: doc[k] for k in ("unit", "better", "layer", "moves",
                                    "source")} \
            == {k: entry[k] for k in ("unit", "better", "layer", "moves",
                                      "source")}
        assert entry["source"] == "device_trace"
        served.append(doc["name"])
        scopes = doc["reader"]["scopes"]
        if scopes is None:
            assert "tpch_sf10_orderkey_lookup" not in entry["workloads"]
            continue
        for s in scopes:
            family, step = s.split(".")
            assert step in named[family], s
            assert s not in split, f"{s} is read by two metrics"
            split.add(s)
        for cell in entry["workloads"]:
            assert doc["reader"]["module"] in Cell(cell).config[
                "kernel_modules"], (doc["name"], cell)
    assert len(served) == 11
    assert split == {f"{f}.{s}" for f, steps in named.items() for s in steps}
