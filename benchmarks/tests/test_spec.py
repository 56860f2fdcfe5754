"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names."""

import json
import os
import re

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


def test_files_under_paths_are_named_from_the_allowed_characters():
    import subprocess
    files = subprocess.run(["git", "ls-files", "-co", "--exclude-standard",
                            "benchmarks"], cwd=ROOT, capture_output=True,
                           text=True, check=True).stdout.split()
    assert files
    assert all(re.match(r"^[A-Za-z0-9_./-]+$", f) for f in files)
