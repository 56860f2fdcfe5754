"""Finds every file of the benchmark by the name ``BENCHMARK.json``
gives: a cell names its configuration and its traffic mix, a traffic
mix names its queries, a metric names its reader.  Adding a cell, a
configuration, a query or a per-layer metric is adding files and an
entry; nothing here is edited."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    """A file of the benchmark is missing or says something the harness
    cannot run."""


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"no such benchmark file: {path}") from None


def plugin(package: str, name: str):
    """Module ``benchmarks.<package>.<name>``: generators, references and
    metric sources are found by name, so a later PR adds one as a file."""
    try:
        return importlib.import_module(f"benchmarks.{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.{package}.{name}":
            raise
        raise SpecError(f"no {package} named {name!r} under benchmarks/"
                        f"{package}/") from None


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, workload: str):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"BENCHMARK.json has no workload {workload!r}; "
                            f"it has {sorted(cells)}")
        self.name = workload
        self.entry = cells[workload]
        self.chips = int(self.entry["chips"])
        files = {c["name"]: c["file"] for c in bench["configs"]}
        with open(os.path.join(ROOT, files[self.entry["config"]])) as fh:
            self.config = json.load(fh)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.queries = {
            s["query"]: load_json("queries", s["query"] + ".json")
            for s in self.traffic["statements"]}
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    def _has(self, metric) -> bool:
        return self.name in metric.get("workloads", [self.name])
