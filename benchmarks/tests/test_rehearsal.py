"""Every cell end to end on the CPU at a tiny row count, in a scratch
checkout that holds what a checkout holds (``BENCHMARK.json``, the
benchmark's directory, the program).  The same scratch checkout shows
that a cell is added by data files and an entry alone, and that a wrong
reference answer prints ``correct: false``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ORDERS = "4000"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    top = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), top)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), top / "benchmarks",
                    ignore=shutil.ignore_patterns(".data", "__pycache__",
                                                  ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "citus_tpu"), top / "citus_tpu")
    return top


def run(checkout, workload, trace, *extra, seconds="1.5", rehearse=True):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", workload,
           "--seed", "7", "--seconds", seconds, "--trace", str(trace), *extra]
    if rehearse:
        cmd += ["--rehearse-on-cpu", "--orders", ORDERS]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=300)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


def bench(checkout):
    with open(checkout / "BENCHMARK.json") as fh:
        return json.load(fh)


def expected_metrics(checkout, workload, group):
    return {m["name"] for m in bench(checkout)[group]
            if workload in m.get("workloads", [workload])}


CELLS = ["tpch_sf10_q1_repeat", "tpch_sf10_q1q6_params",
         "tpch_sf10x4_q1_repeat"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_untraced(checkout, workload):
    p, out = run(checkout, workload, 0)
    assert p.returncode == 0, p.stderr[-2000:]
    assert KEYS <= set(out) and "breakdown" not in out
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["rehearsal"] is True
    assert set(out["metrics"]) == expected_metrics(checkout, workload,
                                                   "end_to_end")
    assert all(m["value"] > 0 for m in out["metrics"].values())
    chips = next(w["chips"] for w in bench(checkout)["workloads"]
                 if w["name"] == workload)
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == chips


@pytest.mark.parametrize("workload", CELLS)
def test_cell_traced(checkout, workload):
    p, out = run(checkout, workload, 1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    want = expected_metrics(checkout, workload, "per_layer")
    # the CPU backend's trace has no device plane: the trace's metrics are
    # left out of a rehearsal, every other one is there
    from_trace = {m["name"] for m in bench(checkout)["per_layer"]
                  if m["source"] == "device_trace"}
    assert set(out["metrics"]) == want - from_trace - {"peak_hbm_gb"}
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    hit = out["metrics"]["cache_hit_share"]["value"]
    assert hit == 100 if workload.endswith("_repeat") else hit < 100
    assert out["info"]["span_ms_per_query"]["execute"] > 0


def test_second_run_reopens_the_table(checkout):
    _, first = run(checkout, "tpch_sf10_q1_repeat", 0)
    _, second = run(checkout, "tpch_sf10_q1_repeat", 0)
    assert second["info"]["data"]["ingested"] is False
    assert second["info"]["rows"] == first["info"]["rows"]


def test_same_seed_same_draws(checkout):
    _, a = run(checkout, "tpch_sf10_q1q6_params", 0)
    _, b = run(checkout, "tpch_sf10_q1q6_params", 0)
    n = min(a["attempted"], b["attempted"])
    assert n >= 2
    assert a["info"]["first_draws"][:n] == b["info"]["first_draws"][:n]


def test_no_tpu_no_result(checkout):
    p, out = run(checkout, "tpch_sf10_q1_repeat", 0, rehearse=False)
    assert p.returncode != 0 and out is None
    assert "no TPU found" in p.stderr or "no accelerator" in p.stderr


def test_only_the_benchmark_is_not_enough(tmp_path):
    """A directory that holds BENCHMARK.json and the benchmark's files
    alone -- no program -- runs nothing."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".data", "__pycache__"))
    p, out = run(tmp_path, "tpch_sf10_q1_repeat", 0)
    assert p.returncode != 0 and out is None


def test_a_wrong_reference_answer_is_not_correct(checkout):
    ref = checkout / "benchmarks" / "references" / "q1.py"
    good = ref.read_text()
    try:
        ref.write_text(good.replace("avg_dec(disc, n, 2), n))",
                                    "avg_dec(disc, n, 2), n + 1))"))
        assert ref.read_text() != good
        p, out = run(checkout, "tpch_sf10_q1_repeat", 0)
    finally:
        ref.write_text(good)
    assert p.returncode == 0 and out["correct"] is False
    assert "wrong answer" in p.stderr


def add_cell(checkout, name, config, traffic_name, traffic, metrics=()):
    """What a later PR does: a traffic file and an entry, nothing edited."""
    with open(checkout / "benchmarks" / "traffic" / f"{traffic_name}.json",
              "x") as fh:
        json.dump(traffic, fh)
    b = bench(checkout)
    b["workloads"].append({"name": name, "config": config,
                           "traffic": traffic_name, "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in metrics and "workloads" in m:
            m["workloads"].append(name)
    with open(checkout / "BENCHMARK.json", "w") as fh:
        json.dump(b, fh)


def test_a_cell_is_added_and_removed_as_data(checkout):
    before = (checkout / "BENCHMARK.json").read_text()
    tracked = subprocess.run(["git", "status", "--short", "benchmarks"],
                             cwd=ROOT, capture_output=True, text=True).stdout
    add_cell(checkout, "tpch_sf10_orderkey_lookup", "tpch_sf10_1chip",
             "orderkey_lookup_uniform",
             {"loop": "closed", "clients": 1, "ordering": "cycle",
              "statements": [{"query": "orderkey_lookup",
                              "parameters": "uniform_key"}],
              "warmup_cycles": 2, "traced_slice_cycles": 20},
             metrics=("query_p50_ms", "query_p95_ms", "plan_ms"))
    try:
        p, out = run(checkout, "tpch_sf10_orderkey_lookup", 0)
        assert p.returncode == 0, p.stderr[-2000:]
        assert out["correct"] is True and out["attempted"] > 5
        assert {"query_p50_ms", "scan_rows_per_s", "setup_s"} <= set(
            out["metrics"])
        keys = {json.dumps(d) for d in out["info"]["first_draws"]}
        assert len(keys) > 3                      # the key varies
        p, out = run(checkout, "tpch_sf10_orderkey_lookup", 1)
        assert out["correct"] is True and "plan_ms" in out["metrics"]
    finally:
        os.remove(checkout / "benchmarks" / "traffic"
                  / "orderkey_lookup_uniform.json")
        (checkout / "BENCHMARK.json").write_text(before)
    _, out = run(checkout, "tpch_sf10_q1_repeat", 0)
    assert out["correct"] is True
    assert tracked == subprocess.run(
        ["git", "status", "--short", "benchmarks"], cwd=ROOT,
        capture_output=True, text=True).stdout


def test_open_loop_with_zipf_keys_and_several_clients(checkout):
    before = (checkout / "BENCHMARK.json").read_text()
    add_cell(checkout, "lookup_open", "tpch_sf10_1chip", "lookup_open_zipf",
             {"loop": "open", "rate_per_s": 20, "clients": 3,
              "ordering": "random",
              "statements": [
                  {"query": "orderkey_lookup", "weight": 3,
                   "parameters": {"kind": "zipf", "theta": 0.99}},
                  {"query": "q6", "weight": 1, "parameters": "tpch"}],
              "warmup_cycles": 4, "traced_slice_cycles": 5})
    try:
        p, out = run(checkout, "lookup_open", 0, seconds="2")
        assert p.returncode == 0, p.stderr[-2000:]
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] == 40             # 20 per second for 2 s
        assert out["info"]["generator_lateness_max_s"] >= 0
        assert out["info"]["by_query"]["orderkey_lookup"] > 0
    finally:
        os.remove(checkout / "benchmarks" / "traffic" / "lookup_open_zipf.json")
        (checkout / "BENCHMARK.json").write_text(before)
