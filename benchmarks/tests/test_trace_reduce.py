"""The reduction from a profiler trace to numbers, on a hand-made trace
whose answers can be checked on paper, and the table of peaks."""

from dataclasses import dataclass, field

import pytest

from benchmarks import peaks, roofline, trace_reduce


@dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Profile:
    planes: list


MS = 1e6


def ev(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


@pytest.fixture
def profile():
    """Two queries of 10 ms each, 2 ms apart, on two chips.

    chip 0, query 1 (span 0..10): ops 2..4, 4..5 (touching), 6..8
    chip 0, query 2 (span 12..22): op 13..17, all-reduce 17..18
    chip 1: one op 2..5 and one 13..19 (so the chips differ)
    An op before the first span (warm-up) must not count.
    """
    host = Plane("/host:CPU", [Line("python", [
        ev("bench.execute.q1", 0, 10), ev("bench.execute.q6", 12, 10),
        ev("PjitFunction(fused)", 1, 1)])])
    chip0 = Plane("/device:TPU:0", [
        Line("XLA Ops", [ev("fusion.1", -5, 1), ev("fusion.1", 2, 2),
                         ev("fusion.2", 4, 1), ev("fusion.1", 6, 2),
                         ev("fusion.1", 13, 4),
                         ev("%all-reduce.3 = f32[12]{0} all-reduce(f32[12]{0} "
                            "%fusion.1), replica_groups={}", 17, 1)]),
        Line("XLA Modules", [ev("jit_fused(123)", -5, 1),
                             ev("jit_fused(123)", 2, 3),
                             ev("jit_fused(123)", 6, 2),
                             ev("jit_fused(987)", 13, 5)]),
        Line("Steps", [ev("0", 0, 22)])])
    chip1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [ev("fusion.1", 2, 3), ev("fusion.1", 13, 6)]),
        Line("XLA Modules", [ev("jit_fused(123)", 2, 3),
                             ev("jit_fused(987)", 13, 6)])])
    other = Plane("/device:TPU:0 scratch", [Line("XLA Ops", [ev("x", 0, 22)])])
    return Profile([host, chip0, chip1, other])


def test_busy_union_window_and_modules(profile):
    r = trace_reduce.reduce_trace(profile)
    assert r["n_devices"] == 2 and r["n_spans"] == 2
    assert r["window_s"] == pytest.approx(0.022)
    # chip 0: (2..5) + (6..8) + (13..18) = 10 ms; chip 1: 3 + 6 = 9 ms
    assert r["busy_s"] == pytest.approx((0.010 + 0.009) / 2)
    m = r["modules"]["jit_fused"]
    # chip 0: 3 + 2 + 5 = 10 ms in 3 runs; chip 1: 9 ms in 2 runs
    assert m["seconds"] == pytest.approx((0.010 + 0.009) / 2)
    assert m["count"] == pytest.approx(2.5)
    assert r["collective_s"] == pytest.approx(0.001 / 2)
    assert r["ops"]["fusion.1"] == pytest.approx((0.008 + 0.009) / 2)
    assert r["ops"]["all-reduce.3"] == pytest.approx(0.0005)


@pytest.mark.parametrize("name,collective", [
    ("all_to_all.27", True),        # a v5e trace: named after lax.all_to_all
    ("all-to-all.3", True),
    ("%all_to_all.27 = u32[4,1,163840]{2,1,0} all-to-all(u32[4,1,163840]{2,1,0}"
     " %fusion.9), replica_groups={{0,1,2,3}}", True),
    ("all-reduce-start.2", True), ("all_reduce.1", True),
    ("all-gather.4", True), ("all_gather.4", True),
    ("reduce-scatter.1", True), ("reduce_scatter.1", True),
    ("collective-permute.5", True), ("collective_permute.5", True),
    ("ppermute.2", True),
    ("fusion.46", False), ("while.20", False), ("copy.3", False),
])
def test_a_collective_is_found_under_either_spelling_of_its_name(name,
                                                                 collective):
    """``collective_s`` feeds the ``trace_collectives`` reader and
    nothing else: the op counts as busy time and under its own name in
    ``ops`` whichever way this goes."""
    host = Plane("/host:CPU", [Line("python", [ev("bench.execute.q12", 0, 10)])])
    chips = [Plane(f"/device:TPU:{d}", [
        Line("XLA Ops", [ev("fusion.1", 2, 2), ev(name, 4, 1)]),
        Line("XLA Modules", [ev("jit_join_exchange(5)", 2, 3)])])
        for d in range(4)]
    r = trace_reduce.reduce_trace(Profile([host] + chips))
    assert r["collective_s"] == pytest.approx(0.001 if collective else 0.0)
    assert r["busy_s"] == pytest.approx(0.003) and r["n_devices"] == 4
    assert r["ops"][trace_reduce.op_name(name)] == pytest.approx(0.001)
    assert r["modules"]["jit_join_exchange"]["seconds"] == pytest.approx(0.003)


def test_idle_gaps_say_what_the_host_was_doing(profile):
    gaps = trace_reduce.reduce_trace(profile)["gaps"]
    assert gaps == {
        "execute.q1: before first device op": pytest.approx(0.002),
        "execute.q1: between device ops": pytest.approx(0.001),
        "execute.q1: after last device op": pytest.approx(0.002),
        "between queries": pytest.approx(0.002),
        "execute.q6: before first device op": pytest.approx(0.001),
        "execute.q6: after last device op": pytest.approx(0.004),
    }
    # busy + idle of the first chip is the window
    assert sum(gaps.values()) + 0.010 == pytest.approx(0.022)
    assert trace_reduce.top(gaps, 2) == [
        ["execute.q6: after last device op", pytest.approx(0.004)],
        ["execute.q1: before first device op", pytest.approx(0.002)]]


def test_without_spans_the_window_is_the_device_events(profile):
    profile.planes[0].lines[0].events.clear()
    r = trace_reduce.reduce_trace(profile)
    assert r["window_s"] == pytest.approx(0.024)      # -5 .. 19
    assert r["gaps"] == {"between queries": pytest.approx(0.024 - 0.011)}


def test_no_device_plane_reduces_to_nothing():
    host = Plane("/host:CPU", [Line("python", [ev("bench.execute.q1", 0, 1)])])
    assert trace_reduce.reduce_trace(Profile([host])) is None


def test_merge_and_clip():
    assert trace_reduce.merge([(0, 2), (1, 3), (3, 4), (6, 7)]) == [[0, 4], [6, 7]]
    assert trace_reduce.clip([(0, 4), (6, 7)], 1, 6.5) == [(1, 4), (6, 6.5)]


def test_peak_table_is_keyed_by_the_exact_kind():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    for kind in ("TPU v5", "tpu v5 lite", "TPU v5 lite pod", "cpu"):
        with pytest.raises(KeyError, match="no hbm_bytes_per_s on record"):
            peaks.peak(kind, "hbm_bytes_per_s")
    assert all(p["source"] for p in peaks.PEAKS.values())


def test_algorithmic_bytes_and_floor():
    q1 = {"scanned_columns": {"a": "decimal", "b": "decimal", "c": "decimal",
                              "d": "decimal", "e": "text", "f": "text",
                              "g": "date"}}
    assert roofline.row_bytes(q1["scanned_columns"]) == 51  # bench.py's Q1 count
    assert roofline.algorithmic_bytes(q1, {"lineitem": 1000}) == 51_000
    assert roofline.hbm_floor_s(819e9, "TPU v5 lite", 1) == pytest.approx(1.0)
    assert roofline.hbm_floor_s(819e9, "TPU v5 lite", 4) == pytest.approx(0.25)
