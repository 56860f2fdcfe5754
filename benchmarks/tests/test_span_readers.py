"""The two readers of the program's spans, on hand-made inputs whose
answers can be checked on paper: a two-thread span file for self time
and union, and a two-query profile with ``citus.*`` annotations for the
idle time by span, with one piece under no leaf span."""

import json

import pytest

from benchmarks.sources import span_self, trace_idle_under
from benchmarks.tests.test_trace_reduce import Line, Plane, Profile, ev


def event(name, span_id, parent_id, ts_ms, dur_ms, tid=1, pid=1):
    args = {"span_id": span_id}
    if parent_id is not None:
        args["parent_id"] = parent_id
    return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": pid, "tid": tid, "args": args}


@pytest.fixture
def spans_dir(tmp_path):
    """One trace of 20 ms.  Caller's thread (tid 1): query 0..20 holds
    parse 0..1 and execute 2..19; execute holds dispatch 3..4,
    wait 5..15 and fetch 16..18.  Decode thread (tid 2): decode_batch
    4..9 (holding pad 7..9) and decode_batch 8..14, overlapping by 1 ms,
    both children of execute.  A remote host's span (pid 1001) under
    execute must not count against it either.  A second trace holds a
    query of 4 ms and nothing else."""
    one = [
        event("query", "q", None, 0, 20),
        event("parse", "p", "q", 0, 1),
        event("execute", "x", "q", 2, 17),
        event("dispatch", "d", "x", 3, 1),
        event("wait:device_round", "w", "x", 5, 10),
        event("fetch", "f", "x", 16, 2),
        event("decode_batch", "b1", "x", 4, 5, tid=2),
        event("pad", "pd", "b1", 7, 2, tid=2),
        event("decode_batch", "b2", "x", 8, 6, tid=2),
        event("execute_task", "r", "x", 6, 3, pid=1001),
        {"name": "process_name", "ph": "M", "pid": 1, "tid": 1,
         "args": {"name": "coordinator"}},
    ]
    two = [event("query", "q", None, 100, 4)]
    for i, events in enumerate((one, two)):
        with open(tmp_path / f"trace_{i}.json", "w") as fh:
            json.dump({"traceEvents": events,
                       "otherData": {"trace_id": str(i), "thread_rows": 2}},
                      fh)
    return tmp_path


def read(spans_dir, spans, **more):
    return span_self.read_dir(str(spans_dir), {"spans": spans, **more})


def test_self_time_subtracts_children_of_the_same_thread_only(spans_dir):
    # execute: 17 - (1 + 10 + 2) = 4; the decode thread's 11 ms and the
    # remote host's 3 ms lie under it and are not taken off
    assert read(spans_dir, ["execute"]) == pytest.approx(4 / 2)
    # query: (20 - 1 - 17) + 4 over two traces
    assert read(spans_dir, ["query"]) == pytest.approx((2 + 4) / 2)
    assert read(spans_dir, ["query", "execute"]) == pytest.approx(10 / 2)
    assert read(spans_dir, ["dispatch", "fetch"]) == pytest.approx(3 / 2)
    # decode_batch self: (5 - 2) + 6
    assert read(spans_dir, ["decode_batch"]) == pytest.approx(9 / 2)


def test_union_is_wall_time_not_a_sum(spans_dir):
    # 4..9 and 8..14 cover 4..14
    assert read(spans_dir, ["decode_batch"], union=True) == \
        pytest.approx(10 / 2)
    assert read(spans_dir, ["decode_batch", "pad"], union=True) == \
        pytest.approx(10 / 2)


def test_a_name_no_trace_holds_reads_zero_and_no_traces_read_nothing(
        spans_dir, tmp_path_factory):
    assert read(spans_dir, ["wait:prefetch_stall"]) == 0.0
    empty = tmp_path_factory.mktemp("none")
    assert read(empty, ["query"]) is None


def test_an_export_without_thread_rows_reads_nothing(spans_dir):
    """The parent commit's export: every span on ``tid`` 1."""
    with open(spans_dir / "trace_1.json", "w") as fh:
        json.dump({"traceEvents": [event("query", "q", None, 0, 4)],
                   "otherData": {"trace_id": "1"}}, fh)
    assert read(spans_dir, ["query"]) is None


@pytest.fixture
def profile():
    """Two queries of 10 ms on the caller's thread, 2 ms apart.

    query 1 (0..10): citus.query 0.5..9.5 > citus.execute 1..9 >
    {citus.init_acc 1..2, citus.device_round 2..3 > citus.dispatch
    2..2.5, citus.wait:device_round 3..7, citus.fetch 7..8.5}.
    The chip runs 2.5..7.  Idle: 0..0.5 under nothing, 0.5..1 under
    query, 1..2 under init_acc, 2..2.5 under dispatch, 7..8.5 under
    fetch, 8.5..9 under execute, 9..9.5 under query, 9.5..10 nothing.
    query 2 (12..22): citus.query 12..22 > citus.fetch 18..20; the chip
    runs 13..18.  Idle: 12..13 and 20..22 under query, 18..20 fetch.
    A decode thread holds citus.decode_batch 3..6, which takes no idle
    time: it is not the calling thread.  Chip 1 is busy elsewhere and
    is not read."""
    caller = Line("python", [
        ev("bench.execute.q1", 0, 10), ev("bench.execute.q6", 12, 10),
        ev("citus.query", 0.5, 9), ev("citus.execute", 1, 8),
        ev("citus.init_acc", 1, 1), ev("citus.device_round", 2, 1),
        ev("citus.dispatch", 2, 0.5), ev("citus.wait:device_round", 3, 4),
        ev("citus.fetch", 7, 1.5),
        ev("citus.query", 12, 10), ev("citus.fetch", 18, 2),
        ev("PjitFunction(fused)", 2, 0.4)])
    decode = Line("citus-host-decode", [ev("citus.decode_batch", 3, 3)])
    chip0 = Plane("/device:TPU:0", [
        Line("XLA Ops", [ev("fusion.1", 2.5, 4.5), ev("fusion.1", 13, 5)])])
    chip1 = Plane("/device:TPU:1", [Line("XLA Ops", [ev("fusion.1", 0, 22)])])
    return Profile([Plane("/host:CPU", [caller, decode]), chip0, chip1])


def test_idle_time_goes_to_the_innermost_span_of_the_calling_thread(profile):
    table, n_queries, n_threads = trace_idle_under.idle_table(profile)
    assert n_queries == 2 and n_threads == 2
    assert {k: v * 1e3 for k, v in table.items()} == {
        "(no span)": pytest.approx(1.0),
        "query": pytest.approx(1.0 + 3.0),
        "execute": pytest.approx(0.5),
        "init_acc": pytest.approx(1.0),
        "dispatch": pytest.approx(0.5),
        "fetch": pytest.approx(1.5 + 2.0),
    }
    # busy + idle of the first chip inside the queries is the queries
    assert sum(table.values()) * 1e3 + 4.5 + 5 == pytest.approx(20)


def test_idle_under_named_spans_and_under_no_leaf_span(profile):
    lines = []
    unattributed = trace_idle_under.read_profile(
        profile, {"spans": None}, log=lines.append)
    assert unattributed == pytest.approx((1.0 + 4.0 + 0.5) / 2)
    assert trace_idle_under.read_profile(
        profile, {"spans": ["fetch", "init_acc"]}) == pytest.approx(4.5 / 2)
    assert lines[0].startswith("idle ms per traced query by span (2 queries")
    assert any(ln.split() == ["fetch", "1.750"] for ln in lines)


def test_no_device_plane_or_no_program_annotation_reads_nothing(profile):
    host_only = Profile(profile.planes[:1])
    assert trace_idle_under.read_profile(host_only, {"spans": None}) is None
    for line in profile.planes[0].lines:
        line.events[:] = [e for e in line.events
                          if not e.name.startswith("citus.")]
    assert trace_idle_under.read_profile(profile, {"spans": None}) is None
    assert trace_idle_under.read_profile(None, {"spans": None}) is None
