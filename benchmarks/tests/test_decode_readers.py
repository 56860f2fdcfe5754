"""The two readers of the decode thread's own account, on hand-made
inputs whose answers can be checked on paper: ``trace_idle_behind`` on a
profile of two host threads (the caller and the decode thread), and
``span_attr`` on span files that carry the native pool's attributes."""

import json

import pytest

from benchmarks.sources import span_attr, trace_idle_behind, trace_idle_under
from benchmarks.tests.test_span_readers import event
from benchmarks.tests.test_trace_reduce import Line, Plane, Profile, ev


@pytest.fixture
def profile():
    """Two queries of 20 ms on the caller's thread, 5 ms apart; the chip
    runs 9..11 and 17..19 in the first and 30..44 in the second.

    query 1 (0..20), caller: citus.query 0..20 > citus.execute 1..19 >
    {citus.wait:prefetch_stall 2..9, citus.h2d 9..10,
    citus.wait:prefetch_stall 11..17, citus.fetch 19..19.5}.
    Decode thread: citus.decode_batch 1.5..8 > {citus.stripe_read 2..6 >
    {citus.footer_read 2..3, citus.batch_layout 3..3.5,
    citus.native_decode 3.5..5.5}, citus.pad 6..7.5}; then
    citus.decode_batch 8.5..16 > citus.stripe_read 9..15 >
    {citus.footer_read 9..12, citus.stripe_fallback 12..13,
    citus.native_decode 13..15}; citus.wait:prefetch_full 16..18.
    Idle under the stall, by the producer: 2..3 footer_read, 3..3.5
    batch_layout, 3.5..5.5 native_decode, 5.5..6 stripe_read itself,
    6..7.5 pad, 7.5..8 decode_batch itself, 8..8.5 nothing, 8.5..9
    decode_batch itself; 11..12 footer_read, 12..13 stripe_fallback,
    13..15 native_decode, 15..16 the parents, 16..17 wait:prefetch_full.
    Idle elsewhere (0..2, 10..11 under h2d, 19..20) is not counted.
    query 2 (25..45): no stall at all, the chip busy under a fetch.
    Chip 1 is busy elsewhere and is not read."""
    caller = Line("python", [
        ev("bench.execute.q1", 0, 20), ev("bench.execute.q6", 25, 20),
        ev("citus.query", 0, 20), ev("citus.execute", 1, 18),
        ev("citus.wait:prefetch_stall", 2, 7), ev("citus.h2d", 9, 1),
        ev("citus.wait:prefetch_stall", 11, 6), ev("citus.fetch", 19, 0.5),
        ev("citus.query", 25, 20), ev("citus.fetch", 30, 14)])
    decode = Line("citus-host-decode", [
        ev("citus.decode_batch", 1.5, 6.5), ev("citus.stripe_read", 2, 4),
        ev("citus.footer_read", 2, 1), ev("citus.batch_layout", 3, 0.5),
        ev("citus.native_decode", 3.5, 2), ev("citus.pad", 6, 1.5),
        ev("citus.decode_batch", 8.5, 7.5), ev("citus.stripe_read", 9, 6),
        ev("citus.footer_read", 9, 3), ev("citus.stripe_fallback", 12, 1),
        ev("citus.native_decode", 13, 2),
        ev("citus.wait:prefetch_full", 16, 2)])
    chip0 = Plane("/device:TPU:0", [Line("XLA Ops", [
        ev("fusion.1", 9, 2), ev("fusion.1", 17, 2), ev("fusion.1", 30, 14)])])
    chip1 = Plane("/device:TPU:1", [Line("XLA Ops", [ev("fusion.1", 0, 45)])])
    return Profile([Plane("/host:CPU", [caller, decode]), chip0, chip1])


def test_idle_under_the_stall_goes_to_the_producers_innermost_span(profile):
    table, n_queries = trace_idle_behind.behind_table(profile)
    assert n_queries == 2
    assert {k: v * 1e3 for k, v in table.items()} == {
        "footer_read": pytest.approx(1.0 + 1.0),
        "batch_layout": pytest.approx(0.5),
        "native_decode": pytest.approx(2.0 + 2.0),
        "pad": pytest.approx(1.5),
        "stripe_fallback": pytest.approx(1.0),
        "wait:prefetch_full": pytest.approx(1.0),
        "(between spans)": pytest.approx(0.5 + 0.5 + 0.5 + 1.0),
        "(no producer span)": pytest.approx(0.5),
    }
    # the parts are the whole: what trace_idle_under files under the wait
    under, _, _ = trace_idle_under.idle_table(profile)
    assert sum(table.values()) == pytest.approx(under["wait:prefetch_stall"])
    assert under["wait:prefetch_stall"] * 1e3 == pytest.approx(7.0 + 6.0)


def test_named_labels_per_query_and_the_table_on_stderr(profile):
    lines = []
    assert trace_idle_behind.read_profile(
        profile, {"spans": ["native_decode"]}, log=lines.append) == \
        pytest.approx(4.0 / 2)
    assert trace_idle_behind.read_profile(
        profile, {"spans": ["(between spans)", "(no producer span)"]}) == \
        pytest.approx(3.0 / 2)
    assert trace_idle_behind.read_profile(
        profile, {"spans": ["chunk_read"]}) == 0.0
    assert lines[0].startswith(
        "idle ms per traced query under wait:prefetch_stall by the "
        "producer's span (2 queries, 6.500 in all)")
    assert any(ln.split() == ["native_decode", "2.000"] for ln in lines)


def test_a_caller_that_decodes_inline_is_no_producer(profile):
    """decode_batch on a thread that holds bench.execute.* (depth 0, the
    mesh loop's first pulls) is the caller's own work, never a producer:
    with the decode thread gone, every stalled piece has no producer."""
    caller, decode = profile.planes[0].lines
    caller.events.append(ev("citus.decode_batch", 19.5, 0.3))
    caller.events.append(ev("citus.footer_read", 19.5, 0.2))
    del decode.events[:]
    table, _ = trace_idle_behind.behind_table(profile)
    assert {k: v * 1e3 for k, v in table.items()} == {
        "(no producer span)": pytest.approx(13.0)}


def test_without_the_inside_names_or_a_device_plane_reads_nothing(profile):
    host_only = Profile(profile.planes[:1])
    assert trace_idle_behind.read_profile(host_only, {"spans": ["pad"]}) is None
    assert trace_idle_behind.read_profile(None, {"spans": ["pad"]}) is None
    # the parent's trace: decode_batch, stripe_read and pad, no more
    for line in profile.planes[0].lines:
        line.events[:] = [e for e in line.events if e.name.split(".")[-1] not in
                          ("footer_read", "batch_layout", "native_decode",
                           "stripe_fallback")]
    assert trace_idle_behind.read_profile(profile, {"spans": ["pad"]}) is None


def native(span_id, ts_ms, dur_ms, **attrs):
    e = event("native_decode", span_id, "r", ts_ms, dur_ms, tid=2)
    e["args"].update(attrs)
    return e


@pytest.fixture
def spans_dir(tmp_path):
    """Two traces.  The first: native_decode of 10 ms on 8 threads with
    read 6 + decompress 34 thread-ms and 100 raw bytes, one of 5 ms on 4
    threads with 2 + 8 and 20 raw bytes, a pad with 30, and a
    native_decode of an older program (no attribute at all).  The
    second: a query and nothing else."""
    one = [
        event("stripe_read", "r", None, 0, 30, tid=2),
        native("n1", 0, 10, threads=8, read_ms=6.0, decompress_ms=34.0,
               bytes_raw=100),
        native("n2", 12, 5, threads=4, read_ms=2.0, decompress_ms=8.0,
               bytes_raw=20),
        native("n3", 20, 5),
        dict(event("pad", "p", None, 31, 2, tid=2),
             args={"span_id": "p", "bytes_raw": 30}),
    ]
    two = [event("query", "q", None, 100, 4)]
    for i, events in enumerate((one, two)):
        with open(tmp_path / f"trace_{i}.json", "w") as fh:
            json.dump({"traceEvents": events,
                       "otherData": {"trace_id": str(i), "thread_rows": 2}},
                      fh)
    return tmp_path


def test_span_attr_sums_per_trace(spans_dir):
    read = lambda **a: span_attr.read_dir(str(spans_dir), a)  # noqa: E731
    assert read(spans=["native_decode"], attrs=["read_ms"]) == \
        pytest.approx(8.0 / 2)
    assert read(spans=["native_decode", "pad"], attrs=["bytes_raw"]) == \
        pytest.approx(150 / 2)
    assert read(spans=["native_decode"], attrs=["read_ms", "decompress_ms"],
                scale=2) == pytest.approx(50.0)


def test_span_attr_share_of_what_the_spans_offered(spans_dir):
    # worked 40 + 10 thread-ms of 8 x 10 + 4 x 5 offered; the span
    # without attributes offers nothing
    assert span_attr.read_dir(str(spans_dir), {
        "spans": ["native_decode"], "attrs": ["read_ms", "decompress_ms"],
        "over": {"attr": "threads", "times_span_ms": True},
        "scale": 100}) == pytest.approx(100 * 50 / 100)
    assert span_attr.read_dir(str(spans_dir), {
        "spans": ["native_decode"], "attrs": ["bytes_raw"],
        "over": {"attr": "threads"}}) == pytest.approx(120 / 12)


def test_span_attr_reads_nothing_where_nothing_was_written(spans_dir,
                                                          tmp_path_factory):
    assert span_attr.read_dir(str(spans_dir), {
        "spans": ["pad"], "attrs": ["read_ms"]}) is None
    assert span_attr.read_dir(str(spans_dir), {
        "spans": ["footer_read"], "attrs": ["chunks"]}) is None
    assert span_attr.read_dir(str(tmp_path_factory.mktemp("none")), {
        "spans": ["native_decode"], "attrs": ["read_ms"]}) is None
    assert span_attr.read_dir(str(spans_dir), {
        "spans": ["native_decode"], "attrs": ["read_ms"],
        "over": {"attr": "no_such"}}) is None


def test_every_new_metric_names_a_reader_the_harness_finds():
    from benchmarks.spec import Cell, load_json, plugin
    # PR 33's and, in the first cell, PR 45's decode_streams_per_query; a
    # later PR adds to a cell and edits no test, so these are floors
    for cell, expect in (("tpch_sf10_q1q6_params", 16),
                         ("tpch_sf1_q18_orders_params", 15),
                         ("tpch_sf10_orderkey_lookup", 4)):
        new = [m["name"] for m in Cell(cell).per_layer
               if m["name"].startswith(("decode_", "stall_idle_", "producer_",
                                        "kernel_compiles"))
               and m["name"] not in ("decode_wall_ms", "decode_wait_ms")]
        assert len(new) >= expect, (cell, new)
        for name in new:
            reader = load_json("layer_metrics", name + ".json")["reader"]
            assert callable(plugin("sources", reader["kind"]).read)
