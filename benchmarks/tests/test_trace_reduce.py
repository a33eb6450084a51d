"""The reduction from a trace to numbers, on hand-made event lists and on a
small extract of a trace recorded on the chip."""

import json
import os

import pytest

from benchmarks import layer_readers as readers
from benchmarks import trace_reduce as tr

MS = 1e6  # ns
PROGRAM = "jit_verify_batch_bytes_device"


def trace_with_gap():
    """Two whole launches of 40 ms whose ops overlap, a deliberate 100 ms
    gap between them; the device's tracer started inside a launch, half a
    millisecond before the harness's slice opened, and stopped inside
    another, half a millisecond after the slice closed."""
    ops = [
        ("tail.0", 2 * MS, 1 * MS),        # the end of a launch in flight
        ("fusion.1", 10 * MS, 25 * MS),
        ("while.2", 30 * MS, 20 * MS),     # overlaps fusion.1: union 10..50
        ("fusion.1", 150 * MS, 40 * MS),   # 150..190
        ("head.9", 296 * MS, 2 * MS),      # the start of one cut by the stop
    ]
    modules = [
        (PROGRAM + "(123)", 2 * MS, 1 * MS),
        (PROGRAM + "(123)", 10 * MS, 40 * MS),
        (PROGRAM + "(123)", 150 * MS, 40 * MS),
        ("jit_other(7)", 200 * MS, 5 * MS),
        (PROGRAM + "(123)", 296 * MS, 2 * MS),
    ]
    host = [
        ("bench.trace_slice", 2.5 * MS, 295 * MS),
        ("bench.submit", 0.0, 12 * MS),
        ("bench.submit", 50 * MS, 95 * MS),
        ("bench.wait_commit", 195 * MS, 105 * MS),
    ]
    return {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules,
                          "Steps": [("0", 0.0, 300 * MS)]},
        "/host:CPU": {"main": host},
    }


SLICE = (2.5 * MS, 297.5 * MS)


def test_busy_is_the_union_of_op_intervals():
    trace = trace_with_gap()
    assert tr.busy_intervals(trace, "/device:TPU:0") == [
        (2 * MS, 3 * MS), (10 * MS, 50 * MS), (150 * MS, 190 * MS),
        (296 * MS, 298 * MS),
    ]
    assert tr.busy_seconds(trace) == pytest.approx(0.083)
    assert tr.idle_share_pct(0.083, 0.296) == pytest.approx(100 * 213 / 296)


def test_a_window_clips_the_events_it_cuts():
    trace = trace_with_gap()
    assert tr.busy_seconds(trace, (40 * MS, 160 * MS)) == pytest.approx(0.020)


def test_the_traced_slice_ends_where_the_buffers_dropped():
    trace = trace_with_gap()
    # the annotation's own span: never cut to the device events' extent
    assert tr.traced_slice(trace, "bench.trace_slice") == SLICE
    trace["/device:TPU:0"]["XLA TraceMe"] = [(tr.DROPPED, 200 * MS, 100 * MS)]
    assert tr.traced_slice(trace, "bench.trace_slice") == (2.5 * MS, 200 * MS)
    with pytest.raises(ValueError):
        tr.traced_slice(trace, "bench.absent")


def closed_loop_trace(slice_from_ms, slice_ms, launch_ms=158.0, gap_ms=16.0,
                      launches=12):
    """One client, nothing overlapped: a launch of `launch_ms`, then a gap
    of `gap_ms` in which the host carries the mask back and the next request
    in; the profiler runs over [slice_from_ms, slice_from_ms + slice_ms) and
    records only what the device did in there."""
    lo, hi = slice_from_ms * MS, (slice_from_ms + slice_ms) * MS
    modules = []
    for n in range(launches):
        start = n * (launch_ms + gap_ms) * MS
        end = start + launch_ms * MS
        if end > lo and start < hi:  # the tracer cuts what it did not see
            modules.append(
                (PROGRAM + "(5)", max(start, lo), min(end, hi) - max(start, lo))
            )
    return {
        "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": list(modules)},
        "/host:CPU": {"main": [("bench.trace_slice", lo, hi - lo)]},
    }


@pytest.mark.parametrize("slice_from_ms, slice_ms", [
    (160.0, 525.0),   # begins and ends in a gap: 3 launches, 2 gaps between
    (160.0, 700.0),   # the same start, a longer slice
    (165.0, 880.0),   # begins in a gap, ends inside a launch
    (40.0, 600.0),    # begins inside a launch, ends in a gap
    (100.0, 1000.0),  # begins and ends inside launches
])
def test_the_idle_share_is_of_whole_cycles_wherever_the_slice_falls(
    slice_from_ms, slice_ms
):
    trace = closed_loop_trace(slice_from_ms, slice_ms)
    within = tr.traced_slice(trace, "bench.trace_slice")
    lo, hi = tr.whole_cycles(trace, within)
    cycles = (hi - lo) / (174.0 * MS)
    assert cycles == pytest.approx(round(cycles)) and round(cycles) >= 2
    idle = tr.idle_share_pct(tr.busy_seconds(trace, (lo, hi)), (hi - lo) / 1e9)
    assert idle == pytest.approx(100 * 16.0 / 174.0)  # 1 - launch / cycle
    # what the events' own extent reads for the first case: a gap too few
    if (slice_from_ms, slice_ms) == (160.0, 525.0):
        first, last = tr.device_extent(trace, "/device:TPU:0")
        assert tr.idle_share_pct(
            tr.busy_seconds(trace, (first, last)), (last - first) / 1e9
        ) == pytest.approx(100 * 32.0 / 506.0)
    got = readers.device_idle_pct(
        {"trace": trace, "slice_ns": within, "window_ns": (lo, hi)}
    )
    assert got == pytest.approx(idle)


def test_cycles_are_of_the_program_that_holds_most_of_the_time():
    trace = closed_loop_trace(160.0, 700.0)
    modules = trace["/device:TPU:0"]["XLA Modules"]
    # a small program of its own in every gap: it opens no cycle
    small = [("jit_copy(9)", s + d + 2 * MS, 1 * MS) for _, s, d in modules]
    trace["/device:TPU:0"]["XLA Modules"] = sorted(
        modules + small, key=lambda e: e[1]
    )
    lo, hi = tr.whole_cycles(trace, tr.traced_slice(trace, "bench.trace_slice"))
    assert (lo, hi) == (174.0 * MS, 4 * 174.0 * MS)


def test_a_slice_with_one_start_or_none_is_read_as_it_is():
    trace = closed_loop_trace(160.0, 150.0)  # one launch starts in it
    within = tr.traced_slice(trace, "bench.trace_slice")
    assert tr.whole_cycles(trace, within) == within
    trace["/device:TPU:0"]["XLA Modules"] = []
    assert tr.whole_cycles(trace, within) == within


def test_a_launch_the_window_or_the_tracer_cuts_is_not_a_launch():
    trace = trace_with_gap()
    # the 1 ms and 2 ms pieces at the tracer's edges never count, with the
    # harness's slice or without it
    assert tr.program_seconds_per_launch(trace, PROGRAM) == (pytest.approx(0.040), 2)
    assert tr.program_seconds_per_launch(trace, PROGRAM, SLICE) == (
        pytest.approx(0.040), 2
    )
    seconds, launches = tr.program_seconds_per_launch(
        trace, PROGRAM, (20 * MS, SLICE[1])
    )
    assert (seconds, launches) == (pytest.approx(0.040), 1)


def test_program_time_is_its_module_events_over_their_count():
    trace = trace_with_gap()
    seconds, launches = tr.program_seconds_per_launch(trace, PROGRAM)
    assert (seconds, launches) == (pytest.approx(0.040), 2)
    assert tr.program_seconds_per_launch(trace, "jit_absent") is None


def test_the_longest_gap_is_labelled_by_what_the_host_did():
    trace = trace_with_gap()
    gaps = tr.longest_idle_gaps(
        trace, ["bench.submit", "bench.wait_commit"], (2 * MS, 298 * MS)
    )
    assert gaps[0] == ["bench.wait_commit", pytest.approx(0.106)]
    assert gaps[1] == ["bench.submit", pytest.approx(0.100)]
    assert gaps[2] == ["bench.submit", pytest.approx(0.007)]


def test_top_ops_are_summed_by_name():
    assert tr.top_device_ops(trace_with_gap())[0] == [
        "fusion.1", pytest.approx(0.065)
    ]
    long_name = "%while.9 = (s32[], u32[2048]{0}) while(...)" + "x" * 5000
    assert tr.op_name(long_name) == "%while.9"


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.busy_seconds({"/host:CPU": {"main": [("x", 0.0, 1.0)]}})


def test_ops_per_verify_follows_its_written_derivation():
    joint = 256 * 8 + 192 * 11 + 11
    inversions = 2 * (255 + 128 + 2)
    assert tr.field_muls_per_verify() == joint + inversions == 4941
    assert tr.ops_per_verify() == 4941 * (2 * 32 * 32) * 2 == 20_238_336
    assert tr.bytes_per_verify() == 102


def test_the_operations_bound_is_the_one_that_applies():
    least, bound = tr.least_seconds(1498, "TPU v5 lite")
    assert bound == "operations"
    assert least == pytest.approx(1498 * 20_238_336 / 393e12)


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        tr.peaks_for("TPU v9 imaginary")


def test_a_share_over_100_is_an_error_not_a_clip():
    with pytest.raises(ValueError):
        tr.roofline_share_pct(1498, 1e-6, "TPU v5 lite")
    assert 0 < tr.roofline_share_pct(1498, 0.05, "TPU v5 lite") < 1


def ctx(**over):
    base = {
        "trace": trace_with_gap(), "slice_ns": SLICE,
        "window_ns": (2 * MS, 298 * MS),
        "device_kind": "TPU v5 lite", "lanes_per_launch": 1498.0,
        "spans": [
            {"name": "pipeline.prepare", "ts": 0, "dur": 12000.0},
            {"name": "pipeline.prepare", "ts": 5, "dur": 8000.0},
            {"name": "serve.verify", "ts": 9, "dur": 50000.0},
        ],
        "client_wall_ms": [60.0, 70.0],
    }
    base.update(over)
    return base


def test_readers_read_and_return_nothing_where_nothing_is():
    assert readers.span_mean_ms(ctx(), "pipeline.prepare") == pytest.approx(10.0)
    assert readers.span_mean_ms(ctx(), "pipeline.commit") is None
    assert readers.client_overhead_ms(ctx(), "serve.verify") == pytest.approx(15.0)
    assert readers.program_ms_per_launch(ctx(), PROGRAM) == pytest.approx(40.0)
    assert readers.device_idle_pct(ctx()) == pytest.approx(100 * 213 / 296)
    assert 0 < readers.program_roofline_pct(ctx(), PROGRAM) < 1
    # a CPU run has no trace: every device metric stays silent, none is 0
    cpu = ctx(trace=None, slice_ns=None, window_ns=None)
    assert readers.program_ms_per_launch(cpu, PROGRAM) is None
    assert readers.program_roofline_pct(cpu, PROGRAM) is None
    assert readers.device_idle_pct(cpu) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data", "chip_trace_extract.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded extract")
def test_the_recorded_chip_trace_reduces_to_what_was_read_by_hand():
    with open(RECORDED, encoding="utf-8") as fh:
        recorded = json.load(fh)
    trace = {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in recorded["trace"].items()
    }
    want = recorded["read_by_hand"]
    modules = trace["/device:TPU:0"]["XLA Modules"]
    assert len(modules) == want["launches"] == 6
    assert sum(m[2] for m in modules) / 6 / 1e6 == pytest.approx(
        want["kernel_ms_per_launch"]  # 125 ms: what a plain mean would read
    )
    assert tr.busy_seconds(trace) == pytest.approx(want["busy_s"], rel=1e-6)
    # the profiler cut the launches in flight when it started (34 ms left of
    # one) and stopped (85 ms of another): only the whole ones count, with
    # the harness's slice or without it
    assert tr.program_seconds_per_launch(trace, PROGRAM)[1] == 4
    within = tr.traced_slice(trace, "bench.trace_slice")
    seconds, launches = tr.program_seconds_per_launch(trace, PROGRAM, within)
    assert launches == want["whole_launches_in_slice"] == 4
    assert seconds * 1e3 == pytest.approx(want["kernel_ms_per_whole_launch"])
    assert 157.7 < seconds * 1e3 < 157.9
    # busy and idle over the whole cycles: from the first launch that began
    # under the tracer to the start of the last one, four launches on
    lo, hi = tr.whole_cycles(trace, within)
    assert (lo, hi) == (modules[1][1], modules[5][1])
