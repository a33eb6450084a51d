"""The multi-channel cell's readers (benchmarks/mc_readers.py and the nine
layer_metrics files that call them) on hand-made spans and a hand-made trace
of four device planes, and on a small extract recorded on four chips."""

import json
import os

import pytest

from benchmarks import layer_readers as readers
from benchmarks import mc_readers
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr

MS = 1e6  # ns
PROGRAM = mc_readers.PROGRAM
PLANES = [f"/device:TPU:{i}" for i in range(4)]


def span(name, dur_ms, **args):
    return {"name": name, "ts": 0.0, "dur": dur_ms * 1e3, "ph": "X", "args": args}


def two_steps():
    spans = []
    for step, scale in ((7, 1.0), (8, 2.0)):
        spans.append(span("mc.validate", 100 * scale, step=step, channels=4))
        for ch in "abcd":
            spans.append(span("mc.prepare", 10 * scale, step=step, channel=ch))
            spans.append(span("mc.epilogue", 4 * scale, step=step, channel=ch))
        spans.append(span("mc.stack", 1 * scale, step=step))
        spans.append(span("mc.dispatch", 3 * scale, step=step))
        spans.append(span("mc.resolve", 40 * scale, step=step))
    spans.append(span("tpu.prep", 9.0, block=3))  # another path's: no step
    return spans


def test_a_steps_four_prepares_add_up_and_the_mean_is_over_steps():
    ctx = {"spans": two_steps()}
    assert mc_readers.ms_per_step(ctx, ["mc.prepare"]) == pytest.approx(60.0)
    assert mc_readers.ms_per_step(ctx, ["mc.epilogue"]) == pytest.approx(24.0)
    assert mc_readers.ms_per_step(ctx, ["mc.resolve"]) == pytest.approx(60.0)
    assert mc_readers.ms_per_step(
        ctx, ["mc.stack", "mc.dispatch"]
    ) == pytest.approx(6.0)


def test_a_program_without_the_spans_reads_nothing():
    parent = {"spans": [span("tpu.prep", 9.0, block=3)]}
    assert mc_readers.ms_per_step(parent, ["mc.prepare"]) is None
    assert mc_readers.ms_per_step({"spans": []}, ["mc.prepare"]) is None
    assert mc_readers.ms_per_step({}, ["mc.prepare"]) is None
    # a part is not reported under the whole's name
    some = {"spans": [span("mc.stack", 1.0, step=0)]}
    assert mc_readers.ms_per_step(some, ["mc.stack", "mc.dispatch"]) is None
    # a span of that name with no step (not this path's) is not counted
    assert mc_readers.ms_per_step(
        {"spans": [span("mc.prepare", 5.0)]}, ["mc.prepare"]
    ) is None


def four_planes(offsets_ms=(0.0, 0.05, 0.10, 0.02), launches=4,
                launch_ms=39.0, period_ms=120.0, busy_ms=(39.0, 39.0, 39.0, 39.0)):
    """`launches` sharded launches, each begun on plane i `offsets_ms[i]`
    after the step's own start; plane i's ops cover `busy_ms[i]` of each
    launch.  The slice opens 10 ms before the first launch and closes 5 ms
    into the last one, which is therefore cut on every plane."""
    first = 50.0
    trace = {}
    for plane, offset, busy in zip(PLANES, offsets_ms, busy_ms):
        modules, ops = [], []
        for n in range(launches):
            start = (first + n * period_ms + offset) * MS
            modules.append((f"{PROGRAM}(77)", start, launch_ms * MS))
            ops.append(("%while.1", start, busy * MS))
        trace[plane] = {tr.MODULES_LINE: modules, tr.OPS_LINE: ops}
    lo = (first - 10.0) * MS
    hi = (first + (launches - 1) * period_ms + 5.0) * MS
    trace["/host:CPU"] = {"main": [("bench.trace_slice", lo, hi - lo)]}
    return trace, (lo, hi)


def ctx_of(trace, slice_ns, **over):
    ctx = {
        "trace": trace, "slice_ns": slice_ns,
        "window_ns": tr.whole_cycles(trace, slice_ns),
        "device_kind": "TPU v5 lite", "lanes_per_launch": 1498.0,
    }
    ctx.update(over)
    return ctx


def test_launch_skew_is_latest_less_earliest_start_over_the_planes():
    trace, slice_ns = four_planes()
    by_plane = mc_readers.launches_by_plane(trace, PROGRAM, slice_ns)
    assert sorted(by_plane) == PLANES
    assert [len(runs) for runs in by_plane.values()] == [3, 3, 3, 3]  # one cut
    assert mc_readers.launch_skew_ms(ctx_of(trace, slice_ns)) == pytest.approx(0.10)
    # the metric file reads the same
    metric = bench_run.load_layer_metric("launch_skew_ms.mc")
    assert metric.read(ctx_of(trace, slice_ns)) == pytest.approx(0.10)


def test_a_launch_that_one_plane_holds_cut_is_left_out_of_the_skew():
    trace, (lo, hi) = four_planes(offsets_ms=(0.0, 0.05, 0.10, 0.02))
    # the slice opens between plane 0's and plane 2's start of launch 0
    cut = (50.0 * MS + 0.03 * MS, hi)
    by_plane = mc_readers.launches_by_plane(trace, PROGRAM, cut)
    assert [len(by_plane[p]) for p in PLANES] == [2, 3, 3, 2]
    # launches 1 and 2 are whole on all four: the skew is still 0.10
    assert mc_readers.launch_skew_ms(ctx_of(trace, cut)) == pytest.approx(0.10)


def test_skew_needs_two_planes_and_a_trace():
    trace, slice_ns = four_planes()
    one = {p: lines for p, lines in trace.items() if p in (PLANES[0], "/host:CPU")}
    assert mc_readers.launch_skew_ms(ctx_of(one, slice_ns)) is None
    assert mc_readers.launch_skew_ms({"trace": None}) is None
    other, _ = four_planes()
    assert mc_readers.launch_skew_ms(
        ctx_of(other, slice_ns), program="jit_something_else"
    ) is None


def test_the_idle_share_is_the_mean_over_the_four_planes():
    busy = (39.0, 30.0, 39.0, 12.0)
    trace, slice_ns = four_planes(busy_ms=busy, offsets_ms=(0.0,) * 4)
    ctx = ctx_of(trace, slice_ns)
    lo, hi = ctx["window_ns"]
    assert (hi - lo) / MS == pytest.approx(3 * 120.0)  # three whole cycles
    per_plane = [100.0 * (1 - b / 120.0) for b in busy]
    want = sum(per_plane) / 4
    assert readers.device_idle_pct(ctx) == pytest.approx(want)
    metric = bench_run.load_layer_metric("device_idle_pct.mc")
    assert metric.read(ctx) == pytest.approx(want)
    assert tr.busy_seconds(trace, (lo, hi)) == pytest.approx(3 * sum(busy) / 4 / 1e3)


def test_the_roofline_is_one_chips_lanes_over_one_chips_launch():
    trace, slice_ns = four_planes()
    kernel = bench_run.load_layer_metric("verify_kernel_ms.mc")
    roofline = bench_run.load_layer_metric("verify_roofline.mc")
    ctx = ctx_of(trace, slice_ns)
    assert kernel.read(ctx) == pytest.approx(39.0)  # one plane's, not four
    least, bound = tr.least_seconds(1498, "TPU v5 lite")
    assert bound == "operations"
    assert roofline.read(ctx) == pytest.approx(100 * least / 0.039)
    assert 0.15 < roofline.read(ctx) < 0.25
    # the step's 5,992 lanes over one chip's launch would read four times it
    assert roofline.read(dict(ctx, lanes_per_launch=5992.0)) == pytest.approx(
        4 * roofline.read(ctx)
    )
    assert roofline.read(dict(ctx, trace=None)) is None


@pytest.mark.parametrize("name, value", [
    ("mc_prepare_ms_per_step", 60.0), ("mc_stack_ms_per_step", 1.5),
    ("mc_dispatch_ms_per_step", 4.5), ("mc_resolve_ms_per_step", 60.0),
    ("mc_epilogue_ms_per_step", 24.0),
])
def test_each_span_metric_file_reads_its_span(name, value):
    metric = bench_run.load_layer_metric(name)
    assert metric.read({"spans": two_steps()}) == pytest.approx(value)
    assert metric.read({"spans": []}) is None


def test_every_metric_of_the_cell_has_a_file_and_a_layer():
    loaded = bench_run.load_cell("multichannel-4ch")
    names = {m["name"] for m in loaded["per_layer"]}
    assert names == {
        "setup_trace_lower_s", "setup_compile_or_load_s",
        "mc_prepare_ms_per_step", "mc_stack_ms_per_step",
        "mc_dispatch_ms_per_step", "mc_resolve_ms_per_step",
        "mc_epilogue_ms_per_step", "verify_kernel_ms.mc", "verify_roofline.mc",
        "device_idle_pct.mc", "launch_skew_ms.mc",
    }
    for m in loaded["per_layer"]:
        module = bench_run.load_layer_metric(m["name"])
        assert module.MOVES == m["moves"], m["name"]
    assert {m["name"] for m in loaded["end_to_end"]} == {
        "verdict_lanes_per_s", "verdict_p95_ms", "setup_s",
    }
    assert loaded["cell"]["chips"] == 4


RECORDED = os.path.join(
    os.path.dirname(__file__), "data", "mc_trace_extract.json"
)


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded extract")
def test_the_recorded_four_plane_trace_reduces_to_what_was_read_by_hand():
    with open(RECORDED, encoding="utf-8") as fh:
        recorded = json.load(fh)
    trace = {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in recorded["trace"].items()
    }
    want = recorded["read_by_hand"]
    assert tr.device_planes(trace) == sorted(want["planes"]) and len(want["planes"]) == 4
    for plane, row in want["planes"].items():
        assert PROGRAM in row["programs"], plane  # the name was read, not guessed
    slice_ns = tr.traced_slice(trace, "bench.trace_slice")
    ctx = ctx_of(trace, slice_ns)
    by_plane = mc_readers.launches_by_plane(trace, PROGRAM, slice_ns)
    assert sorted(by_plane) == sorted(want["planes"])
    assert mc_readers.launch_skew_ms(ctx) == pytest.approx(
        want["launch_skew_ms"], rel=1e-6
    )
    assert 0 < want["launch_skew_ms"] < 5.0
    kernel = bench_run.load_layer_metric("verify_kernel_ms.mc").read(ctx)
    assert kernel == pytest.approx(want["kernel_ms_first_plane"], rel=1e-6)
    roofline = bench_run.load_layer_metric("verify_roofline.mc").read(ctx)
    assert roofline == pytest.approx(
        100 * tr.least_seconds(1498, "TPU v5 lite")[0] / (kernel / 1e3)
    )
    assert 0.1 < roofline < 0.4
