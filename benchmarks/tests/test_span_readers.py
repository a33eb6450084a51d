"""The reducers of span_readers.py, on hand-made span lists, a hand-made
trace and a flight ring filled by hand."""

import importlib.util
import json
import os

import pytest

from benchmarks import layer_readers as readers
from benchmarks import span_readers as spans
from benchmarks import trace_reduce as tr

MS = 1e6  # ns
PROGRAM = "jit_verify_batch_bytes_device"
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def span(name, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_ms * 1e3, "args": args}


def test_mean_per_block_sums_the_named_spans_of_each_block():
    ctx = {"spans": [
        span("prepare.parse", 4.0, block=1), span("prepare.parse", 6.0, block=2),
        span("prepare.content_check", 1.0, block=1),
        span("prepare.content_check", 1.0, block=2),
        span("prepare.collect_sig_jobs", 2.0, block=1),
        # block 2's collect_sig_jobs began after the window: not in the list
        span("pipeline.commit", 150.0, block=1),  # not named: not counted
    ]}
    names = ("prepare.content_check", "prepare.parse", "prepare.collect_sig_jobs")
    assert spans.mean_ms_per_unit(ctx, names) == pytest.approx(14.0 / 2)
    assert spans.mean_ms_per_unit(ctx, ("prepare.parse",)) == pytest.approx(5.0)


def test_mean_per_request_counts_a_coalesced_launch_for_each_of_its_requests():
    ctx = {"spans": [
        span("tpu.prep", 3.0, req_id=7), span("tpu.dispatch", 1.0, req_id=7),
        span("tpu.prep", 5.0, req_ids=[8, 9]),
        span("tpu.dispatch", 3.0, req_ids=[8, 9]),
    ]}
    assert spans.mean_ms_per_unit(ctx, ("tpu.prep", "tpu.dispatch")) == (
        pytest.approx(12.0 / 3)
    )


def test_a_part_of_the_spans_is_not_reported_under_the_wholes_name():
    # the parent of PR 28 has serve.decode (with req_id) and no serve.reply
    ctx = {"spans": [span("serve.decode", 2.9, req_id=1),
                     span("serve.verify", 165.0, req_id=1)]}
    assert spans.mean_ms_per_unit(ctx, ("serve.decode", "serve.reply")) is None
    assert spans.mean_ms_per_unit(ctx, ("serve.decode",)) == pytest.approx(2.9)


def test_self_time_is_the_parent_less_its_children():
    ctx = {"spans": [
        span("pipeline.commit", 154.0, block=1),
        span("pipeline.commit", 158.0, block=2),
        span("commit.await_verdicts", 90.0, block=1),
        span("commit.await_verdicts", 70.0, block=2),
    ]}
    assert spans.self_ms_per_unit(
        ctx, "pipeline.commit", ("commit.await_verdicts",)
    ) == pytest.approx(156.0 - 80.0)


@pytest.mark.parametrize("ctx", [
    {}, {"spans": []}, {"spans": [span("pipeline.prepare", 19.0, block=1)]},
    # a parent of PR 28 has the span but not the identifier scheme's children
    {"spans": [span("pipeline.commit", 150.0, block=1)]},
])
def test_span_reducers_find_nothing_in_a_program_without_the_spans(ctx):
    assert spans.mean_ms_per_unit(ctx, ("commit.await_verdicts",)) is None
    assert spans.self_ms_per_unit(
        ctx, "pipeline.commit", ("commit.await_verdicts",)
    ) is None


def trace_with_attributed_gaps():
    """Three launches of 100 ms, 120 ms apart: two whole cycles with a gap
    of 20 ms each (100..120, 220..240).  The program's annotations lie on
    two host lines and overlap; the harness's own annotation covers all."""
    modules = [(PROGRAM + "(1)", at * MS, 100 * MS) for at in (0, 120, 240)]
    ops = [("while.1", at * MS, 100 * MS) for at in (0, 120, 240)]
    dispatcher = [
        # host prep, nested in the launch span (which is in no class)
        ("batcher.launch", 110 * MS, 10 * MS),
        ("tpu.prep", 111 * MS, 6 * MS),       # 111..117
        ("tpu.dispatch", 117 * MS, 3 * MS),   # 117..120, runs into the launch
        ("batcher.launch", 232 * MS, 8 * MS),
        ("tpu.prep", 233 * MS, 4 * MS),       # 233..237
        ("tpu.dispatch", 237 * MS, 5 * MS),   # 237..242: 2 ms lie in busy time
        ("tpu.prep", 50 * MS, 10 * MS),       # inside a launch: no idle under it
    ]
    client = [
        ("bench.batch_verify", 100 * MS, 120 * MS),
        ("client.decode", 101 * MS, 3 * MS),    # 101..104
        ("client.encode", 105 * MS, 4 * MS),    # 105..109
        ("client.roundtrip", 109 * MS, 111 * MS),  # in no class
        ("serve.decode", 109 * MS, 3 * MS),     # 109..112: 111..112 under prep
        ("serve.reply", 219 * MS, 3 * MS),      # 219..222: 2 ms in the gap
        ("client.decode", 223 * MS, 2 * MS),    # 223..225
        ("client.encode", 226 * MS, 5 * MS),    # 226..231
    ]
    return {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
        "/host:CPU": {"verify-batcher": dispatcher, "main": client},
    }


def test_idle_classes_partition_each_gap_between_two_names_and_no_name():
    trace = trace_with_attributed_gaps()
    window = tr.whole_cycles(trace, (-1 * MS, 341 * MS))
    assert window == (0.0, 240 * MS)
    ctx = {"trace": trace, "window_ns": window}
    got = spans.idle_ms_by_class(ctx)
    # gap 100..120: prep 111..120 = 9; wire 101..104, 105..109, 109..111 = 9;
    #   no name 100..101, 104..105 = 2
    # gap 220..240: prep 233..240 = 7; wire 220..222, 223..225, 226..231 = 9;
    #   no name 222..223, 225..226, 231..233 = 4
    assert got == {
        "host_prep": pytest.approx(16.0 / 2),
        "wire": pytest.approx(18.0 / 2),
        "unattributed": pytest.approx(6.0 / 2),
    }
    # the three add up to the gap: device_idle_pct x the traced cycle
    idle_pct = readers.device_idle_pct(ctx)
    cycle_ms = (window[1] - window[0]) / 2 / MS
    assert sum(got.values()) == pytest.approx(idle_pct / 100 * cycle_ms)
    assert sum(got.values()) == pytest.approx(20.0)
    for label, value in got.items():
        assert spans.idle_ms_per_cycle(ctx, label) == value


def test_idle_reducer_finds_nothing_without_the_programs_annotations():
    trace = trace_with_attributed_gaps()
    window = (0.0, 240 * MS)
    assert spans.idle_ms_by_class({"trace": None, "window_ns": window}) is None
    assert spans.idle_ms_by_class({"trace": trace, "window_ns": None}) is None
    # the parent of PR 28: only the harness's own annotation on the host
    trace["/host:CPU"] = {"main": [("bench.batch_verify", 100 * MS, 120 * MS)]}
    ctx = {"trace": trace, "window_ns": window}
    assert spans.idle_ms_by_class(ctx) is None
    assert spans.idle_ms_per_cycle(ctx, "unattributed") is None
    # no whole cycle in the window, no device plane
    trace = trace_with_attributed_gaps()
    assert spans.idle_ms_by_class(
        {"trace": trace, "window_ns": (10 * MS, 90 * MS)}
    ) is None
    del trace["/device:TPU:0"]
    assert spans.idle_ms_by_class({"trace": trace, "window_ns": window}) is None


def test_interval_arithmetic():
    assert spans._union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [
        (0, 3), (5, 8)
    ]
    assert spans._intersect([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)
    ]
    assert spans._subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 35)]) == [
        (0, 2), (3, 8), (22, 29)
    ]
    assert spans._subtract([(0, 10)], []) == [(0, 10)]
    assert spans._subtract([(0, 10)], [(0, 10)]) == []


def at(name, start_s, end_s, **args):
    return {"name": name, "ph": "X", "ts": start_s * 1e6,
            "dur": (end_s - start_s) * 1e6, "args": args}


def test_setup_seconds_cover_the_spans_that_ended_before_the_window_opened():
    events = [
        at("program.trace_lower", 10.0, 40.0, event="jaxpr_trace_duration"),
        # a jit traced inside the outer trace: covered once, not added
        at("program.trace_lower", 12.0, 20.0, event="jaxpr_trace_duration"),
        at("program.trace_lower", 40.0, 55.0,
           event="jaxpr_to_mlir_module_duration"),
        at("program.compile_or_load", 55.0, 64.0),
        at("pipeline.prepare", 5.0, 64.5, block=0),
        at("bench.window", 100.0, 115.0),
        # a span that ends inside the window is not set-up (and would fail
        # the run's compiles_in_window check besides)
        at("program.compile_or_load", 99.5, 100.5),
        at("program.trace_lower", 101.0, 102.0),
    ]
    assert spans.setup_seconds_of(events, ("program.trace_lower",)) == (
        pytest.approx(45.0)
    )
    assert spans.setup_seconds_of(events, ("program.compile_or_load",)) == (
        pytest.approx(9.0)
    )
    both = ("program.trace_lower", "program.compile_or_load")
    assert spans.setup_seconds_of(events, both) == pytest.approx(54.0)


def test_setup_seconds_find_nothing_without_the_spans_or_the_window():
    window = at("bench.window", 100.0, 115.0)
    name = ("program.trace_lower",)
    assert spans.setup_seconds_of([], name) is None
    # the parent of PR 28: a window, and none of the spans
    assert spans.setup_seconds_of(
        [at("pipeline.prepare", 5.0, 64.5), window], name
    ) is None
    # no window span, or two: nothing to cut set-up off at
    early = at("program.trace_lower", 10.0, 40.0)
    assert spans.setup_seconds_of([early], name) is None
    assert spans.setup_seconds_of([early, window, window], name) is None
    # only a span inside the window
    assert spans.setup_seconds_of(
        [window, at("program.trace_lower", 101.0, 102.0)], name
    ) is None


def test_setup_seconds_read_the_live_ring_and_nothing_where_fabobs_is_off(
        monkeypatch):
    import time

    from fabric_tpu.common import fabobs

    # (an earlier test's Run.start_backend may have left a registry on)
    monkeypatch.setattr(fabobs, "_OBS", None)
    assert spans.setup_seconds(("program.trace_lower",)) is None
    with fabobs.obs_installed():
        now = time.perf_counter()
        fabobs.obs_record_span("program.trace_lower", now - 3.0, now - 1.0)
        with fabobs.span("bench.window"):
            pass
        assert spans.setup_seconds(("program.trace_lower",)) == (
            pytest.approx(2.0, abs=1e-3)
        )
        assert spans.setup_seconds(("program.compile_or_load",)) is None


def new_metrics():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"),
              encoding="utf-8") as fh:
        bench = json.load(fh)
    return [
        m for m in bench["per_layer"]
        if os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        and "span_readers" in open(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"),
            encoding="utf-8").read()
    ]


@pytest.mark.parametrize("metric", new_metrics(), ids=lambda m: m["name"])
def test_each_span_metric_file_reads_and_keeps_silent_on_an_empty_context(
        metric, monkeypatch):
    from fabric_tpu.common import fabobs

    # the set-up readers read the live ring: an earlier test's run may have
    # left a registry on
    monkeypatch.setattr(fabobs, "_OBS", None)
    assert metric["unit"] == ("s" if metric["moves"] == "setup_s" else "ms")
    assert metric["better"] == "lower"
    assert metric["workloads"] and metric["layer"] and metric["moves"]
    path = os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.MOVES == metric["moves"]
    # the parent's context: spans of the old names only, a trace without
    # the program's annotations
    silent = {
        "spans": [span("pipeline.prepare", 19.0, block=1),
                  span("pipeline.commit", 154.0, block=1),
                  span("serve.decode", 2.9, req_id=1),
                  span("serve.verify", 165.0, req_id=1),
                  span("batcher.launch", 2.7), span("batcher.settle", 159.0)],
        "trace": {"/device:TPU:0": {"XLA Modules": [], "XLA Ops": []}},
        "window_ns": (0.0, 1.0),
    }
    assert module.read(silent) is None
    assert module.read({}) is None
