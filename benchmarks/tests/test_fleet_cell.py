"""The cell `sidecar-fleet`: four peer processes through one sidecar.
Each run is benchmarks/tests/fleet_rehearsal.py in a process of its own (the
driver forks its peers), on the host engine at 64-tx blocks; no kernel is
compiled.  A sound run is `correct`; each planted fault and the control
fail it; a tree whose sidecar cannot warm the program it serves exits
before JAX is touched.  Then the cell's readers on hand-made spans and
traces."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import fleet_readers as fr
from benchmarks import trace_reduce as tr
from benchmarks.drivers import sidecar_fleet

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def rehearse(*args):
    """(exit code, result line or None, earlier lines, standard error)."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.fleet_rehearsal", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
    )
    rows = [json.loads(line) for line in done.stdout.splitlines() if line.strip()]
    last = rows[-1] if rows and "correct" in rows[-1] else None
    return done.returncode, last, rows, done.stderr


def failing(line):
    return {k: v["value"] for k, v in line["checks"].items() if v["value"] > v["limit"]}


def test_a_sound_fleet_run_is_correct_and_coalesces():
    rc, line, rows, _ = rehearse()
    assert rc == 0 and line["correct"] is True and line["metrics"] == {}
    assert line["checks"]["mask_mismatch_lanes"] == {"value": 0, "limit": 0}
    assert line["checks"]["peers_running"]["value"] == 0
    assert line["checks"]["launch_buckets"]["value"] == 0
    window = next(r for r in rows if r.get("phase") == "window")
    assert window["peers_answered"] == 4
    assert all(n > 0 for n in window["requests_by_peer"].values())
    # fewer launches than requests: the four peers' requests coalesced
    setup = next(r for r in rows if r.get("phase") == "setup")
    assert window["launches"] < window["requests"] + 4 * 3
    assert setup["warm"]["ladder"] == "verify"
    assert sorted(setup["warm"]["per_bucket"]) == ["2048", "4096", "8192"]


@pytest.mark.parametrize("fault, check", [
    ("swapped_masks", "mask_mismatch_lanes"),
    ("peer_dies", "peers_running"),
    ("unwarmed_width", "launch_buckets"),
    ("exhausted_backlog", "requests_exhausted"),
])
def test_a_planted_fault_fails_the_fleet_cell(fault, check):
    rc, line, _, _ = rehearse("--fault", fault)
    assert rc != 0 and line["correct"] is False
    assert failing(line)[check] > 0


def test_the_control_fails_the_fleet_cell():
    rc, line, rows, _ = rehearse("--control", "accept_high_s")
    assert rc == 0 and line["correct"] is True  # the program's own line
    (control,) = [r for r in rows if r.get("phase") == "control"]
    assert control["correct"] is False
    assert control["checks"]["mask_mismatch_lanes"]["value"] > 0


def test_a_tree_that_cannot_warm_the_served_program_exits_before_jax():
    rc, line, _, err = rehearse("--fault", "parent_tree")
    assert rc == 2 and line is None
    assert "does not warm the program it serves" in err
    assert "JAX touched" not in err


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


def launch(ts, lanes, inflight, requests=1):
    return {"name": "batcher.launch", "ts": ts, "dur": 1.0,
            "args": {"lanes": lanes, "inflight": inflight,
                     "requests": requests}}


def trace_of(runs, plane="/device:TPU:0", extent=(0.0, 300e6)):
    """A device plane with the program's runs, and an op at each end of
    what the tracer recorded."""
    return {plane: {
        tr.MODULES_LINE: [
            (fr.PROGRAM + "(1)", float(s), float(e - s)) for s, e in runs
        ],
        tr.OPS_LINE: [("%copy", extent[0], 1e3), ("%copy", extent[1] - 1e3, 1e3)],
    }}


def test_each_run_gets_its_own_launchs_lanes_on_the_rings_clock():
    # the ring's clock (us) is 5 ms behind the trace's (ns)
    ms = 1_000_000
    runs = [(10 * ms, 20 * ms), (21 * ms, 61 * ms), (70 * ms, 90 * ms),
            (90 * ms, 170 * ms)]
    ctx = {
        "trace": trace_of(runs), "slice_ns": (15.0 * ms, 200.0 * ms),
        "slice_opened_us": 10_000.0,
        "fleet_launches": [
            launch(4_000.0, 1498, 0),     # -> run 0, before the slice
            launch(15_000.0, 2996, 0),    # -> run 1
            launch(60_000.0, 1498, 0),    # -> run 2
            launch(62_000.0, 5992, 1),    # queued behind it -> run 3
        ],
    }
    assert fr.runs_with_lanes(ctx) == [
        (21 * ms, 61 * ms, 2996.0), (70 * ms, 90 * ms, 1498.0),
        (90 * ms, 170 * ms, 5992.0),
    ]
    share = fr.roofline_pct(dict(ctx, device_kind="TPU v5 lite"))
    least, _ = tr.least_seconds(2996 + 1498 + 5992, "TPU v5 lite")
    assert share == pytest.approx(100 * least / 0.140)


def test_a_launch_the_tracer_cut_is_left_out():
    ms = 1_000_000
    # the last run ends where the trace does: the profiler stopped in it
    runs = [(20 * ms, 57 * ms), (60 * ms, 97 * ms), (100 * ms, 150 * ms)]
    ctx = {"trace": trace_of(runs, extent=(0.0, 150.0 * ms)),
           "slice_ns": (10.0 * ms, 150.0 * ms), "slice_opened_us": 10_000.0,
           "fleet_launches": [launch(15_000.0, 1498, 0), launch(58_000.0, 1498, 0),
                              launch(98_000.0, 5992, 0)],
           "device_kind": "TPU v5 lite"}
    assert fr.runs_with_lanes(ctx) == [
        (20 * ms, 57 * ms, 1498.0), (60 * ms, 97 * ms, 1498.0),
    ]
    assert fr.kernel_ms_per_launch(ctx) == pytest.approx(37.0)
    least, _ = tr.least_seconds(2 * 1498, "TPU v5 lite")
    assert fr.roofline_pct(ctx) == pytest.approx(100 * least / 0.074)


def test_a_run_no_launch_matches_is_left_out_and_no_match_reads_nothing():
    runs = [(10_000, 20_000), (30_000, 40_000)]
    ctx = {"trace": trace_of(runs), "slice_ns": (0.0, 50_000.0),
           "slice_opened_us": 0.0,
           # queued behind a launch the ring does not hold: no anchor
           "fleet_launches": [launch(25.0, 1498, 1)]}
    assert fr.runs_with_lanes(ctx) == []
    assert fr.roofline_pct(dict(ctx, device_kind="TPU v5 lite")) is None
    # a program without the attribute (the parent) leaves it out too
    old = {"name": "batcher.launch", "ts": 5.0, "dur": 1.0,
           "args": {"lanes": 1498, "requests": 1}}
    assert fr.runs_with_lanes(dict(ctx, fleet_launches=[old])) == []


def test_the_span_readers():
    spans = [
        launch(0.0, 1498, 0, requests=1), launch(1.0, 5992, 2, requests=4),
        {"name": "tpu.dispatch", "ts": 0.0, "dur": 1.0,
         "args": {"lanes": 1498, "bucket": 2048}},
        {"name": "tpu.dispatch", "ts": 1.0, "dur": 1.0,
         "args": {"lanes": 5992, "bucket": 8192}},
    ]
    ctx = {"spans": spans}
    assert fr.mean_attr(ctx, "batcher.launch", "requests") == 2.5
    assert fr.mean_attr(ctx, "batcher.launch", "inflight") == 1.0
    assert fr.bucket_fill_pct(ctx) == pytest.approx(100 * 7490 / 10240)
    assert fr.mean_attr({"spans": []}, "batcher.launch", "requests") is None
    assert fr.bucket_fill_pct({"spans": spans[:2]}) is None


def test_the_launch_bucket_check():
    snap = {"fabric_batcher_launches_by_bucket_total": {
        "series": {"bucket=2048": 3, "bucket=8192": 2}}}
    sidecar_fleet.check_launch_buckets(snap, (2048, 4096, 8192), 5)
    with pytest.raises(sidecar_fleet.hs.SeamGaveWay):
        sidecar_fleet.check_launch_buckets(snap, (2048, 4096), 5)
    with pytest.raises(sidecar_fleet.hs.SeamGaveWay):
        sidecar_fleet.check_launch_buckets(snap, (2048, 4096, 8192), 6)
