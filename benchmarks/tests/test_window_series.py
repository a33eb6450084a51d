"""The per-run reader of PR 33 (window_series.py): the median period, the
periods over 1.5 x it and the time they hold above it, the drift, the spans
per unit, and the three-way split of the run-to-run spread."""

import pytest

from benchmarks import bounds
from benchmarks import window_series as ws


def steady(period_s, count, start=0.0):
    return [start + period_s * (i + 1) for i in range(count)]


def with_stall(done, at, seconds):
    return [t if i < at else t + seconds for i, t in enumerate(done)]


def series(done, **more):
    return dict({"unit": "block", "done_at": done,
                 "offered_at": [t - 0.35 for t in done], "harness_ms": [],
                 "spans": {}}, **more)


def test_periods_are_completion_to_completion_in_order_of_completion():
    assert ws.periods_ms([0.3, 0.1, 0.2]) == pytest.approx([100.0, 100.0])
    assert ws.periods_ms([0.5]) == []


@pytest.mark.parametrize("stall_s, want_count, want_above", [
    (0.0, 0, 0.0),       # a steady run has none
    (0.04, 0, 0.0),      # 128 ms against 88: under the 1.5 x line
    (0.1, 1, 100.0),     # one of the ~100 ms stalls
    (2.0, 1, 2000.0),    # the rare request of seconds
])
def test_stalls_over_one_and_a_half_medians(stall_s, want_count, want_above):
    done = with_stall(steady(0.088, 340), 100, stall_s)
    got = ws.stalls(ws.periods_ms(done))
    assert got["median_period_ms"] == pytest.approx(88.0)
    assert got["stalls"] == want_count
    assert got["stall_ms_above"] == pytest.approx(want_above, abs=1e-6)
    assert got["longest_period_ms"] == pytest.approx(88.0 + stall_s * 1e3)


def test_two_stalls_add_up_and_the_median_does_not_feel_them():
    done = with_stall(with_stall(steady(0.088, 340), 50, 0.1), 200, 0.12)
    got = ws.stalls(ws.periods_ms(done))
    assert got["stalls"] == 2
    assert got["stall_ms_above"] == pytest.approx(220.0)
    assert got["median_period_ms"] == pytest.approx(88.0)


def test_drift_is_the_last_third_against_the_first():
    rising = []
    t = 0.0
    for i in range(300):
        t += 0.080 * (1 + 0.1 * i / 299)  # the period grows by 10 % over the run
        rising.append(t)
    drift = ws.drift_pct(ws.periods_ms(rising))
    assert 6.0 < drift < 7.5  # the thirds' medians sit at 1/6 and 5/6 of it
    assert ws.drift_pct(ws.periods_ms(steady(0.088, 300))) == pytest.approx(0.0, abs=1e-6)
    assert ws.drift_pct([88.0] * 5) is None  # too few to say


def test_a_runs_mean_period_is_level_plus_stalls_plus_rest_exactly():
    done = with_stall(steady(0.088, 340), 100, 0.1)
    done = [t * (1 + 0.0001 * i) for i, t in enumerate(done)]  # some drift
    s = ws.summarize(series(done))
    n = s["units"] - 1
    assert s["mean_period_ms"] == pytest.approx(
        s["median_period_ms"] + s["stall_ms_above"] / n + s["body_ms_above"] / n
    )


def test_spans_per_unit_sums_by_block_and_leaves_other_spans_out():
    ring = [
        {"name": "ledger.block_append", "ts": 10, "dur": 9000, "args": {"block": 4}},
        {"name": "ledger.block_append", "ts": 90, "dur": 11000, "args": {"block": 5}},
        {"name": "tpu.resolve", "ts": 20, "dur": 1000, "args": {"block": 4}},
        {"name": "tpu.resolve", "ts": 25, "dur": 500, "args": {"block": 4}},
        {"name": "bench.window", "ts": 0, "dur": 99999, "args": {}},
        {"name": "tpu.resolve", "ts": 30, "dur": 700, "args": {}},  # no block: left out
    ]
    got = ws.spans_per_unit(ring, ("ledger.block_append", "tpu.resolve"), "block")
    assert got == {"ledger.block_append": {4: 9.0, 5: 11.0},
                   "tpu.resolve": {4: 1.5}}
    in_order = ws.spans_in_order(ring, ("tpu.resolve",))
    assert in_order == {"tpu.resolve": [1.0, 0.5, 0.7]}


def test_summarize_reads_span_medians_and_the_harness_own_work():
    s = ws.summarize(series(
        steady(0.088, 10), harness_ms=[2.0, 2.2, 2.4],
        spans={"ledger.block_append": [9.0, None, 11.0, 10.0], "tpu.dispatch": []},
    ))
    assert s["span_ms_median"] == {"ledger.block_append": 10.0}
    assert s["harness_ms_median"] == pytest.approx(2.2)
    assert s["harness_ms_mean"] == pytest.approx(2.2)


def run_summary(period_s, stall_s=0.0):
    done = steady(period_s, 340)
    if stall_s:
        done = with_stall(done, 170, stall_s)
    return ws.summarize(series(done))


@pytest.mark.parametrize("runs, largest", [
    # the level differs from process to process, no stall anywhere
    ([run_summary(p) for p in (0.086, 0.087, 0.088, 0.089, 0.090, 0.091)], "level"),
    # one level, and a stall of another length in each run
    ([run_summary(0.088, s) for s in (0.0, 0.1, 0.0, 0.3, 0.0, 1.5)], "stalls"),
])
def test_the_split_names_the_largest_part(runs, largest):
    split = ws.split_between_runs(runs)
    shares = {k: split[f"variance_share_{k}"] for k in ("stalls", "level", "rest")}
    assert sum(shares.values()) == pytest.approx(1.0)
    assert max(shares, key=shares.get) == largest
    assert shares[largest] > 0.95
    if largest == "stalls":
        assert split["iqr_without_stalls_pct"] == pytest.approx(0.0, abs=1e-6)
        assert split["iqr_total_pct"] > 0.05


def test_the_contracts_spread_and_the_drivers():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 130.0]
    # statistics.quantiles(n=4), exclusive: q1 = 100.75, q3 = 110.5
    assert bounds.iqr_share(values) == pytest.approx((110.5 - 100.75) / 102.5)
    # the run farthest from the median (130) is left out of the range
    assert bounds.trimmed_range_share(values) == pytest.approx(4.0 / 102.5)


def test_a_shorter_window_of_a_longer_run_reads_what_that_window_would():
    from benchmarks import harness as hs
    from benchmarks.tools import report

    done = with_stall(steady(0.088, 700), 200, 0.12)  # ~61 s of blocks, one stall
    s = series(done)
    got = report.sub_window(s, 500, 30.0)
    assert got["rate"] == pytest.approx(
        hs.rate_in_window(done, [500] * len(done), 0.0, 30.0)
    )
    # the tail over the blocks offered before the window closed, each waited for
    assert got["tail_ms"] == pytest.approx(350.0)
    assert report.sub_window(s, 500, 90.0) is None  # the run was not that long


def test_report_reads_a_sets_file(tmp_path, capsys):
    import json

    from benchmarks.tools import report

    rows = []
    for set_no in (0, 1):
        for k, rate in enumerate([5600.0, 5650.0, 5700.0, 5710.0, 5720.0, 5900.0]):
            done = steady(500 / rate, 340)
            rows.append({
                "set": set_no, "seed": 100 + k, "rc": 0, "tag": "t",
                "workload": "peer-catchup", "seconds": 30, "env": {},
                "phases": {"series": dict(ws.summarize(series(done)), phase="series"),
                           "setup": {"seconds_since_start": {}, "block_txs": 500}},
                "result": {"correct": True, "checks": {"x": {"value": 0, "limit": 0}},
                           "metrics": {"commit_tx_per_s": {"value": rate, "unit": "tx/s"}}},
            })
    path = tmp_path / "t.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert len(report.load(str(path))) == 12
    assert report.metric_values(report.load(str(path)))["commit_tx_per_s"][1][-1] == 5900.0
    report.print_report(str(path))
    out = capsys.readouterr().out
    assert "runs 12, not correct or no result: []" in out
    assert "commit_tx_per_s: 5705.00" in out
    assert '"variance_share_level": 1.0' in out
