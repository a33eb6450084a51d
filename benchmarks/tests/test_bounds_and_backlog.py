"""The bounds of BENCHMARK.json against the runs they were drawn from
(benchmarks/bound_runs/), by the arithmetic of benchmarks/bounds.py; and the
backlogs the traffic files size against the rate each path sustained."""

import glob
import json
import math
import os
import sys

import pytest

from benchmarks import bounds
from benchmarks import harness as hs

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

with open(os.path.join(REPO_ROOT, "BENCHMARK.json"), encoding="utf-8") as _fh:
    BENCHMARK = json.load(_fh)


def recorded():
    """{metric: its entry} over every file of bound_runs/, the newest file
    (by name) winning: a later `benchmark` PR that draws a bound again adds a
    file and edits none."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "bound_runs", "*.json"))):
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
        for name, entry in data["metrics"].items():
            out[name] = dict(entry, run_seconds=data["run_seconds"], file=path)
    return out


RECORDED = recorded()
# the metrics whose bound has its runs on record: an end-to-end metric that a
# later PR adds comes with a file of its own under bound_runs/, or is passed over
BOUNDED = [m for m in BENCHMARK["end_to_end"] if m["name"] in RECORDED]


@pytest.mark.parametrize("metric", BOUNDED, ids=lambda m: m["name"])
def test_a_bound_is_what_its_runs_give(metric):
    entry = RECORDED[metric["name"]]
    bound, sets = metric["bound"], entry["sets"]
    assert entry["bound"] == bound
    assert entry["run_seconds"] == BENCHMARK["run_seconds"]
    assert entry["cell"] in metric["workloads"]
    # two sets of six or more, every set on the same six seeds
    assert len(sets) >= 2 and all(len(s) == 6 for s in sets)
    assert bounds.FLOOR <= bound <= 0.25
    # the contract's rule: five times the widest spread of the sets, written up
    # to the next half per cent.  Never under it: no run is left out of a set
    # for reading far off, and no bound is fitted to the draw test below
    rule = bounds.rule_bound(sets)
    assert rule - 1e-9 <= bound
    # check (b): each set's range, its farthest run left out, within half of it
    assert bound >= bounds.check_b_bound(sets) - 5e-4
    check = entry.get("driver_check")
    if check is None:
        assert bound <= math.ceil(rule * 200) / 200 + 1e-9
    else:
        # the driver's own runs of this benchmark spread wider than the
        # builder's and refused the rule's value: the bound then lies where
        # the driver's check said it may, away from that range's lower end
        low, high = check["may_lie"]
        assert low < bound <= high <= 0.25
        assert bound >= low + (high - low) / 3 - 1e-9


def pool_of(entry):
    """The runs made on the final harness: the sets.  (`other_runs`, where an
    entry keeps some, were made on other harnesses or in another mode of the
    program; what the draw reads over them is on record beside them.)"""
    return [v for s in entry["sets"] for v in s]


# the draws are fixed by a seed so that the test reads the same every time; a
# verdict has to hold on each of several
DRAW_SEEDS = (1, 2, 3, 33, 2026)


@pytest.mark.parametrize("metric", BOUNDED, ids=lambda m: m["name"])
def test_the_drivers_two_sets_drawn_20000_times(metric):
    entry = RECORDED[metric["name"]]
    for seed in DRAW_SEEDS:
        failed = bounds.draw_failures(
            pool_of(entry), metric["bound"], draws=20000, seed=seed
        )
        if "driver_check" in entry:
            # a bound set from the driver's wider spread reads too loose over
            # the builder's quieter runs (on record in the entry); it is still
            # never too tight for them, and no two medians differ by it
            assert failed["too_tight"] + failed["medians_apart"] < 0.01, (seed, failed)
        else:
            assert failed["any"] < 0.01, (seed, failed)


def test_the_draw_test_fails_a_bound_that_is_too_tight_or_too_loose():
    pool = [100 + 0.3 * i for i in range(14)]  # spread ~2 % of the median
    assert bounds.draw_failures(pool, 0.01, draws=500)["too_tight"] > 0.5
    assert bounds.draw_failures(pool, 0.25, draws=500)["too_loose"] > 0.5
    assert bounds.draw_failures(pool, 0.10, draws=500)["any"] == 0.0
    shifted = [100.0] * 6 + [103.0] * 6 + [100.0, 103.0]
    assert bounds.draw_failures(shifted, 0.02, draws=500)["medians_apart"] > 0.0


def test_rule_and_check_b():
    steady = [1000.0, 1000.2, 1000.4, 1000.6, 1000.8, 1001.0]
    assert bounds.rule_bound([steady, steady]) == bounds.FLOOR
    one_far = steady[:5] + [1100.0]
    # IQR by statistics.quantiles(n=4): q1 1000.15, q3 1025.6, median 1000.5
    assert bounds.rule_bound([steady, one_far]) == pytest.approx(5 * 25.45 / 1000.5)
    # (b) leaves the far run out: range 0.8 of 1000.5, doubled
    assert bounds.check_b_bound([steady, one_far]) == pytest.approx(2 * 0.8 / 1000.5)
    two_far = steady[:4] + [1100.0, 1101.0]
    assert bounds.check_b_bound([two_far]) > 0.15  # two far runs do harm


def sized_backlogs():
    """(file name, traffic) of every traffic file that sizes a backlog against
    a rate it names: ``sustained_per_second``, and under ``backlog`` the keys
    of its own that hold the per-second length and the warm-up's, and what is
    in flight at the window's close.  Nothing here knows a driver or a file by
    name: a traffic file that a later PR adds is held to the same test by what
    it says of itself, and one with no backlog (an open loop paced from a
    rate) is passed over."""
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "traffic", "*.json"))):
        with open(path, encoding="utf-8") as fh:
            traffic = json.load(fh)
        if "sustained_per_second" in traffic:
            out.append((os.path.basename(path), traffic))
    return out


@pytest.mark.parametrize("name, traffic", sized_backlogs(),
                         ids=[name for name, _ in sized_backlogs()])
def test_the_backlog_outlasts_the_window_at_twice_the_sustained_rate(name, traffic):
    backlog = traffic["backlog"]
    seconds = BENCHMARK["run_seconds"]
    warmup = int(traffic[backlog["warmup_key"]])
    built = hs.backlog_length(
        warmup, traffic[backlog["per_second_key"]], seconds
    )
    needed = (2.0 * float(traffic["sustained_per_second"]) * seconds
              + int(backlog["in_flight"]))
    assert built - warmup >= needed
    # and not far beyond it: every block built is set-up every run pays
    assert built - warmup <= 1.1 * needed + 1


def test_a_traffic_file_without_a_sized_backlog_is_passed_over(tmp_path, monkeypatch):
    """A later cell's traffic (another driver, an open loop) brings neither
    key, and this file needs no edit for it."""
    os.makedirs(tmp_path / "traffic")
    with open(tmp_path / "traffic" / "paced.json", "w", encoding="utf-8") as fh:
        json.dump({"driver": "some_later_driver", "rate_per_second": 9.3}, fh)
    monkeypatch.setattr(sys.modules[__name__], "BENCH_DIR", str(tmp_path))
    assert sized_backlogs() == []


def test_backlog_length_counts_the_warm_up_and_rounds_up():
    assert hs.backlog_length(2, 23, 30) == 692
    assert hs.backlog_length(3, 36.8, 30) == 3 + 1104
    assert hs.backlog_length(0, 0.01, 30) == 1
