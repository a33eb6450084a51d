#!/usr/bin/env python3
"""What sets of runs say (the file ``sets.py`` writes):

    python3 benchmarks/tools/report.py chiprun_out/sets/<tag>.jsonl [...]

Per run the end-to-end metrics beside what ``window_series.summarize`` reads
from the window; per set and metric the median, the contract's spread (IQR /
median) and the driver's (the range leaving out the run farthest from the
median); the bound the rule gives (5 x the wider spread, never under 1 %) and
the bound check (b) asks for (twice the widest trimmed range); the split of
the run-to-run spread (stalls, level, rest); and, from the series kept, what
shorter windows of the same runs would have read.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import bounds  # noqa: E402
from benchmarks import harness as hs  # noqa: E402
from benchmarks import window_series as ws  # noqa: E402

TAIL = {"block": 90, "request": 95}  # the cells' tails: p90 of blocks, p95 of requests


def load(path: str) -> List[Dict]:
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


def metric_values(rows: Sequence[Dict]) -> Dict[str, Dict[int, List[float]]]:
    """{metric: {set: [values in the order run]}} of the runs with a result."""
    out: Dict[str, Dict[int, List[float]]] = {}
    for row in rows:
        for name, m in row.get("result", {}).get("metrics", {}).items():
            out.setdefault(name, {}).setdefault(row["set"], []).append(m["value"])
    return out


def sub_window(series: Dict, amount: float, seconds: float) -> Optional[Dict]:
    """What a window of `seconds` would have read from a longer run's series:
    the rate as ``harness.rate_in_window`` closes it, and the tail over the
    units offered before it closed."""
    done, offered = series["done_at"], series["offered_at"]
    if not done or max(done) < seconds:
        return None
    rate = hs.rate_in_window(done, [amount] * len(done), 0.0, seconds)
    lat = [(d - o) * 1e3 for d, o in zip(done, offered) if o < seconds]
    return {"rate": rate, "tail_ms": hs.percentile(lat, TAIL[series["unit"]])}


def _fmt(v, digits=2) -> str:
    return "-" if v is None else f"{v:.{digits}f}"


def print_report(path: str, lengths: Sequence[float] = (30, 45, 51, 60)) -> None:
    rows = load(path)
    if not rows:
        print(f"{path}: no runs")
        return
    tag = rows[0].get("tag")
    print(f"\n==== {tag}: {rows[0]['workload']} at {rows[0]['seconds']} s, "
          f"env {rows[0].get('env')} ====")
    bad = [r for r in rows if not r.get("result", {}).get("correct")]
    print(f"runs {len(rows)}, not correct or no result: "
          f"{[(r['set'], r['seed'], r['rc']) for r in bad]}")
    nonzero = sorted({
        name for r in rows
        for name, c in r.get("result", {}).get("checks", {}).items()
        if c["value"] > c["limit"]
    })
    print(f"checks over their limit: {nonzero}")
    summaries = []
    print("set seed | metrics | setup_s(marks) | periods: median, stalls, ms above, "
          "longest, drift% | harness ms/unit | span medians")
    for r in rows:
        res = r.get("result", {})
        m = {k: round(v["value"], 2) for k, v in res.get("metrics", {}).items()}
        s = r["phases"].get("series", {})
        if s:
            summaries.append(s)
        marks = r["phases"].get("setup", {}).get("seconds_since_start", {})
        spans = {k: round(v, 2) for k, v in (s.get("span_ms_median") or {}).items()}
        print(f"{r['set']} {r['seed']} | {m} | {marks} | "
              f"{_fmt(s.get('median_period_ms'))} {s.get('stalls')} "
              f"{_fmt(s.get('stall_ms_above'), 1)} {_fmt(s.get('longest_period_ms'), 1)} "
              f"{_fmt(s.get('drift_pct'))} | {_fmt(s.get('harness_ms_mean'), 3)} "
              f"| {spans}")
    print("\nmetric: per set median, IQR/median %, trimmed range % -> rule bound, "
          "check (b) bound, second median vs first %")
    values = metric_values(rows)
    for name, by_set in values.items():
        sets = [v for _, v in sorted(by_set.items()) if len(v) >= 4]
        if not sets:
            continue
        parts = [
            f"{statistics.median(s):.2f} {100 * bounds.iqr_share(s):.3f} "
            f"{100 * bounds.trimmed_range_share(s):.3f}" for s in sets
        ]
        apart = (statistics.median(sets[-1]) / statistics.median(sets[0]) - 1) * 100
        print(f"  {name}: {' | '.join(parts)} -> rule {100 * bounds.rule_bound(sets):.2f} %, "
              f"(b) {100 * bounds.check_b_bound(sets):.2f} %, medians apart {apart:+.2f} %")
    split = ws.split_between_runs(summaries)
    if split:
        print("\nsplit of the run-to-run spread of the mean period:")
        print("  " + json.dumps({k: round(v, 4) if isinstance(v, float) else v
                                 for k, v in split.items()}))
    # shorter windows of the same runs
    out_dir = os.path.join(os.path.dirname(path), tag or "")
    longest = rows[0]["seconds"]
    todo = [t for t in lengths if t <= longest]
    if os.path.isdir(out_dir) and len(todo) > 1:
        print("\nshorter windows of the same runs (rate IQR/median %, tail IQR/median % per set):")
        for t in todo:
            per_set: Dict[int, Dict[str, List[float]]] = {}
            for r in rows:
                f = os.path.join(out_dir, f"set{r['set']}_{r['seed']}.json")
                if not os.path.exists(f):
                    continue
                with open(f, encoding="utf-8") as fh:
                    series = json.load(fh)
                setup = r["phases"].get("setup", {})
                amount = setup.get("block_txs") or setup.get("lanes_per_request") or 1
                got = sub_window(series, amount, t)
                if got:
                    d = per_set.setdefault(r["set"], {"rate": [], "tail_ms": []})
                    d["rate"].append(got["rate"])
                    d["tail_ms"].append(got["tail_ms"])
            parts = []
            for set_no, d in sorted(per_set.items()):
                if len(d["rate"]) >= 4:
                    parts.append(
                        f"set {set_no}: rate {statistics.median(d['rate']):.1f} "
                        f"{100 * bounds.iqr_share(d['rate']):.3f} "
                        f"(trim {100 * bounds.trimmed_range_share(d['rate']):.3f}), tail "
                        f"{statistics.median(d['tail_ms']):.1f} "
                        f"{100 * bounds.iqr_share(d['tail_ms']):.3f} "
                        f"(trim {100 * bounds.trimmed_range_share(d['tail_ms']):.3f})"
                    )
            print(f"  {t:g} s: " + " | ".join(parts))


if __name__ == "__main__":
    for p in sys.argv[1:]:
        print_report(p)
