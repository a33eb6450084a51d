"""The per-unit series of one window, and what a run's spread is made of.

A unit is a block (``peer-catchup``) or a request (``sidecar-1peer``).  A
driver hands over, for every unit offered inside the window, when it was
offered and when it was done (seconds since the window opened), what the
harness itself spent on it before offering it, and the program's fabobs spans
per unit.  ``summarize`` reads from that the numbers PR 33's split rests on:
the median period (completion to completion), the periods over
``STALL_FACTOR`` x that median and the time they hold above it, how far the
level moves inside the run, and the median of each span.

``split_between_runs`` then takes the summaries of several runs of one cell
and says how much of the run-to-run spread of the mean period (the inverse of
the rate) is (i) isolated stalls, (ii) a level that differs from process to
process, (iii) the rest: the periods under the stall line that lie off the
median, drift inside a run among them.

Nothing here is an end-to-end metric: the rate stays all work over all time
(``harness.rate_in_window``).
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence

from benchmarks.bounds import iqr_share

STALL_FACTOR = 1.5


def periods_ms(done_at: Sequence[float]) -> List[float]:
    """Completion-to-completion times, in ms, in the order of completion."""
    ordered = sorted(done_at)
    return [(b - a) * 1e3 for a, b in zip(ordered, ordered[1:])]


def stalls(periods: Sequence[float]) -> Dict:
    """The periods over STALL_FACTOR x the median period: how many, and the time
    they hold above the median (what the window lost to them)."""
    if not periods:
        return {"median_period_ms": None, "stalls": 0, "stall_ms_above": 0.0,
                "longest_period_ms": None}
    mid = statistics.median(periods)
    over = [p for p in periods if p > STALL_FACTOR * mid]
    return {
        "median_period_ms": mid,
        "stalls": len(over),
        "stall_ms_above": sum(p - mid for p in over),
        "longest_period_ms": max(periods),
    }


def drift_pct(periods: Sequence[float]) -> Optional[float]:
    """How far the level moves inside the run: the median period of the last
    third over that of the first third, less one, in %.  Stalls are left out
    of neither third: a median does not feel them."""
    third = len(periods) // 3
    if third < 3:
        return None
    first = statistics.median(periods[:third])
    last = statistics.median(periods[-third:])
    return (last / first - 1.0) * 100.0


def spans_per_unit(spans: Sequence[Dict], names: Sequence[str],
                   key: str) -> Dict[str, Dict[int, float]]:
    """{span name: {unit: summed ms}} out of the ring's spans, for the names
    asked; a unit is the span's ``block`` or ``req_id`` argument."""
    wanted = set(names)
    out: Dict[str, Dict[int, float]] = {n: {} for n in names}
    for event in spans:
        if event["name"] not in wanted:
            continue
        unit = (event.get("args") or {}).get(key)
        if unit is None:
            continue
        row = out[event["name"]]
        row[unit] = row.get(unit, 0.0) + event["dur"] / 1e3
    return out


def spans_in_order(spans: Sequence[Dict],
                   names: Sequence[str]) -> Dict[str, List[float]]:
    """{span name: [ms, in the order the spans began]}: for a closed loop of
    one client, where the k-th span of a name belongs to the k-th request."""
    wanted = set(names)
    out: Dict[str, List[float]] = {n: [] for n in names}
    for event in sorted(spans, key=lambda e: e["ts"]):
        if event["name"] in wanted:
            out[event["name"]].append(event["dur"] / 1e3)
    return out


def summarize(series: Dict) -> Dict:
    """One run's numbers.  `series`: ``done_at`` (seconds since the window
    opened, per unit), ``offered_at``, ``harness_ms`` (the harness's own work
    per unit inside the window), ``spans`` ({name: [ms per unit, or None]})."""
    periods = periods_ms(series["done_at"])
    out = stalls(periods)
    mid = out["median_period_ms"]
    out["units"] = len(series["done_at"])
    out["drift_pct"] = drift_pct(periods)
    if periods:
        out["mean_period_ms"] = sum(periods) / len(periods)
        under = [p for p in periods if p <= STALL_FACTOR * mid]
        # what the periods under the stall line add to the mean beyond the
        # median: the body's skew and the drift
        out["body_ms_above"] = sum(p - mid for p in under)
    harness = series.get("harness_ms") or []
    if harness:
        out["harness_ms_median"] = statistics.median(harness)
        out["harness_ms_mean"] = sum(harness) / len(harness)
    per_span = {
        name: [v for v in rows if v is not None]
        for name, rows in (series.get("spans") or {}).items()
    }
    out["span_ms_median"] = {
        name: statistics.median(v) for name, v in per_span.items() if v
    }
    out["span_ms_mean"] = {
        name: sum(v) / len(v) for name, v in per_span.items() if v
    }
    return out


def split_between_runs(summaries: Sequence[Dict]) -> Dict:
    """The run-to-run spread of the mean period, split three ways.

    A run's mean period is  median + stall_ms_above / n + body_ms_above / n
    exactly (n periods), so the three parts are (ii) the level, (i) the
    stalls and (iii) the rest.  For each part: its standard deviation over
    the runs, in ms and as a share of the mean of the mean periods, and its
    share of the variance of their sum (its covariance with the sum over
    that variance, so the three shares add up to 1).  Also what the spread of the mean period
    would be with the stalls taken out (every stalled period set to the
    run's median), the contract's way (IQR / median)."""
    rows = [s for s in summaries if s.get("median_period_ms")]
    if len(rows) < 3:
        return {}
    n = [s["units"] - 1 for s in rows]
    level = [s["median_period_ms"] for s in rows]
    stall = [s["stall_ms_above"] / k for s, k in zip(rows, n)]
    body = [s["body_ms_above"] / k for s, k in zip(rows, n)]
    total = [a + b + c for a, b, c in zip(level, stall, body)]
    centre = sum(total) / len(total)
    var_total = statistics.pvariance(total)

    def cov(a: Sequence[float], b: Sequence[float]) -> float:
        ma, mb = sum(a) / len(a), sum(b) / len(b)
        return sum((x - ma) * (y - mb) for x, y in zip(a, b)) / len(a)

    parts = {"stalls": stall, "level": level, "rest": body}
    out: Dict = {
        "runs": len(rows),
        "mean_period_ms": centre,
        "sd_total_pct": 100.0 * var_total ** 0.5 / centre,
        "iqr_total_pct": 100.0 * iqr_share(total),
        "iqr_without_stalls_pct": 100.0 * iqr_share(
            [a + c for a, c in zip(level, body)]
        ),
        "iqr_level_alone_pct": 100.0 * iqr_share(level),
    }
    for name, values in parts.items():
        out[f"sd_{name}_ms"] = statistics.pstdev(values)
        out[f"sd_{name}_pct"] = 100.0 * statistics.pstdev(values) / centre
        # a part's share of the variance of the sum: its covariance with the sum
        out[f"variance_share_{name}"] = (
            cov(values, total) / var_total if var_total else None
        )
    return out
