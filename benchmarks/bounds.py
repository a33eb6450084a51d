"""How a bound of an end-to-end metric is drawn and checked (README, "Drawing
a bound"): the contract's spread and rule, the driver's reckoning as ISSUE 33
states it (check (b)), and PR 26's draw test.  benchmarks/bound_runs/ keeps the
runs each accepted bound was drawn from; benchmarks/tests holds them to this
arithmetic."""

from __future__ import annotations

import random
import statistics
from typing import Dict, List, Sequence

FLOOR = 0.01         # no bound under 1 %
RULE_FACTOR = 5.0    # the bound: five times the wider spread of the two sets
LOOSE_FACTOR = 8.0   # the driver refuses a bound over eight times the widest spread


def iqr_share(values: Sequence[float]) -> float:
    """The contract's spread: the distance between the first and the third
    quartile, as ``statistics.quantiles(values, n=4)`` gives them, as a share
    of the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The set less the run farthest from its median, as the driver leaves it
    out before it reckons a spread for tightness."""
    mid = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - mid))[:-1]


def trimmed_range_share(values: Sequence[float]) -> float:
    """Check (b)'s spread, as ISSUE 33 states it: the range of a set, leaving
    out the run farthest from the set's median, as a share of that median."""
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def rule_bound(sets: Sequence[Sequence[float]]) -> float:
    """The contract's rule: five times the wider IQR / median, never under 1 %."""
    return max(FLOOR, RULE_FACTOR * max(iqr_share(s) for s in sets))


def check_b_bound(sets: Sequence[Sequence[float]]) -> float:
    """Check (b): every set's range, its farthest run left out, is at most
    half the bound."""
    return 2.0 * max(trimmed_range_share(s) for s in sets)


SET_SIZE = 6        # runs in one of the driver's sets


def draw_failures(pool: Sequence[float], bound: float,
                  draws: int = 20000, seed: int = 33) -> Dict[str, float]:
    """The driver's two sets of six drawn `draws` times from the runs
    made (without replacement), and the share of draws in which one of its
    tests on a bound fails: too tight (the mean of the two sets' IQR / median,
    each set's farthest run left out, is over half the bound), too loose (the
    bound is over eight times the wider spread of all the runs drawn, and
    over 1 %), or the second median differs from the first by more than the
    bound.  `seed` fixes the draws, so that a test reads the same every time;
    a verdict that turns on it is no verdict (the tests take several)."""
    rng = random.Random(seed)
    pool = list(pool)
    counts = {"too_tight": 0, "too_loose": 0, "medians_apart": 0, "any": 0}
    for _ in range(draws):
        drawn = rng.sample(pool, 2 * SET_SIZE)
        a, b = drawn[:SET_SIZE], drawn[SET_SIZE:]
        tight = iqr_share(without_farthest(a)) + iqr_share(without_farthest(b)) > bound
        loose = bound > FLOOR and bound > LOOSE_FACTOR * max(
            iqr_share(a), iqr_share(b), iqr_share(drawn)
        )
        apart = abs(statistics.median(b) / statistics.median(a) - 1.0) > bound
        counts["too_tight"] += tight
        counts["too_loose"] += loose
        counts["medians_apart"] += apart
        counts["any"] += tight or loose or apart
    return {k: v / draws for k, v in counts.items()}
