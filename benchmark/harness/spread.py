"""Run-to-run arithmetic: how widely the runs of one metric spread, by the
contract's rule and by the driver's, the bound that follows from both, and
how much of a percentile's spread is the estimator's own.  Pure functions of
lists; ``prove.py`` prints them, ``PERF.md`` 2 quotes them."""

from __future__ import annotations

import math
import random
import statistics

from . import stats

# The driver refuses a bound as too tight where its own runs spread by more
# than half of it and as too loose where it is over eight times the widest
# spread it reads: a bound has to lie in [2 x spread, 8 x spread] of whatever
# sets the driver happens to draw.  Two sets of one cell can differ by a
# factor of two or three (a host-bound cell on a shared host), so the bound
# goes to the middle of the window they leave between them.
TIGHT_FACTOR, LOOSE_FACTOR = 2.0, 8.0
DRIVER_FACTOR = 2.5     # keeps the mean driver-rule spread under 40 % of it
LEAST_BOUND, MOST_BOUND = 0.01, 0.25    # the contract's limits
RESAMPLES = 1000


def iqr_spread(values: list) -> float:
    """Interquartile distance over the median (the contract's rule)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def driver_spread(values: list) -> float:
    """The driver's rule (ledger, ``reason`` of PR 29): the distance between
    the extreme runs over the median, leaving out the run farthest from the
    median where that narrows it."""
    m = statistics.median(values)
    widest = max(values) - min(values)
    if len(values) > 2:
        rest = sorted(values, key=lambda v: abs(v - m))[:-1]
        widest = min(widest, max(rest) - min(rest))
    return widest / m


def bound_window(sets: list) -> tuple:
    """(least, most) a bound may be for both of the driver's tests to pass on
    sets like these: twice the wider set's interquartile spread, eight times
    the narrower's."""
    iqr = [iqr_spread(s) for s in sets]
    return TIGHT_FACTOR * max(iqr), LOOSE_FACTOR * min(iqr)


def bound_rule(sets: list) -> float:
    """The bound of one metric from the sets of runs of its noisiest cell:
    the geometric middle of ``bound_window`` (4 x the geometric mean of the
    widest and the narrowest set's spread; with one set, 4 x its spread),
    never under ``DRIVER_FACTOR`` x the mean driver-rule spread of the sets,
    rounded up to two decimals, inside the contract's limits."""
    least, most = bound_window(sets)
    need = max(math.sqrt(least * most),
               DRIVER_FACTOR * statistics.fmean(driver_spread(s)
                                                for s in sets))
    return min(MOST_BOUND, max(LEAST_BOUND, math.ceil(need * 100 - 1e-9) / 100))


def bootstrap_se(samples: list, q: float, resamples: int = RESAMPLES,
                 seed: int = 0) -> float:
    """Standard error of ONE run's nearest-rank q-th percentile: the run's
    own samples drawn again with replacement ``resamples`` times, the
    standard deviation of the percentile over the draws."""
    rng = random.Random(seed)
    n = len(samples)
    return statistics.pstdev(
        stats.percentile(rng.choices(samples, k=n), q)
        for _ in range(resamples))


def tail_estimate(runs: list, q: float) -> dict:
    """``runs``: one list of latencies per run of a set.  The q-th percentile
    of each run, the standard deviation between the runs, and each run's
    bootstrap error — what the estimator alone would spread by."""
    each = [stats.percentile(r, q) for r in runs]
    boot = [bootstrap_se(r, q, seed=i) for i, r in enumerate(runs)]
    m = statistics.median(each)
    return {"q": q, "per_run": each, "median": m,
            "run_to_run_sd": statistics.stdev(each) if len(each) > 1 else None,
            "bootstrap_se_per_run": boot,
            "bootstrap_se": math.sqrt(sum(b * b for b in boot) / len(boot)),
            "iqr_spread": iqr_spread(each) if len(each) > 1 else None,
            "driver_spread": driver_spread(each) if len(each) > 1 else None}


def pooled(runs: list, qs=(50, 90, 99)) -> dict:
    """Percentiles of every run's latencies thrown together."""
    everything = [x for r in runs for x in r]
    return {"n": len(everything)} | {
        f"p{q}": stats.percentile(everything, q) for q in qs}
