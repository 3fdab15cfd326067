"""Run-to-run arithmetic on hand-made runs: both spreads, the bound rule and
the bootstrap error of a nearest-rank percentile."""

import statistics

import pytest

from harness import spread, stats


def test_iqr_spread_is_the_contracts_rule():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q = statistics.quantiles(vals, n=4)
    assert spread.iqr_spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)


@pytest.mark.parametrize("vals,want", [
    # one far-off run: left out, the rest span 4 of a median of 102
    ([100.0, 101.0, 102.0, 103.0, 104.0, 150.0], 4.0 / 102.5),
    # the farthest run is an extreme on the other side
    ([50.0, 101.0, 102.0, 103.0, 104.0, 105.0], 4.0 / 102.5),
    # the farthest run lies inside the others' range only when all are one
    # value apart: leaving one extreme out still narrows it
    ([1.0, 2.0, 3.0], 1.0 / 2.0),
    # two runs: nothing to leave out
    ([1.0, 3.0], 2.0 / 2.0),
    # all equal
    ([7.0, 7.0, 7.0, 7.0], 0.0),
])
def test_driver_spread_leaves_the_farthest_run_out(vals, want):
    assert spread.driver_spread(vals) == pytest.approx(want)


def test_driver_spread_never_widens_by_leaving_out():
    # symmetric extremes: either may go, the spread is the narrower range
    vals = [90.0, 99.0, 100.0, 101.0, 110.0]
    assert spread.driver_spread(vals) == pytest.approx(
        min(20.0, 110.0 - 99.0, 101.0 - 90.0) / 100.0)


def test_bound_rule_on_two_made_up_sets():
    quiet = [1.000, 1.004, 1.008, 1.012, 1.016, 1.020]
    noisy = [1.000, 1.010, 1.020, 1.030, 1.040, 1.050]
    sq, sn = spread.iqr_spread(quiet), spread.iqr_spread(noisy)
    least, most = spread.bound_window([quiet, noisy])
    assert (least, most) == pytest.approx((2 * sn, 8 * sq))
    got = spread.bound_rule([quiet, noisy])
    want = 4 * (sq * sn) ** 0.5          # the window's geometric middle
    assert want <= got < want + 0.01
    assert round(got * 100) == pytest.approx(got * 100)   # two decimals
    # both of the driver's tests pass whichever of the two sets it draws
    assert least <= got <= most
    # one set: four times its spread
    assert spread.bound_rule([noisy]) == pytest.approx(
        -(-4 * sn * 100 // 1) / 100)
    # the contract's limits hold at both ends
    assert spread.bound_rule([[1.0, 1.0, 1.0]]) == 0.01
    assert spread.bound_rule([[1.0, 2.0, 3.0, 9.0]]) == 0.25


def test_bound_rule_takes_the_driver_term_when_it_is_the_larger():
    # a tight middle with wide wings: the quartiles see nothing, the driver's
    # rule does, and 2.5 x its spread beats the window's middle
    s = [0.98, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.02, 1.02]
    assert spread.iqr_spread(s) == 0.0
    assert spread.driver_spread(s) == pytest.approx(0.04)
    assert spread.bound_rule([s]) == 0.10


def test_bootstrap_on_known_lists():
    # all equal: the percentile cannot move
    assert spread.bootstrap_se([3.0] * 50, 90) == 0.0
    # the same seed gives the same error; it is host arithmetic only
    walls = [0.060 + 0.0001 * (i % 37) + (0.02 if i % 10 == 0 else 0.0)
             for i in range(670)]
    se = spread.bootstrap_se(walls, 90, seed=3)
    assert se == spread.bootstrap_se(walls, 90, seed=3)
    assert 0.0 < se < 0.02
    # a median of the same list is steadier than its 90th percentile, which
    # sits where one query in ten stalls
    assert spread.bootstrap_se(walls, 50, seed=3) < se
    # fewer samples, wider error
    assert spread.bootstrap_se(walls[:67], 90, seed=3) > se


def test_tail_estimate_and_pooled():
    runs = [[1.0] * 9 + [2.0], [1.0] * 9 + [3.0], [1.0] * 8 + [2.0, 2.0]]
    est = spread.tail_estimate(runs, 90)
    assert est["per_run"] == [stats.percentile(r, 90) for r in runs]
    assert len(est["bootstrap_se_per_run"]) == 3
    assert est["run_to_run_sd"] == pytest.approx(
        statistics.stdev(est["per_run"]))
    assert est["bootstrap_se"] > 0
    pool = spread.pooled(runs)
    assert pool["n"] == 30 and pool["p50"] == 1.0 and pool["p99"] == 3.0
