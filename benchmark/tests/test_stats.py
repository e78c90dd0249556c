import statistics

import pytest

from benchmark.stats import percentile, rate, spread


def test_rate_is_all_work_over_all_time():
    assert rate(300, 10.0) == 30.0
    assert rate(0, 10.0) is None
    assert rate(5, 0.0) is None


def test_percentiles_interpolate_between_ranks():
    xs = list(range(1, 101))               # 1..100
    assert percentile(xs, 50) == 50.5
    assert percentile(xs, 95) == pytest.approx(95.05)
    assert percentile([7.0], 95) == 7.0
    assert percentile([], 50) is None
    # order of the input does not matter
    assert percentile(list(reversed(xs)), 95) == percentile(xs, 95)


def test_spread_is_the_drivers_quartile_rule():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / med)
    assert spread([1.0]) is None
