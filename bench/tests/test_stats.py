import math

import pytest

import stats


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 99, 5.0),
    (list(range(1, 101)), 99, 99.0),
    (list(range(1, 101)), 50, 50.0),
    (list(range(100, 0, -1)), 100, 100.0),
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 75, 3.0),
    (list(range(1, 201)), 99, 198.0),
])
def test_nearest_rank_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_refuses_no_samples_and_bad_rank():
    with pytest.raises(ValueError):
        stats.percentile([], 99)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)


def test_rate_is_all_the_work_over_the_window():
    assert stats.rate(480, 32.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_spread_is_interquartile_range_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q3 = 10.75, 14.25          # statistics.quantiles, exclusive method
    assert math.isclose(stats.spread(xs), (q3 - q1) / 12.5)
