"""The percentile and rate definitions every metric is reduced with."""

import math

import pytest

from bench import stats


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))                 # 1..100
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile(list(range(10)), 90) == 8


def test_missing_values_sort_last():
    xs = [1.0] * 9 + [math.inf]
    assert stats.percentile(xs, 90) == 1.0
    assert math.isinf(stats.percentile(xs + [math.inf], 90))


def test_percentile_of_nothing_fails():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_rate_counts_the_whole_window():
    assert stats.rate(90, 45.0) == 2.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)
