"""Percentiles with failed requests counted as misses, and the spread."""

import math

import pytest

from benchmark import stats


def test_percentile_interpolates_like_the_textbook():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(range(101), 95) == pytest.approx(95.0)
    assert stats.percentile([7.0], 95) == 7.0
    assert math.isnan(stats.percentile([], 95))


def test_a_failed_request_is_a_miss():
    ok = list(range(100))
    # 3 misses among 103: the 95th percentile still lies among the answers
    assert stats.percentile(ok, 95, misses=3) == pytest.approx(96.9)
    # 6 misses among 106: it lies among the misses
    assert stats.percentile(ok, 95, misses=6) == math.inf
    assert stats.percentile([], 95, misses=1) == math.inf
    # and a miss never improves a tail
    assert stats.percentile(ok, 95, misses=1) > stats.percentile(ok, 95)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6: 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10.0] * 6) == 0.0
