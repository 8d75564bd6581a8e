"""Percentiles and their sample counts."""
import math

import stats


def test_percentile_is_nearest_rank_with_its_count_beyond():
    xs = list(range(1, 101))  # 1..100
    p = stats.percentile(xs, 0.95)
    assert p == {"value": 95, "n": 100, "beyond": 5}
    assert stats.percentile(xs, 0.90)["value"] == 90


def test_percentile_of_few_samples_is_the_maximum():
    assert stats.percentile([3, 1, 2], 0.95) == {"value": 3, "n": 3, "beyond": 0}
    assert stats.percentile([], 0.5)["value"] is None


def test_missing_samples_count_as_infinite():
    xs = [1.0] * 8 + [math.inf] * 2
    assert stats.percentile(xs, 0.80)["value"] == 1.0
    assert math.isinf(stats.percentile(xs, 0.90)["value"])
