"""The window rule, the nearest-rank percentile and the spread: arithmetic only."""

import pytest

from benchmark import stats


def test_percentile_is_nearest_rank_and_a_value_that_happened():
    values = [float(v) for v in range(1, 11)]  # 1..10
    assert stats.percentile_nearest_rank(values, 90) == 9.0  # ceil(0.9 * 10) = 9th
    assert stats.percentile_nearest_rank(values, 91) == 10.0
    assert stats.percentile_nearest_rank(values, 100) == 10.0
    assert stats.percentile_nearest_rank([5.0], 90) == 5.0
    assert stats.percentile_nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
    # 170 steps: the 153rd in order, so 17 steps lie beyond it.
    steps = [220.0] * 152 + [221.0] + [230.0] * 17
    assert stats.percentile_nearest_rank(steps, 90) == 221.0


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_refuses_what_is_not_a_percentile(bad):
    with pytest.raises(ValueError):
        stats.percentile_nearest_rank([1.0], bad)
    with pytest.raises(ValueError):
        stats.percentile_nearest_rank([], 90)


def test_window_holds_whole_steps_only():
    ends = [0.25, 0.5, 0.75, 1.0, 1.25]
    assert stats.whole_steps(ends, 1.0) == 4  # the step ending at the limit counts
    assert stats.whole_steps(ends, 0.99) == 3  # one that ends after it does not, not even in part
    assert stats.whole_steps(ends, 0.1) == 0
    assert stats.whole_steps([], 1.0) == 0
    # four-group steps of 13.5 s in 48 s: three whole steps, 40.5 s.
    assert stats.whole_steps([13.5, 27.0, 40.5, 54.0], 48) == 3


def test_a_step_that_would_end_after_the_window_is_not_started():
    assert stats.may_start(0.0, 0.0, 48)  # the first step always starts
    assert stats.may_start(27.0, 13.5, 48)  # 27 + 1.05 * 13.5 = 41.2
    assert not stats.may_start(40.5, 13.5, 48)
    assert not stats.may_start(47.9, 0.22, 48)


def test_spread_is_the_interquartile_distance_over_the_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4), exclusive method: q1 = 100.75, q3 = 104.25
    assert stats.spread(values) == pytest.approx(3.5 / 102.5)
    far = [220.0, 220.1, 220.2, 220.3, 220.4, 230.0]
    assert stats.spread_without_farthest(far) < stats.spread(far)
