"""The spread the driver reads, and the interval arithmetic of the trace
reduction."""

from lib import stats


def test_quartile_spread_matches_numpy():
    import numpy as np
    xs = [10.0, 10.4, 9.8, 10.1, 10.9, 9.5]
    want = (np.percentile(xs, 75) - np.percentile(xs, 25)) / np.median(xs)
    assert abs(stats.quartile_spread(xs) - want) < 1e-12
    assert stats.quartile_spread([1.0]) is None


def test_interval_arithmetic():
    merged = stats.merge_intervals([(3, 4), (0, 1), (0.5, 2), (4, 4)])
    assert merged == [(0, 2), (3, 4)]
    assert stats.total(merged) == 3
    assert stats.clip_intervals(merged, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert stats.subtract_intervals([(0, 10)], merged) == [(2, 3), (4, 10)]
    assert stats.subtract_intervals([(0, 2), (3, 5)], [(1, 4)]) \
        == [(0, 1), (4, 5)]


def test_group_rates_leave_a_stall_to_one_group():
    # a step every 10 ms for 10 s, and one stall of 300 ms
    times, t = [], 0.0
    for i in range(1000):
        t += 0.31 if i == 500 else 0.01
        times.append(t)
    rates = stats.group_rates(times, 0.25)
    assert 35 <= len(rates) <= 41
    assert abs(stats.median(rates) - 100.0) < 1e-6
    assert min(rates) < 60.0                 # the group that held the stall
    whole = (len(times) - 1) / (times[-1] - times[0])
    assert whole < 98.0                      # the whole window carries it
    # groups run from event to event: no multiple of the span
    assert stats.group_rates([0.0, 0.3, 0.7], 0.25) \
        == [1 / 0.3, 1 / (0.7 - 0.3)]
    assert stats.group_rates([0.0], 0.25) == []
