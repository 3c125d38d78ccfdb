import pytest

from harness.window import (TAIL_MIN_BEYOND, rate_per_s, round_durations,
                            tail)


def test_rate_counts_whole_steps_to_the_last_steps_end():
    # window opened at t=10; three steps ended at 11, 12.5 and 14
    assert rate_per_s(10.0, [11.0, 12.5, 14.0]) == pytest.approx(3 / 4.0)
    with pytest.raises(ValueError):
        rate_per_s(10.0, [])


def test_tail_needs_samples_beyond_it():
    n = 20 * TAIL_MIN_BEYOND
    vals = list(range(1, n + 1))
    assert tail(vals) == 0.95 * n          # nearest rank
    with pytest.raises(ValueError):
        tail(vals[:-1])                     # 9 beyond the p95


def test_round_durations_group_steps_by_round():
    stamps = [1.0, 1.001, 1.002, 2.0, 2.001, 3.5]
    rounds = [7, 7, 7, 9, 9, 12]
    assert round_durations(0.5, stamps, rounds) == pytest.approx(
        [0.5, 1.0, 1.5])
