"""The arithmetic of ``tokens_per_s_chip`` on made-up series."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import readings  # noqa: E402


def ends(durations, start=100.0):
    out, t = [], start
    for d in durations:
        t += d
        out.append(t)
    return out


def IDS(n, k=1):
    return [k * (i + 1) for i in range(n)]


def LOSS(n):
    return {i + 1: 1.0 for i in range(n)}


def test_one_hiccup_moves_the_rate_and_is_told_apart_beside_it():
    clean = ends([2.0] * 12)
    hiccup = ends([2.0] * 5 + [20.0] + [2.0] * 6)
    a = readings.summarize(clean, IDS(12), [], LOSS(12), 0, 11, 16384, 1)
    b = readings.summarize(hiccup, IDS(12), [], LOSS(12), 0, 11, 16384, 1)
    # the rate is all the window's tokens over all its seconds: 11 steps
    # in 22 s, and in 40 s with the stall
    assert a["tokens_per_s_chip"] == 8192.0
    assert b["tokens_per_s_chip"] == pytest.approx(16384 * 11 / 40.0)
    # beside it: the median step's rate does not move, the worst reading
    # says it was one stall and not every step
    assert a["tokens_per_s_chip_median_step"] == 8192.0
    assert b["tokens_per_s_chip_median_step"] == 8192.0
    assert a["step_s_worst_over_median"] == 1.0
    assert b["step_s_worst_over_median"] == 10.0


@pytest.mark.parametrize("seconds,steps", [(3.9, 2), (4.0, 2), (4.1, 3),
                                           (10.0, 5)])
def test_window_is_a_whole_number_of_steps(seconds, steps):
    series = ends([2.0] * 10)
    close = readings.window_close_index(series, 1, seconds)
    got = readings.step_readings(series, IDS(10), 1, close)
    assert len(got) == steps
    assert sum(got) == pytest.approx(series[close] - series[1])
    assert series[close] - series[1] >= seconds


def test_window_waits_for_three_whole_steps_after_the_last_compile():
    series = ends([1.0] * 8, start=0.0)       # ends at 1, 2, ..., 8
    # a compilation ends at 2.5, inside the third step: steps 4, 5, 6 are
    # the first three whole steps after it, so the window opens at 6.0.
    assert readings.window_open_index(series, IDS(8), [0.5, 2.5], 3) == 5
    assert readings.window_open_index(series, IDS(8), [], 3) == 3
    assert readings.window_open_index(series[:2], IDS(2), [2.5], 3) is None


def test_a_compilation_inside_the_window_is_not_correct():
    series = ends([2.0] * 10)
    ok = readings.summarize(
        series, IDS(10), [series[0] - 1], LOSS(10), 1, 8, 8, 1)
    bad = readings.summarize(
        series, IDS(10), [series[4] + 0.5], LOSS(10), 1, 8, 8, 1)
    assert ok["ok"] and ok["compiles_in_window"] == 0
    assert not bad["ok"] and bad["compiles_in_window"] == 1


def test_a_non_finite_loss_fails_its_step():
    series = ends([2.0] * 6)
    losses = {**LOSS(6), 3: float("nan")}
    got = readings.summarize(series, IDS(6), [], losses, 0, 5, 8, 1)
    assert got["failed"] == 1 and got["steps"] == 5 and not got["ok"]


def test_chips_divide_the_rate():
    series = ends([2.0] * 6)
    one = readings.summarize(series, IDS(6), [], LOSS(6), 0, 5, 65536, 1)
    four = readings.summarize(series, IDS(6), [], LOSS(6), 0, 5, 65536, 4)
    assert one["tokens_per_s_chip"] == 4 * four["tokens_per_s_chip"]


def test_a_reading_of_k_steps_counts_k_steps():
    """Blocks of four steps closed by one read: seconds over four."""
    series = ends([8.0] * 6)
    got = readings.summarize(
        series, IDS(6, 4), [], LOSS(24), 0, 5, 16384, 1)
    assert got["readings"] == [2.0] * 5 and got["steps"] == 20
    assert got["tokens_per_s_chip"] == 8192.0
    assert got["tokens_per_s_chip_median_step"] == 8192.0
    # three whole steps after the last compile: one whole block is enough
    assert readings.window_open_index(series, IDS(6, 4), [series[0] + 1], 3) == 2
