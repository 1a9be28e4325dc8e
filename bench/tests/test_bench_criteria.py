"""The end-to-end arithmetic: rounds to the 0.5 % criterion and
time_to_tol_ms, on synthetic objective traces and windows."""
import math

import numpy as np
import pytest

import _paths  # noqa: F401
from reference import criteria


def test_rounds_to_tolerance_counts_the_first_round_within_tolerance():
    f_star = 100.0
    trace = [300.0, 150.0, 100.6, 100.4, 100.1]   # 100.5 is the target
    assert criteria.rounds_to_tolerance(trace, f_star, 0.005) == 4


def test_rounds_to_tolerance_when_never_reached_and_non_finite():
    assert criteria.rounds_to_tolerance([200.0, 150.0], 100.0) == 3
    assert criteria.rounds_to_tolerance([np.nan, -np.inf, 99.0], 100.0) == 3
    # a negative optimum: the target is F* + 0.005 |F*|
    assert criteria.rounds_to_tolerance([-90.0, -99.4, -99.6], -100.0) == 3


def test_time_to_tol_ms_is_round_time_times_mean_rounds_needed():
    # 4 solves of 640 rounds in 2.56 s: 1 ms a round; they needed
    # 300, 350, 400 and 350 rounds, 350 on average
    t = criteria.time_to_tol_ms(2.56, 4 * 640, [300, 350, 400, 350])
    assert t == pytest.approx(350.0)


def test_time_to_tol_ms_from_a_window_of_synthetic_solves():
    rng = np.random.default_rng(0)
    f_star, budget, solves = 50.0, 128, 9
    needed = []
    traces = []
    for _ in range(solves):
        # F falls geometrically towards F*; rate differs per solve
        rate = rng.uniform(0.7, 0.9)
        tr = f_star + 1000.0 * rate ** np.arange(1, budget + 1)
        traces.append(tr)
        k = math.ceil(math.log(0.005 * f_star / 1000.0) / math.log(rate))
        needed.append(k)
    got = [criteria.rounds_to_tolerance(t, f_star) for t in traces]
    assert got == needed
    window_s = 0.45
    expect = window_s / (solves * budget) * np.mean(needed) * 1e3
    assert criteria.time_to_tol_ms(window_s, solves * budget, got) == \
        pytest.approx(expect)


def test_rounds_to_tol_reader_takes_the_mean():
    import harness
    read = harness.reader("rounds_to_tol")
    assert read({"counters": {"rounds_needed": [3, 4, 8]}}) == 5.0
    assert read({"counters": {}}) is None
