"""The paper's convergence criterion and the end-to-end arithmetic built on
it, kept with the benchmark so that no change to the program moves them.
"""
from __future__ import annotations

import numpy as np


def rounds_to_tolerance(trace, f_star: float, rel_tol: float = 0.005) -> int:
    """Number of rounds until F <= F* + rel_tol |F*| (the paper's 0.5 %
    criterion at the default): the index of the first such round plus
    one, or ``len(trace) + 1`` when no round gets there.  A non-finite F
    never counts as a hit."""
    t = np.asarray(trace, np.float64)
    target = f_star + rel_tol * abs(f_star)
    hit = np.isfinite(t) & (t <= target)
    return int(np.argmax(hit)) + 1 if hit.any() else len(t) + 1


def time_to_tol_ms(window_s: float, rounds_run: int, rounds_needed) -> float:
    """Milliseconds to the criterion: the window's wall time per round run,
    over all rounds run in it, times the mean rounds each solve needed."""
    return window_s / rounds_run * float(np.mean(rounds_needed)) * 1e3
