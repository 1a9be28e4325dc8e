"""rounds_to_tol: mean over the window's solves of the rounds each needed
to reach F <= F* (1 + rel_tol), read from the solver's per-round objective
trace with ``reference/criteria.rounds_to_tolerance``."""
import statistics


def read(ctx):
    needed = ctx["counters"].get("rounds_needed")
    return float(statistics.fmean(needed)) if needed else None
