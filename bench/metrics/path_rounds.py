"""path_rounds: mean over the window's paths of the rounds a whole path
ran (``PathResult.rounds`` summed over its lambdas): what the warm starts
and the early stop leave to do."""
import statistics


def read(ctx):
    rounds = ctx["counters"].get("path_rounds")
    return float(statistics.fmean(rounds)) if rounds else None
