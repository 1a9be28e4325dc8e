"""Traffic kind ``path``: back-to-back cold lambda paths from one caller.

The timed path is ``solve_path`` on the fused solver with a fresh
warm-start cache and key per path, down to the configuration's lambda.
Its end-to-end metric is ``path_s``.

The output check (every path of the window, at its last lambda):

  f_gap      max of (F(x) - F*) / |F*|, F(x) taken afresh
  trace_gap  max of |F_reported - F(x)| / |F(x)|
"""
from __future__ import annotations

import numpy as np

import harness

SPAN = "bench.path"


def timed(cell):
    from repro.core import SolverSpec, solve_path
    from repro.core import objectives as obj
    from repro.core.batched import WarmStartCache
    cfg, traffic = cell.config, cell.traffic
    spec = SolverSpec(loss=cfg["loss"], P=cfg["P"], rounds=cfg["rounds"])

    def run(A, y, lam, key, num_lambdas):
        prob = obj.make_problem(A, y, lam, loss=cfg["loss"], normalize=False)
        res = solve_path(prob, key, lam_target=lam, num_lambdas=num_lambdas,
                         solver="block_fused", spec=spec,
                         cache=WarmStartCache(), tol=traffic["tol"])
        return res.x, float(res.objectives[-1]), int(res.rounds.sum())
    return run


def warm(run, A, y, lam, tkey, traffic):
    run(A, y, lam, harness.unit_key(tkey, harness.WARM),
        traffic["warm_lambdas"])


def window(run, A, y, lam, tkey, traffic, seconds):
    return harness.back_to_back(
        SPAN, seconds,
        lambda i: run(A, y, lam, harness.unit_key(tkey, i),
                      traffic["num_lambdas"]))


def check(cell, A, y, lam, fstar, units, tkey, seed):
    import jax.numpy as jnp
    fx, _ = harness.objectives(A, y, lam, jnp.stack([u[0] for u in units]),
                               cell.config["loss"])
    rep = np.asarray([u[1] for u in units], np.float64)
    f_gap = (fx - fstar) / abs(fstar)
    numbers = {"f_gap": float(np.max(f_gap)),
               "trace_gap": float(np.max(np.abs(rep - fx) / np.abs(fx)))}
    failed = int(np.sum(f_gap > cell.traffic["rel_tol"]))
    return numbers, {"path_rounds": [u[2] for u in units], "failed": failed}


def end_to_end(window_s, units, counters):
    return {"path_s": window_s / len(units)}
