"""Traffic kind ``solve``: back-to-back cold solves from one caller.

The timed path is ``get_solver("block_fused")`` driven by a
``SolverSpec``, everything else at the program's defaults; each solve
has its own key for the block draws and runs the configuration's round
budget.  Its end-to-end metric is ``time_to_tol_ms``.

The output check (every solve of the window):

  f_gap              max of (F(x) - F*) / |F*|, F(x) taken afresh
  trace_gap          max of |F_reported - F(x)| / |F(x)|: the solver's
                     last in-kernel objective against its own iterate
  margin_gap         max of |z - A x| / |A x|: the margin the solver
                     maintained against that of the iterate it returned
  oracle_gap         over a sample of solves drawn from the seed, max over
                     every round of the budget of |F_solver - F_oracle| /
                     |F_oracle|, the oracle a plain replay of the same
                     block draws (``reference/solvers.block_rounds``)
  oracle_rounds_gap  over the same sample, max of |rounds to the criterion
                     by the solver's trace - by the oracle's|: the number
                     ``time_to_tol_ms`` is built on, read both ways
"""
from __future__ import annotations

import numpy as np

import harness

SPAN = "bench.solve"


def timed(cell):
    from repro.core import SolverSpec, get_solver
    from repro.core import objectives as obj
    cfg = cell.config
    spec = SolverSpec(loss=cfg["loss"], P=cfg["P"], rounds=cfg["rounds"],
                      fused=True, newton=cfg.get("newton", False))
    solve = get_solver("block_fused")

    def run(A, y, lam, key):
        prob = obj.make_problem(A, y, lam, loss=cfg["loss"], normalize=False)
        res = solve(prob, key, spec=spec)
        return res.x, res.z, res.trace.objective
    return run


def warm(run, A, y, lam, tkey, traffic):
    import jax
    jax.block_until_ready(run(A, y, lam, harness.unit_key(tkey, harness.WARM)))


def window(run, A, y, lam, tkey, traffic, seconds):
    import jax
    return harness.back_to_back(
        SPAN, seconds,
        lambda i: jax.block_until_ready(run(A, y, lam,
                                            harness.unit_key(tkey, i))))


def check(cell, A, y, lam, fstar, units, tkey, seed):
    import jax.numpy as jnp
    from reference import criteria, solvers
    cfg, traffic = cell.config, cell.traffic
    loss, B, rel_tol = cfg["loss"], cfg["rounds"], traffic["rel_tol"]
    X = jnp.stack([u[0] for u in units])             # (S, d)
    Z = jnp.stack([u[1] for u in units])             # (S, n)
    T = np.asarray(jnp.stack([u[2] for u in units]), np.float64)
    fx, AX = harness.objectives(A, y, lam, X, loss)
    margin = np.asarray(jnp.linalg.norm(Z.T - AX, axis=0)
                        / jnp.linalg.norm(AX, axis=0), np.float64)
    needed = [criteria.rounds_to_tolerance(t, fstar, rel_tol) for t in T]
    Ap = solvers.pad_blocks(A)
    nblk = Ap.shape[1] // solvers.BLOCK
    K = -(-cfg["P"] // solvers.BLOCK)
    oracle, rounds_gap = 0.0, 0
    for i in harness.sample(len(units), traffic["oracle_solves"], seed):
        idx = solvers.draw_blocks(harness.unit_key(tkey, i), B, nblk, K)
        _, _, f_or = solvers.block_rounds(
            Ap, y, lam, jnp.zeros(Ap.shape[1]), jnp.zeros_like(y), idx,
            loss, cfg.get("newton", False))
        f_or = np.asarray(f_or, np.float64)
        oracle = max(oracle, float(np.max(np.abs(T[i] - f_or)
                                          / np.abs(f_or))))
        rounds_gap = max(rounds_gap, abs(
            needed[i] - criteria.rounds_to_tolerance(f_or, fstar, rel_tol)))
    numbers = {
        "f_gap": float(np.max((fx - fstar) / abs(fstar))),
        "trace_gap": float(np.max(np.abs(T[:, -1] - fx) / np.abs(fx))),
        "margin_gap": float(np.max(margin)),
        "oracle_gap": oracle,
        "oracle_rounds_gap": float(rounds_gap),
    }
    harness.log(f"rounds to the criterion: min {min(needed)}, mean "
                f"{np.mean(needed):.2f}, max {max(needed)} of {B}")
    failed = sum(1 for k in needed if k > B)
    return numbers, {"rounds_needed": needed, "rounds_run": B * len(units),
                     "failed": failed}


def end_to_end(window_s, units, counters):
    from reference import criteria
    return {"time_to_tol_ms": criteria.time_to_tol_ms(
        window_s, counters["rounds_run"], counters["rounds_needed"])}
