"""Fig. 3 reproduction: Shotgun (P=8) vs published Lasso solvers across the
paper's four dataset categories, for lambda in {0.5, 10}.

Metric: wall time to reach within 0.5% of F* (per-solver jit compile time
excluded by warming up on a tiny slice), plus final objective parity."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, fstar_of
from repro.core import objectives as obj
from repro.core.shotgun import shotgun_solve, shooting_solve
from repro.core.baselines import fista, fpc_as, gpsr, iht, l1_ls, sparsa
from repro.data import synthetic as syn

CATEGORIES = {
    "sparco": dict(gen=syn.sparco, kw=dict(seed=0, n=512, d=1024)),
    "singlepixcam": dict(gen=syn.singlepixcam, kw=dict(seed=0, n=410, d=1024)),
    "sparse_imaging": dict(gen=syn.sparse_imaging, kw=dict(seed=0, n=954, d=2048)),
    "large_sparse": dict(gen=syn.large_sparse, kw=dict(seed=0, n=1024, d=8192)),
}
# the paper runs lambda in {0.5, 10} on unnormalized data; after column
# normalization the meaningful analogue is a fraction of lambda_max
# (0.5 = weak regularization / dense solution, 0.05 even denser; above
# lambda_max every solver trivially returns x = 0)
LAMBDA_FRACS = [0.5, 0.1]

BUDGET = {  # iteration budgets tuned for CPU wall time; coordinate descent
    # needs O(d) updates per sweep, so its budgets scale with the category
    "shotgun_p8": 30000, "shooting": 60000, "fista": 4000,
    "sparsa": 4000, "gpsr_bb": 4000, "fpc_as": 40, "l1_ls": 40,
}


def _solvers():
    return {
        "shotgun_p8": lambda p, n: shotgun_solve(p, jax.random.PRNGKey(0), P=8, rounds=n),
        "shooting": lambda p, n: shooting_solve(p, jax.random.PRNGKey(0), rounds=n),
        "fista": lambda p, n: fista.fista_solve(p, n),
        "sparsa": lambda p, n: sparsa.sparsa_solve(p, n),
        "gpsr_bb": lambda p, n: gpsr.gpsr_bb_solve(p, n),
        "fpc_as": lambda p, n: fpc_as.fpc_as_solve(p, cycles=n),
        "l1_ls": lambda p, n: l1_ls.l1_ls_solve(p, outer=n),
    }


def _trace(res):
    return np.asarray(res.trace.objective if hasattr(res, "trace")
                      else res.objective)


def run() -> list[dict]:
    rows = []
    for cat, spec in CATEGORIES.items():
        A, y, _ = spec["gen"](**spec["kw"])
        prob0 = obj.make_problem(A, y, lam=1.0)
        lmax = float(obj.lambda_max(prob0.A, prob0.y, prob0.loss))
        for frac in LAMBDA_FRACS:
            lam = frac * lmax
            prob = obj.make_problem(A, y, lam=lam)
            fstar = fstar_of(prob)
            target = fstar + 0.005 * abs(fstar)
            for name, solver in _solvers().items():
                n = BUDGET[name]
                # a solver that raises is a fault, not a data row
                solver(prob, 4 if name in ("fpc_as", "l1_ls") else 50)  # warm jit
                t0 = time.time()
                res = solver(prob, n)
                tr = _trace(res)
                jax.block_until_ready(tr)
                dt = time.time() - t0
                f_end = float(tr[-1])
                hit = np.nonzero(tr <= target)[0]
                frac_done = (hit[0] + 1) / len(tr) if hit.size else None
                t_hit = dt * frac_done if frac_done else float("inf")
                ok = f_end <= target * (1 + 1e-6) or bool(hit.size)
                rows.append({"category": cat, "lam": lam,
                             "lam_frac_of_max": frac, "solver": name,
                             "time_to_0.5pct_s": None if t_hit == float("inf") else round(t_hit, 3),
                             "total_time_s": round(dt, 3),
                             "final_F": f_end, "fstar": fstar, "converged": ok})
                print(f"fig3,{cat},lam={lam:.3g}({frac}lmax),{name},"
                      f"t={'inf' if t_hit == float('inf') else round(t_hit,3)}s,"
                      f"conv={ok}", flush=True)
    return emit(rows, "fig3_lasso_solvers")


if __name__ == "__main__":
    run()
