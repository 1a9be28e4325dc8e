"""Fig. 4 reproduction: sparse logistic regression on the two regimes
(zeta-like n >> d; rcv1-like d > n), now on the fused loss-seam engine
(DESIGN §12).

Two sections per regime:

  * **Fused-kernel timing + convergence** (always runs, rows tagged
    ``"bench": "logreg"`` and merged into the repo-root
    ``BENCH_kernels.json`` on full runs): per-round wall of the scalar
    logistic Shotgun round vs the fused logistic kernel (gradient form and,
    on the well-conditioned n >> d regime, the per-block Newton variant),
    plus rounds-to-tolerance from each solver's objective trace.  The
    headline trajectory field

        speedup_fused_logreg_vs_scalar
          = (scalar rounds-to-tol x scalar round us)
            / (fused-Newton rounds-to-tol x fused-Newton round us)

    is wall-clock-to-target — the currency of Fig. 4 itself (objective vs
    time): the fused launch amortizes dispatch over R rounds AND the Newton
    steps need fewer rounds, and the product is what a user sees.  It is
    attached to the Newton regime row only; the d > n regime (where
    separable Newton is unsafe without the §9 guard) reports its
    gradient-form ratio under the non-trajectory name
    ``time_to_tol_ratio_vs_scalar``.

  * **Paper baselines** (full runs only): Shotgun CDN / shooting CDN /
    SGD (rate-searched) / parallel SGD / SMIDAS with held-out (10%) error,
    emitted to ``results/fig4_logreg.json`` alongside the timing rows but
    never merged into the root artifact.

Interpret-mode timings (CPU container): the scalar side is jitted XLA and
the fused side pays the Pallas interpreter, so the committed speedup is a
conservative floor — on hardware the fused kernel's halved A traffic
(roofline.logistic_round_model: identical bytes to lasso, more flops, still
memory-bound) only widens it.  Env: BENCH_SMOKE=1 shrinks to one small
regime, skips baselines, and leaves the committed artifact alone.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, merge_root, time_us
from benchmarks.roofline import logistic_round_model
from repro.core import objectives as obj
from repro.core.baselines import sgd, smidas
from repro.core.cdn import shooting_cdn_solve, shotgun_cdn_solve
from repro.core.shotgun import rounds_to_tolerance, shotgun_solve
from repro.core.spec import SolverSpec
from repro.data import synthetic as syn
from repro.kernels import ops
from repro.kernels.shotgun_block import (VMEM_BUDGET, auto_tile_n,
                                         fused_vmem_bytes)

LAM = 0.5
R_LAUNCH = 8          # fused rounds per pallas_call
REL_TOL = 0.005       # rounds_to_tolerance target (repo convention)

# newton=True only where n >> d keeps the logistic problem non-separable —
# the Bian et al. steps have no line search, and on a separable design they
# ride the h >= 1e-8 curvature floor into divergence (that regime belongs
# to the §9 guard, not to a benchmark).
REGIMES = {
    "zeta_like": dict(n=8192, d=256, K=2, newton=True),
    "rcv1_like": dict(n=1024, d=2048, K=4, newton=False),
}
SMOKE_REGIMES = {
    "zeta_like": dict(n=1024, d=256, K=2, newton=True),
}


def _heldout_error(x, A_te, y_te):
    pred = jnp.sign(A_te @ x)
    pred = jnp.where(pred == 0, 1.0, pred)
    return float(jnp.mean(pred != y_te))


def _fused_bench(regime, n, d, K, newton, conv_rounds, smoke):
    A, y, _ = syn.logistic_data(seed=0, n=n, d=d)
    n_tr = int(0.9 * n)
    A_te, y_te = jnp.asarray(A[n_tr:]), jnp.asarray(y[n_tr:])
    prob = obj.make_problem(A[:n_tr], y[:n_tr], lam=LAM, loss=obj.LOGISTIC)
    key = jax.random.PRNGKey(0)
    P = K * ops.BLOCK

    # refuse configs the fused logistic kernel could not compile on
    # hardware — priced with the Newton twin when this regime runs it,
    # since that is the larger resident set (shotgun-lint SL101 re-checks
    # the committed rows through the same fused_vmem_bytes(loss=) seam)
    Ap, _, _ = ops.pad_problem(prob.A, prob.y)
    np_, dp_ = Ap.shape
    tile_n = auto_tile_n(np_, ops.BLOCK, d=dp_)
    loss_tag = "logistic_newton" if newton else "logistic"
    vmem = fused_vmem_bytes(np_, dp_, K, tile_n=tile_n, loss=loss_tag)
    if vmem > VMEM_BUDGET:
        raise ValueError(
            f"fused logistic config (n={np_}, d={dp_}, K={K}, "
            f"loss={loss_tag}) needs {vmem} B of VMEM > {VMEM_BUDGET} B "
            "budget — shrink the regime shape or K")

    def scalar(rounds):
        return shotgun_solve(prob, key, spec=SolverSpec(
            loss="logistic", P=P, rounds=rounds))

    def fused(rounds, newton=False):
        return ops.block_shotgun_solve(prob, key, spec=SolverSpec(
            loss="logistic", P=P, rounds=rounds, newton=newton))

    us_scalar = time_us(lambda: scalar(1), reps=3)
    us_fused = time_us(lambda: fused(R_LAUNCH), reps=3) / R_LAUNCH
    us_newton = (time_us(lambda: fused(R_LAUNCH, newton=True), reps=3)
                 / R_LAUNCH) if newton else None

    f_scalar = np.asarray(scalar(2 * conv_rounds).trace.objective)
    res_grad = fused(conv_rounds)
    f_grad = np.asarray(res_grad.trace.objective)
    f_newton = (np.asarray(fused(conv_rounds, newton=True).trace.objective)
                if newton else None)
    fstar = min(f_scalar.min(), f_grad.min(),
                f_newton.min() if newton else np.inf)
    r_scalar = int(rounds_to_tolerance(f_scalar, fstar, REL_TOL))
    r_grad = int(rounds_to_tolerance(f_grad, fstar, REL_TOL))

    model = logistic_round_model(np_, dp_, K, newton=newton)
    row = {
        "bench": "logreg", "regime": regime, "loss": loss_tag,
        "n": np_, "d": dp_, "K": K, "P_eff": P, "tile_n": tile_n,
        "rounds_per_launch": R_LAUNCH, "lam": LAM, "rel_tol": REL_TOL,
        "scalar_round_us": round(us_scalar, 1),
        "fused_round_us": round(us_fused, 1),
        "rounds_to_tol_scalar": r_scalar,
        "rounds_to_tol_fused": r_grad,
        "heldout_error_fused": _heldout_error(res_grad.x, A_te, y_te),
        "hbm_bytes_per_round_fused": model["fused"]["bytes"],
        "flops_per_byte_fused": round(model["fused"]["intensity"], 3),
        "flops_per_byte_scalar": round(model["scalar"]["intensity"], 3),
    }
    if newton:
        r_newton = int(rounds_to_tolerance(f_newton, fstar, REL_TOL))
        speedup = (r_scalar * us_scalar) / (r_newton * us_newton)
        row.update({
            "newton_round_us": round(us_newton, 1),
            "rounds_to_tol_newton": r_newton,
            "speedup_fused_logreg_vs_scalar": round(speedup, 2),
        })
        if not smoke:
            # the Newton rounds win is the point of the variant (satellite
            # test pins the objective-per-round win; this pins the product)
            assert r_newton <= r_grad, (r_newton, r_grad)
            assert speedup >= 3, (speedup, r_scalar, us_scalar,
                                  r_newton, us_newton)
    else:
        row["time_to_tol_ratio_vs_scalar"] = round(
            (r_scalar * us_scalar) / (r_grad * us_fused), 2)
    print(f"fig4,{regime},scalar_round={us_scalar:.0f}us,"
          f"fused_round={us_fused:.0f}us,"
          f"rounds_to_tol={r_scalar}/{r_grad}"
          + (f"/{row['rounds_to_tol_newton']},speedup="
             f"{row['speedup_fused_logreg_vs_scalar']}" if newton else ""),
          flush=True)
    return row, (prob, A_te, y_te)


def _baseline_rows(regime, prob, A_te, y_te):
    runs = {
        "shotgun_cdn_p8": lambda: shotgun_cdn_solve(
            prob, jax.random.PRNGKey(0), P=8, rounds=2000),
        "shooting_cdn": lambda: shooting_cdn_solve(
            prob, jax.random.PRNGKey(0), rounds=4000),
        "sgd_best_rate": lambda: sgd.sgd_rate_search(
            prob, jax.random.PRNGKey(0), steps=20000,
            rates=np.geomspace(1e-3, 1.0, 7))[0],
        "parallel_sgd_p8": lambda: sgd.parallel_sgd_solve(
            prob, jax.random.PRNGKey(0), eta=0.1, steps=20000, K=8),
        "smidas": lambda: smidas.smidas_solve(
            prob, jax.random.PRNGKey(0), eta=0.05, steps=20000),
    }
    rows = []
    for name, fn in runs.items():
        t0 = time.time()
        res = fn()
        tr = np.asarray(res.trace.objective if hasattr(res, "trace")
                        else res.objective)
        jax.block_until_ready(tr)
        dt = time.time() - t0
        err = _heldout_error(res.x, A_te, y_te)
        rows.append({"regime": regime, "solver": name,
                     "final_objective": float(tr[-1]),
                     "heldout_error": err, "time_s": round(dt, 2)})
        print(f"fig4,{regime},{name},F={tr[-1]:.4f},err={err:.3f},"
              f"t={dt:.1f}s", flush=True)
    return rows


def run() -> list[dict]:
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    regimes = SMOKE_REGIMES if smoke else REGIMES
    conv_rounds = 120 if smoke else 400
    timing_rows, rows = [], []
    for regime, kw in regimes.items():
        row, (prob, A_te, y_te) = _fused_bench(
            regime, kw["n"], kw["d"], kw["K"], kw["newton"],
            conv_rounds, smoke)
        timing_rows.append(row)
        rows.append(row)
        if not smoke:
            rows.extend(_baseline_rows(regime, prob, A_te, y_te))
    emit(rows, "fig4_logreg")
    if not smoke:
        # only the kernel-timing rows join the committed perf trajectory
        merge_root(timing_rows, tag="logreg")
    return rows


if __name__ == "__main__":
    run()
