"""Shotgun (Alg. 2): parallel stochastic coordinate descent for L1 losses.

Three solvers:

``shooting_solve``     Alg. 1 — sequential SCD (P = 1 special case).
``shotgun_solve``      Alg. 2 — practical signed form. Each round samples P
                       coordinates (with replacement, forming the multiset
                       P_t of the paper) and applies the Shooting update to
                       all of them from the same iterate; the collective
                       update is the scatter-add of the per-coordinate deltas,
                       exactly the paper's Δx.
``shotgun_dup_solve``  Alg. 2 verbatim on the duplicated-feature positive
                       orthant form (Eq. 4) with update
                       δx_j = max(-x_j, -(∇F)_j / β). Used by the theory
                       tests; fixed points coincide with the signed form.

All maintain z = A x (Sec. 4.1.1's maintained-Ax trick): per round the work
is O(n·P) instead of O(n·d).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import health
from repro.core import objectives as obj
from repro.core.health import GuardConfig
from repro.core.objectives import Problem, DupProblem
from repro.core.spec import SolverSpec, reject_legacy_kwargs


class Trace(NamedTuple):
    objective: jax.Array   # (rounds,) F(x^(t)) after round t
    nnz: jax.Array         # (rounds,) number of non-zeros


class Result(NamedTuple):
    x: jax.Array
    z: jax.Array           # final margin A x
    trace: Trace
    # health.STATUS_OK / STATUS_RECOVERED / STATUS_DIVERGED (int32 scalar);
    # None only for legacy constructors that predate the sentinel layer.
    status: jax.Array | None = None


def _sample(key, d, P, replace: bool):
    if replace:
        return jax.random.randint(key, (P,), 0, d)
    return jax.random.choice(key, d, (P,), replace=False)


def shotgun_solve(prob: Problem, key: jax.Array, P: int | None = None,
                  rounds: int | None = None,
                  x0: jax.Array | None = None, replace: bool = True,
                  guard: GuardConfig | None = None,
                  spec: SolverSpec | None = None) -> Result:
    """Run `rounds` synchronous Shotgun rounds of P parallel updates each.

    ``spec=SolverSpec(...)`` is the canonical interface (DESIGN §12): P /
    rounds / guard come from the spec and ``spec.loss`` is validated
    against ``prob.loss``.  The legacy (P, rounds, ...) kwargs still work
    through this shim (same jitted core, bit-for-bit) but emit a
    ``DeprecationWarning``.

    ``prob.A`` may be dense or a ``BlockedCSC`` container: the round is
    written against the ``gather_cols`` / ``cols_rmatvec`` /
    ``cols_matvec_add`` seam, so on a sparse design the per-round cost is
    O(tile·P) nnz-tile work instead of O(n·P) dense columns (DESIGN §8).

    ``guard`` enables the divergence sentinel + adaptive-P backoff
    (DESIGN §9): every round still draws P candidate coordinates but only
    the first ``p_eff`` apply; when the objective trips the guard the round
    rolls back to the last-good (x, z) snapshot held in the scan carry and
    ``p_eff`` halves (clamped to ``guard.p_min``, e.g. ``spectral.p_star``).
    ``guard=None`` (default) is the original unguarded path, trajectory
    unchanged.
    """
    if spec is not None:
        reject_legacy_kwargs(spec, P=P, rounds=rounds)
        spec.check_loss(prob.loss)
        P, rounds, guard = spec.P, spec.rounds, spec.guard
    else:
        if P is None or rounds is None:
            raise TypeError("shotgun_solve needs (P, rounds) or spec=")
        warnings.warn(
            "shotgun_solve(P=..., rounds=...) kwargs are deprecated; pass "
            "spec=SolverSpec(...)", DeprecationWarning, stacklevel=2)
    return _shotgun_solve_core(prob, key, P, rounds, x0=x0, replace=replace,
                               guard=guard)


@functools.partial(jax.jit, static_argnames=("P", "rounds", "replace",
                                             "guard"))
def _shotgun_solve_core(prob: Problem, key: jax.Array, P: int, rounds: int,
                        x0: jax.Array | None = None, replace: bool = True,
                        guard: GuardConfig | None = None) -> Result:
    A, y, lam, beta = prob.A, prob.y, prob.lam, prob.beta
    d = A.shape[1]
    x0 = jnp.zeros(d, A.dtype) if x0 is None else x0
    z0 = obj.matvec(A, x0)

    def update(x, z, idx, p_eff):
        r = obj.residual_like(z, y, prob.loss)
        cols = obj.gather_cols(A, idx)       # (n, P) dense or nnz tiles
        g = obj.cols_rmatvec(cols, r)        # (P,) coordinate gradients
        delta = obj.shooting_delta(x[idx], g, lam, beta)
        if p_eff is not None:                # sentinel backoff: mask, don't
            delta = delta * health.live_mask(P, p_eff)   # reshape (DESIGN §9)
        # Collective update Δx: scatter-add sums deltas of duplicate draws,
        # matching the multiset semantics of Alg. 2.
        x = x.at[idx].add(delta)
        z = obj.cols_matvec_add(cols, delta, z)
        return x, z, obj.objective_from_margin(z, x, prob)

    keys = jax.random.split(key, rounds)

    if guard is None:
        def round_fn(carry, key_t):
            x, z = carry
            x, z, f = update(x, z, _sample(key_t, d, P, replace), None)
            return (x, z), (f, jnp.sum(x != 0))

        (x, z), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0), keys)
        return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                      status=health.status_from_trace(fs))

    p_floor = max(1, min(guard.p_min, P))

    def round_fn(carry, key_t):
        x, z, gs = carry
        idx = _sample(key_t, d, P, replace)
        x_new, z_new, f_new = update(x, z, idx, gs.p_eff)
        x, z, f, gs, _ = health.apply_sentinel(
            gs, x_new, z_new, f_new, factor=guard.factor, p_floor=p_floor)
        return (x, z, gs), (f, jnp.sum(x != 0))

    f0 = obj.objective_from_margin(z0, x0, prob)
    gs0 = health.init_guard_state(x0, z0, f0, P)
    (x, z, gs), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0, gs0), keys)
    return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=health.status_from_trace(fs, gs.backoffs))


def shooting_solve(prob: Problem, key: jax.Array, rounds: int,
                   x0: jax.Array | None = None) -> Result:
    """Alg. 1: sequential SCD = Shotgun with P = 1."""
    return _shotgun_solve_core(prob, key, P=1, rounds=rounds, x0=x0)


# ---------------------------------------------------------------------------
# Theory-faithful duplicated-feature form (Eq. 4 / Alg. 2 verbatim)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("P", "rounds"))
def shotgun_dup_solve(dp: DupProblem, key: jax.Array, P: int, rounds: int,
                      xhat0: jax.Array | None = None) -> Result:
    """Alg. 2 on min_{x̂ >= 0} Σ L(â_i^T x̂) + λ Σ x̂_j with â = [a; -a].

    ∇F(x̂)_j = â_j^T r + λ  and  δx̂_j = max(-x̂_j, -(∇F)_j / β).
    """
    A, y, lam, beta = dp.A, dp.y, dp.lam, dp.beta
    n, d = A.shape
    d2 = 2 * d
    xhat0 = jnp.zeros(d2, A.dtype) if xhat0 is None else xhat0
    z0 = A @ (xhat0[:d] - xhat0[d:])

    def round_fn(carry, key_t):
        xhat, z = carry
        idx = jax.random.randint(key_t, (P,), 0, d2)   # multiset P_t
        r = obj.residual_like(z, y, dp.loss)
        sign = jnp.where(idx < d, 1.0, -1.0)            # column of [A, -A]
        Ap = A[:, idx % d] * sign[None, :]              # (n, P)
        g = Ap.T @ r + lam                              # (∇F)_j, Eq. 5 context
        delta = jnp.maximum(-xhat[idx], -g / beta)      # Eq. 5
        xhat_raw = xhat.at[idx].add(delta)
        # Parallel same-coordinate updates may overshoot below 0; the paper's
        # write-conflict note (end of Sec. 3.1) permits clipping to keep
        # x̂ >= 0 — a no-op unless the multiset collides.  Maintain z with one
        # scatter of the deltas plus the (clipped − unclipped) corrections
        # folded in; duplicate draws of a coordinate all see the same
        # correction, so divide by the draw multiplicity to apply it once.
        xhat = jnp.maximum(xhat_raw, 0.0)
        counts = jnp.zeros(d2, A.dtype).at[idx].add(1.0)
        corr = (xhat - xhat_raw)[idx] / counts[idx]
        z = z + Ap @ (delta + corr)                     # maintained Ax, O(n·P)
        f = obj.data_loss_from_margin(z, y, dp.loss) + lam * jnp.sum(xhat)
        nnz = jnp.sum(obj.dup_to_signed(xhat) != 0)
        return (xhat, z), (f, nnz)

    keys = jax.random.split(key, rounds)
    (xhat, z), (fs, nnzs) = jax.lax.scan(round_fn, (xhat0, z0), keys)
    return Result(x=xhat, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=health.status_from_trace(fs))


# ---------------------------------------------------------------------------
# Solver selection
# ---------------------------------------------------------------------------

SOLVER_NAMES = ("shooting", "shotgun", "shotgun_dup", "shotgun_cdn",
                "shooting_cdn", "block_fused", "sharded",
                "shotgun_logreg_fused", "sparse_logreg_fused")


def _loss_bound(fn, loss: str, family, require_sparse: bool = False):
    """Wrap a solver so it refuses problems built for a different loss
    (naming both, serve-layer convention) — and, for the sparse-only
    entries, refuses dense designs.  The check alone is the wrapper's
    ``admit(prob)``."""
    def admit(prob):
        if prob.loss != loss:
            raise ValueError(
                f"solver {family!r} is bound to loss {loss!r} but the "
                f"problem carries loss {prob.loss!r}")
        if require_sparse:
            from repro.data.sparse import BlockedCSC
            if not isinstance(prob.A, BlockedCSC):
                raise ValueError(
                    f"solver {family!r} needs a BlockedCSC design; got "
                    f"{type(prob.A).__name__}")

    @functools.wraps(fn)
    def solve(prob, *args, **kwargs):
        admit(prob)
        return fn(prob, *args, **kwargs)
    solve.admit = admit
    return solve


def get_solver(name):
    """Uniform entry point over every Shotgun-family solver.

    Returns the solve callable for ``name`` (see ``SOLVER_NAMES``):

      shooting / shotgun / shotgun_dup   this module (Alg. 1 / Alg. 2)
      shotgun_cdn / shooting_cdn         CDN inner-Newton variants
      block_fused                        Pallas Block-Shotgun, fused
                                         multi-round kernel
      sharded                            multi-device round-engine driver
                                         (pick the per-shard kernel with
                                         ``engine=`` from ``ENGINE_NAMES``,
                                         DESIGN §3)
      shotgun_logreg_fused               fused kernel bound to logistic loss
      sparse_logreg_fused                same, BlockedCSC designs only

    **Migration note (DESIGN §12):** ``name`` may also be a
    ``(family, loss)`` pair — e.g. ``("block_fused", "logistic")`` — which
    binds any family above to a loss with an admission check (a problem
    carrying a different loss raises ``ValueError`` naming both).  This is
    the forward-compatible spelling: ``SOLVER_NAMES`` stops growing one
    string per (family, loss) cross-product, and the two ``*_logreg_fused``
    strings are frozen aliases of ``("block_fused", "logistic")`` kept for
    existing configs.

    Kernel/sharded solvers are imported lazily: ``repro.kernels.ops`` and
    ``repro.core.sharded`` both import this module at load time.
    ``core.path.solve_path(solver=<name>)`` adapts any entry to the
    λ-continuation loop, warm starts included.
    """
    if isinstance(name, tuple):
        family, loss = name
        if loss not in obj.BETA:
            raise ValueError(
                f"unknown loss {loss!r}; choose from {tuple(obj.BETA)}")
        return _loss_bound(get_solver(family), loss, family)
    if name == "shotgun_logreg_fused":
        from repro.kernels import ops
        return _loss_bound(ops.block_shotgun_solve, obj.LOGISTIC, name)
    if name == "sparse_logreg_fused":
        from repro.kernels import ops
        return _loss_bound(ops.block_shotgun_solve, obj.LOGISTIC, name,
                           require_sparse=True)
    if name == "shooting":
        return shooting_solve
    if name == "shotgun":
        return shotgun_solve
    if name == "shotgun_dup":
        return shotgun_dup_solve
    if name in ("shotgun_cdn", "shooting_cdn"):
        from repro.core import cdn
        return {"shotgun_cdn": cdn.shotgun_cdn_solve,
                "shooting_cdn": cdn.shooting_cdn_solve}[name]
    if name == "block_fused":
        from repro.kernels import ops
        return ops.block_shotgun_solve
    if name == "sharded":
        from repro.core import sharded
        return sharded.shotgun_sharded_solve
    raise ValueError(f"unknown solver {name!r}; choose from {SOLVER_NAMES}")


# ---------------------------------------------------------------------------
# Convergence utilities
# ---------------------------------------------------------------------------

def rounds_to_tolerance(trace_objective, f_star, rel_tol=0.005):
    """First round index with F within rel_tol of F* (paper's 0.5% criterion).

    Returns len(trace) if never reached (incl. divergence).  Non-finite
    entries never count as hits: a -inf/NaN objective is divergence, not
    convergence (NaN compares false anyway; -inf needs the explicit check).
    """
    target = f_star + rel_tol * jnp.abs(f_star)
    t = jnp.asarray(trace_objective)
    hit = (t <= target) & jnp.isfinite(t)
    idx = jnp.argmax(hit)
    reached = jnp.any(hit)
    return jnp.where(reached, idx, t.shape[0])


def diverged(trace_objective) -> jax.Array:
    """True when the trace shows divergence ANYWHERE: any non-finite entry,
    or a final objective blown 1000x past the start.  Scanning the full
    trace matters — a NaN margin can round-trip to a finite-looking
    objective later (0·NaN masking), so trace[-1] alone under-reports."""
    t = jnp.asarray(trace_objective)
    return (jnp.any(jnp.isnan(t) | jnp.isinf(t))
            | (t[-1] > 1e3 * jnp.abs(t[0]) + 1e3))
