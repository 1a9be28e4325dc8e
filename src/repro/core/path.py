"""Pathwise optimization (Sec. 4.1.1, after Friedman et al. 2010).

Rather than solving directly at the target lambda, solve along an
exponentially decreasing sequence lam_1 > lam_2 > ... > lam_target,
warm-starting each solve from the previous solution.  lam_1 is chosen
just below lambda_max = ||A^T dL/dz(0)||_inf (above which x* = 0).

``solve_path`` runs on any ``SOLVER_NAMES`` entry (``core.get_solver``):
pass ``solver="block_fused"`` / ``"sharded"`` / ... and the per-λ solves
ride the Pallas or distributed paths, warm-started through their ``x0``
support.

The path is traced in the profiler's own trace (``jax.profiler``; nothing
is recorded unless a trace is running): host spans ``shotgun.path``,
``shotgun.path.p_star``, ``shotgun.path.lambda_max``,
``shotgun.path.lambda`` (``lam=i``), ``shotgun.path.chunk`` (``chunk=c``)
and ``shotgun.path.sync`` around every device→host read, which
``PathResult.syncs`` counts; the per-λ objective's device ops run under
the scope ``shotgun.path.objective``.

The host reads from the chip only where a decision needs a value: one
read a chunk carries all that chunk's values, and x stays on the chip
from λ to λ unless the cache hands back an x this path did not write
(``PathResult.syncs``, ``PathResult.uploads``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import objectives as obj
from repro.core import shotgun
from repro.core.spec import SolverSpec, reject_legacy_kwargs


class PathResult(NamedTuple):
    x: jax.Array                  # solution at the target lambda
    lambdas: np.ndarray           # the continuation sequence
    objectives: np.ndarray        # final objective at each lambda
    nnz: np.ndarray               # sparsity along the path
    rounds: np.ndarray | None = None   # rounds spent per lambda (cache= only)
    # device→host reads: P* (validate_p), λ_max, then one a chunk with a
    # cache (its F and nnz traces, its x, and F(x0) on a λ's first chunk),
    # or one at the end without (every λ's traces)
    syncs: int | None = None
    # cache hits whose x0 went to the device: entries this path did not
    # write (a hit on the x it last put keeps the device's copy)
    uploads: int | None = None


class _Reads:
    """The path's device→host reads: each one runs inside a
    ``shotgun.path.sync`` span and is counted, at the same boundary."""

    def __init__(self):
        self.count = 0

    def __call__(self, read: Callable, value):
        self.count += 1
        with jax.profiler.TraceAnnotation("shotgun.path.sync"):
            return read(value)


@jax.jit
def _path_objective(x, prob: obj.Problem) -> jax.Array:
    """F(x) at one λ of the path, as one program under its own scope."""
    with jax.named_scope("shotgun.path.objective"):
        return obj.objective(x, prob)


def lambda_sequence(lam_max: float, lam_target: float, num: int = 10) -> np.ndarray:
    """Geometric sequence from just-below lam_max down to lam_target."""
    lam_max = float(lam_max)
    lam_target = float(lam_target)
    if lam_target >= lam_max:
        return np.array([lam_target])
    start = 0.95 * lam_max
    return np.geomspace(start, lam_target, num)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1


def _solver_by_name(name: str, **solver_kwargs) -> Callable:
    """Adapt any ``SOLVER_NAMES`` entry to the uniform path signature
    ``(prob, key, P, rounds, x0) -> Result`` (warm start threaded through).

    ``P`` maps onto each family's parallelism knob: the per-round update
    count for the scalar solvers, K = ceil(P / 128) blocks for the Pallas
    solvers, and P_local for the sharded driver.  ``solver_kwargs`` pass
    through (e.g. ``engine=``, ``mesh=``, ``tile_n=``).
    """
    solve = shotgun.get_solver(name)
    # (family, loss) pairs and the frozen *_logreg_fused aliases adapt like
    # their base family; the loss admission check rides inside ``solve``.
    family = name[0] if isinstance(name, tuple) else name
    if family in ("shotgun_logreg_fused", "sparse_logreg_fused"):
        family = "block_fused"

    if family in ("shooting", "shooting_cdn"):
        return lambda p, k, P, r, x0: solve(p, k, rounds=r, x0=x0,
                                            **solver_kwargs)
    if family in ("shotgun", "shotgun_cdn"):
        return lambda p, k, P, r, x0: solve(p, k, P=P, rounds=r, x0=x0,
                                            **solver_kwargs)
    if family == "shotgun_dup":
        def run_dup(p, k, P, r, x0):
            dp = obj.dup_from(p)
            xhat0 = (None if x0 is None else
                     jnp.concatenate([jnp.maximum(x0, 0.0),
                                      jnp.maximum(-x0, 0.0)]))
            res = solve(dp, k, P=P, rounds=r, xhat0=xhat0, **solver_kwargs)
            return res._replace(x=obj.dup_to_signed(res.x))
        return run_dup
    if family == "block_fused":
        def run_block(p, k, P, r, x0):
            from repro.kernels.shotgun_block import BLOCK
            kw = dict(solver_kwargs)
            K = kw.pop("K", max(1, -(-P // BLOCK)))
            return solve(p, k, K=K, rounds=r, x0=x0, **kw)
        return run_block
    if family == "sharded":
        def run_sharded(p, k, P, r, x0):
            kw = dict(solver_kwargs)
            if kw.get("engine") == "fused":
                # the fused engine takes its parallelism as K blocks of 128
                # per shard, not P_local
                from repro.kernels.shotgun_block import BLOCK
                kw.setdefault("K", max(1, -(-P // BLOCK)))
            return solve(p, k, P_local=P, rounds=r, x0=x0, **kw)
        return run_sharded
    raise ValueError(f"no path adapter for solver {name!r}")


@functools.partial(jax.profiler.annotate_function, name="shotgun.path")
def solve_path(prob: obj.Problem, key: jax.Array, lam_target: float,
               P: int | None = None, rounds_per_lambda: int | None = None,
               num_lambdas: int = 10,
               solver: str | Callable | None = None, validate_p: bool = True,
               cache=None, problem_id=None, tol: float = 1e-4,
               spec: SolverSpec | None = None,
               **solver_kwargs) -> PathResult:
    """Warm-started lambda-continuation wrapper around any shotgun-family
    solver.

    ``solver`` is a ``SOLVER_NAMES`` entry or a ``(family, loss)`` pair
    (adapted automatically, warm starts included) or a callable
    ``solver(prob, key, P, rounds, x0) -> shotgun.Result``.

    ``validate_p`` checks the requested ``P`` against the paper's safe
    parallelism ``spectral.p_star(A)`` (Thm 3.2) before the continuation
    loop and clamps with a warning — a diverging per-λ solve would poison
    every later warm start, so the path driver refuses to start beyond P*
    rather than relying on downstream recovery (DESIGN §9).

    ``cache`` (a ``core.batched.WarmStartCache``, DESIGN §11.4) plugs the
    sweep into the same warm-start store the solver service uses: each λ
    point reads ``cache.get(problem_id, λ)`` (exact hit, else nearest-λ —
    which naturally returns the previous sweep point) before falling back
    to in-sweep continuation, writes its solution back, and early-stops on
    a ``tol``-flat chunk of rounds — so a SECOND sweep over the same
    (problem_id, λ grid) converges in strictly fewer total rounds (tested).
    With a cache the per-λ budget becomes a cap, not a fixed spend, and
    ``PathResult.rounds`` reports the actual rounds per λ; ``cache=None``
    (the default) keeps the fixed-budget behavior and key schedule
    bit-for-bit.

    Reads: with a cache, each chunk is one read of its F and nnz traces
    and its x, and a λ's first chunk also carries F(x0), dispatched just
    before it; the λ's F, nnz and cached x are the last chunk's.  A cache
    hit on the x this path last put keeps the device x (the same values);
    any other hit uploads x0 and counts in ``PathResult.uploads``.
    Without a cache the path reads once, at its end.

    ``spec=SolverSpec(...)`` is the canonical interface (DESIGN §12): P =
    spec.P, rounds_per_lambda = spec.rounds, with ``spec.loss`` validated
    against ``prob.loss``.  The legacy (P, rounds_per_lambda) kwargs still
    work but emit a ``DeprecationWarning``.
    """
    if spec is not None:
        reject_legacy_kwargs(spec, P=P, rounds_per_lambda=rounds_per_lambda)
        spec.check_loss(prob.loss)
        P, rounds_per_lambda = spec.P, spec.rounds
    else:
        if P is not None or rounds_per_lambda is not None:
            import warnings
            warnings.warn(
                "solve_path(P=..., rounds_per_lambda=...) kwargs are "
                "deprecated; pass spec=SolverSpec(...)", DeprecationWarning,
                stacklevel=3)
        P = 8 if P is None else P
        rounds_per_lambda = 200 if rounds_per_lambda is None else rounds_per_lambda
    read = _Reads()
    if validate_p:
        from repro.core import spectral
        with jax.profiler.TraceAnnotation("shotgun.path.p_star"):
            ps = read(int, spectral.p_star_array(prob.A))
        if P > ps:
            import warnings
            warnings.warn(
                f"solve_path: P={P} exceeds the Thm 3.2 safe parallelism "
                f"P*={ps} for this design; clamping to P*={ps} "
                f"(pass validate_p=False to override)", stacklevel=3)
            P = ps
    if isinstance(solver, (str, tuple)):
        solver = _solver_by_name(solver, **solver_kwargs)
    elif solver_kwargs:
        raise ValueError(
            f"solver_kwargs {sorted(solver_kwargs)} are only forwarded when "
            f"``solver`` is a registry name; got solver={solver!r}")
    elif solver is None:
        solver = lambda p, k, P, rounds, x0: shotgun.shotgun_solve(p, k, P=P, rounds=rounds, x0=x0)
    with jax.profiler.TraceAnnotation("shotgun.path.lambda_max"):
        lmax = read(float, obj.lambda_max(prob.A, prob.y, prob.loss))
    lams = lambda_sequence(lmax, lam_target, num_lambdas)
    dt = prob.A.dtype if hasattr(prob.A, "dtype") else jnp.float32
    x = jnp.zeros(prob.d, dt)
    if cache is None:
        traces = []
        for i, lam in enumerate(lams):
            with jax.profiler.TraceAnnotation("shotgun.path.lambda", lam=i):
                key, sub = jax.random.split(key)
                p_i = prob._replace(lam=jnp.float32(lam))
                res = solver(p_i, sub, P, rounds_per_lambda, x)
                x = res.x
                traces.append((res.trace.objective, res.trace.nnz))
        fs, ks = zip(*read(jax.device_get, traces))
        return PathResult(x=x, lambdas=lams,
                          objectives=np.array([float(f[-1]) for f in fs]),
                          nnz=np.array([int(k[-1]) for k in ks]),
                          syncs=read.count, uploads=0)

    from repro.core.batched import launch_converged
    pid = "path" if problem_id is None else problem_id
    chunk = _largest_divisor_leq(rounds_per_lambda, 8)
    objs, nnzs, rounds_used = [], [], []
    uploads, x_host = 0, None    # x_host: the last chunk's x, as cached
    for i, lam in enumerate(lams):
        with jax.profiler.TraceAnnotation("shotgun.path.lambda", lam=i):
            p_i = prob._replace(lam=jnp.float32(lam))
            x0, kind = cache.get(pid, float(lam), loss=prob.loss)
            if kind != "miss" and x0 is not x_host:
                x = jnp.asarray(x0, dt)      # cache hit beats in-sweep x
                uploads += 1
            # F(x0) stays on the device and is read with the first chunk
            f_prev = _path_objective(x, p_i)
            spent = 0
            while spent < rounds_per_lambda:
                with jax.profiler.TraceAnnotation("shotgun.path.chunk",
                                                  chunk=spent // chunk):
                    key, sub = jax.random.split(key)
                    res = solver(p_i, sub, P, chunk, x)
                    x = res.x
                    spent += chunk
                    f_prev, f_chunk, nnz_chunk, x_host = read(
                        jax.device_get,
                        (f_prev, res.trace.objective, res.trace.nnz, x))
                    if launch_converged(f_prev, f_chunk, tol):
                        break
                    f_prev = float(f_chunk[-1])
            cache.put(pid, float(lam), x_host, loss=prob.loss)
            rounds_used.append(spent)
            objs.append(float(f_chunk[-1]))
            nnzs.append(int(nnz_chunk[-1]))
    return PathResult(x=x, lambdas=lams, objectives=np.array(objs),
                      nnz=np.array(nnzs), rounds=np.array(rounds_used),
                      syncs=read.count, uploads=uploads)
