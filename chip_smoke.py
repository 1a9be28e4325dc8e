"""Chip smoke test: the dense fused Shotgun solve, end to end on a TPU.

    python3 chip_smoke.py             # phases (a)-(d), one chip
    python3 chip_smoke.py --chips 4   # only the feature-sharded solve, 4 chips

Drives the solver through the entry points a user calls -- ``SolverSpec``
into ``get_solver``, ``solve_path`` and ``SolverService`` (with
``--chips 4``, ``shotgun_sharded_solve``) -- at real sizes on data made
from seeds, and checks each answer against a plain float32 reference:

  (a) dense Lasso, Sparco category, n=4096, d=65536 (A f32 = 1 GiB),
      lambda = 0.1 lambda_max, P at or below P*: the first launch's
      objective trace against the ``kernels/ref.py`` oracle for the same
      block draws, and the final F against F* from FISTA.
  (b) dense n >> d logistic with per-block Newton, n=16384, d=2048, cut
      from zeta's 500,000 x 2,000 because the (n, 1) vectors resident in
      VMEM cap n: final F against F* from a long scalar Shotgun run.
  (c) ``solve_path`` over 10 warm-started lambdas down to (a)'s lambda:
      the last F against (a)'s F*.
  (d) ``SolverService``: 8 ``make_stream`` requests (n=2048, d=16384) on
      4 slots against ``solve_queue_sequential``.
  --chips 4: ``shotgun_sharded_solve(engine="fused", merge="round")`` on
      a 4-device ("f",) mesh, n=4096, d=262144 (A f32 = 4 GiB, 1 GiB per
      chip), against the one-chip fused solve of the same problem; both
      against F* from FISTA.  Prints the device of every shard of A.

References run under ``jax.default_matmul_precision("highest")``: on a
TPU the default would round f32 matmul operands to bf16.

Runs in one process and starts none.  Prints the device first and exits
non-zero unless JAX's platform is "tpu".  Each phase prints one line with
what it compared, the tolerance, compile seconds (first call less a
second, cached call) and solve seconds (the cached call): bring-up
timings, not benchmark numbers.  A failed check raises and is not caught.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax

ROOT = pathlib.Path(__file__).resolve().parent

REL_TOL = 0.005      # the repo's criterion (shotgun.rounds_to_tolerance)
ORACLE_RTOL = 1e-4   # kernel vs ref.py objective trace, f32 (phase_lasso)
R_LAUNCH = 8         # fused rounds per launch
SERVE_TOL = 1e-4     # SolverService launch-boundary convergence tolerance


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _timed(fn):
    """(result, compile_s, solve_s): a first call (compile + run) and a
    second, cached call, each waited for with ``block_until_ready``."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return out, max(0.0, (t1 - t0) - (t2 - t1)), t2 - t1


def _report(phase: str, compared: str, tol: str, compile_s: float,
            solve_s: float, **extra) -> None:
    more = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[{phase}] {compared} | tol {tol} | compile_s={compile_s:.3f} "
          f"solve_s={solve_s:.3f} {more}".rstrip(), flush=True)


def _rel(f, ref) -> float:
    return abs(float(f) - float(ref)) / abs(float(ref))


def _lasso_problem(n, d, lam_frac, seed=0):
    import jax.numpy as jnp
    from repro.core import objectives as obj
    from repro.data import synthetic as syn
    A, y, _ = syn.sparco(seed=seed, n=n, d=d)
    prob = obj.make_problem(A, y, lam=1.0)
    lmax = float(obj.lambda_max(prob.A, prob.y, prob.loss))
    return prob._replace(lam=jnp.float32(lam_frac * lmax))


def _block_parallelism(A) -> int:
    """P for the block solvers: whole 128-blocks at or below P*."""
    from repro.core import spectral
    from repro.kernels.shotgun_block import BLOCK
    p_star = spectral.p_star(A)
    _require(p_star >= BLOCK, f"P*={p_star} is below one {BLOCK}-block")
    return p_star // BLOCK * BLOCK


def _fista_fstar(prob, iters):
    """F* from FISTA (``core/baselines/fista.py``) in full f32; (F*, s)."""
    from repro.core.baselines import fista
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        f = float(fista.fista_solve(prob, iters).objective[-1])
    return f, time.perf_counter() - t0


def phase_lasso(n=4096, d=65536, lam_frac=0.1, rounds=1600,
                fista_iters=4000):
    """(a): returns (problem, P, F*) for phase (c)."""
    import jax.numpy as jnp
    from repro.core import SolverSpec, get_solver, rounds_to_tolerance
    from repro.kernels import ref
    from repro.kernels.batched import batched_draw_blocks
    from repro.kernels.shotgun_block import BLOCK

    prob = _lasso_problem(n, d, lam_frac)
    P = _block_parallelism(prob.A)
    spec = SolverSpec(loss="lasso", P=P, rounds=rounds)
    key = jax.random.PRNGKey(0)
    solve = get_solver("block_fused")
    res, c_s, s_s = _timed(lambda: solve(prob, key, spec=spec,
                                         rounds_per_launch=R_LAUNCH))
    trace = res.trace.objective

    # Same draws as the solver's first launch, replayed through the
    # pure-jnp multi-round oracle.  Both accumulate in f32 over n samples
    # and K*128 coordinates in different orders; over R rounds the
    # objectives agree to ~1e-5 relative, so 1e-4 leaves room without
    # hiding a wrong update (one wrong block moves F by far more).
    keys = jax.random.split(key, rounds).reshape(rounds // R_LAUNCH,
                                                 R_LAUNCH, -1)[0]
    idx = batched_draw_blocks(keys[None], P // BLOCK, d // BLOCK)[0]
    with jax.default_matmul_precision("highest"):
        _, _, f_ref, _ = ref.fused_shotgun_rounds_ref(
            prob.A, jnp.zeros(n), jnp.zeros(d), idx, prob.lam, prob.beta,
            prob.y, jnp.ones(n), "lasso", BLOCK)
    oracle_err = max(_rel(a, b) for a, b in zip(trace[:R_LAUNCH], f_ref))
    _require(oracle_err <= ORACLE_RTOL,
             f"(a) first-launch trace vs ref.py: rel err {oracle_err:.3e}")

    fstar, ref_s = _fista_fstar(prob, fista_iters)
    f_end = float(trace[-1])
    hit = int(rounds_to_tolerance(trace, fstar, REL_TOL))
    _require(hit < rounds and _rel(f_end, fstar) <= REL_TOL,
             f"(a) F={f_end} not within {REL_TOL} of F*={fstar}")
    _report("a lasso", f"fused F vs FISTA F* ({fista_iters} it); first "
            f"{R_LAUNCH}-round trace vs ref.py oracle",
            f"{REL_TOL} rel / oracle {ORACLE_RTOL} rel", c_s, s_s,
            n=n, d=d, P=P, rounds=rounds, F=f_end, Fstar=fstar,
            rel=f"{_rel(f_end, fstar):.2e}", rounds_to_tol=hit,
            oracle_rel=f"{oracle_err:.2e}", ref_s=f"{ref_s:.3f}")
    return prob, P, fstar


def phase_logistic(n=16384, d=2048, lam_frac=0.05, rounds=160,
                   ref_rounds=6000):
    """(b): per-block Newton against a long scalar Shotgun run."""
    import jax.numpy as jnp
    from repro.core import SolverSpec, get_solver, shotgun_solve
    from repro.core import objectives as obj
    from repro.data import synthetic as syn

    A, y, _ = syn.logistic_data(seed=0, n=n, d=d)
    prob = obj.make_problem(A, y, lam=1.0, loss="logistic")
    lmax = float(obj.lambda_max(prob.A, prob.y, prob.loss))
    prob = prob._replace(lam=jnp.float32(lam_frac * lmax))
    P = _block_parallelism(prob.A)
    key = jax.random.PRNGKey(0)
    spec = SolverSpec(loss="logistic", P=P, rounds=rounds, newton=True)
    solve = get_solver("block_fused")
    res, c_s, s_s = _timed(lambda: solve(prob, key, spec=spec,
                                         rounds_per_launch=R_LAUNCH))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref_res = shotgun_solve(prob, jax.random.PRNGKey(1), spec=SolverSpec(
            loss="logistic", P=P, rounds=ref_rounds))
        fstar = float(ref_res.trace.objective[-1])
    ref_s = time.perf_counter() - t0
    f_end = float(res.trace.objective[-1])
    _require(_rel(f_end, fstar) <= REL_TOL,
             f"(b) F={f_end} not within {REL_TOL} of scalar F*={fstar}")
    _report("b logistic newton", f"fused Newton F vs scalar Shotgun F* "
            f"({ref_rounds} rounds, P={P})", f"{REL_TOL} rel", c_s, s_s,
            n=n, d=d, P=P, rounds=rounds, F=f_end, Fstar=fstar,
            rel=f"{_rel(f_end, fstar):.2e}", ref_s=f"{ref_s:.3f}")


def phase_path(prob, P, fstar, num_lambdas=10, rounds_per_lambda=400):
    """(c): warm-started lambda path down to (a)'s lambda."""
    from repro.core import SolverSpec, solve_path
    spec = SolverSpec(loss="lasso", P=P, rounds=rounds_per_lambda)
    key = jax.random.PRNGKey(0)
    path, c_s, s_s = _timed(lambda: solve_path(
        prob, key, lam_target=float(prob.lam), num_lambdas=num_lambdas,
        solver="block_fused", spec=spec))
    f_last = float(path.objectives[-1])
    _require(_rel(f_last, fstar) <= REL_TOL,
             f"(c) path F={f_last} not within {REL_TOL} of F*={fstar}")
    _report("c path", f"last of {num_lambdas} path F vs (a) F*",
            f"{REL_TOL} rel", c_s, s_s, rounds_per_lambda=rounds_per_lambda,
            F=f_last, Fstar=fstar, rel=f"{_rel(f_last, fstar):.2e}")


def phase_serve(n=2048, d=16384, requests=8, slots=4, K=8, max_rounds=1024,
                lam=30.0):
    """(d): batched service answers against one-at-a-time answers.

    ``lam`` is about 0.09 lambda_max of these designs (336 for seed 0) and
    K*128 = 1024 sits under their P* (1127); the budget lets solves stop
    early, so finished slots are refilled mid-stream.

    Every request gets its own design (``num_designs=requests``), so no
    request can warm-start from another on either side and the two runs
    solve the same problems from the same keys.  A slot of the batched
    launch runs the same kernel body as the 1-slot launch, but XLA may
    reduce the (S, n) objective in another order, which can move one
    launch-boundary stop by one launch; a stopped solve moved less than
    ``SERVE_TOL`` relative over its last launch, so the answers must agree
    within 2 * SERVE_TOL relative in F.
    """
    from repro.core.batched import batch_meta_of
    from repro.launch.solver_serve import (SolverService, make_stream,
                                           solve_queue_sequential)
    stream = make_stream(n, d, requests=requests, num_designs=requests,
                         lam=lam, seed=0)
    kw = dict(K=K, max_rounds=max_rounds, rounds_per_launch=R_LAUNCH,
              tol=SERVE_TOL)
    fresh = lambda: [dataclasses.replace(r) for r in stream]
    meta = batch_meta_of(stream[0].prob)
    served, c_s, s_s = _timed(
        lambda: SolverService(meta, slots=slots, **kw).serve(fresh()))
    seq = solve_queue_sequential(fresh(), **kw)
    by_rid = {r.rid: r for r in seq}
    worst, bitwise = 0.0, True
    for r in served:
        s = by_rid[r.rid]
        _require(r.status == s.status == "ok",
                 f"(d) request {r.rid}: status {r.status} vs {s.status}")
        worst = max(worst, _rel(r.f_final, s.f_final))
        bitwise &= bool((r.x == s.x).all()) and r.f_final == s.f_final
    _require(worst <= 2 * SERVE_TOL,
             f"(d) served vs sequential F: rel {worst:.3e}")
    _report("d serve", f"{requests} served answers vs "
            f"solve_queue_sequential", f"{2 * SERVE_TOL} rel in F", c_s, s_s,
            n=n, d=d, slots=slots, K=K, worst_rel=f"{worst:.2e}",
            bitwise_equal=bitwise,
            rounds_used=sorted(r.rounds_used for r in served))


def phase_sharded(n=4096, d=262144, lam_frac=0.1, rounds=4000,
                  fista_iters=4000, chips=4):
    """--chips 4: feature-sharded fused solve vs the one-chip fused solve."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (SolverSpec, get_solver, rounds_to_tolerance,
                            shotgun_sharded_solve)
    from repro.core.sharded import make_feature_mesh
    from repro.kernels.shotgun_block import BLOCK

    devices = jax.devices()[:chips]
    _require(len(devices) == chips, f"need {chips} devices, have "
             f"{len(jax.devices())}")
    mesh = make_feature_mesh(devices)
    prob = _lasso_problem(n, d, lam_frac)
    K_total = _block_parallelism(prob.A) // BLOCK
    K = max(1, K_total // chips)
    key = jax.random.PRNGKey(0)
    sharded = prob._replace(A=jax.device_put(
        prob.A, NamedSharding(mesh, P(None, "f"))))
    shards = sorted((s.device.id, s.index[1].start, s.data.shape)
                    for s in sharded.A.addressable_shards)
    print(f"[sharded] A shards (device id, first column, shape): {shards}",
          flush=True)
    _require(len({dev for dev, _, _ in shards}) == chips,
             "A's shards do not span every chip of the mesh")
    res, c_s, s_s = _timed(lambda: shotgun_sharded_solve(
        sharded, key, mesh=mesh, engine="fused", K=K,
        spec=SolverSpec(loss="lasso", P=K * BLOCK, rounds=rounds,
                        merge="round")))
    one, c1, s1 = _timed(lambda: get_solver("block_fused")(
        prob, key, spec=SolverSpec(loss="lasso", P=chips * K * BLOCK,
                                   rounds=rounds),
        rounds_per_launch=R_LAUNCH))
    fstar, ref_s = _fista_fstar(sharded, fista_iters)   # XLA splits it
    f_sh = float(res.trace.objective[-1])
    f_one = float(one.trace.objective[-1])
    hits = [int(rounds_to_tolerance(r.trace.objective, fstar, REL_TOL))
            for r in (res, one)]
    _require(_rel(f_sh, fstar) <= REL_TOL and _rel(f_one, fstar) <= REL_TOL,
             f"(sharded) F={f_sh} / one-chip F={f_one} not within {REL_TOL} "
             f"of F*={fstar} (rounds to tolerance {hits})")
    _report("sharded 4-chip", f"sharded fused F and one-chip fused F vs "
            f"FISTA F* ({fista_iters} it)", f"{REL_TOL} rel", c_s, s_s,
            n=n, d=d, K_per_chip=K, rounds=rounds, F_sharded=f_sh,
            F_one_chip=f_one, Fstar=fstar, rounds_to_tol=hits,
            one_chip_compile_s=f"{c1:.3f}",
            one_chip_solve_s=f"{s1:.3f}", ref_s=f"{ref_s:.3f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the feature-sharded solve")
    args = ap.parse_args(argv)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's platform is "
                 f"{device['platform']!r}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_sharded()
    else:
        prob, P, fstar = phase_lasso()
        phase_logistic()
        phase_path(prob, P, fstar)
        phase_serve()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
