"""Solver-serving microbenchmark (DESIGN §11.5): steady-state solves/sec
of the continuous-batched solver service vs. the repo's previous way of
serving the same stream — one fixed-budget ``block_shotgun_solve`` at a
time.  Modeled on the LM decode microbenchmark pattern (steady-state
throughput after a warm-up pass; per-slot occupancy reported alongside).

Three numbers, one committed row (``bench: "serve"``):

  * ``speedup_serve_vs_sequential`` — the headline: served throughput
    over the one-at-a-time fixed-budget baseline.  Wins compound from
    (a) batching S slots into one launch, (b) launch-boundary early
    exit + immediate refill, (c) warm-cache hits on repeat traffic.
  * ``speedup_serve_vs_sequential_early`` — honest secondary: the same
    stream through a 1-slot service (early stop + its own cache), so
    only the batching win remains.
  * ``warm_rounds_frac_of_cold`` — rounds the repeated (problem_id, λ)
    solves spent as a fraction of their cold counterparts (acceptance:
    ≤ 0.5, i.e. a warm hit skips at least half the cold rounds).

Interpret-mode caveat (DESIGN §11.5): these are CPU interpret-mode
timings — per-launch cost is dominated by the interpreter, so the
batching term underestimates hardware (where slot-stacking amortizes
fixed launch/dispatch cost); the refill/warm-start terms carry over.

Env: BENCH_SMOKE=1 shrinks the stream (CI smoke; no artifact merge).
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import emit, merge_root
from repro.core.batched import WarmStartCache, batch_meta_of
from repro.kernels import ops
from repro.kernels.shotgun_block import (VMEM_BUDGET, auto_tile_n,
                                         fused_vmem_bytes)
from repro.launch.solver_serve import (SolverService, make_stream,
                                       solve_queue_sequential)

N, D = 256, 512
K = 1
SLOTS = 4
MAX_ROUNDS = 128
R = 8
TOL = 1e-4
LAM = 4.0


def _check_vmem(meta, slots):
    """Refuse configs the stacked fused kernel could not hold in VMEM on
    hardware — interpret mode would happily "run" them (SL101 checks the
    same ``slots``-scaled bound on the committed rows)."""
    tile_n = auto_tile_n(meta.n_pad, meta.block, d=meta.d_pad)
    vmem = fused_vmem_bytes(meta.n_pad, meta.d_pad, K, tile_n=tile_n,
                            slots=slots)
    if vmem > VMEM_BUDGET:
        raise ValueError(
            f"serve config (n={meta.n_pad}, d={meta.d_pad}, K={K}, "
            f"slots={slots}) needs {vmem} B of VMEM > {VMEM_BUDGET} B "
            "budget — shrink the shape, K, or slots")
    return vmem


def _serve_once(reqs, slots, cache=None):
    svc = SolverService(batch_meta_of(reqs[0].prob), slots=slots, K=K,
                        max_rounds=MAX_ROUNDS, rounds_per_launch=R,
                        tol=TOL, cache=cache)
    t0 = time.time()
    done = svc.serve(reqs)
    return svc, done, time.time() - t0


def run() -> list[dict]:
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    requests = 8 if smoke else 12
    repeat_frac = 0.5
    meta = batch_meta_of(make_stream(N, D, requests=1, lam=LAM)[0].prob)
    vmem = _check_vmem(meta, SLOTS)

    # warm-up pass: compile the batched (S=SLOTS and S=1) and standalone
    # jaxprs so the timed passes measure steady-state serving, not tracing
    warm = make_stream(N, D, requests=2, lam=LAM, seed=7)
    _serve_once(warm, SLOTS)
    _serve_once(make_stream(N, D, requests=1, lam=LAM, seed=7), 1)
    wu = make_stream(N, D, requests=1, lam=LAM, seed=7)[0]
    jax.block_until_ready(ops.block_shotgun_solve(
        wu.prob, wu.key, K, MAX_ROUNDS, rounds_per_launch=R).x)

    stream = lambda seed: make_stream(N, D, requests=requests,
                                      repeat_frac=repeat_frac, lam=LAM,
                                      seed=seed)

    svc, done, dt_serve = _serve_once(stream(0), SLOTS)
    solves_serve = len(done) / dt_serve

    # baseline 1: the repo's previous serving story — one fixed-budget
    # fused solve at a time, no early stop, no cache
    seq_reqs = stream(0)
    t0 = time.time()
    for rq in seq_reqs:
        jax.block_until_ready(ops.block_shotgun_solve(
            rq.prob, rq.key, K, MAX_ROUNDS, rounds_per_launch=R).x)
    dt_seq = time.time() - t0
    solves_seq = len(seq_reqs) / dt_seq

    # baseline 2 (honest secondary): same early stop + warm cache, but one
    # slot — isolates the batching term
    t0 = time.time()
    solve_queue_sequential(stream(0), K=K, max_rounds=MAX_ROUNDS,
                           rounds_per_launch=R, tol=TOL,
                           cache=WarmStartCache())
    dt_seq_early = time.time() - t0
    solves_seq_early = requests / dt_seq_early

    by_rid = {rq.rid: rq for rq in done}
    n_unique = max(1, int(round(requests * (1.0 - repeat_frac))))
    cold = [by_rid[i].rounds_used for i in range(n_unique)]
    warm_r = [by_rid[i].rounds_used for i in range(n_unique, requests)]
    warm_frac = (sum(warm_r) / max(1, sum(cold))) if warm_r else None

    row = {
        "bench": "serve", "n": N, "d": D, "K": K, "slots": SLOTS,
        "rounds_per_launch": R, "max_rounds": MAX_ROUNDS,
        "requests": requests, "repeat_frac": repeat_frac, "tol": TOL,
        "fused_vmem_bytes_stacked": vmem,
        "solves_per_sec_serve": round(solves_serve, 3),
        "solves_per_sec_sequential": round(solves_seq, 3),
        "solves_per_sec_sequential_early": round(solves_seq_early, 3),
        "speedup_serve_vs_sequential": round(solves_serve / solves_seq, 2),
        "speedup_serve_vs_sequential_early": round(
            solves_serve / solves_seq_early, 2),
        "slot_occupancy": round(svc.slot_occupancy, 3),
        "launches_serve": svc.launch_count,
        "warm_rounds_frac_of_cold": (round(warm_frac, 3)
                                     if warm_frac is not None else None),
        "cache_hits_exact": svc.cache.stats.hits_exact,
        "cache_hits_near": svc.cache.stats.hits_near,
        "cache_misses": svc.cache.stats.misses,
        "statuses": sorted({rq.status for rq in done}),
    }
    print(f"serve,n={N},d={D},slots={SLOTS},K={K},"
          f"serve={solves_serve:.2f}/s,seq={solves_seq:.2f}/s,"
          f"speedup={row['speedup_serve_vs_sequential']}x,"
          f"occupancy={row['slot_occupancy']},"
          f"warm_frac={row['warm_rounds_frac_of_cold']}", flush=True)
    rows = [row]
    emit(rows, "bench_serve")
    if not smoke:
        merge_root(rows, tag="serve")
    return rows


if __name__ == "__main__":
    run()
