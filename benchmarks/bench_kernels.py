"""Kernel-layer microbenchmark (DESIGN §4.4): per-round cost of

  * the scalar Shotgun round it all replaces (P = K·128 gathered columns),
  * the fused multi-round kernel — ONE pallas_call per R rounds with z
    resident in VMEM, with and without the armed sentinel.

CPU interpret-mode timings; the TPU claims are structural (arithmetic
intensity O(block) vs O(1); one A-stream a round in the single-phase
fused kernel; launch/dispatch cost amortized R×).  Emits the repo-root
``BENCH_kernels.json`` perf-trajectory point.

Env: BENCH_SMOKE=1 shrinks to the small shape only (CI smoke).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, merge_root, time_us
from benchmarks.roofline import shotgun_round_model
from repro.core import objectives as obj
from repro.core.shotgun import shotgun_solve
from repro.data import synthetic as syn
from repro.kernels import ops
from repro.kernels.shotgun_block import (VMEM_BUDGET, auto_tile_n,
                                         fused_shotgun_rounds,
                                         fused_vmem_bytes)

ROUNDS_PER_LAUNCH = 8
K = 4


def run() -> list[dict]:
    shapes = [(1024, 2048)]
    if not os.environ.get("BENCH_SMOKE"):
        shapes.append((2048, 8192))
    rows = []
    for (n, d) in shapes:
        A, y, _ = syn.sparco(seed=0, n=n, d=d)
        prob = obj.make_problem(A, y, lam=0.5)
        Ap, yp, mask = ops.pad_problem(prob.A, prob.y)
        x = jnp.zeros(Ap.shape[1])
        z = jnp.zeros(Ap.shape[0])
        R = ROUNDS_PER_LAUNCH
        idx = (jnp.arange(R * K, dtype=jnp.int32).reshape(R, K)
               % (Ap.shape[1] // ops.BLOCK))

        # refuse configs the fused kernel could not compile on hardware —
        # interpret mode would happily "run" them and OOM much later
        # (shotgun-lint SL101 checks the same bound on the committed rows)
        np_, dp_ = Ap.shape
        vmem = fused_vmem_bytes(np_, dp_, K, tile_n=auto_tile_n(
            np_, ops.BLOCK, d=dp_))
        if vmem > VMEM_BUDGET:
            raise ValueError(
                f"fused config (n={np_}, d={dp_}, K={K}, R={R}) needs "
                f"{vmem} B of VMEM > {VMEM_BUDGET} B budget — shrink the "
                "bench shape or K")

        us_fused_launch = time_us(lambda: fused_shotgun_rounds(
            Ap, z, x, idx, prob.lam, prob.beta, yp, mask),
            reps=10)
        us_fused = us_fused_launch / R
        # sentinel-armed launch: dynamic k_eff/guard ride the scalar-prefetch
        # vector, health is one (1,1) VMEM scalar — overhead must stay ≤ 5%
        # of per-round wall (DESIGN §9 acceptance; tests/test_health.py)
        k_eff = jnp.int32(K)
        guard_f = jnp.float32(3.4e38)
        us_fused_g = time_us(lambda: fused_shotgun_rounds(
            Ap, z, x, idx, prob.lam, prob.beta, yp, mask,
            k_eff=k_eff, guard_f=guard_f), reps=10) / R
        # scalar Shotgun round with the same effective P = K*128
        us_scalar = time_us(lambda: shotgun_solve(
            prob, jax.random.PRNGKey(0), P=K * ops.BLOCK, rounds=1), reps=5)
        model = shotgun_round_model(Ap.shape[0], Ap.shape[1], K,
                                    block=ops.BLOCK)
        rows.append({
            "n": n, "d": d, "K": K, "P_eff": K * ops.BLOCK,
            "rounds_per_launch": R,
            "fused_round_us": round(us_fused, 1),
            "fused_round_guarded_us": round(us_fused_g, 1),
            "sentinel_overhead_pct": round(
                100.0 * (us_fused_g - us_fused) / us_fused, 2),
            "scalar_round_us": round(us_scalar, 1),
            "launches_per_round_fused": 1.0 / R,
            "hbm_bytes_per_round_fused": model["fused"]["bytes"],
            "flops_per_byte_fused": round(model["fused"]["intensity"], 3),
            "flops_per_byte_scalar": round(model["scalar"]["intensity"], 3),
        })
        print(f"kernels,n={n},d={d},K={K},fused_round={us_fused:.0f}us,"
              f"scalar_round={us_scalar:.0f}us", flush=True)
    emit(rows, "bench_kernels")
    if not os.environ.get("BENCH_SMOKE"):
        # full runs own the untagged rows of the committed perf trajectory
        merge_root(rows, tag=None)
    return rows


if __name__ == "__main__":
    run()
