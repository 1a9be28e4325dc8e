"""Dense vs blocked-CSC Shotgun benchmark (DESIGN §8): wall time and HBM
traffic of the data paths on the paper's Large-Sparse category at
n=2048, d=16384, density=0.002 — the shape whose dense form is what makes
``large_sparse`` memory-bound before the solver starts — plus one larger
sparse-only point at d=65536 where the dense design (512 MB) is no longer
worth materializing.

Comparisons per shape:

  * scalar Shotgun round (P = K·128 sampled coordinates): dense column
    gather A[:, idx] vs the O(tile·P) nnz-tile pack;
  * fused multi-round rounds (R rounds per launch, margin in VMEM):
    streamed (n × 128) dense blocks in the §4.2 kernel vs the
    (tile × 128) rows/vals tiles of the §8.3 kernel
    (``kernels/shotgun_sparse.py``), reported as
    ``speedup_fused_sparse_vs_dense_fused``.

Interpret-mode timings (CPU container), not device numbers; the analytic
HBM model (``roofline.sparse_round_model``) carries the TPU claim.
Appends rows tagged ``"bench": "sparse"`` to the repo-root
``BENCH_kernels.json`` on full runs; BENCH_SMOKE=1 shrinks the shape
(still exercising the fused-sparse config) and leaves the artifact
alone.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, merge_root, time_us
from benchmarks.roofline import sparse_round_model
from repro.core import objectives as obj
from repro.core.shotgun import shotgun_solve
from repro.data import synthetic as syn
from repro.kernels import ops
from repro.kernels.shotgun_block import VMEM_BUDGET, fused_shotgun_rounds
from repro.kernels.shotgun_sparse import (fused_sparse_shotgun_rounds,
                                          fused_sparse_vmem_bytes)

K = 4
R = 8    # fused rounds per launch


def run() -> list[dict]:
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    # (n, d, density, with_dense): the d=65536 point is sparse-only — its
    # dense form is 512 MB and the dense kernels would dominate the run.
    shapes = ([(256, 1024, 0.02, True)] if smoke
              else [(2048, 16384, 0.002, True), (2048, 65536, 0.002, False)])
    rows = []
    for (n, d, density, with_dense) in shapes:
        S, y, _ = syn.large_sparse(seed=0, n=n, d=d, density=density,
                                   layout="bcsc")
        ps = obj.make_problem(S, y, lam=0.5)

        rows_t, vals_t = ps.A.rows, ps.A.vals
        nblk = rows_t.shape[0]
        xs = jnp.zeros(nblk * 128)
        zs = jnp.zeros(n)
        idx_rk = (jnp.arange(R * K, dtype=jnp.int32) % nblk).reshape(R, K)

        # refuse configs the fused sparse kernel could not compile on
        # hardware — interpret mode hides an oversized resident set
        # (shotgun-lint SL101 checks the same bound on the committed rows)
        vmem = fused_sparse_vmem_bytes(n, nblk, int(ps.A.tile), K)
        if vmem > VMEM_BUDGET:
            raise ValueError(
                f"fused sparse config (n={n}, d={d}, K={K}, R={R}, "
                f"tile={int(ps.A.tile)}) needs {vmem} B of VMEM > "
                f"{VMEM_BUDGET} B budget — shrink the tile or K")

        us_fused_sparse = time_us(lambda: fused_sparse_shotgun_rounds(
            rows_t, vals_t, zs, xs, idx_rk, ps.lam, ps.beta, ps.y)) / R

        # bf16 nnz value tiles (DESIGN §8.3): 6 B/slot instead of 8, f32
        # accumulation in-kernel — time the same fused launch and check the
        # CONVERGED objective stays within 1% of the f32 tiles (early-round
        # objectives from a zero init diverge transiently: bf16 rounding
        # perturbs the coordinate updates before the iterates settle)
        vals16 = vals_t.astype(jnp.bfloat16)
        us_fused_bf16 = time_us(lambda: fused_sparse_shotgun_rounds(
            rows_t, vals16, zs, xs, idx_rk, ps.lam, ps.beta, ps.y)) / R

        def solve_chain(vals, launches, idx):
            x, z = xs, zs
            for _ in range(launches):
                x, z, f, _, _ = fused_sparse_shotgun_rounds(
                    rows_t, vals, z, x, idx, ps.lam, ps.beta, ps.y)
            return float(f[-1])

        rel_err_bf16 = None
        if with_dense:
            # parity runs at K=1 (P=128): the bench's K=4 grid is past the
            # Thm 3.2 interference limit on these shapes and diverges, which
            # is fine for timing but meaningless for an objective comparison
            idx_par = (jnp.arange(R, dtype=jnp.int32) % nblk).reshape(R, 1)
            launches = max(8, 16 * nblk // R)   # ~16 sweeps over the blocks
            f_f32 = solve_chain(vals_t, launches, idx_par)
            f_b16 = solve_chain(vals16, launches, idx_par)
            rel_err_bf16 = abs(f_b16 - f_f32) / abs(f_f32)
            assert rel_err_bf16 < 0.01, (f_b16, f_f32, launches)

        model = sparse_round_model(n, d, K, tile=ps.A.tile, R=R)
        model16 = sparse_round_model(n, d, K, tile=ps.A.tile, R=R,
                                     val_bytes=2)
        assert model["sparse_fused"]["bytes"] < model["dense"]["bytes"], model
        row = {
            "bench": "sparse", "n": n, "d": d, "density": density,
            "K": K, "P_eff": K * 128, "tile": int(ps.A.tile),
            "rounds_per_launch": R,
            "fused_round_us_bcsc": round(us_fused_sparse, 1),
            "hbm_bytes_per_round_fused_dense": model["dense"]["bytes"],
            "hbm_bytes_per_round_fused_bcsc":
                round(model["sparse_fused"]["bytes"]),
            "hbm_bytes_ratio_fused": round(model["hbm_bytes_ratio_fused"], 1),
            "storage_bytes_dense": model["storage_bytes_dense"],
            "storage_bytes_bcsc": model["storage_bytes_bcsc"],
            "fused_round_us_bcsc_bf16": round(us_fused_bf16, 1),
            "hbm_bytes_per_round_fused_bcsc_bf16":
                round(model16["sparse_fused"]["bytes"]),
            "storage_bytes_bcsc_bf16": model16["storage_bytes_bcsc"],
        }
        if rel_err_bf16 is not None:
            row["objective_rel_err_bf16"] = rel_err_bf16

        if with_dense:
            Ad, yd, _ = syn.large_sparse(seed=0, n=n, d=d, density=density)
            pd = obj.make_problem(Ad, yd, lam=0.5)

            # scalar solver: identical round math, different column gather
            us_scalar_dense = time_us(lambda: shotgun_solve(
                pd, jax.random.PRNGKey(0), P=K * 128, rounds=1))
            us_scalar_sparse = time_us(lambda: shotgun_solve(
                ps, jax.random.PRNGKey(0), P=K * 128, rounds=1))

            # dense Pallas rounds: R fused rounds per launch
            Ap, yp, mask = ops.pad_problem(pd.A, pd.y)
            x = jnp.zeros(Ap.shape[1])
            z = jnp.zeros(Ap.shape[0])
            us_fused_dense = time_us(lambda: fused_shotgun_rounds(
                Ap, z, x, idx_rk, pd.lam, pd.beta, yp, mask)) / R

            row.update({
                "scalar_round_us_dense": round(us_scalar_dense, 1),
                "scalar_round_us_bcsc": round(us_scalar_sparse, 1),
                "fused_round_us_dense": round(us_fused_dense, 1),
                "speedup_scalar":
                    round(us_scalar_dense / us_scalar_sparse, 2),
                "speedup_fused_sparse_vs_dense_fused":
                    round(us_fused_dense / us_fused_sparse, 2),
            })

        rows.append(row)
        print(f"sparse,n={n},d={d},density={density},tile={int(ps.A.tile)},"
              f"fused_bcsc={us_fused_sparse:.0f}us", flush=True)

    emit(rows, "bench_sparse")
    if not smoke:
        # append to the committed perf trajectory, replacing any previous
        # sparse rows (bench_kernels owns the untagged rows)
        merge_root(rows, tag="sparse")
    return rows


if __name__ == "__main__":
    run()
