"""Fused multi-round Block-Shotgun kernel (DESIGN §4.2): interpret-mode
equivalence against the pure-jnp multi-round oracle, padding/duplicate-draw
edge cases, bf16 A storage, and solver-level trace parity (the launch scan
must reproduce an oracle replay of its own block draws)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.data import synthetic as syn
from repro.kernels import ops, ref, shotgun_block
from repro.kernels.shotgun_block import (BLOCK, VMEM_BUDGET, auto_tile_n,
                                         fused_shotgun_rounds,
                                         fused_vmem_bytes)


def _padded_problem(loss, seed=0, n=300, d=500, lam=0.4):
    """Non-divisible n/d on purpose — exercises pad_problem's zero rows/cols
    (mask kills padded samples; padded columns have zero gradient)."""
    A, y, _ = (syn.sparco(seed=seed, n=n, d=d) if loss == obj.LASSO
               else syn.logistic_data(seed=seed, n=n, d=d))
    prob = obj.make_problem(A, y, lam=lam, loss=loss)
    Ap, yp, mask = ops.pad_problem(prob.A, prob.y)
    return prob, Ap, yp, mask


def _warm_start(Ap, seed=1, scale=0.1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(Ap.shape[1]) * scale, jnp.float32)
    return x, Ap @ x


def _idx_with_duplicates(nblk, R, K, seed=2):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, nblk, (R, K))
    idx[R // 2, -1] = idx[R // 2, 0]          # duplicate draw inside a round
    return jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("loss", [obj.LASSO, obj.LOGISTIC])
@pytest.mark.parametrize("tile_n", [None, 128])   # single-phase / T=4 phases
def test_fused_rounds_match_oracle(loss, tile_n):
    prob, Ap, yp, mask = _padded_problem(loss)
    x, z = _warm_start(Ap)
    R, K = 8, 2
    idx = _idx_with_duplicates(Ap.shape[1] // BLOCK, R, K)

    xk, zk, fk, nk, _h = fused_shotgun_rounds(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask, loss=loss,
        tile_n=tile_n)
    xr, zr, fr, nr = ref.fused_shotgun_rounds_ref(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask, loss, BLOCK)

    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


@pytest.mark.parametrize("loss", [obj.LASSO, "logistic_newton"])
@pytest.mark.parametrize("tile_n", [None, 128])
def test_fused_rounds_in_chunks_match_oracle(monkeypatch, loss, tile_n):
    """n = 640 in 256-row chunks: two whole chunks and a 128-row tail
    single-phase, a tail alone per 128-row tile two-phase."""
    monkeypatch.setattr(shotgun_block, "SUB_TILE", 256)
    jax.clear_caches()
    A, y, _ = syn.logistic_data(seed=3, n=600, d=500)
    prob = obj.make_problem(A, y, lam=1.0, loss=(
        obj.LASSO if loss == obj.LASSO else obj.LOGISTIC))
    Ap, yp, mask = ops.pad_problem(prob.A, prob.y, tile_n=128)
    assert Ap.shape[0] == 640
    x, z = _warm_start(Ap, scale=0.01)
    idx = _idx_with_duplicates(Ap.shape[1] // BLOCK, 8, 2)
    xk, zk, fk, nk, _h = fused_shotgun_rounds(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask, loss=loss,
        tile_n=tile_n)
    xr, zr, fr, nr = ref.fused_shotgun_rounds_ref(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask, loss, BLOCK)
    jax.clear_caches()
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


def test_fused_padded_coordinates_stay_zero():
    """Zero-padded columns are fixed points: x on the pad never moves, and
    masked-out padded samples contribute nothing to the trace objective."""
    prob, Ap, yp, mask = _padded_problem(obj.LASSO)
    x0 = jnp.zeros(Ap.shape[1], jnp.float32)
    z0 = jnp.zeros(Ap.shape[0], jnp.float32)
    nblk = Ap.shape[1] // BLOCK
    idx = jnp.tile(jnp.arange(nblk, dtype=jnp.int32), (8, 1))[:, :nblk]
    xk, zk, fk, _, _h = fused_shotgun_rounds(
        Ap, z0, x0, idx, prob.lam, prob.beta, yp, mask, loss=obj.LASSO)
    np.testing.assert_allclose(np.asarray(xk[prob.d:]), 0.0)
    np.testing.assert_allclose(np.asarray(zk[prob.n:]), 0.0, atol=1e-6)
    assert np.all(np.isfinite(np.asarray(fk)))


def test_fused_bf16_storage():
    """bf16 A halves streamed bytes while accumulation stays f32: the kernel
    on bf16-stored A must match the f32 oracle fed the same rounded A (only
    reduction order may differ), and stay close to the full-f32 trajectory
    on the convergent cold-start path."""
    prob, Ap, yp, mask = _padded_problem(obj.LASSO)
    Abf = Ap.astype(jnp.bfloat16)
    x, z = _warm_start(Ap)
    idx = _idx_with_duplicates(Ap.shape[1] // BLOCK, 8, 2)
    xk, zk, fk, nk, _h = fused_shotgun_rounds(
        Abf, z, x, idx, prob.lam, prob.beta, yp, mask,
        loss=obj.LASSO)
    xr, zr, fr, nr = ref.fused_shotgun_rounds_ref(
        Abf, z, x, idx, prob.lam, prob.beta, yp, mask, obj.LASSO, BLOCK)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                               rtol=1e-3, atol=1e-3)

    # cold start (convergent regime): bf16 storage tracks the f32 objective
    x0 = jnp.zeros_like(x)
    z0 = jnp.zeros_like(z)
    _, _, f16, _, _ = fused_shotgun_rounds(
        Abf, z0, x0, idx, prob.lam, prob.beta, yp, mask, loss=obj.LASSO)
    _, _, f32_, _, _ = fused_shotgun_rounds(
        Ap, z0, x0, idx, prob.lam, prob.beta, yp, mask, loss=obj.LASSO)
    np.testing.assert_allclose(np.asarray(f16), np.asarray(f32_), rtol=2e-2)


def test_auto_tile_n():
    assert auto_tile_n(512, d=512) == 512     # whole-n tile -> single phase
    assert auto_tile_n(2048, d=8192) == 2048  # benchmark shape fits easily
    # the double-buffered (n, 128) A panel alone outgrows the budget
    big = auto_tile_n(1 << 17)
    assert big < (1 << 17) and (1 << 17) % big == 0
    # at 4 B per sample per (1, n) row the resident rows alone outgrow the
    # budget: refused up front, naming the limit
    with pytest.raises(ValueError, match="VMEM_BUDGET"):
        auto_tile_n(1 << 23)
    # large d pins 3 full-d x buffers in VMEM: must veto single-phase even
    # though the A tile alone would fit
    n = 8192
    spare = VMEM_BUDGET - fused_vmem_bytes(n, 0, 1, tile_n=n)
    assert auto_tile_n(n, d=(spare // 12 // BLOCK + 1) * BLOCK) < n


def test_zeta_cell_shape_runs_single_phase():
    """The Newton kernel at the zeta cell's padded shape takes the whole
    n as one tile (T == 1: one A-panel fetch per block a round), because
    each sample-indexed vector is a (1, n) row priced at 4 B a sample."""
    n, d, K = 24064, 2048, 8
    assert auto_tile_n(n, d=d, K=K, loss="logistic_newton") == n
    for loss, dz, rows in [("lasso", False, 5), ("lasso", True, 6),
                           ("logistic_newton", False, 6),
                           ("logistic_newton", True, 7)]:
        grow = (fused_vmem_bytes(n + 128, d, K, tile_n=128, emit_dz=dz,
                                 loss=loss)
                - fused_vmem_bytes(n, d, K, tile_n=128, emit_dz=dz,
                                   loss=loss))
        assert grow == rows * 128 * 4
    with pytest.raises(ValueError, match="multiple of 128"):
        auto_tile_n(24000, d=d, K=K)


@pytest.mark.parametrize("loss", [obj.LASSO, obj.LOGISTIC])
def test_fused_solve_trace_parity(loss):
    """block_shotgun_solve must retrace a replay of its own block draws
    (round t: ``choice(split(key, rounds)[t], nblk, (K,))``) through the
    pure-jnp oracle: same objective/nnz trajectory, iterate and margin.
    Guards the launch scan against trajectory drift."""
    prob, Ap, yp, mask = _padded_problem(loss, seed=6, n=640, d=1024,
                                         lam=1.0 if loss == obj.LASSO
                                         else 0.05)
    key, K, rounds = jax.random.PRNGKey(0), 2, 32
    fus = ops.block_shotgun_solve(prob, key, K=K, rounds=rounds,
                                  rounds_per_launch=8)
    idx = jax.vmap(lambda kt: jax.random.choice(
        kt, Ap.shape[1] // BLOCK, (K,), replace=False))(
        jax.random.split(key, rounds)).astype(jnp.int32)
    x0 = jnp.zeros(Ap.shape[1], jnp.float32)
    xr, zr, fr, nr = ref.fused_shotgun_rounds_ref(
        Ap, jnp.zeros(Ap.shape[0], jnp.float32), x0, idx, prob.lam,
        prob.beta, yp, mask, loss, BLOCK)
    np.testing.assert_allclose(np.asarray(fus.trace.objective),
                               np.asarray(fr), rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(fus.trace.nnz), np.asarray(nr))
    np.testing.assert_allclose(np.asarray(fus.x), np.asarray(xr[: prob.d]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fus.z), np.asarray(zr[: prob.n]),
                               rtol=1e-4, atol=1e-4)


def test_fused_solve_rejects_indivisible_rounds():
    A, y, _ = syn.sparco(seed=0, n=256, d=512)
    prob = obj.make_problem(A, y, lam=0.5)
    with pytest.raises(ValueError, match="rounds_per_launch"):
        ops.block_shotgun_solve(prob, jax.random.PRNGKey(0), K=1, rounds=9,
                                rounds_per_launch=8)


def test_solver_registry_exposes_fused():
    from repro.core import get_solver, SOLVER_NAMES
    assert "block_fused" in SOLVER_NAMES
    solve = get_solver("block_fused")
    A, y, _ = syn.sparco(seed=0, n=256, d=512)
    prob = obj.make_problem(A, y, lam=1.0)
    res = solve(prob, jax.random.PRNGKey(0), K=1, rounds=8)
    assert res.trace.objective.shape == (8,)
    assert res.x.shape == (prob.d,)
    with pytest.raises(ValueError, match="unknown solver"):
        get_solver("nope")
