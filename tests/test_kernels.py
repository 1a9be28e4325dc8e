"""Pallas kernel allclose sweeps (the interpreter on the CPU backend) against the ref.py oracles,
across shapes and dtypes, plus full-round and solver-level parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.data import synthetic as syn
from repro.kernels import ops, ref
from repro.kernels.shotgun_block import gather_block_matvec, scatter_block_update

SHAPES = [
    # (n, d, block, tile_n, K)
    (256, 256, 128, 128, 1),
    (512, 512, 128, 256, 2),
    (1024, 768, 128, 512, 3),
    (512, 1024, 256, 256, 2),
    (768, 512, 128, 256, 4),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(n, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((n, d)), dtype)
    r = jnp.asarray(rng.standard_normal(n), dtype)
    z = jnp.asarray(rng.standard_normal(n), dtype)
    return A, r, z


@pytest.mark.parametrize("n,d,block,tile_n,K", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gather_block_matvec_allclose(n, d, block, tile_n, K, dtype):
    A, r, _ = _mk(n, d, dtype)
    nblk = d // block
    blk = jax.random.choice(jax.random.PRNGKey(1), nblk, (K,), replace=False)
    got = gather_block_matvec(A, r, blk, block=block, tile_n=tile_n)
    want = ref.gather_block_matvec_ref(A, r, blk, block)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("n,d,block,tile_n,K", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_scatter_block_update_allclose(n, d, block, tile_n, K, dtype):
    A, _, z = _mk(n, d, dtype, seed=1)
    rng = np.random.default_rng(2)
    nblk = d // block
    blk = jax.random.choice(jax.random.PRNGKey(2), nblk, (K,), replace=False)
    delta = jnp.asarray(rng.standard_normal((K, block)) * 0.1, dtype)
    got = scatter_block_update(A, z, blk, delta, block=block, tile_n=tile_n)
    want = ref.scatter_block_update_ref(A, z, blk, delta, block)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("loss", [obj.LASSO, obj.LOGISTIC])
def test_block_round_matches_ref(loss):
    A, y, _ = (syn.sparco(seed=3, n=512, d=512) if loss == obj.LASSO
               else syn.logistic_data(seed=3, n=512, d=512))
    prob = obj.make_problem(A, y, lam=0.4, loss=loss)
    Ap, yp, mask = ops.pad_problem(prob.A, prob.y)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal(Ap.shape[1]) * 0.1, jnp.float32)
    z = Ap @ x
    blk = jax.random.choice(jax.random.PRNGKey(5), Ap.shape[1] // ops.BLOCK,
                            (3,), replace=False)
    x_k, z_k, d_k = ops.block_shotgun_round(Ap, z, x, blk, prob.lam, prob.beta,
                                            yp, mask, loss=loss)
    x_r, z_r, d_r = ref.block_shotgun_round_ref(Ap, z, x, blk, prob.lam,
                                                prob.beta, yp, loss, ops.BLOCK)
    np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_r), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(z_k), np.asarray(z_r), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-4, atol=1e-4)


def test_block_solver_converges_to_reference_objective():
    """Block-Shotgun (the TPU formulation) must reach the same optimum as
    scalar Shotgun — it IS Shotgun with P = K*block coordinates."""
    from repro.core.shotgun import shotgun_solve
    from repro.core.spectral import p_star
    A, y, _ = syn.sparco(seed=6, n=1024, d=2048)
    prob = obj.make_problem(A, y, lam=1.0)
    assert p_star(prob.A) > 2 * ops.BLOCK   # P = K*128 = 256 is theory-legal
    f_blk = float(ops.block_shotgun_solve(prob, jax.random.PRNGKey(0), K=2,
                                          rounds=800)
                  .trace.objective[-1])
    f_ref = float(shotgun_solve(prob, jax.random.PRNGKey(1), P=256,
                                rounds=2000).trace.objective[-1])
    assert abs(f_blk - f_ref) / abs(f_ref) < 1e-3


def test_pad_problem_roundtrip():
    A = jnp.ones((300, 200))
    y = jnp.ones((300,))
    Ap, yp, mask = ops.pad_problem(A, y)
    assert Ap.shape[0] % ops.TILE_N == 0 and Ap.shape[1] % ops.BLOCK == 0
    assert float(mask.sum()) == 300
    np.testing.assert_allclose(np.asarray(Ap[:300, :200]), np.asarray(A))
    np.testing.assert_allclose(np.asarray(Ap[300:]), 0.0)
