"""Pallas kernel allclose sweeps (the interpreter on the CPU backend) against the ref.py oracles,
across shapes, dtypes and losses, plus solver-level convergence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.data import synthetic as syn
from repro.kernels import ops, ref
from repro.kernels.shotgun_block import fused_shotgun_rounds

SHAPES = [
    # (n, d, block, tile_n, K)
    (256, 256, 128, 128, 1),
    (512, 512, 128, 256, 2),
    (1024, 768, 128, 512, 3),
    (512, 1024, 256, 256, 2),
    (768, 512, 128, 256, 4),
]
DTYPES = [jnp.float32, jnp.bfloat16]


LOSSES = [obj.LASSO, obj.LOGISTIC, "logistic_newton"]


def _mk(n, d, dtype, loss, seed=0):
    """A stored in ``dtype``; labels ±1 for the logistic losses; a mask
    with its last 16 samples off, as ``pad_problem`` leaves padded ones."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((n, d)) / np.sqrt(n), dtype)
    y = rng.standard_normal(n)
    if loss != obj.LASSO:
        y = np.where(y >= 0, 1.0, -1.0)
    mask = np.ones(n)
    mask[-16:] = 0.0
    x = jnp.asarray(rng.standard_normal(d) * 0.1, jnp.float32)
    z = jnp.asarray(A, jnp.float32) @ x
    return A, jnp.asarray(y, jnp.float32), jnp.asarray(mask, jnp.float32), x, z


@pytest.mark.parametrize("n,d,block,tile_n,K", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("loss", LOSSES)
def test_fused_rounds_allclose_sweep(n, d, block, tile_n, K, dtype, loss):
    """Two fused rounds against ``ref.fused_shotgun_rounds_ref`` over the
    sweep's shapes, block widths (256 included), sample tiles (two-phase
    where tile_n < n) and stored dtypes of A, for every loss spec."""
    A, y, mask, x, z = _mk(n, d, dtype, loss)
    beta = 1.0 if loss == obj.LASSO else 0.25
    idx = jnp.stack([
        jax.random.choice(jax.random.PRNGKey(r), d // block, (K,),
                          replace=False) for r in range(2)]).astype(jnp.int32)
    got = fused_shotgun_rounds(A, z, x, idx, 0.05, beta, y, mask, loss=loss,
                               block=block, tile_n=tile_n)
    want = ref.fused_shotgun_rounds_ref(A, z, x, idx, 0.05, beta, y, mask,
                                        loss, block)
    tol = 1e-4 if dtype == jnp.float32 else 1e-3
    for g, w in zip(got[:3], want[:3]):          # x, z, f
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


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
