"""Blocked-CSC sparse data path (DESIGN §8): container/ops correctness,
the fused sparse Pallas kernels vs the oracles, and dense-vs-sparse solver
equivalence (same key => same trajectory) across the stack."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.core.shotgun import shotgun_solve
from repro.core.spectral import spectral_radius
from repro.data import synthetic as syn
from repro.data.sparse import BlockedCSC, pad_feature_blocks
from repro.kernels import ops, ref
from repro.kernels.shotgun_sparse import (fused_sparse_shotgun_delta_rounds,
                                          fused_sparse_shotgun_rounds)


def _pair(seed=0, n=256, d=512, density=0.02, category="sparse_imaging"):
    gen = getattr(syn, category)
    Ad, y, _ = gen(seed=seed, n=n, d=d, density=density)
    S, y2, _ = gen(seed=seed, n=n, d=d, density=density, layout="bcsc")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    return Ad, S, y


# ---------------------------------------------------------------------------
# Container + linear-op seam
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("category", ["sparse_imaging", "large_sparse"])
def test_bcsc_roundtrip_and_layout_identity(category):
    """layout='bcsc' packs exactly the matrix the dense layout returns."""
    Ad, S, _ = _pair(category=category)
    np.testing.assert_array_equal(np.asarray(S.to_dense()), Ad)
    assert S.shape == Ad.shape
    assert S.tile % 8 == 0 and S.d_pad % S.block == 0
    # padding slots are additive identities
    assert int(S.nnz) == int((Ad != 0).sum())


def test_bcsc_rejects_undersized_tile():
    Ad, _, _ = _pair()
    with pytest.raises(ValueError):
        BlockedCSC.from_dense(Ad, tile=1)


def test_bcsc_astype_bf16():
    """bf16 value tiles: rows stay int32, padding stays an exact additive
    identity, nnz is preserved, and the linear ops (which accumulate in
    f32) agree with the f32 container to bf16 precision."""
    Ad, S, _ = _pair()
    Sb = S.astype(jnp.bfloat16)
    assert Sb.dtype == jnp.bfloat16
    assert Sb.rows.dtype == jnp.int32
    assert int(Sb.nnz) == int(S.nnz)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(S.d), jnp.float32)
    r = jnp.asarray(rng.standard_normal(S.n), jnp.float32)
    mv = obj.matvec(Sb, x)
    rv = obj.rmatvec(Sb, r)
    assert mv.dtype == jnp.float32 and rv.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(mv), np.asarray(obj.matvec(S, x)),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(obj.rmatvec(S, r)),
                               rtol=2e-2, atol=2e-2)


def test_sparse_fused_solver_bf16_vals_parity():
    """Halved nnz-tile storage must not move the optimum: a sparse_fused
    solve on bf16 value tiles (cast AFTER column normalization) tracks the
    f32 solve's final objective to <= 1%."""
    from repro.core.sharded import make_feature_mesh, shotgun_sharded_solve
    _, S, y = _pair(n=512, d=512, density=0.01)
    prob = obj.make_problem(S, y, lam=0.5)
    prob16 = prob._replace(A=prob.A.astype(jnp.bfloat16))
    mesh = make_feature_mesh(jax.devices()[:1])
    kw = dict(rounds=64, mesh=mesh, engine="sparse_fused", K=1,
              merge="launch", rounds_per_launch=8, trace_every=8)
    f32 = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), **kw)
    b16 = shotgun_sharded_solve(prob16, jax.random.PRNGKey(0), **kw)
    f0 = float(f32.trace.objective[-1])
    f1 = float(b16.trace.objective[-1])
    assert abs(f1 - f0) / f0 < 0.01, (f1, f0)


def test_bcsc_linear_ops_match_dense():
    Ad, S, _ = _pair()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(S.d), jnp.float32)
    r = jnp.asarray(rng.standard_normal(S.n), jnp.float32)
    np.testing.assert_allclose(np.asarray(obj.matvec(S, x)), Ad @ x,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(obj.rmatvec(S, r)), Ad.T @ r,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S.col_norms()),
                               np.linalg.norm(Ad, axis=0), rtol=1e-5, atol=1e-5)


def test_bcsc_gather_cols_pack():
    Ad, S, _ = _pair()
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, S.d, 7), jnp.int32)
    r = jnp.asarray(rng.standard_normal(S.n), jnp.float32)
    delta = jnp.asarray(rng.standard_normal(7), jnp.float32)
    z = jnp.asarray(rng.standard_normal(S.n), jnp.float32)
    cols = obj.gather_cols(S, idx)
    dense_cols = obj.gather_cols(jnp.asarray(Ad), idx)
    np.testing.assert_allclose(np.asarray(obj.cols_rmatvec(cols, r)),
                               np.asarray(obj.cols_rmatvec(dense_cols, r)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(obj.cols_matvec_add(cols, delta, z)),
        np.asarray(obj.cols_matvec_add(dense_cols, delta, z)),
        rtol=1e-4, atol=1e-4)


def test_problem_consumers_run_unchanged_on_bcsc():
    """normalize_columns / lambda_max / spectral_radius / objective all run
    on the container and agree with the dense path."""
    Ad, S, y = _pair()
    pd = obj.make_problem(Ad, y, lam=0.5)
    ps = obj.make_problem(S, y, lam=0.5)
    np.testing.assert_allclose(np.asarray(ps.scales), np.asarray(pd.scales),
                               rtol=1e-5)
    np.testing.assert_allclose(float(obj.lambda_max(ps.A, y, ps.loss)),
                               float(obj.lambda_max(pd.A, y, pd.loss)),
                               rtol=1e-5)
    np.testing.assert_allclose(float(spectral_radius(ps.A)),
                               float(spectral_radius(pd.A)), rtol=1e-4)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(S.d), jnp.float32)
    np.testing.assert_allclose(float(obj.objective(x, ps)),
                               float(obj.objective(x, pd)), rtol=1e-4)


def test_pad_feature_blocks_zero_tail():
    _, S, _ = _pair()
    Sp = pad_feature_blocks(S, 3)
    assert Sp.nblk % 3 == 0
    assert float(jnp.abs(Sp.vals[S.nblk:]).sum()) == 0.0
    assert pad_feature_blocks(Sp, 3) is Sp


# ---------------------------------------------------------------------------
# Solver-level equivalence: same key => same trajectory
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("category", ["sparse_imaging", "large_sparse"])
def test_sparse_shotgun_matches_dense_trajectory(category):
    Ad, S, y = _pair(category=category)
    pd = obj.make_problem(Ad, y, lam=0.5)
    ps = obj.make_problem(S, y, lam=0.5)
    rd = shotgun_solve(pd, jax.random.PRNGKey(0), P=8, rounds=300)
    rs = shotgun_solve(ps, jax.random.PRNGKey(0), P=8, rounds=300)
    np.testing.assert_allclose(np.asarray(rs.trace.objective),
                               np.asarray(rd.trace.objective),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(rs.x), np.asarray(rd.x),
                               rtol=1e-3, atol=1e-3)
    # acceptance: objective parity well under 1%
    f_d, f_s = float(rd.trace.objective[-1]), float(rs.trace.objective[-1])
    assert abs(f_s - f_d) / abs(f_d) < 0.01


def test_sparse_warm_start_threads_through():
    """x0 warm start (λ-continuation) initializes z = A x0 on the sparse
    path exactly as on the dense one."""
    Ad, S, y = _pair()
    pd = obj.make_problem(Ad, y, lam=0.5)
    ps = obj.make_problem(S, y, lam=0.5)
    x0 = np.asarray(shotgun_solve(pd, jax.random.PRNGKey(2), P=8,
                                  rounds=200).x)
    rd = ops.block_shotgun_solve(pd, jax.random.PRNGKey(3), K=2, rounds=40, x0=jnp.asarray(x0))
    rs = ops.block_shotgun_solve(ps, jax.random.PRNGKey(3), K=2, rounds=40, x0=jnp.asarray(x0))
    np.testing.assert_allclose(np.asarray(rs.trace.objective),
                               np.asarray(rd.trace.objective),
                               rtol=1e-3, atol=1e-3)


def test_sparse_path_continuation():
    """solve_path runs unchanged on a BlockedCSC problem (scalar solver)."""
    from repro.core.path import solve_path
    _, S, y = _pair()
    ps = obj.make_problem(S, y, lam=0.5)
    path = solve_path(ps, jax.random.PRNGKey(0), lam_target=0.5, P=8,
                      rounds_per_lambda=100, num_lambdas=4)
    assert np.isfinite(path.objectives).all()
    assert path.x.shape == (S.d,)


# ---------------------------------------------------------------------------
# Fused multi-round sparse kernel (DESIGN §8.3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("category", ["sparse_imaging", "large_sparse"])
@pytest.mark.parametrize("K", [1, 3])
def test_fused_sparse_kernel_matches_refs(category, K):
    """The fused sparse kernel retraces both the nnz-tile oracle and the
    dense fused oracle for the same (R, K) index matrix."""
    Ad, S, y = _pair(seed=10, category=category)
    rng = np.random.default_rng(11)
    R = 4
    idx = jnp.asarray(rng.integers(0, S.nblk, (R, K)), jnp.int32)
    x = jnp.asarray(rng.standard_normal(S.d_pad) * 0.1, jnp.float32)
    z = S.matvec(x)
    y = jnp.asarray(y, jnp.float32)
    lam, beta = 0.5, 1.0

    xk, zk, fk, nnzk, _h = fused_sparse_shotgun_rounds(
        S.rows, S.vals, z, x, idx, lam, beta, y)
    xs, zs, fs, nnzs = ref.fused_sparse_shotgun_rounds_ref(
        S.rows, S.vals, z, x, idx, lam, beta, y, "lasso")
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xs),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zs),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fs), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(nnzk), np.asarray(nnzs))

    mask = jnp.ones(S.n, jnp.float32)
    xd, zd, fd, _ = ref.fused_shotgun_rounds_ref(
        jnp.asarray(Ad), z, x[: S.d], idx, lam, beta, y, mask, "lasso",
        S.block)
    np.testing.assert_allclose(np.asarray(xk[: S.d]), np.asarray(xd),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fd), rtol=1e-3)


def test_fused_sparse_delta_rounds_matches_ref():
    """The engine variant reports (x, Δz) with Δz = z_new − z₀ and the same
    iterate as the margin-owning kernel."""
    _, S, y = _pair(seed=12)
    rng = np.random.default_rng(13)
    R, K = 3, 2
    idx = jnp.asarray(rng.integers(0, S.nblk, (R, K)), jnp.int32)
    x = jnp.asarray(rng.standard_normal(S.d_pad) * 0.1, jnp.float32)
    z = S.matvec(x)
    y = jnp.asarray(y, jnp.float32)

    xk, dzk, _h = fused_sparse_shotgun_delta_rounds(
        S.rows, S.vals, z, x, idx, 0.5, 1.0, y)
    xs, dzs = ref.fused_sparse_shotgun_delta_rounds_ref(
        S.rows, S.vals, z, x, idx, 0.5, 1.0, y, "lasso")
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xs),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dzk), np.asarray(dzs),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("category,seed,rounds,rounds_per_launch", [
    ("sparse_imaging", 1, 80, 8), ("large_sparse", 1, 80, 8),
    ("sparse_imaging", 1, 80, 4), ("large_sparse", 1, 80, 5),
    ("sparse_imaging", 5, 16, 8)])
def test_fused_sparse_solver_matches_dense_fused(category, seed, rounds,
                                                 rounds_per_launch):
    """Same key on the densified design: the sparse solve draws the same
    blocks as the dense one, so whole trajectories coincide (the §8.3
    acceptance equivalence), whatever the launch length."""
    Ad, S, y = _pair(category=category)
    pd = obj.make_problem(Ad, y, lam=0.5)
    ps = obj.make_problem(S, y, lam=0.5)
    kw = dict(K=2, rounds=rounds, rounds_per_launch=rounds_per_launch)
    rd = ops.block_shotgun_solve(pd, jax.random.PRNGKey(seed), **kw)
    rs = ops.block_shotgun_solve(ps, jax.random.PRNGKey(seed), **kw)
    np.testing.assert_allclose(np.asarray(rs.trace.objective),
                               np.asarray(rd.trace.objective),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(rs.x), np.asarray(rd.x),
                               rtol=1e-3, atol=1e-3)


def test_fused_sparse_rejects_bad_rounds_per_launch():
    _, S, y = _pair()
    ps = obj.make_problem(S, y, lam=0.5)
    with pytest.raises(ValueError, match="rounds=10"):
        ops.block_shotgun_solve(ps, jax.random.PRNGKey(0), K=2, rounds=10,
                                rounds_per_launch=8)


def test_fused_sparse_warm_start():
    """x0 warm start initializes z0 = bcsc_matvec(A, x0) in the fused
    launch scan exactly as the dense fused path initializes z0 = A x0."""
    Ad, S, y = _pair()
    pd = obj.make_problem(Ad, y, lam=0.5)
    ps = obj.make_problem(S, y, lam=0.5)
    x0 = np.asarray(shotgun_solve(pd, jax.random.PRNGKey(2), P=8,
                                  rounds=200).x)
    rd = ops.block_shotgun_solve(pd, jax.random.PRNGKey(3), K=2, rounds=16,
                                 rounds_per_launch=8, x0=jnp.asarray(x0))
    rs = ops.block_shotgun_solve(ps, jax.random.PRNGKey(3), K=2, rounds=16,
                                 rounds_per_launch=8, x0=jnp.asarray(x0))
    np.testing.assert_allclose(np.asarray(rs.trace.objective),
                               np.asarray(rd.trace.objective),
                               rtol=1e-3, atol=1e-3)
    # warm trace must continue below the cold start's first objective
    cold = ops.block_shotgun_solve(ps, jax.random.PRNGKey(3), K=2, rounds=16,
                                   rounds_per_launch=8)
    assert float(rs.trace.objective[0]) < float(cold.trace.objective[0])


def test_sparse_fused_engine_single_shard_matches_fused_solver():
    """engine="sparse_fused", merge="round" on a 1-shard mesh retraces
    block_shotgun_solve on the same BlockedCSC problem (DESIGN
    §3 trace equivalence), and merge="launch" matches at merge points."""
    from repro.core.sharded import make_feature_mesh, shotgun_sharded_solve
    _, S, y = _pair()
    ps = obj.make_problem(S, y, lam=0.5)
    mesh = make_feature_mesh(jax.devices()[:1])
    rounds = 16
    rf = ops.block_shotgun_solve(ps, jax.random.PRNGKey(4), K=2,
                                 rounds=rounds, rounds_per_launch=8)
    r_sh = shotgun_sharded_solve(ps, jax.random.PRNGKey(4), rounds=rounds,
                                 engine="sparse_fused", merge="round", K=2,
                                 mesh=mesh, trace_every=rounds)
    np.testing.assert_allclose(float(r_sh.trace.objective[-1]),
                               float(rf.trace.objective[-1]), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(r_sh.x), np.asarray(rf.x),
                               rtol=1e-3, atol=1e-3)
    r_la = shotgun_sharded_solve(ps, jax.random.PRNGKey(4), rounds=rounds,
                                 engine="sparse_fused", merge="launch",
                                 rounds_per_launch=8, K=2, mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(r_la.trace.objective),
        np.asarray(rf.trace.objective)[7::8], rtol=1e-4)


def test_fused_sparse_vmem_budget_tracks_scratch_list():
    """Drift pin for ``fused_sparse_vmem_bytes`` (DESIGN §8.3): the formula
    must mirror ``_fused_sparse_call``'s actual resident set — 5 (6 with
    Δz) (8, 128)-tiled n-vectors, three (nblk, block) x buffers, the
    (K, block) δ scratch,
    and the double-buffered rows+vals tile pair.  Editing the kernel's
    scratch/output lists must come back here."""
    from repro.kernels.shotgun_sparse import fused_sparse_vmem_bytes
    n, nblk, tile, K, block = 2048, 128, 16, 4, 128
    # (n, 1) vectors take 512 B per sample in (8, 128) tiles; the (K, block)
    # delta scratch pads K to 8 rows
    vec = n * 512
    expect = (5 * vec + 3 * nblk * block * 4 + 8 * block * 4
              + 2 * tile * block * 8)
    assert fused_sparse_vmem_bytes(n, nblk, tile, K) == expect
    assert (fused_sparse_vmem_bytes(n, nblk, tile, K, emit_dz=True)
            == expect + vec)
    # bf16 value tiles shrink only the streamed rows+vals pair: 4+2 B/slot
    expect16 = (5 * vec + 3 * nblk * block * 4 + 8 * block * 4
                + 2 * tile * block * 6)
    assert fused_sparse_vmem_bytes(n, nblk, tile, K, val_bytes=2) == expect16


SUB_FUSED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import objectives as obj
from repro.core.sharded import shotgun_sharded_solve, make_feature_mesh
from repro.core.shotgun import shotgun_solve
from repro.data import synthetic as syn
from repro.kernels import ops

# Same interference-safe shape as the dense engine leg: P* ~ 855 at
# (2048, 8192, density 0.002), P_eff = 8 shards * K=1 * 128 = 1024 with
# merge="round" disjoint-coordinate sampling (Thm 3.2 / Lemma 3.3).
S, y, _ = syn.sparse_imaging(seed=0, n=2048, d=8192, density=0.002,
                             layout="bcsc")
prob = obj.make_problem(S, y, lam=0.5)
mesh8 = make_feature_mesh()
assert mesh8.devices.size == 8
f_ref = float(shotgun_solve(prob, jax.random.PRNGKey(1), P=256,
                            rounds=600).trace.objective[-1])

# sparse_fused engine, one psum per round: matches the single-shard solve's
# converged objective and keeps z == A x
r = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=256,
                          mesh=mesh8, engine="sparse_fused", merge="round",
                          K=1, trace_every=8)
f = float(r.trace.objective[-1])
assert abs(f - f_ref) / f_ref < 0.10, (f, f_ref)
np.testing.assert_allclose(np.asarray(r.z), np.asarray(obj.matvec(prob.A, r.x)),
                           rtol=2e-3, atol=2e-3)
# the one-device fused sparse solve at the same P = 8 * 128 draws other
# blocks but runs the same algorithm: the same converged objective
rb = ops.block_shotgun_solve(prob, jax.random.PRNGKey(0), K=8, rounds=256)
np.testing.assert_allclose(float(rb.trace.objective[-1]), f, rtol=1e-4)
print("SPARSE_FUSED_ROUND_OK")

# merge="launch" on 2 shards: stale windows of R*K*128*2 = 512 updates stay
# inside the interference budget (Lemma 3.3) and still converge (same shape
# as the dense fused launch leg in test_sharded_engines.py)
S2, y2, _ = syn.sparse_imaging(seed=1, n=2048, d=2048, density=0.002,
                               layout="bcsc")
prob2 = obj.make_problem(S2, y2, lam=0.5)
f_ref2 = float(shotgun_solve(prob2, jax.random.PRNGKey(1), P=64,
                             rounds=800).trace.objective[-1])
mesh2 = Mesh(np.array(jax.devices()[:2]), ("f",))
r = shotgun_sharded_solve(prob2, jax.random.PRNGKey(0), rounds=256,
                          mesh=mesh2, engine="sparse_fused", merge="launch",
                          rounds_per_launch=2, K=1, trace_every=8)
f = float(r.trace.objective[-1])
assert abs(f - f_ref2) / f_ref2 < 0.10, (f, f_ref2)
print("SPARSE_FUSED_LAUNCH_OK")

# compression + hierarchical merge compose with the sparse_fused engine
c = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                          mesh=mesh8, engine="sparse_fused", merge="round",
                          K=1, trace_every=8, compression="int8")
b = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                          mesh=mesh8, engine="sparse_fused", merge="round",
                          K=1, trace_every=8)
fc, fb = float(c.trace.objective[-1]), float(b.trace.objective[-1])
assert abs(fc - fb) / fb < 0.01, (fc, fb)
meshh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "f"))
h0 = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                           mesh=meshh, engine="sparse_fused", K=1,
                           trace_every=8)
h1 = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                           mesh=meshh, engine="sparse_fused", K=1,
                           trace_every=8, hierarchical=True)
np.testing.assert_allclose(np.asarray(h0.trace.objective),
                           np.asarray(h1.trace.objective), rtol=1e-5)
print("SPARSE_FUSED_WIRE_OK")
"""


@pytest.mark.slow
def test_multidevice_sparse_fused_engine():
    import os
    import subprocess
    import sys
    out = subprocess.run([sys.executable, "-c", SUB_FUSED],
                         capture_output=True, text=True, timeout=900,
                         env={**os.environ, "PYTHONPATH": "src"})
    for tag in ["SPARSE_FUSED_ROUND_OK", "SPARSE_FUSED_LAUNCH_OK",
                "SPARSE_FUSED_WIRE_OK"]:
        assert tag in out.stdout, out.stdout + out.stderr
