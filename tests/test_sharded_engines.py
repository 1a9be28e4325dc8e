"""Round-engine architecture (DESIGN §3): the sharded driver over
scalar/fused engines, merge cadences, Δz wire compression, and the
λ-path registry wiring.

Single-shard trace-equivalence and validation run in-process (a 1-device
mesh exists everywhere); the real multi-device behavior — 8-shard
convergence, merge="launch" staleness, compression parity, hierarchical
merges — runs in a subprocess with 8 forced host devices (and on the CI
sharded-mesh leg, where XLA_FLAGS forces 8 devices for this whole file).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.core.sharded import make_feature_mesh, shotgun_sharded_solve
from repro.data import synthetic as syn
from repro.kernels import ops


def _mesh1():
    return make_feature_mesh(jax.devices()[:1])


@pytest.fixture(scope="module")
def prob():
    A, y, _ = syn.sparco(seed=6, n=640, d=1024)
    return obj.make_problem(A, y, lam=1.0)


# ---------------------------------------------------------------------------
# Single-shard trace equivalence (acceptance: sharded-fused == fused solver)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss", [obj.LASSO, "logistic_newton"])
def test_fused_engine_single_shard_matches_fused_solver(prob, loss):
    """engine="fused", merge="round" on a 1-shard mesh must retrace
    ``block_shotgun_solve`` for the same key: same split/choice draws,
    same kernel dataflow, Δz merged through an identity psum."""
    newton = loss == "logistic_newton"
    if newton:
        A, y, _ = syn.logistic_data(seed=6, n=640, d=1024)
        prob = obj.make_problem(A, y, lam=0.05, loss=obj.LOGISTIC)
    key = jax.random.PRNGKey(0)
    sh = shotgun_sharded_solve(prob, key, rounds=16, mesh=_mesh1(),
                               engine="fused", merge="round", K=2,
                               newton=newton)
    fu = ops.block_shotgun_solve(prob, key, K=2, rounds=16,
                                 rounds_per_launch=8, newton=newton)
    np.testing.assert_allclose(np.asarray(sh.trace.objective),
                               np.asarray(fu.trace.objective), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(sh.x), np.asarray(fu.x),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sh.z), np.asarray(fu.z),
                               rtol=1e-4, atol=1e-4)


def test_fused_engine_merge_launch_converges_single_shard(prob):
    """merge="launch" (stale rounds, 1 merge per launch) still descends; on
    one shard there is no cross-shard staleness so it must track the
    merge="round" trajectory exactly (same draws, same kernel)."""
    key = jax.random.PRNGKey(0)
    r1 = shotgun_sharded_solve(prob, key, rounds=16, mesh=_mesh1(),
                               engine="fused", merge="round", K=2,
                               trace_every=8)
    r2 = shotgun_sharded_solve(prob, key, rounds=16, mesh=_mesh1(),
                               engine="fused", merge="launch",
                               rounds_per_launch=8, K=2)
    np.testing.assert_allclose(np.asarray(r1.trace.objective),
                               np.asarray(r2.trace.objective), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Validation: ValueErrors (not asserts) with the offending values
# ---------------------------------------------------------------------------

def test_unknown_engine_merge_compression_raise(prob):
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="unknown engine"):
        shotgun_sharded_solve(prob, key, rounds=4, mesh=_mesh1(), engine="gpu")
    with pytest.raises(ValueError, match="unknown merge"):
        shotgun_sharded_solve(prob, key, rounds=4, mesh=_mesh1(), merge="bad")
    with pytest.raises(ValueError, match="unknown compression"):
        shotgun_sharded_solve(prob, key, rounds=4, mesh=_mesh1(),
                              compression="zip")


def test_divisibility_value_errors(prob):
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="rounds=9"):
        shotgun_sharded_solve(prob, key, rounds=9, mesh=_mesh1(),
                              merge="launch", rounds_per_launch=8)
    with pytest.raises(ValueError, match="trace_every=7"):
        shotgun_sharded_solve(prob, key, rounds=10, mesh=_mesh1(),
                              trace_every=7)
    with pytest.raises(ValueError, match="hierarchical"):
        shotgun_sharded_solve(prob, key, rounds=4, mesh=_mesh1(),
                              hierarchical=True)
    with pytest.raises(ValueError, match="local blocks"):
        shotgun_sharded_solve(prob, key, rounds=4, mesh=_mesh1(),
                              engine="fused", K=64)


def test_kernel_shape_checks_raise_value_error_not_assert():
    """Tiling checks survive ``python -O``: they must be ValueErrors."""
    from repro.kernels.shotgun_block import fused_shotgun_rounds

    def call(n, d, **kw):
        v = jnp.zeros(n)
        return fused_shotgun_rounds(jnp.zeros((n, d)), v, jnp.zeros(d),
                                    jnp.zeros((1, 1), jnp.int32), 1.0, 1.0,
                                    v, jnp.ones(n), **kw)
    with pytest.raises(ValueError, match="block"):
        call(256, 200)                 # 200 % 128 != 0
    with pytest.raises(ValueError, match="tile_n"):
        call(250, 256, tile_n=512)     # 250 % 512 != 0


# ---------------------------------------------------------------------------
# Warm starts + λ-path over the solver registry
# ---------------------------------------------------------------------------

def test_block_solver_warm_start(prob):
    """x0 warm start: the first traced objective continues from F(x0), not
    from F(0), and the returned margin stays consistent with x."""
    key = jax.random.PRNGKey(3)
    warm = ops.block_shotgun_solve(prob, key, K=2, rounds=64)
    res = ops.block_shotgun_solve(prob, key, K=2, rounds=8,
                                  x0=warm.x)
    f_warm0 = float(res.trace.objective[0])
    f_cold0 = float(ops.block_shotgun_solve(
        prob, key, K=2, rounds=8).trace.objective[0])
    assert f_warm0 < f_cold0
    assert f_warm0 <= float(warm.trace.objective[-1]) * 1.01
    np.testing.assert_allclose(np.asarray(res.z),
                               np.asarray(prob.A @ res.x),
                               rtol=2e-3, atol=2e-3)


def test_sharded_solver_warm_start(prob):
    key = jax.random.PRNGKey(3)
    warm = ops.block_shotgun_solve(prob, key, K=2, rounds=64)
    res = shotgun_sharded_solve(prob, key, P_local=4, rounds=20,
                                mesh=_mesh1(), x0=warm.x)
    assert float(res.trace.objective[0]) < float(
        shotgun_sharded_solve(prob, key, P_local=4, rounds=20,
                              mesh=_mesh1()).trace.objective[0])


@pytest.mark.parametrize("name", [
    "shotgun", pytest.param(("block_fused", "lasso"), id="block_fused-lasso"),
    "block_fused"])
def test_solve_path_runs_on_registry_solvers(name):
    from repro.core.path import solve_path
    A, y, _ = syn.sparco(seed=0, n=512, d=1024)
    prob = obj.make_problem(A, y, lam=0.5)
    # P=128 (one 128-block for the Pallas solvers) respects P* here
    res = solve_path(prob, jax.random.PRNGKey(0), lam_target=0.5, P=128,
                     rounds_per_lambda=16, num_lambdas=3, solver=name)
    assert res.x.shape == (prob.d,)
    assert res.lambdas.shape == (3,)
    assert np.all(np.isfinite(res.objectives))
    # continuation must not end above the direct single-λ solve by much
    direct = float(obj.objective(jnp.zeros(prob.d), prob))
    assert res.objectives[-1] < direct


def test_solve_path_unknown_solver():
    from repro.core.path import solve_path
    A, y, _ = syn.sparco(seed=0, n=64, d=128)
    prob = obj.make_problem(A, y, lam=0.5)
    with pytest.raises(ValueError, match="unknown solver"):
        solve_path(prob, jax.random.PRNGKey(0), lam_target=0.5, solver="nope")


# ---------------------------------------------------------------------------
# Multi-device behavior (8 forced host devices, own process)
# ---------------------------------------------------------------------------

SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import objectives as obj
from repro.core.sharded import shotgun_sharded_solve, make_feature_mesh
from repro.core.shotgun import shotgun_solve
from repro.data import synthetic as syn

# Low-coherence design so the block engines' P_eff = shards*K*128 = 1024
# respects Thm 3.2 (P* ~ 855 here; merge="round" sampling without
# replacement across shards shrinks the interference term further).
A, y, _ = syn.sparse_imaging(seed=0, n=2048, d=8192, density=0.002)
prob = obj.make_problem(A, y, lam=0.5)
mesh8 = make_feature_mesh()
assert mesh8.devices.size == 8
f_ref = float(shotgun_solve(prob, jax.random.PRNGKey(1), P=256,
                            rounds=600).trace.objective[-1])

# fused engine, one psum per round, full 8-shard mesh
r = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=256,
                          mesh=mesh8, engine="fused", merge="round", K=1,
                          trace_every=8)
f = float(r.trace.objective[-1])
assert abs(f - f_ref) / f_ref < 0.10, (f, f_ref)
np.testing.assert_allclose(np.asarray(r.z), np.asarray(prob.A @ r.x),
                           rtol=2e-3, atol=2e-3)
print("FUSED_ROUND_OK")

# Δz compression with error feedback reaches parity with the dense merge
base = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                             mesh=mesh8, engine="fused", merge="round", K=1,
                             trace_every=8)
f0 = float(base.trace.objective[-1])
for scheme in ["int8", "topk"]:
    c = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), rounds=64,
                              mesh=mesh8, engine="fused", merge="round", K=1,
                              trace_every=8, compression=scheme,
                              topk_frac=0.25)
    fc = float(c.trace.objective[-1])
    assert abs(fc - f0) / f0 < 0.01, (scheme, fc, f0)
print("COMPRESSION_OK")

# merge="launch": R stale rounds per merge still converges when the merge
# window R*P_eff stays within the interference budget (Lemma 3.3 knob)
r = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), P_local=16,
                          rounds=1024, mesh=mesh8, merge="launch",
                          rounds_per_launch=4, trace_every=16)
f = float(r.trace.objective[-1])
assert abs(f - f_ref) / f_ref < 0.10, (f, f_ref)
print("SCALAR_LAUNCH_OK")

# fused merge="launch" on 2 shards: stale windows of R*K*128*2 = 512
# updates stay inside the interference budget and reach the reference
A2, y2, _ = syn.sparse_imaging(seed=1, n=2048, d=2048, density=0.002)
prob2 = obj.make_problem(A2, y2, lam=0.5)
f_ref2 = float(shotgun_solve(prob2, jax.random.PRNGKey(1), P=64,
                             rounds=800).trace.objective[-1])
mesh2 = Mesh(np.array(jax.devices()[:2]), ("f",))
r = shotgun_sharded_solve(prob2, jax.random.PRNGKey(0), rounds=256,
                          mesh=mesh2, engine="fused", merge="launch",
                          rounds_per_launch=2, K=1, trace_every=8)
f = float(r.trace.objective[-1])
assert abs(f - f_ref2) / f_ref2 < 0.10, (f, f_ref2)
print("FUSED_LAUNCH_OK")

# hierarchical (reduce-scatter inner / psum outer / all-gather) merge is a
# drop-in for the flat psum
meshh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "f"))
h0 = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), P_local=4,
                           rounds=64, mesh=meshh, trace_every=8)
h1 = shotgun_sharded_solve(prob, jax.random.PRNGKey(0), P_local=4,
                           rounds=64, mesh=meshh, trace_every=8,
                           hierarchical=True)
np.testing.assert_allclose(np.asarray(h0.trace.objective),
                           np.asarray(h1.trace.objective), rtol=1e-5)
print("HIERARCHICAL_OK")
"""


@pytest.mark.slow
def test_multidevice_engines():
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, timeout=900,
                         env={**os.environ, "PYTHONPATH": "src"})
    for tag in ["FUSED_ROUND_OK", "COMPRESSION_OK", "SCALAR_LAUNCH_OK",
                "FUSED_LAUNCH_OK", "HIERARCHICAL_OK"]:
        assert tag in out.stdout, out.stdout + out.stderr
