"""Self-healing solver stack (DESIGN §9): divergence sentinel, adaptive-P
backoff, Δz fault injection, and checkpointed sharded solves.

The headline regime is Thm 3.2's dark side: on a correlated design with
P = 8·P* the unguarded solver genuinely diverges; the guarded one must
detect it in-flight, roll back to the last-good iterate, back its
parallelism off toward P*, and still reach the paper's 0.5%-of-F*
convergence criterion.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import health
from repro.core import objectives as obj
from repro.core import spectral
from repro.core.baselines.fista import fista_solve
from repro.core.health import GuardConfig, SolverFailure
from repro.core.sharded import shotgun_sharded_solve
from repro.core.shotgun import diverged, rounds_to_tolerance, shotgun_solve
from repro.data import synthetic as syn
from repro.dist.faults import FaultPlan, inject_dz
from repro.kernels import ops

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def corr_prob():
    """Correlated columns push rho(A^T A) up and P* down to ~3 — the
    divergent regime of Fig. 2 at any interesting P."""
    A, y, _ = syn.sparco(seed=0, n=256, d=512, corr=0.5)
    return obj.make_problem(A, y, lam=1.0)


# ---------------------------------------------------------------------------
# Scalar solver: sentinel + backoff recovers a diverging solve to F*
# ---------------------------------------------------------------------------

def test_scalar_guard_recovers_divergent_solve(corr_prob):
    ps = spectral.p_star(corr_prob.A)
    P = 8 * ps                      # far past Thm 3.2's safe parallelism
    key = jax.random.PRNGKey(0)

    r_un = shotgun_solve(corr_prob, key, P=P, rounds=6000)
    assert int(r_un.status) == health.STATUS_DIVERGED
    assert bool(diverged(r_un.trace.objective))

    fstar = fstar_corr(corr_prob)
    r_g = shotgun_solve(corr_prob, key, P=P, rounds=6000,
                        guard=GuardConfig(factor=10.0, p_min=ps))
    f = r_g.trace.objective
    assert bool(jnp.all(jnp.isfinite(f)))          # rollback keeps the trace sane
    assert int(r_g.status) == health.STATUS_RECOVERED
    gap = (float(f[-1]) - fstar) / abs(fstar)
    assert gap <= 0.005, f"guarded solve gap {gap:.2%} > 0.5% of F*"
    # the backoff must have clamped at the floor, not below it
    assert int(rounds_to_tolerance(f, fstar)) < 6000


_FSTAR_CACHE = {}


def fstar_corr(prob):
    k = (id(prob))
    if k not in _FSTAR_CACHE:
        _FSTAR_CACHE[k] = float(fista_solve(prob, iters=3000).objective[-1])
    return _FSTAR_CACHE[k]


def test_guard_is_bitexact_noop_at_safe_p(corr_prob):
    ps = spectral.p_star(corr_prob.A)
    key = jax.random.PRNGKey(1)
    r0 = shotgun_solve(corr_prob, key, P=ps, rounds=400)
    r1 = shotgun_solve(corr_prob, key, P=ps, rounds=400,
                       guard=GuardConfig(factor=10.0, p_min=1))
    np.testing.assert_array_equal(np.asarray(r0.trace.objective),
                                  np.asarray(r1.trace.objective))
    assert int(r0.status) == health.STATUS_OK
    assert int(r1.status) == health.STATUS_OK


# ---------------------------------------------------------------------------
# Fused Pallas solver: in-kernel sentinel + launch-granular backoff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["dense", "bcsc"])
def test_fused_guard_backs_off_and_recovers(layout):
    A, y, _ = syn.sparco(seed=0, n=256, d=2048)   # d >> n: rho > d, P* = 1
    if layout == "bcsc":
        from repro.data.sparse import BlockedCSC
        A = BlockedCSC.from_dense(A)
    prob = obj.make_problem(A, y, lam=1.0)
    key = jax.random.PRNGKey(0)

    r_un = ops.block_shotgun_solve(prob, key, K=16, rounds=96,
                                   rounds_per_launch=8)
    assert int(r_un.status) == health.STATUS_DIVERGED

    r_g = ops.block_shotgun_solve(prob, key, K=16, rounds=96,
                                  rounds_per_launch=8,
                                  guard=GuardConfig(factor=10.0, p_min=1))
    f = r_g.trace.objective
    assert int(r_g.status) == health.STATUS_RECOVERED
    assert bool(jnp.all(jnp.isfinite(f)))
    # after backing off to a safe K the solve makes real progress again
    assert float(f[-1]) < 0.5 * float(f[0])


# ---------------------------------------------------------------------------
# Fault-injected Δz merges: checksummed re-merge keeps objective parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_prob():
    A, y, _ = syn.sparco(seed=0, n=128, d=512)
    return obj.make_problem(A, y, lam=1.0)


def test_faulted_merges_reach_objective_parity(mesh_prob):
    key = jax.random.PRNGKey(1)
    clean = shotgun_sharded_solve(mesh_prob, key, P_local=8, rounds=400,
                                  trace_every=4)
    plan = FaultPlan(drop_prob=0.1, corrupt_prob=0.05, max_retries=3)
    faulted = shotgun_sharded_solve(mesh_prob, key, P_local=8, rounds=400,
                                    trace_every=4, faults=plan,
                                    guard=GuardConfig(factor=10.0, p_min=4))
    f0 = float(clean.trace.objective[-1])
    f1 = float(faulted.trace.objective[-1])
    assert int(faulted.status) != health.STATUS_DIVERGED
    assert abs(f1 - f0) / abs(f0) <= 0.01, (f1, f0)


def test_nan_corruption_always_caught_by_checksum(mesh_prob):
    # every merge NaN-corrupts on the first attempt; retry_decay=0 makes the
    # retry fault-free, so the accepted merge is the clean psum and the
    # trajectory matches the fault-free run EXACTLY
    key = jax.random.PRNGKey(1)
    clean = shotgun_sharded_solve(mesh_prob, key, P_local=8, rounds=100,
                                  trace_every=4)
    plan = FaultPlan(corrupt_prob=1.0, corrupt_nan=True, max_retries=1,
                     retry_decay=0.0)
    faulted = shotgun_sharded_solve(mesh_prob, key, P_local=8, rounds=100,
                                    trace_every=4, faults=plan)
    np.testing.assert_array_equal(np.asarray(clean.trace.objective),
                                  np.asarray(faulted.trace.objective))
    np.testing.assert_array_equal(np.asarray(clean.x), np.asarray(faulted.x))


def test_inject_dz_modes():
    dz = jnp.ones(16)
    key = jax.random.PRNGKey(0)
    drop = inject_dz(dz, key, FaultPlan(drop_prob=1.0))
    np.testing.assert_array_equal(np.asarray(drop), 0.0)
    dup = inject_dz(dz, key, FaultPlan(dup_prob=1.0))
    np.testing.assert_array_equal(np.asarray(dup), 2.0)
    bad = inject_dz(dz, key, FaultPlan(corrupt_prob=1.0, corrupt_nan=True))
    assert bool(jnp.all(jnp.isnan(bad)))
    clean = inject_dz(dz, key, FaultPlan(drop_prob=1.0), scale=0.0)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dz))


def test_faults_with_hierarchical_still_needs_2d_mesh(mesh_prob):
    """faults= composes with hierarchical= (the re-merge rides the
    inter-pod hop, exercised on a real 4x4 mesh in test_async_pipeline),
    but the mesh-shape validation still applies."""
    with pytest.raises(ValueError, match="2-D"):
        shotgun_sharded_solve(mesh_prob, jax.random.PRNGKey(0), P_local=2,
                              rounds=8, faults=FaultPlan(drop_prob=0.1),
                              hierarchical=True)


# ---------------------------------------------------------------------------
# Checkpointed sharded solves: kill mid-run, resume, match exactly
# ---------------------------------------------------------------------------

def test_sharded_ckpt_kill_resume_matches(mesh_prob, tmp_path):
    key = jax.random.PRNGKey(1)
    kw = dict(P_local=8, rounds=200, trace_every=4, ckpt_every=20)
    ref = shotgun_sharded_solve(mesh_prob, key, **kw)    # uninterrupted

    with pytest.raises(SolverFailure):
        shotgun_sharded_solve(mesh_prob, key, ckpt_dir=tmp_path,
                              fail_at_merge=100, **kw)
    res = shotgun_sharded_solve(mesh_prob, key, ckpt_dir=tmp_path,
                                resume=True, **kw)
    np.testing.assert_array_equal(np.asarray(ref.trace.objective),
                                  np.asarray(res.trace.objective))
    np.testing.assert_array_equal(np.asarray(ref.x), np.asarray(res.x))
    np.testing.assert_allclose(np.asarray(res.z),
                               np.asarray(mesh_prob.A @ res.x),
                               rtol=1e-4, atol=1e-4)


def test_segmentation_validates_cadence(mesh_prob):
    with pytest.raises(ValueError, match="ckpt_every"):
        shotgun_sharded_solve(mesh_prob, jax.random.PRNGKey(0), P_local=2,
                              rounds=200, trace_every=4, ckpt_every=30)
    with pytest.raises(ValueError, match="ckpt_every"):
        shotgun_sharded_solve(mesh_prob, jax.random.PRNGKey(0), P_local=2,
                              rounds=200, fail_at_merge=10)


# ---------------------------------------------------------------------------
# NaN/Inf edge cases in the convergence utilities
# ---------------------------------------------------------------------------

def test_objective_from_margin_propagates_nonfinite():
    A = jnp.eye(4)
    prob = obj.make_problem(A, jnp.ones(4), lam=0.5)
    x = jnp.zeros(4)
    f_nan = obj.objective_from_margin(jnp.full(4, jnp.nan), x, prob)
    assert not bool(jnp.isfinite(f_nan))
    f_inf = obj.objective_from_margin(jnp.full(4, jnp.inf), x, prob)
    assert not bool(jnp.isfinite(f_inf))


def test_rounds_to_tolerance_ignores_nonfinite_hits():
    # NaN compares false anyway; -inf would look like an excellent objective
    t = jnp.array([10.0, jnp.nan, -jnp.inf, 5.0])
    assert int(rounds_to_tolerance(t, 5.0)) == 3
    t_bad = jnp.array([jnp.nan, -jnp.inf, jnp.nan])
    assert int(rounds_to_tolerance(t_bad, 5.0)) == 3   # never reached


def test_diverged_scans_full_trace():
    assert bool(diverged(jnp.array([10.0, jnp.nan, 8.0])))    # mid-trace NaN
    assert bool(diverged(jnp.array([10.0, 9.0, 1e9])))        # blown up
    assert not bool(diverged(jnp.array([10.0, 9.0, 8.0])))


def test_status_from_trace_precedence():
    good = jnp.array([10.0, 9.0, 8.0])
    bad = jnp.array([10.0, jnp.nan, 8.0])
    assert int(health.status_from_trace(good)) == health.STATUS_OK
    assert int(health.status_from_trace(good, backoffs=jnp.int32(2))) \
        == health.STATUS_RECOVERED
    # divergence wins over a nonzero backoff count
    assert int(health.status_from_trace(bad, backoffs=jnp.int32(2))) \
        == health.STATUS_DIVERGED


def test_solve_path_clamps_unsafe_p(corr_prob):
    from repro.core.path import solve_path
    with pytest.warns(UserWarning, match="exceeds the Thm 3.2"):
        res = solve_path(corr_prob, jax.random.PRNGKey(0),
                         lam_target=float(corr_prob.lam), P=64,
                         rounds_per_lambda=200, num_lambdas=3)
    assert np.all(np.isfinite(res.objectives))


# ---------------------------------------------------------------------------
# Sentinel overhead on the fused hot path (committed perf trajectory)
# ---------------------------------------------------------------------------

def test_sentinel_overhead_within_budget():
    data = json.loads((REPO / "BENCH_kernels.json").read_text())
    rows = data["rows"] if isinstance(data, dict) else data
    checked = [r for r in rows if "sentinel_overhead_pct" in r]
    assert checked, "BENCH_kernels.json has no sentinel_overhead_pct rows"
    for r in checked:
        assert r["sentinel_overhead_pct"] <= 5.0, r


# ---------------------------------------------------------------------------
# Multi-device behavior (8 forced host devices, own process)
# ---------------------------------------------------------------------------

SUB = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import objectives as obj
from repro.core.health import GuardConfig, SolverFailure
from repro.core.sharded import make_feature_mesh, shotgun_sharded_solve
from repro.dist.faults import FaultPlan
from repro.data import synthetic as syn

A, y, _ = syn.sparco(seed=0, n=128, d=512)
prob = obj.make_problem(A, y, lam=1.0)
mesh = make_feature_mesh()
assert mesh.devices.size == 8
key = jax.random.PRNGKey(1)

# guarded solve under injected drop+corrupt faults reaches objective parity
clean = shotgun_sharded_solve(prob, key, P_local=8, rounds=800, mesh=mesh,
                              trace_every=4)
plan = FaultPlan(drop_prob=0.05, corrupt_prob=0.02, max_retries=3)
faulted = shotgun_sharded_solve(prob, key, P_local=8, rounds=800, mesh=mesh,
                                trace_every=4, faults=plan,
                                guard=GuardConfig(factor=10.0, p_min=4))
f0 = float(clean.trace.objective[-1])
f1 = float(faulted.trace.objective[-1])
assert abs(f1 - f0) / abs(f0) <= 0.01, (f1, f0)
print("FAULT_MESH_OK")

# kill an 8-shard checkpointed solve mid-path, resume on the same mesh,
# match the uninterrupted segmented trajectory exactly
kw = dict(P_local=8, rounds=200, mesh=mesh, trace_every=4, ckpt_every=20)
ref = shotgun_sharded_solve(prob, key, **kw)
with tempfile.TemporaryDirectory() as tmp:
    died = False
    try:
        shotgun_sharded_solve(prob, key, ckpt_dir=tmp, fail_at_merge=100, **kw)
    except SolverFailure:
        died = True
    assert died
    res = shotgun_sharded_solve(prob, key, ckpt_dir=tmp, resume=True, **kw)
np.testing.assert_array_equal(np.asarray(ref.trace.objective),
                              np.asarray(res.trace.objective))
np.testing.assert_array_equal(np.asarray(ref.x), np.asarray(res.x))
print("CKPT_MESH_OK")
"""


@pytest.mark.slow
def test_multidevice_health():
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, timeout=900,
                         env={**os.environ, "PYTHONPATH": "src"})
    for tag in ["FAULT_MESH_OK", "CKPT_MESH_OK"]:
        assert tag in out.stdout, out.stdout + out.stderr
