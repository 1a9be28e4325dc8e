"""Loss seam on the fused path (DESIGN §12): sparse logistic regression and
per-block Newton in the fused kernels, behind the unified SolverSpec /
get_solver((family, loss)) API.

Newton parity fixtures are deliberately well-conditioned (n > d, moderate
λ, cold start): on a separable design the no-line-search Newton steps ride
the h >= 1e-8 curvature floor into divergence, where fp noise is amplified
chaotically and kernel-vs-oracle comparison is meaningless — that regime
belongs to the §9 guard, not to a parity test."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.core.batched import (WarmStartCache, batch_meta_of,
                                batched_block_shotgun_solve)
from repro.core.shotgun import (diverged, get_solver, rounds_to_tolerance,
                                shotgun_solve)
from repro.core.spec import SolverSpec
from repro.core.spectral import p_star
from repro.data import synthetic as syn
from repro.kernels import ops, ref
from repro.kernels.shotgun_block import BLOCK, fused_shotgun_rounds
from repro.kernels.shotgun_sparse import fused_sparse_shotgun_rounds
from repro.launch.solver_serve import SolveRequest, SolverService


def _logistic_problem(seed=6, n=600, d=256, lam=0.5):
    A, y, _ = syn.logistic_data(seed=seed, n=n, d=d)
    return obj.make_problem(A, y, lam=lam, loss=obj.LOGISTIC)


def _bcsc_logistic_problem(seed=4, n=512, d=256, lam=0.3, density=0.05):
    S, y, _ = syn.logistic_data(seed=seed, n=n, d=d, density=density,
                                layout="bcsc")
    return obj.make_problem(S, y, lam=lam, loss=obj.LOGISTIC)


# ---------------------------------------------------------------------------
# Newton kernel twins vs the independent CDN-formulation oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile_n", [None, 128])
def test_fused_newton_matches_oracle(tile_n):
    prob = _logistic_problem(lam=1.0)
    Ap, yp, mask = ops.pad_problem(prob.A, prob.y)
    x = jnp.zeros(Ap.shape[1])
    z = jnp.zeros(Ap.shape[0])
    R, K = 8, 2
    idx = (jnp.arange(R * K, dtype=jnp.int32).reshape(R, K)
           % (Ap.shape[1] // BLOCK))

    xk, zk, fk, nk, _h = fused_shotgun_rounds(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask,
        loss="logistic_newton", tile_n=tile_n)
    xr, zr, fr, nr = ref.fused_shotgun_rounds_ref(
        Ap, z, x, idx, prob.lam, prob.beta, yp, mask, "logistic_newton",
        BLOCK)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


def test_fused_sparse_newton_matches_oracle():
    prob = _bcsc_logistic_problem(lam=1.0)
    rows, vals = prob.A.rows, prob.A.vals
    nblk = rows.shape[0]
    x = jnp.zeros(nblk * BLOCK)
    z = jnp.zeros(prob.n)
    R, K = 6, 1
    idx = (jnp.arange(R * K, dtype=jnp.int32).reshape(R, K) % nblk)

    xk, zk, fk, nk, _h = fused_sparse_shotgun_rounds(
        rows, vals, z, x, idx, prob.lam, prob.beta, prob.y,
        loss="logistic_newton")
    xr, zr, fr, nr = ref.fused_sparse_shotgun_rounds_ref(
        rows, vals, z, x, idx, prob.lam, prob.beta, prob.y,
        "logistic_newton")
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zk), np.asarray(zr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr))


# ---------------------------------------------------------------------------
# Fused logistic solver vs the scalar logistic solver (dense + BlockedCSC)
# ---------------------------------------------------------------------------

def test_fused_logistic_matches_scalar_solution_dense():
    """Same optimum from both paths — the fused logistic kernel IS Shotgun
    on Eq. 3 with P = K·128 coordinates (same x, not just same F)."""
    A, y, _ = syn.logistic_data(seed=3, n=1024, d=512)
    prob = obj.make_problem(A, y, lam=0.5, loss=obj.LOGISTIC)
    rf = ops.block_shotgun_solve(prob, jax.random.PRNGKey(0),
                                 spec=SolverSpec(loss="logistic", P=256,
                                                 rounds=600))
    rs = shotgun_solve(prob, jax.random.PRNGKey(1),
                       spec=SolverSpec(loss="logistic", P=256, rounds=1500))
    ff, fs = float(rf.trace.objective[-1]), float(rs.trace.objective[-1])
    assert abs(ff - fs) / abs(fs) < 1e-3, (ff, fs)
    np.testing.assert_allclose(np.asarray(rf.x), np.asarray(rs.x),
                               atol=1e-4)


def test_fused_logistic_matches_scalar_solution_bcsc():
    prob = _bcsc_logistic_problem()
    rf = ops.block_shotgun_solve(prob, jax.random.PRNGKey(0),
                                 spec=SolverSpec(loss="logistic", P=128,
                                                 rounds=600))
    rs = shotgun_solve(prob, jax.random.PRNGKey(1),
                       spec=SolverSpec(loss="logistic", P=128, rounds=2000))
    ff, fs = float(rf.trace.objective[-1]), float(rs.trace.objective[-1])
    assert abs(ff - fs) / abs(fs) < 1e-3, (ff, fs)
    np.testing.assert_allclose(np.asarray(rf.x), np.asarray(rs.x),
                               atol=1e-4)


def test_logistic_beta_quarter_converges_near_pstar():
    """β = 1/4 (Eq. 6) is the bound that keeps Shotgun's Thm 3.2 analysis
    valid for logistic loss: at P just under P* = d/ρ the fused logistic
    solve must still descend, not diverge."""
    A, y, _ = syn.logistic_data(seed=5, n=800, d=512)
    prob = obj.make_problem(A, y, lam=0.5, loss=obj.LOGISTIC)
    assert p_star(prob.A) >= BLOCK      # K=1 → P=128 is theory-legal
    r = ops.block_shotgun_solve(prob, jax.random.PRNGKey(0),
                                spec=SolverSpec(loss="logistic", P=BLOCK,
                                                rounds=200))
    tr = np.asarray(r.trace.objective)
    assert not bool(diverged(tr))
    assert tr[-1] < tr[0]


def test_newton_beats_gradient_rounds_to_tolerance():
    """Per-block Newton (Bian et al.): with the true curvature
    h_b = Σ a² σ(1-σ) instead of the worst-case β = 1/4, the same target
    objective is reached in fewer rounds on a well-conditioned problem."""
    prob = _logistic_problem()
    key = jax.random.PRNGKey(0)
    rg = ops.block_shotgun_solve(prob, key, spec=SolverSpec(
        loss="logistic", P=256, rounds=64))
    rn = ops.block_shotgun_solve(prob, key, spec=SolverSpec(
        loss="logistic", P=256, rounds=64, newton=True))
    fg, fn = np.asarray(rg.trace.objective), np.asarray(rn.trace.objective)
    fstar = min(fg.min(), fn.min())
    r_grad = int(rounds_to_tolerance(fg, fstar, 0.005))
    r_newton = int(rounds_to_tolerance(fn, fstar, 0.005))
    assert r_newton < r_grad, (r_newton, r_grad)


# ---------------------------------------------------------------------------
# SolverSpec: one spec in, bit-for-bit the legacy trajectory out
# ---------------------------------------------------------------------------

def test_spec_shim_bit_for_bit_scalar():
    prob = _logistic_problem(n=300, d=256)
    key = jax.random.PRNGKey(2)
    with pytest.warns(DeprecationWarning):
        r_old = shotgun_solve(prob, key, P=64, rounds=5)
    r_new = shotgun_solve(prob, key, spec=SolverSpec(loss="logistic", P=64,
                                                     rounds=5))
    np.testing.assert_array_equal(np.asarray(r_old.x), np.asarray(r_new.x))
    np.testing.assert_array_equal(np.asarray(r_old.trace.objective),
                                  np.asarray(r_new.trace.objective))


def test_spec_shim_bit_for_bit_fused():
    prob = _logistic_problem(n=300, d=256)
    key = jax.random.PRNGKey(2)
    with pytest.warns(DeprecationWarning):
        r_old = ops.block_shotgun_solve(prob, key, K=1, rounds=8)
    r_new = ops.block_shotgun_solve(prob, key, spec=SolverSpec(
        loss="logistic", P=128, rounds=8))
    np.testing.assert_array_equal(np.asarray(r_old.x), np.asarray(r_new.x))
    np.testing.assert_array_equal(np.asarray(r_old.trace.objective),
                                  np.asarray(r_new.trace.objective))


def test_spec_shim_bit_for_bit_batched():
    probs = [_logistic_problem(seed=s, n=200, d=128) for s in (7, 8)]
    keys = [jax.random.PRNGKey(i) for i in range(2)]
    with pytest.warns(DeprecationWarning):
        old = batched_block_shotgun_solve(probs, keys, 1, 4,
                                          rounds_per_launch=4)
    new = batched_block_shotgun_solve(probs, keys, rounds_per_launch=4,
                                      spec=SolverSpec(loss="logistic",
                                                      P=128, rounds=4))
    np.testing.assert_array_equal(np.asarray(old.x), np.asarray(new.x))
    np.testing.assert_array_equal(np.asarray(old.trace.objective),
                                  np.asarray(new.trace.objective))


def test_spec_rejects_mixed_interfaces_and_bad_combos():
    prob = _logistic_problem(n=200, d=128)
    spec = SolverSpec(loss="logistic", P=128, rounds=4)
    with pytest.raises(ValueError, match="spec"):
        ops.block_shotgun_solve(prob, jax.random.PRNGKey(0), K=1, rounds=4,
                                spec=spec)
    # the two-kernel round is retired: a spec that asks for it is refused
    with pytest.raises(ValueError, match="two-kernel"):
        SolverSpec(loss="logistic", P=128, rounds=4, fused=False)
    # spec loss must match the problem's loss
    lasso = obj.make_problem(*syn.sparco(seed=0, n=128, d=256)[:2], lam=0.5)
    with pytest.raises(ValueError) as ei:
        ops.block_shotgun_solve(lasso, jax.random.PRNGKey(0), spec=spec)
    assert "logistic" in str(ei.value) and "lasso" in str(ei.value)


# ---------------------------------------------------------------------------
# get_solver: (family, loss) pairs and the frozen *_logreg_fused aliases
# ---------------------------------------------------------------------------

def test_get_solver_family_loss_pair_admission():
    solver = get_solver(("block_fused", "logistic"))
    prob = _logistic_problem(n=200, d=128)
    r = solver(prob, jax.random.PRNGKey(0), 1, 2, rounds_per_launch=2)
    assert np.isfinite(float(r.trace.objective[-1]))
    lasso = obj.make_problem(*syn.sparco(seed=0, n=128, d=256)[:2], lam=0.5)
    with pytest.raises(ValueError) as ei:
        solver(lasso, jax.random.PRNGKey(0), 1, 2)
    assert "logistic" in str(ei.value) and "lasso" in str(ei.value)
    with pytest.raises(ValueError, match="unknown loss"):
        get_solver(("block_fused", "huber"))


def test_logreg_fused_aliases():
    prob = _logistic_problem(n=200, d=128)
    r = get_solver("shotgun_logreg_fused")(
        prob, jax.random.PRNGKey(0), 1, 2, rounds_per_launch=2)
    assert np.isfinite(float(r.trace.objective[-1]))
    # the sparse alias insists on a BlockedCSC design
    with pytest.raises(ValueError, match="BlockedCSC"):
        get_solver("sparse_logreg_fused")(prob, jax.random.PRNGKey(0), 1, 2)
    sprob = _bcsc_logistic_problem()
    rs = get_solver("sparse_logreg_fused")(
        sprob, jax.random.PRNGKey(0), 1, 2, rounds_per_launch=2)
    assert np.isfinite(float(rs.trace.objective[-1]))
    # the alias speaks spec= too, and refuses the mixed spec+legacy
    # interface like every entry point
    r2 = get_solver("shotgun_logreg_fused")(
        prob, jax.random.PRNGKey(0), rounds_per_launch=2,
        spec=SolverSpec(loss="logistic", P=128, rounds=2))
    assert np.array_equal(np.asarray(r.x), np.asarray(r2.x))
    with pytest.raises(ValueError, match="spec"):
        get_solver("shotgun_logreg_fused")(
            prob, jax.random.PRNGKey(0), K=1,
            spec=SolverSpec(loss="logistic", P=128, rounds=2))


# ---------------------------------------------------------------------------
# Serving: loss-tagged streams and warm cache
# ---------------------------------------------------------------------------

def test_mixed_loss_stream_rejected():
    A, y, _ = syn.sparco(seed=0, n=128, d=256)
    lasso = obj.make_problem(A, y, lam=0.5)
    svc = SolverService(batch_meta_of(lasso), slots=1, max_rounds=8,
                        rounds_per_launch=8)
    req = SolveRequest(rid=0, problem_id="q0",
                       prob=_logistic_problem(n=128, d=256),
                       key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError) as ei:
        svc.serve([req])
    msg = str(ei.value)
    assert "mixed-loss stream" in msg
    assert "logistic" in msg and "lasso" in msg


def test_warm_cache_keys_carry_loss():
    cache = WarmStartCache()
    x = np.ones(8, np.float32)
    cache.put("p0", 0.5, x, loss="logistic")
    x0, kind = cache.get("p0", 0.5)            # legacy default: lasso
    assert x0 is None and kind == "miss"
    x1, kind1 = cache.get("p0", 0.5, loss="logistic")
    assert kind1 == "exact"
    np.testing.assert_array_equal(x1, x)


# ---------------------------------------------------------------------------
# Problem construction: logistic label validation
# ---------------------------------------------------------------------------

def test_make_problem_rejects_bad_logistic_labels():
    A = np.eye(4, dtype=np.float32)
    with pytest.raises(ValueError) as ei:
        obj.make_problem(A, np.array([1.0, -1.0, 0.0, 2.0]), lam=0.1,
                         loss=obj.LOGISTIC)
    msg = str(ei.value)
    assert "0.0" in msg and "2.0" in msg and "2/4" in msg
    # same labels are fine for lasso (real-valued y)
    obj.make_problem(A, np.array([1.0, -1.0, 0.0, 2.0]), lam=0.1)
    # and valid ±1 labels construct
    obj.make_problem(A, np.array([1.0, -1.0, -1.0, 1.0]), lam=0.1,
                     loss=obj.LOGISTIC)
