"""Continuous-batched solving + the solver service (DESIGN §11).

Four contracts, each load-bearing for serving:

  * slot exactness — ``batched_block_shotgun_solve`` slot i is
    bit-identical in x to the standalone fused solve with the same key
    (dense and BlockedCSC): batching changes the grid, never the math;
  * admission normalization — a problem padded onto a larger canvas
    (features, nnz tiles) solves bit-identically to the standalone solve
    of the explicitly padded problem;
  * refill determinism — a served stream's per-request results equal
    solving the queue one-at-a-time: results cannot depend on slot
    assignment, co-tenants, or eviction history;
  * warm starts — a repeated (problem_id, λ) skips ≥ half the cold
    rounds, and a second cached ``solve_path`` sweep spends strictly
    fewer total rounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as obj
from repro.core.batched import (WarmStartCache, batch_meta_of,
                                batched_block_shotgun_solve,
                                launch_converged, launch_rounds,
                                normalize_problem, stack_problems)
from repro.core.path import solve_path
from repro.data import synthetic as syn
from repro.data.sparse import BlockedCSC
from repro.kernels import ops
from repro.launch.slots import SlotBoard
from repro.launch.solver_serve import (SolveRequest, SolverService,
                                       make_stream, solve_queue_sequential)

K, ROUNDS, R = 2, 8, 4


def _dense_probs(num=3, n=192, d=384, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(num):
        A = rng.standard_normal((n, d)).astype(np.float32)
        y = rng.standard_normal(n).astype(np.float32)
        out.append(obj.make_problem(jnp.asarray(A), jnp.asarray(y),
                                    lam=0.1 * (s + 1)))
    return out


def _sparse_probs(num=2, n=192, d=384, seed=0, tile=None):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        A = rng.standard_normal((n, d)).astype(np.float32)
        A[rng.random((n, d)) < 0.8] = 0.0
        y = rng.standard_normal(n).astype(np.float32)
        p = obj.make_problem(jnp.asarray(A), jnp.asarray(y), lam=0.1)
        out.append(p._replace(A=BlockedCSC.from_dense(p.A, block=128,
                                                      tile=tile)))
    return out


# ---------------------------------------------------------------------------
# Slot exactness: batched slot i == standalone solve, bit for bit
# ---------------------------------------------------------------------------

def test_batched_dense_slots_bit_identical_to_standalone():
    probs = _dense_probs()
    keys = [jax.random.PRNGKey(7 + s) for s in range(len(probs))]
    res = batched_block_shotgun_solve(probs, keys, K, ROUNDS,
                                      rounds_per_launch=R)
    for s, (p, k) in enumerate(zip(probs, keys)):
        ref = ops.block_shotgun_solve(p, k, K, ROUNDS,
                                      rounds_per_launch=R)
        assert np.array_equal(np.asarray(res.x[s][: p.d]),
                              np.asarray(ref.x)), f"slot {s}"
        assert np.array_equal(np.asarray(res.trace.objective[s]),
                              np.asarray(ref.trace.objective)), f"slot {s}"


def test_batched_sparse_slots_bit_identical_to_standalone():
    # equal nnz-tile depth across the stack → slot i must equal the
    # standalone solve of the ORIGINAL problem bit for bit
    probs = _sparse_probs(tile=64)
    keys = [jax.random.PRNGKey(99 + s) for s in range(len(probs))]
    res = batched_block_shotgun_solve(probs, keys, K, ROUNDS,
                                      rounds_per_launch=R)
    for s, (p, k) in enumerate(zip(probs, keys)):
        ref = ops.block_shotgun_solve(p, k, K, ROUNDS,
                                      rounds_per_launch=R)
        assert np.array_equal(np.asarray(res.x[s][: p.d]),
                              np.asarray(ref.x)), f"slot {s}"


def test_heterogeneous_tile_admission_matches_stream_tiling():
    """Auto-tiled BCSC problems carry different nnz-tile depths; admission
    pads the shallow ones with (row 0, val 0) identity entries.  The padded
    problem IS the same matrix, so slot i must equal the standalone solve
    on the stream's tiling bit for bit (fp reduction order follows the
    tile depth, so the reference must share it — DESIGN §11.2)."""
    probs = _sparse_probs(tile=None)        # auto tiles: 56 and 64 here
    tiles = {p.A.tile for p in probs}
    meta, _ = stack_problems(probs)
    assert meta.tile == max(tiles)
    keys = [jax.random.PRNGKey(5 + s) for s in range(len(probs))]
    res = batched_block_shotgun_solve(probs, keys, K, ROUNDS,
                                      rounds_per_launch=R)
    for s, (p, k) in enumerate(zip(probs, keys)):
        S = p.A
        if S.tile < meta.tile:
            pad = ((0, 0), (0, meta.tile - S.tile), (0, 0))
            S = BlockedCSC(rows=jnp.pad(S.rows, pad),
                           vals=jnp.pad(S.vals, pad),
                           n=S.n, d=S.d, block=S.block)
        ref = ops.block_shotgun_solve(p._replace(A=S), k, K, ROUNDS,
                                      rounds_per_launch=R)
        assert np.array_equal(np.asarray(res.x[s][: p.d]),
                              np.asarray(ref.x)), f"slot {s}"


def test_frozen_slot_is_bit_exact_noop():
    """k_eff = 0 must freeze a slot exactly (the admission contract for
    empty/converged slots) without perturbing live ones."""
    probs = _dense_probs(num=2)
    meta, stacked = stack_problems(probs)
    x0 = jnp.zeros((2, meta.d_pad), jnp.float32)
    z0 = jnp.zeros((2, meta.n_pad), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(3)] * 2)
    idx = jax.vmap(lambda k: jax.random.choice(
        k, meta.nblk, (R, K), replace=True))(keys).astype(jnp.int32)
    x, z, fs, _, _ = launch_rounds(meta, stacked, z0, x0, idx,
                                   jnp.array([0.0, float(K)]))
    assert np.array_equal(np.asarray(x[0]), np.asarray(x0[0]))
    assert np.array_equal(np.asarray(z[0]), np.asarray(z0[0]))
    assert np.any(np.asarray(x[1]) != 0)    # the live slot actually moved


def test_stack_problems_rejects_mixed_streams():
    dense = _dense_probs(num=1)[0]
    sparse = _sparse_probs(num=1)[0]
    with pytest.raises(ValueError, match="heterogeneous stream"):
        stack_problems([dense, sparse])
    meta = batch_meta_of(dense)
    small = _dense_probs(num=1, n=64, d=128, seed=9)[0]
    with pytest.raises(ValueError, match="sample"):
        normalize_problem(small, meta)


# ---------------------------------------------------------------------------
# Refill determinism: served stream == one-at-a-time queue
# ---------------------------------------------------------------------------

def _fresh_stream(**kw):
    kw.setdefault("requests", 6)
    kw.setdefault("repeat_frac", 0.0)
    kw.setdefault("lam", 2.0)
    return make_stream(192, 384, **kw)


def _clone(reqs):
    return [SolveRequest(rid=r.rid, problem_id=r.problem_id, prob=r.prob,
                         key=r.key) for r in reqs]


def test_served_stream_matches_sequential_queue():
    """Per-request results must be independent of slot assignment and
    co-tenants: the 3-slot served stream equals solving the queue through
    a 1-slot service, request by request, bit for bit.  Distinct
    problem_ids + a fresh cache per run keep warm starts out of the
    comparison (they are exercised separately below)."""
    reqs = _fresh_stream()
    for r in reqs:
        r.problem_id = ("solo", r.rid)      # no cross-request cache hits
    kw = dict(K=1, max_rounds=24, rounds_per_launch=8, tol=1e-4)
    svc = SolverService(batch_meta_of(reqs[0].prob), slots=3,
                        cache=WarmStartCache(), **kw)
    served = {r.rid: r for r in svc.serve(_clone(reqs))}
    seq = {r.rid: r for r in solve_queue_sequential(
        _clone(reqs), cache=WarmStartCache(), **kw)}
    assert sorted(served) == sorted(seq) == [r.rid for r in reqs]
    for rid in served:
        a, b = served[rid], seq[rid]
        assert a.status == b.status, rid
        assert a.rounds_used == b.rounds_used, rid
        assert np.array_equal(a.x, b.x), rid


def test_served_stream_deterministic_under_eviction():
    """Round-deadline eviction re-queues a solve and resumes it from its
    partial iterate; the final per-request results must still match the
    eviction-free serve (the request's draw schedule is fixed at first
    admission, and the resumed x0 is exactly the evicted iterate)."""
    reqs = _fresh_stream(requests=4)
    for r in reqs:
        r.problem_id = ("solo", r.rid)
    kw = dict(K=1, max_rounds=24, rounds_per_launch=8, tol=1e-4)
    plain = {r.rid: r for r in SolverService(
        batch_meta_of(reqs[0].prob), slots=2, cache=WarmStartCache(),
        **kw).serve(_clone(reqs))}
    evicting = {r.rid: r for r in SolverService(
        batch_meta_of(reqs[0].prob), slots=2, cache=WarmStartCache(),
        deadline_launches=1, max_evictions=10, **kw).serve(_clone(reqs))}
    assert any(r.evictions > 0 for r in evicting.values())
    for rid in plain:
        assert np.array_equal(evicting[rid].x, plain[rid].x), rid
        assert evicting[rid].rounds_used == plain[rid].rounds_used, rid


# ---------------------------------------------------------------------------
# Warm starts
# ---------------------------------------------------------------------------

def test_warm_cache_hit_skips_half_the_cold_rounds():
    """Repeat traffic over a shared design: the repeated (problem_id, λ)
    solves must spend ≤ half the rounds of their cold counterparts."""
    reqs = make_stream(256, 512, requests=8, repeat_frac=0.5, lam=2.0,
                       seed=0)
    svc = SolverService(batch_meta_of(reqs[0].prob), slots=4, K=1,
                        max_rounds=64, rounds_per_launch=8, tol=1e-4)
    done = {r.rid: r for r in svc.serve(reqs)}
    cold = [done[r].rounds_used for r in range(4)]
    warm = [done[r].rounds_used for r in range(4, 8)]
    assert all(done[r].status == "ok" for r in done)
    assert all(done[r].warm in ("exact", "near") for r in range(4, 8))
    assert sum(warm) <= 0.5 * sum(cold), (warm, cold)
    assert svc.cache.stats.hits_exact + svc.cache.stats.hits_near >= 4


def test_solve_path_cached_second_sweep_fewer_rounds():
    """solve_path(cache=...) shares the service's warm-start store: the
    second sweep over the same λ grid hits the cache at every point and
    must converge in strictly fewer total rounds."""
    A, y, _ = syn.sparco(seed=0, n=256, d=512)
    prob = obj.make_problem(A, y, lam=2.0)
    cache = WarmStartCache()
    kw = dict(lam_target=2.0, P=128, rounds_per_lambda=64, num_lambdas=4,
              solver="block_fused", validate_p=False,
              cache=cache, problem_id="p0")
    r1 = solve_path(prob, jax.random.PRNGKey(0), **kw)
    r2 = solve_path(prob, jax.random.PRNGKey(1), **kw)
    assert r1.rounds is not None and r2.rounds is not None
    assert int(r2.rounds.sum()) < int(r1.rounds.sum())
    # and the cached sweep must not land above the first one
    assert np.all(r2.objectives <= r1.objectives * (1 + 1e-5))


def test_warm_cache_nearest_lambda_fallback():
    cache = WarmStartCache()
    x5, x9 = np.full(4, 5.0), np.full(4, 9.0)
    cache.put("p", 0.5, x5)
    cache.put("p", 0.9, x9)
    got, kind = cache.get("p", 0.5)
    assert kind == "exact" and np.array_equal(got, x5)
    got, kind = cache.get("p", 0.55)
    assert kind == "near" and np.array_equal(got, x5)
    got, kind = cache.get("p", 5.0)
    assert kind == "near" and np.array_equal(got, x9)
    got, kind = cache.get("q", 0.5)
    assert got is None and kind == "miss"
    assert cache.stats.misses == 1 and cache.stats.hits_exact == 1


def test_launch_converged_rejects_overshoot():
    assert launch_converged(100.0, np.array([100.0, 100.001]), 1e-3)
    assert not launch_converged(100.0, np.array([100.0, 150.0]), 1e-3)
    assert not launch_converged(100.0, np.array([100.0, 50.0]), 1e-3)
    assert not launch_converged(100.0, np.array([100.0, np.nan]), 1e-3)


# ---------------------------------------------------------------------------
# SlotBoard unit behavior (shared by launch/serve.py and solver_serve.py)
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, rid):
        self.rid = rid
        self.done = False
        self.evictions = 0


def test_slotboard_refill_order_and_age_reset():
    b = SlotBoard(2)
    b.queue.extend(_Req(i) for i in range(4))
    admitted = []
    b.refill(lambda r, s: (admitted.append((r.rid, s)), b.place(r, s)))
    assert admitted == [(0, 0), (1, 1)]
    b.tick()
    assert b.age == [1, 1] and b.occupancy() == 1.0
    b.slots[0].done = True
    b.refill(lambda r, s: b.place(r, s))
    assert b.slots[0].rid == 2 and b.age[0] == 0 and b.age[1] == 1
    assert [r.rid for r in b.finished] == [0]


def test_slotboard_eviction_requeues_at_tail_then_gives_up():
    b = SlotBoard(1, max_rounds=1, max_evictions=1)
    r0, r1 = _Req(0), _Req(1)
    b.queue.extend([r0, r1])
    b.refill(lambda r, s: b.place(r, s))
    b.tick()
    assert b.evict_stale() == [0]
    assert b.queue == [r1, r0] and r0.evictions == 1    # tail re-queue
    b.refill(lambda r, s: b.place(r, s))
    assert b.slots[0] is r1
    b.tick()
    b.evict_stale()
    b.refill(lambda r, s: b.place(r, s))
    b.tick()
    b.evict_stale()                                     # r0's 2nd eviction
    assert r0.done and r0 in b.finished                 # gave up
    assert not b.pending() or b.queue == [r1]


def test_slotboard_drain_collects_remaining():
    b = SlotBoard(2)
    r = _Req(0)
    b.place(r, 1)
    out = b.drain()
    assert out == [r] and b.slots == [None, None]
