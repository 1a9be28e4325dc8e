"""The λ path's own spans and read counter, and the kernels' names.

``solve_path`` marks its steps with ``jax.profiler.TraceAnnotation`` spans,
counts its device→host reads in ``PathResult.syncs`` (one a chunk, none
elsewhere in a λ) and the cached x it sends back in ``PathResult.uploads``;
none of it may change what it computes.  Every ``pallas_call`` carries a
name of its own.
"""
import contextlib
import copy
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolverSpec, solve_path, spectral
from repro.core import objectives as obj
from repro.core.batched import WarmStartCache, launch_converged
from repro.core.path import _solver_by_name, lambda_sequence
from repro.data.sparse import BlockedCSC
from repro.kernels import shotgun_block, shotgun_sparse


def _problem(loss, shape=None, sparse=False):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    n, d = shape or ((300, 1000) if loss == "lasso" else (256, 512))
    A = jax.random.normal(k1, (n, d)) / np.sqrt(n)
    signal = A @ jnp.zeros(d).at[:10].set(2.0)
    noise = jax.random.normal(k2, (n,))
    y = (signal + 0.01 * noise if loss == "lasso"
         else jnp.sign(signal + 0.1 * noise))
    lam = 0.1 * float(obj.lambda_max(A, y, loss))
    if sparse:
        A = BlockedCSC.from_dense(A * (jnp.abs(A) > 1.0 / np.sqrt(n)))
    return obj.make_problem(A, y, lam, loss=loss, normalize=False), lam


def _loop_without_spans(prob, key, lam, spec, num_lambdas, tol, cache=None):
    """The warm-started path loop as it stood before it had spans: eager
    reads and an eager objective, nothing traced, every cache hit
    uploaded."""
    P = min(spec.P, spectral.p_star(prob.A))
    solver = _solver_by_name("block_fused")
    lams = lambda_sequence(float(obj.lambda_max(prob.A, prob.y, prob.loss)),
                           lam, num_lambdas)
    cache = WarmStartCache() if cache is None else cache
    x, chunk = jnp.zeros(prob.d), 8
    objs, nnzs, rounds = [], [], []
    for lam_i in lams:
        p_i = prob._replace(lam=jnp.float32(lam_i))
        x0, kind = cache.get("path", float(lam_i), loss=prob.loss)
        if kind != "miss":
            x = jnp.asarray(x0, x.dtype)
        f_prev, spent = float(obj.objective(x, p_i)), 0
        while spent < spec.rounds:
            key, sub = jax.random.split(key)
            res = solver(p_i, sub, P, chunk, x)
            x, spent = res.x, spent + chunk
            f_chunk = np.asarray(res.trace.objective)
            if launch_converged(f_prev, f_chunk, tol):
                break
            f_prev = float(f_chunk[-1])
        cache.put("path", float(lam_i), np.asarray(x), loss=prob.loss)
        rounds.append(spent)
        objs.append(float(res.trace.objective[-1]))
        nnzs.append(int(res.trace.nnz[-1]))
    return x, np.array(objs), np.array(nnzs), np.array(rounds)


def _assert_same_path(got, want):
    x, objs, nnz, rounds = want
    assert np.array_equal(np.asarray(got.x), np.asarray(x))
    assert np.array_equal(got.objectives, objs)
    assert np.array_equal(got.nnz, nnz)
    assert np.array_equal(got.rounds, rounds)


@pytest.mark.parametrize("loss,shape,sparse,pads", [
    pytest.param("lasso", None, False, 1, id="lasso"),
    pytest.param("logistic", None, False, 1, id="logistic"),
    pytest.param("lasso", (512, 1024), False, 0, id="lasso-aligned"),
    pytest.param("lasso", None, True, 0, id="lasso-bcsc")])
def test_path_with_spans_is_bit_identical_to_the_loop_without(loss, shape,
                                                              sparse, pads):
    """The path pads a ragged dense design once and reuses it in every
    chunk of every λ, against the loop's padded copy a chunk: the same
    answers, bit for bit.  An aligned or a BlockedCSC design is never
    copied."""
    prob, lam = _problem(loss, shape, sparse)
    spec = SolverSpec(loss=loss, P=64, rounds=32)
    got = solve_path(prob, jax.random.PRNGKey(7), lam_target=lam,
                     num_lambdas=5, solver="block_fused", spec=spec,
                     cache=WarmStartCache(), tol=1e-4)
    want = _loop_without_spans(prob, jax.random.PRNGKey(7), lam, spec, 5,
                               1e-4)
    _assert_same_path(got, want)
    # one read a chunk, then λ_max and P*; every hit is the path's own x
    assert got.syncs == want[3].sum() // 8 + 2
    assert got.uploads == 0
    assert got.pads == pads and want[3].max() > 8


class _RecordingCache(WarmStartCache):
    """A warm-start cache that keeps every x put and every x0 handed out."""

    def __init__(self):
        super().__init__()
        self.written, self.handed = [], []

    def put(self, problem_id, lam, x, loss="lasso"):
        super().put(problem_id, lam, x, loss=loss)
        self.written.append(x)

    def get(self, problem_id, lam, loss="lasso"):
        x0, kind = super().get(problem_id, lam, loss=loss)
        self.handed.append(x0)
        return x0, kind


def test_path_on_a_cache_filled_by_another_sweep_uploads_only_its_entries():
    prob, lam = _problem("lasso")
    spec = SolverSpec(loss="lasso", P=64, rounds=32)
    cache = _RecordingCache()
    solve_path(prob, jax.random.PRNGKey(11), lam_target=lam, num_lambdas=2,
               solver="block_fused", spec=spec, cache=cache, tol=1e-4)
    first, cache.handed = list(cache.written), []
    want = _loop_without_spans(prob, jax.random.PRNGKey(7), lam, spec, 5,
                               1e-4, cache=copy.deepcopy(cache))
    got = solve_path(prob, jax.random.PRNGKey(7), lam_target=lam,
                     num_lambdas=5, solver="block_fused", spec=spec,
                     cache=cache, tol=1e-4)
    _assert_same_path(got, want)
    from_first = sum(any(x0 is x for x in first) for x0 in cache.handed)
    assert 0 < from_first < 5       # some hits the first sweep's, some own
    assert got.uploads == from_first


@pytest.mark.parametrize("cached", [True, False])
def test_no_lambda_reads_outside_its_chunks(cached, monkeypatch):
    """Every read of a λ runs inside one of its chunks, one a chunk; the
    rest are P* and λ_max, and without a cache the one read at the end."""
    from repro.core import path
    open_spans, where, per_chunk = [], [], []

    @contextlib.contextmanager
    def annotation(name, **_):
        open_spans.append(name)
        if name == "shotgun.path.chunk":
            per_chunk.append(0)
        try:
            yield
        finally:
            open_spans.pop()

    class CountingReads(path._Reads):
        def __call__(self, read, value):
            where.append(tuple(open_spans))
            if open_spans[-1:] == ["shotgun.path.chunk"]:
                per_chunk[-1] += 1
            return super().__call__(read, value)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(path, "_Reads", CountingReads)
    prob, lam = _problem("lasso")
    res = solve_path(prob, jax.random.PRNGKey(7), lam_target=lam,
                     num_lambdas=3, solver="block_fused",
                     spec=SolverSpec(loss="lasso", P=64, rounds=32),
                     cache=WarmStartCache() if cached else None, tol=1e-4)
    assert len(where) == res.syncs
    inside = [w for w in where if "shotgun.path.lambda" in w]
    assert all(w[-1] == "shotgun.path.chunk" for w in inside)
    outside = [w[-1:] for w in where if "shotgun.path.lambda" not in w]
    assert outside[:2] == [("shotgun.path.p_star",),
                           ("shotgun.path.lambda_max",)]
    if cached:
        assert per_chunk == [1] * (res.rounds.sum() // 8)
        assert len(outside) == 2
    else:
        assert inside == [] and outside[2:] == [()]


def test_syncs_counted_without_a_cache():
    prob, lam = _problem("lasso")
    got = solve_path(prob, jax.random.PRNGKey(1), lam_target=lam,
                     num_lambdas=3, solver="block_fused",
                     spec=SolverSpec(loss="lasso", P=64, rounds=16),
                     validate_p=False)
    assert got.rounds is None
    assert got.syncs == 2     # λ_max, then every λ's F and nnz at the end
    assert got.uploads == 0
    assert got.pads == 1      # (300, 1000), padded once for three λ


def _host_events(log_dir):
    """[name, start_ns, end_ns] of the host events on the thread that holds
    the path's spans, in the newest trace under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(name == "shotgun.path.chunk" for name, _, _ in evs):
                return evs
    raise AssertionError("no shotgun.path.chunk span in the trace")


def _dispatches(evs, span):
    """The programs dispatched inside ``span`` outside its reads: each
    ``PjitFunction(...)`` event once (the profiler nests two of a name for
    one dispatch)."""
    _, s0, s1 = span
    reads = [(s, e) for name, s, e in evs
             if name == "shotgun.path.sync" and s0 <= s < s1]
    calls = sorted((s, e, name) for name, s, e in evs
                   if name.startswith("PjitFunction(") and s0 <= s < s1
                   and not any(r0 <= s < r1 for r0, r1 in reads))
    out, end = [], {}
    for s, e, name in calls:
        if s >= end.get(name, -1):
            out.append(name)
            end[name] = e
    return out


def test_a_chunk_after_the_first_is_one_dispatch(tmp_path):
    """Each chunk of a λ path dispatches one program, the solve, beside
    its read: the key split, x0's pad and the result's slices run inside
    it.  The path's first chunk also pads the ragged design, once."""
    prob, lam = _problem("lasso")

    def path():
        return solve_path(prob, jax.random.PRNGKey(7), lam_target=lam,
                          num_lambdas=3, solver="block_fused",
                          spec=SolverSpec(loss="lasso", P=64, rounds=32),
                          cache=WarmStartCache(), tol=1e-4)
    path()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = path()
    evs = _host_events(str(tmp_path))
    chunks = sorted((e for e in evs if e[0] == "shotgun.path.chunk"),
                    key=lambda e: e[1])
    assert len(chunks) == res.rounds.sum() // 8 > 3
    assert _dispatches(evs, chunks[0]) == ["PjitFunction(pad_problem)",
                                           "PjitFunction(_fused_solve)"]
    for chunk in chunks[1:]:
        assert _dispatches(evs, chunk) == ["PjitFunction(_fused_solve)"]
    assert res.pads == 1


def test_pad_problem_is_one_program_and_keeps_an_aligned_design():
    from repro.kernels import ops
    A, y = jnp.ones((300, 200)), jnp.ones(300)
    low = ops._pad.lower(A, y, shotgun_block.BLOCK, shotgun_block.TILE_N)
    assert "jit_pad_problem" in low.as_text()
    Ap, yp, mask = ops.pad_problem(A, y)
    assert Ap.shape[0] % shotgun_block.TILE_N == 0
    assert Ap.shape[1] % shotgun_block.BLOCK == 0
    assert float(mask.sum()) == 300 and float(yp.sum()) == 300
    B, z = jnp.ones((512, 256)), jnp.ones(512)
    Bp, zp, ones = ops.pad_problem(B, z)
    assert Bp is B and zp is z and ones.shape == (512,)


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------

def _dense_args(n=512, d=1024, R=2, K=2):
    return (jnp.zeros((n, d)), jnp.zeros(n), jnp.zeros(d),
            jnp.zeros((R, K), jnp.int32), 1.0, 1.0, jnp.zeros(n),
            jnp.ones(n))


def _sparse_args(nblk=4, tile=8, block=128, n=64, R=2, K=2):
    rows = jnp.zeros((nblk, tile, block), jnp.int32)
    vals = jnp.zeros((nblk, tile, block))
    return (rows, vals, jnp.zeros(n), jnp.zeros(nblk * block),
            jnp.zeros((R, K), jnp.int32), 1.0, 1.0, jnp.zeros(n))


def _kernel_calls():
    A, z, x, idx, lam, beta, y, mask = _dense_args()
    rows, vals, zs, xs, sidx, _, _, ys = _sparse_args()
    return {
        "fused_shotgun_rounds": lambda: shotgun_block.fused_shotgun_rounds(
            A, z, x, idx, lam, beta, y, mask),
        "fused_shotgun_delta_rounds":
            lambda: shotgun_block.fused_shotgun_delta_rounds(
                A, z, x, idx, lam, beta, y, mask),
        "fused_sparse_shotgun_rounds":
            lambda: shotgun_sparse.fused_sparse_shotgun_rounds(
                rows, vals, zs, xs, sidx, lam, beta, ys),
        "fused_sparse_shotgun_delta_rounds":
            lambda: shotgun_sparse.fused_sparse_shotgun_delta_rounds(
                rows, vals, zs, xs, sidx, lam, beta, ys),
    }


def _pallas_names(jaxpr):
    """Names of the pallas_calls in ``jaxpr``, through nested jits."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        elif "jaxpr" in eqn.params:
            out += _pallas_names(eqn.params["jaxpr"].jaxpr)
    return out


@pytest.mark.parametrize("name", [
    "fused_shotgun_rounds", "fused_shotgun_delta_rounds",
    "fused_sparse_shotgun_rounds", "fused_sparse_shotgun_delta_rounds"])
def test_every_pallas_call_carries_its_own_name(name):
    jaxpr = jax.make_jaxpr(_kernel_calls()[name])().jaxpr
    assert _pallas_names(jaxpr) == [name]


def test_fused_kernel_name_reaches_the_tpu_custom_call():
    """Lowered for the TPU (no chip needed), the dense fused kernel's
    custom call is named ``fused_shotgun_rounds``: the name the benchmark's
    roofline reader finds in a device trace."""
    traced = shotgun_block.fused_shotgun_rounds.trace(*_dense_args(),
                                                      interpret=False)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "fused_shotgun_rounds"' in text
