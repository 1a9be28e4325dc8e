"""Public jit'd wrappers around the Pallas Block-Shotgun kernels.

``block_shotgun_round``   one synchronous round: K random aligned blocks of
                          128 coordinates updated in parallel (P_eff = K·128),
                          issued as two pallas_call launches.
``fused_shotgun_rounds``  R rounds in ONE pallas_call with the margin z (and
                          the residual/iterate/deltas) resident in VMEM —
                          see shotgun_block.py and DESIGN §4.2.
``block_shotgun_solve``   full solver.  ``fused=False`` scans over rounds
                          (two launches each); ``fused=True`` scans over
                          *launches* of ``rounds_per_launch`` fused rounds.
                          Both draw identical block indices from the same
                          key, so their traces coincide.

The kernels run in the Pallas interpreter on the CPU backend and compile to
Mosaic on a TPU (``shotgun_block.interpret_mode`` decides; no solver takes
an ``interpret`` flag).  ``ref.py`` holds the pure-jnp oracles used by the
tests.

``block_shotgun_solve`` also accepts ``BlockedCSC`` problems (DESIGN §8):
the round scan then runs the nnz-tile kernels from ``shotgun_sparse.py``,
and ``fused=True`` scans over launches of ``fused_sparse_shotgun_rounds``
(DESIGN §8.3) — same block draws as the dense path for the same key in
both modes, so all four trajectories coincide.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core import health
from repro.core import objectives as obj
from repro.core.health import GuardConfig
from repro.core.objectives import Problem
from repro.core.shotgun import Result, Trace
from repro.core.spec import SolverSpec, reject_legacy_kwargs
from repro.data.sparse import BlockedCSC, bcsc_matvec
from repro.kernels.shotgun_block import (BLOCK, TILE_N,
                                         fused_shotgun_rounds,
                                         gather_block_matvec, resolve_loss,
                                         scatter_block_update)
from repro.kernels.shotgun_sparse import (block_delta,
                                          fused_sparse_shotgun_rounds,
                                          require_sparse_backend,
                                          sparse_gather_block_matvec,
                                          sparse_scatter_block_update)


def pad_problem(A, y, block=BLOCK, tile_n=TILE_N):
    """Zero-pad A to (n % tile_n == 0, d % block == 0).  Zero rows contribute
    nothing to gradients if y is padded with zeros *and* the loss is the
    squared loss; for logistic we pad with a sample-weight mask instead.

    An aligned design comes back as it is; any other is padded by one
    program, ``jit_pad_problem`` in a profile."""
    n, d = A.shape
    if n % tile_n == 0 and d % block == 0:
        return A, y, jnp.ones(n, A.dtype)
    return _pad(A, y, block, tile_n)


def _pad(A, y, block, tile_n):
    n, d = A.shape
    n_pad = (-n) % tile_n
    A = jnp.pad(A, ((0, n_pad), (0, (-d) % block)))
    y = jnp.pad(y, (0, n_pad))
    mask = jnp.pad(jnp.ones(n, A.dtype), (0, n_pad))
    return A, y, mask


_pad.__name__ = "pad_problem"         # the program's name in a profile
_pad = jax.jit(_pad, static_argnums=(2, 3))


@functools.partial(jax.jit, static_argnames=("block", "loss"))
def block_shotgun_round(A, z, x, blk_idx, lam, beta, y, mask,
                        loss: str = obj.LASSO, block: int = BLOCK,
                        k_eff=None):
    """One Block-Shotgun round.  Returns (x_new, z_new, delta).

    ``k_eff`` (dynamic) masks blocks at or past the backoff point
    (DESIGN §9); None applies all K drawn blocks, bit-exactly."""
    r = obj.residual_like(z, y, loss) * mask
    g = gather_block_matvec(A, r, blk_idx, block=block)
    d = x.shape[0]
    xb = x.reshape(d // block, block)
    x_sel = jnp.take(xb, blk_idx, axis=0)
    x_new_sel = obj.soft_threshold(x_sel - g / beta, lam / beta)
    delta = x_new_sel - x_sel
    if k_eff is not None:
        delta = delta * health.live_mask(blk_idx.shape[0], k_eff)[:, None]
    z_new = scatter_block_update(A, z, blk_idx, delta, block=block)
    xb = xb.at[blk_idx].add(delta)
    return xb.reshape(d), z_new, delta


@functools.partial(jax.jit, static_argnames=("K", "rounds", "block", "loss",
                                             "guard"))
def _solve(A, y, mask, lam, beta, key, K, rounds, block, loss, x0=None,
           guard=None):
    n, d = A.shape
    nblk = d // block
    x0 = jnp.zeros(d, A.dtype) if x0 is None else x0.astype(A.dtype)
    # warm-start margin: accumulate in f32 even when A is stored bf16
    z0 = obj.matvec(A.astype(jnp.float32), x0.astype(jnp.float32))

    def objective(z, x):
        return obj.masked_data_loss(z, y, mask, loss) + lam * jnp.sum(jnp.abs(x))

    keys = jax.random.split(key, rounds)

    if guard is None:
        def round_fn(carry, key_t):
            x, z = carry
            blk_idx = jax.random.choice(key_t, nblk, (K,), replace=False)
            x, z, _ = block_shotgun_round(A, z, x, blk_idx, lam, beta, y,
                                          mask, loss=loss, block=block)
            return (x, z), (objective(z, x), jnp.sum(x != 0))

        (x, z), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0), keys)
        return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                      status=health.status_from_trace(fs))

    p_floor = max(1, min(guard.p_min, K))

    def round_fn(carry, key_t):
        x, z, gs = carry
        blk_idx = jax.random.choice(key_t, nblk, (K,), replace=False)
        x_new, z_new, _ = block_shotgun_round(A, z, x, blk_idx, lam, beta,
                                              y, mask, loss=loss,
                                              block=block, k_eff=gs.p_eff)
        x, z, f, gs, _ = health.apply_sentinel(
            gs, x_new, z_new, objective(z_new, x_new),
            factor=guard.factor, p_floor=p_floor)
        return (x, z, gs), (f, jnp.sum(x != 0))

    gs0 = health.init_guard_state(x0, z0, objective(z0, x0), K)
    (x, z, gs), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0, gs0), keys)
    return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=health.status_from_trace(fs, gs.backoffs))


@functools.partial(jax.jit, static_argnames=("K", "rounds", "R", "block",
                                             "tile_n", "loss", "guard"))
def _fused_solve(A, y, mask, lam, beta, key, K, rounds, R, block, tile_n,
                 loss, x0=None, guard=None):
    """Scan over launches: one fused pallas_call per R rounds.

    Draws the same per-round keys/indices as ``_solve`` (jax.random.split of
    the same key, same choice() calls), so the two trajectories coincide.

    With ``guard`` the in-kernel sentinel (health scalar + k_eff mask) makes
    the *launch* the rollback granularity: a launch whose health scalar
    trips is discarded wholesale — iterate and margin roll back to the
    last-good snapshot in the scan carry, k_eff halves — so divergence
    detection costs one scalar read per launch, not a trace scan.

    Its device ops carry the scopes ``shotgun.warm_margin`` (z0 = A x0),
    ``shotgun.draw`` (the block draws) and ``shotgun.rounds`` (the scan of
    launches) in their ``op_name``.
    """
    n, d = A.shape
    nblk = d // block
    L = rounds // R
    # ``loss`` may be a registry string or a full Loss spec (e.g. a Newton
    # variant); objectives.py only knows the name.
    lname = loss if isinstance(loss, str) else loss.name
    x0 = (jnp.zeros(d, jnp.float32) if x0 is None
          else x0.astype(jnp.float32))
    # warm-start margin in f32 even for bf16-stored A (cast before the
    # matmul, not after — the accumulation itself is what must stay f32)
    with jax.named_scope("shotgun.warm_margin"):
        z0 = obj.matvec(A.astype(jnp.float32), x0)

    def draw(keys_l):
        with jax.named_scope("shotgun.draw"):
            return jax.vmap(lambda kt: jax.random.choice(
                kt, nblk, (K,), replace=False))(keys_l).astype(jnp.int32)

    with jax.named_scope("shotgun.draw"):
        keys = jax.random.split(key, rounds).reshape(L, R, -1)

    if guard is None:
        def launch_fn(carry, keys_l):
            x, z = carry
            x, z, fs, nnzs, _ = fused_shotgun_rounds(
                A, z, x, draw(keys_l), lam, beta, y, mask, loss=loss,
                block=block, tile_n=tile_n)
            return (x, z), (fs, nnzs)

        with jax.named_scope("shotgun.rounds"):
            (x, z), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0), keys)
        fs = fs.reshape(rounds)
        return Result(x=x, z=z,
                      trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                      status=health.status_from_trace(fs))

    p_floor = max(1, min(guard.p_min, K))

    def launch_fn(carry, keys_l):
        x, z, gs = carry
        x_new, z_new, fs, nnzs, h = fused_shotgun_rounds(
            A, z, x, draw(keys_l), lam, beta, y, mask, loss=loss,
            block=block, tile_n=tile_n, k_eff=gs.p_eff,
            guard_f=health.guard_threshold(gs.f_good, guard.factor))
        x, z, f_rep, gs, bad = health.apply_sentinel(
            gs, x_new, z_new, fs[-1], factor=guard.factor, p_floor=p_floor,
            health=h)
        # A rolled-back launch reports the snapshot objective for all its
        # rounds: the trace stays finite through a recovered divergence.
        fs = jnp.where(bad, jnp.full_like(fs, f_rep), fs)
        nnzs = jnp.where(bad, jnp.full_like(nnzs, jnp.sum(x != 0)), nnzs)
        return (x, z, gs), (fs, nnzs)

    f0 = (obj.masked_data_loss(z0, y, mask, lname)
          + lam * jnp.sum(jnp.abs(x0)))
    gs0 = health.init_guard_state(x0, z0, f0, K)
    with jax.named_scope("shotgun.rounds"):
        (x, z, gs), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0, gs0), keys)
    fs = fs.reshape(rounds)
    return Result(x=x, z=z,
                  trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                  status=health.status_from_trace(fs, gs.backoffs))


@functools.partial(jax.jit, static_argnames=("loss",))
def sparse_block_shotgun_round(rows, vals, z, x, blk_idx, lam, beta, y,
                               loss: str = obj.LASSO, k_eff=None):
    """One Block-Shotgun round on BlockedCSC nnz tiles (the sparse
    counterpart of ``block_shotgun_round``; no mask — the sparse path never
    pads samples).  ``k_eff`` masks blocks past the backoff point
    (DESIGN §9).  Returns (x_new, z_new, delta)."""
    nblk, tile, block = rows.shape
    r = obj.residual_like(z, y, loss)
    g = sparse_gather_block_matvec(rows, vals, r, blk_idx)
    xb = x.reshape(nblk, block)
    x_sel = jnp.take(xb, blk_idx, axis=0)
    delta = block_delta(x_sel, g, lam, beta)
    if k_eff is not None:
        delta = delta * health.live_mask(blk_idx.shape[0], k_eff)[:, None]
    z_new = sparse_scatter_block_update(rows, vals, z, blk_idx, delta)
    xb = xb.at[blk_idx].add(delta)
    return xb.reshape(-1), z_new, delta


@functools.partial(jax.jit, static_argnames=("K", "rounds", "loss",
                                             "guard"))
def _sparse_solve(rows, vals, y, lam, beta, key, K, rounds, loss, x0=None,
                  guard=None):
    """Round scan over the sparse Pallas kernels (BlockedCSC tiles).

    Draws the same block indices as the dense ``_solve`` for the same key,
    so dense/sparse trajectories coincide up to fp accumulation order.  No
    sample padding is needed: z stays full-length (n,) in both kernels.
    """
    nblk, tile, block = rows.shape
    n = y.shape[0]
    d_pad = nblk * block
    mask = jnp.ones(n, jnp.float32)
    x0 = jnp.zeros(d_pad, jnp.float32) if x0 is None else x0.astype(jnp.float32)
    z0 = bcsc_matvec(rows, vals, x0, n)

    def objective(z, x):
        return obj.masked_data_loss(z, y, mask, loss) + lam * jnp.sum(jnp.abs(x))

    keys = jax.random.split(key, rounds)

    if guard is None:
        def round_fn(carry, key_t):
            x, z = carry
            blk_idx = jax.random.choice(key_t, nblk, (K,),
                                        replace=False).astype(jnp.int32)
            x, z, _ = sparse_block_shotgun_round(rows, vals, z, x, blk_idx,
                                                 lam, beta, y, loss=loss)
            return (x, z), (objective(z, x), jnp.sum(x != 0))

        (x, z), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0), keys)
        return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                      status=health.status_from_trace(fs))

    p_floor = max(1, min(guard.p_min, K))

    def round_fn(carry, key_t):
        x, z, gs = carry
        blk_idx = jax.random.choice(key_t, nblk, (K,),
                                    replace=False).astype(jnp.int32)
        x_new, z_new, _ = sparse_block_shotgun_round(
            rows, vals, z, x, blk_idx, lam, beta, y, loss=loss,
            k_eff=gs.p_eff)
        x, z, f, gs, _ = health.apply_sentinel(
            gs, x_new, z_new, objective(z_new, x_new),
            factor=guard.factor, p_floor=p_floor)
        return (x, z, gs), (f, jnp.sum(x != 0))

    gs0 = health.init_guard_state(x0, z0, objective(z0, x0), K)
    (x, z, gs), (fs, nnzs) = jax.lax.scan(round_fn, (x0, z0, gs0), keys)
    return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=health.status_from_trace(fs, gs.backoffs))


@functools.partial(jax.jit, static_argnames=("K", "rounds", "R", "loss",
                                             "guard"))
def _fused_sparse_solve(rows, vals, y, lam, beta, key, K, rounds, R, loss,
                        x0=None, guard=None):
    """Scan over launches of the fused sparse kernel: one pallas_call per R
    rounds (DESIGN §8.3).

    Draws the same per-round keys/indices as ``_sparse_solve`` (and hence
    the dense ``_solve``/``_fused_solve``) for the same key, so all four
    trajectories coincide.  ``guard`` enables launch-granular sentinel
    rollback exactly as in the dense ``_fused_solve``.
    """
    nblk, tile, block = rows.shape
    n = y.shape[0]
    L = rounds // R
    lname = loss if isinstance(loss, str) else loss.name
    mask = jnp.ones(n, jnp.float32)
    x0 = (jnp.zeros(nblk * block, jnp.float32) if x0 is None
          else x0.astype(jnp.float32))
    z0 = bcsc_matvec(rows, vals, x0, n)
    draw = functools.partial(jax.random.choice, a=nblk, shape=(K,),
                             replace=False)
    keys = jax.random.split(key, rounds).reshape(L, R, -1)

    if guard is None:
        def launch_fn(carry, keys_l):
            x, z = carry
            idx = jax.vmap(lambda kt: draw(kt))(keys_l).astype(jnp.int32)
            x, z, fs, nnzs, _ = fused_sparse_shotgun_rounds(
                rows, vals, z, x, idx, lam, beta, y, loss=loss)
            return (x, z), (fs, nnzs)

        (x, z), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0), keys)
        fs = fs.reshape(rounds)
        return Result(x=x, z=z,
                      trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                      status=health.status_from_trace(fs))

    p_floor = max(1, min(guard.p_min, K))

    def launch_fn(carry, keys_l):
        x, z, gs = carry
        idx = jax.vmap(lambda kt: draw(kt))(keys_l).astype(jnp.int32)
        x_new, z_new, fs, nnzs, h = fused_sparse_shotgun_rounds(
            rows, vals, z, x, idx, lam, beta, y, loss=loss,
            k_eff=gs.p_eff,
            guard_f=health.guard_threshold(gs.f_good, guard.factor))
        x, z, f_rep, gs, bad = health.apply_sentinel(
            gs, x_new, z_new, fs[-1], factor=guard.factor, p_floor=p_floor,
            health=h)
        fs = jnp.where(bad, jnp.full_like(fs, f_rep), fs)
        nnzs = jnp.where(bad, jnp.full_like(nnzs, jnp.sum(x != 0)), nnzs)
        return (x, z, gs), (fs, nnzs)

    f0 = (obj.masked_data_loss(z0, y, mask, lname)
          + lam * jnp.sum(jnp.abs(x0)))
    gs0 = health.init_guard_state(x0, z0, f0, K)
    (x, z, gs), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0, gs0), keys)
    fs = fs.reshape(rounds)
    return Result(x=x, z=z,
                  trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                  status=health.status_from_trace(fs, gs.backoffs))


@functools.partial(jax.profiler.annotate_function, name="shotgun.solve")
def block_shotgun_solve(prob: Problem, key: jax.Array,
                        K: int | None = None, rounds: int | None = None,
                        block: int = BLOCK,
                        fused: bool = False, rounds_per_launch: int = 8,
                        tile_n: int | None = None,
                        x0: jax.Array | None = None,
                        guard: GuardConfig | None = None,
                        newton: bool = False,
                        spec: SolverSpec | None = None) -> Result:
    """TPU-native Shotgun: K parallel blocks of `block` coordinates/round.

    Effective parallelism P = K * block must respect Thm 3.2's
    P < d/rho + 1 (checked by the caller via ``core.spectral.p_star``) —
    or pass ``guard`` (a ``health.GuardConfig``, with ``p_min`` in units of
    blocks) to enable the divergence sentinel + adaptive-K backoff
    (DESIGN §9): tripped rounds/launches roll back to the last-good
    snapshot and the effective block count halves toward ``p_min``.

    ``fused=True`` runs ``rounds_per_launch`` rounds per kernel launch with
    the margin held in VMEM (must divide ``rounds``); the trajectory and
    trace are the same as the two-kernel path for the same key.

    ``x0`` warm-starts the iterate (λ-continuation, ``core.path``): it is
    zero-padded to the block-padded width and the margin is initialized to
    ``z0 = A x0`` — padded columns carry zero weight so the trajectory of
    real coordinates is unchanged.

    A ``BlockedCSC`` problem routes to the sparse kernels
    (``kernels/shotgun_sparse.py``): same block draws for the same key, so
    the trajectory matches the dense path on the densified design.  They
    run only on the CPU backend; elsewhere this raises
    ``NotImplementedError`` (``require_sparse_backend``).
    ``fused=True`` runs the fused multi-round sparse kernel (DESIGN §8.3)
    — one launch per ``rounds_per_launch`` rounds with the margin resident
    in VMEM and nnz tiles as the only per-round A traffic; ``tile_n`` is
    ignored (the sparse kernels never tile the sample dimension).

    ``spec=SolverSpec(...)`` is the canonical interface (DESIGN §12): K is
    derived as ceil(spec.P / block) and ``fused``/``guard``/``newton`` come
    from the spec.  The legacy (K, rounds, ...) kwargs still work through
    this shim (same jitted core, bit-for-bit) but emit a
    ``DeprecationWarning``.  ``newton=True`` (or ``spec.newton``) swaps the
    β-Lipschitz step for the per-block Newton curvature computed from the
    already-fetched A tile — fused path only.

    The host side (padding, dispatch, result slices; it returns before the
    device finishes) runs in the profiler span ``shotgun.solve``.
    """
    if spec is not None:
        reject_legacy_kwargs(spec, K=K, rounds=rounds)
        spec.check_loss(prob.loss)
        K = max(1, -(-spec.P // block))
        rounds = spec.rounds
        fused, guard, newton = spec.fused, spec.guard, spec.newton
    else:
        if K is None or rounds is None:
            raise TypeError("block_shotgun_solve needs (K, rounds) or spec=")
        warnings.warn(
            "block_shotgun_solve(K=..., rounds=...) kwargs are deprecated; "
            "pass spec=SolverSpec(...)", DeprecationWarning, stacklevel=3)
    loss = prob.loss
    if newton:
        if not fused:
            raise ValueError(
                "newton=True requires fused=True: the per-block curvature "
                "tile is computed inside the fused kernel body")
        loss = resolve_loss(prob.loss)._replace(newton=True)
    if isinstance(prob.A, BlockedCSC):
        require_sparse_backend()
        if block != prob.A.block:
            raise ValueError(f"block={block} != BlockedCSC block "
                             f"{prob.A.block}")
        if x0 is not None:
            x0 = jnp.pad(jnp.asarray(x0), (0, prob.A.d_pad - prob.d))
        if fused:
            if rounds % rounds_per_launch:
                raise ValueError(
                    f"rounds={rounds} not divisible by "
                    f"rounds_per_launch={rounds_per_launch}")
            res = _fused_sparse_solve(prob.A.rows, prob.A.vals, prob.y,
                                      prob.lam, prob.beta, key, K, rounds,
                                      rounds_per_launch, loss, x0=x0,
                                      guard=guard)
        else:
            res = _sparse_solve(prob.A.rows, prob.A.vals, prob.y, prob.lam,
                                prob.beta, key, K, rounds, loss, x0=x0,
                                guard=guard)
        return Result(x=res.x[: prob.d], z=res.z, trace=res.trace,
                      status=res.status)

    A, y, mask = pad_problem(prob.A, prob.y)
    if x0 is not None:
        x0 = jnp.pad(jnp.asarray(x0), (0, A.shape[1] - prob.d))
    if fused:
        if rounds % rounds_per_launch:
            raise ValueError(
                f"rounds={rounds} not divisible by "
                f"rounds_per_launch={rounds_per_launch}")
        res = _fused_solve(A, y, mask.astype(jnp.float32), prob.lam,
                           prob.beta, key, K, rounds, rounds_per_launch,
                           block, tile_n, loss, x0=x0, guard=guard)
    else:
        res = _solve(A, y, mask, prob.lam, prob.beta, key, K, rounds, block,
                     loss, x0=x0, guard=guard)
    return Result(x=res.x[: prob.d], z=res.z[: prob.n], trace=res.trace,
                  status=res.status)


def fused_block_shotgun_solve(prob: Problem, key: jax.Array,
                              K: int | None = None,
                              rounds: int | None = None,
                              rounds_per_launch: int = 8,
                              block: int = BLOCK, tile_n: int | None = None,
                              x0: jax.Array | None = None,
                              guard: GuardConfig | None = None,
                              spec: SolverSpec | None = None) -> Result:
    """Convenience alias: ``block_shotgun_solve(..., fused=True)``.

    Accepts ``spec=SolverSpec(...)`` like every entry point (DESIGN §12);
    the alias pins the fused path, so a spec left at ``fused=False`` is
    promoted to ``fused=True`` (``newton`` passes through unchanged).
    """
    if spec is not None:
        reject_legacy_kwargs(spec, K=K, rounds=rounds, guard=guard)
        if not spec.fused:
            spec = dataclasses.replace(spec, fused=True)
        return block_shotgun_solve(prob, key, block=block,
                                   rounds_per_launch=rounds_per_launch,
                                   tile_n=tile_n, x0=x0, spec=spec)
    return block_shotgun_solve(prob, key, K, rounds, block=block, fused=True,
                               rounds_per_launch=rounds_per_launch,
                               tile_n=tile_n, x0=x0, guard=guard)
