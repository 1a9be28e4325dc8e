"""Public jit'd wrappers around the Pallas Block-Shotgun kernels.

``block_shotgun_solve``   the block solver: a scan over *launches*, each one
                          ``fused_shotgun_rounds`` pallas_call running
                          ``rounds_per_launch`` rounds with the margin z (and
                          the residual/iterate/deltas) resident in VMEM — see
                          shotgun_block.py and DESIGN §4.2.
``BlockShotgunPath``      the same solve over the chunks of a λ path: one
                          program a chunk, on a design padded once a path.

The kernels run in the Pallas interpreter on the CPU backend and compile to
Mosaic on a TPU (``shotgun_block.interpret_mode`` decides; no solver takes
an ``interpret`` flag).  ``ref.py`` holds the pure-jnp oracles used by the
tests.

``block_shotgun_solve`` also accepts ``BlockedCSC`` problems (DESIGN §8):
the launch scan then runs ``fused_sparse_shotgun_rounds`` (DESIGN §8.3),
with the same block draws as the dense path for the same key, so the two
trajectories coincide on the densified design.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import health
from repro.core import objectives as obj
from repro.core.health import GuardConfig
from repro.core.objectives import Problem
from repro.core.path import _largest_divisor_leq
from repro.core.shotgun import Result, Trace
from repro.core.spec import SolverSpec, reject_legacy_kwargs
from repro.data.sparse import BlockedCSC, bcsc_matvec
from repro.kernels.shotgun_block import (BLOCK, TILE_N,
                                         fused_shotgun_rounds, resolve_loss)
from repro.kernels.shotgun_sparse import (fused_sparse_shotgun_rounds,
                                          require_sparse_backend)


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


@functools.partial(jax.jit, static_argnames=("K", "rounds", "R", "block",
                                             "tile_n", "loss", "guard", "n",
                                             "d", "split"))
def _fused_solve(A, y, mask, lam, beta, key, K, rounds, R, block, tile_n,
                 loss, x0=None, guard=None, *, n, d, split=False):
    """Scan over launches: one fused pallas_call per R rounds.

    ``A``, ``y`` and ``mask`` are the padded design (``pad_problem``), and
    ``n`` and ``d`` its real size: ``x0`` has the real width, is padded
    here, and the result's x and z are cut back to d and n here.

    Round t draws its K blocks as ``jax.random.choice(split(key,
    rounds)[t], nblk, (K,), replace=False)``: the draw rule that the
    sparse solve, the sharded engines on one shard and the ``ref.py``
    replays in the tests share.  With ``split`` the program first splits
    ``key`` as ``jax.random.split`` does, draws from the second half and
    returns ``(first half, Result)``: one chunk of a λ path is then this
    one program.

    With ``guard`` the in-kernel sentinel (health scalar + k_eff mask) makes
    the *launch* the rollback granularity: a launch whose health scalar
    trips is discarded wholesale — iterate and margin roll back to the
    last-good snapshot in the scan carry, k_eff halves — so divergence
    detection costs one scalar read per launch, not a trace scan.

    Its device ops carry the scopes ``shotgun.warm_margin`` (z0 = A x0),
    ``shotgun.draw`` (the block draws) and ``shotgun.rounds`` (the scan of
    launches) in their ``op_name``.
    """
    d_pad = A.shape[1]
    nblk = d_pad // block
    L = rounds // R
    # ``loss`` may be a registry string or a full Loss spec (e.g. a Newton
    # variant); objectives.py only knows the name.
    lname = loss if isinstance(loss, str) else loss.name
    x0 = (jnp.zeros(d_pad, jnp.float32) if x0 is None
          else jnp.pad(x0, (0, d_pad - d)).astype(jnp.float32))
    # warm-start margin in f32 even for bf16-stored A (cast before the
    # matmul, not after — the accumulation itself is what must stay f32)
    with jax.named_scope("shotgun.warm_margin"):
        z0 = obj.matvec(A.astype(jnp.float32), x0)

    def draw(keys_l):
        with jax.named_scope("shotgun.draw"):
            return jax.vmap(lambda kt: jax.random.choice(
                kt, nblk, (K,), replace=False))(keys_l).astype(jnp.int32)

    with jax.named_scope("shotgun.draw"):
        next_key, key = jax.random.split(key) if split else (None, key)
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
        backoffs = None
    else:
        p_floor = max(1, min(guard.p_min, K))

        def launch_fn(carry, keys_l):
            x, z, gs = carry
            x_new, z_new, fs, nnzs, h = fused_shotgun_rounds(
                A, z, x, draw(keys_l), lam, beta, y, mask, loss=loss,
                block=block, tile_n=tile_n, k_eff=gs.p_eff,
                guard_f=health.guard_threshold(gs.f_good, guard.factor))
            x, z, f_rep, gs, bad = health.apply_sentinel(
                gs, x_new, z_new, fs[-1], factor=guard.factor,
                p_floor=p_floor, health=h)
            # A rolled-back launch reports the snapshot objective for all
            # its rounds: the trace stays finite through a recovered
            # divergence.
            fs = jnp.where(bad, jnp.full_like(fs, f_rep), fs)
            nnzs = jnp.where(bad, jnp.full_like(nnzs, jnp.sum(x != 0)), nnzs)
            return (x, z, gs), (fs, nnzs)

        f0 = (obj.masked_data_loss(z0, y, mask, lname)
              + lam * jnp.sum(jnp.abs(x0)))
        gs0 = health.init_guard_state(x0, z0, f0, K)
        with jax.named_scope("shotgun.rounds"):
            (x, z, gs), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0, gs0),
                                                  keys)
        backoffs = gs.backoffs
    fs = fs.reshape(rounds)
    res = Result(x=x[:d], z=z[:n],
                 trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                 status=health.status_from_trace(fs, backoffs))
    return (next_key, res) if split else res


@functools.partial(jax.jit, static_argnames=("K", "rounds", "R", "loss",
                                             "guard", "d", "split"))
def _sparse_fused_solve(rows, vals, y, lam, beta, key, K, rounds, R, loss,
                        x0=None, guard=None, *, d, split=False):
    """Scan over launches of the fused sparse kernel: one pallas_call per R
    rounds (DESIGN §8.3).

    Draws the same per-round keys/indices as the dense ``_fused_solve``
    for the same key, so the two trajectories coincide; ``d``, ``split``
    and ``guard`` (launch-granular sentinel rollback) act as there.
    """
    nblk, tile, block = rows.shape
    n = y.shape[0]
    L = rounds // R
    lname = loss if isinstance(loss, str) else loss.name
    mask = jnp.ones(n, jnp.float32)
    x0 = (jnp.zeros(nblk * block, jnp.float32) if x0 is None
          else jnp.pad(x0, (0, nblk * block - d)).astype(jnp.float32))
    z0 = bcsc_matvec(rows, vals, x0, n)
    draw = functools.partial(jax.random.choice, a=nblk, shape=(K,),
                             replace=False)
    next_key, key = jax.random.split(key) if split else (None, key)
    keys = jax.random.split(key, rounds).reshape(L, R, -1)

    if guard is None:
        def launch_fn(carry, keys_l):
            x, z = carry
            idx = jax.vmap(lambda kt: draw(kt))(keys_l).astype(jnp.int32)
            x, z, fs, nnzs, _ = fused_sparse_shotgun_rounds(
                rows, vals, z, x, idx, lam, beta, y, loss=loss)
            return (x, z), (fs, nnzs)

        (x, z), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0), keys)
        backoffs = None
    else:
        p_floor = max(1, min(guard.p_min, K))

        def launch_fn(carry, keys_l):
            x, z, gs = carry
            idx = jax.vmap(lambda kt: draw(kt))(keys_l).astype(jnp.int32)
            x_new, z_new, fs, nnzs, h = fused_sparse_shotgun_rounds(
                rows, vals, z, x, idx, lam, beta, y, loss=loss,
                k_eff=gs.p_eff,
                guard_f=health.guard_threshold(gs.f_good, guard.factor))
            x, z, f_rep, gs, bad = health.apply_sentinel(
                gs, x_new, z_new, fs[-1], factor=guard.factor,
                p_floor=p_floor, health=h)
            fs = jnp.where(bad, jnp.full_like(fs, f_rep), fs)
            nnzs = jnp.where(bad, jnp.full_like(nnzs, jnp.sum(x != 0)), nnzs)
            return (x, z, gs), (fs, nnzs)

        f0 = (obj.masked_data_loss(z0, y, mask, lname)
              + lam * jnp.sum(jnp.abs(x0)))
        gs0 = health.init_guard_state(x0, z0, f0, K)
        (x, z, gs), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0, gs0), keys)
        backoffs = gs.backoffs
    fs = fs.reshape(rounds)
    res = Result(x=x[:d], z=z,
                 trace=Trace(objective=fs, nnz=nnzs.reshape(rounds)),
                 status=health.status_from_trace(fs, backoffs))
    return (next_key, res) if split else res


class _Design(NamedTuple):
    """A problem's design as the solve programs read it."""
    source: tuple                  # (A, y, loss) as handed
    A: jax.Array | BlockedCSC      # dense: padded by ``pad_problem``
    y: jax.Array
    mask: jax.Array | None         # dense: f32, 1 on the real samples
    beta: jax.Array                # the loss's step bound, on the device
    pads: int                      # padded copies of A made for it: 0 or 1

    def serves(self, prob: Problem) -> bool:
        """Was it made from ``prob``'s A, y and loss (the same objects)?"""
        A, y, loss = self.source
        return prob.A is A and prob.y is y and prob.loss == loss


def _design(prob: Problem) -> _Design:
    if isinstance(prob.A, BlockedCSC):
        A, y, mask = prob.A, prob.y, None
    else:
        A, y, mask = pad_problem(prob.A, prob.y)
        mask = mask.astype(jnp.float32)
    return _Design((prob.A, prob.y, prob.loss), A, y, mask,
                   jax.device_put(prob.beta), int(A is not prob.A))


def _launch_plan(prob: Problem, rounds, block, rounds_per_launch, newton):
    """(rounds per launch, loss spec) of a solve, checked against ``prob``."""
    if rounds_per_launch is None:
        rounds_per_launch = _largest_divisor_leq(rounds, 8)
    elif rounds % rounds_per_launch:
        raise ValueError(
            f"rounds={rounds} not divisible by "
            f"rounds_per_launch={rounds_per_launch}")
    loss = prob.loss
    if newton:
        loss = resolve_loss(prob.loss)._replace(newton=True)
    if isinstance(prob.A, BlockedCSC):
        require_sparse_backend()
        if block != prob.A.block:
            raise ValueError(f"block={block} != BlockedCSC block "
                             f"{prob.A.block}")
    return rounds_per_launch, loss


def _solve(prob: Problem, design: _Design, key, x0, K, rounds, R, block,
           tile_n, loss, guard, split):
    """One dispatch of the layout's solve program on a prepared design."""
    if isinstance(design.A, BlockedCSC):
        return _sparse_fused_solve(design.A.rows, design.A.vals, design.y,
                                   prob.lam, design.beta, key, K, rounds, R,
                                   loss, x0=x0, guard=guard, d=prob.d,
                                   split=split)
    return _fused_solve(design.A, design.y, design.mask, prob.lam,
                        design.beta, key, K, rounds, R, block, tile_n, loss,
                        x0=x0, guard=guard, n=prob.n, d=prob.d, split=split)


@functools.partial(jax.profiler.annotate_function, name="shotgun.solve")
def block_shotgun_solve(prob: Problem, key: jax.Array,
                        K: int | None = None, rounds: int | None = None,
                        block: int = BLOCK,
                        rounds_per_launch: int | None = None,
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
    (DESIGN §9): a tripped launch rolls back to the last-good snapshot and
    the effective block count halves toward ``p_min``.

    Each kernel launch runs ``rounds_per_launch`` rounds with the margin
    held in VMEM; it must divide ``rounds``, and left unset it is the
    largest divisor of ``rounds`` that is at most 8.

    ``x0`` warm-starts the iterate (λ-continuation, ``core.path``): it is
    zero-padded to the block-padded width and the margin is initialized to
    ``z0 = A x0`` — padded columns carry zero weight so the trajectory of
    real coordinates is unchanged.

    A ``BlockedCSC`` problem routes to the fused sparse kernel
    (``kernels/shotgun_sparse.py``, DESIGN §8.3): same block draws for the
    same key, so the trajectory matches the dense path on the densified
    design; nnz tiles are the only per-round A traffic and ``tile_n`` is
    ignored.  It runs only on the CPU backend; elsewhere this raises
    ``NotImplementedError`` (``require_sparse_backend``).

    ``spec=SolverSpec(...)`` is the canonical interface (DESIGN §12): K is
    derived as ceil(spec.P / block) and ``guard``/``newton`` come from the
    spec.  The legacy (K, rounds, ...) kwargs still work through this shim
    (same jitted core, bit-for-bit) but emit a ``DeprecationWarning``.
    ``newton=True`` (or ``spec.newton``) swaps the β-Lipschitz step for
    the per-block Newton curvature computed from the already-fetched A
    tile.

    The host side runs in the profiler span ``shotgun.solve`` and returns
    before the device finishes: a dense design that is not aligned to
    (``TILE_N``, ``block``) is padded by one program (``pad_problem``),
    then one dispatch of the solve program pads x0 and cuts the result
    back to (n, d) itself.  ``BlockShotgunPath`` runs the same program for
    the chunks of a λ path, on a design it pads once.
    """
    if spec is not None:
        reject_legacy_kwargs(spec, K=K, rounds=rounds, guard=guard)
        spec.check_loss(prob.loss)
        K = max(1, -(-spec.P // block))
        rounds = spec.rounds
        guard, newton = spec.guard, spec.newton
    else:
        if K is None or rounds is None:
            raise TypeError("block_shotgun_solve needs (K, rounds) or spec=")
        warnings.warn(
            "block_shotgun_solve(K=..., rounds=...) kwargs are deprecated; "
            "pass spec=SolverSpec(...)", DeprecationWarning, stacklevel=3)
    R, loss = _launch_plan(prob, rounds, block, rounds_per_launch, newton)
    return _solve(prob, _design(prob), key, x0, K, rounds, R, block, tile_n,
                  loss, guard, split=False)


class BlockShotgunPath:
    """``block_shotgun_solve`` over the chunks of one λ path
    (``core.path.solve_path``).

    A call ``(prob, key, P, rounds, x0) -> (next key, Result)`` is one
    chunk and one dispatch of the solve program, in its own
    ``shotgun.solve`` span: the program splits the path's ``key`` as
    ``jax.random.split`` would on the host, solves with the second half
    from ``x0`` and returns the first.  K is ceil(P / 128) unless ``K`` is
    given; the other keywords are ``block_shotgun_solve``'s.

    The design is prepared on the first call and kept while later calls
    hand the same A, y and loss, as ``prob._replace(lam=...)`` does, so a
    ragged dense design is padded once a path; ``pads`` counts the padded
    copies of A made (0 for an aligned or a ``BlockedCSC`` design).
    """

    def __init__(self, K: int | None = None, block: int = BLOCK,
                 rounds_per_launch: int | None = None,
                 tile_n: int | None = None,
                 guard: GuardConfig | None = None, newton: bool = False):
        self.K, self.block, self.tile_n = K, block, tile_n
        self.rounds_per_launch, self.guard, self.newton = (
            rounds_per_launch, guard, newton)
        self.pads, self._design = 0, None

    @functools.partial(jax.profiler.annotate_function, name="shotgun.solve")
    def __call__(self, prob: Problem, key: jax.Array, P: int, rounds: int,
                 x0: jax.Array | None):
        design = self._design
        if design is None or not design.serves(prob):
            design = self._design = _design(prob)
            self.pads += design.pads
        K = max(1, -(-P // BLOCK)) if self.K is None else self.K
        R, loss = _launch_plan(prob, rounds, self.block,
                               self.rounds_per_launch, self.newton)
        return _solve(prob, design, key, x0, K, rounds, R, self.block,
                      self.tile_n, loss, self.guard, split=True)
