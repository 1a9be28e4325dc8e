"""Continuous-batched multi-problem solving: stacked slots, one jaxpr
(DESIGN §11).

The serving scenario (ROADMAP "millions of users": λ-path sweeps and
repeat solves) issues many independent (problem, λ) requests whose
individual solves under-fill a launch.  This module stacks up to S of them
on a new leading *slot* axis and drives the fused Pallas kernels through
the batched entry points (``kernels/batched.py``), so one ``pallas_call``
advances every live slot R rounds:

  * ``BatchMeta`` / ``normalize_problem`` — the admission contract: every
    request is zero-padded to ONE canonical stacked shape (dense: sample/
    block padding via ``ops.pad_problem`` semantics; BlockedCSC: block
    padding via ``data.sparse.pad_feature_blocks`` + tile-axis padding),
    so the whole request stream traces exactly one jaxpr (SL102).  Padded
    rows/columns are additive identities — masked samples and zero
    columns are fixed points of the update — so the per-slot trajectory
    equals the standalone solve of the same padded problem.
  * ``batched_block_shotgun_solve`` — the fixed-budget stacked solve:
    slot *i* is bit-identical in x to ``ops.block_shotgun_solve(prob_i,
    key_i)`` for the same key (dense and BlockedCSC; tested).
  * ``launch_rounds`` — the serving step: ONE batched launch of R rounds
    against stacked state, per-slot ``k_eff`` freezing converged/empty
    slots bit-exactly, returning the in-kernel per-round objective/nnz
    traces and health scalars the service reads at the launch boundary.
  * ``WarmStartCache`` — (problem_id, λ)-keyed x cache with nearest-λ
    fallback, shared by the solver service (``launch/solver_serve.py``)
    and ``core.path.solve_path(cache=...)`` so λ-continuation and repeat
    traffic ride one warm-start code path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import health
from repro.core import objectives as obj
from repro.core.objectives import Problem
from repro.core.shotgun import Result, Trace
from repro.core.spec import SolverSpec, reject_legacy_kwargs
from repro.data.sparse import BlockedCSC, bcsc_matvec, pad_feature_blocks
from repro.kernels.batched import (batched_draw_blocks,
                                   batched_fused_shotgun_rounds,
                                   batched_fused_sparse_shotgun_rounds)
from repro.kernels.shotgun_block import BLOCK, TILE_N
from repro.kernels.shotgun_sparse import require_sparse_backend


class BatchMeta(NamedTuple):
    """Canonical stacked shape every admitted request is normalized to.

    One ``BatchMeta`` ⇒ one jaxpr: the service builds it once (from its
    first request or an explicit template) and every later admission is
    padded to it — never the other way round (growing the canvas would
    retrace).  ``layout`` is "dense" or "bcsc"; sparse metadata (``nblk``,
    ``tile``) is 0 for dense and ``n_pad``/``d_pad`` are the padded sample/
    feature counts (dense pads samples to a ``TILE_N`` multiple exactly
    like ``ops.pad_problem``; bcsc never pads samples, DESIGN §8)."""
    layout: str
    loss: str
    n: int            # true sample count (common to the stream)
    n_pad: int        # padded sample count (== n for bcsc)
    d_pad: int        # padded feature count (nblk · block)
    block: int
    tile: int         # bcsc nnz-tile depth (0 for dense)

    @property
    def nblk(self) -> int:
        return self.d_pad // self.block


def batch_meta_of(prob: Problem, block: int = BLOCK,
                  tile_n: int = TILE_N) -> BatchMeta:
    """The canonical shape a stream templated on ``prob`` normalizes to."""
    if isinstance(prob.A, BlockedCSC):
        return BatchMeta(layout="bcsc", loss=prob.loss, n=prob.n,
                         n_pad=prob.n, d_pad=prob.A.d_pad,
                         block=prob.A.block, tile=prob.A.tile)
    n, d = prob.A.shape
    n_pad = n + (-n) % tile_n
    d_pad = d + (-d) % block
    return BatchMeta(layout="dense", loss=prob.loss, n=n, n_pad=n_pad,
                     d_pad=d_pad, block=block, tile=0)


class SlotArrays(NamedTuple):
    """One admitted problem, normalized to a ``BatchMeta`` canvas.  Dense
    slots carry ``A``/``mask``; bcsc slots carry ``rows``/``vals``.  The
    unused pair is None — the stream is single-layout by construction."""
    A: jax.Array | None          # (n_pad, d_pad) f32
    rows: jax.Array | None       # (nblk, tile, block) int32
    vals: jax.Array | None       # (nblk, tile, block) f32
    y: jax.Array                 # (n_pad,) f32
    mask: jax.Array | None       # (n_pad,) f32 (dense only)
    lam: jax.Array               # () f32
    beta: jax.Array              # () f32


def normalize_problem(prob: Problem, meta: BatchMeta) -> SlotArrays:
    """Admission shape-normalization: zero-pad ``prob`` onto the stream's
    canonical canvas.  Raises when the problem cannot fit (larger than the
    canvas, mismatched loss/layout/samples) — admission never grows the
    canvas, because that would retrace the stream's one jaxpr."""
    sparse = isinstance(prob.A, BlockedCSC)
    layout = "bcsc" if sparse else "dense"
    if layout != meta.layout:
        raise ValueError(f"layout {layout!r} != stream layout "
                         f"{meta.layout!r}")
    if prob.loss != meta.loss:
        raise ValueError(f"loss {prob.loss!r} != stream loss {meta.loss!r}")
    if prob.n != meta.n:
        raise ValueError(f"n={prob.n} != stream n={meta.n} — the sample "
                         "dimension is common to the whole stream")
    if sparse:
        S = prob.A
        if S.block != meta.block:
            raise ValueError(f"block={S.block} != stream block={meta.block}")
        if S.tile > meta.tile:
            raise ValueError(f"tile={S.tile} > stream tile={meta.tile} — "
                             "denser than the stream canvas admits")
        if S.d_pad > meta.d_pad:
            raise ValueError(f"d_pad={S.d_pad} > stream d_pad={meta.d_pad}")
        S = pad_feature_blocks(S, meta.nblk)       # right-pad zero blocks
        rows, vals = S.rows, S.vals
        if S.tile < meta.tile:                     # pad the nnz-tile axis
            pad = ((0, 0), (0, meta.tile - S.tile), (0, 0))
            rows = jnp.pad(rows, pad)              # (row 0, val 0) slots are
            vals = jnp.pad(vals, pad)              # additive identities
        return SlotArrays(A=None, rows=rows,
                          vals=vals.astype(jnp.float32),
                          y=jnp.asarray(prob.y, jnp.float32), mask=None,
                          lam=jnp.asarray(prob.lam, jnp.float32),
                          beta=jnp.asarray(prob.beta, jnp.float32))
    n, d = prob.A.shape
    if d > meta.d_pad:
        raise ValueError(f"d={d} > stream d_pad={meta.d_pad}")
    A = jnp.pad(jnp.asarray(prob.A, jnp.float32),
                ((0, meta.n_pad - n), (0, meta.d_pad - d)))
    y = jnp.pad(jnp.asarray(prob.y, jnp.float32), (0, meta.n_pad - n))
    mask = jnp.pad(jnp.ones(n, jnp.float32), (0, meta.n_pad - n))
    return SlotArrays(A=A, rows=None, vals=None, y=y, mask=mask,
                      lam=jnp.asarray(prob.lam, jnp.float32),
                      beta=jnp.asarray(prob.beta, jnp.float32))


def stack_problems(probs: Sequence[Problem], meta: BatchMeta | None = None
                   ) -> tuple[BatchMeta, SlotArrays]:
    """Normalize every problem to one canvas and stack on a leading slot
    axis.  With ``meta=None`` the canvas is the elementwise max over the
    stack (so any member could have been the template)."""
    if not probs:
        raise ValueError("stack_problems: empty problem list")
    if meta is None:
        metas = [batch_meta_of(p) for p in probs]
        m0 = metas[0]
        for m in metas[1:]:
            if (m.layout, m.loss, m.n, m.block) != (m0.layout, m0.loss,
                                                    m0.n, m0.block):
                raise ValueError(
                    f"heterogeneous stream: {m0.layout}/{m0.loss}/n={m0.n}"
                    f"/block={m0.block} vs {m.layout}/{m.loss}/n={m.n}"
                    f"/block={m.block}")
        meta = m0._replace(
            n_pad=max(m.n_pad for m in metas),
            d_pad=max(m.d_pad for m in metas),
            tile=max(m.tile for m in metas))
    slots = [normalize_problem(p, meta) for p in probs]
    stacked = jax.tree.map(
        lambda *xs: None if xs[0] is None else jnp.stack(xs), *slots,
        is_leaf=lambda x: x is None)
    return meta, stacked


# ---------------------------------------------------------------------------
# One batched launch (the serving step) and the fixed-budget stacked solve
# ---------------------------------------------------------------------------

def launch_rounds(meta: BatchMeta, stacked: SlotArrays, z, x, idx, k_eff,
                  guard_f=None, tile_n: int | None = None):
    """ONE batched launch: R fused rounds on every slot with ``k_eff[s]``
    live blocks (0 = frozen, bit-exact no-op).  ``guard_f`` is the per-slot
    in-kernel objective guard ((S,), None = +inf = unguarded, bit-exact):
    a slot whose objective blows past its threshold freezes mid-launch and
    raises its health scalar — the service reads it at the boundary and
    rolls that slot back (§11.3).  Returns (x (S, d_pad), z (S, n_pad),
    f (S, R), nnz (S, R), health (S,))."""
    S = z.shape[0]
    guard = (jnp.full((S,), jnp.inf, jnp.float32) if guard_f is None
             else jnp.asarray(guard_f, jnp.float32))
    k_eff = jnp.asarray(k_eff, jnp.float32)
    if meta.layout == "bcsc":
        return batched_fused_sparse_shotgun_rounds(
            stacked.rows, stacked.vals, z, x, idx, stacked.lam,
            stacked.beta, stacked.y, k_eff, guard, loss=meta.loss)
    return batched_fused_shotgun_rounds(
        stacked.A, z, x, idx, stacked.lam, stacked.beta, stacked.y,
        stacked.mask, k_eff, guard, loss=meta.loss, block=meta.block,
        tile_n=tile_n)


def init_margin(meta: BatchMeta, stacked: SlotArrays, x):
    """Stacked warm-start margins z0 = A x0, f32 accumulation — exactly the
    per-slot init of ``ops._fused_solve`` / ``_sparse_fused_solve``."""
    if meta.layout == "bcsc":
        return jax.vmap(lambda r, v, x_: bcsc_matvec(r, v, x_, meta.n_pad)
                        )(stacked.rows, stacked.vals, x)
    return jax.vmap(lambda a, x_: obj.matvec(a.astype(jnp.float32), x_))(
        stacked.A, x)


def _stack_x0(x0s, S, d_pad):
    if x0s is None:
        return jnp.zeros((S, d_pad), jnp.float32)
    cols = []
    for x0 in x0s:
        if x0 is None:
            cols.append(jnp.zeros(d_pad, jnp.float32))
        else:
            x0 = jnp.asarray(x0, jnp.float32)
            cols.append(jnp.pad(x0, (0, d_pad - x0.shape[0])))
    return jnp.stack(cols)


def batched_block_shotgun_solve(probs: Sequence[Problem], keys,
                                K: int | None = None,
                                rounds: int | None = None,
                                rounds_per_launch: int = 8,
                                meta: BatchMeta | None = None,
                                x0s=None, tile_n: int | None = None,
                                spec: SolverSpec | None = None
                                ) -> Result:
    """Fixed-budget stacked solve: every slot runs the full round budget in
    lock-step batched launches.  Slot *i* is bit-identical in x to
    ``ops.block_shotgun_solve(probs[i], keys[i], K, rounds,
    rounds_per_launch=R)`` run standalone on the same padded canvas — the
    vmapped kernels change the grid, not the math (tested for dense and
    BlockedCSC in tests/test_batched_serve.py).

    ``keys`` is a sequence/stack of S PRNG keys, one per slot: each slot
    draws its own independent key stream, exactly the standalone draw
    sequence, so results do not depend on which slot a problem lands in.
    Returns a stacked ``Result`` (leaves carry the leading S axis; x is
    sliced to each problem's true d only by the caller, since slots may
    have heterogeneous d on one canvas).

    ``spec=SolverSpec(...)`` is the canonical interface (DESIGN §12):
    K = ceil(spec.P / block) and rounds = spec.rounds, with ``spec.loss``
    validated against every admitted problem's loss.  The legacy
    (K, rounds) kwargs still work but emit a ``DeprecationWarning``.
    """
    if spec is not None:
        reject_legacy_kwargs(spec, K=K, rounds=rounds)
        for p_i in probs:
            spec.check_loss(p_i.loss)
        K = max(1, -(-spec.P // BLOCK))
        rounds = spec.rounds
    else:
        if K is None or rounds is None:
            raise TypeError(
                "batched_block_shotgun_solve needs (K, rounds) or spec=")
        import warnings
        warnings.warn(
            "batched_block_shotgun_solve(K=..., rounds=...) kwargs are "
            "deprecated; pass spec=SolverSpec(...)", DeprecationWarning,
            stacklevel=2)
    R = rounds_per_launch
    if rounds % R:
        raise ValueError(f"rounds={rounds} not divisible by "
                         f"rounds_per_launch={R}")
    meta, stacked = stack_problems(probs, meta)
    if meta.layout == "bcsc":
        require_sparse_backend()
    S = len(probs)
    keys = jnp.stack([jnp.asarray(k) for k in keys]) \
        if not isinstance(keys, jax.Array) else keys
    if keys.shape[0] != S:
        raise ValueError(f"{keys.shape[0]} keys for {S} problems")
    x0 = _stack_x0(x0s, S, meta.d_pad)
    z0 = init_margin(meta, stacked, x0)
    L = rounds // R
    # per-slot key schedule == ops._fused_solve: split(key, rounds) → (L, R)
    keys_lr = jax.vmap(lambda k: jax.random.split(k, rounds))(keys)
    keys_lr = keys_lr.reshape(S, L, R, -1).transpose(1, 0, 2, 3)
    k_eff = jnp.full((S,), float(K), jnp.float32)

    def launch_fn(carry, keys_l):
        x, z = carry
        idx = batched_draw_blocks(keys_l, K, meta.nblk)
        x, z, fs, nnzs, _ = launch_rounds(meta, stacked, z, x, idx, k_eff,
                                          tile_n=tile_n)
        return (x, z), (fs, nnzs)

    (x, z), (fs, nnzs) = jax.lax.scan(launch_fn, (x0, z0), keys_lr)
    fs = fs.transpose(1, 0, 2).reshape(S, rounds)
    nnzs = nnzs.transpose(1, 0, 2).reshape(S, rounds)
    status = jax.vmap(health.status_from_trace)(fs)
    return Result(x=x, z=z, trace=Trace(objective=fs, nnz=nnzs),
                  status=status)


# ---------------------------------------------------------------------------
# Warm-start cache: (problem_id, λ) → x, with nearest-λ fallback
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CacheStats:
    hits_exact: int = 0
    hits_near: int = 0
    misses: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits_exact + self.hits_near + self.misses
        return 0.0 if not total else (self.hits_exact + self.hits_near) \
            / total


class WarmStartCache:
    """Warm-start x cache keyed on (problem_id, λ) (DESIGN §11.4).

    ``get`` returns the stored solution on an exact-λ hit (relative
    tolerance ``lam_rtol``) and falls back to the NEAREST cached λ for the
    same problem_id otherwise — λ-path neighbours are the classic warm
    start (Sec. 4.1.1), so repeat traffic that lands between sweep points
    still starts near the solution manifold.  Keys carry the problem's
    loss tag (default "lasso" for legacy callers), so a lasso warm start
    can never seed a logistic solve of the same problem_id.  Entries store
    the true-d
    (unpadded) x as host numpy; admission re-pads onto whatever canvas the
    consuming stream uses.  Shared by ``launch/solver_serve.py`` and
    ``core.path.solve_path(cache=...)`` — one warm-start code path.
    """

    def __init__(self, lam_rtol: float = 1e-6):
        self.lam_rtol = lam_rtol
        self._store: dict = {}     # (pid, loss) -> {float(lam): np.ndarray}
        self.stats = CacheStats()

    def __len__(self) -> int:
        return sum(len(v) for v in self._store.values())

    def put(self, problem_id, lam, x, loss: str = "lasso") -> None:
        self._store.setdefault((problem_id, loss), {})[float(lam)] = \
            np.asarray(x, np.float32)

    def get(self, problem_id, lam, loss: str = "lasso"):
        """(x0 | None, kind) with kind in "exact" / "near" / "miss"."""
        lam = float(lam)
        entries = self._store.get((problem_id, loss))
        if not entries:
            self.stats.misses += 1
            return None, "miss"
        nearest = min(entries, key=lambda l: abs(l - lam))
        if abs(nearest - lam) <= self.lam_rtol * max(1.0, abs(lam)):
            self.stats.hits_exact += 1
            return entries[nearest], "exact"
        self.stats.hits_near += 1
        return entries[nearest], "near"


# ---------------------------------------------------------------------------
# Launch-boundary convergence test (host-side, shared by service + path)
# ---------------------------------------------------------------------------

def launch_converged(f_prev, f_launch, tol: float) -> bool:
    """Has a slot converged over one launch?  True when the objective's
    relative CHANGE from the pre-launch value to the launch's last round is
    below ``tol`` in magnitude (and stayed finite) — the launch boundary is
    the only place per-slot progress is observable without breaking the
    fused R-round dataflow, so this is deliberately coarse: a slot costs at
    most one extra launch past true convergence.  The test is symmetric on
    purpose: an objective that moved UP more than tol is overshooting
    (early-round interference, Thm 3.2's P² term), not converged — only a
    genuinely flat launch stops the solve."""
    f_prev = float(f_prev)
    f_end = float(f_launch[-1])
    if not np.isfinite(f_end):
        return False
    return abs(f_prev - f_end) <= tol * max(1.0, abs(f_end))
