"""Batched (multi-slot) entry points for the fused Shotgun kernels
(DESIGN §11).

The serving layer stacks up to S independent (problem, λ) *slots* on a new
leading axis and runs them all in ONE launch of the existing fused kernels
(``shotgun_block.fused_shotgun_rounds`` / ``shotgun_sparse.
fused_sparse_shotgun_rounds``) via ``jax.vmap``: the batch dimension
becomes the outermost grid dimension, each slot re-initializes the VMEM
scratch from its own (z0, x0) block, and every per-slot quantity that used
to be a scalar — λ, β, the §9 ``k_eff`` backoff count and the ``guard_f``
objective guard — rides the scalar-prefetch vector as an (S,)-batched
per-slot scalar.  Two consequences the serving layer is built on:

  * slot *i* of the batched launch is bit-identical to an unbatched launch
    of the same slot state (tested in tests/test_batched_serve.py) — the
    kernel body, accumulation order, and draws are untouched, only the
    grid gains an outer dimension;
  * ``k_eff = 0`` makes a slot a bit-exact no-op (every delta is masked to
    zero, the slot's x/z pass through), so converged, empty, or backed-off
    slots cost no retrace and change no shapes — the admission contract
    that keeps the whole request stream on one jaxpr (SL102).

``shared_design=True`` broadcasts one design across all slots
(``in_axes=None`` for A / the nnz tiles) — the λ-path and repeat-traffic
case, where stacking S copies of A would multiply HBM residency S× for no
information.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.shotgun_block import (BLOCK, fused_shotgun_rounds)
from repro.kernels.shotgun_sparse import fused_sparse_shotgun_rounds


@functools.partial(jax.jit, static_argnames=("loss", "block", "tile_n",
                                             "interpret", "shared_design"))
def batched_fused_shotgun_rounds(A, z, x, blk_idx, lam, beta, y, mask,
                                 k_eff, guard_f, loss: str = "lasso",
                                 block: int = BLOCK,
                                 tile_n: int | None = None,
                                 interpret: bool | None = None,
                                 shared_design: bool = False):
    """R fused dense rounds on S stacked slots in ONE launch.

    A        (S, n, d) stacked designs, or (n, d) with
             ``shared_design=True`` (broadcast, not copied).
    z/y/mask (S, n);  x (S, d);  blk_idx (S, R, K) int32 per-slot draws.
    lam/beta/k_eff/guard_f  (S,) per-slot prefetch scalars — ``k_eff[s]=0``
             freezes slot s bit-exactly (DESIGN §11.2).

    Returns (x (S, d), z (S, n), f (S, R), nnz (S, R), health (S,)).
    """
    run = functools.partial(fused_shotgun_rounds, loss=loss, block=block,
                            tile_n=tile_n, interpret=interpret)
    a_ax = None if shared_design else 0
    return jax.vmap(
        lambda a, z_, x_, i_, l_, b_, y_, m_, ke, gf:
            run(a, z_, x_, i_, l_, b_, y_, m_, k_eff=ke, guard_f=gf),
        in_axes=(a_ax, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    )(A, z, x, blk_idx, lam, beta, y, mask, k_eff, guard_f)


@functools.partial(jax.jit, static_argnames=("loss", "interpret",
                                             "shared_design"))
def batched_fused_sparse_shotgun_rounds(rows, vals, z, x, blk_idx, lam,
                                        beta, y, k_eff, guard_f,
                                        loss: str = "lasso",
                                        interpret: bool | None = None,
                                        shared_design: bool = False):
    """R fused sparse rounds on S stacked slots in ONE launch.

    rows/vals  (S, nblk, tile, block) stacked BlockedCSC tiles, or
               (nblk, tile, block) with ``shared_design=True``.
    z/y        (S, n);  x (S, nblk·block);  blk_idx (S, R, K) int32.
    lam/beta/k_eff/guard_f  (S,) per-slot prefetch scalars.

    Returns (x (S, nblk·block), z (S, n), f (S, R), nnz (S, R),
    health (S,)).
    """
    run = functools.partial(fused_sparse_shotgun_rounds, loss=loss,
                            interpret=interpret)
    a_ax = None if shared_design else 0
    return jax.vmap(
        lambda rw, vl, z_, x_, i_, l_, b_, y_, ke, gf:
            run(rw, vl, z_, x_, i_, l_, b_, y_, k_eff=ke, guard_f=gf),
        in_axes=(a_ax, a_ax, 0, 0, 0, 0, 0, 0, 0, 0),
    )(rows, vals, z, x, blk_idx, lam, beta, y, k_eff, guard_f)


@functools.partial(jax.jit, static_argnames=("K", "nblk"))
def batched_draw_blocks(keys, K: int, nblk: int):
    """Per-slot per-round block draws: keys (S, R, 2) → idx (S, R, K) int32.

    Exactly the draw ``ops._fused_solve`` makes per launch (``jax.random.
    choice`` without replacement over ``nblk``), vmapped over slots — so a
    slot fed the key row ``jax.random.split(key, rounds).reshape(L, R, -1)
    [l]`` reproduces the standalone solver's round-``l·R+t`` indices
    bit-for-bit.
    """
    draw = functools.partial(jax.random.choice, a=nblk, shape=(K,),
                             replace=False)
    return jax.vmap(jax.vmap(lambda kt: draw(kt)))(keys).astype(jnp.int32)
