"""Per-shard round engines for the distributed solver (DESIGN §3).

The distributed Shotgun driver (``core/sharded.py``) is a thin shard_map
loop over a pluggable **round engine**: the per-shard computation "run R
rounds of coordinate updates against a margin snapshot z, emit the margin
contribution Δz = A_shard δx" behind one small protocol, so the same driver
composes the scalar jnp path and the fused multi-round Pallas kernels
(dense §4.2, sparse §8.3) with either merge cadence.

Protocol (all engines are hashable NamedTuples so they can ride through
``jax.jit`` as static configuration; the driver owns iterate init,
padding, and the Δz merge):

  ``engine.run(A_blk, y, mask, lam, beta, z, x_l, keys, p_eff)
      -> (x_l, dz, health)``
      run ``keys.shape[0]`` rounds.  ``z`` is the last *merged* global
      margin; the engine sees its own updates immediately (its live view is
      ``z + dz_partial``) and other shards' updates only at the next merge —
      with ``merge="round"`` the driver merges after every round, so there
      is no staleness; with ``merge="launch"`` the engine runs R stale
      rounds per merge (the paper's interference story, Lemma 3.3, as an
      explicit knob).  ``keys`` are already shard-decorrelated by the
      driver.

      ``p_eff`` (dynamic int32 scalar) is the driver's adaptive-P backoff
      knob (DESIGN §9), in the engine's own parallelism units (coordinates
      for the scalar engine, 128-blocks for the fused ones): each round still
      draws the engine's full candidate set but masks updates at or past
      ``p_eff`` — a bit-exact no-op at full width.  ``health`` is a scalar
      f32 flag (0.0 healthy / 1.0 tripped): the O(1)-per-merge divergence
      sentinel — non-finite Δz (or, for the fused engines, the in-kernel
      health output).

  ``engine.run_segment(A_blk, y, mask, lam, beta, z, w_pend, x_l, keys,
      p_eff) -> (x_l, dz, health)``
      the pipelined-mode entry (DESIGN §3.4): one merge window against the
      *stale* merged margin ``z`` plus the shard's own not-yet-merged wire
      contribution ``w_pend`` from the previous segment.  The emitted Δz is
      relative to ``z + w_pend``, so the driver's catch-up
      ``z + psum(w_pend)`` counts each shard's pending wire exactly once.
      The shared default simply calls ``run`` on ``z + w_pend`` — exact for
      every engine because ``run`` only ever reads the margin through an
      additive base (``z + dz_partial`` in the scan engines, the VMEM-
      resident view seeded from ``z`` in the fused kernels).  The seam
      exists so an engine with its own overlap schedule (e.g. a kernel that
      double-buffers the wire in VMEM) can override it without touching the
      driver.

  ``engine.p_full``
      the engine's full parallelism in the same units, for initializing the
      driver's ``p_eff`` carry.

  ``engine.fold_always``
      scalar engine: True — the per-round key is folded with the shard
      index even on a 1-shard mesh, preserving the pre-engine trajectory
      bit-for-bit.  The fused engines fold only on real multi-shard
      meshes so a 1-shard run draws *exactly* the same block indices as the
      single-device solver in ``kernels/ops.py`` (trace-equivalence,
      DESIGN §3).

Engines never touch collectives — the driver owns the Δz merge (psum /
hierarchical psum / compressed, DESIGN §7).  Pallas imports stay inside
method bodies so ``repro.core`` remains import-light.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import health
from repro.core import objectives as obj

ENGINE_NAMES = ("scalar", "fused", "sparse_fused")


def _run_segment(self, A_blk, y, mask, lam, beta, z, w_pend, x_l, keys,
                 p_eff):
    """Shared ``run_segment`` implementation (assigned as a class attribute
    on each engine — plain functions are descriptors, so it binds like a
    method): fold the pending wire into the margin base and run the window.
    """
    return self.run(A_blk, y, mask, lam, beta, z + w_pend, x_l, keys, p_eff)


class ScalarEngine(NamedTuple):
    """The original per-coordinate jnp engine (trajectory-preserving).

    Each round samples ``P_local`` coordinates of the shard (with
    replacement) and applies the Shooting update against the current local
    margin view — exactly the pre-refactor ``round_fn`` of
    ``core/sharded.py``.
    """

    P_local: int
    loss: str

    fold_always = True
    run_segment = _run_segment

    @property
    def p_full(self):
        return self.P_local

    def run(self, A_blk, y, mask, lam, beta, z, x_l, keys, p_eff):
        d_local = x_l.shape[0]
        live = health.live_mask(self.P_local, p_eff)

        def round_fn(carry, key_t):
            x_l, dz = carry
            idx = jax.random.randint(key_t, (self.P_local,), 0, d_local)
            r = obj.residual_like(z + dz, y, self.loss) * mask
            Ap = A_blk[:, idx]
            g = Ap.T @ r
            delta = obj.shooting_delta(x_l[idx], g, lam, beta) * live
            x_l = x_l.at[idx].add(delta)
            dz = dz + Ap @ delta
            return (x_l, dz), None

        (x_l, dz), _ = jax.lax.scan(round_fn, (x_l, jnp.zeros_like(z)), keys)
        return x_l, dz, health.nonfinite_flag(dz)


class FusedEngine(NamedTuple):
    """Fused multi-round Pallas engine: all R rounds of a merge window in
    ONE ``pallas_call`` with the local margin view and Δz accumulator
    resident in VMEM (``fused_shotgun_delta_rounds``, DESIGN §4.2)."""

    K: int
    loss: str
    block: int = 128
    tile_n: int | None = None     # None: the kernel sizes it (auto_tile_n)

    fold_always = False
    run_segment = _run_segment

    @property
    def p_full(self):
        return self.K

    def run(self, A_blk, y, mask, lam, beta, z, x_l, keys, p_eff):
        from repro.kernels.shotgun_block import fused_shotgun_delta_rounds
        nblk = x_l.shape[0] // self.block
        draw = lambda kt: jax.random.choice(kt, nblk, (self.K,),
                                            replace=False)
        idx = jax.vmap(draw)(keys).astype(jnp.int32)
        return fused_shotgun_delta_rounds(
            A_blk, z, x_l, idx, lam, beta, y, mask, loss=self.loss,
            block=self.block, tile_n=self.tile_n, k_eff=p_eff)


class SparseFusedEngine(NamedTuple):
    """Fused multi-round sparse engine for BlockedCSC designs (DESIGN §8.3):
    all R rounds of a merge window in ONE ``pallas_call`` with the shard's
    live local margin view AND the Δz accumulator resident in VMEM,
    streaming only the selected (tile, 128) nnz tiles
    (``fused_sparse_shotgun_delta_rounds``).  ``A_blk`` arrives as a
    column-sharded ``BlockedCSC`` (leaves split on the nblk axis by
    shard_map) and only its raw rows/vals tiles are read, so the global-d
    metadata needs no per-shard fix-up (block width included — no
    ``block`` field);
    the sample mask is ignored (the sparse path never pads samples)."""

    K: int
    loss: str

    fold_always = False
    run_segment = _run_segment

    @property
    def p_full(self):
        return self.K

    def run(self, A_blk, y, mask, lam, beta, z, x_l, keys, p_eff):
        from repro.kernels.shotgun_sparse import (
            fused_sparse_shotgun_delta_rounds)
        rows, vals = A_blk.rows, A_blk.vals
        nblk = rows.shape[0]
        draw = lambda kt: jax.random.choice(kt, nblk, (self.K,),
                                            replace=False)
        idx = jax.vmap(draw)(keys).astype(jnp.int32)
        return fused_sparse_shotgun_delta_rounds(
            rows, vals, z, x_l, idx, lam, beta, y, loss=self.loss,
            k_eff=p_eff)


def make_engine(name: str, *, loss: str, P_local: int = 8, K: int = 2,
                block: int = 128, tile_n: int | None = None,
                newton: bool = False):
    """Engine registry: build a ``RoundEngine`` by name (``ENGINE_NAMES``).

    ``loss`` is a registry string ("lasso" / "logistic") or a full
    ``kernels.shotgun_block.Loss`` spec — engines carry it as static
    configuration either way.  ``newton=True`` upgrades a fused engine to
    the per-block Newton curvature step (DESIGN §12); the scalar engine
    has no curvature tile, so it is fused-only.
    """
    if newton:
        if name not in ("fused", "sparse_fused"):
            raise ValueError(
                f"newton=True requires a fused engine, got {name!r}")
        from repro.kernels.shotgun_block import resolve_loss
        loss = resolve_loss(loss)._replace(newton=True)
    if name == "scalar":
        # the scalar engine reads the loss through objectives.py, which
        # only knows registry names
        lname = loss if isinstance(loss, str) else loss.name
        return ScalarEngine(P_local=P_local, loss=lname)
    if name == "fused":
        return FusedEngine(K=K, loss=loss, block=block, tile_n=tile_n)
    if name == "sparse_fused":
        return SparseFusedEngine(K=K, loss=loss)
    raise ValueError(f"unknown engine {name!r}; choose from {ENGINE_NAMES}")
