"""Pallas Block-Shotgun kernels for BlockedCSC designs (DESIGN §8).

Sparse counterparts of the dense fused kernels in ``shotgun_block.py``.
The dense kernels stream whole (tile_n × 128) column blocks of A; at the
paper's Large-Sparse densities (~0.002) that is ~500× more HBM traffic than
the nonzeros.  Here a scalar-prefetched block pointer selects the selected
block's padded (tile, 128) nnz row/value tiles instead, so every kernel
touches O(tile·128) bytes of A per block instead of O(n·128).

The fused multi-round kernel (DESIGN §8.3) composes the nnz-tile data path
with the §4.2 VMEM-residency dataflow:

  fused_sparse_shotgun_rounds  R rounds in ONE pallas_call.  The margin z,
  the round-start residual r, the iterate x, and the per-round deltas all
  live in VMEM scratch across the whole launch; a scalar-prefetched (R, K)
  block-index matrix selects each grid step's nnz tiles.  Because z is
  full-length in VMEM (never sample-tiled), every round is "single-phase":
  one tile fetch per block serves both g_B = A_Bᵀ r and z += A_B δ_B.
  ``fused_sparse_shotgun_delta_rounds`` is the shard-local engine variant
  (DESIGN §3): z is a read-only global snapshot and the kernel additionally
  accumulates its contributions into a Δz output for the caller's psum.

Padded tile slots hold (row 0, value 0) so they are additive no-ops in both
directions.  Value tiles may be stored bf16 (``BlockedCSC.astype``) to halve
their HBM/wire bytes — every kernel here casts the fetched tile to f32
before accumulating, exactly like the dense fused kernel's bf16 A storage.

These kernels run only in the Pallas interpreter (CPU backend).  Mosaic
refuses the data-dependent row gather in ``_tile_gather`` ("Only 2D gather
is supported"), and the scatter-add in ``_tile_scatter`` is the same kind
of operation, so every BlockedCSC entry point calls
``require_sparse_backend`` and raises ``NotImplementedError`` on a TPU
rather than falling back.  The tile layout is still chosen for the TPU
path: tiles are rectangular (tile × 128), lane-aligned, and selected by
``PrefetchScalarGridSpec`` index maps exactly like the dense A blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.shotgun_block import (BLOCK, LASSO, Loss, _call_params,
                                         _soft_threshold, interpret_mode,
                                         resolve_loss, vec_vmem_bytes)


def require_sparse_backend() -> None:
    """Refuse a BlockedCSC kernel solve where the kernels cannot lower.

    Called at entry by every solver, engine and service path that would
    run these kernels.  Off the CPU backend they would have to compile
    for Mosaic, which rejects the row gather of ``_tile_gather`` ("Only 2D
    gather is supported"); there is no interpreter or ``kernels/ref.py``
    fallback on purpose."""
    if not interpret_mode():
        raise NotImplementedError(
            f"BlockedCSC solves do not run on the {jax.default_backend()} "
            "backend: Mosaic refuses the data-dependent row gather in "
            "kernels/shotgun_sparse._tile_gather ('Only 2D gather is "
            "supported'); densify the design or solve on the CPU backend")


# ---------------------------------------------------------------------------
# Per-block update math of the fused round: the gather/scatter tile bodies
# and the soft-threshold delta.
# ---------------------------------------------------------------------------

def _tile_gather(rows, vals, r_flat):
    """g (1, block) = A_Bᵀ r from one (tile, block) nnz tile: gather r at the
    row indices, multiply-accumulate over the tile axis."""
    rv = jnp.take(r_flat, rows)                   # (tile, block)
    return jnp.sum(vals * rv, axis=0, keepdims=True)


def _tile_scatter(z_flat, rows, vals, dlt):
    """z + A_B δ from one nnz tile: scatter-add vals·δ at the row indices.
    ``z_flat`` (n,) f32, ``dlt`` (1, block); returns the updated (n,)."""
    contrib = vals * dlt                          # broadcast over tile axis
    return z_flat.at[rows.reshape(-1)].add(contrib.reshape(-1))


def block_delta(x_sel, g, lam, beta):
    """The per-block Shotgun update δ_B = S(x_B − g_B/β, λ/β) − x_B (Alg. 2
    soft-threshold step); ``beta`` is the Lipschitz bound or, for a Newton
    spec, the per-block curvature."""
    return _soft_threshold(x_sel - g / beta, lam / beta) - x_sel


# ---------------------------------------------------------------------------
# The fused multi-round sparse Shotgun kernel — R rounds per launch, z and
# the Δz accumulator resident in VMEM, nnz tiles as the only per-round A
# traffic (DESIGN §8.3).
# ---------------------------------------------------------------------------

def _make_fused_sparse_kernel(loss: Loss, K: int, emit_dz: bool = False):
    """Kernel body factory.  grid = (R, K): one selected column block per
    step, every round "single-phase" — the step's (tile, block) rows/vals
    tiles serve both the gradient gather and the margin scatter, so each
    block's nnz tiles stream exactly once per round.

    ``emit_dz`` selects the shard-local engine variant (DESIGN §3): z0 is a
    read-only *global* margin snapshot; the kernel still keeps its own live
    local view z_s = z0 + Σ own contributions in VMEM, but additionally
    accumulates those contributions into a Δz scratch and outputs (Δz, x)
    instead of (z, x, f, nnz) — the caller merges Δz across shards (psum)
    and owns the trace bookkeeping.

    Divergence sentinel (DESIGN §9): like the dense fused kernel, the
    scalar-prefetch vector carries ``k_eff`` (blocks past it have their
    delta masked to zero; exactly 1.0 at k_eff == K) and a guard objective
    level, and a (1, 1) max-accumulated SMEM health output trips on a
    guard-crossing / non-finite round.

    Per-block Newton (``loss.newton``, DESIGN §12): the round start also
    snapshots the curvature weights w = L''(z) into a (n, 1) scratch; each
    step re-gathers w through the SAME (tile, block) nnz tiles already in
    VMEM as h_B = Σ vals² · w[rows] — no extra A traffic, no extra scratch
    beyond the weight vector (the per-step h is a local, gather and delta
    happen in the same grid step here)."""
    newton = loss.newton

    def kernel(idx_ref, scal_ref, rows_ref, vals_ref, z0_ref, x0_ref, y_ref,
               *refs):
        if newton:
            refs, (w_s,) = refs[:-1], refs[-1:]
        if emit_dz:
            (dzo_ref, xo_ref, h_ref, z_s, dz_s, r_s, x_s, d_s) = refs
        else:
            (zo_ref, xo_ref, f_ref, nnz_ref, h_ref, z_s, r_s, x_s,
             d_s) = refs
        r_id = pl.program_id(0)
        k_id = pl.program_id(1)
        lam = scal_ref[0]
        beta = scal_ref[1]
        k_eff = scal_ref[2].astype(jnp.int32)
        guard = scal_ref[3]
        one = jnp.float32(1.0)       # no sample padding on the sparse path

        @pl.when((r_id == 0) & (k_id == 0))
        def _init_launch():
            z_s[...] = z0_ref[...]
            x_s[...] = x0_ref[...]
            h_ref[0, 0] = jnp.float32(0.0)
            if emit_dz:
                dz_s[...] = jnp.zeros_like(dz_s)

        @pl.when(k_id == 0)
        def _round_start():
            r_s[...] = loss.residual(z_s[...], y_ref[...], one)
            if newton:
                w_s[...] = loss.curvature_weights(z_s[...], y_ref[...], one)

        rows = rows_ref[0]                        # (tile, block)
        vals = vals_ref[0].astype(jnp.float32)
        g = _tile_gather(rows, vals, r_s[...].reshape(-1))    # (1, block)
        if newton:
            # Per-block Newton curvature from the tiles already fetched:
            # h_B = Σ vals² · w[rows] (padded slots are val-0 no-ops).
            h = jnp.maximum(
                _tile_gather(rows, vals * vals, w_s[...].reshape(-1)), 1e-8)
        else:
            h = beta
        b = idx_ref[r_id, k_id]
        # All K deltas are taken from the *pre-round* x (the x scratch is
        # only updated at round end), so duplicate block draws within a
        # round reproduce Alg. 2's multiset semantics exactly; the gathers
        # all read the round-start residual r_s, untouched by the scatters.
        # Backoff mask: blocks at or past k_eff contribute nothing this
        # round (multiply by exactly 1.0 when k_eff == K).
        live = jnp.where(k_id < k_eff, 1.0, 0.0).astype(jnp.float32)
        dlt = block_delta(x_s[pl.ds(b, 1), :], g, lam, h) * live
        d_s[pl.ds(k_id, 1), :] = dlt
        n = z_s.shape[0]
        z_s[...] = _tile_scatter(z_s[...].reshape(-1), rows, vals,
                                 dlt).reshape(n, 1)
        if emit_dz:
            dz_s[...] = _tile_scatter(dz_s[...].reshape(-1), rows, vals,
                                      dlt).reshape(n, 1)

        @pl.when(k_id == K - 1)
        def _round_end():
            def apply_delta(kk, carry):
                bb = idx_ref[r_id, kk]
                x_s[pl.ds(bb, 1), :] += d_s[pl.ds(kk, 1), :]
                return carry

            jax.lax.fori_loop(0, K, apply_delta, 0)
            # Constant-index outputs flush to HBM once, after the last grid
            # step; rewriting them every round is free in VMEM.
            if emit_dz:
                dzo_ref[...] = dz_s[...]
                xo_ref[...] = x_s[...]
                ok = jnp.all(jnp.isfinite(z_s[...]))
                h_ref[0, 0] = jnp.maximum(
                    h_ref[0, 0], jnp.where(ok, 0.0, 1.0))
            else:
                f = loss.objective(z_s[...], y_ref[...], one,
                                   x_s[...], lam)
                f_ref[r_id, 0] = f
                bad = ~jnp.isfinite(f) | (f > guard)
                h_ref[0, 0] = jnp.maximum(
                    h_ref[0, 0], jnp.where(bad, 1.0, 0.0))
                nnz_ref[r_id, 0] = jnp.sum(
                    (x_s[...] != 0).astype(jnp.int32))
                zo_ref[...] = z_s[...]
                xo_ref[...] = x_s[...]

    return kernel


def _fused_sparse_call(rows, vals, z, x, blk_idx, lam, beta, y, loss,
                       interpret, emit_dz, k_eff=None, guard_f=None):
    """Shared pallas_call plumbing for both fused-sparse variants.

    ``k_eff`` (dynamic, defaults to K) and ``guard_f`` (defaults to +inf)
    ride in the scalar-prefetch vector — see the dense ``_fused_call``."""
    loss = resolve_loss(loss)
    nblk, tile, block = rows.shape
    n = z.shape[0]
    R, K = blk_idx.shape

    idx = blk_idx.astype(jnp.int32)
    k_eff = jnp.asarray(K if k_eff is None else k_eff, jnp.float32)
    guard_f = jnp.asarray(jnp.inf if guard_f is None else guard_f,
                          jnp.float32)
    scal = jnp.stack([jnp.asarray(lam, jnp.float32),
                      jnp.asarray(beta, jnp.float32), k_eff, guard_f])
    z0 = z.reshape(n, 1).astype(jnp.float32)
    x0 = x.reshape(nblk, block).astype(jnp.float32)
    y2 = y.reshape(n, 1).astype(jnp.float32)

    tile_map = lambda r, k, idx, scal: (idx[r, k], 0, 0)
    const = lambda r, k, idx, scal: (0, 0)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars (dense twin)

    if emit_dz:
        out_specs = [
            pl.BlockSpec((n, 1), const),            # Δz
            pl.BlockSpec((nblk, block), const),     # x
            smem,                                   # health scalar
        ]
        out_shape = [
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((nblk, block), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ]
        extra_scratch = [pltpu.VMEM((n, 1), jnp.float32)]   # Δz accumulator
    else:
        out_specs = [
            pl.BlockSpec((n, 1), const),            # z
            pl.BlockSpec((nblk, block), const),     # x
            smem,                                   # f trace
            smem,                                   # nnz trace
            smem,                                   # health scalar
        ]
        out_shape = [
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((nblk, block), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ]
        extra_scratch = []

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, K),
        in_specs=[
            pl.BlockSpec((1, tile, block), tile_map),  # rows tile
            pl.BlockSpec((1, tile, block), tile_map),  # vals tile
            pl.BlockSpec((n, 1), const),               # z0   (VMEM-resident)
            pl.BlockSpec((nblk, block), const),        # x0   (VMEM-resident)
            pl.BlockSpec((n, 1), const),               # y    (VMEM-resident)
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),           # z  (live local view)
        ] + extra_scratch + [
            pltpu.VMEM((n, 1), jnp.float32),           # r  (round-start res.)
            pltpu.VMEM((nblk, block), jnp.float32),    # x
            pltpu.VMEM((K, block), jnp.float32),       # delta
        ] + ([
            pltpu.VMEM((n, 1), jnp.float32),           # w  curvature weights
        ] if loss.newton else []),
    )
    return pl.pallas_call(
        _make_fused_sparse_kernel(loss, K, emit_dz=emit_dz),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=("fused_sparse_shotgun_delta_rounds" if emit_dz
              else "fused_sparse_shotgun_rounds"),
        **_call_params(interpret),
    )(idx, scal, rows, vals, z0, x0, y2)


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def fused_sparse_shotgun_rounds(rows, vals, z, x, blk_idx, lam, beta, y,
                                loss: str | Loss = LASSO,
                                interpret: bool | None = None,
                                k_eff=None, guard_f=None):
    """R Block-Shotgun rounds over BlockedCSC tiles in ONE pallas_call.

    rows/vals  (nblk, tile, block) BlockedCSC nnz tiles (DESIGN §8).
    z          (n,) margin A x;  x (nblk·block,) iterate;  y (n,).
    blk_idx    (R, K) int32 — round t updates aligned coordinate blocks
               blk_idx[t, 0..K-1] (duplicates allowed, multiset semantics).
    k_eff      dynamic effective block count (backoff mask, DESIGN §9);
               None = all K live, bit-exactly.
    guard_f    objective guard level for the health output; None = +inf.

    Returns (x_new (nblk·block,) f32, z_new (n,) f32, f (R,) f32,
    nnz (R,) int32, health () f32) with per-round objective/nnz traces
    computed in-kernel — the same contract as the dense
    ``fused_shotgun_rounds`` but with O(tile·128) bytes of A per grid step
    instead of O(n·128).
    """
    nblk, tile, block = rows.shape
    n = z.shape[0]
    R = blk_idx.shape[0]
    z_new, x_new, f, nnz, h = _fused_sparse_call(
        rows, vals, z, x, blk_idx, lam, beta, y, loss, interpret,
        emit_dz=False, k_eff=k_eff, guard_f=guard_f)
    return (x_new.reshape(nblk * block), z_new.reshape(n),
            f.reshape(R), nnz.reshape(R), h.reshape(()))


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def fused_sparse_shotgun_delta_rounds(rows, vals, z, x, blk_idx, lam, beta,
                                      y, loss: str | Loss = LASSO,
                                      interpret: bool | None = None,
                                      k_eff=None):
    """Shard-local fused sparse engine kernel: R rounds against a margin
    *snapshot* (DESIGN §3).  Same dataflow as ``fused_sparse_shotgun_rounds``
    but the kernel does not own the global margin: ``z`` is the last merged
    global snapshot, the live VMEM view tracks only the shard's OWN updates
    on top of it, and the contributions are additionally accumulated into a
    Δz = A_shard δx output for the caller to all-reduce.  ``k_eff`` masks
    blocks past the backoff point; health trips on a non-finite margin view.

    Returns (x_new (nblk·block,) f32, dz (n,) f32, health () f32).
    """
    nblk, tile, block = rows.shape
    n = z.shape[0]
    dz, x_new, h = _fused_sparse_call(
        rows, vals, z, x, blk_idx, lam, beta, y, loss, interpret,
        emit_dz=True, k_eff=k_eff)
    return x_new.reshape(nblk * block), dz.reshape(n), h.reshape(())


def fused_sparse_vmem_bytes(n: int, nblk: int, tile: int, K: int,
                            block: int = BLOCK, emit_dz: bool = False,
                            val_bytes: int = 4, slots: int = 1,
                            loss: str | Loss = "lasso") -> int:
    """VMEM resident set of the fused sparse kernel (DESIGN §8.3), each
    buffer priced at its (8, 128)-tiled layout like the dense twin
    ``shotgun_block.fused_vmem_bytes``: the z/r scratch (+ Δz for the
    engine variant), the z0/y in- and z out-vectors (512 B per sample
    each), the three (nblk, block) x buffers (x0/scratch/out), the K-row
    delta scratch, and the double-buffered (tile, block) rows+vals tile
    pair.  ``val_bytes`` is the stored dtype of the vals tiles (4 = f32,
    2 = bf16 via ``BlockedCSC.astype`` — rows stay int32 and all in-kernel
    accumulation stays f32, so only the vals term shrinks).  R never
    enters: the (R, K) index matrix and the (R, 1) traces live in SMEM.
    ``slots`` is the batched-launch multiplier (DESIGN §11), modeled as
    slots × the per-problem resident set (see
    ``shotgun_block.fused_vmem_bytes``).  ``loss`` prices the logistic
    kernel twins: a Newton spec adds the (n, 1) curvature-weight scratch
    (the per-block h is a per-step local here — no (K, block) accumulator,
    DESIGN §12).  No compiler temporaries are counted: Mosaic refuses
    this kernel before allocating (``require_sparse_backend``)."""
    newton = resolve_loss(loss).newton
    # z0-in, y-in, z_s, r_s, plus z-out (margin-owning) or dz_s + dz-out
    # minus z-out (engine variant): 5 vs 6 n-vectors; Newton adds the
    # curvature-weight vector
    vecs = ((6 if emit_dz else 5) + (1 if newton else 0)) * vec_vmem_bytes(n)
    xbuf = 3 * vec_vmem_bytes(nblk, block)         # x0, x_s, x out
    dbuf = vec_vmem_bytes(K, block)                # delta scratch
    # rows (int32) + vals (val_bytes), each double-buffered
    tiles = 2 * tile * block * (4 + val_bytes)
    return slots * (vecs + xbuf + dbuf + tiles)
