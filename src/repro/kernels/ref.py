"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _curvature_weights_ref(z, y, mask, name: str):
    """Per-sample diagonal-Hessian weights for the Newton oracles, written
    the CDN way (``core.cdn._newton_quantities``: p = σ(z), w = p(1−p)) so
    the oracle stays an independent formulation of the kernel's
    σ(−yz)(1−σ(−yz)) tile — identical for y ∈ {−1, +1}."""
    if name == "lasso":
        return mask
    p = jax.nn.sigmoid(z)
    return p * (1.0 - p) * mask


def _resolve(loss):
    from repro.kernels.shotgun_block import resolve_loss
    return resolve_loss(loss)


def gather_block_matvec_ref(A, r, blk_idx, block: int):
    """g[k] = A[:, blk_k*B:(blk_k+1)*B]^T r  for each selected block k.

    A: (n, d) with d % block == 0;  r: (n,);  blk_idx: (K,) int32.
    Returns (K, block) float32.
    """
    d = A.shape[1]
    Ab = A.reshape(A.shape[0], d // block, block)       # (n, nblk, B)
    Ak = jnp.take(Ab, blk_idx, axis=1)                  # (n, K, B)
    return jnp.einsum("nkb,n->kb", Ak.astype(jnp.float32),
                      r.astype(jnp.float32))


def scatter_block_update_ref(A, z, blk_idx, delta, block: int):
    """z_new = z + sum_k A[:, blk_k] @ delta[k].

    delta: (K, block).  Returns z_new with z's dtype, f32 accumulation.
    """
    d = A.shape[1]
    Ab = A.reshape(A.shape[0], d // block, block)
    Ak = jnp.take(Ab, blk_idx, axis=1)                  # (n, K, B)
    dz = jnp.einsum("nkb,kb->n", Ak.astype(jnp.float32),
                    delta.astype(jnp.float32))
    return (z.astype(jnp.float32) + dz).astype(z.dtype)


def fused_shotgun_rounds_ref(A, z, x, blk_idx, lam, beta, y, mask, loss,
                             block: int):
    """Multi-round oracle for ``shotgun_block.fused_shotgun_rounds``.

    blk_idx: (R, K) int32 — duplicates within a row follow Alg. 2's multiset
    semantics (all deltas from the pre-round iterate, then accumulated).
    ``loss`` is a registry string or ``shotgun_block.Loss`` spec; a Newton
    spec divides by the per-block curvature h_B = A_B²ᵀ w (floored 1e-8)
    computed from the round-start margin, like the kernel (DESIGN §12).
    Returns (x (d,) f32, z (n,) f32, f (R,) f32, nnz (R,) int32).
    """
    from repro.core import objectives as obj
    ls = _resolve(loss)
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    A32 = A.astype(jnp.float32)
    A2 = A32 * A32 if ls.newton else None

    def round_fn(carry, idx_t):
        x, z = carry
        r = obj.residual_like(z, y, ls.name) * mask
        g = gather_block_matvec_ref(A32, r, idx_t, block)
        if ls.newton:
            w = _curvature_weights_ref(z, y, mask, ls.name)
            h = jnp.maximum(gather_block_matvec_ref(A2, w, idx_t, block),
                            1e-8)
        else:
            h = beta
        xb = x.reshape(-1, block)
        x_sel = jnp.take(xb, idx_t, axis=0)
        x_new = obj.soft_threshold(x_sel - g / h, lam / h)
        delta = x_new - x_sel
        z = scatter_block_update_ref(A32, z, idx_t, delta, block)
        x = xb.at[idx_t].add(delta).reshape(-1)
        f = (obj.masked_data_loss(z, y, mask, ls.name)
             + lam * jnp.sum(jnp.abs(x)))
        return (x, z), (f, jnp.sum(x != 0))

    (x, z), (fs, nnzs) = jax.lax.scan(round_fn, (x, z), blk_idx)
    return x, z, fs, nnzs.astype(jnp.int32)


def fused_shotgun_delta_rounds_ref(A, z, x, blk_idx, lam, beta, y, mask,
                                   loss, block: int):
    """Oracle for ``shotgun_block.fused_shotgun_delta_rounds``: the same
    multi-round trajectory, reported as (x_new, dz) with dz = z_new − z₀
    (what the shard would contribute to the Δz all-reduce)."""
    x_new, z_new, _, _ = fused_shotgun_rounds_ref(
        A, z, x, blk_idx, lam, beta, y, mask, loss, block)
    return x_new, z_new - z.astype(jnp.float32)


def fused_sparse_shotgun_rounds_ref(rows, vals, z, x, blk_idx, lam, beta, y,
                                    loss):
    """Multi-round oracle for ``shotgun_sparse.fused_sparse_shotgun_rounds``
    — the same trajectory computed from the nnz tiles in pure jnp.

    rows/vals: (nblk, tile, block) BlockedCSC tiles; x: (nblk·block,);
    blk_idx: (R, K) int32.  ``loss`` is a registry string or
    ``shotgun_block.Loss`` spec (Newton specs divide by the per-block
    curvature Σ vals²·w[rows], floored 1e-8).  Returns
    (x (nblk·block,) f32, z (n,) f32, f (R,) f32, nnz (R,) int32).
    """
    from repro.core import objectives as obj
    ls = _resolve(loss)
    nblk, tile, block = rows.shape
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    ones = jnp.ones_like(y, jnp.float32)

    def round_fn(carry, idx_t):
        x, z = carry
        r = obj.residual_like(z, y, ls.name)
        rows_k = jnp.take(rows, idx_t, axis=0)              # (K, tile, B)
        vals_k = jnp.take(vals, idx_t, axis=0).astype(jnp.float32)
        g = jnp.sum(vals_k * jnp.take(r, rows_k), axis=1)   # (K, B)
        if ls.newton:
            w = _curvature_weights_ref(z, y, ones, ls.name)
            h = jnp.maximum(
                jnp.sum(vals_k * vals_k * jnp.take(w, rows_k), axis=1), 1e-8)
        else:
            h = beta
        xb = x.reshape(nblk, block)
        x_sel = jnp.take(xb, idx_t, axis=0)
        x_new = obj.soft_threshold(x_sel - g / h, lam / h)
        delta = x_new - x_sel
        z = z.at[rows_k.reshape(-1)].add(
            (vals_k * delta[:, None, :]).reshape(-1))
        x = xb.at[idx_t].add(delta).reshape(-1)
        f = (obj.masked_data_loss(z, y, ones, ls.name)
             + lam * jnp.sum(jnp.abs(x)))
        return (x, z), (f, jnp.sum(x != 0))

    (x, z), (fs, nnzs) = jax.lax.scan(round_fn, (x, z), blk_idx)
    return x, z, fs, nnzs.astype(jnp.int32)


def fused_sparse_shotgun_delta_rounds_ref(rows, vals, z, x, blk_idx, lam,
                                          beta, y, loss):
    """Oracle for ``shotgun_sparse.fused_sparse_shotgun_delta_rounds``: the
    same multi-round trajectory, reported as (x_new, dz) with
    dz = z_new − z₀ (the shard's Δz all-reduce contribution)."""
    x_new, z_new, _, _ = fused_sparse_shotgun_rounds_ref(
        rows, vals, z, x, blk_idx, lam, beta, y, loss)
    return x_new, z_new - z.astype(jnp.float32)

