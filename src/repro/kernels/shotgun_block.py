"""Pallas TPU kernels for Block-Shotgun (DESIGN.md §4).

The paper's per-update hot loop (read column j, dot with residual, soft
threshold, write back to the shared Ax) is memory-wall bound on multicore:
O(1) flops per byte (Sec. 4.3).  The TPU adaptation updates an *aligned
block of 128 coordinates* at a time so that

  * the random column gather becomes a contiguous VMEM DMA whose source
    block is selected by a scalar-prefetched index (`PrefetchScalarGridSpec`
    index_map) — no scalar scatter/gather,
  * the gradient gather g_B = A_B^T r and the margin update z += A_B δ are
    (TILE_N × 128) MXU matmuls — arithmetic intensity O(128) flops/byte.

The fused multi-round kernel (DESIGN §4.2):

  fused_shotgun_rounds_kernel   R rounds per launch; the margin z, the
  round-start residual r, the iterate x, and the per-round deltas all live
  in VMEM scratch across the whole launch, so streamed column blocks of A
  are the only per-round HBM traffic.  A scalar-prefetched (R, K) index
  matrix selects the blocks each round touches.  When one sample tile
  covers all of n (T == 1) the kernel runs single-phase — each A block is
  fetched ONCE per round and used for both g_B = A_Bᵀ r and z += A_B δ;
  otherwise it runs a gather phase and a scatter phase over the sample
  tiles, streaming A twice a round, with z/r/g/δ still in VMEM.

Block size B = 128 (MXU/lane width); the two-phase sample tile is TILE_N
= 512.  The fused kernel keeps every sample-indexed vector as a lane-dense
(1, n) row and works through its A tile SUB_TILE rows at a time.  Every
kernel compiles with ``vmem_limit_bytes = VMEM_BUDGET``; the VMEM model
(each (1, n) row at 4 B per sample) is in ``fused_vmem_bytes`` and DESIGN
§4.3.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128        # coordinate block width (MXU dimension)
TILE_N = 512       # sample-dimension tile
SUB_TILE = 4096    # rows of A (lanes of a row) per in-kernel chunk

# Scoped-VMEM limit every kernel here is compiled with (``vmem_limit_bytes``)
# and the ceiling ``fused_vmem_bytes`` / ``fused_sparse_vmem_bytes`` refuse
# shapes against.  A v5e core has 128 MiB of VMEM and the compiler's default
# scoped limit is 16 MiB; 8 MiB stay free for the XLA ops around the kernel.
# Rehearsed on a described v5e: at the largest n ``auto_tile_n`` admits
# single-phase (d=2048, K=8: lasso 119808, logistic Newton and the lasso Δz
# engine variant 119296) every fused variant compiles; the double-buffered
# (n, 128) A panel is nearly all of it.
VMEM_BUDGET = 120 * 2 ** 20
MOSAIC_SLACK = 256 * 2 ** 10  # the compiler's spill slots beside the buffers


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter: exactly when the
    default backend is the CPU.  Every solver, engine and service decides
    through here; only the raw ``pallas_call`` wrappers take an explicit
    ``interpret`` (None defers to this), so a TPU never interprets."""
    return jax.default_backend() == "cpu"


def _call_params(interpret: bool | None) -> dict:
    """``pallas_call`` keyword arguments shared by every kernel: the
    backend-chosen interpret flag and the ``VMEM_BUDGET`` scoped limit."""
    return dict(
        interpret=interpret_mode() if interpret is None else interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET))


def _f32_dot(a, b, contract):
    """MXU contraction over the ``contract`` dims at full f32 precision.
    Without ``HIGHEST`` the TPU may round f32 operands to bf16; with it the
    chip computes what the interpreter and the ``ref.py`` oracles do."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _check_divisible(n: int, d: int, block: int, tile_n: int) -> None:
    """Raise (don't assert — asserts vanish under ``python -O``) when the
    operand shape doesn't tile: these kernels index A by whole blocks."""
    if d % block:
        raise ValueError(f"d={d} not divisible by block={block}")
    if n % tile_n:
        raise ValueError(f"n={n} not divisible by tile_n={tile_n}")
    if tile_n % 128:
        raise ValueError(f"tile_n={tile_n} is not a multiple of 128: a tile "
                         f"is a lane slice of the (1, n) sample rows")


# ---------------------------------------------------------------------------
# The fused multi-round Block-Shotgun kernel — R rounds per launch, z in VMEM
# ---------------------------------------------------------------------------

LASSO = "lasso"      # kept in sync with repro.core.objectives (string keys
LOGISTIC = "logistic"  # only; kernels stay import-independent of core)


def _soft_threshold(v, t):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


def _stable_logistic_tile(z, y):
    """The blessed stable-logistic tile (DESIGN §12, shotgun-lint SL004):
    the ONE place raw ``jnp.exp``/``jnp.log*`` may appear in kernel bodies.

    Works on the VMEM-resident margin tile in f32: with m = −y·z,

      sig = σ(m) = σ(−y·z)        |residual| factor (r = −y·sig)
      ll  = log(1 + exp(m))       per-sample loss, the max+log1p form of
                                  logaddexp(0, m) — exp only sees
                                  non-positive arguments
      w   = σ(z)(1 − σ(z))        diagonal-Hessian weight; equals
                                  sig·(1 − sig) because y ∈ {−1, +1} makes
                                  {σ(yz), σ(−yz)} = {σ(z), σ(−z)}

    Everything stays f32 through the exp/log1p — the tile is consumed by
    f32 accumulators (dot_general with preferred_element_type=f32)."""
    m = -y * z
    sig = jax.nn.sigmoid(m)
    ll = jnp.maximum(m, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(m)))
    w = sig * (1.0 - sig)
    return sig, ll, w


class Loss(NamedTuple):
    """Static loss spec for the fused kernels (the loss seam, DESIGN §12).

    A ``Loss`` is everything the fused round body needs to know about the
    data term, as a hashable NamedTuple that rides ``jax.jit`` /
    ``pallas_call`` as static configuration:

      ``residual(z, y, m)``            dL/dz on the VMEM margin tile
      ``curvature_weights(z, y, m)``   per-sample diagonal-Hessian weights
                                       w_i with h_j = Σ_i a_ij² w_i — what
                                       the per-block Newton option
                                       accumulates from the already-fetched
                                       A tile (Bian et al. 2013)
      ``data_loss(z, y, m)``           the masked data term for the
                                       in-kernel objective trace
      ``beta``                         the Assumption-2.1 curvature bound
                                       (1 squared, 1/4 logistic per Eq. 6)
                                       used when ``newton`` is off
      ``newton``                       True → the delta divides by the
                                       accumulated per-block curvature
                                       (floored at 1e-8) instead of beta

    Kernel entry points accept either a registry string (``"lasso"`` /
    ``"logistic"`` / ``"logistic_newton"``) or a ``Loss`` instance — see
    ``resolve_loss``.  Kept import-independent of ``repro.core``."""

    name: str
    beta: float
    newton: bool = False

    def residual(self, z, y, m):
        """dL/dz masked to real samples; matches objectives.residual_like."""
        if self.name == LASSO:
            return (z - y) * m
        sig, _, _ = _stable_logistic_tile(z, y)
        return (-y * sig) * m

    def curvature_weights(self, z, y, m):
        """Per-sample w_i such that h_j = Σ_i a_ij² w_i is the diagonal
        second derivative of the data term (exact for both losses: L'' = 1
        squared, σ(z)(1−σ(z)) logistic)."""
        if self.name == LASSO:
            return m
        _, _, w = _stable_logistic_tile(z, y)
        return w * m

    def data_loss(self, z, y, m):
        """Masked data term; matches objectives.masked_data_loss."""
        if self.name == LASSO:
            e = z - y
            return 0.5 * jnp.sum(e * (e * m))
        _, ll, _ = _stable_logistic_tile(z, y)
        return jnp.sum(m * ll)

    def objective(self, z, y, m, x, lam):
        """F(x) from the VMEM-resident margin/iterate; matches
        ``objectives.masked_data_loss`` + λ‖x‖₁."""
        return self.data_loss(z, y, m) + lam * jnp.sum(jnp.abs(x))


SQUARED_LOSS = Loss(LASSO, beta=1.0)
LOGISTIC_LOSS = Loss(LOGISTIC, beta=0.25)                  # Eq. 6
LOGISTIC_NEWTON = Loss(LOGISTIC, beta=0.25, newton=True)   # Bian et al.

LOSSES = {"lasso": SQUARED_LOSS, "logistic": LOGISTIC_LOSS,
          "logistic_newton": LOGISTIC_NEWTON}


def resolve_loss(loss) -> Loss:
    """Map a registry string (or a ``Loss``, returned unchanged) to the
    static ``Loss`` spec the kernel factories consume."""
    if isinstance(loss, Loss):
        return loss
    try:
        return LOSSES[loss]
    except KeyError:
        raise ValueError(
            f"unknown loss {loss!r}; choose from {sorted(LOSSES)} or pass a "
            f"Loss instance") from None


def _make_fused_kernel(loss: Loss, R: int, K: int, T: int, block: int,
                       tile_n: int, emit_dz: bool = False):
    """Kernel body factory.  grid = (R, K) when T == 1 (single-phase: each A
    block fetched once per round), else (R, K, 2, T) (gather phase p=0,
    scatter phase p=1; A streamed twice per round, but z/r/g/δ never leave
    VMEM).

    ``emit_dz`` selects the shard-local engine variant (DESIGN §3/§4.2): z0
    is a read-only *global* margin snapshot; the kernel still keeps its own
    live local view z_s = z0 + Σ own contributions in VMEM, but additionally
    accumulates those contributions into its Δz output and outputs (Δz, x)
    instead of (z, x, f, nnz) — the caller merges Δz across shards (psum)
    and owns the trace bookkeeping.

    Divergence sentinel (DESIGN §9): the scalar-prefetch vector carries
    ``k_eff`` (blocks past it get their delta masked to zero — the in-kernel
    half of adaptive-P backoff; at k_eff == K the mask multiplies by exactly
    1.0) and a guard objective level; the kernel max-accumulates a (1, 1)
    SMEM health output that goes 1.0 the first round the objective crosses the
    guard or goes non-finite (engine variant: the margin view goes
    non-finite), so the caller detects an in-launch divergence from one
    scalar instead of scanning the trace.

    Per-block Newton (``loss.newton``, DESIGN §12): the round start also
    snapshots the per-sample curvature weights w = L''(z) into a (1, n)
    scratch, and the gather phase accumulates the per-block diagonal
    curvature h_B = A_B²ᵀ w from the SAME already-fetched A tile (one extra
    dot_general, zero extra HBM traffic); the delta then divides by
    max(h, 1e-8) instead of the global beta bound."""
    single = T == 1
    newton = loss.newton

    def chunks(length, body, carry):
        """``body(off, size, carry)`` over [0, length) in ``SUB_TILE``-row
        chunks, the last one shorter where ``SUB_TILE`` does not divide
        ``length`` (every size a multiple of 128), so no value outgrows a
        chunk whatever n is."""
        full, tail = divmod(length, SUB_TILE)
        if full:
            carry = jax.lax.fori_loop(
                0, full, lambda i, c: body(
                    pl.multiple_of(i * SUB_TILE, SUB_TILE), SUB_TILE, c),
                carry)
        return body(full * SUB_TILE, tail, carry) if tail else carry

    def over_row(body, init):
        """``body(lanes, carry)`` over a whole (1, n) row, chunk by chunk."""
        return chunks(T * tile_n,
                      lambda off, size, c: body(pl.ds(off, size), c), init)

    def kernel(idx_ref, scal_ref, a_ref, z0_ref, x0_ref, y_ref, m_ref,
               *refs):
        if newton:
            refs, (w_s, c_s) = refs[:-2], refs[-2:]
        if emit_dz:
            # Δz accumulates in its own output block; z_ref is the live view.
            (dz_ref, xo_ref, h_ref, z_ref, r_s, x_s, g_s, d_s) = refs
        else:
            # The z output block is the live margin itself.
            (z_ref, xo_ref, f_ref, nnz_ref, h_ref, r_s, x_s, g_s, d_s) = refs
        r_id = pl.program_id(0)
        k_id = pl.program_id(1)
        if single:
            # One step = both phases for (round, block); predicates constant.
            t_id = jnp.int32(0)
            gather_on = scatter_on = jnp.bool_(True)
            first_step = (r_id == 0) & (k_id == 0)
        else:
            p_id = pl.program_id(2)
            t_id = pl.program_id(3)
            gather_on = p_id == 0
            scatter_on = p_id == 1
            first_step = (r_id == 0) & (k_id == 0) & gather_on & (t_id == 0)
        lam = scal_ref[0]
        beta = scal_ref[1]
        k_eff = scal_ref[2].astype(jnp.int32)
        guard = scal_ref[3]

        @pl.when(first_step)
        def _init_launch():
            def init(lanes, carry):
                z_ref[:, lanes] = z0_ref[:, lanes]
                if emit_dz:
                    dz_ref[:, lanes] = jnp.zeros_like(z0_ref[:, lanes])
                return carry

            over_row(init, 0)
            x_s[...] = x0_ref[...]
            h_ref[0, 0] = jnp.float32(0.0)

        @pl.when((k_id == 0) & gather_on & (t_id == 0))
        def _round_start():
            def start(lanes, carry):
                z, y, m = z_ref[:, lanes], y_ref[:, lanes], m_ref[:, lanes]
                r_s[:, lanes] = loss.residual(z, y, m)
                if newton:
                    # Curvature weights from the SAME round-start margin the
                    # residual uses — all K blocks see pre-round curvature,
                    # preserving Alg. 2's multiset semantics.
                    w_s[:, lanes] = loss.curvature_weights(z, y, m)
                return carry

            over_row(start, 0)

        # The step's A tile is consumed chunk by chunk, each paired with
        # the matching lane slice of the (1, n) rows.
        base = 0 if single else t_id * tile_n

        def tile_chunks(body, carry):
            def step(off, size, c):
                a = a_ref[pl.ds(off, size), :].astype(jnp.float32)
                lanes = pl.ds(pl.multiple_of(base + off, 128), size)
                return body(a, lanes, c)
            return chunks(tile_n, step, carry)

        @pl.when(gather_on)
        def _gather_phase():
            row = pl.ds(k_id, 1)

            @pl.when(t_id == 0)
            def _zero_g():
                g_s[row, :] = jnp.zeros((1, block), jnp.float32)
                if newton:
                    c_s[row, :] = jnp.zeros((1, block), jnp.float32)

            def gather(a, lanes, carry):
                g, c = carry
                g += _f32_dot(r_s[:, lanes], a, ((1,), (0,)))   # (1, block)
                if newton:
                    # h_B += w (a∘a) from the chunk already in VMEM: the
                    # Newton curvature costs one more dot, no more A bytes.
                    c += _f32_dot(w_s[:, lanes], a * a, ((1,), (0,)))
                return g, c

            g, c = tile_chunks(
                gather,
                (g_s[row, :], c_s[row, :] if newton else jnp.float32(0.0)))
            g_s[row, :] = g
            if newton:
                c_s[row, :] = c

            @pl.when(t_id == T - 1)
            def _delta():
                # All K deltas are taken from the *pre-round* x (scratch is
                # only updated at round end), so duplicate block draws within
                # a round reproduce Alg. 2's multiset semantics exactly.
                b = idx_ref[r_id, k_id]
                x_sel = x_s[pl.ds(b, 1), :]
                if newton:
                    # Per-block Newton: divide by the accumulated diagonal
                    # curvature, floored (zero/padded columns fall back to a
                    # tiny h whose threshold λ/h kills the step anyway).
                    h = jnp.maximum(c_s[row, :], 1e-8)
                else:
                    h = beta
                x_new = _soft_threshold(x_sel - g_s[row, :] / h, lam / h)
                # Backoff mask: blocks at or past k_eff contribute nothing
                # this round (multiply by exactly 1.0 when k_eff == K).
                live = jnp.where(k_id < k_eff, 1.0, 0.0).astype(jnp.float32)
                d_s[row, :] = (x_new - x_sel) * live

        @pl.when(scatter_on)
        def _scatter_phase():
            dlt = d_s[pl.ds(k_id, 1), :]                 # (1, block)

            def scatter(a, lanes, carry):
                contrib = _f32_dot(dlt, a, ((1,), (1,)))  # (1, size)
                z_ref[:, lanes] += contrib
                if emit_dz:
                    dz_ref[:, lanes] += contrib
                return carry

            tile_chunks(scatter, 0)

            @pl.when((k_id == K - 1) & (t_id == T - 1))
            def _round_end():
                def apply_delta(kk, carry):
                    b = idx_ref[r_id, kk]
                    x_s[pl.ds(b, 1), :] += d_s[pl.ds(kk, 1), :]
                    return carry

                jax.lax.fori_loop(0, K, apply_delta, 0)
                # Constant-index outputs flush to HBM once, after the last
                # grid step; rewriting them every round is free in VMEM.
                xo_ref[...] = x_s[...]
                if emit_dz:
                    # Engine variant has no in-kernel objective; the health
                    # scalar trips on a non-finite margin view instead.
                    def nonfinite(lanes, bad):
                        return jnp.maximum(bad, jnp.max(jnp.where(
                            jnp.isfinite(z_ref[:, lanes]), 0.0, 1.0)))

                    h_ref[0, 0] = jnp.maximum(
                        h_ref[0, 0], over_row(nonfinite, jnp.float32(0.0)))
                else:
                    def data(lanes, acc):
                        return acc + loss.data_loss(
                            z_ref[:, lanes], y_ref[:, lanes], m_ref[:, lanes])

                    f = (over_row(data, jnp.float32(0.0))
                         + lam * jnp.sum(jnp.abs(x_s[...])))
                    f_ref[r_id, 0] = f
                    bad = ~jnp.isfinite(f) | (f > guard)
                    h_ref[0, 0] = jnp.maximum(
                        h_ref[0, 0], jnp.where(bad, 1.0, 0.0))
                    nnz_ref[r_id, 0] = jnp.sum(
                        (x_s[...] != 0).astype(jnp.int32))

    return kernel


def _fused_call(A, z, x, blk_idx, lam, beta, y, mask, loss, block, tile_n,
                interpret, emit_dz, k_eff=None, guard_f=None):
    """Shared pallas_call plumbing for both fused-kernel variants.

    ``k_eff`` (dynamic scalar, defaults to K) and ``guard_f`` (objective
    guard level, defaults to +inf = never trips) ride in the scalar-prefetch
    vector so a backoff changes no shapes and triggers no recompilation."""
    loss = resolve_loss(loss)
    n, d = A.shape
    R, K = blk_idx.shape
    a_bytes = A.dtype.itemsize
    if tile_n is None:
        tile_n = auto_tile_n(n, block, d=d, K=K, loss=loss, emit_dz=emit_dz,
                             a_bytes=a_bytes)
    _check_divisible(n, d, block, tile_n)
    check_vmem(fused_vmem_bytes(n, d, K, block, tile_n, emit_dz, a_bytes,
                                loss=loss),
               f"fused kernel (n={n}, d={d}, K={K}, tile_n={tile_n})")
    nblk = d // block
    T = n // tile_n
    single = T == 1

    idx = blk_idx.astype(jnp.int32)
    k_eff = jnp.asarray(K if k_eff is None else k_eff, jnp.float32)
    guard_f = jnp.asarray(jnp.inf if guard_f is None else guard_f,
                          jnp.float32)
    scal = jnp.stack([jnp.asarray(lam, jnp.float32),
                      jnp.asarray(beta, jnp.float32), k_eff, guard_f])
    # Every sample-indexed vector is a lane-dense (1, n) row.
    z0 = z.reshape(1, n).astype(jnp.float32)
    x0 = x.reshape(nblk, block).astype(jnp.float32)
    y2 = y.reshape(1, n).astype(jnp.float32)
    m2 = mask.reshape(1, n).astype(jnp.float32)

    if single:
        grid = (R, K)
        a_map = lambda r, k, idx, scal: (0, idx[r, k])
        const = lambda r, k, idx, scal: (0, 0)
    else:
        grid = (R, K, 2, T)
        a_map = lambda r, k, p, t, idx, scal: (t, idx[r, k])
        const = lambda r, k, p, t, idx, scal: (0, 0)

    # The per-round traces and the health flag are scalar stores, which
    # Mosaic only takes in SMEM: whole-array SMEM outputs, written at
    # [r_id, 0] / [0, 0] and flushed once at the end of the launch.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if emit_dz:
        out_specs = [
            pl.BlockSpec((1, n), const),            # Δz
            pl.BlockSpec((nblk, block), const),     # x
            smem,                                   # health scalar
        ]
        out_shape = [
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((nblk, block), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ]
        extra_scratch = [pltpu.VMEM((1, n), jnp.float32)]   # z (live view)
    else:
        out_specs = [
            pl.BlockSpec((1, n), const),            # z
            pl.BlockSpec((nblk, block), const),     # x
            smem,                                   # f trace
            smem,                                   # nnz trace
            smem,                                   # health scalar
        ]
        out_shape = [
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((nblk, block), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ]
        extra_scratch = []

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, block), a_map),   # streamed A block
            pl.BlockSpec((1, n), const),            # z0   (VMEM-resident)
            pl.BlockSpec((nblk, block), const),     # x0   (VMEM-resident)
            pl.BlockSpec((1, n), const),            # y    (VMEM-resident)
            pl.BlockSpec((1, n), const),            # mask (VMEM-resident)
        ],
        out_specs=out_specs,
        scratch_shapes=extra_scratch + [
            pltpu.VMEM((1, n), jnp.float32),        # r  (round-start residual)
            pltpu.VMEM((nblk, block), jnp.float32),  # x
            pltpu.VMEM((K, block), jnp.float32),    # g  accumulators
            pltpu.VMEM((K, block), jnp.float32),    # delta
        ] + ([
            pltpu.VMEM((1, n), jnp.float32),        # w  curvature weights
            pltpu.VMEM((K, block), jnp.float32),    # h  curvature accumulators
        ] if loss.newton else []),
    )
    return pl.pallas_call(
        _make_fused_kernel(loss, R, K, T, block, tile_n, emit_dz=emit_dz),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=("fused_shotgun_delta_rounds" if emit_dz
              else "fused_shotgun_rounds"),
        **_call_params(interpret),
    )(idx, scal, A, z0, x0, y2, m2)


@functools.partial(jax.jit,
                   static_argnames=("loss", "block", "tile_n", "interpret"))
def fused_shotgun_rounds(A, z, x, blk_idx, lam, beta, y, mask,
                         loss: str | Loss = LASSO, block: int = BLOCK,
                         tile_n: int | None = None,
                         interpret: bool | None = None,
                         k_eff=None, guard_f=None):
    """R Block-Shotgun rounds in ONE pallas_call.

    A        (n, d) design, f32 or bf16 (bf16 halves streamed bytes; all
             accumulation is f32 regardless).
    z        (n,) margin A x;  x (d,) iterate;  y (n,);  mask (n,) sample
             mask from ``ops.pad_problem``.
    blk_idx  (R, K) int32 — round t updates aligned coordinate blocks
             blk_idx[t, 0..K-1] (duplicates allowed, multiset semantics).
    loss     registry string (``"lasso"`` / ``"logistic"`` /
             ``"logistic_newton"``) or a ``Loss`` spec — the static loss
             seam (DESIGN §12); ``beta`` is ignored by Newton specs.
    k_eff    dynamic effective block count (DESIGN §9): blocks k >= k_eff
             are drawn but masked out — the adaptive-P backoff knob.  None
             (default) means all K live, bit-exactly.
    guard_f  objective guard level: the health output trips when a round's
             F exceeds it (or goes non-finite).  None = +inf = finite-only.

    Returns (x_new (d,) f32, z_new (n,) f32, f (R,) f32, nnz (R,) int32,
    health () f32) with per-round objective/nnz traces computed in-kernel;
    ``health`` is 1.0 iff any round tripped the in-kernel sentinel.
    """
    n, d = A.shape
    R = blk_idx.shape[0]
    z_new, x_new, f, nnz, h = _fused_call(A, z, x, blk_idx, lam, beta, y,
                                          mask, loss, block, tile_n,
                                          interpret, emit_dz=False,
                                          k_eff=k_eff, guard_f=guard_f)
    return (x_new.reshape(d), z_new.reshape(n), f.reshape(R), nnz.reshape(R),
            h.reshape(()))


@functools.partial(jax.jit,
                   static_argnames=("loss", "block", "tile_n", "interpret"))
def fused_shotgun_delta_rounds(A, z, x, blk_idx, lam, beta, y, mask,
                               loss: str | Loss = LASSO, block: int = BLOCK,
                               tile_n: int | None = None,
                               interpret: bool | None = None, k_eff=None):
    """Shard-local fused engine kernel: R rounds against a margin *snapshot*.

    Same dataflow as ``fused_shotgun_rounds`` — z/r/x/g/δ resident in VMEM,
    streamed A blocks as the only per-round HBM traffic — but the kernel does
    not own the global margin: ``z`` is the last merged global snapshot, the
    kernel's live VMEM view tracks only its OWN updates on top of it, and the
    contributions are additionally accumulated into a Δz = A_shard δx output
    for the caller to all-reduce (DESIGN §3).  Within the launch the shard
    sees its own rounds immediately; other shards' rounds arrive only at the
    next merge — the staleness the ``merge="launch"`` mode trades off.

    ``k_eff`` masks blocks past the backoff point (see
    ``fused_shotgun_rounds``); there is no in-kernel objective here, so the
    health output trips only on a non-finite margin view.

    Returns (x_new (d,) f32, dz (n,) f32, health () f32).
    """
    n, d = A.shape
    dz, x_new, h = _fused_call(A, z, x, blk_idx, lam, beta, y, mask,
                               loss, block, tile_n, interpret, emit_dz=True,
                               k_eff=k_eff)
    return x_new.reshape(d), dz.reshape(n), h.reshape(())


def vec_vmem_bytes(rows: int, cols: int = 1) -> int:
    """VMEM bytes of a (rows, cols) f32 buffer as Mosaic lays it out: in
    (8, 128) tiles, so an (n, 1) column costs 512 B per sample, and a
    single row in (1, 128) tiles, so a (1, n) row costs 4."""
    sub = 1 if rows == 1 else 8
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * 4


def check_vmem(need: int, what: str) -> None:
    """Refuse a kernel shape whose modelled VMEM exceeds ``VMEM_BUDGET``
    before the compiler does, naming the limit."""
    if need > VMEM_BUDGET:
        raise ValueError(
            f"{what} needs {need} B of VMEM > VMEM_BUDGET = {VMEM_BUDGET} B "
            f"(kernels/shotgun_block.py): shrink n, d, K or the tile, or "
            f"shard the design")


def fused_vmem_bytes(n: int, d: int, K: int, block: int = BLOCK,
                     tile_n: int | None = None, emit_dz: bool = False,
                     a_bytes: int = 4, slots: int = 1,
                     loss: str | Loss = "lasso") -> int:
    """VMEM the dense fused kernel needs — the twin of
    ``shotgun_sparse.fused_sparse_vmem_bytes`` for ``_fused_call``'s
    buffers, each priced at its tiled layout (``vec_vmem_bytes``).

    Every sample-indexed vector is a (1, n) row at 4 B per sample: the
    z0/y/mask in-rows, the z (or Δz) out-row, which is also the live
    margin (or the Δz accumulator), the r scratch (+ the live-view z
    scratch for the ``emit_dz`` engine variant, + the curvature-weight
    scratch for a Newton spec).  The loss math runs one ``SUB_TILE`` chunk
    at a time, so it adds no n-sized temporary.  Then the three
    full-d x buffers (x0/scratch/out), the (K, block) g/δ (+ Newton h)
    scratches, the double-buffered streamed (tile_n, block) A tile, and
    ``MOSAIC_SLACK`` for the compiler's own spill slots.  ``a_bytes`` is
    the stored dtype of A (4 = f32, 2 = bf16).  R never enters: the (R, K)
    index matrix and the (R, 1) traces live in SMEM.  Calibrated against
    the v5e compiler's own VMEM reports.

    ``slots`` is the batched-launch multiplier (DESIGN §11): the vmapped
    entry points (``kernels/batched.py``) stack S independent problems on
    a leading axis, so the stacked-slot resident set is modeled as
    slots × the per-problem set — conservative on hardware, where the
    batch axis is the outermost (sequential) grid dimension, and exact in
    interpret mode, where vmap physically batches every buffer."""
    spec = resolve_loss(loss)
    if tile_n is None:
        tile_n = auto_tile_n(n, block, d=d, K=K, loss=spec, emit_dz=emit_dz,
                             a_bytes=a_bytes)
    # z0/y/mask in, z-or-Δz out, r scratch (+ z view scratch, + Newton w)
    vecs = (5 + emit_dz + spec.newton) * vec_vmem_bytes(1, n)
    xbuf = 3 * vec_vmem_bytes(d // block, block)   # x0, x scratch, x out
    kbuf = (3 if spec.newton else 2) * vec_vmem_bytes(K, block)
    tiles = 2 * tile_n * block * a_bytes           # double-buffered A tile
    return slots * (vecs + xbuf + kbuf + tiles + MOSAIC_SLACK)


def auto_tile_n(n: int, block: int = BLOCK, d: int = 0, K: int = 1,
                loss: str | Loss = "lasso", emit_dz: bool = False,
                a_bytes: int = 4) -> int:
    """Largest sample tile whose ``fused_vmem_bytes`` fits ``VMEM_BUDGET``.
    Prefers tile_n == n (single-phase fused kernel, one A fetch per block
    per round) whenever it fits, else ``TILE_N`` (two-phase).  A tile is a
    lane slice of the (1, n) rows, so n must be a multiple of 128.  Raises
    ``ValueError`` naming the limit when even ``TILE_N`` does not fit: the
    resident (1, n) rows and x buffers alone exceed it.  See DESIGN §4.3."""
    if n % 128:
        raise ValueError(f"n={n} is not a multiple of 128: a tile is a lane "
                         f"slice of the (1, n) sample rows")

    def need(tile):
        return fused_vmem_bytes(n, d, K, block, tile, emit_dz, a_bytes,
                                loss=loss)

    if need(n) <= VMEM_BUDGET:
        return n
    tile = TILE_N
    while n % tile:            # n is pre-padded to a TILE_N multiple by
        tile //= 2             # ops.pad_problem; 128 always divides it
    check_vmem(need(tile), f"fused kernel (n={n}, d={d}, K={K})")
    return tile
