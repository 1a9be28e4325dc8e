"""L1-regularized objectives from the paper (Eq. 1-4).

Two problem families:
  * Lasso (Eq. 2):             F(x) = 1/2 ||Ax - y||^2 + lam ||x||_1
  * Sparse logistic (Eq. 3):   F(x) = sum_i log(1 + exp(-y_i a_i^T x)) + lam ||x||_1

Conventions
-----------
- ``A`` is (n, d): either a dense ``jax.Array`` or a
  ``repro.data.sparse.BlockedCSC`` container (the sparse categories of
  Sec. 4.1.3 — ``sparse_imaging`` / ``large_sparse`` — emit the latter
  natively).  Everything downstream goes through the ``matvec`` /
  ``rmatvec`` / ``gather_cols`` seam below, which dispatches on the
  representation (DESIGN §8).
- Columns of A are assumed normalized so diag(A^T A) = 1 (the paper's
  w.l.o.g.); ``normalize_columns`` enforces it and returns the original
  column scales (carried on ``Problem.scales`` by ``make_problem`` so
  ``unscale_x`` can map solutions back to the raw feature space).
- beta is the per-coordinate curvature bound of Assumption 2.1:
  beta = 1 (squared loss), beta = 1/4 (logistic loss)  [Eq. 6].

The duplicated-feature positive-orthant form (Eq. 4) is used by the
theory-faithful solver in ``shotgun.py``; practical solvers use the signed
form with the soft-threshold update (equivalent fixed points).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import BlockedCSC, SparseCols

LASSO = "lasso"
LOGISTIC = "logistic"

BETA = {LASSO: 1.0, LOGISTIC: 0.25}


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("A", "y", "lam", "scales"),
                   meta_fields=("loss",))
@dataclasses.dataclass(frozen=True)
class Problem:
    """An instance of Eq. (1).  ``loss`` is static metadata under jit."""

    A: jax.Array          # (n, d) design, dense or BlockedCSC, col-normalized
    y: jax.Array          # (n,) observations (reals for lasso, +-1 for logistic)
    lam: jax.Array        # scalar regularization
    loss: str             # LASSO | LOGISTIC
    scales: jax.Array | None = None   # (d,) original column norms, or None

    def _replace(self, **kw) -> "Problem":
        return dataclasses.replace(self, **kw)

    @property
    def n(self) -> int:
        return self.A.shape[0]

    @property
    def d(self) -> int:
        return self.A.shape[1]

    @property
    def beta(self) -> float:
        return BETA[self.loss]


def normalize_columns(A, eps: float = 1e-12):
    """Scale columns of A (dense or BlockedCSC) to unit l2 norm; returns
    (A_normalized, scales)."""
    if isinstance(A, BlockedCSC):
        scales = A.col_norms()
        scales = jnp.where(scales < eps, 1.0, scales)
        return A.scale_cols(scales), scales
    scales = jnp.sqrt(jnp.sum(A * A, axis=0))
    scales = jnp.where(scales < eps, 1.0, scales)
    return A / scales[None, :], scales


def make_problem(A, y, lam, loss=LASSO, normalize=True) -> Problem:
    if not isinstance(A, BlockedCSC):
        A = jnp.asarray(A, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if loss == LOGISTIC and not isinstance(y, jax.core.Tracer):
        # Eq. 3 needs y ∈ {−1, +1}: the stable log1p margin form silently
        # computes nonsense for anything else, so fail at construction
        # (concrete labels only — a traced y is validated by its producer).
        labels = np.asarray(y)
        bad = labels[(labels != 1.0) & (labels != -1.0)]
        if bad.size:
            raise ValueError(
                f"logistic labels must be in {{-1.0, +1.0}}; got "
                f"{np.unique(bad)[:8].tolist()} "
                f"({bad.size}/{labels.size} offending values)")
    scales = None
    if normalize:
        A, scales = normalize_columns(A)
    return Problem(A=A, y=y, lam=jnp.float32(lam), loss=loss, scales=scales)


def unscale_x(x: jax.Array, scales: jax.Array | None) -> jax.Array:
    """Map a solution of the column-normalized problem back to the raw
    feature space: A_raw (x / scales) == A_norm x.  Accepts the ``scales``
    from ``normalize_columns`` / ``Problem.scales`` (None = identity)."""
    return x if scales is None else x / scales


# ---------------------------------------------------------------------------
# Representation seam (DESIGN §8): every consumer of A goes through these
# four ops so dense arrays and BlockedCSC containers run the same code.
# ---------------------------------------------------------------------------

def require_dense(A, what: str):
    """Clear trace-time error for solver families with no sparse path (the
    CDN inner-Newton variants and the duplicated-feature form index raw
    columns); returns A unchanged when dense."""
    if isinstance(A, BlockedCSC):
        raise TypeError(
            f"{what} supports dense designs only, got BlockedCSC — use the "
            "shotgun / block solver families for sparse A (DESIGN §8)")
    return A


def matvec(A, x) -> jax.Array:
    """A @ x for dense or BlockedCSC A, at full f32 precision: on a TPU the
    default would round f32 operands to bf16, and a margin built here (warm
    starts, objectives) must agree with the kernels' f32 accumulation."""
    if isinstance(A, BlockedCSC):
        return A.matvec(x)
    return jnp.matmul(A, x, precision=jax.lax.Precision.HIGHEST)


def rmatvec(A, r) -> jax.Array:
    """A^T r for dense or BlockedCSC A, at full f32 precision (see
    ``matvec``)."""
    if isinstance(A, BlockedCSC):
        return A.rmatvec(r)
    return jnp.matmul(A.T, r, precision=jax.lax.Precision.HIGHEST)


def gather_cols(A, idx):
    """Pack of the P columns ``idx``: dense (n, P) array, or the nnz tiles
    (``SparseCols``) for BlockedCSC — O(n·P) vs O(tile·P) bytes."""
    if isinstance(A, BlockedCSC):
        return A.gather_cols(idx)
    return A[:, idx]


def cols_rmatvec(cols, r) -> jax.Array:
    """(P,) coordinate gradients A_P^T r from a ``gather_cols`` pack."""
    if isinstance(cols, SparseCols):
        rv = jnp.take(jnp.asarray(r, jnp.float32), cols.rows)   # (P, tile)
        return jnp.sum(cols.vals * rv, axis=1)
    return cols.T @ r


def cols_matvec_add(cols, delta, z) -> jax.Array:
    """z + A_P @ delta (the maintained-margin update) from a column pack."""
    if isinstance(cols, SparseCols):
        return z.at[cols.rows.reshape(-1)].add(
            (cols.vals * delta[:, None]).reshape(-1))
    return z + cols @ delta


# ---------------------------------------------------------------------------
# Objective values / gradients.  All solvers maintain the "margin" vector
# z = A x  (the paper's maintained Ax trick, Sec 4.1.1) so none of these
# recompute A x from scratch inside the inner loop.
# ---------------------------------------------------------------------------

def data_loss_from_margin(z: jax.Array, y: jax.Array, loss: str) -> jax.Array:
    if loss == LASSO:
        r = z - y
        return 0.5 * jnp.vdot(r, r)
    # logistic: sum log(1 + exp(-y z)), numerically stable
    m = -y * z
    return jnp.sum(jnp.logaddexp(0.0, m))


def masked_data_loss(z: jax.Array, y: jax.Array, mask: jax.Array,
                     loss: str) -> jax.Array:
    """Data loss restricted to real samples (``mask`` zeros out the rows
    ``kernels.ops.pad_problem`` added).  The Pallas kernels keep their own
    import-independent copy of this formula
    (``shotgun_block.Loss.objective``) — keep the two in sync."""
    if loss == LASSO:
        e = z - y
        return 0.5 * jnp.sum(e * (e * mask))
    return jnp.sum(mask * jnp.logaddexp(0.0, -y * z))


def objective_from_margin(z, x, prob: Problem) -> jax.Array:
    return data_loss_from_margin(z, prob.y, prob.loss) + prob.lam * jnp.sum(jnp.abs(x))


def objective(x: jax.Array, prob: Problem) -> jax.Array:
    return objective_from_margin(matvec(prob.A, x), x, prob)


def residual_like(z: jax.Array, y: jax.Array, loss: str) -> jax.Array:
    """dL/dz — the vector 'r' such that grad of data loss = A^T r.

    Lasso: r = z - y.  Logistic: r = -y * sigmoid(-y z).
    """
    if loss == LASSO:
        return z - y
    return -y * jax.nn.sigmoid(-y * z)


def coordinate_grad(A: jax.Array, r: jax.Array, j) -> jax.Array:
    """(∇ of data loss)_j = A[:, j]^T r."""
    return A[:, j] @ r


def soft_threshold(v: jax.Array, t) -> jax.Array:
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


def shooting_delta(x_j, g_j, lam, beta):
    """Signed-form coordinate update (equivalent to Eq. 5 on the duplicated
    problem): minimize the Assumption-2.1 quadratic model plus lam|x_j + d|.

        x_j_new = S(x_j - g_j / beta, lam / beta),   delta = x_j_new - x_j
    """
    x_new = soft_threshold(x_j - g_j / beta, lam / beta)
    return x_new - x_j


def lambda_max(A, y: jax.Array, loss: str) -> jax.Array:
    """Smallest lam for which x = 0 is optimal: ||A^T dL/dz(0)||_inf."""
    z0 = jnp.zeros(A.shape[0], A.dtype)
    r0 = residual_like(z0, y, loss)
    return jnp.max(jnp.abs(rmatvec(A, r0)))


# ---------------------------------------------------------------------------
# Duplicated-feature positive-orthant form (Eq. 4), used by the
# theory-faithful Alg. 2 implementation and the theory tests.
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("A", "y", "lam"), meta_fields=("loss",))
@dataclasses.dataclass(frozen=True)
class DupProblem:
    A: jax.Array   # original (n, d); A_hat = [A, -A] is never materialized
    y: jax.Array
    lam: jax.Array
    loss: str

    @property
    def d2(self) -> int:
        return 2 * self.A.shape[1]

    @property
    def beta(self) -> float:
        return BETA[self.loss]


def dup_from(prob: Problem) -> DupProblem:
    require_dense(prob.A, "the duplicated-feature form (Eq. 4)")
    return DupProblem(prob.A, prob.y, prob.lam, prob.loss)


def dup_column(dp: DupProblem, j):
    """Column j of A_hat = [A, -A] without materializing it."""
    d = dp.A.shape[1]
    sign = jnp.where(j < d, 1.0, -1.0)
    return sign * dp.A[:, j % d], sign


def dup_objective(xhat: jax.Array, dp: DupProblem) -> jax.Array:
    d = dp.A.shape[1]
    x = xhat[:d] - xhat[d:]
    z = dp.A @ x
    return data_loss_from_margin(z, dp.y, dp.loss) + dp.lam * jnp.sum(xhat)


def dup_to_signed(xhat: jax.Array) -> jax.Array:
    d = xhat.shape[0] // 2
    return xhat[:d] - xhat[d:]
