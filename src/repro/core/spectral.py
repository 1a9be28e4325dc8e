"""Spectral-radius estimation and the P* plug-in (Sec. 3.1).

rho = spectral radius of A^T A (its largest eigenvalue; A^T A is PSD).
P*  = ceil(d / rho)  — the paper's predicted maximal useful parallelism
      (without duplicated features, Thm 3.2 remark).

Power iteration runs through A (cost O(nd) per step, O(nnz) for BlockedCSC
designs — it only touches A through the ``objectives.matvec``/``rmatvec``
seam) and never forms A^T A (d x d).  The paper notes power iteration gives
good-enough estimates "within a small fraction of the total runtime".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import objectives as obj


@functools.partial(jax.jit, static_argnames=("iters",))
def spectral_radius(A, key: jax.Array | None = None, iters: int = 100) -> jax.Array:
    """Largest eigenvalue of A^T A via power iteration with Rayleigh quotient.

    Its device ops run under the scope ``shotgun.p_star``."""
    d = A.shape[1]
    if key is None:
        key = jax.random.PRNGKey(0)
    with jax.named_scope("shotgun.p_star"):
        v0 = jax.random.normal(key, (d,), A.dtype)
        v0 = v0 / jnp.linalg.norm(v0)

        def step(v, _):
            w = obj.rmatvec(A, obj.matvec(A, v))
            nw = jnp.linalg.norm(w)
            v = w / jnp.maximum(nw, 1e-30)
            return v, nw

        v, _ = jax.lax.scan(step, v0, None, length=iters)
        Av = obj.matvec(A, v)
        return jnp.vdot(Av, Av) / jnp.maximum(jnp.vdot(v, v), 1e-30)


def p_star_array(A: jax.Array, key: jax.Array | None = None,
                 iters: int = 100) -> jax.Array:
    """``p_star`` left on the device, for a caller that reads it itself."""
    rho = spectral_radius(A, key, iters)
    return jnp.ceil(A.shape[1] / jnp.maximum(rho, 1.0) - 0.01)


def p_star(A: jax.Array, key: jax.Array | None = None, iters: int = 100) -> int:
    """P* = ceil(d / rho): the plug-in estimate of the ideal parallelism.

    Power iteration approaches rho from below; the 1% slack keeps d/rho from
    landing epsilon above an integer (e.g. exactly-correlated features must
    give P* = 1, not 2)."""
    return int(p_star_array(A, key, iters))


def p_star_dup(A: jax.Array, key: jax.Array | None = None, iters: int = 100) -> int:
    """Duplicated-feature bound of Thm 3.2: P < 2d/rho + 1."""
    rho = spectral_radius(A, key, iters)
    return int(jnp.ceil(2 * A.shape[1] / jnp.maximum(rho, 1.0)))


def p_star_blocks(A: jax.Array, block: int = 128,
                  key: jax.Array | None = None, iters: int = 100) -> int:
    """P* expressed in ``block``-sized coordinate blocks (>= 1): the backoff
    floor for the Pallas block solvers, whose parallelism unit is K blocks
    of 128 coordinates (``GuardConfig.p_min`` wants the solver's own
    units, DESIGN §9)."""
    return max(1, -(-p_star(A, key, iters) // block))
