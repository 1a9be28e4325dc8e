"""Plain float32 references: the objective, F* from FISTA, and the
multi-round block coordinate-descent oracle.

Imports nothing of the program.  Every product runs at ``highest``
precision: on a TPU the default would round float32 operands to bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 128
BETA = {"lasso": 1.0, "logistic": 0.25}


def dot(a, b):
    """a @ b in full float32."""
    return jnp.matmul(a, b, precision=HIGHEST)


def residual(z, y, loss):
    """dL/dz: z - y (lasso) or -y sigmoid(-y z) (logistic)."""
    if loss == "lasso":
        return z - y
    return -y * jax.nn.sigmoid(-y * z)


def data_loss(z, y, loss):
    if loss == "lasso":
        e = z - y
        return 0.5 * jnp.sum(e * e)
    return jnp.sum(jnp.logaddexp(0.0, -y * z))


def soft_threshold(v, t):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


@functools.partial(jax.jit, static_argnames=("loss",))
def lambda_max(A, y, loss):
    """Smallest lam at which x = 0 is optimal: |A^T dL/dz(0)|_inf."""
    r0 = residual(jnp.zeros_like(y), y, loss)
    return jnp.max(jnp.abs(dot(r0, A)))


@functools.partial(jax.jit, static_argnames=("iters",))
def spectral_norm_sq(A, iters: int = 40):
    """Largest eigenvalue of A^T A by power iteration from a fixed start."""
    v = jnp.ones(A.shape[1], jnp.float32) / jnp.sqrt(A.shape[1])

    def step(v, _):
        w = dot(dot(A, v), A)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None

    v, _ = jax.lax.scan(step, v, None, length=iters)
    Av = dot(A, v)
    return jnp.vdot(Av, Av)


@functools.partial(jax.jit, static_argnames=("loss", "iters"))
def fista(A, y, lam, lip, loss, iters: int):
    """Monotone FISTA from x = 0; returns (x, F per iteration).

    The margin of the extrapolated point is kept by linearity, so each
    iteration reads A twice (A^T r and A x_new); F is taken from a fresh
    A x_new, so the trace is the objective of the iterate it names."""
    d = A.shape[1]
    x0 = jnp.zeros(d, jnp.float32)
    z0 = jnp.zeros_like(y)
    f0 = data_loss(z0, y, loss)

    def step(carry, _):
        x, zx, v, zv, t, f = carry
        g = dot(residual(zv, y, loss), A)
        x_new = soft_threshold(v - g / lip, lam / lip)
        z_new = dot(A, x_new)
        f_new = data_loss(z_new, y, loss) + lam * jnp.sum(jnp.abs(x_new))
        worse = ~(f_new <= f)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        c = (t - 1.0) / t_new
        keep = lambda old, new: jnp.where(worse, old, new)
        x_out, z_out = keep(x, x_new), keep(zx, z_new)
        v_out = keep(x, x_new + c * (x_new - x))
        zv_out = keep(zx, z_new + c * (z_new - zx))
        f_out = jnp.minimum(f, f_new)
        return (x_out, z_out, v_out, zv_out, keep(1.0, t_new), f_out), f_out

    carry = (x0, z0, x0, z0, jnp.float32(1.0), f0)
    (x, *_), fs = jax.lax.scan(step, carry, None, length=iters)
    return x, fs


def f_star(A, y, lam, loss: str, iters: int):
    """F* of the problem from FISTA: (F*, the F trace)."""
    lip = spectral_norm_sq(A) * (0.25 if loss == "logistic" else 1.0) * 1.02
    _, fs = fista(A, y, lam, lip, loss, iters)
    return fs[-1], fs


def pad_blocks(A):
    """Zero columns up to a whole number of 128-blocks, as the solver's
    block layout addresses them."""
    pad = (-A.shape[1]) % BLOCK
    if pad:
        A = jnp.pad(A, ((0, 0), (0, pad)))
    return A


def draw_blocks(key, rounds: int, nblk: int, K: int):
    """(rounds, K) block indices: round t draws K distinct blocks with the
    t-th key of ``split(key, rounds)``."""
    keys = jax.random.split(key, rounds)
    draw = lambda k: jax.random.choice(k, nblk, (K,), replace=False)
    return jax.vmap(draw)(keys).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("loss", "newton"))
def block_rounds(A, y, lam, x, z, idx, loss: str, newton: bool):
    """Block-Shotgun rounds over ``idx`` (R, K) from (x, z), in plain jnp.

    Each round takes every selected block's step from the round-start
    iterate and margin (the multiset semantics of Alg. 2), then adds all
    steps.  The step divides by beta, or with ``newton`` by the block's
    diagonal curvature sum_i a_ij^2 L''(z_i), floored at 1e-8.  A is
    block-padded (``pad_blocks``).  Returns (x, z, F per round)."""
    n, d = A.shape
    nblk = d // BLOCK
    Ab = A.reshape(n, nblk, BLOCK)

    def round_fn(carry, idx_t):
        x, z = carry
        K = idx_t.shape[0]
        cols = jnp.take(Ab, idx_t, axis=1).reshape(n, K * BLOCK)
        g = dot(residual(z, y, loss), cols)
        if newton:
            p = jax.nn.sigmoid(z) if loss == "logistic" else None
            w = p * (1.0 - p) if loss == "logistic" else jnp.ones_like(z)
            h = jnp.maximum(dot(w, cols * cols), 1e-8)
        else:
            h = BETA[loss]
        xb = x.reshape(nblk, BLOCK)
        x_sel = jnp.take(xb, idx_t, axis=0).reshape(-1)
        delta = soft_threshold(x_sel - g / h, lam / h) - x_sel
        z = z + dot(cols, delta)
        x = xb.at[idx_t].add(delta.reshape(K, BLOCK)).reshape(-1)
        f = data_loss(z, y, loss) + lam * jnp.sum(jnp.abs(x))
        return (x, z), f

    (x, z), fs = jax.lax.scan(round_fn, (x, z), idx)
    return x, z, fs
