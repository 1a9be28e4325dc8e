"""Problem instances made on the device from a seed.

The benchmark's own copy of the formulas of the program's synthetic
generators (``sparco`` and ``logistic_data``), drawn with ``jax.random``
in one jitted call, a block of columns at a time so that a design of
gigabytes needs no second copy of itself:

  A   (n, d) raw columns from the configuration's ``design``
      (``designs/<design>.py``, entries of unit variance), then scaled to
      unit norm (the paper's w.l.o.g. normalisation, done here so that the
      program is handed the design as a user hands it, and the reference
      reads the same array without taking anything the program made).
  x   teacher with ``k = int(d * nnz_frac)`` non-zeros whose values are
      fixed: the k quantiles (i + 1/2) / k of 2 N(0, 1), placed at random
      coordinates.  The seed moves where they sit, not how large they are,
      so that every seed poses a problem of the same difficulty.
  y   lasso: A_raw x + noise N(0, 1); logistic: +-1 drawn with
      probability sigmoid(A_raw x), then flipped with probability ``flip``.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
from jax.scipy.special import ndtri

HIGHEST = jax.lax.Precision.HIGHEST
DESIGNS = pathlib.Path(__file__).resolve().parents[1] / "designs"
BLOCK_BYTES = 2 ** 28          # raw columns drawn at a time, at most


def seed_key(seed: int) -> jax.Array:
    """PRNG key of a whole-number seed; seeds past 32 bits keep their high
    bits (``PRNGKey`` splits a Python int into two 32-bit words)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.PRNGKey(seed)


def design(name: str):
    """The ``columns(key, n, cols, cfg)`` function of
    ``designs/<name>.py``."""
    path = DESIGNS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_design_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.columns


def column_block(n: int, d: int) -> int:
    """Columns drawn at a time: the largest divisor of d whose raw block
    stays under ``BLOCK_BYTES``."""
    cap = max(1, BLOCK_BYTES // (4 * n))
    return max(c for c in range(1, min(d, cap) + 1) if d % c == 0)


def teacher(key, d: int, nnz_frac: float):
    """The teacher x: fixed values at coordinates drawn from ``key``."""
    k = max(1, int(d * nnz_frac))
    values = 2.0 * ndtri((jnp.arange(k, dtype=jnp.float32) + 0.5) / k)
    support = jax.random.permutation(key, d)[:k]
    return jnp.zeros(d, jnp.float32).at[support].set(values)


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple):
    cfg = dict(cfg_items)
    n, d, loss = cfg["n"], cfg["d"], cfg["loss"]
    columns = design(cfg["design"])
    c = column_block(n, d)

    @jax.jit
    def make(key):
        k_a, k_x, k_y, k_f = jax.random.split(key, 4)
        x = teacher(k_x, d, cfg["nnz_frac"])

        def block(i, carry):
            A, t = carry
            raw = columns(jax.random.fold_in(k_a, i), n, c, cfg)
            t = t + jnp.matmul(raw, jax.lax.dynamic_slice(x, (i * c,), (c,)),
                               precision=HIGHEST)
            norms = jnp.sqrt(jnp.sum(raw * raw, axis=0))
            cols = raw / jnp.where(norms < 1e-12, 1.0, norms)[None, :]
            return jax.lax.dynamic_update_slice(A, cols, (0, i * c)), t

        A, t = jax.lax.fori_loop(
            0, d // c, block,
            (jnp.zeros((n, d), jnp.float32), jnp.zeros(n, jnp.float32)))
        if loss == "lasso":
            y = t + cfg.get("noise", 0.0) * jax.random.normal(k_y, (n,))
        elif loss == "logistic":
            y = jnp.where(jax.random.uniform(k_y, (n,)) < jax.nn.sigmoid(t),
                          1.0, -1.0)
            y = jnp.where(jax.random.uniform(k_f, (n,)) < cfg.get("flip", 0.0),
                          -y, y)
        else:
            raise ValueError(f"unknown loss {loss!r}")
        return A, y.astype(jnp.float32)
    return make


def make_data(key, cfg: dict):
    """(A, y) of the configuration ``cfg`` at ``key``; A is
    column-normalised."""
    keys = ("n", "d", "loss", "design", "nnz_frac", "noise", "flip",
            "density")
    return _maker(tuple((k, cfg[k]) for k in keys if k in cfg))(key)
