"""dense_pm1: dense random -1/+1 measurements, as a single-pixel camera's
random mirror patterns take them (Duarte et al., IEEE Signal Processing
Magazine 25(2), 2008)."""
import jax
import jax.numpy as jnp


def columns(key, n: int, cols: int, cfg: dict):
    """(n, cols) raw columns, entries -1 or +1 with equal probability."""
    return jnp.where(jax.random.bernoulli(key, 0.5, (n, cols)), 1.0,
                     -1.0).astype(jnp.float32)
