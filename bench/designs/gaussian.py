"""gaussian: iid N(0, 1) entries, a stand-in for a dense real-valued
design such as zeta's features."""
import jax
import jax.numpy as jnp


def columns(key, n: int, cols: int, cfg: dict):
    """(n, cols) raw columns, entries of unit variance."""
    return jax.random.normal(key, (n, cols), jnp.float32)
