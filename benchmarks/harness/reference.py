"""The comparisons that decide ``correct``, the same for every family.
The plain references themselves are the families' (``families/*.py``).
"""

from __future__ import annotations


def rel_err_device(a, b):
    """||a - b|| / ||b|| in float32 where the arrays live (they can be
    sharded and large)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))
    return f(a, b)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
