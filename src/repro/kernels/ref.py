"""Pure-jnp oracles for the Pallas distance kernels.

These are the semantic ground truth: every Pallas kernel in this package is
validated (interpret mode on CPU, compiled on TPU) against these functions
over shape/dtype sweeps in ``tests/test_kernels.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_sqdist_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distances. x: (q, d), y: (p, d) -> (q, p) fp32.

    Uses the direct (x - y)^2 formulation — numerically the reference; the
    kernel uses the BLAS3 expansion and is checked to a tolerance.
    """
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    diff = x[:, None, :] - y[None, :, :]
    return jnp.sum(diff * diff, axis=-1)


def pairwise_sqdist_blas3_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """BLAS3 expansion ||x||^2 + ||y||^2 - 2<x,y> — matches kernel math
    (the dot at HIGHEST precision: fp32-exact on TPU, not one bf16 pass)."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)[None, :]
    d = xn + yn - 2.0 * jnp.matmul(x, y.T,
                                   precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d, 0.0)


def pairwise_hamming_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Hamming distances over packed bit words.

    x: (q, w) uint32, y: (p, w) uint32 -> (q, p) int32 popcount(x ^ y).
    """
    import jax.lax as lax
    xor = jnp.bitwise_xor(x[:, None, :], y[None, :, :])
    return jnp.sum(lax.population_count(xor).astype(jnp.int32), axis=-1)


def eps_count_ref(x: jnp.ndarray, y: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Per-query count of y-points within L2 distance eps. -> (q,) int32."""
    d2 = pairwise_sqdist_ref(x, y)
    return jnp.sum((d2 <= jnp.float32(eps) ** 2).astype(jnp.int32), axis=1)
