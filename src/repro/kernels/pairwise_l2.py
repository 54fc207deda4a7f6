"""Tiled pairwise squared-L2 distance Pallas kernel (TPU target).

Computes D[i, j] = ||x_i||^2 + ||y_j||^2 - 2 <x_i, y_j> tile-by-tile on the
MXU. Grid = (nq/TQ, np/TP, d/TD); the feature dim is the innermost
(sequential, arbitrary) grid axis so the -2<x,y> term accumulates in the
output VMEM block, and the norm terms are added on the final feature step.

VMEM per step (fp32, defaults TQ=TP=256, TD=512):
  X tile 256*512*4 = 512 KiB, Y tile 512 KiB, out 256*256*4 = 256 KiB
  -> ~1.3 MiB, comfortably under the ~16 MiB/core v5e VMEM with double
  buffering. TQ/TP/TD are multiples of the 128-lane MXU dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sqdist_kernel(x_ref, y_ref, out_ref, *, nsteps: int):
    """One (TQ, TP) output tile, accumulating over feature-dim grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]  # (TQ, TD)
    y = y_ref[...]  # (TP, TD)
    # MXU contraction; fp32 accumulation regardless of input dtype.
    acc = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    out_ref[...] += -2.0 * acc

    # Per-step partial norms: add ||x_k||^2 + ||y_k||^2 for this feature
    # slice (cheap VPU work on the resident tiles; summing per-slice keeps
    # the accumulation correct for any nsteps without a second HBM stream).
    xs = (x.astype(jnp.float32) ** 2).sum(axis=1)[:, None]  # (TQ, 1)
    ys = (y.astype(jnp.float32) ** 2).sum(axis=1)[None, :]  # (1, TP)
    out_ref[...] += xs + ys

    @pl.when(k == nsteps - 1)
    def _clamp():
        out_ref[...] = jnp.maximum(out_ref[...], 0.0)


def pairwise_sqdist_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    tq: int = 256,
    tp: int = 256,
    td: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pairwise squared L2 distances, (q, d) x (p, d) -> (q, p) fp32.

    Shapes must be pre-padded to tile multiples by the caller (ops.py).
    """
    q, d = x.shape
    p, _ = y.shape
    assert q % tq == 0 and p % tp == 0 and d % td == 0, (x.shape, y.shape)
    nsteps = d // td
    grid = (q // tq, p // tp, nsteps)
    kernel = functools.partial(_sqdist_kernel, nsteps=nsteps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tp, td), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tq, tp), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, p), jnp.float32),
        interpret=interpret,
    )(x, y)
