"""Fused ε-neighbor counting kernel (L2).

Computes, per query row, |{ j : ||x_i - y_j||^2 <= eps^2 }| WITHOUT ever
writing the (q, p) distance matrix to HBM — distances live only in the VMEM
tile and are reduced to per-query counts in-register. This is the memory-
roofline win over kernel+jnp composition: HBM traffic drops from
O(q*p) to O(q) on the output side.

Grid = (nq/TQ, np/TP); the TP axis is innermost/sequential so partial counts
accumulate in the (TQ,) output block. Feature dim is loaded whole per tile
(the NNG engine tiles d at the caller when d > 2048).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.nng_tile import _eps2_f32


def _eps_count_kernel(x_ref, y_ref, mask_ref, out_ref, *, eps2: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)  # (TQ, d)
    y = y_ref[...].astype(jnp.float32)  # (TP, d)
    acc = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    xs = (x * x).sum(axis=1)[:, None]
    ys = (y * y).sum(axis=1)[None, :]
    d2 = xs + ys - 2.0 * acc
    hit = (d2 <= eps2) & (mask_ref[...] != 0)[None, :]  # mask padded y rows
    out_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=1)


def eps_count_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    y_mask: jnp.ndarray,
    eps: float,
    *,
    tq: int = 256,
    tp: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (q, d), y (p, d), y_mask (p,) int32 -> counts (q,) int32."""
    q, d = x.shape
    p, _ = y.shape
    assert q % tq == 0 and p % tp == 0, (x.shape, y.shape)
    grid = (q // tq, p // tp)
    # _eps2_f32, not float(eps) ** 2: squaring in f64 and letting the
    # compare cast the literal to f32 lands 1 ulp off the oracle's
    # f32(eps)**2 threshold on knife-edge pairs (repro.analysis RA101)
    kernel = functools.partial(_eps_count_kernel, eps2=_eps2_f32(eps))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tp, d), lambda i, j: (j, 0)),
            pl.BlockSpec((tp,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((tq,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        interpret=interpret,
    )(x, y, y_mask)
