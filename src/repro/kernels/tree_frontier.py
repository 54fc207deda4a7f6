"""Level-synchronous cover-tree frontier kernels (device tree traversal).

One traversal level of the batched cover-tree query (Alg. 3) is a dense
(frontier queries × level nodes) decision tile. These kernels fuse the
distance computation with the three per-pair decisions and emit only two
packed survivor bitmasks (the PR 1/2 bitmask idiom — 1/128 the bytes of an
fp32 decision tile):

  emit[q, v]    the node's whole DFS leaf range joins q's neighbor set:
                  leaf node:     d(q, v) <= eps        (EXACT, the same
                                 fp32 arithmetic as the flat tile kernels)
                  internal node: d(q, v) + radius(v) <= eps - slack
                                 (full inclusion, conservatively shrunk by
                                 a scale-relative fp32 slack — a borderline
                                 inclusion demotes to expansion and gets
                                 decided exactly at the leaves)
  expand[q, v]  the node's children enter the next level's frontier:
                  d(q, v) <= radius(v) + eps + slack   (triangle prune,
                                 over-expansion is always safe)

``active`` (packed, computed by the traversal driver from the previous
level's expand mask + cell scoping) gates everything; a (TQ × TN) block
whose active words are all zero early-outs without touching the MXU — the
in-cell analogue of the grouped kernel's block skip.

The kernels share the tile kernels' distance bodies and transposed layout
(``repro.kernels.nng_tile``): node rows on the sublanes, queries on the
lanes, and the active / emit / expand masks travel word-major as (N/32, nq)
int32, transposed back to the (nq, N/32) uint32 contract by the wrappers.

Hamming distances are exact integers: both slacks are zero and every
decision is exact at every level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .nng_tile import (_HAMMING, _L1, _L2, _Dist, _dists, _from_lanes,
                       _pack_words, _pack_words_t, _to_lanes, _unpack_words,
                       _unpack_words_t)


def _frontier_masks_float(d, rad, leaf, active, eps, leaf_hit=None):
    """Shared float-metric decision epilogue over TRUE distances d ->
    (emit, expand). ``rad`` / ``leaf`` broadcast against d along the node
    axis. ``leaf_hit`` overrides the exact leaf test when the caller has a
    sharper form (L2 compares d2 vs eps² with no sqrt)."""
    eps_f = jnp.float32(eps)
    # scale-relative fp32 slack (same family as the block-summary prune and
    # Lemma-1 slacks): also covers the fp32 rounding of the float64 radii
    slack = (d + rad + eps_f) * jnp.float32(1e-5) + jnp.float32(1e-6)
    leafb = leaf != 0
    if leaf_hit is None:
        leaf_hit = d <= eps_f
    incl = d + rad <= eps_f - slack
    emit = active & ((leafb & leaf_hit) | (~leafb & incl))
    expand = active & ~leafb & ~emit & (d <= rad + eps_f + slack)
    return emit, expand


def _frontier_masks_l2(d2, rad, leaf, active, eps):
    """L2 decision epilogue: squared-distance tile -> masks."""
    eps_f = jnp.float32(eps)
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    return _frontier_masks_float(d, rad, leaf, active, eps,
                                 leaf_hit=d2 <= eps_f * eps_f)


def _frontier_masks_hamming(d, rad, leaf, active, eps):
    """Hamming decision epilogue — integer distances, zero slack."""
    eps_i = jnp.int32(int(eps))
    radr = rad.astype(jnp.int32)
    leafb = leaf != 0
    leaf_hit = d <= eps_i
    incl = d + radr <= eps_i
    emit = active & ((leafb & leaf_hit) | (~leafb & incl))
    expand = active & ~leafb & ~emit & (d <= radr + eps_i)
    return emit, expand


def _frontier_kernel(*refs, nxd, body, masks, eps):
    xd_refs = refs[:nxd]
    yd_refs = refs[nxd:-5]
    rad_ref, leaf_ref, act_ref, emit_ref, exp_ref = refs[-5:]
    act = act_ref[...]
    live = jnp.any(act != 0)

    @pl.when(live)
    def _compute():
        d = body(*[r[...] for r in xd_refs], *[r[...] for r in yd_refs])
        emit, expand = masks(d, rad_ref[...], leaf_ref[...],
                             _unpack_words_t(act), eps)      # (TN, TQ)
        emit_ref[...] = _pack_words_t(emit)
        exp_ref[...] = _pack_words_t(expand)

    @pl.when(jnp.logical_not(live))
    def _skip():
        emit_ref[...] = jnp.zeros_like(emit_ref)
        exp_ref[...] = jnp.zeros_like(exp_ref)


def _frontier_call(dist: _Dist, masks, q, c, rad, leaf, act_bits, eps, tq,
                   tn, interpret):
    """q (nq, ·) queries, c (N, ·) level-node rows, rad (N,) fp32 radii,
    leaf (N,) int32 flags, act_bits (nq, N/32) packed active mask ->
    (emit_bits, expand_bits) each (nq, N/32) uint32. nq % tq == 0,
    N % tn == 0, tn % 32 == 0 (caller pads; pad columns must be
    inactive)."""
    nq, N = q.shape[0], c.shape[0]
    assert nq % tq == 0 and N % tn == 0 and tn % 32 == 0, (nq, tq, N, tn)
    xd, yd = dist.prep(q, c)
    ys = list(yd) + [jnp.asarray(rad, jnp.float32)[:, None],
                     jnp.asarray(leaf, jnp.int32)[:, None]]
    kernel = functools.partial(_frontier_kernel, nxd=len(xd),
                               body=dist.body, masks=masks, eps=eps)
    word_spec = pl.BlockSpec((tn // 32, tq), lambda i, j: (j, i))
    emit_t, exp_t = pl.pallas_call(
        kernel,
        grid=(nq // tq, N // tn),
        in_specs=([pl.BlockSpec((a.shape[0], tq), lambda i, j: (0, i))
                   for a in xd]
                  + [pl.BlockSpec((tn, a.shape[1]), lambda i, j: (j, 0))
                     for a in ys]
                  + [word_spec]),
        out_specs=[word_spec, word_spec],
        out_shape=[jax.ShapeDtypeStruct((N // 32, nq), jnp.int32)] * 2,
        interpret=interpret,
    )(*xd, *ys, _to_lanes(act_bits))
    return _from_lanes(emit_t), _from_lanes(exp_t)


def _frontier_ref(dist: _Dist, masks, q, c, rad, leaf, act_bits, eps):
    emit, expand = masks(_dists(dist, q, c),
                         jnp.asarray(rad, jnp.float32)[None, :],
                         jnp.asarray(leaf, jnp.int32)[None, :],
                         _unpack_words(act_bits), eps)
    return _pack_words(emit), _pack_words(expand)


def tree_frontier_pallas(q, c, rad, leaf, act_bits, eps: float, *,
                         tq: int = 256, tn: int = 512,
                         interpret: bool = False):
    """L2 frontier tile (see ``_frontier_call`` for the contract)."""
    return _frontier_call(_L2, _frontier_masks_l2, q, c, rad, leaf,
                          act_bits, float(eps), tq, tn, interpret)


def tree_frontier_ref(q, c, rad, leaf, act_bits, eps: float):
    """Pure-jnp oracle (the same fp32 BLAS3 body as the kernel)."""
    return _frontier_ref(_L2, _frontier_masks_l2, q, c, rad, leaf, act_bits,
                         float(eps))


def tree_frontier_hamming_pallas(q, c, rad, leaf, act_bits, eps: float, *,
                                 tq: int = 128, tn: int = 256,
                                 interpret: bool = False):
    """Hamming frontier tile over packed uint32 word rows, exact integer
    thresholds."""
    return _frontier_call(_HAMMING, _frontier_masks_hamming, q, c, rad, leaf,
                          act_bits, int(eps), tq, tn, interpret)


def tree_frontier_hamming_ref(q, c, rad, leaf, act_bits, eps: float):
    """Pure-jnp oracle (exact integer distances)."""
    return _frontier_ref(_HAMMING, _frontier_masks_hamming, q, c, rad, leaf,
                         act_bits, int(eps))


def tree_frontier_l1_pallas(q, c, rad, leaf, act_bits, eps: float, *,
                            tq: int = 128, tn: int = 256,
                            interpret: bool = False):
    """L1 frontier tile over fp32 rows, true-distance thresholds with the
    shared float slack."""
    return _frontier_call(_L1, _frontier_masks_float, q, c, rad, leaf,
                          act_bits, float(eps), tq, tn, interpret)


def tree_frontier_l1_ref(q, c, rad, leaf, act_bits, eps: float):
    """Pure-jnp oracle (the same column-ordered fp32 summation)."""
    return _frontier_ref(_L1, _frontier_masks_float, q, c, rad, leaf,
                         act_bits, float(eps))
