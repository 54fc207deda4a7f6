"""Fused ε-NNG tile kernels: distances + threshold + bit-packed adjacency.

The systolic step's HBM traffic is dominated by materializing the fp32
distance tile (n² × 4 B) and sorting it for id extraction. These kernels keep
the distance tile in VMEM and write only:

  - cnt  (n,)        exact per-row ε-neighbor counts,
  - bits (n, n/32)   the adjacency bitmask, packed 32 columns per uint32 —
                     128× smaller than the fp32 distance tile.

Kernel layout (what Mosaic lowers). Inside a kernel the tile is held
TRANSPOSED, (TP, TQ): the y rows run down the sublanes and the x rows across
the lanes. x-side operands arrive lane-major as (rows, q) arrays (``x.T``,
norms and per-row metadata as (1, q) rows), y-side operands sublane-major as
(p, cols) arrays (per-row metadata as (p, 1) columns). Bit packing then
folds groups of 32 sublanes into one int32 word — a sublane reduction — and
each program writes a (TP/32, TQ) block of the word-major (p/32, q) mask,
whose blocks satisfy the (8, 128) tiling rule at every engine tile shape.
The ``*_pallas`` wrappers transpose that mask back to the (q, p/32) uint32
contract (column j -> word j // 32, bit j % 32) before returning, so
callers never see the kernel layout.

Three distance bodies share one kernel skeleton:
  - L2       MXU BLAS3 expansion at ``Precision.HIGHEST`` (fp32-exact dot
             on TPU — the default single bf16 pass is far outside the fp32
             slack the ghost and frontier bounds assume)
  - Hamming  packed uint32 words, XOR + popcount per word (VPU)
  - L1       fp32 |x - y| summed one feature column at a time (VPU)

and three hit rules:
  - plain    threshold & y-row validity (systolic ring)
  - grouped  + Voronoi cell equality, row validity (group < 0 is padding)
             and global-id inequality (landmark W/G phases). Blocks whose
             valid-group ranges cannot intersect early-out with a zero word
             tile; ``repro.kernels.ops.grouped_block_active`` mirrors the
             rule host-side for the tiles_scheduled / tiles_skipped counters.
  - ghost    + the slacked Lemma-1 ghost test carried as per-row packed cell
             masks (landmark ``ghost_mode="ring"``), looked up with one exact
             0/1 MXU contraction; ``ops.ghost_block_active`` mirrors its
             block skip.

Each ``*_ref`` is the pure-jnp oracle of its kernel and calls the SAME
distance body (in the natural orientation), so knife-edge pairs classify
identically on the kernel and fallback paths.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def _eps2_f32(eps: float) -> float:
    """The canonical L2 comparable threshold: eps rounded to fp32, squared
    IN fp32 — exactly what the jnp oracles (``jnp.float32(eps) ** 2``) and
    the frontier kernels (``eps_f * eps_f``) compute. The Pallas kernels
    must embed the same value, or a pair whose fp32 d² lands exactly on
    the threshold classifies differently between kernel and oracle paths
    (1-ulp threshold skew)."""
    return float(np.float32(eps) ** 2)


def _pack_words(hit):
    """(TQ, TP) bool hit mask -> (TQ, TP/32) uint32, little-endian bit order
    (column j lands in word j // 32, bit j % 32)."""
    tq, tp = hit.shape
    words = hit.reshape(tq, tp // 32, 32).astype(jnp.uint32)
    powers = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(words * powers[None, None, :], axis=-1)


def _unpack_words(bits):
    """(TQ, W) uint32 -> (TQ, 32*W) bool, little-endian bit order (the
    inverse of ``_pack_words``)."""
    tq, w = bits.shape
    bitpos = jnp.arange(32, dtype=jnp.uint32)
    b = ((bits[:, :, None] >> bitpos[None, None, :]) & 1) == 1
    return b.reshape(tq, w * 32)


def _pack_words_t(hit_t):
    """Kernel-layout packing: (TP, TQ) bool -> (TP/32, TQ) int32; word w of
    lane i holds rows 32w .. 32w+31 of column i (bit b = row 32w + b) — the
    transpose of ``_pack_words``, as a sublane reduction."""
    tp, tq = hit_t.shape
    h = hit_t.astype(jnp.int32).reshape(tp // 32, 32, tq)
    return jnp.sum(h << jax.lax.broadcasted_iota(jnp.int32, h.shape, 1),
                   axis=1)


def _unpack_words_t(words_t):
    """Inverse of ``_pack_words_t``: (W, TQ) int32 -> (32W, TQ) bool."""
    w, tq = words_t.shape
    r = jnp.broadcast_to(words_t[:, None, :], (w, 32, tq)).reshape(32 * w, tq)
    sh = jax.lax.broadcasted_iota(jnp.int32, r.shape, 0) % 32
    return (jax.lax.shift_right_logical(r, sh) & 1) != 0


def _to_lanes(words):
    """(n, W) uint32 words -> (W, n) int32, the kernel's lane-major layout."""
    return jax.lax.bitcast_convert_type(jnp.asarray(words, jnp.uint32),
                                        jnp.int32).T


def _from_lanes(words_t):
    """(W, n) int32 kernel-layout words -> (n, W) uint32 contract layout."""
    return jax.lax.bitcast_convert_type(words_t.T, jnp.uint32)


# ---------------------------------------------------------------------------
# distance bodies, transposed orientation: x-side (rows, TQ), y-side (TP, cols)
# -> (TP, TQ) comparable distances
# ---------------------------------------------------------------------------

def _l2_body_t(xt, xn, y, yn):
    """Squared L2 via the BLAS3 expansion: xt (d, TQ), xn (1, TQ) norms,
    y (TP, d), yn (TP, 1) norms. HIGHEST keeps the dot fp32-exact on TPU.
    ALL L2 kernels and oracles use this body so their numerics never
    diverge."""
    acc = jax.lax.dot_general(
        y, xt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST)
    return (yn + xn) - 2.0 * acc


def _hamming_body_t(xt, y):
    """Hamming over packed words: xt (W, TQ), y (TP, W) uint32 -> int32
    counts, one word column at a time (2-D VPU work, no cube)."""
    d = jnp.zeros((y.shape[0], xt.shape[1]), jnp.int32)
    for c in range(xt.shape[0]):
        d = d + jax.lax.population_count(
            jnp.bitwise_xor(y[:, c:c + 1], xt[c:c + 1, :])).astype(jnp.int32)
    return d


def _l1_body_t(xt, y):
    """L1 (Manhattan): xt (d, TQ), y (TP, d) fp32 -> sums of |x - y|,
    accumulated one feature column at a time in a fixed order."""
    d = jnp.zeros((y.shape[0], xt.shape[1]), jnp.float32)
    for c in range(xt.shape[0]):
        d = d + jnp.abs(y[:, c:c + 1] - xt[c:c + 1, :])
    return d


def sq_norms(x):
    """Row norms ||x||^2 of (n, d) fp32 rows, summed by a fixed pairwise
    tree of elementwise adds over the zero-padded power-of-two width.
    Elementwise adds are never reassociated, so a point's norm is the same
    bits whatever array holds it, whatever its layout or fusion. A plain
    ``jnp.sum`` leaves the order to the compiler, which may pick another
    one per array, and one engine's copy of a point could then classify a
    knife-edge pair differently from another's."""
    s = x * x
    w = 1 << (s.shape[1] - 1).bit_length()
    s = jnp.pad(s, ((0, 0), (0, w - s.shape[1])))
    while s.shape[1] > 1:
        s = s[:, :s.shape[1] // 2] + s[:, s.shape[1] // 2:]
    return s[:, 0]


def _l2_prep(x, y):
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    return ((x.T, sq_norms(x)[None, :]), (y, sq_norms(y)[:, None]))


def _words_prep(x, y):
    return (jnp.asarray(x).T,), (jnp.asarray(y),)


def _l1_prep(x, y):
    return (jnp.asarray(x, jnp.float32).T,), (jnp.asarray(y, jnp.float32),)


class _Dist(NamedTuple):
    prep: Callable      # (x, y) -> (x-side operands, y-side operands)
    body: Callable      # (*x-side blocks, *y-side blocks) -> (TP, TQ)
    thresh: Callable    # eps -> the comparable threshold literal


_L2 = _Dist(_l2_prep, _l2_body_t, _eps2_f32)
_HAMMING = _Dist(_words_prep, _hamming_body_t, lambda eps: int(eps))
_L1 = _Dist(_l1_prep, _l1_body_t, lambda eps: np.float32(eps))


def _dists(dist: _Dist, x, y):
    """Natural-orientation (q, p) comparable distances through the kernel
    body — the oracles' distance arithmetic."""
    xs, ys = dist.prep(x, y)
    return dist.body(*xs, *ys).T


def l2_sq_dists(x, y):
    """(q, d), (p, d) -> (q, p) fp32 squared L2, the kernels' arithmetic."""
    return _dists(_L2, x, y)


# ---------------------------------------------------------------------------
# hit rules. Operands arrive broadcast-ready: x-side (1, TQ) rows and y-side
# (TP, 1) columns in the kernel, (q, 1) / (1, p) in the natural oracles.
# ---------------------------------------------------------------------------

_GBIG = 2**30        # "no valid group in this tile" sentinel (python int so
                     # kernels don't capture a traced constant)


def _grouped_hit(ok, xg, yg, xid, yid):
    """Fold group equality, validity (group < 0 = padding) and
    id-inequality into the hit mask."""
    return ok & (xg == yg) & (xg >= 0) & (yg >= 0) & (xid != yid)


def _group_active(xg, yg):
    """A grouped block is live iff the two valid-group [min, max] ranges
    intersect (callers cell-sort rows, so the ranges are tight); this also
    covers all-padding tiles (empty range)."""
    xmin = jnp.min(jnp.where(xg >= 0, xg, _GBIG))
    xmax = jnp.max(jnp.where(xg >= 0, xg, -1))
    ymin = jnp.min(jnp.where(yg >= 0, yg, _GBIG))
    ymax = jnp.max(jnp.where(yg >= 0, yg, -1))
    return (xmin <= ymax) & (ymin <= xmax)


def _ghost_hit(d_ok, xb, yg, yv):
    """Natural-orientation ghost rule: hit(i, j) = d_ok and y_group[j] >= 0
    and bit y_group[j] of row i's mask. The lookup is one MXU contraction,
    unpacked masks (TQ, M) x one-hot y cells (M, TP); its 0/1 products sum
    exactly, so the > 0.5 test is exact."""
    cells = jnp.arange(xb.shape[1], dtype=jnp.int32)
    oneh = (yg[None, :] == cells[:, None]) & yv[None, :]
    sel = jax.lax.dot_general(
        xb.astype(jnp.bfloat16), oneh.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return d_ok & (sel > 0.5)


class _Rule(NamedTuple):
    nx: int               # x-side rule operands
    active: Callable | None   # (xr, yr) -> scalar bool block-live flag
    hit: Callable         # (ok, xr, yr) -> (TP, TQ) bool


def _plain_hit(ok, xr, yr):
    (yv,) = yr
    return ok & (yv != 0)


def _grouped_active_k(xr, yr):
    return _group_active(xr[0], yr[0])


def _grouped_hit_k(ok, xr, yr):
    xg, xid = xr
    yg, yid = yr
    return _grouped_hit(ok, xg, yg, xid, yid)


def _ghost_active_k(xr, yr):
    """Live iff some visiting row has a ghost bit inside the y tile's
    valid-cell [min, max] range."""
    xb = _unpack_words_t(xr[0])                         # (M, TQ)
    yg = yr[0]
    ymin = jnp.min(jnp.where(yg >= 0, yg, _GBIG))
    ymax = jnp.max(jnp.where(yg >= 0, yg, -1))
    cells = jax.lax.broadcasted_iota(jnp.int32, (xb.shape[0], 1), 0)
    hot = (jnp.any(xb, axis=1, keepdims=True)
           & (cells >= ymin) & (cells <= ymax))
    return jnp.any(hot)


def _ghost_hit_k(ok, xr, yr):
    xb = _unpack_words_t(xr[0])                         # (M, TQ)
    yg = yr[0]                                          # (TP, 1)
    cells = jax.lax.broadcasted_iota(jnp.int32, (1, xb.shape[0]), 1)
    oneh = (yg == cells) & (yg >= 0)                    # (TP, M)
    sel = jax.lax.dot_general(
        oneh.astype(jnp.bfloat16), xb.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return ok & (sel > 0.5)


_PLAIN = _Rule(0, None, _plain_hit)
_GROUPED = _Rule(2, _grouped_active_k, _grouped_hit_k)
_GHOST = _Rule(1, _ghost_active_k, _ghost_hit_k)


# ---------------------------------------------------------------------------
# the shared kernel skeleton and launcher
# ---------------------------------------------------------------------------

def _tile_kernel(*refs, nxd, nyd, rule: _Rule, body, thr):
    nx = nxd + rule.nx
    xd_refs, xr_refs = refs[:nxd], refs[nxd:nx]
    yd_refs = refs[nx:nx + nyd]
    yr_refs = refs[nx + nyd:-2]
    cnt_ref, bits_ref = refs[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    xr = [r[...] for r in xr_refs]
    yr = [r[...] for r in yr_refs]

    def _compute():
        d = body(*[r[...] for r in xd_refs], *[r[...] for r in yd_refs])
        hit = rule.hit(d <= thr, xr, yr)                    # (TP, TQ)
        cnt_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)
        bits_ref[...] = _pack_words_t(hit)

    if rule.active is None:
        _compute()
        return
    active = rule.active(xr, yr)
    pl.when(active)(_compute)

    @pl.when(jnp.logical_not(active))
    def _skip():
        bits_ref[...] = jnp.zeros_like(bits_ref)


def _tile_call(dist: _Dist, rule: _Rule, x, y, x_rule, y_rule, eps, tq, tp,
               interpret):
    """Launch one fused tile kernel. x_rule / y_rule are the rule's x-side
    (rows, q) and y-side (p, cols) operands."""
    q, p = x.shape[0], y.shape[0]
    assert q % tq == 0 and p % tp == 0 and tp % 32 == 0, (q, tq, p, tp)
    xd, yd = dist.prep(x, y)
    xs = list(xd) + list(x_rule)
    ys = list(yd) + list(y_rule)
    kernel = functools.partial(
        _tile_kernel, nxd=len(xd), nyd=len(yd), rule=rule, body=dist.body,
        thr=dist.thresh(eps))
    cnt, bits_t = pl.pallas_call(
        kernel,
        grid=(q // tq, p // tp),
        in_specs=([pl.BlockSpec((a.shape[0], tq), lambda i, j: (0, i))
                   for a in xs]
                  + [pl.BlockSpec((tp, a.shape[1]), lambda i, j: (j, 0))
                     for a in ys]),
        out_specs=[
            pl.BlockSpec((1, tq), lambda i, j: (0, i)),
            pl.BlockSpec((tp // 32, tq), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, q), jnp.int32),
            jax.ShapeDtypeStruct((p // 32, q), jnp.int32),
        ],
        interpret=interpret,
    )(*xs, *ys)
    return cnt[0], _from_lanes(bits_t)


def _row(v):
    return jnp.asarray(v, jnp.int32)[None, :]


def _col(v):
    return jnp.asarray(v, jnp.int32)[:, None]


# ---------------------------------------------------------------------------
# public kernels (ungrouped: systolic ring) and their oracles
#
# Tiling contract for every kernel: q % tq == 0, p % tp == 0, tp % 32 == 0
# (callers pad; pad y rows must be invalid). Word-axis / feature-axis
# padding must be zero in BOTH operands.
# ---------------------------------------------------------------------------

def _plain(dist, x, y, y_valid, eps, tq, tp, interpret):
    return _tile_call(dist, _PLAIN, x, y, (), (_col(y_valid),), eps, tq, tp,
                      interpret)


def _plain_ref(dist, x, y, y_valid, eps):
    hit = ((_dists(dist, x, y) <= dist.thresh(eps))
           & (jnp.asarray(y_valid) != 0)[None, :])
    return jnp.sum(hit.astype(jnp.int32), axis=1), _pack_words(hit)


def nng_tile_pallas(x, y, y_valid, eps: float, *, tq: int = 256,
                    tp: int = 512, interpret: bool = False):
    """L2 tile: x (q, d), y (p, d), y_valid (p,) int32 ->
    (cnt (q,), bits (q, p/32))."""
    return _plain(_L2, x, y, y_valid, eps, tq, tp, interpret)


def nng_tile_ref(x, y, y_valid, eps: float):
    """Pure-jnp oracle."""
    return _plain_ref(_L2, x, y, y_valid, eps)


def nng_tile_hamming_pallas(x, y, y_valid, eps: float, *, tq: int = 128,
                            tp: int = 256, interpret: bool = False):
    """Hamming tile over packed uint32 word rows, exact integer
    threshold."""
    return _plain(_HAMMING, x, y, y_valid, eps, tq, tp, interpret)


def nng_tile_hamming_ref(x, y, y_valid, eps: float):
    """Pure-jnp oracle (exact integer distances)."""
    return _plain_ref(_HAMMING, x, y, y_valid, eps)


def nng_tile_l1_pallas(x, y, y_valid, eps: float, *, tq: int = 128,
                       tp: int = 256, interpret: bool = False):
    """L1 tile over fp32 rows, true-distance threshold."""
    return _plain(_L1, x, y, y_valid, eps, tq, tp, interpret)


def nng_tile_l1_ref(x, y, y_valid, eps: float):
    """Pure-jnp oracle (the same column-ordered fp32 summation)."""
    return _plain_ref(_L1, x, y, y_valid, eps)


# ---------------------------------------------------------------------------
# group-aware variants (landmark engine)
# ---------------------------------------------------------------------------

def _grouped(dist, x, y, xg, yg, xid, yid, eps, tq, tp, interpret):
    return _tile_call(dist, _GROUPED, x, y, (_row(xg), _row(xid)),
                      (_col(yg), _col(yid)), eps, tq, tp, interpret)


def _grouped_ref(dist, x, y, xg, yg, xid, yid, eps):
    hit = _grouped_hit(_dists(dist, x, y) <= dist.thresh(eps),
                       _col(xg), _row(yg), _col(xid), _row(yid))
    return jnp.sum(hit.astype(jnp.int32), axis=1), _pack_words(hit)


def nng_tile_grouped_pallas(x, y, x_group, y_group, x_ids, y_ids,
                            eps: float, *, tq: int = 256, tp: int = 512,
                            interpret: bool = False):
    """Group-aware L2 tile: groups (q,)/(p,) int32 (< 0 = invalid row),
    ids (q,)/(p,) int32 global point ids -> (cnt (q,), bits (q, p/32)).

    hit(i, j) = d2 <= eps² and x_group[i] == y_group[j] >= 0 and
    x_ids[i] != y_ids[j]."""
    return _grouped(_L2, x, y, x_group, y_group, x_ids, y_ids, eps, tq, tp,
                    interpret)


def nng_tile_grouped_ref(x, y, x_group, y_group, x_ids, y_ids, eps: float):
    """Pure-jnp oracle for the grouped L2 tile."""
    return _grouped_ref(_L2, x, y, x_group, y_group, x_ids, y_ids, eps)


def nng_tile_grouped_hamming_pallas(x, y, x_group, y_group, x_ids, y_ids,
                                    eps: float, *, tq: int = 128,
                                    tp: int = 256, interpret: bool = False):
    """Group-aware Hamming tile over packed uint32 rows."""
    return _grouped(_HAMMING, x, y, x_group, y_group, x_ids, y_ids, eps, tq,
                    tp, interpret)


def nng_tile_grouped_hamming_ref(x, y, x_group, y_group, x_ids, y_ids,
                                 eps: float):
    """Pure-jnp oracle for the grouped Hamming tile."""
    return _grouped_ref(_HAMMING, x, y, x_group, y_group, x_ids, y_ids, eps)


def nng_tile_grouped_l1_pallas(x, y, x_group, y_group, x_ids, y_ids,
                               eps: float, *, tq: int = 128, tp: int = 256,
                               interpret: bool = False):
    """Group-aware L1 tile over fp32 rows."""
    return _grouped(_L1, x, y, x_group, y_group, x_ids, y_ids, eps, tq, tp,
                    interpret)


def nng_tile_grouped_l1_ref(x, y, x_group, y_group, x_ids, y_ids,
                            eps: float):
    """Pure-jnp oracle for the grouped L1 tile."""
    return _grouped_ref(_L1, x, y, x_group, y_group, x_ids, y_ids, eps)


# ---------------------------------------------------------------------------
# ghost-ring variants (landmark engine, ghost_mode="ring"): the visiting
# block's rows carry packed Lemma-1 cell masks (ceil(m/32) uint32 words per
# row). A row's OWN cell bit is never set, so same-cell pairs (self pairs
# included) are structurally excluded without an id test; padding x rows
# carry all-zero masks and padding y rows group -1.
# ---------------------------------------------------------------------------

def _ghost(dist, x, y, x_gbits, y_group, eps, tq, tp, interpret):
    assert x_gbits.shape[0] == x.shape[0]
    return _tile_call(dist, _GHOST, x, y, (_to_lanes(x_gbits),),
                      (_col(y_group),), eps, tq, tp, interpret)


def _ghost_ref(dist, x, y, x_gbits, y_group, eps):
    y_group = jnp.asarray(y_group, jnp.int32)
    hit = _ghost_hit(_dists(dist, x, y) <= dist.thresh(eps),
                     _unpack_words(jnp.asarray(x_gbits, jnp.uint32)),
                     y_group, y_group >= 0)
    return jnp.sum(hit.astype(jnp.int32), axis=1), _pack_words(hit)


def nng_tile_ghost_pallas(x, y, x_gbits, y_group, eps: float, *,
                          tq: int = 256, tp: int = 512,
                          interpret: bool = False):
    """Ghost-ring L2 tile: x (q, d) visiting rows, y (p, d) local rows,
    x_gbits (q, mw) packed ghost-cell masks, y_group (p,) int32 cell ids
    (< 0 = padding) -> (cnt (q,), bits (q, p/32)).

    hit(i, j) = d2 <= eps² and y_group[j] >= 0 and x_gbits[i] has bit
    y_group[j]."""
    return _ghost(_L2, x, y, x_gbits, y_group, eps, tq, tp, interpret)


def nng_tile_ghost_ref(x, y, x_gbits, y_group, eps: float):
    """Pure-jnp oracle for the ghost L2 tile."""
    return _ghost_ref(_L2, x, y, x_gbits, y_group, eps)


def nng_tile_ghost_hamming_pallas(x, y, x_gbits, y_group, eps: float, *,
                                  tq: int = 128, tp: int = 256,
                                  interpret: bool = False):
    """Ghost-ring Hamming tile over packed uint32 rows."""
    return _ghost(_HAMMING, x, y, x_gbits, y_group, eps, tq, tp, interpret)


def nng_tile_ghost_hamming_ref(x, y, x_gbits, y_group, eps: float):
    """Pure-jnp oracle for the ghost Hamming tile."""
    return _ghost_ref(_HAMMING, x, y, x_gbits, y_group, eps)


def nng_tile_ghost_l1_pallas(x, y, x_gbits, y_group, eps: float, *,
                             tq: int = 128, tp: int = 256,
                             interpret: bool = False):
    """Ghost-ring L1 tile over fp32 rows."""
    return _ghost(_L1, x, y, x_gbits, y_group, eps, tq, tp, interpret)


def nng_tile_ghost_l1_ref(x, y, x_gbits, y_group, eps: float):
    """Pure-jnp oracle for the ghost L1 tile."""
    return _ghost_ref(_L1, x, y, x_gbits, y_group, eps)
