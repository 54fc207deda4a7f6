"""Fused result epilogues over packed survivor bitmasks.

The engines' inner loops emit packed little-endian uint32 hit masks
(column c -> word c // 32, bit c % 32 — the ``_pack_words`` idiom). Two
epilogues turn those words into the SENTINEL-padded neighbor-id tables the
drivers consume, without the dense intermediates the pre-kernel path
materialized:

``bits_to_cols``  (m, W) uint32 -> (m, k) int32: the k lowest set column
    indices of each row, ascending, ``NOCOL``-padded. Replaces the two
    chained ``lax.top_k`` passes (word occupancy -> candidate columns) of
    the old extraction — the selection is a rank computation over word
    popcounts, so the kernel reads each word once and never sorts.

``leaf_range_pack``  (delta (nq, >=NL) int32 range-deltas, leaf_ids (NL,),
    qids (nq,)) -> (cnt (nq,), bits (nq, NL/32) uint32): fuses the tree
    traversal's emitted-leaf-range reconstruction — running prefix sum of
    the ±1 deltas, the >0 cover test, leaf-slot validity, structural
    self-pair exclusion — with the bit packing and the per-row popcount,
    so the dense (nq, NL) cover mask never exists outside registers/VMEM.

Both selections are deterministic functions of the input words (no value
sorts, no tie-breaking), so the pallas kernel, the interpret path and the
jnp oracle are bit-identical — and identical to the ``top_k`` extraction
they replace, whose output spec ("k smallest hit columns, ascending,
padded") is the same function.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .nng_tile import (HIGHEST, _from_lanes, _pack_words, _pack_words_t,
                       _to_lanes, _unpack_words)

NOCOL = 2**30        # "no more hit columns" padding (device._NOCOL)
SENTINEL = 2**31 - 1  # neighbor-table padding id


# ---------------------------------------------------------------------------
# bitmask -> sorted column ids
# ---------------------------------------------------------------------------

def bits_to_cols_ref(bits, k: int):
    """Pure-jnp oracle: (m, W) uint32 -> (m, k) int32 lowest set columns,
    ascending, NOCOL-padded. The rank of a set column (cumulative popcount
    of all lower columns) IS its output slot; ranks >= k scatter-drop."""
    m, w = bits.shape
    cols = _unpack_words(bits)                        # (m, 32W) bool
    ci = cols.astype(jnp.int32)
    rank = jnp.cumsum(ci, axis=1) - ci                # exclusive bit rank
    slot = jnp.where(cols, rank, k)                   # unset bits -> dropped
    col = jnp.broadcast_to(
        jnp.arange(32 * w, dtype=jnp.int32)[None, :], (m, 32 * w))
    row = jnp.broadcast_to(jnp.arange(m)[:, None], (m, 32 * w))
    out = jnp.full((m, k), NOCOL, jnp.int32)
    return out.at[row, slot].set(col, mode="drop")


def _select_nth_set_bit(word, r):
    """word int32/uint32, r int32 (0-based, same shape) -> bit position of
    the r-th set bit of each word, by a 5-step binary descent over
    popcounts of the low half of the remaining window (2-D VPU ops only).
    Meaningless when the word has <= r set bits; callers mask those."""
    word = jnp.asarray(word, jnp.int32)
    pos = jnp.zeros_like(r)
    for s in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            jax.lax.shift_right_logical(word, pos) & ((1 << s) - 1))
        up = r >= low
        r = jnp.where(up, r - low, r)
        pos = jnp.where(up, pos + s, pos)
    return pos


_CHUNK = 128     # word rows per triangular prefix-sum block
_GROUP = 8       # output slots ranked together: one sublane tile of out


def bits_cols_bounds(words_t, k: int, tq: int):
    """What each tq-row block's prefix counts prove about its slots:
    (W, m) int32 lane-major words (``_to_lanes``; m % tq == 0,
    W % 128 == 0, k % 8 == 0) -> (bounds, scanned).

    Chunk c of 128 words can hold output slot j of some row of the block
    only if lo_c <= j < min(hi_c, k), where lo_c is the fewest set bits any
    row has before the chunk and hi_c the most any row has through it.
    ``bounds`` (m // tq, 1, 2C + 1) int32, C = W // 128: g0_c for each
    chunk, then g1_c, then the 8-slot groups below the block's largest row
    count. The kernel reads chunk c for the groups [g0_c, g1_c), those that
    meet its slots (none where it holds none). ``scanned`` (float32): the
    (slot, chunk) pairs the kernel scans, 8 x the sum of g1_c - g0_c over
    blocks and chunks."""
    w, m = words_t.shape
    nc, nb = w // _CHUNK, m // tq
    # set bits per (chunk, row): a 0/1 chunk-membership contraction on the
    # MXU, which reads the words once (popcounts <= 32, sums <= 4096: exact)
    member = (jax.lax.broadcasted_iota(jnp.int32, (nc, w), 1) // _CHUNK
              == jax.lax.broadcasted_iota(jnp.int32, (nc, w), 0))
    pc = jax.lax.dot_general(
        member.astype(jnp.bfloat16),
        jax.lax.population_count(words_t).astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    cumi = jnp.cumsum(pc, axis=0)
    lo = jnp.min((cumi - pc).reshape(nc, nb, tq), axis=2).T
    top = jnp.minimum(jnp.max(cumi.reshape(nc, nb, tq), axis=2).T, k)
    live = lo < top
    g0 = jnp.where(live, lo // _GROUP, 0)
    g1 = jnp.where(live, -(-top // _GROUP), 0)
    groups = -(-top[:, -1:] // _GROUP)
    return (jnp.concatenate([g0, g1, groups], axis=1)[:, None, :],
            jnp.float32(_GROUP) * jnp.sum((g1 - g0).astype(jnp.float32)))


def _bits_cols_kernel(bnd_ref, bits_ref, out_ref, cume_ref, cumi_ref):
    """Word-major block: bits_ref (W, TQ) int32, one row per lane;
    out_ref (K, TQ); bnd_ref (1, 2C + 1) the block's ``bits_cols_bounds``
    in SMEM. W % 128 == 0 (the wrapper pads with zero words), K % 8 == 0."""
    w, tq = bits_ref.shape
    k = out_ref.shape[0]
    nc = w // _CHUNK
    # inclusive/exclusive per-word set-bit prefix down the word axis: a
    # lower-triangular 0/1 MXU contraction per 128-word block plus a carry
    # (popcounts <= 32 and prefixes < 2^24 are exact in bf16 x bf16 -> f32)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 1)
             ).astype(jnp.bfloat16)
    carry = jnp.zeros((1, tq), jnp.float32)
    for c in range(nc):
        rows = slice(c * _CHUNK, (c + 1) * _CHUNK)
        pc = jax.lax.population_count(bits_ref[rows, :]).astype(jnp.float32)
        cs = jax.lax.dot_general(
            lower, pc.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + carry
        cumi_ref[rows, :] = cs.astype(jnp.int32)
        cume_ref[rows, :] = (cs - pc).astype(jnp.int32)
        carry = cs[_CHUNK - 1:, :]
    total = carry.astype(jnp.int32)                   # (1, TQ)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, tq), 0)
    # slots at or past the block's largest count hold no column of any row
    out_ref[...] = jnp.full(out_ref.shape, NOCOL, jnp.int32)

    def group(g):
        # output slot j lives in the unique word with cume <= j < cumi
        # (an unsigned 0 <= j - cume < its popcount); masked sums per
        # 8-word tile recover that word and 32 x its index + the rank of j
        # in it — no sort, no gather. Only the chunks whose bounds meet
        # this group's slots are read; the others would add nothing.
        j0 = g * _GROUP

        def scan(c, acc):
            acc = list(acc)
            for r in range(_CHUNK // 8):
                base = pl.multiple_of(c * _CHUNK + r * 8, 8)
                rows = pl.ds(base, 8)
                ce, word = cume_ref[rows, :], bits_ref[rows, :]
                held = (cumi_ref[rows, :] - ce).astype(jnp.uint32)
                w32 = (sub + base) * 32
                for t in range(_GROUP):
                    rank = j0 + t - ce
                    hit = rank.astype(jnp.uint32) < held
                    acc[2 * t] += jnp.where(hit, w32 + rank, 0)
                    acc[2 * t + 1] += jnp.where(hit, word, 0)
            return tuple(acc)

        def chunk(c, acc):
            meets = (bnd_ref[0, c] <= g) & (g < bnd_ref[0, nc + c])
            return jax.lax.cond(meets, lambda a: scan(c, a), lambda a: a, acc)

        zero = jnp.zeros((8, tq), jnp.int32)
        acc = jax.lax.fori_loop(0, nc, chunk, (zero,) * (2 * _GROUP))
        for t in range(_GROUP):
            at, word = (jnp.sum(a, axis=0, keepdims=True)
                        for a in acc[2 * t:2 * t + 2])
            j = j0 + t
            col = (at & ~31) + _select_nth_set_bit(word, at & 31)
            out_ref[pl.ds(j, 1), :] = jnp.where(j < total, col,
                                                jnp.int32(NOCOL))

    def group_if_held(g, carry):
        pl.when(g < bnd_ref[0, 2 * nc])(lambda: group(g))
        return carry

    jax.lax.fori_loop(0, k // _GROUP, group_if_held, 0)


def bits_to_cols_pallas(bits, k: int, *, tq: int = 128,
                        interpret: bool = False):
    """Pallas kernel: same contract as ``bits_to_cols_ref``, and the
    (slot, chunk) pairs it scans -> ((m, k) int32, float32). One program
    per tq-row block; the rows ride the lanes (word-major layout) and the
    output slots are ranked eight at a time from the row block's per-word
    prefix counts in VMEM, each group over the 128-word chunks that can
    hold it, up to the block's largest row count (``bits_cols_bounds``,
    one XLA pass over the words, in SMEM). m % tq == 0, W % 128 == 0,
    k % 8 == 0 (wrappers pad)."""
    m, w = bits.shape
    assert m % tq == 0 and w % _CHUNK == 0 and k % _GROUP == 0, (m, tq, w, k)
    words_t = _to_lanes(bits)
    bounds, scanned = bits_cols_bounds(words_t, k, tq)
    nbnd = bounds.shape[-1]
    out_t = pl.pallas_call(
        _bits_cols_kernel,
        grid=(m // tq,),
        in_specs=[pl.BlockSpec((None, 1, nbnd), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((w, tq), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, tq), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((w, tq), jnp.int32)] * 2,
        interpret=interpret,
    )(bounds, words_t)
    return out_t.T, scanned


# ---------------------------------------------------------------------------
# leaf-range delta -> packed cover bits
# ---------------------------------------------------------------------------

def leaf_range_pack_ref(delta, leaf_ids, qids, sentinel=SENTINEL):
    """Pure-jnp oracle. delta (nq, NL) int32 (±1 range deltas over leaf
    slots), leaf_ids (NL,) int32 global ids (sentinel = padding), qids
    (nq,) int32 query ids -> (cnt (nq,), bits (nq, NL/32) uint32)."""
    cover = jnp.cumsum(delta, axis=1) > 0
    cover &= (leaf_ids != sentinel)[None, :]
    cover &= qids[:, None] != leaf_ids[None, :]
    cnt = jnp.sum(cover.astype(jnp.int32), axis=1)
    return cnt, _pack_words(cover)


def _leaf_pack_kernel(delta_ref, lid_ref, qid_ref, cnt_ref, bits_ref,
                      carry_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    d = delta_ref[...].astype(jnp.float32)            # (TQ, TN) exact ints
    tn = d.shape[1]
    # within-block inclusive prefix sum via a lower-triangular MXU
    # contraction, produced transposed (leaf slots down the sublanes):
    # csum_t[n, q] = sum_{m <= n} d[q, m]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (tn, tn), 1)
             ).astype(jnp.float32)
    csum_t = jax.lax.dot_general(
        lower, d, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=HIGHEST) + carry_ref[...]
    carry_ref[...] = csum_t[tn - 1:, :]
    lid = lid_ref[...]                                # (TN, 1)
    cover = ((csum_t > 0.5) & (lid != SENTINEL)
             & (qid_ref[...] != lid))                 # (TN, TQ)
    bits_ref[...] = _pack_words_t(cover)
    cnt_ref[...] += jnp.sum(cover.astype(jnp.int32), axis=0, keepdims=True)


def leaf_range_pack_pallas(delta, leaf_ids, qids, *, tq: int = 128,
                           tn: int = 512, interpret: bool = False):
    """Pallas kernel: same contract as ``leaf_range_pack_ref``. The leaf
    axis is the sequential (minor) grid dimension; a (1, tq) VMEM scratch
    carries the running prefix sum across column blocks, and the cnt block
    accumulates in place across them. nq % tq == 0, NL % tn == 0,
    tn % 256 == 0 or tn == NL (wrappers pad)."""
    nq, nl = delta.shape
    assert nq % tq == 0 and nl % tn == 0 and tn % 32 == 0, (nq, tq, nl, tn)
    cnt, bits_t = pl.pallas_call(
        _leaf_pack_kernel,
        grid=(nq // tq, nl // tn),
        in_specs=[
            pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tq), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq), lambda i, j: (0, i)),
            pl.BlockSpec((tn // 32, tq), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nq), jnp.int32),
            jax.ShapeDtypeStruct((nl // 32, nq), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, tq), jnp.float32)],
        interpret=interpret,
    )(delta, jnp.asarray(leaf_ids, jnp.int32)[:, None],
      jnp.asarray(qids, jnp.int32)[None, :])
    return cnt[0], _from_lanes(bits_t)
