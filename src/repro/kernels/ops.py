"""Jit'd public wrappers around the Pallas kernels.

Handles: padding to tile multiples, masking of pad rows, dtype policy, and
the CPU test modes.

``pallas_mode()`` resolves to:
  - "compiled"  on a TPU backend, always. There ``REPRO_PALLAS`` may only be
                unset or "compiled": an override that would route the
                engine through the jnp oracles or the interpreter raises,
                so nothing can hide the device path.
  - off TPU (CPU test modes): ``REPRO_PALLAS`` = "interpret" runs the real
    kernels through the Pallas interpreter; "jnp" (the default) runs the
    pure-jnp oracles, which share the kernels' distance bodies.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import bits_epilogue as _be
from . import ref
from .bits_epilogue import NOCOL, SENTINEL
from .eps_count import eps_count_pallas
from .nng_tile import (_GBIG, _ghost_hit, _grouped_hit, _pack_words,
                       _to_lanes, _unpack_words)
from .pairwise_hamming import pairwise_hamming_pallas
from .pairwise_l2 import pairwise_sqdist_pallas
from .tree_frontier import _frontier_masks_float


def _resolve_metric(metric):
    """str | Metric -> the registry Metric (lazy import: the registry lives
    in ``repro.core.metrics``, which imports this package's raw kernels)."""
    from repro.core.metrics import get_metric
    return get_metric(metric)


def _mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "")
    if env not in ("", "interpret", "jnp", "compiled"):
        raise ValueError(f"REPRO_PALLAS={env!r}: expected interpret, jnp "
                         "or compiled")
    if jax.default_backend() == "tpu":
        if env not in ("", "compiled"):
            raise RuntimeError(
                f"REPRO_PALLAS={env!r} would route the engine around the "
                "compiled kernels on a TPU backend; jnp and interpret are "
                "CPU test modes")
        return "compiled"
    return env or "jnp"


def pallas_mode() -> str:
    """The resolved kernel execution mode ("compiled" | "interpret" |
    "jnp") — public accessor for consumers that must key on it (the device
    engine's program memoization, benchmark provenance)."""
    return _mode()


def _pad_rows(a: jnp.ndarray, mult: int, value=0):
    n = a.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return a, n
    pad = [(0, rem)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=value), n


def _pad_cols(a: jnp.ndarray, mult: int, value=0):
    d = a.shape[1]
    rem = (-d) % mult
    if rem == 0:
        return a
    return jnp.pad(a, [(0, 0), (0, rem)], constant_values=value)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sqdist_padded(x, y, interpret):
    return pairwise_sqdist_pallas(x, y, interpret=interpret)


def pairwise_sqdist(x, y) -> jnp.ndarray:
    """Squared L2 distances (q, p) fp32; pad rows get +inf-ish distance."""
    mode = _mode()
    if mode == "jnp":
        return ref.pairwise_sqdist_blas3_ref(x, y)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    tq, tp, td = 256, 256, 512
    xp, q = _pad_rows(x, tq)
    yp, p = _pad_rows(y, tp)
    xp = _pad_cols(xp, td)
    yp = _pad_cols(yp, td)
    out = _sqdist_padded(xp, yp, mode == "interpret")
    out = out[:q, :p]
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _hamming_padded(x, y, interpret):
    return pairwise_hamming_pallas(x, y, interpret=interpret)


def pairwise_hamming(x, y) -> jnp.ndarray:
    """Hamming distances between packed-uint32 bit rows -> (q, p) int32."""
    mode = _mode()
    if mode == "jnp":
        return ref.pairwise_hamming_ref(x, y)
    x = jnp.asarray(x, jnp.uint32)
    y = jnp.asarray(y, jnp.uint32)
    tq, tp, tw = 128, 128, 8
    xp, q = _pad_rows(x, tq)
    yp, p = _pad_rows(y, tp)
    xp = _pad_cols(xp, tw)
    yp = _pad_cols(yp, tw)
    out = _hamming_padded(xp, yp, mode == "interpret")
    return out[:q, :p]


def eps_count(x, y, eps: float) -> jnp.ndarray:
    """Per-query ε-neighbor counts against y (L2), fused (no (q,p) in HBM)."""
    mode = _mode()
    if mode == "jnp":
        return ref.eps_count_ref(x, y, eps)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    tq, tp = 256, 256
    xp, q = _pad_rows(x, tq)
    yp, p = _pad_rows(y, tp)
    mask = (jnp.arange(yp.shape[0]) < p).astype(jnp.int32)
    out = eps_count_pallas(xp, yp, mask, eps, interpret=(mode == "interpret"))
    return out[:q]


@functools.partial(
    jax.jit, static_argnames=("fn", "eps", "tq", "tp", "interpret"))
def _tile_padded_call(x, y, yv, *, fn, eps, tq, tp, interpret):
    return fn(x, y, yv, eps, tq=tq, tp=tp, interpret=interpret)


def nng_tile_bits(x, y, y_valid, eps: float, metric="euclidean"):
    """Fused ε-NNG tile: (cnt (q,), bits (q, ceil(p/32)) uint32).

    cnt[i] = |{j : valid[j] and d(x_i, y_j) <= eps}| (true-distance eps for
    every metric); bits packs the hit mask little-endian (column j -> word
    j // 32, bit j % 32). Pads to tile multiples internally; pad rows carry
    y_valid = 0, so bits beyond column p - 1 are always zero. On the
    compiled/interpret path the distance tile never leaves VMEM.

    ``metric`` is a registry name or ``Metric`` object. A metric without a
    tile kernel runs the generic pure-jnp fallback (comparable threshold
    over ``metric.cdist``) — slower, but the same edge set.
    """
    met = _resolve_metric(metric)
    mode = _mode()
    q = x.shape[0]
    p = y.shape[0]
    nw = -(-p // 32)
    yv = jnp.asarray(y_valid, jnp.int32)
    x = jnp.asarray(x, met.dtype)
    y = jnp.asarray(y, met.dtype)
    if met.tile_pallas is None or mode == "jnp":
        if met.tile_ref is not None:
            yp, _ = _pad_rows(y, 32)
            yvp, _ = _pad_rows(yv, 32)
            cnt, bits = met.tile_ref(x, yp, yvp, eps)
            return cnt, bits[:, :nw]
        hit = (met.cdist(x, y) <= met.comparable(eps)) & (yv != 0)[None, :]
        cnt = jnp.sum(hit.astype(jnp.int32), axis=1)
        if nw * 32 > p:
            hit = jnp.pad(hit, [(0, 0), (0, nw * 32 - p)])
        return cnt, _pack_words(hit)
    tq, tp = met.tile_shape(q, p)
    xp, _ = _pad_rows(x, tq)
    yp, _ = _pad_rows(y, tp)
    yvp, _ = _pad_rows(yv, tp)
    xp = _pad_cols(xp, met.col_mult)
    yp = _pad_cols(yp, met.col_mult)
    cnt, bits = _tile_padded_call(
        xp, yp, yvp, fn=met.tile_pallas, eps=float(eps), tq=tq, tp=tp,
        interpret=mode == "interpret")
    return cnt[:q], bits[:q, :nw]


def nng_tile_bits_pair(x, y, eps: float, metric="euclidean"):
    """Fused forward + mirror ε-NNG tile pair for one systolic ring round.

    The dense-round fallback of the tree flavor's split ring schedule: a
    round that rotates raw point tiles instead of forest tables still needs
    BOTH edge directions when its tile evaluates (the symmetry-halved ring
    emits forward edges for the local block and mirror edges for the
    visiting one). Returns ``(fcnt, fbits, rcnt, rbits)`` — the forward
    tile ``nng_tile_bits(x, y)`` and the mirror tile ``nng_tile_bits(y,
    x)`` with every row valid. Two kernel launches over shared operands
    (the scheduler is free to fuse or overlap them); no dense distance
    tile reaches HBM on either direction.
    """
    fcnt, fbits = nng_tile_bits(
        x, y, jnp.ones((y.shape[0],), jnp.int32), eps, metric=metric)
    rcnt, rbits = nng_tile_bits(
        y, x, jnp.ones((x.shape[0],), jnp.int32), eps, metric=metric)
    return fcnt, fbits, rcnt, rbits


@functools.partial(
    jax.jit, static_argnames=("fn", "eps", "tq", "tp", "interpret"))
def _grouped_padded_call(x, y, xg, yg, xid, yid, *, fn, eps, tq, tp,
                         interpret):
    return fn(x, y, xg, yg, xid, yid, eps, tq=tq, tp=tp, interpret=interpret)


def grouped_block_active(x_group, y_group, tq: int, tp: int):
    """Host-side mirror of the grouped kernel's block-skip rule.

    Reduces the (tile-padded) group arrays to per-tile valid-group
    [min, max] ranges and marks a (tq × tp) block live iff the ranges
    intersect. This is exactly the decision ``_group_ranges`` makes inside
    the Pallas kernel, so the (nqb, npb) bool map it returns is the ground
    truth for the tiles_scheduled / tiles_skipped counters (and for
    host-vs-device schedule parity tests)."""
    q = x_group.shape[0]
    p = y_group.shape[0]
    assert q % tq == 0 and p % tp == 0, (q, tq, p, tp)
    xg = x_group.reshape(q // tq, tq)
    yg = y_group.reshape(p // tp, tp)
    xmin = jnp.min(jnp.where(xg >= 0, xg, _GBIG), axis=1)
    xmax = jnp.max(jnp.where(xg >= 0, xg, -1), axis=1)
    ymin = jnp.min(jnp.where(yg >= 0, yg, _GBIG), axis=1)
    ymax = jnp.max(jnp.where(yg >= 0, yg, -1), axis=1)
    return ((xmin[:, None] <= ymax[None, :])
            & (ymin[None, :] <= xmax[:, None]))


def nng_tile_geometry(q: int, p: int, metric) -> tuple[int, int]:
    """The (tq, tp) block shape the fused tile wrappers (``nng_tile_bits``
    and ``nng_tile_bits_grouped``) use for given operand row counts — the
    single source of truth for tile tuning (now carried per-metric by the
    registry), exposed so callers can reproduce the grouped tile-block
    accounting (parity tests)."""
    return _resolve_metric(metric).tile_shape(q, p)


def nng_tile_bits_grouped(
    x, y, x_group, y_group, x_ids, y_ids, eps: float,
    metric="euclidean",
):
    """Group-aware fused ε-NNG tile for the landmark engine.

    hit(i, j) = d(x_i, y_j) <= eps  and  x_group[i] == y_group[j]  and both
    groups >= 0 (negative group = padding/invalid row) and
    x_ids[i] != y_ids[j] (structural self-pair exclusion, robust to fp32
    d(x, x) rounding past eps).

    Returns (cnt (q,), bits (q, ceil(p/32)) uint32, tiles_scheduled,
    tiles_skipped): exact per-row counts, the packed little-endian hit
    mask, and int32 scalar counters for the kernel's whole-block skip of
    all-padding / cross-cell (tq × tp) blocks. Callers should cell-sort
    rows so group ranges per tile are tight and the skip actually fires;
    skipping is conservative (a block is only skipped when NO same-group
    pair can exist in it), so results never depend on the row order.
    Pads to tile multiples internally (pad rows get group -1).

    ``metric`` is a registry name or ``Metric``; metrics without a grouped
    kernel run the generic pure-jnp fallback over ``metric.cdist``."""
    met = _resolve_metric(metric)
    mode = _mode()
    q = x.shape[0]
    p = y.shape[0]
    nw = -(-p // 32)
    tq, tp = met.tile_shape(q, p)
    xp, _ = _pad_rows(jnp.asarray(x, met.dtype), tq)
    yp, _ = _pad_rows(jnp.asarray(y, met.dtype), tp)
    xgp, _ = _pad_rows(jnp.asarray(x_group, jnp.int32), tq, value=-1)
    ygp, _ = _pad_rows(jnp.asarray(y_group, jnp.int32), tp, value=-1)
    xidp, _ = _pad_rows(jnp.asarray(x_ids, jnp.int32), tq, value=-1)
    yidp, _ = _pad_rows(jnp.asarray(y_ids, jnp.int32), tp, value=-1)
    active = grouped_block_active(xgp, ygp, tq, tp)
    scheduled = jnp.int32(active.size)
    skipped = scheduled - jnp.sum(active.astype(jnp.int32))
    if met.grouped_pallas is None or mode == "jnp":
        if met.grouped_ref is not None:
            cnt, bits = met.grouped_ref(xp, yp, xgp, ygp, xidp, yidp, eps)
        else:
            hit = _grouped_hit(
                met.cdist(xp, yp) <= met.comparable(eps), xgp[:, None],
                ygp[None, :], xidp[:, None], yidp[None, :])
            cnt = jnp.sum(hit.astype(jnp.int32), axis=1)
            bits = _pack_words(hit)
    else:
        xp = _pad_cols(xp, met.col_mult)
        yp = _pad_cols(yp, met.col_mult)
        cnt, bits = _grouped_padded_call(
            xp, yp, xgp, ygp, xidp, yidp, fn=met.grouped_pallas,
            eps=float(eps), tq=tq, tp=tp, interpret=mode == "interpret")
    return cnt[:q], bits[:q, :nw], scheduled, skipped


@functools.partial(
    jax.jit, static_argnames=("fn", "eps", "tq", "tp", "interpret"))
def _ghost_padded_call(x, y, gb, yg, *, fn, eps, tq, tp, interpret):
    return fn(x, y, gb, yg, eps, tq=tq, tp=tp, interpret=interpret)


def ghost_block_active(x_gbits, y_group, tq: int, tp: int):
    """Host-side mirror of the ghost kernel's block-skip rule.

    A (tq × tp) block is live iff some visiting row's packed ghost-cell
    mask has a bit inside the y tile's valid-cell [min, max] range —
    exactly the decision ``_ghost_active`` makes inside the Pallas kernel,
    so the (nqb, npb) bool map it returns is the ground truth for the
    tiles_scheduled / tiles_skipped counters on the ghost-ring path."""
    q = x_gbits.shape[0]
    p = y_group.shape[0]
    assert q % tq == 0 and p % tp == 0, (q, tq, p, tp)
    xb = _unpack_words(x_gbits)                       # (q, m_pad) bool
    m_pad = xb.shape[1]
    xany = jnp.any(xb.reshape(q // tq, tq, m_pad), axis=1)   # (nqb, m_pad)
    yg = y_group.reshape(p // tp, tp)
    ymin = jnp.min(jnp.where(yg >= 0, yg, _GBIG), axis=1)
    ymax = jnp.max(jnp.where(yg >= 0, yg, -1), axis=1)
    cells = jnp.arange(m_pad, dtype=jnp.int32)
    inrange = ((cells[None, :] >= ymin[:, None])
               & (cells[None, :] <= ymax[:, None]))   # (npb, m_pad)
    return jnp.any(xany[:, None, :] & inrange[None, :, :], axis=-1)


def nng_tile_bits_ghost(
    x, y, x_gbits, y_group, eps: float, metric="euclidean",
):
    """Ghost-ring fused ε-NNG tile for the landmark engine.

    hit(i, j) = d(x_i, y_j) <= eps  and  y_group[j] >= 0  and bit
    y_group[j] of x_gbits[i] is set — the slacked Lemma-1 ghost test
    evaluated from the visiting block's packed per-row cell masks instead
    of materialized ghost copies. A row's own cell bit is never set (the
    mask packer clears it), so same-cell pairs — including self pairs —
    are structurally excluded without an id test.

    Returns (cnt (q,), bits (q, ceil(p/32)) uint32, tiles_scheduled,
    tiles_skipped) with the same conventions as ``nng_tile_bits_grouped``;
    callers cell-sort y so the kernel's ghost-bit/cell-range block skip
    fires. Pads internally (x pad rows get all-zero masks, y pad rows get
    group -1).

    ``metric`` is a registry name or ``Metric``; metrics without a ghost
    kernel run the generic pure-jnp fallback over ``metric.cdist``."""
    met = _resolve_metric(metric)
    mode = _mode()
    q = x.shape[0]
    p = y.shape[0]
    nw = -(-p // 32)
    tq, tp = met.tile_shape(q, p)
    xp, _ = _pad_rows(jnp.asarray(x, met.dtype), tq)
    yp, _ = _pad_rows(jnp.asarray(y, met.dtype), tp)
    gbp, _ = _pad_rows(jnp.asarray(x_gbits, jnp.uint32), tq)
    ygp, _ = _pad_rows(jnp.asarray(y_group, jnp.int32), tp, value=-1)
    active = ghost_block_active(gbp, ygp, tq, tp)
    scheduled = jnp.int32(active.size)
    skipped = scheduled - jnp.sum(active.astype(jnp.int32))
    if met.ghost_pallas is None or mode == "jnp":
        if met.ghost_ref is not None:
            cnt, bits = met.ghost_ref(xp, yp, gbp, ygp, eps)
        else:
            hit = _ghost_hit(
                met.cdist(xp, yp) <= met.comparable(eps),
                _unpack_words(gbp), ygp, ygp >= 0)
            cnt = jnp.sum(hit.astype(jnp.int32), axis=1)
            bits = _pack_words(hit)
    else:
        xp = _pad_cols(xp, met.col_mult)
        yp = _pad_cols(yp, met.col_mult)
        cnt, bits = _ghost_padded_call(
            xp, yp, gbp, ygp, fn=met.ghost_pallas, eps=float(eps),
            tq=tq, tp=tp, interpret=mode == "interpret")
    return cnt[:q], bits[:q, :nw], scheduled, skipped


@functools.partial(
    jax.jit, static_argnames=("fn", "eps", "tq", "tn", "interpret"))
def _frontier_padded_call(q, c, rad, leaf, act, *, fn, eps, tq, tn,
                          interpret):
    return fn(q, c, rad, leaf, act, eps, tq=tq, tn=tn, interpret=interpret)


def tree_frontier_step(q, c, rad, leaf, act_bits, eps: float,
                       metric="euclidean"):
    """One level of the batched cover-tree traversal, fused.

    q (nq, d) queries; c (N, d) level-node coords; rad (N,) fp32 radii;
    leaf (N,) int32 leaf flags; act_bits (nq, N/32) packed active mask
    (N % 32 == 0 — the flat-tree builder guarantees it). Returns
    (emit_bits, expand_bits), each (nq, N/32) uint32: nodes whose DFS leaf
    range joins the query's neighbor set, and nodes whose children enter
    the next level's frontier (see ``repro.kernels.tree_frontier`` for the
    decision rules and fp32 slack policy). Pads to tile multiples
    internally; pad rows/columns are inactive and emit nothing.

    ``metric`` is a registry name or ``Metric``; metrics without a
    frontier kernel run a generic jnp fallback (true distances over
    ``metric.cdist`` + the shared float decision epilogue — conservative
    slack, exact at the leaves).
    """
    met = _resolve_metric(metric)
    mode = _mode()
    nq = q.shape[0]
    N = c.shape[0]
    assert N % 32 == 0, N
    nw = N // 32
    rad = jnp.asarray(rad, jnp.float32)
    leaf = jnp.asarray(leaf, jnp.int32)
    act_bits = jnp.asarray(act_bits, jnp.uint32)
    q = jnp.asarray(q, met.dtype)
    c = jnp.asarray(c, met.dtype)
    if met.frontier_pallas is None or mode == "jnp":
        if met.frontier_ref is not None:
            return met.frontier_ref(q, c, rad, leaf, act_bits, eps)
        active = _unpack_words(act_bits)
        d = met.true(met.cdist(q, c))
        emit, expand = _frontier_masks_float(d, rad[None, :], leaf[None, :],
                                             active, eps)
        return _pack_words(emit), _pack_words(expand)
    tq, tn = met.tile_shape(nq, N)
    qp, _ = _pad_rows(q, tq)
    actp, _ = _pad_rows(act_bits, tq)
    cp, _ = _pad_rows(c, tn)
    radp, _ = _pad_rows(rad, tn)
    leafp, _ = _pad_rows(leaf, tn)
    # node-axis padding extends the WORD axis of the packed masks
    actp = jnp.pad(actp, [(0, 0), (0, tn * ((N + tn - 1) // tn) // 32 - nw)])
    qp = _pad_cols(qp, met.col_mult)
    cp = _pad_cols(cp, met.col_mult)
    emit, expand = _frontier_padded_call(
        qp, cp, radp, leafp, actp, fn=met.frontier_pallas, eps=float(eps),
        tq=tq, tn=tn, interpret=mode == "interpret")
    return emit[:nq, :nw], expand[:nq, :nw]


# ---------------------------------------------------------------------------
# fused result epilogues (packed bitmask words -> neighbor-id tables)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "tq", "interpret"))
def _bits_cols_padded(bits, *, k, tq, interpret):
    return _be.bits_to_cols_pallas(bits, k, tq=tq, interpret=interpret)


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def bits_to_cols_scanned(bits, k: int):
    """``bits_to_cols`` and what its kernel scans: -> ((m, k) int32
    columns, (2,) float32 [(slot, chunk) pairs scanned, pairs a full scan
    takes]). Each 128-row block's slot loop stops at its largest row count
    and ranks each group of 8 slots over the 128-word chunks that can hold
    one of them (``bits_epilogue.bits_cols_bounds``); the pairs follow
    from those bounds alone, so every mode reports the same."""
    bits = jnp.asarray(bits, jnp.uint32)
    mode = _mode()
    m = bits.shape[0]
    tq = 128 if m >= 128 else _round_up(max(m, 1), 8)
    kp = _round_up(k, 8)
    bp, _ = _pad_rows(bits, tq)
    bp = _pad_cols(bp, 128)          # zero words select nothing
    full = float(bp.shape[0] // tq * kp * (bp.shape[1] // 128))
    if mode == "jnp":
        cols = _be.bits_to_cols_ref(bits, k)
        _, scanned = _be.bits_cols_bounds(_to_lanes(bp), kp, tq)
    else:
        out, scanned = _bits_cols_padded(bp, k=kp, tq=tq,
                                         interpret=mode == "interpret")
        cols = out[:m, :k]
    return cols, jnp.stack([scanned, jnp.float32(full)])


def bits_to_cols(bits, k: int) -> jnp.ndarray:
    """(m, W) packed uint32 hit words -> (m, k) int32: each row's k lowest
    set column indices, ascending, ``NOCOL``-padded — the fused epilogue
    that replaced the two chained ``lax.top_k`` passes. Deterministic (a
    rank computation, no value sort), so every mode is bit-identical."""
    return bits_to_cols_scanned(bits, k)[0]


def bits_to_ids_scanned(bits, id0, k: int):
    """Hit words over a CONTIGUOUS id block starting at ``id0`` -> ((m, k)
    int32 neighbor ids, ascending, SENTINEL-padded; the epilogue's scan
    pairs as ``bits_to_cols_scanned`` gives them)."""
    cols, scan = bits_to_cols_scanned(bits, k)
    return jnp.where(cols < jnp.int32(NOCOL), id0 + cols,
                     jnp.int32(SENTINEL)), scan


def bits_to_ids(bits, id0, k: int) -> jnp.ndarray:
    """``bits_to_ids_scanned`` without the scan pairs."""
    return bits_to_ids_scanned(bits, id0, k)[0]


def bits_to_gathered_ids(bits, ids_row, k: int) -> jnp.ndarray:
    """Hit words whose columns index an arbitrary id row -> (m, k) int32
    neighbor ids, sorted ascending, SENTINEL-padded. The gather can permute
    id order, so a small (m, k) sort restores it — k, not the tile width."""
    cols = bits_to_cols(bits, k)
    p = ids_row.shape[0]
    ids = jnp.where(cols < p,
                    jnp.take(ids_row, jnp.minimum(cols, p - 1)),
                    jnp.int32(SENTINEL))
    return jnp.sort(ids, axis=-1)


@functools.partial(jax.jit, static_argnames=("tq", "tn", "interpret"))
def _leaf_pack_padded(delta, lid, qid, *, tq, tn, interpret):
    return _be.leaf_range_pack_pallas(delta, lid, qid, tq=tq, tn=tn,
                                      interpret=interpret)


def leaf_range_pack(delta, leaf_ids, qids):
    """Fused tree-traversal leaf epilogue: ±1 range deltas over DFS leaf
    slots -> (cnt (nq,), bits (nq, NL/32) uint32) packed cover mask, with
    leaf-slot validity and structural self-pair exclusion applied — the
    dense (nq, NL) cover mask never reaches HBM on the kernel path.

    ``delta`` may carry trailing overflow columns (the traversal scatters
    hi = NL there); only the first ``len(leaf_ids)`` columns participate.
    ``len(leaf_ids)`` % 32 == 0 (the flat-tree padding invariant)."""
    nl = leaf_ids.shape[0]
    assert nl % 32 == 0, nl
    delta = jnp.asarray(delta, jnp.int32)[:, :nl]
    leaf_ids = jnp.asarray(leaf_ids, jnp.int32)
    qids = jnp.asarray(qids, jnp.int32)
    mode = _mode()
    if mode == "jnp":
        return _be.leaf_range_pack_ref(delta, leaf_ids, qids)
    nq = delta.shape[0]
    tq = 128 if nq >= 128 else _round_up(max(nq, 1), 8)
    # leaf slots pad to whole 512-slot blocks (one block when smaller); pad
    # slots carry zero deltas and SENTINEL ids, so they never cover
    tn = 512 if nl >= 512 else _round_up(nl, 128)
    dp, _ = _pad_rows(delta, tq)
    dp = _pad_cols(dp, tn)
    lp, _ = _pad_rows(leaf_ids, tn, value=SENTINEL)
    qp, _ = _pad_rows(qids, tq, value=-1)
    cnt, bits = _leaf_pack_padded(dp, lp, qp, tq=tq, tn=tn,
                                  interpret=mode == "interpret")
    return cnt[:nq], bits[:nq, :nl // 32]


@jax.jit
def rowwise_sqdist(x, y):
    """Row-aligned squared L2: x (n, d), y (n, d) -> (n,) fp32."""
    diff = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.sum(diff * diff, axis=-1)


@jax.jit
def rowwise_hamming(x, y):
    """Row-aligned Hamming over packed words -> (n,) int32."""
    xor = jnp.bitwise_xor(x, y)
    return jnp.sum(jax.lax.population_count(xor).astype(jnp.int32), axis=-1)


# NOTE: metric dispatch moved to the registry in ``repro.core.metrics`` —
# every wrapper above resolves names through it, and new metrics register
# there without touching this module.
