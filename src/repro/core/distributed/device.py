"""Device (SPMD) ε-graph engine: the paper's algorithms as shard_map programs.

This is the TPU-native, *sparsity-aware* realization described in DESIGN.md
§3:

- ``systolic_nng`` — Algorithm 4. Point blocks rotate around the mesh ring
  via ``jax.lax.ppermute`` inside a ``fori_loop``. Each ring step runs the
  fused bitmask tile kernel (``repro.kernels.nng_tile_bits``): distances are
  computed in VMEM on the MXU, thresholded there, and only a bit-packed
  adjacency mask (n_loc × n_loc/32 uint32, 128× smaller than the fp32
  distance tile) plus exact per-row counts reach HBM. Neighbor ids are then
  extracted by the fused bitmask→ids epilogue kernel
  (``repro.kernels.bits_epilogue`` via ``ops.bits_to_ids``): output slots
  are ranked directly from word popcounts in VMEM — no ``top_k`` pass and
  no sort ever touch an n_loc² array. The fp32 distance tile is never
  materialized in HBM on this path.

  Block-summary pruning (the paper's sparsity claim): each shard computes a
  bounding center + radius for its block once up front and all-gathers the
  (nranks, d+1) summary table. A ring round whose partner block satisfies
  d(center_i, center_j) > r_i + r_j + eps cannot contain any ε-pair
  (triangle inequality), so the tile evaluation is skipped entirely via
  ``lax.cond`` — only the collective-permute runs, keeping the ring flowing.
  A per-rank ``tiles_skipped`` counter reports the pruning rate.

  Ring schedule: both ring bodies are double-buffered — round r+1's
  ``ppermute`` is issued before round r's tile evaluation consumes the
  already-received block, so the collective genuinely overlaps the kernels
  (the reference implementation's MPI_Irecv/MPI_Isend-around-compute
  discipline) at the cost of one extra priming hop. The
  tree flavor additionally runs a SPLIT ring schedule: per round, the host
  planner (``plan_ring_schedule``) statically chooses between rotating the
  levelized forest tables (dense rounds — in-tree pruning pays for the
  ~(d+6)·L·N·4-byte hop) and rotating raw point tiles with on-the-fly
  dense bitmask evaluation (sparse / ring-wide-skipped rounds — the
  d·n_loc·4-byte hop is the cheapest ring-bytes schedule available).

- ``landmark_nng`` — Algorithms 5 + 6. Voronoi assignment against replicated
  centers (one (n_loc × m) MXU tile), cell coalescing and ε-ghost exchange as
  capacity-padded ``jax.lax.all_to_all`` (the MPI_Alltoallv adaptation). The
  coalesce (W) and ghost (G) buffers are then *cell-sorted* (padding rows
  clustered at the end, cells contiguous) and the intra-cell W×W and ghost
  G×W phases run the group-aware fused bitmask tile kernel
  (``repro.kernels.nng_tile_bits_grouped``): the ε-threshold, cell-id
  equality, validity, and self-pair exclusion are all applied in VMEM and
  only packed uint32 adjacency words + exact per-row counts reach HBM — no
  dense (nranks·cap)² distance tile or boolean mask is ever materialized.
  Whole tile blocks that are all-padding or cross-cell are skipped inside
  the kernel (group [min, max] range disjointness over the sorted buffers),
  reported per rank via ``tiles_skipped`` / ``tiles_scheduled`` counters
  like the systolic engine's. Neighbor ids are recovered from the bitmask
  by the same fused epilogue as the ring path (``ops.bits_to_gathered_ids``
  — rank-select in VMEM, then a gather through the cell-sorted id table),
  and the Lemma-1 ghost test carries a scale-aware fp32 slack so boundary
  ghosts are never dropped.

Everything is shape-static: neighbor lists are (·, K) id arrays padded with
INT32_MAX, counts are exact, and overflow flags report capacity misses so the
host driver can re-plan (grow K / capacities) and re-run — exactness is
preserved end-to-end. Both engines sit behind the shared plan → run → grow
driver in ``repro.nng`` (``build_nng`` is the public entry point;
``systolic_nng`` / ``landmark_nng`` remain as deprecated tuple-API
wrappers over the internal ``systolic_run`` / ``landmark_run``). Metrics
are resolved through ``repro.core.metrics`` — distance arithmetic, block
summaries and slack policies are registry hooks, never engine branches.

Shapes are planned host-side by ``plan_landmark`` (the "indexing phase"):
capacity knobs are static compile-time values, as they would be in a real
deployment where the planner runs on a data sample.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map as _shard_map
from repro.core.metrics import get_metric
from repro.kernels import (nng_tile_bits, nng_tile_bits_ghost,
                           nng_tile_bits_grouped, nng_tile_bits_pair,
                           nng_tile_geometry, tree_frontier_step)
from repro.kernels.nng_tile import _pack_words, _unpack_words
from repro.kernels.ops import pallas_mode as _pallas_mode
from repro.obs import span
# fused bitmask→ids epilogues (repro.kernels.bits_epilogue): rank-selection
# over word popcounts in VMEM replaces the old two-pass ``lax.top_k``
# extraction — same contract (k smallest hit columns/ids, ascending,
# padded), bit-identical output, no dense candidate array
from repro.kernels.ops import (bits_to_ids_scanned as _bits_to_ids_op,
                               bits_to_gathered_ids as _bits_to_gathered_ids,
                               leaf_range_pack as _leaf_range_pack)

SENTINEL = jnp.int32(2**31 - 1)


class DeviceForest(NamedTuple):
    """Device-resident levelized cover-tree forest (one rank's tables, or
    rank-stacked with a leading axis — see ``flat_tree.stack_device_forests``).

    Shapes (single rank): coords (L, N, d); radius/cell/leaf/parent/
    leaf_lo/leaf_hi (L, N); leaf_ids (n_leaf,) global point ids in forest
    DFS order, SENTINEL-padded.
    """

    coords: jax.Array
    radius: jax.Array
    cell: jax.Array
    leaf: jax.Array
    parent: jax.Array
    leaf_lo: jax.Array
    leaf_hi: jax.Array
    leaf_ids: jax.Array

    @classmethod
    def from_tables(cls, tables: dict) -> "DeviceForest":
        return cls(**{k: jnp.asarray(v) for k, v in tables.items()})


# ---------------------------------------------------------------------------
# tile distance math (jnp; XLA lowers the euclidean path onto the MXU —
# repro.kernels provides the hand-tiled Pallas equivalents for TPU hot spots)
# ---------------------------------------------------------------------------

def _on_ring(mesh: Mesh, axis: str, a, dtype=None):
    """Place ``a`` block-sharded along its leading axis over the ring, one
    block per device (not whole on the default device for the jitted
    program to reshard). Device arrays move device to device."""
    a = (jnp.asarray(a, dtype) if isinstance(a, jax.Array)
         else np.asarray(a, dtype))
    return jax.device_put(a, NamedSharding(mesh, P(axis)))


def tile_cdist(x, y, metric):
    """Comparable distances between tiles — the registered metric's device
    ``cdist`` (sq-L2 fp32 for euclidean, counts for hamming, |diff| sums
    for manhattan, whatever a user metric declares)."""
    return get_metric(metric).cdist(x, y)


# The systolic bodies name their stages with ``jax.named_scope`` ("nng.tile",
# "nng.traverse", "nng.epilogue", "nng.merge", "nng.ring",
# "nng.mirror_home"): the names land in each operation's ``op_name``
# metadata, which a profile viewer shows, and rename no HLO instruction —
# the kernels' custom calls keep the names of their jitted wrappers.

def _merge_ids(buf, new_ids):
    """Merge two per-row sorted id sets, keeping the K smallest (dedup-free:
    ids are globally unique per source)."""
    with jax.named_scope("nng.merge"):
        k = buf.shape[-1]
        cat = jnp.concatenate([buf, new_ids], axis=-1)
        return jnp.sort(cat, axis=-1)[..., :k]


def _bits_to_ids_scanned(bits, id0, k_cap):
    """The bitmask epilogue: hit words -> ((m, k_cap) ids from ``id0``,
    (2,) float32 [(slot, chunk) pairs its kernel scanned, pairs a full
    scan takes])."""
    with jax.named_scope("nng.epilogue"):
        return _bits_to_ids_op(bits, id0, k_cap)


def _bits_to_ids(bits, id0, k_cap):
    """The bitmask epilogue: hit words -> (m, k_cap) ids from ``id0``."""
    return _bits_to_ids_scanned(bits, id0, k_cap)[0]


def _ring_permute(arrays, axis, perm, scope="nng.ring"):
    """One ``ppermute`` of each array, under the ring's scope."""
    with jax.named_scope(scope):
        return tuple(jax.lax.ppermute(a, axis, perm) for a in arrays)




def _popcount_rows(bits):
    """Exact per-row hit counts from the packed bitmask -> (m,) int32."""
    return jnp.sum(jax.lax.population_count(bits).astype(jnp.int32), axis=-1)


# Each traversal level holds dense (queries x level width) masks and a
# (queries x leaf slots) range-delta accumulator: queries are traversed in
# blocks that keep every such array under this many elements (512 MiB of
# int32). At 2^17 queries x 2^17 leaves an unblocked delta alone is 64 GiB.
_TRAVERSE_BLOCK_ELEMS = 1 << 27


def tree_traverse(qp, qids, qcells, forest: DeviceForest, eps, k_cap: int,
                  metric: str, qghost_bits=None):
    """Level-synchronous batched cover-tree traversal on device.

    A ``lax.scan`` over the forest's levels. Each level:

      1. active mask (jnp): a node is active for a query iff its parent's
         expand bit survived the previous level, the slot is valid, and the
         node's cell matches the query's cell (the in-cell scoping that
         makes cells the level-1 cover). With ``qghost_bits`` (the ring
         ghost path: (nq, ceil(m/32)) packed per-query cell sets from the
         slacked Lemma-1 test) the equality test generalizes to membership
         — a node is in scope iff its cell's bit is set for the query —
         so one traversal visits every locally-owned cell the visiting
         point ghosts into; ``qcells`` is ignored (pass ``None``).
      2. frontier kernel (``repro.kernels.tree_frontier``): fused distance
         + {emit, expand} decisions, packed survivor bitmasks; blocks with
         no active pair are skipped without touching the MXU.
      3. leaf-range emission: emitted nodes contribute their whole DFS leaf
         range via a ±1 scatter into a per-query range-delta accumulator —
         NO per-leaf distances for fully-included balls. One cumsum at the
         end turns the deltas into the per-query leaf coverage mask.

    Self pairs are excluded by global-id inequality (qids vs leaf_ids),
    mirroring the grouped tile kernel's structural exclusion. Large query
    sets run as a ``lax.map`` over power-of-two query blocks sized by
    ``_TRAVERSE_BLOCK_ELEMS``; padding queries have no scope, and no
    query's result depends on the blocking.

    Returns (nbrs (nq, k_cap) sorted SENTINEL-padded ids, cnt (nq,) exact
    counts, dists_evaluated, nodes_pruned) — the counters are float32
    scalars (exact below 2^24, fp32-approximate beyond; int32 would wrap
    at paper scale) with the same definitions the host ``TraversalStats``
    mirrors: frontier pairs whose distance was computed, and frontier
    pairs whose subtree was discarded after that single distance.
    """
    nq = qp.shape[0]
    _, N = forest.radius.shape
    width = max(N, forest.leaf_ids.shape[0] + 1)
    qb = max(_TRAVERSE_BLOCK_ELEMS // width, 128)
    qb = 1 << (qb.bit_length() - 1)
    ghost = qghost_bits is not None
    # padding query rows: cell -1 / empty ghost set, so nothing is in scope
    scope, fill = ((qghost_bits, 0) if ghost
                   else (jnp.asarray(qcells, jnp.int32), -1))
    if nq <= qb:
        return _traverse_block(qp, qids, scope, ghost, forest, eps, k_cap,
                               metric)
    nb = -(-nq // qb)

    def blocks(a, value):
        a = jnp.concatenate(
            [a, jnp.full((nb * qb - nq,) + a.shape[1:], value, a.dtype)])
        return a.reshape((nb, qb) + a.shape[1:])

    nbrs, cnt, dists, pruned = jax.lax.map(
        lambda b: _traverse_block(*b, ghost, forest, eps, k_cap, metric),
        (blocks(qp, 0), blocks(qids, -1), blocks(scope, fill)))
    return (nbrs.reshape(nb * qb, k_cap)[:nq], cnt.reshape(-1)[:nq],
            jnp.sum(dists), jnp.sum(pruned))


def _traverse_block(qp, qids, scope, ghost: bool, forest: DeviceForest, eps,
                    k_cap: int, metric: str):
    """``tree_traverse`` over one query block; ``scope`` is the (nq,) cell
    ids, or with ``ghost`` the (nq, ceil(m/32)) packed ghost cell sets."""
    nq = qp.shape[0]
    L, N = forest.radius.shape
    n_leaf = forest.leaf_ids.shape[0]

    ones = jnp.full((nq, N // 32), jnp.uint32(0xFFFFFFFF))
    delta0 = jnp.zeros((nq, n_leaf + 1), jnp.int32)

    def body(carry, xs):
        prev_bits, delta, dists, pruned = carry
        coords, rad, cell, leaf, parent, lo, hi = xs
        pw = parent // 32
        pb = (parent % 32).astype(jnp.uint32)
        pwords = jnp.take(prev_bits, pw, axis=1)            # (nq, N)
        pbit = ((pwords >> pb[None, :]) & 1) == 1
        if ghost:
            c = jnp.maximum(cell, 0)
            cw = jnp.take(scope, c // 32, axis=1)           # (nq, N)
            in_scope = ((cw >> (c % 32).astype(jnp.uint32)[None, :]) & 1) == 1
        else:
            in_scope = cell[None, :] == scope[:, None]
        active = pbit & (cell[None, :] >= 0) & in_scope
        act_bits = _pack_words(active)
        emit_bits, exp_bits = tree_frontier_step(
            qp, coords, rad, leaf, act_bits, eps, metric)
        emit_i = _unpack_words(emit_bits)[:, :N].astype(jnp.int32)
        delta = delta.at[:, lo].add(emit_i).at[:, hi].add(-emit_i)
        dists = dists + jnp.sum(_popcount_rows(act_bits)).astype(jnp.float32)
        pruned = pruned + jnp.sum(_popcount_rows(
            act_bits & ~(emit_bits | exp_bits))).astype(jnp.float32)
        return (exp_bits, delta, dists, pruned), None

    xs = (forest.coords, forest.radius, forest.cell, forest.leaf,
          forest.parent, forest.leaf_lo, forest.leaf_hi)
    (_, delta, dists, pruned), _ = jax.lax.scan(
        body, (ones, delta0, jnp.float32(0), jnp.float32(0)), xs)
    # fused leaf-range pack: prefix-sum the ±1 deltas, apply the cover /
    # validity / self-pair tests and pack to words in one kernel — the
    # dense (nq, n_leaf) cover mask never reaches HBM
    cnt, bits = _leaf_range_pack(delta, forest.leaf_ids, qids)
    nbrs = _bits_to_gathered_ids(bits, forest.leaf_ids, k_cap)
    return nbrs, cnt, dists, pruned


# ---------------------------------------------------------------------------
# Algorithm 4 — systolic ring (fused bitmask tiles + block-summary pruning)
# ---------------------------------------------------------------------------

def _block_summary(x, metric):
    """Bounding (center, radius) of a shard's block in TRUE distance —
    the metric's ``summary`` hook (euclidean: centroid + max L2; generic
    default: first block point as center, valid in any metric)."""
    return get_metric(metric).summary(x)


def _round_skip_flags(x, partner, eps, *, axis, metric, prune):
    """Per-round prune decisions from the all-gathered block summary table.

    skip[r] is True when no point of my block can be within eps of any
    point of round r's partner block: d(c_me, c_p) > r_me + r_p + eps.
    Float-metric center distances are fp32, so the bound carries a small
    relative slack — under-pruning is always safe, over-pruning never is.
    """
    nrounds = partner.shape[0]
    if not prune:
        return jnp.zeros((nrounds,), bool)
    met = get_metric(metric)
    c, rad = met.summary(x)
    call = jax.lax.all_gather(c, axis)          # (nranks, d) summary table
    radall = jax.lax.all_gather(rad, axis)      # (nranks,)
    pc = call[partner]
    dc = met.summary_dist(pc, c)
    bound = rad + radall[partner] + eps
    if not met.exact:
        bound = bound * (1.0 + 1e-5) + 1e-6
    skip = dc > bound
    return skip.at[0].set(False)                # self tile never skipped


def _systolic_local(x, ids, *, axis, nranks, eps, metric, k_cap, prune):
    """Per-shard body (runs under shard_map). x: (n_loc, d), ids: (n_loc,).

    Symmetry halving (paper §IV-C: "we therefore only need N/2 rounds"):
    each (local × visiting) tile emits BOTH edge directions — the visiting
    block carries its own neighbor accumulator around the ring and one final
    collective-permute sends it home. Tiles evaluated: N/2 + 1 instead of N
    (at the boundary round of even N only the lower rank of each pair
    evaluates). The fused kernel is invoked once per direction (forward and
    mirror), each writing only its bitmask + counts to HBM.

    Double buffering: each loop iteration issues the
    ``ppermute`` that feeds round r+1 BEFORE evaluating round r's block, so
    the collective shares no data dependency with the tile kernels and the
    scheduler can genuinely run them concurrently — the reference
    implementation's MPI_Irecv/MPI_Isend-around-compute discipline. The
    pipeline is primed with one extra hop before the loop (the round-0 self
    tile overlaps it), and the mirror accumulator rides one hop BEHIND the
    block: its permute is issued in the same iteration that merges into it,
    so it too overlaps the kernels.

    Relies on block-contiguous global ids (``ids = arange(n)`` sharded along
    the ring), so a visiting block is fully described by its first id.
    """
    n_loc = x.shape[0]
    perm = [(i, (i - 1) % nranks) for i in range(nranks)]
    me = jax.lax.axis_index(axis)
    rounds = nranks // 2
    id0 = ids[0]

    # prune schedule: skip[r] / sched[r] for ring rounds r = 0..rounds
    rr = jnp.arange(rounds + 1)
    partner = (me + rr) % nranks
    skip = _round_skip_flags(x, partner, eps,
                             axis=axis, metric=metric, prune=prune)
    if nranks % 2 == 0 and rounds > 0:
        sched = jnp.where(rr == rounds, me < partner, True)
    else:
        sched = jnp.ones((rounds + 1,), bool)
    do_eval = sched & ~skip
    # float32 counters everywhere (the RunStats normalization): int32 wraps
    # at paper scale, fp32 is exact below 2^24 and approximate beyond
    tiles_skipped = jnp.sum((sched & skip).astype(jnp.float32))

    ones = jnp.ones((n_loc,), jnp.int32)

    def tile_bits(a, b):
        with jax.named_scope("nng.tile"):
            return nng_tile_bits(a, b, ones, eps, metric=metric)

    # the WHOLE tile evaluation — kernel, id extraction, merge — sits
    # inside a cond so a pruned round costs only the permutes
    def _eval_pair(y, yid0, acc):
        nbrs_, cnt_, ynbrs_, ycnt_, scan_ = acc
        fc, fb = tile_bits(x, y)     # visiting pts near my rows
        rc, rb = tile_bits(y, x)     # my pts near visiting rows (mirror)
        fids, fscan = _bits_to_ids_scanned(fb, yid0, k_cap)
        rids, rscan = _bits_to_ids_scanned(rb, id0, k_cap)
        return (_merge_ids(nbrs_, fids), cnt_ + fc,
                _merge_ids(ynbrs_, rids), ycnt_ + rc, scan_ + fscan + rscan)

    def step(r, carry):
        # double-buffered: the carry block already ARRIVED (hop issued last
        # iteration / pre-loop); issue hop r+1 first, then evaluate round r
        # — permute and kernels are dependency-free, so they overlap
        y, yid0, ynbrs, ycnt, nbrs, cnt, scan = carry
        y_next, yid_next = _ring_permute((y, yid0), axis, perm)
        # mirror accumulator rides one hop behind the block: permuted here,
        # merged by this round's eval (also overlaps the kernels)
        ynbrs, ycnt = _ring_permute((ynbrs, ycnt), axis, perm)
        nbrs, cnt, ynbrs, ycnt, scan = jax.lax.cond(
            do_eval[r], lambda acc: _eval_pair(y, yid0, acc),
            lambda acc: acc, (nbrs, cnt, ynbrs, ycnt, scan))
        return y_next, yid_next, ynbrs, ycnt, nbrs, cnt, scan

    nbrs0 = jnp.full((n_loc, k_cap), SENTINEL, dtype=jnp.int32)
    cnt0 = jnp.zeros((n_loc,), dtype=jnp.int32)
    if rounds > 0:
        # prime the pipeline: hop 1 in flight while the self tile runs below
        y1, yid1 = _ring_permute((x, id0), axis, perm)
    # self tile (round 0): clear the diagonal bit (row i, column i) and take
    # counts from the cleared bitmask — structurally excludes self pairs
    # even when fp32 rounding pushes d(x, x) past eps.
    _, bits0 = tile_bits(x, x)
    rows = jnp.arange(n_loc)
    wsel = rows // 32
    bsel = (rows % 32).astype(jnp.uint32)
    bits0 = bits0.at[rows, wsel].set(
        bits0[rows, wsel] & ~(jnp.uint32(1) << bsel))
    cnt = _popcount_rows(bits0)
    ids0, scan = _bits_to_ids_scanned(bits0, id0, k_cap)
    nbrs = _merge_ids(nbrs0, ids0)
    if rounds > 0:
        _, _, ynbrs, ycnt, nbrs, cnt, scan = jax.lax.fori_loop(
            1, rounds + 1, step, (y1, yid1, nbrs0, cnt0, nbrs, cnt, scan))
        # each block's mirror accumulator sits `rounds` hops downstream of
        # its home rank; one permute returns it
        perm_home = [(i, (i + rounds) % nranks) for i in range(nranks)]
        ynbrs, ycnt = _ring_permute((ynbrs, ycnt), axis, perm_home,
                                    "nng.mirror_home")
        nbrs = _merge_ids(nbrs, ynbrs)
        cnt = cnt + ycnt
    overflow = jnp.any(cnt > k_cap)[None]
    # tile-granular work counter: every evaluated ring round computes the
    # full n_loc × n_loc distance tile (no in-tile pruning on this path).
    # float32 like the tree counters — int32 wraps at n_loc >= 2^15.5
    dists = (jnp.sum(do_eval.astype(jnp.float32))
             * jnp.float32(float(n_loc) * float(n_loc)))
    return (nbrs, cnt, overflow, tiles_skipped[None], dists[None],
            jnp.zeros((1,), jnp.float32), scan[None])


def _systolic_local_tree_split(x, ids, *forest_arrays, axis, nranks, eps,
                               metric, k_cap, prune, ring_modes):
    """Per-shard systolic body, tree flavor: double-buffered ring with the
    SPLIT ring schedule.

    ``ring_modes[r - 1]`` statically selects what round r rotates. It is
    planned host-side (``plan_ring_schedule``) from the same block-summary
    table the device prune uses, and is uniform across ranks — a collective
    permute is global, so every rank must agree on what a hop carries:

    - ``"forest"``: the visiting block's levelized cover-tree tables jump
      to their round-r position in ONE ``ppermute`` (a multi-hop shift when
      intervening rounds rotated points only, so skipped rounds never pay
      forest bytes) and the forward direction runs the level-synchronous
      traversal against them. Wins on dense rounds, where in-tree pruning
      amortizes the ~(d+6)·L·N·4-byte hop.
    - ``"points"``: only the raw point tile + its id vector rotate
      (d·n_loc·4 bytes/hop) and an evaluated tile falls back to the fused
      dense bitmask kernel pair (``nng_tile_bits_pair``). Wins when the
      summary table says the round is sparse or skipped ring-wide — the
      cheapest ring-bytes schedule available.

    The loop is unrolled over rounds = nranks // 2 (each round may carry a
    different payload, so the body is not ``fori_loop``-uniform), issuing
    round r+1's permutes before round r's evaluation exactly like the tiles
    flavor: collectives overlap the traversal / tile kernels. The mirror
    traversal always queries the LOCAL forest, so only the forward
    direction ever needs the rotated tables. Mirror accumulators rotate one
    hop behind the block and return home via the final shift-``rounds``
    permute. Exactness is schedule-independent: dense tiles and the
    cover-tree traversal emit identical edge sets in the declared fp32
    arithmetic, so the mode choice moves bytes and FLOPs, never edges.
    """
    n_loc = x.shape[0]
    forest = DeviceForest(*[a[0] for a in forest_arrays])   # drop rank dim
    perm = [(i, (i - 1) % nranks) for i in range(nranks)]
    me = jax.lax.axis_index(axis)
    rounds = nranks // 2
    assert len(ring_modes) == rounds, (ring_modes, rounds)
    qcells = jnp.zeros((n_loc,), jnp.int32)
    id0 = ids[0]

    rr = jnp.arange(rounds + 1)
    partner = (me + rr) % nranks
    skip = _round_skip_flags(x, partner, eps,
                             axis=axis, metric=metric, prune=prune)
    if nranks % 2 == 0 and rounds > 0:
        sched = jnp.where(rr == rounds, me < partner, True)
    else:
        sched = jnp.ones((rounds + 1,), bool)
    do_eval = sched & ~skip
    tiles_skipped = jnp.sum((sched & skip).astype(jnp.float32))

    def trav(qp, qids, fr):
        with jax.named_scope("nng.traverse"):
            return tree_traverse(qp, qids, qcells, fr, eps, k_cap, metric)

    def rot(a):
        return _ring_permute((a,), axis, perm)[0]

    nbrs0 = jnp.full((n_loc, k_cap), SENTINEL, dtype=jnp.int32)
    cnt0 = jnp.zeros((n_loc,), dtype=jnp.int32)
    if rounds > 0:
        # prime round 1's payloads; the round-0 self traversal overlaps them
        y = rot(x)
        yids = rot(ids)
        vforest, vpos = forest, 0
        if ring_modes[0] == "forest":
            vforest = jax.tree.map(rot, forest)
            vpos = 1
    # round 0 (self tile): one traversal of my own tree; the global-id
    # inequality inside tree_traverse excludes self pairs structurally
    nbrs, cnt, dists, pruned = trav(x, ids, forest)
    ynbrs, ycnt = nbrs0, cnt0

    for r in range(1, rounds + 1):
        y_cur, yids_cur, vf_cur = y, yids, vforest
        if r < rounds:
            # issue round r+1's payloads before this round's evaluation
            y = rot(y_cur)
            yids = rot(yids_cur)
            if ring_modes[r] == "forest":
                # jump the forest from its last rotated position straight
                # to round r+1 — one collective, one hop's bytes
                jump = (r + 1) - vpos
                pjump = [(i, (i - jump) % nranks) for i in range(nranks)]
                vforest = DeviceForest(*_ring_permute(vforest, axis,
                                                      pjump))
                vpos = r + 1
        # mirror accumulator: one hop behind the block, merged by this
        # round's eval — its permute overlaps the kernels too
        ynbrs = rot(ynbrs)
        ycnt = rot(ycnt)

        if ring_modes[r - 1] == "forest":
            def _eval(acc):
                nbrs_, cnt_, ynbrs_, ycnt_, d_, p_ = acc
                fn, fc, fd, fp = trav(x, ids, vf_cur)     # vs visiting tree
                rn, rc, rd, rp = trav(y_cur, yids_cur, forest)    # mirror
                return (_merge_ids(nbrs_, fn), cnt_ + fc,
                        _merge_ids(ynbrs_, rn), ycnt_ + rc,
                        d_ + fd + rd, p_ + fp + rp)
        else:
            def _eval(acc):
                nbrs_, cnt_, ynbrs_, ycnt_, d_, p_ = acc
                with jax.named_scope("nng.tile"):
                    fc, fb, rc, rb = nng_tile_bits_pair(x, y_cur, eps,
                                                        metric=metric)
                nbrs_ = _merge_ids(nbrs_, _bits_to_ids(fb, yids_cur[0],
                                                       k_cap))
                ynbrs_ = _merge_ids(ynbrs_, _bits_to_ids(rb, id0, k_cap))
                return (nbrs_, cnt_ + fc, ynbrs_, ycnt_ + rc,
                        d_ + jnp.float32(float(n_loc) * float(n_loc)), p_)
        nbrs, cnt, ynbrs, ycnt, dists, pruned = jax.lax.cond(
            do_eval[r], _eval, lambda acc: acc,
            (nbrs, cnt, ynbrs, ycnt, dists, pruned))

    if rounds > 0:
        perm_home = [(i, (i + rounds) % nranks) for i in range(nranks)]
        ynbrs, ycnt = _ring_permute((ynbrs, ycnt), axis, perm_home,
                                    "nng.mirror_home")
        nbrs = _merge_ids(nbrs, ynbrs)
        cnt = cnt + ycnt
    overflow = jnp.any(cnt > k_cap)[None]
    return (nbrs, cnt, overflow, tiles_skipped[None], dists[None],
            pruned[None], jnp.zeros((1, 2), jnp.float32))


def plan_ring_schedule(points, nranks: int, eps: float, *,
                       metric="euclidean", prune: bool = True,
                       dense_frac: float = 0.5) -> tuple:
    """Host-side split-ring planner: one ``"forest"``/``"points"`` mode per
    ring round (length nranks // 2), from the same block summaries the
    device prune uses.

    For each round r it replays the device schedule — partner = (me + r) %
    nranks, the even-nranks halving round evaluated only by the lower rank
    of each pair, the summary-distance skip test with the identical inexact-
    metric slack — and counts how many ranks would actually evaluate their
    tile. If more than ``dense_frac`` of the scheduled tiles evaluate, the
    round is dense and rotating the forest tables pays for itself
    (``"forest"``); otherwise only raw point tiles rotate and the few
    evaluating ranks fall back to the dense bitmask kernel (``"points"``).

    The choice is purely a bytes/FLOPs trade: the device's own per-rank
    skip flags stay authoritative for correctness, so a knife-edge
    disagreement between this host replay and the fp32 device test can
    only mis-cost a round, never mis-classify an edge. With ``prune=False``
    every tile evaluates, so every round plans ``"forest"`` — matching the
    pre-split behavior.
    """
    met = get_metric(metric)
    rounds = nranks // 2
    if rounds == 0:
        return ()
    pts = jnp.asarray(np.asarray(points), met.dtype)
    n = pts.shape[0]
    assert n % nranks == 0, (n, nranks)
    n_loc = n // nranks
    summaries = [met.summary(pts[j * n_loc:(j + 1) * n_loc])
                 for j in range(nranks)]
    call = jnp.stack([c for c, _ in summaries])
    radall = np.asarray(jnp.stack([r for _, r in summaries]), np.float64)
    # dcc[j, p] = summary distance from block j's center to block p's
    dcc = np.stack([np.asarray(met.summary_dist(call, call[j]), np.float64)
                    for j in range(nranks)])
    modes = []
    for r in range(1, rounds + 1):
        evals = scheduled = 0
        for j in range(nranks):
            p = (j + r) % nranks
            if nranks % 2 == 0 and r == rounds and not j < p:
                continue                      # halving round: upper half idle
            scheduled += 1
            if prune:
                bound = radall[j] + radall[p] + eps
                if not met.exact:
                    bound = bound * (1.0 + 1e-5) + 1e-6
                if dcc[j, p] > bound:
                    continue
            evals += 1
        modes.append("forest" if evals > dense_frac * scheduled
                     else "points")
    return tuple(modes)


def make_nng_mesh(nranks: int | None = None) -> Mesh:
    devs = np.asarray(jax.devices())
    if nranks is not None:
        devs = devs[:nranks]
    return Mesh(devs, ("ring",))


_N_FOREST = len(DeviceForest._fields)


@functools.lru_cache(maxsize=64)
def _systolic_fn(mesh, eps, metric, k_cap, axis, prune, pallas_mode,
                 traversal, ring_modes=None):
    """Memoized jitted shard_map program: rebuilding the closure per call
    defeats the jit cache (every invocation would retrace + recompile, and
    compile dominates wall clock on re-plan loops / benchmarks). Mesh and
    the capacity knobs are hashable, so the same engine configuration
    always returns the SAME callable and jit caching works.

    ``pallas_mode`` (the resolved REPRO_PALLAS value) is part of the key
    because the tile wrappers read it at TRACE time — without it, flipping
    the env mid-process would silently reuse a program traced under the
    old mode. ``traversal`` selects the dense-tile vs cover-tree body
    (different arities); forest table SHAPES are not part of the key — jit
    retraces per shape as usual. ``ring_modes`` (a per-round
    "forest"/"points" tuple from ``plan_ring_schedule``, tree only) is
    static because every round's rotating payload must be known at trace
    time — a different schedule IS a different program."""
    nranks = mesh.shape[axis]
    if traversal == "tree":
        body = functools.partial(
            _systolic_local_tree_split, axis=axis, nranks=nranks, eps=eps,
            metric=metric, k_cap=k_cap, prune=prune, ring_modes=ring_modes)
        in_specs = (P(axis, None), P(axis)) + (P(axis),) * _N_FOREST
    else:
        body = functools.partial(
            _systolic_local, axis=axis, nranks=nranks, eps=eps,
            metric=metric, k_cap=k_cap, prune=prune)
        in_specs = (P(axis, None), P(axis))
    return jax.jit(_shard_map(
        body, mesh,
        in_specs=in_specs,
        out_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis),
                   P(axis), P(axis, None)),
    ))


def systolic_run(
    points,
    eps: float,
    mesh: Mesh,
    *,
    metric="euclidean",
    k_cap: int = 64,
    axis: str = "ring",
    prune: bool = True,
    traversal: str = "tiles",
    forest: dict | None = None,
    ring_schedule: tuple | None = None,
):
    """Distributed exact ε-NNG via the sparsity-aware systolic ring.

    ``traversal="tiles"`` (default) evaluates each ring tile with the fused
    bitmask kernel; ``traversal="tree"`` traverses per-block cover trees
    (``forest`` = rank-stacked tables from ``flat_tree.build_block_forests``
    + ``stack_device_forests``) so the triangle-inequality prune fires
    inside every tile, not just at block granularity.

    The ring is double-buffered: each round's
    ``ppermute`` is issued before the previous round's tile is evaluated,
    so comm genuinely overlaps compute (one extra priming hop on the tiles
    flavor). The tree flavor additionally runs the split ring schedule —
    ``ring_schedule`` is the per-round ``"forest"``/``"points"`` mode tuple
    (computed via ``plan_ring_schedule`` when None).

    Returns (nbrs, cnt, overflow, tiles_skipped, dists_evaluated,
    nodes_pruned, epilogue_scan):
      - nbrs (n, k_cap) int32 neighbor ids (SENTINEL-padded),
      - cnt (n,) exact neighbor counts,
      - overflow (nranks,) bool — grow k_cap and re-run if any is set
        (``repro.launch.nng_run.run_systolic`` automates this),
      - tiles_skipped (nranks,) int32 — ring tiles pruned per rank by the
        block-summary triangle-inequality test (``prune=False`` disables),
      - dists_evaluated (nranks,) float32 — pair distances evaluated per
        rank (dense n_loc² per evaluated round on the tiles path; frontier
        pairs on the tree path; fp32 so paper-scale counts can't wrap),
      - nodes_pruned (nranks,) float32 — tree-path frontier pairs whose
        subtree was discarded (0 on the tiles path),
      - epilogue_scan (nranks, 2) float32 — per rank, the (slot, chunk)
        pairs its ``bits_to_cols`` calls scanned and the pairs a full scan
        takes (``ops.bits_to_cols_scanned``; 0, 0 on the tree path).

    ``points`` rows must be a multiple of the ring size (pad upstream with
    far-away sentinel points if needed; repro.launch handles this).
    """
    met = get_metric(metric)
    nranks = mesh.shape[axis]
    n, _ = points.shape
    assert n % nranks == 0, (n, nranks)
    ids = np.arange(n, dtype=np.int32)
    if traversal == "tree" and ring_schedule is None:
        ring_schedule = plan_ring_schedule(points, nranks, float(eps),
                                           metric=metric, prune=prune)
    ring_modes = tuple(ring_schedule) if traversal == "tree" else None
    fn = _systolic_fn(mesh, float(eps), met, k_cap, axis, prune,
                      _pallas_mode(), traversal, ring_modes)
    if traversal == "tree":
        assert forest is not None, "traversal='tree' needs stacked forests"
    with span("nng.put"):
        points = _on_ring(mesh, axis, points, met.dtype)
        ids = _on_ring(mesh, axis, ids)
        ftabs = ([_on_ring(mesh, axis, forest[k])
                  for k in DeviceForest._fields]
                 if traversal == "tree" else [])
    return fn(points, ids, *ftabs)


def systolic_nng(points, eps, mesh, **kw):
    """Deprecated alias of ``systolic_run`` (the PR 4 tuple API). Use
    ``repro.nng.build_nng(points, eps, partition="point", ...)`` instead —
    same engine, CSR ``NNGraph`` result, shared re-plan driver."""
    warnings.warn(
        "systolic_nng is deprecated; use repro.nng.build_nng(..., "
        "partition='point') or repro.core.distributed.systolic_run",
        DeprecationWarning, stacklevel=2)
    return systolic_run(points, eps, mesh, **kw)[:6]


# ---------------------------------------------------------------------------
# delta traversal — online-maintenance entry point (repro.stream)
#
# Deliberately NOT in the static-analysis matrices: it introduces no new
# Pallas kernels (tree_frontier + the bits epilogue are reused as-is, and
# their contracts are already registered in repro.analysis.contracts) and
# no in-program collectives (the traffic audit has nothing to classify —
# the only movement is the host-side batch broadcast, modeled by
# ``delta_bcast_bytes`` and accounted per update as ``delta_bcast``).
# ---------------------------------------------------------------------------

def _delta_local(qp, qids, qbits, *forest_arrays, eps, metric, k_cap):
    """Per-shard delta body: the (replicated) inserted batch traverses THIS
    rank's forest once. ``qbits`` is an all-ones packed cell-membership
    mask, so every tree of every cell is in scope — an inserted point must
    be checked against the whole local forest regardless of which cell it
    lands in (exactness needs no cell scoping here; the batch is tiny, so
    widening scope costs frontier work only at the roots)."""
    forest = DeviceForest(*[a[0] for a in forest_arrays])   # drop rank dim
    nbrs, cnt, dists, pruned = tree_traverse(
        qp, qids, None, forest, eps, k_cap, metric, qghost_bits=qbits)
    return nbrs[None], cnt[None], dists[None], pruned[None]


@functools.lru_cache(maxsize=64)
def _delta_fn(mesh, eps, metric, k_cap, axis, pallas_mode):
    """Memoized jitted shard_map program for the delta traversal (same
    rationale as ``_systolic_fn``; ``pallas_mode`` keys trace-time tile
    wrapper mode). No collective appears in the body: the batch arrives
    replicated (host-side broadcast — the comm model the driver accounts
    as ``delta_bcast``) and per-rank results come back rank-stacked."""
    body = functools.partial(_delta_local, eps=eps, metric=metric,
                             k_cap=k_cap)
    return jax.jit(_shard_map(
        body, mesh,
        in_specs=(P(None, None), P(None), P(None, None))
        + (P(axis),) * _N_FOREST,
        out_specs=(P(axis, None), P(axis), P(axis), P(axis)),
    ))


def delta_traverse_run(qp, qids, forest: dict, eps, mesh: Mesh, *,
                       metric="euclidean", k_cap: int = 64,
                       axis: str = "ring"):
    """Query ONLY the batch ``qp`` against every rank's forest — the online
    insert path. Instead of re-running a full systolic/landmark schedule,
    the inserted points are broadcast once and each rank runs one
    level-synchronous traversal of its local forest; the union of per-rank
    hits IS the new-edge set (forests partition the corpus).

    Returns (nbrs (nranks*nq, k_cap) SENTINEL-padded, cnt (nranks*nq,),
    dists (nranks,) float32, pruned (nranks,) float32): row r*nq + i holds
    rank r's neighbors of query i, so pairing with ``tile(qids, nranks)``
    recovers directed (src, dst) hit pairs. Self pairs are excluded by
    global-id inequality inside ``tree_traverse`` as always.
    """
    met = get_metric(metric)
    nranks = mesh.shape[axis]
    nq = qp.shape[0]
    # packed cell-membership mask wide enough for every cell id present
    max_cell = int(np.max(np.asarray(forest["cell"]).max(initial=0), initial=0))
    words = max_cell // 32 + 1
    qbits = jnp.full((nq, words), jnp.uint32(0xFFFFFFFF))
    fn = _delta_fn(mesh, float(eps), met, k_cap, axis, _pallas_mode())
    ftabs = [_on_ring(mesh, axis, forest[k]) for k in DeviceForest._fields]
    nbrs, cnt, dists, pruned = fn(
        jnp.asarray(qp, met.dtype), jnp.asarray(qids, jnp.int32), qbits,
        *ftabs)
    return (nbrs.reshape(nranks * nq, -1), cnt.reshape(nranks * nq),
            dists, pruned)


def delta_bcast_bytes(nranks: int, nq: int, dim: int, itemsize: int) -> int:
    """Host-side comm model of the delta broadcast: every other rank
    receives the batch's coords + int32 ids once."""
    return (nranks - 1) * nq * (dim * itemsize + 4)


# ---------------------------------------------------------------------------
# Algorithms 5 + 6 — landmark partitioning with ε-ghosts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LandmarkPlan:
    """Static capacities for the landmark engine (host planning output)."""
    m_centers: int      # Voronoi sites
    cap_coal: int       # per (src, dst) rank-pair coalesce capacity (points)
    cap_ghost: int      # per (src, dst) rank-pair ghost capacity (copies)
    g_per_pt: int       # max cells one point may ghost into
    k_cap: int          # neighbor-list capacity
    cap_rank: int = 0   # max coalesced points on any ONE rank (ring ghost
    #                     block height; 0 = unplanned, coll-only plan)


def ghost_coll_bytes(nranks: int, cap_ghost: int, dim: int,
                     itemsize: int) -> int:
    """Exact planned bytes of the collective (all_to_all) ghost exchange:
    every rank ships nranks × cap_ghost capacity-padded rows of
    (point, id, cell) regardless of how many ghosts actually exist."""
    row = itemsize * dim + 4 + 4            # pts + int32 id + int32 cell
    return nranks * nranks * cap_ghost * row


def ghost_ring_bytes(nranks: int, cap_rank: int, dim: int, itemsize: int,
                     m_centers: int) -> int:
    """Exact planned bytes of the ring ghost exchange: nranks // 2 hops of
    the compacted (cap_rank, dim) block + ids + packed Lemma-1 ghost bits
    (ceil(m/32) uint32 words per row), per rank. Eps-independent — the
    ghost TEST travels as bits instead of materialized ghost copies."""
    mw = (m_centers + 31) // 32
    row = itemsize * dim + 4 + mw * 4       # pts + int32 id + gbits words
    return nranks * (nranks // 2) * cap_rank * row


def resolve_ghost_mode(ghost_mode: str, plan: "LandmarkPlan", dim: int,
                       itemsize: int, nranks: int) -> str:
    """Resolve ``"auto"`` to ``"coll"`` / ``"ring"`` from the exact byte
    models above (ring wins iff it moves strictly fewer planned bytes).
    Plans without ``cap_rank`` (hand-built / heuristic) stay ``"coll"``."""
    if ghost_mode != "auto":
        return ghost_mode
    if plan.cap_rank <= 0:
        return "coll"
    ring = ghost_ring_bytes(nranks, plan.cap_rank, dim, itemsize,
                            plan.m_centers)
    coll = ghost_coll_bytes(nranks, plan.cap_ghost, dim, itemsize)
    return "ring" if ring < coll else "coll"


def plan_landmark(
    n: int, nranks: int, *, m_centers: int | None = None,
    avg_degree_hint: float = 64.0, skew: float = 2.0,
) -> LandmarkPlan:
    """Capacity planning from workload stats (sample-based in production)."""
    m = m_centers or max(2 * nranks, 32)
    per_pair = int(np.ceil(n / nranks / nranks))
    return LandmarkPlan(
        m_centers=m,
        cap_coal=int(per_pair * skew) + 8,
        cap_ghost=int(per_pair * skew) + 8,
        g_per_pt=8,
        k_cap=int(avg_degree_hint * skew),
    )


def _plan_count_local(x, centers, f, *, axis, nranks, eps, two_eps_c,
                      metric):
    """Per-shard capacity counting pass: EXACT per-(src, dst) coalesce and
    ghost-copy counts plus the max ghost fanout, using the SAME Voronoi
    assignment and slacked Lemma-1 bound the engine itself applies — so the
    returned capacities are exactly what the engine's buffers need."""
    n_loc = x.shape[0]
    m = centers.shape[0]
    dpc = tile_cdist(x, centers, metric)
    cell = jnp.argmin(dpc, axis=1).astype(jnp.int32)
    d_min = jnp.min(dpc, axis=1)
    dest = f[cell]
    coal = jnp.zeros((nranks,), jnp.int32).at[dest].add(1)
    tru, gbound = _lemma1_ghost_bound(x, centers, dpc, d_min, two_eps_c,
                                      metric)
    gmask = (tru <= gbound[:, None]) & (
        jnp.arange(m)[None, :] != cell[:, None])
    g_per_pt = jnp.max(jnp.sum(gmask.astype(jnp.int32), axis=1))
    # ghosts into cell c land on rank f[c]: segment-sum the per-cell ghost
    # column counts by destination rank
    gcol = jnp.sum(gmask.astype(jnp.int32), axis=0)
    ghost = jnp.zeros((nranks,), jnp.int32).at[f].add(gcol)
    # all-reduce the maxima across ranks (one collective each)
    coal_all = jax.lax.all_gather(coal, axis)   # (src, dst) coalesce counts
    coal_max = jnp.max(coal_all)
    ghost_max = jnp.max(jax.lax.all_gather(ghost, axis))
    gpp_max = jnp.max(jax.lax.all_gather(g_per_pt[None], axis))
    # total rows any ONE rank receives in coalesce = the compacted block
    # height the ring ghost path rotates (column sums of the src×dst table)
    rank_tot = jnp.max(jnp.sum(coal_all, axis=0))
    return coal_max[None], ghost_max[None], gpp_max[None], rank_tot[None]


@functools.lru_cache(maxsize=64)
def _plan_count_fn(mesh, eps, metric, axis, pallas_mode):
    nranks = mesh.shape[axis]
    body = functools.partial(
        _plan_count_local, axis=axis, nranks=nranks, eps=eps,
        two_eps_c=2.0 * eps, metric=metric)
    return jax.jit(_shard_map(
        body, mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    ))


def plan_landmark_device(
    points, centers, f, eps: float, mesh: Mesh, *,
    metric="euclidean", axis: str = "ring", k_cap: int = 128,
    pad: int = 8,
) -> LandmarkPlan:
    """EXACT landmark capacity planning as ONE shard_map counting pass.

    Replaces the host heuristic + overflow → ``grow_plan`` re-run loop for
    the common case: each rank bincounts its coalesce destinations and its
    slacked-Lemma-1 ghost copies per destination rank (the same tests the
    engine applies), an all-reduce takes the maxima, and the returned
    ``LandmarkPlan`` capacities are exact (+``pad`` slop). Only ``k_cap``
    (the neighbor-list width) remains a heuristic the overflow loop may
    still grow.
    """
    met = get_metric(metric)
    nranks = mesh.shape[axis]
    n, _ = points.shape
    assert n % nranks == 0, (n, nranks)
    fn = _plan_count_fn(mesh, float(eps), met, axis, _pallas_mode())
    coal, ghost, gpp, rank_tot = fn(_on_ring(mesh, axis, points, met.dtype),
                                    jnp.asarray(centers, met.dtype),
                                    jnp.asarray(f, jnp.int32))
    return LandmarkPlan(
        m_centers=int(np.asarray(centers).shape[0]),
        cap_coal=int(np.asarray(coal)[0]) + pad,
        cap_ghost=max(int(np.asarray(ghost)[0]), 1) + pad,
        g_per_pt=max(int(np.asarray(gpp)[0]), 1),
        k_cap=k_cap,
        cap_rank=int(np.asarray(rank_tot)[0]) + pad,
    )


def _pack_by_dest(dest, valid, payload, nranks: int, cap: int):
    """Pack rows of `payload` (pytree of (L, ...)) into (nranks, cap, ...)
    send buffers by destination rank. Returns (buffers, dropped_count).
    Invalid/overflow rows go to a trash row that is sliced away."""
    L = dest.shape[0]
    key = jnp.where(valid, dest, nranks)
    order = jnp.argsort(key)  # jnp argsort is stable
    ks = key[order]
    pos = jnp.arange(L) - jnp.searchsorted(ks, ks, side="left")
    ok = (ks < nranks) & (pos < cap)
    row = jnp.where(ok, ks, nranks)
    col = jnp.where(ok, pos, 0)
    dropped = jnp.sum(valid) - jnp.sum(ok & (ks < nranks))

    def pack_one(x, fill):
        shp = (nranks + 1, cap) + x.shape[1:]
        buf = jnp.full(shp, fill, dtype=x.dtype)
        buf = buf.at[row, col].set(x[order])
        return buf[:nranks]

    out = jax.tree.map(lambda x: pack_one(x[0], x[1]), payload,
                       is_leaf=lambda t: isinstance(t, tuple))
    return out, dropped


def _lemma1_ghost_bound(x, centers, dpc, d_min, two_eps_c, metric):
    """Slacked Lemma-1 ghost bound: (tru, bound) with p a ghost candidate
    of cell i iff ``tru[p, i] <= bound[p]``.

    The raw test is d(p, c_i) <= d(p, C) + 2ε in TRUE distance. Both sides
    come out of the fp32 BLAS3 expansion, whose cancellation error is
    O(u · (‖p‖ + ‖c‖)²); propagated through sqrt at magnitude ``bound``
    that is O(u · scale² / bound) — an ABSOLUTE 0 slack (the pre-fix code)
    silently drops boundary ghosts on large-magnitude data, losing exact
    edges. The guard is scale-aware like the block-summary prune slack and
    PER-POINT (each row's slack scales with its own ‖p‖², so mixed-scale
    data only over-ghosts where the fp32 error is actually large):
    over-inclusion only costs extra ghost copies (capacity overflow
    re-plans handle it), under-inclusion is never recoverable.

    The slack POLICY is the metric's ``lemma1_slack`` hook: zero for exact
    integer metrics, the dimension-aware BLAS3 cancellation bound for
    euclidean, a scale-relative generic slack for other float metrics.
    """
    met = get_metric(metric)
    tru = met.true(dpc)
    bound = met.true(d_min) + two_eps_c
    slack = met.lemma1_slack(x, centers, tru, bound)
    return tru, bound + slack


def _cell_sort(key_cell, valid, m, *arrays):
    """Cell-sorted compaction: stable-sort rows so cells are contiguous and
    padding rows (key m) cluster at the end — the layout that makes the
    grouped kernel's per-tile group ranges tight enough to skip whole
    all-padding / cross-cell blocks."""
    order = jnp.argsort(jnp.where(valid, key_cell, jnp.int32(m)))
    return tuple(a[order] for a in arrays)


def _ghost_ring(W, Wids, Wcell, Wvalid, Wgrp, centers, forest, *, axis,
                nranks, eps, two_eps_c, metric, plan, traversal):
    """Ring ghost phase (``ghost_mode="ring"``): the ε-ghost exchange as a
    systolic rotation of the COMPACTED coalesce buffer instead of the
    capacity-padded all_to_all scatter.

    Each rank compacts its cell-sorted W buffer to the planner's exact
    ``cap_rank`` block (valid rows first — the cell sort clusters padding
    at the end), computes the slacked Lemma-1 ghost test ONCE at home as a
    packed per-row cell bitset (own cell cleared, invalid rows zeroed),
    and rotates (block, ids, gbits) around the mesh with the PR 6
    double-buffering discipline: round r+1's ``ppermute`` is issued before
    round r's kernels consume the already-received block. The gbits travel
    WITH the block — recomputing them per hop would let fp32 argmin
    near-ties diverge between ranks and silently drop edges.

    Per round, the visiting rows query the LOCAL cells only within their
    ghost set: the tiles flavor runs the ghost-aware fused bitmask kernel
    (``nng_tile_bits_ghost`` — bitset membership replaces group equality
    in VMEM), the tree flavor the cover-tree traversal with
    ``qghost_bits`` scoping. Results stay local — the visiting ids arrived
    with the block, so the per-round hit tables need no return trip and
    there is no traveling mirror accumulator; the CSR assembly symmetrizes
    directed pairs. Round 0 (own block vs own cells) covers same-rank
    cross-cell pairs; rounds 1..nranks//2 cover every rank pair because
    Lemma 1 holds in both directions of an ε-pair, so ONE visiting
    direction suffices — and on an even ring the boundary round, where the
    pair {me, me+R} meets at both ends, is evaluated by the lower rank
    only. No cap_ghost / g_per_pt capacities exist on this path; overflow
    means the valid coalesce rows outgrew ``cap_rank``.
    """
    m = centers.shape[0]
    B = plan.cap_rank
    k_cap = plan.k_cap
    me = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % nranks) for i in range(nranks)]
    rounds = nranks // 2

    Wb, Wbids, Wbcell, Wbvalid = W[:B], Wids[:B], Wcell[:B], Wvalid[:B]
    over = (jnp.sum(Wvalid.astype(jnp.int32)) > B)

    dpc_w = tile_cdist(Wb, centers, metric)
    d_min_w = jnp.min(dpc_w, axis=1)
    tru_w, gbound_w = _lemma1_ghost_bound(Wb, centers, dpc_w, d_min_w,
                                          two_eps_c, metric)
    gmask = ((tru_w <= gbound_w[:, None])
             & (jnp.arange(m)[None, :] != Wbcell[:, None])
             & Wbvalid[:, None])
    mw = (m + 31) // 32
    gbits = _pack_words(jnp.pad(gmask, ((0, 0), (0, mw * 32 - m))))

    zeros = (jnp.full((B, k_cap), SENTINEL, jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.int32(0), jnp.int32(0),
             jnp.float32(0), jnp.float32(0))

    def eval_block(bp, bi, bg):
        if traversal == "tree":
            nbrs_r, cnt_r, d_r, p_r = tree_traverse(
                bp, bi, None, forest, eps, k_cap, metric, qghost_bits=bg)
            return nbrs_r, cnt_r, jnp.int32(0), jnp.int32(0), d_r, p_r
        cnt_r, bits_r, sch_r, skp_r = nng_tile_bits_ghost(
            bp, W, bg, Wgrp, eps, metric=metric)
        nbrs_r = _bits_to_gathered_ids(bits_r, Wids, k_cap)
        tq, tp = nng_tile_geometry(B, W.shape[0], metric)
        d_r = (sch_r - skp_r).astype(jnp.float32) * jnp.float32(tq * tp)
        return nbrs_r, cnt_r, sch_r, skp_r, d_r, jnp.float32(0)

    ids_parts, nbr_parts, cnt_parts = [], [], []
    sched = skip = jnp.int32(0)
    dists = pruned = jnp.float32(0)
    blk = (Wb, Wbids, gbits)
    for r in range(rounds + 1):
        if r < rounds:
            # double buffering: issue round r+1's hop BEFORE this round's
            # kernels touch the already-received block — the permute and
            # the evaluation share no data dependency, so they overlap
            nxt = tuple(jax.lax.ppermute(a, axis, perm) for a in blk)
        bp, bi, bg = blk
        if r == rounds and rounds > 0 and nranks % 2 == 0:
            partner = (me + rounds) % nranks
            out = jax.lax.cond(me < partner,
                               lambda: eval_block(bp, bi, bg),
                               lambda: zeros)
        else:
            out = eval_block(bp, bi, bg)
        nbrs_r, cnt_r, sch_r, skp_r, d_r, p_r = out
        ids_parts.append(bi)
        nbr_parts.append(nbrs_r)
        cnt_parts.append(cnt_r)
        sched, skip = sched + sch_r, skip + skp_r
        dists, pruned = dists + d_r, pruned + p_r
        if r < rounds:
            blk = nxt
    Gids = jnp.concatenate(ids_parts)
    gnbrs = jnp.concatenate(nbr_parts)
    gcnt = jnp.concatenate(cnt_parts)
    over = over | jnp.any(gcnt > k_cap)
    return Gids, gnbrs, gcnt, over, sched, skip, dists, pruned


def _landmark_local(
    x, ids, centers, f, *tree_args, axis, nranks, eps, two_eps_c,
    metric, plan, traversal="tiles", ghost_mode="coll",
):
    """Per-shard landmark body. x (n_loc, d); centers (m, d) replicated;
    f (m,) cell->rank assignment (host-planned LPT).

    ``traversal="tree"``: ``tree_args`` is (cell_in, *forest_arrays) —
    Phases 3 + 4 traverse this rank's per-cell cover-tree forest (built
    host-side over the cells LPT-assigned to the rank) instead of running
    the grouped dense tiles: the paper's per-cell cover-tree query,
    pruning *within* each cell. ``cell_in`` is the SAME (sharded) Voronoi
    assignment the forests were built from — the engine must not recompute
    its own fp32 argmin, or a near-tie disagreement would scope a query to
    a tree that does not contain its point and silently drop edges."""
    n_loc = x.shape[0]
    m = centers.shape[0]
    if traversal == "tree":
        cell_in, forest_arrays = tree_args[0], tree_args[1:]
        forest = DeviceForest(*[a[0] for a in forest_arrays])
    else:
        cell_in, forest = None, None

    # -- Phase 1: Voronoi assignment (one (n_loc, m) MXU tile) --------------
    dpc = tile_cdist(x, centers, metric)          # comparable distances
    cell = (cell_in.astype(jnp.int32) if cell_in is not None
            else jnp.argmin(dpc, axis=1).astype(jnp.int32))
    # d(p, C) stays the true fp32 min over ALL centers: with a provided
    # assignment, d(p, c_cell) may exceed d_min by a knife-edge ulp — the
    # slacked Lemma-1 bound absorbs exactly that gap
    d_min = jnp.min(dpc, axis=1)

    # -- Phase 2: coalesce cells via capacity-padded all_to_all -------------
    dest = f[cell]
    payload = {
        "pts": (x, metric.dtype(0)),
        "ids": (ids, SENTINEL),
        "cell": (cell, jnp.int32(-1)),
    }
    send, dropped_c = _pack_by_dest(
        dest, jnp.ones((n_loc,), bool), payload, nranks, plan.cap_coal)
    recv = {
        k: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=True)
        for k, v in send.items()
    }
    W = recv["pts"].reshape(nranks * plan.cap_coal, -1)
    Wids = recv["ids"].reshape(-1)
    Wcell = recv["cell"].reshape(-1)
    Wvalid = Wids != SENTINEL
    W, Wids, Wcell, Wvalid = _cell_sort(
        Wcell, Wvalid, m, W, Wids, Wcell, Wvalid)
    Wgrp = jnp.where(Wvalid, Wcell, jnp.int32(-1))

    # -- Phase 3: intra-cell queries. Tiles flavor: group-aware fused
    # bitmask tile (cells are the level-1 cover, pruning at block
    # granularity). Tree flavor: level-synchronous traversal of the rank's
    # per-cell cover-tree forest — the in-cell levels BELOW the cell cover,
    # pruning inside each cell too. ---------------------------------------
    if traversal == "tree":
        nbrs, cnt, w_dists, w_pruned = tree_traverse(
            W, Wids, Wgrp, forest, eps, plan.k_cap, metric)
        w_sched = w_skip = jnp.int32(0)
    else:
        cnt, bits, w_sched, w_skip = nng_tile_bits_grouped(
            W, W, Wgrp, Wgrp, Wids, Wids, eps, metric=metric)
        nbrs = _bits_to_gathered_ids(bits, Wids, plan.k_cap)
        tq, tp = nng_tile_geometry(W.shape[0], W.shape[0], metric)
        w_dists = ((w_sched - w_skip).astype(jnp.float32)
                   * jnp.float32(tq * tp))
        w_pruned = jnp.float32(0)

    # -- Phase 4: ε-ghost exchange (Lemma 1, scale-aware fp32 slack) --------
    if ghost_mode == "ring":
        # ring flavor: no ghost copies are ever materialized — the
        # compacted coalesce block rotates and the Lemma-1 test rides
        # along as packed per-row cell bits (see ``_ghost_ring``)
        (Gids, gnbrs, gcnt, g_over, g_sched, g_skip, g_dists,
         g_pruned) = _ghost_ring(
            W, Wids, Wcell, Wvalid, Wgrp, centers, forest, axis=axis,
            nranks=nranks, eps=eps, two_eps_c=two_eps_c, metric=metric,
            plan=plan, traversal=traversal)
        overflow = (
            (dropped_c > 0) | g_over | jnp.any(cnt > plan.k_cap)
        )[None]
        tiles_skipped = (w_skip + g_skip).astype(jnp.float32)[None]
        tiles_scheduled = (w_sched + g_sched).astype(jnp.float32)[None]
        dists_evaluated = (w_dists + g_dists)[None]
        nodes_pruned = (w_pruned + g_pruned)[None]
        return (Wids, nbrs, cnt, Gids, gnbrs, gcnt, overflow,
                tiles_skipped, tiles_scheduled, dists_evaluated,
                nodes_pruned)

    tru, gbound = _lemma1_ghost_bound(x, centers, dpc, d_min, two_eps_c,
                                      metric)
    gmask = (tru <= gbound[:, None]) & (
        jnp.arange(m)[None, :] != cell[:, None])
    # cap ghost fanout per point: keep the g_per_pt nearest ghost cells
    gscore = jnp.where(gmask, tru, jnp.float32(3e38))
    gcells = jnp.argsort(gscore, axis=1)[:, : plan.g_per_pt].astype(jnp.int32)
    gvalid = jnp.take_along_axis(gmask, gcells, axis=1)
    g_dropped = jnp.sum(gmask) - jnp.sum(gvalid)
    # flatten (point, ghost-cell) pairs
    gp = jnp.repeat(jnp.arange(n_loc, dtype=jnp.int32), plan.g_per_pt)
    gc = gcells.reshape(-1)
    gv = gvalid.reshape(-1)
    gdest = f[gc]
    gpayload = {
        "pts": (x[gp], metric.dtype(0)),
        "ids": (ids[gp], SENTINEL),
        "cell": (gc, jnp.int32(-1)),
    }
    gsend, dropped_g = _pack_by_dest(gdest, gv, gpayload, nranks, plan.cap_ghost)
    grecv = {
        k: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=True)
        for k, v in gsend.items()
    }
    G = grecv["pts"].reshape(nranks * plan.cap_ghost, -1)
    Gids = grecv["ids"].reshape(-1)
    Gcell = grecv["cell"].reshape(-1)
    Gvalid = Gids != SENTINEL
    G, Gids, Gcell, Gvalid = _cell_sort(
        Gcell, Gvalid, m, G, Gids, Gcell, Gvalid)
    Ggrp = jnp.where(Gvalid, Gcell, jnp.int32(-1))

    # ghost G×W queries: a ghost copy carries its TARGET cell id, so cell
    # scoping (group equality / tree cell match) confines it to that cell;
    # its own W row sits in a different cell and is excluded by the group
    # test — and id inequality guards the degenerate single-cell case.
    if traversal == "tree":
        gnbrs, gcnt, g_dists, g_pruned = tree_traverse(
            G, Gids, Ggrp, forest, eps, plan.k_cap, metric)
        g_sched = g_skip = jnp.int32(0)
    else:
        gcnt, gbits, g_sched, g_skip = nng_tile_bits_grouped(
            G, W, Ggrp, Wgrp, Gids, Wids, eps, metric=metric)
        gnbrs = _bits_to_gathered_ids(gbits, Wids, plan.k_cap)
        gtq, gtp = nng_tile_geometry(G.shape[0], W.shape[0], metric)
        g_dists = ((g_sched - g_skip).astype(jnp.float32)
                   * jnp.float32(gtq * gtp))
        g_pruned = jnp.float32(0)

    overflow = (
        (dropped_c > 0) | (dropped_g > 0) | (g_dropped > 0)
        | jnp.any(cnt > plan.k_cap) | jnp.any(gcnt > plan.k_cap)
    )[None]
    tiles_skipped = (w_skip + g_skip).astype(jnp.float32)[None]
    tiles_scheduled = (w_sched + g_sched).astype(jnp.float32)[None]
    dists_evaluated = (w_dists + g_dists)[None]
    nodes_pruned = (w_pruned + g_pruned)[None]
    return (Wids, nbrs, cnt, Gids, gnbrs, gcnt, overflow,
            tiles_skipped, tiles_scheduled, dists_evaluated, nodes_pruned)


def landmark_run(
    points,
    eps: float,
    centers,
    f,
    mesh: Mesh,
    plan: LandmarkPlan,
    *,
    metric="euclidean",
    axis: str = "ring",
    traversal: str = "tiles",
    forest: dict | None = None,
    cell=None,
    ghost_mode: str = "coll",
):
    """Distributed landmark ε-NNG. ``ghost_mode`` selects the Phase 4
    schedule: ``"coll"`` (capacity-padded all_to_all scatter of ghost
    copies) or ``"ring"`` (double-buffered rotation of the compacted
    coalesce block with in-kernel Lemma-1 scoping — needs
    ``plan.cap_rank`` from ``plan_landmark_device``). ``"auto"`` must be
    resolved upstream (``resolve_ghost_mode``) — the mode is part of the
    compiled program. Returns
    (Wids, nbrs, cnt, Gids, gnbrs, gcnt, overflow, tiles_skipped,
    tiles_scheduled, dists_evaluated, nodes_pruned): owned-point and
    ghost-copy neighbor lists keyed by global point id, plus per-rank
    (nranks,) counters — grouped-tile blocks skipped/scheduled (int32,
    tiles flavor) by the cell-sorted fast path, and pair distances
    evaluated / tree frontier pairs pruned (float32, both flavors; the
    tiles flavor counts tq×tp pairs per live block, the tree flavor counts
    frontier pairs of the level-synchronous per-cell traversal). The union of (Wids → nbrs)
    and (Gids → gnbrs) edges is the exact ε-graph when ``overflow`` is
    False.

    ``traversal="tree"`` needs ``forest`` (the rank-stacked per-cell
    cover-tree tables from ``flat_tree.build_cell_forests`` +
    ``stack_device_forests``) AND ``cell`` (the (n,) Voronoi assignment
    those forests were built from — fed to the engine so Phase 1 cannot
    diverge from the forest scoping on argmin near-ties).
    """
    met = get_metric(metric)
    nranks = mesh.shape[axis]
    n, _ = points.shape
    assert n % nranks == 0, (n, nranks)
    assert ghost_mode in ("coll", "ring"), (
        f"ghost_mode={ghost_mode!r}: 'auto' is resolved upstream "
        "(resolve_ghost_mode) — the engine compiles one mode")
    if ghost_mode == "ring":
        assert plan.cap_rank > 0, (
            "ghost_mode='ring' needs plan.cap_rank (use "
            "plan_landmark_device, or set cap_rank explicitly)")
    fn = _landmark_fn(mesh, float(eps), met, plan, axis, _pallas_mode(),
                      traversal, ghost_mode)
    if traversal == "tree":
        assert forest is not None, "traversal='tree' needs stacked forests"
        assert cell is not None, ("traversal='tree' needs the cell "
                                  "assignment the forests were built from")
    with span("nng.put"):
        ids = _on_ring(mesh, axis, np.arange(n, dtype=np.int32))
        points = _on_ring(mesh, axis, points, met.dtype)
        centers = jnp.asarray(centers, met.dtype)
        f = jnp.asarray(f, jnp.int32)
        tree_args = ([_on_ring(mesh, axis, cell, np.int32)]
                     + [_on_ring(mesh, axis, forest[k])
                        for k in DeviceForest._fields]
                     if traversal == "tree" else [])
    return fn(points, ids, centers, f, *tree_args)


def landmark_nng(points, eps, centers, f, mesh, plan, **kw):
    """Deprecated alias of ``landmark_run`` (the PR 4 tuple API). Use
    ``repro.nng.build_nng(points, eps, partition="spatial", ...)`` instead
    — same engine, CSR ``NNGraph`` result, shared re-plan driver."""
    warnings.warn(
        "landmark_nng is deprecated; use repro.nng.build_nng(..., "
        "partition='spatial') or repro.core.distributed.landmark_run",
        DeprecationWarning, stacklevel=2)
    return landmark_run(points, eps, centers, f, mesh, plan, **kw)


@functools.lru_cache(maxsize=64)
def _landmark_fn(mesh, eps, metric, plan, axis, pallas_mode,
                 traversal="tiles", ghost_mode="coll"):
    """Memoized jitted shard_map program (see ``_systolic_fn``, including
    the ``pallas_mode`` key); the frozen
    ``LandmarkPlan`` is the static capacity key, so only genuine re-plans
    (grown capacities) pay a recompile. ``ghost_mode`` (resolved "coll" /
    "ring", never "auto") keys the Phase 4 schedule — the two modes are
    different collective programs with different output shapes."""
    nranks = mesh.shape[axis]
    body = functools.partial(
        _landmark_local, axis=axis, nranks=nranks, eps=eps,
        two_eps_c=2.0 * eps, metric=metric, plan=plan, traversal=traversal,
        ghost_mode=ghost_mode)
    in_specs = (P(axis, None), P(axis), P(), P())
    if traversal == "tree":
        in_specs = in_specs + (P(axis),) * (1 + _N_FOREST)   # cell + forest
    return jax.jit(_shard_map(
        body, mesh,
        in_specs=in_specs,
        out_specs=(P(axis), P(axis, None), P(axis),
                   P(axis), P(axis, None), P(axis), P(axis),
                   P(axis), P(axis), P(axis), P(axis)),
    ))
