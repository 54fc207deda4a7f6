"""The metric registry — one object per metric, every hookup in one place.

The paper's claim is exact fixed-radius graphs in *general metric spaces*;
this module is where a metric becomes a first-class value instead of a
string threaded through every layer. A ``Metric`` bundles:

  - the float64 host reference (``HostMetric`` — cover-tree build/query,
    brute-force oracle, planners),
  - the device comparable-distance function (``cdist`` — Voronoi phase,
    capacity counting, generic fallbacks),
  - the fused bitmask tile kernel, its group-aware variant, and the
    tree-frontier kernel hookups (Pallas + jnp oracle pairs),
  - the engine's geometry hooks: block summaries for the systolic
    triangle-inequality prune and the Lemma-1 ghost slack policy.

Kernel hookups are OPTIONAL: a metric registered with only ``cdist`` (plus
its host reference) runs end-to-end through the pure-jnp fallback path in
``repro.kernels.ops`` — slower, but exact. That is the extension contract:
adding a metric is ``register_metric(Metric(...))``, never an engine edit.

"Comparable" distances are any monotone transform of the true distance
(squared L2, raw Hamming counts, the L1 sum itself); ``true_device`` maps
them back because cover-tree / ghost arithmetic is additive. ``exact``
marks integer-valued metrics whose comparisons need no fp32 slack.

Metrics are identity-hashed (``eq=False``): the registry returns the same
object every call, so engine program memoization keys on them directly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .metrics_host import HostMetric, get_host_metric


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@dataclass(frozen=True, eq=False)
class Metric:
    """A registered metric: host reference + device hookups.

    Only ``name``, ``host`` and ``cdist`` are required — everything else
    has a metric-generic default (see module docstring for the fallback
    contract)."""

    name: str
    host: HostMetric                 # float64 host reference
    cdist: Callable                  # (x, y) -> (q, p) comparable dists, jnp
    dtype: Any = jnp.float32         # device point dtype
    exact: bool = False              # integer distances: zero-slack compares
    col_mult: int = 1                # kernel feature-axis pad multiple
    tile_q: int = 256                # fused-tile block shape (full tiles)
    tile_p: int = 512
    # comparable -> true distance on device (None = identity fp32 cast)
    true_device: Callable | None = None
    # row-aligned TRUE distance, (n, d), (n, d) -> (n,) fp32 — the on-device
    # forest builder's distance primitive. Diff-form arithmetic where the
    # metric allows it (no BLAS3 cancellation: builder radii stay ulp-exact
    # at any coordinate scale); None = generic per-row cdist fallback
    rowwise: Callable | None = None
    # fused bitmask tile kernel (systolic): pallas + jnp-oracle pair
    tile_pallas: Callable | None = None
    tile_ref: Callable | None = None
    # group-aware variant (landmark W/G phases)
    grouped_pallas: Callable | None = None
    grouped_ref: Callable | None = None
    # ghost-ring variant (landmark ghost_mode="ring": visiting block rows
    # carry packed Lemma-1 cell masks instead of materialized ghost copies)
    ghost_pallas: Callable | None = None
    ghost_ref: Callable | None = None
    # level-synchronous tree-frontier kernel (traversal="tree")
    frontier_pallas: Callable | None = None
    frontier_ref: Callable | None = None
    # systolic block summary: x -> (center, fp32 true radius); None =
    # first-point center (valid in ANY metric; euclidean overrides with
    # the tighter centroid)
    block_summary: Callable | None = None
    # accurate center-pair true distances for the prune bound:
    # (partner_centers (r, d), my_center (d,)) -> (r,) fp32
    center_dist: Callable | None = None
    # Lemma-1 ghost slack: (x, centers, tru, bound) -> (n,) fp32; None =
    # zero for exact metrics, scale-relative generic slack otherwise
    ghost_slack: Callable | None = None

    # -- derived helpers (metric-generic) -----------------------------------
    def comparable(self, eps: float) -> float:
        return self.host.comparable(eps)

    def true(self, c):
        if self.true_device is not None:
            return self.true_device(c)
        return jnp.asarray(c, jnp.float32)

    def rowwise_true(self, x, y):
        """Row-aligned true distances (the builder primitive); generic
        fallback evaluates ``cdist`` one aligned row pair at a time."""
        if self.rowwise is not None:
            return self.rowwise(x, y)
        f = lambda a, b: self.true(self.cdist(a[None, :], b[None, :]))[0, 0]
        return jax.vmap(f)(x, y)

    def tile_shape(self, q: int, p: int) -> tuple[int, int]:
        tq = self.tile_q if q >= self.tile_q else _round_up(max(q, 1), 8)
        tp = self.tile_p if p >= self.tile_p else _round_up(max(p, 1), 128)
        return tq, tp

    def summary(self, x):
        if self.block_summary is not None:
            return self.block_summary(x)
        c = x[0]
        r = jnp.max(self.true(self.cdist(x, c[None, :]))[:, 0])
        return c, r.astype(jnp.float32)

    def summary_dist(self, pc, c):
        if self.center_dist is not None:
            return self.center_dist(pc, c)
        return self.true(self.cdist(pc, c[None, :]))[:, 0]

    def lemma1_slack(self, x, centers, tru, bound):
        if self.ghost_slack is not None:
            return self.ghost_slack(x, centers, tru, bound)
        if self.exact:
            return jnp.zeros_like(bound)
        # generic float metric: relative slack on the row's distance scale;
        # over-inclusion only costs ghost copies, never exactness
        scale = jnp.max(tru, axis=1)
        return (scale + bound) * jnp.float32(1e-5) + jnp.float32(1e-6)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Metric] = {}


def register_metric(metric: Metric, *, overwrite: bool = False) -> Metric:
    """Register a metric under ``metric.name``; returns it for chaining."""
    if metric.name in _REGISTRY and not overwrite:
        raise ValueError(f"metric {metric.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[metric.name] = metric
    return metric


def get_metric(metric: str | Metric) -> Metric:
    """Resolve a metric name (or pass a ``Metric`` through unchanged)."""
    if isinstance(metric, Metric):
        return metric
    try:
        return _REGISTRY[metric]
    except KeyError:
        raise ValueError(
            f"unknown metric {metric!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_metrics() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# built-in metrics, registered from the existing kernel layer
# ---------------------------------------------------------------------------

def _euclidean_cdist(x, y):
    """Squared L2 via the fp32 BLAS3 expansion, the tile kernels' own
    arithmetic (``nng_tile.l2_sq_dists``: canonical norms, the dot at
    HIGHEST precision; on TPU the default is a single bf16 pass, far
    outside the fp32 slack the Lemma-1 ghost bound assumes)."""
    from repro.kernels.nng_tile import l2_sq_dists
    return jnp.maximum(l2_sq_dists(x, y), 0.0)


def _euclidean_true(c):
    return jnp.sqrt(jnp.maximum(jnp.asarray(c, jnp.float32), 0.0))


def _euclidean_block_summary(x):
    xf = x.astype(jnp.float32)
    c = jnp.mean(xf, axis=0)
    r = jnp.sqrt(jnp.max(jnp.sum((xf - c[None, :]) ** 2, axis=-1)))
    return c, r


def _euclidean_center_dist(pc, c):
    # direct diff form: no BLAS3 cancellation on large-offset data, so the
    # prune bound's relative slack is a true error bound
    return jnp.sqrt(jnp.sum((pc - c[None, :]) ** 2, axis=-1))


def _euclidean_ghost_slack(x, centers, tru, bound):
    xf = x.astype(jnp.float32)
    cf = centers.astype(jnp.float32)
    sx = jnp.sum(xf * xf, axis=-1)              # (n,) per-point ‖p‖²
    sc = jnp.max(jnp.sum(cf * cf, axis=-1))     # worst center the row meets
    scale2 = sx + sc + 2.0 * jnp.sqrt(sx * sc)  # >= (‖p‖ + max‖c‖)² per row
    # DIMENSION-AWARE error coefficient: the BLAS3 accumulation error in
    # the squared distances grows ~√d with the contraction length (see the
    # PR 2 regression tests at d = 4 .. 128)
    coef = jnp.float32((8.0 + 2.0 * float(np.sqrt(x.shape[1]))) * 6e-8)
    return (coef * scale2 / jnp.maximum(bound, jnp.float32(1e-30))
            + jnp.float32(1e-5) * bound + jnp.float32(1e-6))


def _euclidean_rowwise(x, y):
    diff = jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32)
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


def _hamming_rowwise(x, y):
    xor = jnp.bitwise_xor(x, y)
    return jnp.sum(jax.lax.population_count(xor).astype(jnp.int32),
                   axis=-1).astype(jnp.float32)


def _l1_rowwise(x, y):
    return jnp.sum(jnp.abs(jnp.asarray(x, jnp.float32)
                           - jnp.asarray(y, jnp.float32)), axis=-1)


def _hamming_cdist(x, y):
    xor = jnp.bitwise_xor(x[:, None, :], y[None, :, :])
    return jnp.sum(jax.lax.population_count(xor).astype(jnp.int32),
                   axis=-1).astype(jnp.float32)


def _l1_cdist(x, y):
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)


def _register_builtins() -> None:
    from repro.kernels import nng_tile as nt
    from repro.kernels import tree_frontier as tf

    register_metric(Metric(
        name="euclidean",
        host=get_host_metric("euclidean"),
        cdist=_euclidean_cdist,
        true_device=_euclidean_true,
        rowwise=_euclidean_rowwise,
        dtype=jnp.float32,
        col_mult=128,
        tile_q=256, tile_p=512,
        tile_pallas=nt.nng_tile_pallas,
        tile_ref=nt.nng_tile_ref,
        grouped_pallas=nt.nng_tile_grouped_pallas,
        grouped_ref=nt.nng_tile_grouped_ref,
        ghost_pallas=nt.nng_tile_ghost_pallas,
        ghost_ref=nt.nng_tile_ghost_ref,
        frontier_pallas=tf.tree_frontier_pallas,
        frontier_ref=tf.tree_frontier_ref,
        block_summary=_euclidean_block_summary,
        center_dist=_euclidean_center_dist,
        ghost_slack=_euclidean_ghost_slack,
    ))
    register_metric(Metric(
        name="hamming",
        host=get_host_metric("hamming"),
        cdist=_hamming_cdist,
        rowwise=_hamming_rowwise,
        dtype=jnp.uint32,
        exact=True,
        tile_q=128, tile_p=256,
        tile_pallas=nt.nng_tile_hamming_pallas,
        tile_ref=nt.nng_tile_hamming_ref,
        grouped_pallas=nt.nng_tile_grouped_hamming_pallas,
        grouped_ref=nt.nng_tile_grouped_hamming_ref,
        ghost_pallas=nt.nng_tile_ghost_hamming_pallas,
        ghost_ref=nt.nng_tile_ghost_hamming_ref,
        frontier_pallas=tf.tree_frontier_hamming_pallas,
        frontier_ref=tf.tree_frontier_hamming_ref,
    ))
    # the PR 5 metric: L1 through its own Pallas tile/grouped/frontier
    # kernels — registered exactly like the seed metrics, zero engine edits
    register_metric(Metric(
        name="manhattan",
        host=get_host_metric("manhattan"),
        cdist=_l1_cdist,
        rowwise=_l1_rowwise,
        dtype=jnp.float32,
        tile_q=128, tile_p=256,
        tile_pallas=nt.nng_tile_l1_pallas,
        tile_ref=nt.nng_tile_l1_ref,
        grouped_pallas=nt.nng_tile_grouped_l1_pallas,
        grouped_ref=nt.nng_tile_grouped_l1_ref,
        ghost_pallas=nt.nng_tile_ghost_l1_pallas,
        ghost_ref=nt.nng_tile_ghost_l1_ref,
        frontier_pallas=tf.tree_frontier_l1_pallas,
        frontier_ref=tf.tree_frontier_l1_ref,
    ))


_register_builtins()
