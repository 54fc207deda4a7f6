"""FlatCoverTree (levelized SoA cover trees) + device tree traversal tests:

- host flat query vs brute force on both metrics (single tree and cell
  forest with query scoping), incl. the PR 2 collinear-boundary scale~1e8
  regression geometry,
- traversal counters sanity (dists_evaluated / nodes_pruned),
- tree_frontier kernel interpret-mode vs jnp-oracle parity,
- single-process device traversal vs the host flat query,
- 8-simulated-device systolic + landmark engines with traversal="tree"
  vs brute force on both metrics, with the tree path evaluating strictly
  fewer pair distances than the grouped-tile path, and the device
  capacity planner yielding an overflow-free first run.
"""
import os

import numpy as np
import pytest

from repro.core.brute import brute_force_graph
from repro.core.covertree import build_covertree
from repro.core.flat_tree import (TraversalStats, build_cell_forests,
                                  flatten_covertree, flatten_forest,
                                  stack_device_forests)
from repro.core.graph import EpsGraph
from tests.helpers import run_subprocess, safe_eps


@pytest.mark.parametrize("metric,gen", [
    ("euclidean", lambda rng, n: rng.normal(size=(n, 6)).astype(np.float32)),
    ("hamming", lambda rng, n: rng.integers(0, 2**32, size=(n, 6),
                                            dtype=np.uint32)),
])
def test_flat_query_equals_brute(metric, gen):
    rng = np.random.default_rng(11)
    pts = gen(rng, 700)
    eps = safe_eps(pts, metric)
    flat = flatten_covertree(build_covertree(pts, metric))
    stats = TraversalStats()
    qi, pj = flat.query_host(pts, eps, stats=stats)
    g = EpsGraph(len(pts), qi, pj)
    gb = brute_force_graph(pts, eps, metric)
    assert g == gb
    # the traversal must do real work and really prune
    assert 0 < stats.dists_evaluated
    assert stats.nodes_pruned > 0
    assert stats.levels >= 2


def test_flat_forest_cell_scoping():
    """A forest query with qcells must return exactly the intra-cell pairs."""
    rng = np.random.default_rng(3)
    pts = (rng.normal(size=(500, 5)) * 2).astype(np.float32)
    cell = (pts[:, 0] > 0).astype(np.int64)
    trees, cells, gids = [], [], []
    for ci in (0, 1):
        members = np.flatnonzero(cell == ci)
        trees.append(build_covertree(pts[members], "euclidean"))
        cells.append(ci)
        gids.append(members)
    flat = flatten_forest(trees, cells=cells, gids=gids, points=pts)
    eps = safe_eps(pts, "euclidean", target_quantile=0.3)
    qi, pj = flat.query_host(pts, eps, qcells=cell)
    got = set(zip(qi.tolist(), pj.tolist()))
    from repro.core.metrics_host import get_host_metric
    met = get_host_metric("euclidean")
    d = np.asarray(met.true(met.cdist(pts, pts)))
    want = set(zip(*np.nonzero((d <= eps)
                               & (cell[:, None] == cell[None, :]))))
    assert got == want


def test_flat_collinear_scale_regression():
    """The flat traversal inherits the PR 2 scale-relative expand slack:
    collinear fp32 points at distance scale ~1e8 must not drop boundary
    neighbors (same construction as test_covertree's regression)."""
    S = float(2**17)
    M = 80
    rng = np.random.default_rng(0)
    ms = np.sort(rng.choice(400, size=200, replace=False))
    pts = (ms[:, None] * S * np.ones((1, 2))).astype(np.float32)
    eps = float(np.sqrt(2.0 * (M * S) ** 2))
    want = int((np.abs(ms[:, None] - ms[None, :]) <= M).sum() - len(ms))
    flat = flatten_covertree(build_covertree(pts, "euclidean", leaf_size=4))
    qi, pj = flat.query_host(pts, eps)
    got = int((qi != pj).sum())
    assert got == want, f"dropped {want - got} collinear boundary neighbors"


def test_flat_structure_invariants():
    """Levelized tables must tile the tree: contiguous child ranges,
    parent positions consistent, every leaf covered exactly once."""
    rng = np.random.default_rng(5)
    pts = rng.normal(size=(400, 4)).astype(np.float32)
    tree = build_covertree(pts)
    flat = tree.flat()
    assert flat.level_width % 32 == 0
    assert flat.leaf_ids.shape[0] % 32 == 0
    assert flat.num_leaves == len(pts)
    assert sorted(flat.leaf_ids[flat.leaf_ids != 2**31 - 1].tolist()) == \
        list(range(len(pts)))
    for lvl in range(flat.num_levels - 1):
        valid = np.flatnonzero(flat.node_cell[lvl] >= 0)
        lo = flat.child_lo[lvl][valid]
        hi = flat.child_hi[lvl][valid]
        # non-empty children ranges are disjoint, ordered, and together
        # with the empty (leaf) ranges they tile level l+1 exactly
        ne = hi > lo
        order = np.argsort(lo[ne])
        assert (hi[ne][order][:-1] <= lo[ne][order][1:]).all()
        nxt_valid = int(np.sum(flat.node_cell[lvl + 1] >= 0))
        assert int((hi - lo).sum()) == nxt_valid
        # parent_pos of level l+1 points back into level l's valid slots
        for j in np.flatnonzero(flat.node_cell[lvl + 1] >= 0):
            p = flat.parent_pos[lvl + 1][j]
            assert flat.child_lo[lvl][p] <= j < flat.child_hi[lvl][p]


# ---------------------------------------------------------------------------
# frontier kernel: interpret mode vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["euclidean", "hamming", "manhattan"])
@pytest.mark.parametrize("nq,N", [(7, 32), (70, 96), (300, 544)])
def test_tree_frontier_interpret_matches_jnp(metric, nq, N):
    import jax.numpy as jnp
    from repro.kernels import tree_frontier_step
    from repro.kernels.nng_tile import _pack_words

    rng = np.random.default_rng(nq + N)
    if metric in ("euclidean", "manhattan"):
        q = rng.normal(size=(nq, 5)).astype(np.float32)
        c = rng.normal(size=(N, 5)).astype(np.float32)
        eps = 1.2 if metric == "euclidean" else 3.5
        rad = np.abs(rng.normal(size=N)).astype(np.float32) * 0.5
    else:
        q = rng.integers(0, 2**32, size=(nq, 4), dtype=np.uint32)
        c = rng.integers(0, 2**32, size=(N, 4), dtype=np.uint32)
        eps = 40
        rad = rng.integers(0, 30, size=N).astype(np.float32)
    leaf = (rng.random(N) < 0.4).astype(np.int32)
    act = np.asarray(_pack_words(jnp.asarray(rng.random((nq, N)) < 0.6)))
    prev = os.environ.get("REPRO_PALLAS", "")
    try:
        os.environ["REPRO_PALLAS"] = "interpret"
        ei, xi = tree_frontier_step(q, c, rad, leaf, act, eps, metric)
        os.environ["REPRO_PALLAS"] = "jnp"
        ej, xj = tree_frontier_step(q, c, rad, leaf, act, eps, metric)
    finally:
        os.environ["REPRO_PALLAS"] = prev
    assert (np.asarray(ei) == np.asarray(ej)).all()
    assert (np.asarray(xi) == np.asarray(xj)).all()
    # survivors are always a subset of the active set
    assert (np.asarray(ei) & ~act).sum() == 0
    assert (np.asarray(xi) & ~act).sum() == 0


def test_device_traversal_matches_host_flat_query():
    """Single-process device traversal (jnp kernel path) vs the float64
    host flat query on a cell forest — identical edges, and the counter
    definitions line up (device fp32 slack may expand slightly more, so
    device dists >= host dists but both prune)."""
    import jax.numpy as jnp
    from repro.core.distributed import DeviceForest, tree_traverse

    rng = np.random.default_rng(7)
    n = 600
    pts = (rng.normal(size=(n, 6)) * 2).astype(np.float32)
    cell = (rng.random(n) * 4).astype(np.int64)
    f = np.zeros(4, np.int64)
    forests = build_cell_forests(pts, cell, f, 1)
    eps = safe_eps(pts, "euclidean", target_quantile=0.2)

    hstats = TraversalStats()
    qi, pj = forests[0].query_host(pts, eps, qcells=cell, stats=hstats)
    keep = qi != pj                      # device path excludes self pairs
    g_host = EpsGraph(n, qi[keep], pj[keep])

    tabs = stack_device_forests(forests)
    fr = DeviceForest.from_tables({k: v[0] for k, v in tabs.items()})
    nbrs, cnt, dists, pruned = tree_traverse(
        jnp.asarray(pts), jnp.arange(n, dtype=jnp.int32),
        jnp.asarray(cell, np.int32), fr, float(eps), 256, "euclidean")
    nbrs = np.asarray(nbrs)
    ii, kk = np.nonzero(nbrs != 2**31 - 1)
    g_dev = EpsGraph(n, ii, nbrs[ii, kk])
    assert g_dev == g_host
    assert int(np.asarray(cnt).sum()) == len(qi[keep])
    assert int(dists) >= hstats.dists_evaluated > 0
    assert int(pruned) > 0


@pytest.mark.parametrize("scope", ["cells", "ghost_bits"])
def test_device_traversal_query_blocks_match_whole(monkeypatch, scope):
    """Traversing the queries in padded blocks (``lax.map``) gives the
    whole-batch result: same neighbor tables, counts and counters."""
    import jax.numpy as jnp
    from repro.core.distributed import DeviceForest, device, tree_traverse

    rng = np.random.default_rng(11)
    n = 600                                 # not a multiple of the block
    pts = (rng.normal(size=(n, 6)) * 2).astype(np.float32)
    cell = (rng.random(n) * 4).astype(np.int64)
    tabs = stack_device_forests(build_cell_forests(pts, cell,
                                                   np.zeros(4, np.int64), 1))
    fr = DeviceForest.from_tables({k: v[0] for k, v in tabs.items()})
    eps = safe_eps(pts, "euclidean", target_quantile=0.2)
    args = (jnp.asarray(pts), jnp.arange(n, dtype=jnp.int32))
    if scope == "cells":
        kw = dict(qcells=jnp.asarray(cell, np.int32))
    else:
        kw = dict(qcells=None, qghost_bits=jnp.asarray(
            rng.integers(0, 16, size=(n, 1)), jnp.uint32))

    def run():
        return [np.asarray(a) for a in tree_traverse(
            *args, forest=fr, eps=float(eps), k_cap=256, metric="euclidean",
            **kw)]

    whole = run()
    monkeypatch.setattr(device, "_TRAVERSE_BLOCK_ELEMS", 1)  # 128-row blocks
    blocked = run()
    assert whole[1].sum() > 0
    for a, b in zip(whole, blocked):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# 8 simulated devices: both engines, traversal="tree", both metrics
# ---------------------------------------------------------------------------

_TREE_8DEV_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import (landmark_nng, make_nng_mesh,
                                    plan_landmark_device, systolic_nng)
from repro.core.flat_tree import (build_block_forests, build_cell_forests,
                                  stack_device_forests)
from repro.core.landmark import lpt_assignment, select_centers
from repro.core.metrics_host import get_host_metric
from repro.core.graph import EpsGraph
from repro.core.brute import brute_force_graph
from repro.data import synthetic_pointset

SEN = 2**31 - 1
mesh = make_nng_mesh(8)

def gap_safe_eps(pts, target=1.0):
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(len(pts), 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    assert vals[j + 1] - vals[j] > 1e-5
    return 0.5 * (vals[j] + vals[j + 1])

def edges_of(ids, nb, n):
    ids = np.asarray(ids); nb = np.asarray(nb)
    valid = ids != SEN
    ii, kk = np.nonzero((nb != SEN) & valid[:, None])
    return ids[ii], nb[ii, kk]

for metric, n, dim, eps in (("euclidean", 1024, 6, None),
                            ("hamming", 512, 8, 40)):
    pts = synthetic_pointset(n, dim, metric, seed=13)
    if eps is None:
        eps = gap_safe_eps(pts)
    gb = brute_force_graph(pts, eps, metric)

    # systolic, tree traversal
    forest = stack_device_forests(build_block_forests(pts, 8, metric))
    nbrs, cnt, ovf, skipped, dists, pruned = systolic_nng(
        jnp.asarray(pts), float(eps), mesh, metric=metric, k_cap=512,
        traversal="tree", forest=forest)
    assert not bool(np.asarray(ovf).any()), metric
    ii, kk = np.nonzero(np.asarray(nbrs) != SEN)
    g = EpsGraph(n, ii, np.asarray(nbrs)[ii, kk])
    assert g == gb, f"systolic tree vs brute ({metric})"
    assert int(np.asarray(pruned).sum()) > 0, metric
    # strictly fewer pair distances than the dense-tile ring
    _, _, _, _, dists_tiles, _ = systolic_nng(
        jnp.asarray(pts), float(eps), mesh, metric=metric, k_cap=512)
    assert int(np.asarray(dists).sum()) < int(np.asarray(dists_tiles).sum())

    # landmark, tree traversal, device-planned capacities (no overflow on
    # the first run: the counting pass is exact)
    met = get_host_metric(metric)
    rng = np.random.default_rng(5)
    m = 16
    cpts = pts[select_centers(n, m, rng)]
    cell = np.argmin(met.cdist(pts, cpts), axis=1)
    f = lpt_assignment(np.bincount(cell, minlength=m), 8)
    plan = plan_landmark_device(pts, cpts, np.asarray(f, np.int32),
                                float(eps), mesh, metric=metric, k_cap=512)
    cforest = stack_device_forests(build_cell_forests(pts, cell, f, 8, metric))
    out = landmark_nng(jnp.asarray(pts), float(eps), jnp.asarray(cpts),
                       jnp.asarray(f, np.int32), mesh, plan, metric=metric,
                       traversal="tree", forest=cforest, cell=cell)
    assert not bool(np.asarray(out[6]).any()), f"device plan overflowed ({metric})"
    s1, d1 = edges_of(out[0], out[1], n)
    s2, d2 = edges_of(out[3], out[4], n)
    gl = EpsGraph(n, np.concatenate([s1, s2]), np.concatenate([d1, d2]))
    assert gl == gb, f"landmark tree vs brute ({metric})"
    # strictly below the grouped-tile path's distance work
    out_t = landmark_nng(jnp.asarray(pts), float(eps), jnp.asarray(cpts),
                         jnp.asarray(f, np.int32), mesh, plan, metric=metric)
    assert not bool(np.asarray(out_t[6]).any())
    assert (int(np.asarray(out[9]).sum())
            < int(np.asarray(out_t[9]).sum())), metric
print("TREE_8DEV_OK")
"""


def test_tree_traversal_engines_8dev():
    out = run_subprocess(_TREE_8DEV_CODE, devices=8, timeout=1200)
    assert "TREE_8DEV_OK" in out
