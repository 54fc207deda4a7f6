"""Distributed ε-NNG algorithms (host-simulated + device shard_map) must all
produce the exact brute-force graph."""
import numpy as np
import pytest

from repro.core.brute import brute_force_graph
from repro.core.host_algos import landmark_host, systolic_ring_host
from repro.core.landmark import (ghost_membership, lpt_assignment,
                                 select_centers, voronoi_assign)
from repro.core.snn import snn_graph
from repro.data import synthetic_pointset
from tests.helpers import given, run_subprocess, settings, st


def clustered(n, d, seed):
    return synthetic_pointset(n, d, "euclidean", seed=seed)


@pytest.mark.parametrize("nranks", [1, 2, 5, 8])
def test_systolic_matches_brute(nranks):
    pts = clustered(1500, 8, 0)
    gb = brute_force_graph(pts, 1.0)
    g, stats = systolic_ring_host(pts, 1.0, nranks)
    assert g == gb
    assert stats.comm_bytes["ring"] >= 0


@pytest.mark.parametrize("nranks,ghost_mode,strategy", [
    (1, "coll", "random"), (4, "coll", "random"), (4, "ring", "random"),
    (8, "coll", "greedy"), (7, "ring", "greedy"),
])
def test_landmark_matches_brute(nranks, ghost_mode, strategy):
    pts = clustered(1500, 8, 1)
    gb = brute_force_graph(pts, 1.0)
    g, stats = landmark_host(pts, 1.0, nranks, ghost_mode=ghost_mode,
                             center_strategy=strategy, seed=2)
    assert g == gb
    assert stats.partition_s >= 0 and stats.ghost_s >= 0


def test_snn_matches_brute():
    pts = clustered(2000, 10, 2)
    assert snn_graph(pts, 1.0) == brute_force_graph(pts, 1.0)


def test_hamming_distributed():
    pts = synthetic_pointset(800, 8, "hamming", seed=3)
    eps = 40
    gb = brute_force_graph(pts, eps, "hamming")
    g1, _ = systolic_ring_host(pts, eps, 4, metric="hamming")
    g2, _ = landmark_host(pts, eps, 4, metric="hamming", seed=5)
    assert g1 == gb and g2 == gb


def test_lpt_balance():
    rng = np.random.default_rng(0)
    sizes = rng.integers(1, 1000, 64)
    f = lpt_assignment(sizes, 8)
    loads = np.bincount(f, weights=sizes, minlength=8)
    # Graham bound: max load <= (4/3 - 1/3m) * OPT; OPT >= mean
    assert loads.max() <= (4 / 3) * max(sizes.sum() / 8, sizes.max()) + 1


def test_ghost_lemma_soundness():
    """Every cross-cell ε-pair's endpoints satisfy the Lemma-1 ghost bound."""
    pts = clustered(600, 5, 4)
    eps = 1.0
    rng = np.random.default_rng(0)
    centers = select_centers(len(pts), 16, rng)
    cell, d_pC = voronoi_assign(pts, pts[centers], "euclidean")
    from repro.core.metrics_host import get_host_metric
    met = get_host_metric("euclidean")
    dmat = np.asarray(met.true(met.cdist(pts, pts[centers])))
    g = ghost_membership(dmat, cell, d_pC, eps)
    gb = brute_force_graph(pts, eps)
    for i, j in zip(gb.src, gb.dst):
        ci, cj = cell[i], cell[j]
        if ci != cj:
            assert g[i, cj] and g[j, ci], (i, j)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(40, 300), nranks=st.integers(1, 9),
       seed=st.integers(0, 1000), mode=st.sampled_from(["coll", "ring"]))
def test_property_all_algorithms_agree(n, nranks, seed, mode):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, 4)).astype(np.float32) * 2
    from tests.helpers import safe_eps
    eps = safe_eps(pts, "euclidean", target_quantile=0.3)
    gb = brute_force_graph(pts, eps)
    g1, _ = systolic_ring_host(pts, eps, nranks)
    g2, _ = landmark_host(pts, eps, nranks, ghost_mode=mode, seed=seed)
    assert g1 == gb and g2 == gb


# ---------------------------------------------------------------------------
# device (shard_map) engine — 8 host devices in a subprocess
# ---------------------------------------------------------------------------

_DEVICE_CODE = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import (systolic_nng, landmark_nng, make_nng_mesh,
                                    LandmarkPlan)
from repro.core.landmark import lpt_assignment, select_centers
from repro.core.brute import brute_force_graph
from repro.core.graph import EpsGraph
from repro.core.metrics_host import get_host_metric
from repro.data import synthetic_pointset

SEN = 2**31 - 1
rng = np.random.default_rng(3)
n = 2048
pts = synthetic_pointset(n, 6, "euclidean", seed=9)
# the device engine evaluates distances in fp32 on the MXU; the oracle must
# use the SAME arithmetic (tile_cdist) so knife-edge pairs at the eps
# boundary classify identically (exactness = identical edge set under the
# declared fp32 distance function, as in the paper's float implementation)
from repro.core.distributed.device import tile_cdist
eps = 1.0
_d2 = np.asarray(tile_cdist(jnp.asarray(pts), jnp.asarray(pts), "euclidean"))
_ii, _jj = np.nonzero(_d2 <= eps * eps)
_keep = _ii < _jj
gb = EpsGraph(n, _ii[_keep], _jj[_keep])
mesh = make_nng_mesh(8)

nbrs, cnt, ovf, skipped, dists, pruned = systolic_nng(
    jnp.asarray(pts), float(eps), mesh, k_cap=512)
assert not bool(np.asarray(ovf).any())
nbrs = np.asarray(nbrs)
ii, kk = np.nonzero(nbrs != SEN)
assert EpsGraph(n, ii, nbrs[ii, kk]) == gb, "systolic mismatch"

# overflow flag fires with tiny k_cap
_, cnt2, ovf2, *_rest = systolic_nng(jnp.asarray(pts), eps, mesh, k_cap=1)
assert bool(np.asarray(ovf2).any()) == bool((np.asarray(cnt2) > 1).any())

m = 24
met = get_host_metric("euclidean")
cidx = select_centers(n, m, rng)
cpts = pts[cidx]
cell = np.argmin(met.cdist(pts, cpts), axis=1)
sizes = np.bincount(cell, minlength=m)
f = lpt_assignment(sizes, 8)
plan = LandmarkPlan(m_centers=m, cap_coal=int(sizes.max())+32, cap_ghost=2048,
                    g_per_pt=m, k_cap=512)
(Wids, wn, wc, Gids, gn, gc, ovf, tskip, tsched, ldists,
 lpruned) = landmark_nng(
    jnp.asarray(pts), eps, jnp.asarray(cpts), jnp.asarray(f, np.int32),
    mesh, plan)
assert not bool(np.asarray(ovf).any())
assert int(np.asarray(tskip).sum()) > 0, "cell-sorted buffers must skip tiles"
assert int(np.asarray(tsched).sum()) > int(np.asarray(tskip).sum())
src, dst = [], []
for idsv, nb in ((np.asarray(Wids), np.asarray(wn)),
                 (np.asarray(Gids), np.asarray(gn))):
    valid = idsv != SEN
    ii, kk = np.nonzero((nb != SEN) & valid[:, None])
    src.append(idsv[ii]); dst.append(nb[ii, kk])
assert EpsGraph(n, np.concatenate(src), np.concatenate(dst)) == gb, "landmark"

# hamming on device
hpts = synthetic_pointset(1024, 8, "hamming", seed=4)
heps = 40
hgb = brute_force_graph(hpts, heps, "hamming")
nbrs, cnt, ovf, skipped, hdists, hpruned = systolic_nng(
    jnp.asarray(hpts), heps, mesh, metric="hamming", k_cap=256)
nbrs = np.asarray(nbrs)
ii, kk = np.nonzero(nbrs != SEN)
assert EpsGraph(1024, ii, nbrs[ii, kk]) == hgb, "hamming systolic"
print("DEVICE_OK")
"""


def test_device_engine_exact_8dev():
    out = run_subprocess(_DEVICE_CODE, devices=8)
    assert "DEVICE_OK" in out


# ---------------------------------------------------------------------------
# block-summary pruning (host mirror + device fast path)
# ---------------------------------------------------------------------------

def test_host_block_pruning_fires_and_exact():
    from repro.data import blocked_clusters
    pts = blocked_clusters(2000, 4, 8)
    gb = brute_force_graph(pts, 1.0)
    g, stats = systolic_ring_host(pts, 1.0, 8)
    assert stats.tiles_skipped > 0
    assert stats.tiles_scheduled > stats.tiles_skipped  # self tiles remain
    assert g == gb
    # pruning must be a pure optimization: identical edges with it disabled
    g2, st2 = systolic_ring_host(pts, 1.0, 8, prune=False)
    assert st2.tiles_skipped == 0 and g2 == gb


def test_host_block_pruning_conservative_on_mixed_blocks():
    """Index-shuffled clusters give huge block radii: pruning never fires
    but exactness must hold (the skip test is conservative)."""
    from repro.data import blocked_clusters
    pts = blocked_clusters(1200, 4, 6, seed=3)
    pts = pts[np.random.default_rng(0).permutation(len(pts))]
    g, stats = systolic_ring_host(pts, 1.0, 6)
    assert stats.tiles_skipped == 0
    assert g == brute_force_graph(pts, 1.0)


_PRUNE_CODE = r"""
import numpy as np, jax.numpy as jnp
from repro.core.distributed import systolic_nng, make_nng_mesh
from repro.core.brute import brute_force_graph
from repro.core.graph import EpsGraph
from repro.core.host_algos import systolic_ring_host

SEN = 2**31 - 1
rng = np.random.default_rng(0)
from repro.data import blocked_clusters
pts = blocked_clusters(2048, 4, 8)
n = len(pts)
eps = 1.0
mesh = make_nng_mesh(8)

nbrs, cnt, ovf, skipped, dists, pruned = systolic_nng(
    jnp.asarray(pts), eps, mesh, k_cap=512)
assert not bool(np.asarray(ovf).any())
nskip = int(np.asarray(skipped).sum())
assert nskip > 0, "clustered blocks must prune tiles"
ii, kk = np.nonzero(np.asarray(nbrs) != SEN)
g = EpsGraph(n, ii, np.asarray(nbrs)[ii, kk])
gb = brute_force_graph(pts, eps)
assert g == gb, "device pruned graph != brute force"
gh, stats = systolic_ring_host(pts, eps, 8)
assert g == gh, "device pruned graph != host systolic"
assert stats.tiles_skipped > 0

# pruning off -> same edges, zero skip counter
nbrs2, _, ovf2, skipped2, dists2, _p2 = systolic_nng(
    jnp.asarray(pts), eps, mesh, k_cap=512, prune=False)
assert not bool(np.asarray(ovf2).any())
assert int(np.asarray(skipped2).sum()) == 0
ii2, kk2 = np.nonzero(np.asarray(nbrs2) != SEN)
assert EpsGraph(n, ii2, np.asarray(nbrs2)[ii2, kk2]) == gb

# hamming fast path: per-block bit-cluster centers, far apart in popcount
nblocks, w = 8, 8
hctr = rng.integers(0, 2**32, size=(nblocks, w), dtype=np.uint32)
hpts = np.repeat(hctr, 128, axis=0)
nh = len(hpts)
word = rng.integers(0, w, size=(nh, 3))
bit = rng.integers(0, 32, size=(nh, 3)).astype(np.uint32)
for t in range(3):  # flip <=3 bits per point: intra<=6, inter~128
    hpts[np.arange(nh), word[:, t]] ^= (np.uint32(1) << bit[:, t])
heps = 12
hnbrs, hcnt, hovf, hskip, _hd, _hp = systolic_nng(
    jnp.asarray(hpts), heps, mesh, metric="hamming", k_cap=256)
assert not bool(np.asarray(hovf).any())
assert int(np.asarray(hskip).sum()) > 0, "hamming blocks must prune"
hi, hk = np.nonzero(np.asarray(hnbrs) != SEN)
hg = EpsGraph(nh, hi, np.asarray(hnbrs)[hi, hk])
assert hg == brute_force_graph(hpts, heps, "hamming"), "hamming pruned graph"
hgh, hstats = systolic_ring_host(hpts, heps, 8, metric="hamming")
assert hg == hgh and hstats.tiles_skipped > 0
print("PRUNE_OK")
"""


def test_device_systolic_pruning_8dev():
    out = run_subprocess(_PRUNE_CODE, devices=8)
    assert "PRUNE_OK" in out


_REPLAN_CODE = r"""
import numpy as np, jax.numpy as jnp
from repro.core.distributed import (LandmarkPlan, make_nng_mesh, systolic_nng)
from repro.core.landmark import lpt_assignment, select_centers
from repro.core.metrics_host import get_host_metric
from repro.core.graph import EpsGraph
from repro.data import synthetic_pointset
from repro.launch.nng_run import (edges_from_neighbor_lists, run_landmark,
                                  run_systolic)

SEN = 2**31 - 1
n = 1024
pts = synthetic_pointset(n, 6, "euclidean", seed=11)
from repro.core.distributed.device import tile_cdist
eps = 1.0
_d2 = np.asarray(tile_cdist(jnp.asarray(pts), jnp.asarray(pts), "euclidean"))
_ii, _jj = np.nonzero(_d2 <= eps * eps)
_keep = _ii < _jj
gb = EpsGraph(n, _ii[_keep], _jj[_keep])
mesh = make_nng_mesh(8)

# k_cap=1 must overflow, then the driver grows it to the exact max count
_, cnt1, ovf1, *_rest = systolic_nng(jnp.asarray(pts), eps, mesh, k_cap=1)
assert bool(np.asarray(ovf1).any()), "k_cap=1 must overflow on this input"
nbrs, cnt, counters, k_final = run_systolic(pts, eps, mesh, k_cap=1)
assert k_final >= int(np.asarray(cnt).max())
ii, kk = np.nonzero(np.asarray(nbrs) != SEN)
assert EpsGraph(n, ii, np.asarray(nbrs)[ii, kk]) == gb, "replanned systolic"

# landmark: undersized caps everywhere; driver doubles until exact
rng = np.random.default_rng(1)
met = get_host_metric("euclidean")
m = 16
cidx = select_centers(n, m, rng)
cpts = pts[cidx]
cell = np.argmin(met.cdist(pts, cpts), axis=1)
f = lpt_assignment(np.bincount(cell, minlength=m), 8)
tiny = LandmarkPlan(m_centers=m, cap_coal=8, cap_ghost=8, g_per_pt=1, k_cap=2)
(Wids, wn, wc, Gids, gn, gc, ovf, tskip, tsched, ldists,
 lpruned), plan = run_landmark(pts, eps, cpts, f, mesh, tiny, max_grows=10)
assert not bool(np.asarray(ovf).any())
assert plan.k_cap > 2 and plan.cap_coal > 8, "plan must have grown"
s1, d1 = edges_from_neighbor_lists(Wids, wn)
s2, d2 = edges_from_neighbor_lists(Gids, gn)
g = EpsGraph(n, np.concatenate([s1, s2]), np.concatenate([d1, d2]))
assert g == gb, "replanned landmark"
print("REPLAN_OK")
"""


def test_overflow_replan_drivers_8dev():
    out = run_subprocess(_REPLAN_CODE, devices=8)
    assert "REPLAN_OK" in out


# ---------------------------------------------------------------------------
# exactness hardening regressions + landmark grouped fast path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nranks", [2, 5, 8])
def test_ring_bytes_accounting(nranks):
    """The visiting block rotates to EVERY rank each ring round — including
    the half of the halving round whose tile evaluation is elided, and
    pruned rounds (the docstring's 'block still rotates' contract). So
    ring_bytes must equal rounds * n * point_bytes regardless of pruning."""
    pts = clustered(1600, 6, 5)
    n = len(pts)
    want = (nranks // 2) * n * pts.dtype.itemsize * pts.shape[1]
    _, stats = systolic_ring_host(pts, 1.0, nranks)
    assert stats.comm_bytes["ring"] == want
    _, st2 = systolic_ring_host(pts, 1.0, nranks, prune=False)
    assert st2.comm_bytes["ring"] == want


def test_ghost_slack_boundary_points():
    """Adversarial Lemma-1 regression: at large coordinate offsets the fp32
    BLAS3 expansion's cancellation error exceeds any absolute tolerance and
    the UNSLACKED ghost test (`tru <= bound`, the pre-fix code) drops
    float64-true boundary ghosts — losing exact edges. The scale-aware
    slacked bound must include every true ghost (over-inclusion is safe:
    it only costs ghost copies)."""
    import jax.numpy as jnp
    from repro.core.distributed.device import _lemma1_ghost_bound, tile_cdist
    rng = np.random.default_rng(0)
    n, m = 2000, 12
    eps = 1.0
    dropped_unslacked = 0
    # low AND high dimension: the BLAS3 accumulation error grows ~sqrt(d),
    # so the slack coefficient must be dimension-aware (a fixed few-ulp
    # multiple tuned at d=4 still drops ghosts on sift-like d=128 data)
    for off, d in ((512.0, 4), (4096.0, 4), (512.0, 64), (2048.0, 128)):
        pts = (rng.normal(size=(n, d)) * 2 + off).astype(np.float32)
        cpts = pts[rng.choice(n, m, replace=False)]
        d64 = np.sqrt(((pts[:, None, :].astype(np.float64)
                        - cpts[None, :, :].astype(np.float64)) ** 2).sum(-1))
        dmin64 = d64.min(axis=1)
        true_g = d64 <= (dmin64 + 2 * eps)[:, None]
        dpc = np.asarray(tile_cdist(jnp.asarray(pts), jnp.asarray(cpts),
                                    "euclidean"))
        d_min = dpc.min(axis=1)
        unslacked = np.sqrt(dpc) <= (np.sqrt(d_min) + 2 * eps)[:, None]
        dropped_unslacked += int((true_g & ~unslacked).sum())
        tru, bound = _lemma1_ghost_bound(
            jnp.asarray(pts), jnp.asarray(cpts), jnp.asarray(dpc),
            jnp.asarray(d_min), 2.0 * eps, "euclidean")
        slacked = np.asarray(tru) <= np.asarray(bound)[:, None]
        assert not (true_g & ~slacked).any(), f"true ghosts dropped at {off}"
    # the construction must actually be adversarial for the pre-fix test
    assert dropped_unslacked > 0, "construction no longer exercises the bug"


def test_ghost_slack_hamming_unchanged():
    """Hamming distances are exact integers: the slack guard must add
    nothing (no spurious ghost copies on the integer metric)."""
    import jax.numpy as jnp
    from repro.core.distributed.device import _lemma1_ghost_bound
    rng = np.random.default_rng(1)
    dpc = rng.integers(0, 200, size=(64, 8)).astype(np.float32)
    d_min = dpc.min(axis=1)
    x = rng.integers(0, 2**32, size=(64, 4), dtype=np.uint32)
    c = rng.integers(0, 2**32, size=(8, 4), dtype=np.uint32)
    tru, bound = _lemma1_ghost_bound(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(dpc),
        jnp.asarray(d_min), 2.0 * 40, "hamming")
    assert (np.asarray(tru) == dpc).all()
    assert (np.asarray(bound) == d_min + 80.0).all()


_LANDMARK_PARITY_CODE = r"""
import numpy as np, jax.numpy as jnp
from repro.core.distributed import (LandmarkPlan, landmark_nng, make_nng_mesh,
                                    systolic_nng)
from repro.core.brute import brute_force_graph
from repro.core.graph import EpsGraph
from repro.core.host_algos import landmark_host
from repro.core.landmark import lpt_assignment, select_centers
from repro.core.metrics_host import get_host_metric
from repro.data import synthetic_pointset
from repro.launch.nng_run import edges_from_neighbor_lists, run_landmark

SEN = 2**31 - 1
mesh = make_nng_mesh(8)

def landmark_edges(out, n):
    s1, d1 = edges_from_neighbor_lists(out[0], out[1])
    s2, d2 = edges_from_neighbor_lists(out[3], out[4])
    return EpsGraph(n, np.concatenate([s1, s2]), np.concatenate([d1, d2]))

def gap_safe_eps(pts, target=1.0):
    # eps in the middle of a gap of the FULL float64 pairwise-distance set
    # near `target`, so no pair sits within fp32 error of the threshold and
    # the fp32 device engine, float64 host algorithms, and brute force all
    # classify every pair identically
    n = len(pts)
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(n, 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    eps = 0.5 * (vals[j] + vals[j + 1])
    assert vals[j + 1] - vals[j] > 1e-5, "no safe gap near target"
    return float(eps)

for metric, n, dim, eps in (("euclidean", 2048, 6, None),
                            ("hamming", 1024, 8, 40)):
    rng = np.random.default_rng(7)
    pts = synthetic_pointset(n, dim, metric, seed=13)
    if eps is None:
        eps = gap_safe_eps(pts)
    met = get_host_metric(metric)
    m = 20
    cidx = select_centers(n, m, rng)
    cpts = pts[cidx]
    cell = np.argmin(met.cdist(pts, cpts), axis=1)
    sizes = np.bincount(cell, minlength=m)
    f = lpt_assignment(sizes, 8)
    plan = LandmarkPlan(m_centers=m, cap_coal=int(sizes.max()) + 32,
                        cap_ghost=2048, g_per_pt=m, k_cap=512)
    out = landmark_nng(jnp.asarray(pts), eps, jnp.asarray(cpts),
                       jnp.asarray(f, np.int32), mesh, plan, metric=metric)
    assert not bool(np.asarray(out[6]).any()), metric
    g = landmark_edges(out, n)
    # device engine vs host-simulated landmark (cover-tree reference) and
    # vs brute force: all three must agree exactly
    gh, _ = landmark_host(pts, eps, 8, metric=metric, seed=5)
    gb = brute_force_graph(pts, eps, metric)
    assert gh == gb, f"host landmark vs brute ({metric})"
    assert g == gb, f"device landmark vs brute ({metric})"
    # the grouped fast path must actually engage
    assert int(np.asarray(out[7]).sum()) > 0, f"no tiles skipped ({metric})"
    assert int(np.asarray(out[8]).sum()) > int(np.asarray(out[7]).sum())

# ghost-capacity overflow -> grow_plan re-plan path (small problem: each
# re-plan is a fresh compile): g_per_pt=1 and a tiny cap_ghost must
# overflow, then the driver doubles capacities until the exact graph
# comes out with both knobs grown
n, m = 512, 8
pts = synthetic_pointset(n, 4, "euclidean", seed=21)
eps = gap_safe_eps(pts)
met = get_host_metric("euclidean")
cpts = pts[select_centers(n, m, np.random.default_rng(2))]
cell = np.argmin(met.cdist(pts, cpts), axis=1)
sizes = np.bincount(cell, minlength=m)
f = lpt_assignment(sizes, 8)
gb = brute_force_graph(pts, eps)
tiny = LandmarkPlan(m_centers=m, cap_coal=int(sizes.max()) + 32,
                    cap_ghost=4, g_per_pt=1, k_cap=256)
out0 = landmark_nng(jnp.asarray(pts), eps, jnp.asarray(cpts),
                    jnp.asarray(f, np.int32), mesh, tiny)
assert bool(np.asarray(out0[6]).any()), "tiny ghost caps must overflow"
out2, grown = run_landmark(pts, eps, cpts, f, mesh, tiny, max_grows=12)
assert grown.g_per_pt > 1 and grown.cap_ghost > 4, grown
assert landmark_edges(out2, n) == gb, "replanned landmark"
print("LANDMARK_PARITY_OK")
"""


@pytest.mark.slow  # CI runs this in its own dedicated step (by -k name)
def test_landmark_device_parity_8dev():
    """Landmark device engine (grouped bitmask fast path) vs landmark_host
    vs brute force on 8 simulated devices, both metrics, including the
    g_per_pt / cap_ghost overflow -> grow_plan re-plan path."""
    out = run_subprocess(_LANDMARK_PARITY_CODE, devices=8, timeout=1200)
    assert "LANDMARK_PARITY_OK" in out


# ---------------------------------------------------------------------------
# odd / non-power-of-two meshes: halving schedule, perm_home, ring bytes
# ---------------------------------------------------------------------------

_MESH_PARITY_CODE = r"""
import numpy as np, jax
from repro.core.brute import brute_force_graph
from repro.core.distributed import make_nng_mesh
from repro.core.flat_tree import build_block_forests, stack_device_forests
from repro.core.graph import NNGraph
from repro.core.metrics_host import get_host_metric
from repro.data import synthetic_pointset
from repro.nng import (PointPartitionEngine, SpatialPartitionEngine,
                       build_nng, drive)

nranks = len(jax.devices())
n, dim = 600, 6          # divisible by 3, 5, 6 — no duplicate padding
pts = synthetic_pointset(n, dim, "euclidean", seed=17)

def gap_safe_eps(pts, target=1.0):
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(n, 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    assert vals[j + 1] - vals[j] > 1e-5, "no safe gap near target"
    return float(0.5 * (vals[j] + vals[j + 1]))

eps = gap_safe_eps(pts)
gb = brute_force_graph(pts, eps)   # float64 oracle

rounds = nranks // 2
n_loc = n // nranks
forest = stack_device_forests(build_block_forests(
    pts, nranks, get_host_metric("euclidean")))
forest_hop = sum(np.asarray(v).nbytes for v in forest.values()) / nranks

for traversal in ("tiles", "tree"):
    g = build_nng(pts, eps, partition="point", traversal=traversal,
                  k_cap=256)
    assert g == gb, (traversal, nranks)
    st, k_fin = g.stats, g.meta["plan"]
    assert st.elapsed_s > 0 and st.replans == 0
    # analytic per-channel ring-byte formulas (see nng.py docstring)
    mirror = nranks * (rounds + 1) * (n_loc * k_fin * 4 + n_loc * 4)
    assert st.comm_bytes["ring_mirror"] == mirror, traversal
    assert st.comm_bytes["ring_summary"] == nranks * (dim * 4 + 4)
    if traversal == "tiles":
        assert st.comm_bytes["ring_points"] == \
            nranks * (rounds + 1) * (n_loc * dim * 4 + 4), nranks
        assert "ring_forest" not in st.comm_bytes
    else:
        assert st.comm_bytes["ring_points"] == \
            nranks * rounds * (n_loc * dim * 4 + n_loc * 4)
        modes = g.meta["ring_schedule"]
        assert len(modes) == rounds
        fhops = sum(m == "forest" for m in modes)
        assert st.comm_bytes["ring_forest"] == \
            nranks * fhops * forest_hop, nranks

# forced split schedules: exactness must be schedule-independent, and a
# "points"->"forest" transition exercises the multi-hop forest jump permute
mesh = make_nng_mesh()
if rounds > 0:
    for sched in {("points",) * rounds,
                  ("points",) * (rounds - 1) + ("forest",)}:
        eng = PointPartitionEngine(pts, eps, mesh, "euclidean", k_cap=256,
                                   traversal="tree")
        eng.ring_schedule = sched
        out, plan, _, _ = drive(eng)
        g = NNGraph.from_neighbor_tables(n, eng.neighbor_tables(out))
        assert g == gb, (sched, nranks)

# spatial partition at the same mesh sizes (all_to_all + ghosts, not ring)
g = build_nng(pts, eps, partition="spatial", traversal="tiles", k_cap=256)
assert g == gb, ("spatial", nranks)

# non-shardable n must raise a clear error from the host planner (the
# device path asserts divisibility; build_nng duplicate-pads around both)
bad = synthetic_pointset(nranks * 7 + 1, 4, "euclidean", seed=3)
eng = SpatialPartitionEngine(bad, 1.0, mesh, "euclidean", planner="host")
try:
    eng.initial_plan()
    raise SystemExit("expected ValueError for non-shardable n")
except ValueError as e:
    assert "shardable" in str(e), e
print("MESH_PARITY_OK")
"""


@pytest.mark.parametrize("devices", [3, 5, 6])
def test_device_parity_and_ring_bytes_meshes(devices):
    """The halving-round schedule and perm_home return hop at odd and
    non-power-of-two mesh sizes (both parities of nranks), both
    double-buffered ring bodies, exact vs float64 brute force — plus the
    per-channel ring-byte counters against the analytic formulas, forced
    split schedules (incl. the multi-hop forest jump), and the
    non-shardable-n host-planner error."""
    out = run_subprocess(_MESH_PARITY_CODE, devices=devices, timeout=1200)
    assert "MESH_PARITY_OK" in out


# ---------------------------------------------------------------------------
# landmark ghost ring: block rotation vs capacity-padded all_to_all
# ---------------------------------------------------------------------------

_GHOST_RING_CODE = r"""
import numpy as np, jax
from repro.core.brute import brute_force_graph
from repro.core.distributed import ghost_ring_bytes, resolve_ghost_mode
from repro.core.metrics import get_metric
from repro.data import synthetic_pointset
from repro.nng import build_nng

nranks = len(jax.devices())
n = 600                       # divisible by 3, 5, 8 — no duplicate padding

def gap_safe_eps(pts, target=1.0):
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(n, 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    assert vals[j + 1] - vals[j] > 1e-5, "no safe gap near target"
    return float(0.5 * (vals[j] + vals[j + 1]))

epts = synthetic_pointset(n, 6, "euclidean", seed=17)
workloads = [("euclidean", epts, gap_safe_eps(epts)),
             ("hamming", synthetic_pointset(n, 8, "hamming", seed=11), 40)]

for metric, pts, eps in workloads:
    gb = brute_force_graph(pts, eps, metric)   # float64 / exact oracle
    met = get_metric(metric)
    run_pts = np.asarray(pts, met.host.dtype)
    dim, item = run_pts.shape[1], run_pts.dtype.itemsize
    for traversal in ("tiles", "tree"):
        for gm in ("coll", "ring", "auto"):
            g = build_nng(pts, eps, metric=metric, partition="spatial",
                          traversal=traversal, ghost_mode=gm, k_cap=256,
                          seed=1)
            assert g == gb, (metric, traversal, gm, nranks)
            plan, st = g.meta["plan"], g.stats
            resolved = g.meta["ghost_mode"]
            if gm == "auto":
                # the recorded mode is what the byte models pick, never
                # the literal "auto"
                assert resolved == resolve_ghost_mode(
                    "auto", plan, dim, item, nranks), (metric, traversal)
            else:
                assert resolved == gm, (metric, traversal, gm)
            if resolved == "ring":
                # the ring channel replaces the padded ghost all_to_all,
                # and its counter IS the analytic formula
                assert "ghost" not in st.comm_bytes
                assert st.comm_bytes["ghost_ring"] == ghost_ring_bytes(
                    nranks, plan.cap_rank, dim, item, plan.m_centers), \
                    (metric, traversal, gm, nranks)
            else:
                assert "ghost_ring" not in st.comm_bytes
                assert st.comm_bytes["ghost"] > 0
print("GHOST_RING_PARITY_OK")
"""


@pytest.mark.parametrize("devices", [3, 5, 8])
def test_ghost_ring_parity_meshes(devices):
    """Landmark ghost ring vs the collective ghost exchange vs the float64
    brute oracle at odd, non-power-of-two, and even mesh sizes (the even
    case exercises the half-ring boundary round), both metrics, both
    traversal flavors, plus the ``ghost_ring`` byte counter against the
    analytic formula and the "auto" mode resolution."""
    out = run_subprocess(_GHOST_RING_CODE, devices=devices, timeout=1800)
    assert "GHOST_RING_PARITY_OK" in out


def test_resolve_ghost_mode_auto():
    """Unit: the auto picker follows the exact byte models, falls back to
    the collective path on unplanned (cap_rank=0) plans, and explicit
    modes pass through untouched."""
    from repro.core.distributed import (LandmarkPlan, ghost_coll_bytes,
                                        ghost_ring_bytes, resolve_ghost_mode)
    # fat ghost capacity, short ring block -> ring moves fewer bytes
    p_ring = LandmarkPlan(m_centers=32, cap_coal=64, cap_ghost=4096,
                          g_per_pt=8, k_cap=64, cap_rank=64)
    assert ghost_ring_bytes(8, 64, 16, 4, 32) \
        < ghost_coll_bytes(8, 4096, 16, 4)
    assert resolve_ghost_mode("auto", p_ring, 16, 4, 8) == "ring"
    # tiny ghost capacity, tall ring block -> the padded all_to_all wins
    p_coll = LandmarkPlan(m_centers=32, cap_coal=2048, cap_ghost=16,
                          g_per_pt=1, k_cap=64, cap_rank=2048)
    assert ghost_coll_bytes(8, 16, 16, 4) \
        < ghost_ring_bytes(8, 2048, 16, 4, 32)
    assert resolve_ghost_mode("auto", p_coll, 16, 4, 8) == "coll"
    # hand-built plans (cap_rank left at the 0 default) can never run ring
    p0 = LandmarkPlan(m_centers=32, cap_coal=64, cap_ghost=4096,
                      g_per_pt=8, k_cap=64)
    assert resolve_ghost_mode("auto", p0, 16, 4, 8) == "coll"
    assert resolve_ghost_mode("ring", p0, 16, 4, 8) == "ring"
    assert resolve_ghost_mode("coll", p_ring, 16, 4, 8) == "coll"


# ---------------------------------------------------------------------------
# split-ring schedule + tree pruning regression (dense overlapping blocks)
# ---------------------------------------------------------------------------

_TREE_PRUNE_CODE = r"""
import numpy as np, jax
from repro.core.brute import brute_force_graph
from repro.core.distributed import plan_ring_schedule
from repro.data import synthetic_pointset
from repro.nng import build_nng

nranks = len(jax.devices())
n = 800
pts = synthetic_pointset(n, 4, "euclidean", seed=1)

def gap_safe_eps(pts, target=1.0):
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(n, 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    assert vals[j + 1] - vals[j] > 1e-5, "no safe gap near target"
    return float(0.5 * (vals[j] + vals[j + 1]))

eps = gap_safe_eps(pts)
# contiguous blocks of uniform data all overlap -> every cross-block round
# is dense and the planner must rotate forest tables, not raw points
modes = plan_ring_schedule(pts, nranks, eps)
assert len(modes) == nranks // 2 and any(m == "forest" for m in modes), modes

g = build_nng(pts, eps, partition="point", traversal="tree", k_cap=256)
assert g == brute_force_graph(pts, eps), nranks
assert tuple(g.meta["ring_schedule"]) == modes, g.meta
# the cover-tree frontier must actually discard subtrees on forest rounds
# (regression: an all-"points" schedule reports nodes_pruned == 0 and the
# tree path silently degenerates into the dense bitmask kernel)
assert g.stats.nodes_pruned > 0, g.stats
assert g.stats.dists_evaluated > 0
print("TREE_PRUNE_OK")
"""


def test_tree_forest_rounds_prune_8dev():
    """Dense overlapping blocks: the split-ring planner emits "forest"
    rounds and the device cover-tree traversal reports nonzero
    ``nodes_pruned`` while staying exact vs brute force."""
    out = run_subprocess(_TREE_PRUNE_CODE, devices=8, timeout=1200)
    assert "TREE_PRUNE_OK" in out


def test_plan_ring_schedule_heuristic():
    """Host split-ring planner: far-apart blocked clusters make every
    cross-block round sparse -> "points" mode; prune=False evaluates every
    scheduled tile -> all "forest" (the pre-split behavior); nranks=1 has
    no ring."""
    from repro.core.distributed import plan_ring_schedule
    from repro.data import blocked_clusters
    pts = blocked_clusters(1600, 4, 8, seed=4)
    modes = plan_ring_schedule(pts, 8, 1.0)
    assert len(modes) == 4 and set(modes) <= {"forest", "points"}
    assert all(m == "points" for m in modes), modes
    assert plan_ring_schedule(pts, 8, 1.0, prune=False) == ("forest",) * 4
    assert plan_ring_schedule(pts, 1, 1.0) == ()
    # overlapping uniform data: every round dense -> forest everywhere
    dense = synthetic_pointset(800, 4, "euclidean", seed=1)
    assert plan_ring_schedule(dense, 8, 1.0) == ("forest",) * 4
