"""The unified public front-end: ``repro.nng.build_nng`` + ``NNGraph`` CSR
results + the ``Metric`` registry extension contract + deprecation shims.

Covers the PR 5 acceptance matrix: all three registered metrics x both
partitions x both traversals produce bit-identical edge sets (vs a brute
oracle in the engines' declared arithmetic), CSR invariants hold, a
user-defined plain-jnp metric (no Pallas kernels) runs end-to-end through
the fallback path, and the deprecated tuple APIs still return the PR 4
shapes (with a DeprecationWarning)."""
import os
import warnings

import numpy as np
import pytest

from repro.core.brute import brute_force_graph
from repro.core.graph import EpsGraph, NNGraph, RunStats
from repro.data import synthetic_pointset
from tests.helpers import run_subprocess


# ---------------------------------------------------------------------------
# NNGraph CSR construction invariants (pure numpy, no engines)
# ---------------------------------------------------------------------------

def test_nngraph_from_directed_pairs():
    n = 10
    # directed hits incl. duplicates, self loops, and out-of-range padding
    src = np.array([0, 1, 2, 2, 5, 9, 3, 11, 4])
    dst = np.array([1, 0, 3, 3, 5, 0, 2, 1, 12])
    g = NNGraph.from_directed_pairs(n, src, dst)
    # surviving undirected edges: (0,1), (2,3), (0,9)
    assert g.num_edges == 3
    assert int(g.row_ptr[-1]) == 6              # symmetric CSR: 2 per edge
    assert (g.degrees() == [2, 1, 1, 1, 0, 0, 0, 0, 0, 1]).all()
    assert (g.neighbors(0) == [1, 9]).all()     # sorted ascending
    assert (g.neighbors(2) == [3]).all()
    # round-trips
    ep = g.to_eps_graph()
    assert isinstance(ep, EpsGraph) and ep.num_edges == 3
    assert g == ep
    pytest.importorskip("scipy")    # optional dep: lazy in to_scipy_csr
    csr = g.to_scipy_csr()
    assert csr.shape == (n, n) and csr.nnz == 6
    assert (np.asarray(csr.todense()) == np.asarray(csr.todense()).T).all()


def test_nngraph_from_neighbor_tables():
    SEN = 2**31 - 1
    n = 6
    ids = np.array([0, 1, 2, SEN, 7])           # padding row + dup-pad id 7
    nbrs = np.array([
        [1, 2, SEN], [0, SEN, SEN], [0, SEN, SEN],
        [3, 4, 5], [0, 1, 2],                   # both rows must be dropped
    ], np.int32)
    st = RunStats(tiles_scheduled=4.0, tiles_skipped=1.0)
    g = NNGraph.from_neighbor_tables(n, [(ids, nbrs)], stats=st,
                                     meta={"metric": "euclidean"})
    pytest.importorskip("scipy")    # optional dep: lazy in to_scipy_csr
    assert sorted(map(tuple, zip(*np.nonzero(g.to_scipy_csr().todense())))) \
        == [(0, 1), (0, 2), (1, 0), (2, 0)]
    assert g.stats.tile_skip_rate == 0.25
    assert g.meta["metric"] == "euclidean"


SEN = 2**31 - 1


def _sorted_table(n, m, k, edges, rng):
    """(m, k) SENTINEL-padded table of a symmetric random graph on ``m``
    ids with ``edges`` undirected edges, each row sorted ascending."""
    a, b = rng.integers(0, m, (2, edges))
    a, b = a[a != b], b[a != b]
    key = np.unique(np.r_[a * m + b, b * m + a])
    rows, cols = key // m, key % m
    deg = np.bincount(rows, minlength=m)
    assert deg.max() <= k
    table = np.full((m, k), SEN, np.int32)
    table[rows, np.arange(len(key)) - np.repeat(np.cumsum(deg) - deg, deg)] \
        = cols
    return table


def _put(table, i, ids):
    """Make row ``i`` hold ``ids``, sorted and SENTINEL-padded."""
    table[i] = SEN
    table[i, :len(ids)] = np.sort(ids)


def _insert(table, i, j):
    """Put id ``j`` into row ``i``, keeping the row sorted."""
    _put(table, i, np.r_[table[i][table[i] != SEN], j])


def _one_sided(table, n, count, rng):
    """Add ``count`` entries (i, j), i != j < n, whose mirror is absent
    and that are not there yet; returns the table and ``count``."""
    added = set()
    while len(added) < count:
        i, j = (int(v) for v in rng.integers(0, n, 2))
        if (i == j or j in table[i] or i in table[j]
                or (j, i) in added):
            continue
        _insert(table, i, j)
        added.add((i, j))
    return table, count


def _table_case(case, rng):
    """(n, tables, takes the row-table path, mirror entries it adds)."""
    n, k = 60, 24
    table = _sorted_table(n, n, k, 150, rng)
    ids = np.arange(n)
    if case == "symmetric":
        return n, [(ids, table)], True, 0
    if case == "padding":
        m = n + 4                     # rows n.. duplicate rows 0..3
        table = _sorted_table(n, m, k, 170, rng)
        return n, [(np.arange(m), table)], True, 0
    if case == "self_loop":
        _insert(table, 5, 5)
        return n, [(ids, table)], True, 0
    if case == "one_sided":
        table, added = _one_sided(table, n, 9, rng)
        return n, [(ids, table)], True, added
    if case == "swapped_mirror":
        # a row keeps its length: one mirror dropped, one one-sided id in
        x = next(i for i in range(n - 1, 0, -1)
                 if (table[i] < i).any() and (table[i] == SEN).any())
        row = table[x][table[x] != SEN]
        b = next(j for j in range(x) if j not in row and x not in table[j])
        _put(table, x, np.r_[row[1:], b])
        return n, [(ids, table)], True, 2
    if case == "unsorted":
        row = table[3][table[3] != SEN]
        table[3, :len(row)] = row[::-1]
        return n, [(ids, table)], False, 0
    if case == "duplicate":
        row = table[7][table[7] != SEN]
        table[7, :len(row) + 1] = np.sort(np.r_[row, row[0]])
        return n, [(ids, table)], False, 0
    if case == "empty_rows":
        for i in (0, 11, 59):
            table[table == i] = SEN
        table[[0, 11, 59]] = SEN
        table = np.sort(table, axis=1)
        return n, [(ids, table)], True, 0
    if case == "n1":
        return 1, [(np.arange(2), np.array([[1, SEN], [0, SEN]]))], True, 0
    if case == "spatial":
        half = n // 2
        return n, [(ids[:half], table[:half]),
                   (ids[half:], table[half:])], False, 0
    if case == "permuted_ids":
        order = rng.permutation(n)
        return n, [(ids[order], table[order])], False, 0
    raise ValueError(case)


@pytest.mark.parametrize("case", [
    "symmetric", "padding", "self_loop", "one_sided", "swapped_mirror",
    "unsorted",
    "duplicate", "empty_rows", "n1", "spatial", "permuted_ids"])
def test_row_table_csr_matches_the_general_path(case):
    """One table with ids 0..m-1 takes the row-table path; its CSR is
    byte-identical to ``from_directed_pairs`` of the same pairs, with the
    same counters, and ``csr_mirror_added`` counts the mirrors it added."""
    from repro.obs import recording

    n, tables, row_path, added = _table_case(
        case, np.random.default_rng([ord(c) for c in case]))
    src, dst, pairs = [], [], 0
    for ids, nbrs in tables:
        ii, kk = np.nonzero((nbrs != SEN) & (ids < n)[:, None])
        src.append(ids[ii])
        dst.append(nbrs[ii, kk])
        pairs += len(ii)
    want = NNGraph.from_directed_pairs(n, np.concatenate(src),
                                       np.concatenate(dst))
    with recording() as rec:
        got = NNGraph.from_neighbor_tables(n, tables)
    assert got.row_ptr.dtype == want.row_ptr.dtype
    assert got.col_ids.dtype == want.col_ids.dtype
    assert np.array_equal(got.row_ptr, want.row_ptr)
    assert np.array_equal(got.col_ids, want.col_ids)
    assert rec.counts["table_slots"] == sum(t.size for _, t in tables)
    assert rec.counts["pairs_selected"] == pairs
    names = {name for name, *_ in rec.spans}
    assert ("nng.csr.mirror" in names) == (len(tables) == 1
                                           and case != "permuted_ids")
    assert ("nng.csr.sort" in names) == (not row_path)
    if row_path:
        assert rec.counts["csr_mirror_added"] == added
        d = np.concatenate(dst)
        kept = np.count_nonzero((d < n) & (d != np.concatenate(src)))
        assert int(got.row_ptr[-1]) == kept + added
    else:
        assert "csr_mirror_added" not in rec.counts


def _row_table_matches_brute(metric, traversal):
    from repro.nng import build_nng

    pts = synthetic_pointset(300, 8, metric, seed=17)
    if metric == "euclidean":
        # a 2^-5 grid keeps every fp32 term of the L2 expansion exact
        pts = (np.round(pts * 32) / 32).astype(np.float32)
        x = pts.astype(np.float64)
        d2 = np.unique(((x[:, None] - x[None]) ** 2).sum(-1))
        k = int(np.searchsorted(d2, 1.0))
        eps = float(np.sqrt(0.5 * (d2[k - 1] + d2[k])))
    else:
        eps = 40
    g = build_nng(pts, eps, metric=metric, traversal=traversal, k_cap=64)
    oracle = brute_force_graph(pts, eps, metric)
    assert oracle.num_edges > 100
    assert g == oracle
    assert g.stats.csr_mirror_added == 0
    assert g.stats.pairs_selected == 2 * oracle.num_edges
    names = {name for name, *_ in g.stats.spans}
    assert "nng.csr.mirror" in names and "nng.csr.sort" not in names
    nranks = g.meta["nranks"]
    if nranks > 1:
        # the ring's ppermute bytes per rank: the priming hop and `rounds`
        # block hops (points + first id), `rounds` mirror hops and the hop
        # home (ids + counts), in each engine call
        n_loc, rounds, k = len(pts) // nranks, nranks // 2, g.meta["plan"]
        hops = (rounds + 1) * (n_loc * pts.shape[1] * pts.itemsize + 4
                               + n_loc * k * 4 + n_loc * 4)
        assert g.stats.replans == 0
        assert g.stats.ring_bytes == g.stats.engine_calls * hops
    else:
        assert g.stats.ring_bytes == 0


@pytest.mark.parametrize("metric,traversal,nranks", [
    pytest.param("euclidean", "tiles", 1, id="euclidean-tiles"),
    pytest.param("euclidean", "tree", 1, id="euclidean-tree"),
    pytest.param("hamming", "tiles", 1, id="hamming-tiles"),
    pytest.param("euclidean", "tiles", 4, id="euclidean-tiles-ring4")])
def test_build_nng_point_row_table_matches_brute(metric, traversal, nranks):
    """The point engine takes the row-table path: the exact graph of the
    brute-force oracle, and no mirror entry added on points whose
    distances are exact in fp32. On a ring of four host devices each row
    of the table comes from two tiles with swapped operands: the forward
    tile where its block stays home and the mirror tile where it visits."""
    if nranks == 1:
        _row_table_matches_brute(metric, traversal)
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = run_subprocess(
        f"import sys; sys.path.insert(0, {root!r})\n"
        "from tests.test_build_nng import _row_table_matches_brute\n"
        f"_row_table_matches_brute({metric!r}, {traversal!r})\n"
        "print('ROW_TABLE_RING_OK')", devices=nranks)
    assert "ROW_TABLE_RING_OK" in out


def test_symmetric_difference_matches_set_semantics():
    """The np.setxor1d fast path must return exactly what the old
    Python-set xor did, for disjoint, overlapping, identical, and empty
    edge sets."""
    from repro.core.graph import EpsGraph
    n = 50
    rng = np.random.default_rng(3)

    def rand_graph(m):
        src = rng.integers(0, n, m)
        dst = (src + 1 + rng.integers(0, n - 1, m)) % n
        return EpsGraph(n, src, dst)

    empty = EpsGraph(n, np.array([], np.int64), np.array([], np.int64))
    a, b = rand_graph(40), rand_graph(40)
    ka = set(a.edge_key().tolist())
    kb = set(b.edge_key().tolist())
    assert a.symmetric_difference(b) == len(ka ^ kb)
    assert b.symmetric_difference(a) == len(ka ^ kb)
    assert a.symmetric_difference(a) == 0
    assert a.symmetric_difference(empty) == len(ka)
    assert empty.symmetric_difference(empty) == 0


# ---------------------------------------------------------------------------
# deprecated tuple APIs: warn, delegate, identical outputs
# ---------------------------------------------------------------------------

def test_deprecated_engine_wrappers_parity():
    import jax.numpy as jnp
    from repro.core.distributed import (LandmarkPlan, landmark_nng,
                                        landmark_run, make_nng_mesh,
                                        systolic_nng, systolic_run)
    from repro.core.landmark import lpt_assignment, select_centers
    from repro.core.metrics_host import get_host_metric

    mesh = make_nng_mesh()
    n = 256
    pts = synthetic_pointset(n, 6, "euclidean", seed=3)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = systolic_nng(jnp.asarray(pts), 1.0, mesh, k_cap=256)
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    new = systolic_run(jnp.asarray(pts), 1.0, mesh, k_cap=256)
    assert len(old) == 6                        # the PR 4 tuple, unchanged
    for a, b in zip(old, new):
        assert (np.asarray(a) == np.asarray(b)).all()

    met = get_host_metric("euclidean")
    m = 8
    cpts = pts[select_centers(n, m, np.random.default_rng(0))]
    cell = np.argmin(met.cdist(pts, cpts), axis=1)
    f = lpt_assignment(np.bincount(cell, minlength=m), mesh.size)
    plan = LandmarkPlan(m_centers=m, cap_coal=n + 8, cap_ghost=n * m,
                        g_per_pt=m, k_cap=256)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = landmark_nng(jnp.asarray(pts), 1.0, jnp.asarray(cpts),
                           np.asarray(f, np.int32), mesh, plan)
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    new = landmark_run(jnp.asarray(pts), 1.0, jnp.asarray(cpts),
                       np.asarray(f, np.int32), mesh, plan)
    assert len(old) == 11                       # the PR 4 tuple, unchanged
    for a, b in zip(old, new):
        assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# registry extension contract: user-defined plain-jnp metric, no kernels
# ---------------------------------------------------------------------------

def _chebyshev_metric():
    import jax.numpy as jnp

    from repro.core.metrics import Metric
    from repro.core.metrics_host import HostMetric

    class HostChebyshev(HostMetric):
        name = "chebyshev"

        def cdist(self, x, y):
            x = np.asarray(x, np.float32)
            y = np.asarray(y, np.float32)
            return np.abs(x[:, None, :] - y[None, :, :]).max(-1)

        def rowwise(self, x, y):
            diff = np.asarray(x, np.float64) - np.asarray(y, np.float64)
            return np.abs(diff).max(-1)

        def band_slack(self, x, y, ceps):
            return 1e-5 * ceps + 1e-6

        def comparable(self, eps):
            return float(eps)

        def true(self, c):
            return np.asarray(c, np.float64)

    def cheb_cdist(x, y):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        return jnp.max(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)

    # ONLY host reference + device cdist: no Pallas kernels, no refs — the
    # wrappers must route everything through the generic fallback path
    return Metric(name="chebyshev", host=HostChebyshev(), cdist=cheb_cdist)


def test_user_defined_metric_end_to_end():
    """A plain-jnp metric object runs through build_nng on both partitions
    and both traversals via the fallback path, exactly matching a float64
    numpy oracle (eps picked in a distance gap so fp32 cannot flip)."""
    from repro.nng import build_nng

    met = _chebyshev_metric()
    n = 400
    pts = synthetic_pointset(n, 6, "euclidean", seed=11)
    d = np.abs(pts.astype(np.float64)[:, None, :]
               - pts.astype(np.float64)[None, :, :]).max(-1)
    vals = np.sort(d[np.triu_indices(n, 1)])
    k = int(len(vals) * 0.02)
    j = k + int(np.argmax(vals[k + 1:k + 2000] - vals[k:k + 1999]))
    eps = 0.5 * (vals[j] + vals[j + 1])
    assert vals[j + 1] - vals[j] > 1e-5, "no safe eps gap"
    ii, jj = np.nonzero(np.triu(d <= eps, 1))
    gb = EpsGraph(n, ii, jj)
    assert gb.num_edges > 100
    for partition in ("point", "spatial"):
        for traversal in ("tiles", "tree"):
            g = build_nng(pts, eps, metric=met, partition=partition,
                          traversal=traversal, k_cap=256)
            assert g == gb, (partition, traversal)
            assert int(g.row_ptr[-1]) == 2 * gb.num_edges
            assert g.meta["metric"] == "chebyshev"


def test_register_metric_roundtrip():
    from repro.core.metrics import get_metric, register_metric

    met = _chebyshev_metric()
    register_metric(met, overwrite=True)
    assert get_metric("chebyshev") is met
    with pytest.raises(ValueError):
        register_metric(met)                    # duplicate without overwrite
    with pytest.raises(ValueError):
        get_metric("no-such-metric")


# ---------------------------------------------------------------------------
# 8-device acceptance matrix (subprocess: own XLA device count)
# ---------------------------------------------------------------------------

_BUILD_NNG_8DEV_CODE = r"""
import numpy as np
from repro.core.brute import brute_force_graph
from repro.core.graph import EpsGraph
from repro.core.metrics import get_metric
from repro.data import synthetic_pointset
from repro.nng import build_nng

def declared_oracle(pts, eps, metric):
    met = get_metric(metric)
    d = np.asarray(met.cdist(pts, pts), np.float32)
    ceps = (np.float32(eps) ** 2 if metric == "euclidean"
            else np.float32(met.comparable(eps)))
    ii, jj = np.nonzero(d <= ceps)
    keep = ii < jj
    return EpsGraph(len(pts), ii[keep], jj[keep])

def gap_safe_l1_eps(pts, target=3.0):
    x = pts.astype(np.float64)
    d = np.concatenate([np.abs(x[i, None, :] - x[i + 1:, :]).sum(-1)
                        for i in range(len(x) - 1)])
    d.sort()
    k = int(np.searchsorted(d, target))
    lo, hi = max(k - 2000, 0), min(k + 2000, len(d) - 1)
    j = lo + int(np.argmax(d[lo + 1:hi + 1] - d[lo:hi]))
    assert d[j + 1] - d[j] > 1e-5, "no safe gap"
    return 0.5 * float(d[j] + d[j + 1])

def grid_safe_l2(pts, target=1.0):
    # Snap the points to a 2^-5 grid. With |coords| < 24 every fp32 term of
    # the BLAS3 expansion near eps (norms, dot, the final difference) is
    # exact, so every engine's d2 IS the float64 d2, whatever its blocking.
    # eps2 midway between two adjacent d2 values then leaves no pair for a
    # rounding (or a one-ulp blocking difference) to decide.
    pts = (np.round(pts * 32) / 32).astype(np.float32)
    assert np.abs(pts).max() < 24
    x = pts.astype(np.float64)
    d2 = np.unique(((x[:, None, :] - x[None, :, :]) ** 2).sum(-1))
    k = int(np.searchsorted(d2, target ** 2))
    eps = float(np.sqrt(0.5 * (d2[k - 1] + d2[k])))
    assert d2[k - 1] < np.float32(eps) ** 2 < d2[k]
    return pts, eps

n = 1070                       # 1070 % 8 == 6: duplicate padding path
cases = [("euclidean", 1.0), ("manhattan", None), ("hamming", 40)]
for metric, eps in cases:
    pts = synthetic_pointset(n, 8, metric, seed=13)
    if metric == "manhattan":
        eps = gap_safe_l1_eps(pts)
        # the ISSUE's headline case: L1 on 8 devices vs the FLOAT64 host
        # brute force (gap-safe eps => fp32 must agree exactly)
        oracle = brute_force_graph(pts, eps, metric)
    elif metric == "hamming":
        oracle = brute_force_graph(pts, eps, metric)   # integers: exact
    else:
        pts, eps = grid_safe_l2(pts, eps)
        oracle = brute_force_graph(pts, eps, metric)
        # the declared fp32 arithmetic agrees with the float64 oracle
        assert declared_oracle(pts, eps, metric) == oracle
    keys = []
    for partition in ("point", "spatial"):
        for traversal in ("tiles", "tree"):
            g = build_nng(pts, eps, metric=metric, partition=partition,
                          traversal=traversal, k_cap=512)
            assert g == oracle, (metric, partition, traversal)
            assert int(g.row_ptr[-1]) == 2 * oracle.num_edges
            assert g.num_edges == oracle.num_edges
            assert (np.diff(g.row_ptr) == g.degrees()).all()
            keys.append(tuple(g.edge_key().tolist()))
    assert all(k == keys[0] for k in keys), f"{metric}: engines disagree"
    print(metric, "OK", oracle.num_edges)

# tiny point set on a wide mesh: pad = (-n) % nranks EXCEEDS n, the
# cycling duplicate-pad must still yield the exact graph
tiny = synthetic_pointset(5, 4, "euclidean", seed=1)
gt = brute_force_graph(tiny, 10.0)
for partition in ("point", "spatial"):
    g = build_nng(tiny, 10.0, metric="euclidean", partition=partition,
                  k_cap=64)
    assert g == gt, (partition, "tiny-n padding")
print("BUILD_NNG_8DEV_OK")
"""


def test_build_nng_8dev_all_metrics_partitions_traversals():
    """Acceptance: bit-identical edge sets vs the brute oracle on 8 devices
    for all three registered metrics x both partitions x both traversals,
    with CSR row_ptr[-1] == 2x the brute-force edge count, including the
    duplicate-padding path (n % nranks != 0)."""
    out = run_subprocess(_BUILD_NNG_8DEV_CODE, devices=8, timeout=1200)
    assert "BUILD_NNG_8DEV_OK" in out


_RUNSTATS_8DEV_CODE = r"""
import numpy as np
from repro.data import blocked_clusters
from repro.nng import build_nng

pts = blocked_clusters(2048, 8, 8, seed=2)
g = build_nng(pts, 1.0, partition="point", k_cap=512)
st = g.stats
assert st.tiles_skipped > 0, "blocked clusters must prune ring tiles"
assert st.tiles_scheduled > st.tiles_skipped
assert st.dists_evaluated > 0 and st.nodes_pruned == 0
# per-channel ring bytes (double-buffered tiles flavor at 8 ranks:
# rounds + 1 = 5 point hops incl. the priming hop, rounds + 1 mirror hops
# incl. the return home), analytic formula per rank summed over ranks
n_loc = 2048 // 8
pt_hop = n_loc * pts.shape[1] * pts.dtype.itemsize + 4
assert st.comm_bytes["ring_points"] == 8 * 5 * pt_hop
assert st.comm_bytes["ring_mirror"] == 8 * 5 * (n_loc * 512 * 4 + n_loc * 4)
# one-shot block-summary all_gather (prune only): (dim,) center + scalar
# radius per rank
assert st.comm_bytes["ring_summary"] == 8 * (pts.shape[1] * 4 + 4)
assert set(st.comm_bytes) == {"ring_points", "ring_mirror", "ring_summary"}
assert not st.overflow and st.replans == 0 and st.elapsed_s > 0
assert "ring_schedule" not in g.meta

g2 = build_nng(pts, 1.0, partition="spatial", traversal="tree", k_cap=512)
st2 = g2.stats
assert g2 == g, "partitions disagree"
assert st2.dists_evaluated > 0 and st2.nodes_pruned >= 0
assert set(st2.comm_bytes) == {"coalesce", "ghost"}
assert st2.total_comm_bytes > 0

# overflow -> grow loop through the unified driver: tiny k_cap must replan
g3 = build_nng(pts, 1.0, partition="point", k_cap=1)
assert g3 == g and g3.stats.replans >= 1
print("RUNSTATS_8DEV_OK")
"""


def test_build_nng_8dev_runstats_and_replan():
    """RunStats normalization (counters + comm bytes under the canonical
    names) and the shared grow-on-overflow driver (k_cap=1 must replan to
    the exact graph)."""
    out = run_subprocess(_RUNSTATS_8DEV_CODE, devices=8, timeout=1200)
    assert "RUNSTATS_8DEV_OK" in out
