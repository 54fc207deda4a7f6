"""Online maintenance: ``NNGraph`` delta log + incremental forest inserts
+ ``repro.stream.OnlineNNG`` exactness ladders.

The contract under test is the strongest one the subsystem makes: after
EVERY insert / delete, the merged view (base CSR + delta log) equals a
float64 brute-force rebuild over the live points. The ladders run
randomized schedules over both metrics x both partitions x both insert
backends at mesh sizes 3 and 8, with ``compact_ratio`` tuned low enough
that auto-compaction fires mid-schedule (compaction must be invisible)."""
import sys

import numpy as np
import pytest

from repro.core.brute import brute_force_graph
from repro.core.graph import NNGraph
from repro.data import synthetic_pointset
from tests.helpers import run_subprocess, safe_eps


# ---------------------------------------------------------------------------
# layer 1: the CSR delta log (pure numpy, no engines)
# ---------------------------------------------------------------------------

def _ref_graph(n, edges, dead):
    """Reference merged view: plain edge set minus dead endpoints."""
    live = [(a, b) for a, b in edges if a not in dead and b not in dead]
    src = np.array([a for a, b in live] + [b for a, b in live], np.int64)
    dst = np.array([b for a, b in live] + [a for a, b in live], np.int64)
    return NNGraph.from_directed_pairs(n, src, dst)


def test_delta_log_randomized_vs_reference():
    """30-step property test: random node inserts, edge adds (incl.
    duplicates / self loops / dead endpoints), node deletes, and forced
    compactions; the merged view must track a plain edge-set model."""
    rng = np.random.default_rng(7)
    n = 12
    base = [(0, 1), (1, 2), (2, 3), (0, 4), (5, 6)]
    src = np.array([a for a, b in base], np.int64)
    dst = np.array([b for a, b in base], np.int64)
    g = NNGraph.from_directed_pairs(n, np.r_[src, dst], np.r_[dst, src])
    edges, dead = set(base), set()
    for step in range(30):
        op = rng.integers(4)
        if op == 0:                                   # insert nodes
            k = int(rng.integers(1, 4))
            new = g.delta_insert_nodes(k)
            assert (new == np.arange(n, n + k)).all()
            n += k
        elif op == 1:                                 # add edges
            m = int(rng.integers(1, 6))
            a = rng.integers(0, n, m)
            b = rng.integers(0, n, m)
            added = g.delta_add_edges(a, b)
            want = {(min(x, y), max(x, y)) for x, y in zip(a, b)
                    if x != y and x not in dead and y not in dead}
            assert added == len(want - edges)
            edges |= want - edges
        elif op == 2 and n - len(dead) > 2:           # delete nodes
            alive = [i for i in range(n) if i not in dead]
            ids = rng.choice(alive, size=min(2, len(alive)), replace=False)
            removed = g.delta_delete_nodes(ids)
            killed = {e for e in edges if e[0] in set(ids) or e[1] in set(ids)}
            assert removed == len(killed)
            edges -= killed
            dead |= set(int(i) for i in ids)
        else:                                         # compact (idempotent)
            before = g.edge_key()
            g.compact()
            assert not g.has_delta
            assert np.array_equal(g.edge_key(), before)
            g.compact()                               # second is a no-op
            assert np.array_equal(g.edge_key(), before)
        ref = _ref_graph(n, edges, dead)
        assert g.n == n and np.array_equal(g.edge_key(), ref.edge_key()), \
            f"step {step} diverged"
        for i in rng.integers(0, n, 3):               # spot-check row views
            assert np.array_equal(g.neighbors(int(i)),
                                  ref.neighbors(int(i)))
        assert np.array_equal(g.degrees(), ref.degrees())


def test_delta_add_edges_guards():
    g = NNGraph.from_directed_pairs(
        4, np.array([0, 1], np.int64), np.array([1, 0], np.int64))
    # self loops, out-of-range, and duplicates of existing edges: all dropped
    assert g.delta_add_edges([2, 2, 0, 9], [2, 3, 1, 1]) == 1
    assert (g.neighbors(2) == [3]).all()
    g.delta_delete_nodes([3])
    # edges to a dead node are rejected even after compaction clears the log
    g.compact()
    assert g.delta_add_edges([2], [3]) == 0
    assert len(g.neighbors(2)) == 0


def test_edge_key_int64_large_n():
    """n large enough that src * n + dst overflows int32: the edge key
    must be computed in int64 (regression: keys used to collide)."""
    n = 200_000
    src = np.array([0, n - 2], np.int64)
    dst = np.array([n - 1, n - 1], np.int64)
    g = NNGraph.from_directed_pairs(n, np.r_[src, dst], np.r_[dst, src])
    key = g.edge_key()
    assert key.dtype == np.int64
    assert (key == np.sort(src * n + dst)).all()
    assert key[1] > np.iinfo(np.int32).max
    # delta path takes the same keyed route
    g.delta_add_edges([1], [n - 1])
    assert g.num_edges == 3


def test_to_scipy_csr_missing_scipy_error(monkeypatch):
    g = NNGraph.from_directed_pairs(
        3, np.array([0, 1], np.int64), np.array([1, 0], np.int64))
    monkeypatch.setitem(sys.modules, "scipy", None)
    monkeypatch.setitem(sys.modules, "scipy.sparse", None)
    with pytest.raises(ImportError, match="optional dependency scipy"):
        g.to_scipy_csr()


# ---------------------------------------------------------------------------
# layer 2: incremental host forest (float64, no devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["euclidean", "hamming"])
def test_insert_host_and_tombstone_exact(metric):
    """Grow a single tree point by point, then tombstone: query_host must
    match float64 brute force at every stage."""
    from repro.core.covertree import build_covertree
    from repro.core.flat_tree import flatten_forest
    from repro.core.metrics_host import get_host_metric

    rng = np.random.default_rng(11)
    pts = synthetic_pointset(160, 6, metric, seed=11)
    eps = safe_eps(pts, metric)
    met = get_host_metric(metric)
    n0 = 100
    tree = build_covertree(pts[:n0], met, 8)
    ft = flatten_forest([tree], cells=[0],
                        gids=[np.arange(n0, dtype=np.int64)], points=pts)
    live = np.zeros(len(pts), bool)
    live[:n0] = True

    def check():
        ids = np.flatnonzero(live)
        d = np.asarray(met.true(met.cdist(pts[:8], pts[ids])))
        want = [set(ids[np.flatnonzero(row <= eps)].tolist()) for row in d]
        qi, gid = ft.query_host(pts[:8], eps)
        for q in range(8):
            assert set(gid[qi == q].tolist()) == want[q]

    check()
    for lo in range(n0, len(pts), 16):
        hi = min(lo + 16, len(pts))
        ft.insert_host(np.arange(lo, hi, dtype=np.int64), points=pts)
        live[lo:hi] = True
        check()
    doomed = rng.choice(np.flatnonzero(live), size=30, replace=False)
    ft.tombstone_host(doomed)
    live[doomed] = False
    check()


# ---------------------------------------------------------------------------
# layer 3: OnlineNNG exactness ladders (subprocess, multi-device meshes)
# ---------------------------------------------------------------------------

LADDER = r"""
import numpy as np
from repro.core.brute import brute_force_graph
from repro.data import synthetic_pointset
from repro.stream import OnlineNNG
from tests.helpers import safe_eps

metric, partition, backend, seed = {metric!r}, {partition!r}, {backend!r}, {seed}
rng = np.random.default_rng(seed)
pool = synthetic_pointset(420, 6, metric, seed=seed)
eps = safe_eps(pool, metric)              # gap-safe over initial AND inserts
n0 = 320
o = OnlineNNG(pool[:n0], eps, metric=metric, partition=partition,
              insert_backend=backend, compact_ratio=0.25, seed=seed)

def check(tag):
    live = np.flatnonzero(o.live)
    gb = brute_force_graph(o.points[live], eps, metric)
    bkey = np.sort(live[gb.src] * o.graph.n + live[gb.dst])
    assert np.array_equal(o.graph.edge_key(), bkey), (
        tag + ": merged view != float64 brute force on live points")

check("initial")
cursor = n0
for step in range(5):
    if step % 3 == 2:
        live = np.flatnonzero(o.live)
        o.delete(rng.choice(live, size=20, replace=False))
    else:
        new = o.insert(pool[cursor:cursor + 20])
        assert (new == np.arange(cursor, cursor + 20)).all()
        cursor += 20
    check("step %d" % step)
assert o.graph.meta["compactions"] >= 1, "compaction never fired"
key = o.graph.edge_key()
o.compact()                               # explicit compaction: invisible
assert np.array_equal(o.graph.edge_key(), key)
check("post-compact")
print("OK", o.graph.meta["compactions"], o.stats.edges_added,
      o.stats.edges_removed)
"""


@pytest.mark.parametrize("devices,metric,partition,backend", [
    (3, "euclidean", "point", "host"),
    (3, "hamming", "spatial", "device"),
    (8, "euclidean", "spatial", "host"),
    (8, "hamming", "point", "device"),
])
def test_online_nng_ladder(devices, metric, partition, backend):
    out = run_subprocess(
        LADDER.format(metric=metric, partition=partition, backend=backend,
                      seed=13 + devices),
        devices=devices, timeout=1200)
    assert out.startswith("OK")


def test_online_nng_single_rank_delete_all_but_one():
    """Degenerate schedules on the in-process 1-device mesh: delete down
    to a single live point, then keep inserting — ids never reused."""
    from repro.stream import OnlineNNG

    pts = synthetic_pointset(96, 4, "euclidean", seed=5)
    eps = safe_eps(pts, "euclidean")
    o = OnlineNNG(pts[:64], eps, compact_ratio=None)
    o.delete(np.arange(1, 64))
    assert o.num_live == 1 and o.graph.num_edges == 0
    new = o.insert(pts[64:96])
    assert (new == np.arange(64, 96)).all()
    live = np.flatnonzero(o.live)
    gb = brute_force_graph(o.points[live], eps, "euclidean")
    bkey = np.sort(live[gb.src] * o.graph.n + live[gb.dst])
    assert np.array_equal(o.graph.edge_key(), bkey)
    # deleting an already-dead id is a no-op
    assert o.delete([3]) == 0


DELTA_WORK = r"""
import numpy as np
from repro.data import blocked_clusters
from repro.nng import build_nng
from repro.stream import OnlineNNG

n, dim, b = 4096, 16, 32
# the corpus and first batch of a six-batch stream, one cluster per rank
pool = blocked_clusters(n + 6 * b, dim, 8, seed=4)
eps = 1.0
rebuild = build_nng(pool[:n + b], eps, partition="point", k_cap=512)
o = OnlineNNG(pool[:n], eps, partition="point", k_cap=512,
              compact_ratio=None)
o.insert(pool[n:n + b])
assert np.array_equal(o.graph.edge_key(), rebuild.edge_key())
ratio = rebuild.stats.dists_evaluated / o.last_update_stats.dists_evaluated
assert ratio >= 10, ratio
# compaction folds the insert's delta edges into the base CSR
key = o.graph.edge_key()
assert o.graph.delta_edges > 0
o.compact()
assert not o.graph.has_delta and np.array_equal(o.graph.edge_key(), key)
print("OK", ratio)
"""


def test_delta_insert_evaluates_a_tenth_of_a_rebuild():
    """A 32-point insert into 4096 clustered points (0.78 % of the corpus)
    on eight ranks: the delta traversal evaluates at least 10x fewer pair
    distances than a point-partition rebuild of the grown corpus, and
    gives the same graph."""
    out = run_subprocess(DELTA_WORK, devices=8, timeout=600)
    assert out.startswith("OK")
