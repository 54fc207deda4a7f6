"""The harness, run on the CPU past its look for a chip, with the timed
build broken underneath: ``correct`` comes out false for every fault a
cell can have, and true for the sound build."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests.util import run_tiny, tiny_cell


def _rebuild(n, src, dst, g):
    from repro.core.graph import NNGraph
    return NNGraph.from_directed_pairs(n, src, dst, stats=g.stats,
                                       meta=g.meta)


def _pairs(g):
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    return src, g.col_ids.astype(np.int64)


def unchanged(build, points, eps, **kw):
    """The build hands back its starting state: no edges at all."""
    g = build(points[:0], eps, **kw)
    g0 = build(points[:8], eps, **kw)       # stats and plan of a real call
    return _rebuild(len(points), np.zeros(0, np.int64),
                    np.zeros(0, np.int64), g0) if g.n == 0 else g


def half(build, points, eps, **kw):
    """Half of the points left out: the graph of the first half only."""
    g = build(points[:len(points) // 2], eps, **kw)
    return _rebuild(len(points), *_pairs(g), g)


def altered(build, points, eps, **kw):
    """One answer altered where it is produced: one neighbour of every
    row moved to the next id."""
    g = build(points, eps, **kw)
    src, dst = _pairs(g)
    first = g.row_ptr[:-1][np.diff(g.row_ptr) > 0]
    dst = dst.copy()
    dst[first] = (dst[first] + 1) % g.n
    return _rebuild(g.n, src, dst, g)


FAULTS = [("sift-sparse-point-tiles", unchanged),
          ("sift-sparse-point-tiles", half),
          ("sift-sparse-point-tiles", altered),
          ("w2b-sparse-point-tiles", altered),
          ("sift-dense-point-tiles", altered)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_reads_incorrect(name, fault):
    from repro.nng import build_nng
    result, _ = run_tiny(
        tiny_cell(name),
        build=lambda pts, eps, **kw: fault(build_nng, pts, eps, **kw))
    assert result["correct"] is False, result["checks"]
    assert result["failed"] >= 1


@pytest.mark.parametrize("name", ["sift-sparse-point-tiles",
                                  "w2b-sparse-point-tiles",
                                  "sift-dense-point-tiles"])
def test_sound_build_reads_correct(name):
    result, counters = run_tiny(tiny_cell(name))
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert counters["window_compiles"] == 0
    assert set(result["metrics"]) >= {"graph_s", "setup_s"}


def test_build_that_raises_reads_incorrect():
    def broken(pts, eps, **kw):
        broken.calls += 1
        if broken.calls > 1:
            raise RuntimeError("device lost")
        from repro.nng import build_nng
        return build_nng(pts, eps, **kw)
    broken.calls = 0
    result, _ = run_tiny(tiny_cell("sift-sparse-point-tiles"), build=broken)
    assert result["correct"] is False
    assert result["failed"] == 1
