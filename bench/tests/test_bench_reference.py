"""The float64 reference agrees with ``build_nng`` at n = 512 and rejects
corrupted graphs."""
from __future__ import annotations

import numpy as np
import pytest

from bench.data import clustered, run_inputs
from bench.reference import RowSets, check_graphs, compared_numbers, worst
from bench.tests.util import tiny_cell

CASES = [("sift-sparse-point-tiles", "euclidean", 128, 4.0),
         ("w2b-sparse-point-tiles", "hamming", 25, 44)]


def _graph(metric, dim, eps, n=512):
    from repro.nng import build_nng
    pts = clustered(n, dim, metric, seed=3)
    g = build_nng(pts, eps, metric=metric, k_cap=64)
    return pts, g.row_ptr.copy(), g.col_ids.copy()


def _readings(pts, rp, cols, eps, metric, rows=None):
    rows = np.arange(len(pts)) if rows is None else rows
    return worst(check_graphs([(rp, cols)], pts, rows, eps, metric), metric)


@pytest.mark.parametrize("cell,metric,dim,eps", CASES)
def test_reference_agrees_with_build_nng(cell, metric, dim, eps):
    pts, rp, cols = _graph(metric, dim, eps)
    assert rp[-1] > 0
    got = _readings(pts, rp, cols, eps, metric)
    limits = tiny_cell(cell).params["limits"]
    for k in compared_numbers(metric):
        assert got[k] <= limits[k], (k, got)


def _drop_edge(rp, cols, i):
    """Remove row i's first neighbour j from both rows."""
    j = cols[rp[i]]
    keep = np.ones(len(cols), bool)
    keep[rp[i]] = False
    keep[rp[j] + np.searchsorted(cols[rp[j]:rp[j + 1]], i)] = False
    rows = np.repeat(np.arange(len(rp) - 1), np.diff(rp))[keep]
    out = np.zeros_like(rp)
    np.cumsum(np.bincount(rows, minlength=len(rp) - 1), out=out[1:])
    return out, cols[keep]


@pytest.mark.parametrize("cell,metric,dim,eps", CASES)
def test_reference_rejects_corrupted_graphs(cell, metric, dim, eps):
    pts, rp, cols = _graph(metric, dim, eps)
    limits = tiny_cell(cell).params["limits"]
    gap = compared_numbers(metric)[-1]
    i = int(np.argmax(np.diff(rp)))

    def fails(rp_, cols_):
        got = _readings(pts, rp_, cols_, eps, metric)
        return any(got[k] > limits[k] for k in got)

    assert not fails(rp, cols)
    # an edge well inside eps left out, from both rows
    assert fails(*_drop_edge(rp, cols, i))
    # one half of an edge left out
    bad = cols.copy()
    bad[rp[i]] = bad[rp[i] + 1] if rp[i + 1] - rp[i] > 1 else (bad[rp[i]] + 1)
    assert fails(rp, bad)
    # a self pair
    bad = cols.copy()
    bad[rp[i]] = i
    assert fails(rp, bad)
    # an id out of range
    bad = cols.copy()
    bad[rp[i + 1] - 1] = len(pts)
    assert fails(rp, bad)
    # row pointers that do not end at the number of ids
    assert fails(rp[:-1], cols)
    # the mismatch number alone sees a neighbour set shifted by one id
    rows = np.array([i])
    shifted = RowSets(np.array([0, rp[i + 1] - rp[i]]),
                      (cols[rp[i]:rp[i + 1]] + 1) % len(pts))
    from bench.reference import compare_rows
    [res] = compare_rows([shifted], pts, rows, eps, metric)
    assert res[gap] > limits[gap]


def test_run_inputs_follow_the_seed():
    cfg = tiny_cell("sift-sparse-point-tiles").config
    a, ra = run_inputs(cfg, 2**31 + 5, 64)
    b, rb = run_inputs(cfg, 2**31 + 5, 64)
    c, rc = run_inputs(cfg, 7, 64)
    assert np.array_equal(a, b) and np.array_equal(ra, rb)
    assert not np.array_equal(a, c)
    # every seed brings the same points, in another order
    key = lambda p: np.sort(p.view(np.uint32).sum(axis=1, dtype=np.uint64))
    assert np.array_equal(key(a), key(c))
