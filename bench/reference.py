"""The plain reference and the comparison that decides ``correct``.

The reference is float64 numpy, independent of the program: true
distances from the sampled rows to every point, one block of rows at a
time. Each graph a timed build returned is held to it:

- ``csr_faults``: entries of the whole CSR that break its form: row
  pointers not rising from 0 to the number of ids, ids out of range,
  rows not strictly ascending, self pairs, an odd number of ids;
- ``asym_pairs``: edges (i, j) of the sampled rows i whose mirror (j, i)
  is missing;
- ``mismatched_pairs`` (hamming): sampled pairs whose membership differs
  from the reference. Distances are exact integers, so the limit is 0;
- ``max_gap_ulp`` (euclidean): the largest |d^2 - eps^2| over sampled
  pairs whose membership differs from the reference, in units of the
  float32 rounding of the pair's squared norms, 2^-24 (|x|^2 + |y|^2).
  The program computes d^2 = |x|^2 + |y|^2 - 2 x.y in float32, so a pair
  within a few such units of eps^2 may fall either way; a pair split
  further away is a fault or a lower precision. The limit is set from
  measured readings.
"""
from __future__ import annotations

import numpy as np

BLOCK = 256      # sampled rows per float64 distance block


class RowSets:
    """Neighbour ids of the sampled rows: row k's ids are
    ``ids[ptr[k]:ptr[k + 1]]``."""

    def __init__(self, ptr, ids):
        self.ptr = np.asarray(ptr, np.int64)
        self.ids = np.asarray(ids, np.int64)

    @classmethod
    def from_csr(cls, row_ptr, col_ids, rows):
        lo, hi = row_ptr[rows], row_ptr[np.asarray(rows) + 1]
        ptr = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(hi - lo, out=ptr[1:])
        idx = np.repeat(lo - ptr[:-1], hi - lo) + np.arange(ptr[-1])
        return cls(ptr, np.asarray(col_ids)[idx])

    @classmethod
    def from_mask(cls, mask):
        k, j = np.nonzero(mask)
        ptr = np.zeros(mask.shape[0] + 1, np.int64)
        np.cumsum(np.bincount(k, minlength=mask.shape[0]), out=ptr[1:])
        return cls(ptr, j)

    def mask(self, lo: int, hi: int, n: int) -> np.ndarray:
        """(hi - lo, n) bool membership of sampled rows lo..hi-1."""
        out = np.zeros((hi - lo, n), bool)
        cnt = np.diff(self.ptr[lo:hi + 1])
        sel = self.ids[self.ptr[lo]:self.ptr[hi]]
        ok = (sel >= 0) & (sel < n)
        out[np.repeat(np.arange(hi - lo), cnt)[ok], sel[ok]] = True
        return out


U32 = 2.0 ** -24      # unit roundoff of float32


def distance_blocks(points, rows, metric: str):
    """Yield (lo, hi, d, thr, scale) for sampled rows lo..hi-1: float64
    comparable distances (hi - lo, n) to every point, the threshold they
    are held to for a given eps (``thr(eps)``), and the scale a gap is
    measured in (None for exact integer distances)."""
    rows = np.asarray(rows)
    if metric == "hamming":
        bits = np.unpackbits(np.ascontiguousarray(points).view(np.uint8),
                             axis=1).astype(np.float32)
        ones = bits.sum(axis=1, dtype=np.float64)
        for lo in range(0, len(rows), BLOCK):
            r = rows[lo:lo + BLOCK]
            same = (bits[r] @ bits.T).astype(np.float64)   # exact: <= width
            yield (lo, lo + len(r), ones[r, None] + ones[None, :] - 2.0 * same,
                   float, None)
        return
    if metric != "euclidean":
        raise ValueError(f"no reference for metric {metric!r}")
    x = np.asarray(points, np.float64)
    sq = np.einsum("ij,ij->i", x, x)
    for lo in range(0, len(rows), BLOCK):
        r = rows[lo:lo + BLOCK]
        d2 = np.maximum(sq[r, None] + sq[None, :] - 2.0 * (x[r] @ x.T), 0.0)
        # squared distances against eps^2; a gap is counted in units of
        # the float32 rounding of the pair's squared norms, the scale of
        # the error of the expansion |x|^2 + |y|^2 - 2 x.y in float32
        yield (lo, lo + len(r), d2, lambda e: float(e) ** 2,
               U32 * (sq[r, None] + sq[None, :]))


def csr_readings(n: int, row_ptr, col_ids, rows) -> tuple[int, float]:
    """(csr_faults, asym_pairs) of one graph. A CSR too broken to search
    for mirrors reads ``inf`` missing mirrors."""
    rp = np.asarray(row_ptr, np.int64)
    cols = np.asarray(col_ids, np.int64)
    if rp.shape != (n + 1,):
        return 1 + abs(len(rp) - (n + 1)), float("inf")
    steps = np.diff(rp)
    bad = (int(rp[0] != 0) + int(rp[-1] != len(cols)) + int(len(cols) % 2)
           + int(np.count_nonzero(steps < 0)))
    if bad:
        return bad, float("inf")
    bad += int(np.count_nonzero((cols < 0) | (cols >= n)))
    src_all = np.repeat(np.arange(n, dtype=np.int64), steps)
    bad += int(np.count_nonzero(src_all == cols))
    key = src_all * n + cols
    del src_all
    bad += int(np.count_nonzero(np.diff(key) <= 0))
    if bad:
        return bad, float("inf")
    # a sound CSR's keys ascend strictly: look each mirror up by bisection
    mine = RowSets.from_csr(rp, cols, rows)
    src = np.repeat(np.asarray(rows, np.int64), np.diff(mine.ptr))
    want = mine.ids * n + src
    pos = np.minimum(np.searchsorted(key, want), max(len(key) - 1, 0))
    found = key[pos] == want if len(key) else np.zeros(len(want), bool)
    return 0, int(np.count_nonzero(~found))


def compare_rows(candidates: list[RowSets], points, rows, eps: float,
                 metric: str) -> list[dict]:
    """Hold every candidate's sampled rows to the reference; returns, per
    candidate, ``mismatched_pairs`` and ``max_gap_ulp``."""
    n = len(points)
    rows = np.asarray(rows)
    out = [{"mismatched_pairs": 0, "max_gap_ulp": 0.0} for _ in candidates]
    for lo, hi, dist, thr, scale in distance_blocks(points, rows, metric):
        want = dist <= thr(eps)
        want[np.arange(hi - lo), rows[lo:hi]] = False      # no self pairs
        for res, cand in zip(out, candidates):
            diff = cand.mask(lo, hi, n) != want
            # ids out of range never reach the mask: count them apart
            sel = cand.ids[cand.ptr[lo]:cand.ptr[hi]]
            stray = int(np.count_nonzero((sel < 0) | (sel >= n)))
            res["mismatched_pairs"] += int(np.count_nonzero(diff)) + stray
            if scale is None:
                continue
            if stray:
                res["max_gap_ulp"] = float("inf")
            elif diff.any():
                gap = np.abs(dist[diff] - thr(eps)) / scale[diff]
                res["max_gap_ulp"] = max(res["max_gap_ulp"],
                                         float(gap.max()))
    return out


def check_graphs(graphs, points, rows, eps: float, metric: str) -> list[dict]:
    """Every number compared, per graph: ``graphs`` is a list of
    (row_ptr, col_ids) CSR arrays of the same ``points``."""
    n = len(points)
    form, sets = [], []
    for rp, cols in graphs:
        faults, asym = csr_readings(n, rp, cols, rows)
        form.append((faults, asym))
        # the rows of a CSR that breaks its form read as empty
        sets.append(RowSets.from_csr(np.asarray(rp, np.int64), cols, rows)
                    if asym != float("inf") else
                    RowSets(np.zeros(len(rows) + 1), np.zeros(0)))
    readings = compare_rows(sets, points, rows, eps, metric)
    for res, (faults, asym) in zip(readings, form):
        res["csr_faults"], res["asym_pairs"] = faults, asym
    return readings


def compared_numbers(metric: str) -> tuple[str, ...]:
    """The numbers that decide ``correct`` for a metric, in print order."""
    gap = "mismatched_pairs" if metric == "hamming" else "max_gap_ulp"
    return ("csr_faults", "asym_pairs", gap)


def worst(readings: list[dict], metric: str) -> dict:
    """The worst reading of each compared number over the graphs."""
    return {k: max((r[k] for r in readings), default=0)
            for k in compared_numbers(metric)}
