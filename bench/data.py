"""Point sets of the benchmark's configurations, made from a seed.

``clustered`` is the benchmark's own copy of the repository's clustered
low-intrinsic-dimension generator (``repro.data.synthetic_pointset``), so
that a change to the program cannot change the inputs it is measured on.

A configuration fixes its point set through ``data_seed``: every run of a
cell builds the graph of the same points, as users build the graph of one
dataset. ``--seed`` permutes the rows and draws the rows the check
samples, so every seed brings the same work in another order.
"""
from __future__ import annotations

import numpy as np


def clustered(n: int, dim: int, metric: str, seed: int,
              n_clusters: int | None = None, cluster_std: float = 0.3,
              intrinsic_dim: int | None = None) -> np.ndarray:
    """Clustered cloud on a low-dimensional manifold. ``metric ==
    "hamming"`` gives packed uint32 bit rows (``dim`` words); any other
    metric float32 rows of width ``dim``."""
    rng = np.random.default_rng(seed)
    n_clusters = n_clusters or max(8, int(np.sqrt(n) / 4))
    if metric != "hamming":
        idim = intrinsic_dim or max(2, dim // 8)
        basis = rng.normal(size=(idim, dim)).astype(np.float32)
        ctrs = rng.normal(size=(n_clusters, idim)).astype(np.float32) * 6.0
        assign = rng.integers(0, n_clusters, n)
        low = (ctrs[assign]
               + rng.normal(size=(n, idim)).astype(np.float32) * cluster_std)
        return (low @ basis / np.sqrt(idim)).astype(np.float32)
    words = dim
    ctrs = rng.integers(0, 2**32, size=(n_clusters, words), dtype=np.uint32)
    assign = rng.integers(0, n_clusters, n)
    pts = ctrs[assign].copy()
    # flip a small random subset of bits per point
    for _ in range(max(1, int(words * 32 * 0.03))):
        word = rng.integers(0, words, n)
        bit = rng.integers(0, 32, n).astype(np.uint32)
        pts[np.arange(n), word] ^= (np.uint32(1) << bit)
    return pts


def config_points(cfg: dict) -> np.ndarray:
    """The configuration's point set, in its generator's row order."""
    gen = cfg["generator"]
    return clustered(cfg["n"], cfg["dim"], cfg["metric"], gen["data_seed"],
                     n_clusters=gen.get("n_clusters"),
                     cluster_std=gen.get("cluster_std", 0.3),
                     intrinsic_dim=gen.get("intrinsic_dim"))


def run_inputs(cfg: dict, seed: int, sample_rows: int):
    """(points, rows): the configuration's points in the row order drawn
    from ``seed``, and the sorted rows the check compares."""
    base = config_points(cfg)
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 0x6E6E67])
    pts = np.ascontiguousarray(base[rng.permutation(len(base))])
    rows = np.sort(rng.choice(len(pts), min(sample_rows, len(pts)),
                              replace=False))
    return pts, rows
