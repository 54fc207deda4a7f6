#!/usr/bin/env python3
"""Chip smoke test: the exact ε-NNG build, end to end, on a TPU.

Drives ``repro.nng.build_nng`` through its public entry point at the
paper's widths and checks every graph against a float64 numpy oracle::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip paths, four chips

One chip (the default):
  - sift width: ``synthetic_pointset(2^17, 128, "euclidean", seed=0)``
    (the ``nng-sift-1m`` dimension), ε = 3.3 (mean degree ~66), through
    all four paths: partition point/spatial x traversal tiles/tree, the
    tree forests built on the device;
  - word2bits width: ``synthetic_pointset(2^17, 25, "hamming", seed=0)``
    (800 bits), ε = 40, partition point, traversal tiles.
Four chips (``--chips 4``): the same sift-width set on a 4-device ring
through point x {tiles, tree} and spatial x ghost_mode {coll, ring} x
{tiles, tree}, each edge set compared bit for bit with the 1-device build
of the same points in this process.

Checks, per build: ``row_ptr[-1] == 2 * num_edges``; 2048 seeded sample
rows carry exactly the oracle's neighbour sets (euclidean rows may differ
only in pairs within the fp32 boundary tolerance of ε, hamming not at
all); the euclidean edge sets of all paths are identical.

Each build prints one JSON line. Its seconds (forest build, compile, the
compile-inclusive call and the steady ``elapsed_s``) are a record of this
run, not a benchmark. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure exits non-zero without it. The script needs a TPU and
compiled Pallas kernels, and runs everything in this one process.

n = 2^17 is the largest power of two the point partition holds on one
chip: its self tile materializes an n x n/32 uint32 bitmask, n^2/8 bytes
(2 GiB here, 128 GiB at the 2^20 points of nng-sift-1m), and at 2^18 the
bitmask epilogue's whole-row word block no longer fits the kernel's VMEM.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

N = 1 << 17
# k_cap: the neighbour-list capacity, set above each set's max degree
# (583 and 1503) so that no build re-plans and compiles twice
SIFT = dict(n=N, dim=128, metric="euclidean", eps=3.3, k_cap=640)
WORD2BITS = dict(n=N, dim=25, metric="hamming", eps=40, k_cap=1536)
SAMPLE_ROWS = 2048
EUCLIDEAN_PATHS = [("point", "tiles", "coll"), ("point", "tree", "coll"),
                   ("spatial", "tiles", "coll"), ("spatial", "tree", "coll")]
CROSS_CHIP_PATHS = [("point", "tiles", "coll"), ("point", "tree", "coll"),
                    ("spatial", "tiles", "coll"), ("spatial", "tiles", "ring"),
                    ("spatial", "tree", "coll"), ("spatial", "tree", "ring")]


class SmokeFailure(Exception):
    """A check failed: the run exits non-zero and prints no ok line."""


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its ``/jax/core/compile/*`` monitoring events while the clock is
    entered."""

    def __init__(self):
        self.total = 0.0

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def oracle_distances(pts, rows, metric: str) -> np.ndarray:
    """(len(rows), n) float64 true distances from the sample rows to every
    point — numpy, independent of the device code."""
    if metric == "hamming":
        bits = np.unpackbits(pts.view(np.uint8), axis=1).astype(np.float32)
        ones = bits.sum(axis=1, dtype=np.float64)
        same = (bits[rows] @ bits.T).astype(np.float64)    # exact: <= 800
        return ones[rows, None] + ones[None, :] - 2.0 * same
    x = pts.astype(np.float64)
    sq = (x * x).sum(axis=1)
    d2 = sq[rows, None] + sq[None, :] - 2.0 * (x[rows] @ x.T)
    return np.sqrt(np.maximum(d2, 0.0))


def sample_rows(n: int, count: int = SAMPLE_ROWS) -> np.ndarray:
    return np.sort(np.random.default_rng(0).choice(n, min(count, n),
                                                   replace=False))


def check_graph(g, pts, eps: float, metric: str, rows, dist) -> int:
    """Raise ``SmokeFailure`` unless ``g`` is the exact ε-graph on the
    sample rows. Returns the count of fp32 boundary pairs that differ."""
    from repro.launch.nng_run import fp32_boundary_tol

    if g.row_ptr[-1] != 2 * g.num_edges:
        raise SmokeFailure(f"row_ptr[-1]={g.row_ptr[-1]} != 2 * "
                           f"{g.num_edges} edges")
    got = np.zeros(dist.shape, bool)
    for k, i in enumerate(rows):
        got[k, g.col_ids[g.row_ptr[i]:g.row_ptr[i + 1]]] = True
    want = dist <= eps
    want[np.arange(len(rows)), rows] = False           # no self pairs
    kk, jj = np.nonzero(got != want)
    if not len(kk):
        return 0
    tol = fp32_boundary_tol(pts, eps, metric)
    worst = float(np.max(np.abs(dist[kk, jj] - eps)))
    if worst > tol:
        i, j = rows[kk[0]], jj[0]
        raise SmokeFailure(
            f"{len(kk)} sampled pairs differ from the float64 oracle, worst "
            f"|d - eps| = {worst:.3e} > tol {tol:.3e} (first: {i}-{j} "
            f"d={dist[kk[0], j]!r} in graph: {bool(got[kk[0], j])})")
    return len(kk)


def build(pts, cfg: dict, mesh, partition: str, traversal: str,
          ghost_mode: str, clock: CompileClock, rows, dist, label: str):
    """One ``build_nng`` call, checked against the oracle; prints its JSON
    line and returns the graph."""
    from repro.nng import build_nng

    c0, t0 = clock.total, time.perf_counter()
    g = build_nng(pts, cfg["eps"], metric=cfg["metric"], partition=partition,
                  traversal=traversal, mesh=mesh, k_cap=cfg["k_cap"],
                  ghost_mode=ghost_mode)
    wall = time.perf_counter() - t0
    boundary = check_graph(g, pts, cfg["eps"], cfg["metric"], rows, dist)
    st = g.stats
    print(json.dumps({
        "phase": label, "n": cfg["n"], "dim": cfg["dim"],
        "metric": cfg["metric"], "eps": cfg["eps"], "devices": mesh.size,
        "partition": partition, "traversal": traversal,
        "ghost_mode": g.meta.get("ghost_mode"), "edges": g.num_edges,
        "mean_degree": g.avg_degree, "replans": st.replans,
        "build_s": st.build_s, "compile_s": clock.total - c0,
        "call_s": wall, "elapsed_s": st.elapsed_s,
        "sample_rows": len(rows), "boundary_pairs": boundary}), flush=True)
    return g


def point_set(cfg: dict):
    from repro.data import synthetic_pointset

    pts = synthetic_pointset(cfg["n"], cfg["dim"], cfg["metric"], seed=0)
    rows = sample_rows(cfg["n"])
    return pts, rows, oracle_distances(pts, rows, cfg["metric"])


def same_edges(graphs: dict, what: str, pts, eps: float) -> None:
    """Raise ``SmokeFailure`` unless every graph has the first one's edges;
    the message names a few differing pairs with their float64 |d - eps|."""
    ref_label, ref = next(iter(graphs.items()))
    key = ref.edge_key()
    for label, g in graphs.items():
        diff = np.setxor1d(g.edge_key(), key)
        if len(diff):
            i, j = np.divmod(diff[:8], g.n)
            d = np.sqrt(((pts[i].astype(np.float64)
                          - pts[j].astype(np.float64)) ** 2).sum(axis=1))
            raise SmokeFailure(
                f"{what}: {label} has {g.num_edges} edges, {ref_label} "
                f"{ref.num_edges}; {len(diff)} differ, e.g. "
                + ", ".join(f"{a}-{b} |d-eps|={abs(e - eps):.2e}"
                            for a, b, e in zip(i, j, d)))


def run_one_chip(mesh, clock, sift=SIFT, word2bits=WORD2BITS) -> None:
    pts, rows, dist = point_set(sift)
    graphs = {}
    for part, trav, gm in EUCLIDEAN_PATHS:
        graphs[f"{part}/{trav}"] = build(pts, sift, mesh, part, trav, gm,
                                         clock, rows, dist, "sift")
    same_edges(graphs, "sift width, one chip", pts, sift["eps"])
    pts, rows, dist = point_set(word2bits)
    build(pts, word2bits, mesh, "point", "tiles", "coll", clock, rows, dist,
          "word2bits")


def run_cross_chip(mesh, mesh1, clock, sift=SIFT) -> None:
    devices = list(mesh.devices.flat)
    if len({d.id for d in devices}) != mesh.size:
        raise SmokeFailure(f"ring {mesh.devices} repeats a device")
    pts, rows, dist = point_set(sift)
    graphs = {}
    for part, trav, gm in CROSS_CHIP_PATHS:
        graphs[f"{mesh.size} devices {part}/{trav}/{gm}"] = build(
            pts, sift, mesh, part, trav, gm, clock, rows, dist,
            f"sift-{mesh.size}dev")
    # before the 1-device build: the ring's own high-water marks
    print(json.dumps({"peak_bytes_in_use": [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]}),
        flush=True)
    graphs["1 device point/tiles"] = build(
        pts, sift, mesh1, "point", "tiles", "coll", clock, rows, dist,
        "sift-1dev")
    same_edges(graphs, "sift width, ring vs one device", pts, sift["eps"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every path on one chip; 4: the cross-chip "
                         "paths on a 4-device ring vs one device")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    import jax

    from repro import nng
    from repro.kernels.ops import pallas_mode
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_ring_mesh

    if SRC not in Path(nng.__file__).resolve().parents:
        raise SmokeFailure(f"imported repro from {nng.__file__}, not {SRC}")
    enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {devs[0].platform}")
    if pallas_mode() != "compiled":
        raise SmokeFailure(f"Pallas mode {pallas_mode()!r}, not compiled")
    if len(devs) < args.chips:
        raise SmokeFailure(f"{args.chips} chips asked for, {len(devs)} found")

    with CompileClock() as clock:
        if args.chips == 1:
            run_one_chip(make_ring_mesh(1), clock)
        else:
            run_cross_chip(make_ring_mesh(args.chips), make_ring_mesh(1),
                           clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
