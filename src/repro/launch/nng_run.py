"""Distributed ε-NNG job driver (the paper's workload, end to end).

A thin CLI over the public front-end ``repro.nng.build_nng``: pick a
metric (any registry name), a partition strategy, a traversal flavor and a
planner, get back the CSR ``NNGraph``, optionally verified against the
brute-force oracle.

Runs on the available devices (ring mesh); on this container that is 1 CPU
device unless XLA_FLAGS requests more.

Usage:
  python -m repro.launch.nng_run --n 4096 --dim 8 --eps 1.0 \
      --algo landmark --verify
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.nng_run --n 8192 --dim 16 --algo systolic \
      --metric manhattan

``run_systolic`` / ``run_landmark`` remain as thin adapters over the
unified ``repro.nng.drive`` loop, returning the historical tuple shapes
(benchmarks and regression tests still consume them).
"""
from __future__ import annotations

import argparse
import numpy as np

SEN = 2**31 - 1


def run_systolic(pts, eps, mesh, *, metric="euclidean", k_cap=64,
                 prune=True, max_grows=6, traversal="tiles", forest=None):
    """Systolic engine via the unified driver. Returns
    (nbrs, cnt, counters, k_cap) with overflow guaranteed False;
    ``counters`` = (tiles_skipped, dists_evaluated, nodes_pruned) per-rank
    arrays. ``traversal="tree"`` builds per-block cover-tree forests once
    and traverses them on device (the re-plan loop reuses them)."""
    from repro.nng import PointPartitionEngine, drive
    engine = PointPartitionEngine(
        pts, eps, mesh, metric, k_cap=k_cap, prune=prune,
        traversal=traversal, forest=forest)
    # adapter callers consume the tables, not elapsed_s: skip the timing
    # re-run (steady-state timing lives in build_nng / the benches)
    out, k_final, _, _ = drive(engine, max_grows=max_grows,
                               steady_state=False)
    nbrs, cnt, _ovf, skipped, dists, pruned = out
    return nbrs, cnt, (skipped, dists, pruned), k_final


def grow_plan(plan):
    """Double every capacity knob of a LandmarkPlan (overflow re-plan)."""
    from repro.nng import grow_plan as _grow
    return _grow(plan)


def run_landmark(pts, eps, centers, f, mesh, plan, *, metric="euclidean",
                 max_grows=6, traversal="tiles", cell=None, forest=None,
                 ghost_mode="coll"):
    """Landmark engine via the unified driver. Returns (outputs, plan)
    with the overflow flag (outputs[6]) guaranteed False; outputs[7..10]
    are the per-rank tiles_skipped / tiles_scheduled / dists_evaluated /
    nodes_pruned counters of the final, non-overflowing run.
    ``traversal="tree"`` builds the per-cell forests once from ``cell``
    (the Voronoi assignment matching ``centers``/``f``); re-plans reuse
    them — capacities don't change the trees."""
    from repro.nng import SpatialPartitionEngine, drive
    if traversal == "tree":
        assert cell is not None, "traversal='tree' needs the cell assignment"
    engine = SpatialPartitionEngine(
        pts, eps, mesh, metric, traversal=traversal, centers=centers, f=f,
        cell=cell, plan=plan, forest=forest, ghost_mode=ghost_mode)
    out, plan, _, _ = drive(engine, max_grows=max_grows,
                            steady_state=False)
    return out, plan


def edges_from_neighbor_lists(ids, nbrs):
    """(ids (m,), nbrs (m, k)) SENTINEL-padded -> (src, dst) edge arrays."""
    ids = np.asarray(ids)
    nbrs = np.asarray(nbrs)
    valid = ids != SEN
    ii, kk = np.nonzero((nbrs != SEN) & valid[:, None])
    return ids[ii], nbrs[ii, kk]


def fp32_boundary_tol(pts, eps: float, metric: str) -> float:
    """How far from eps (in true distance) a pair may sit and still be
    classified differently by the device's fp32 arithmetic than by a
    float64 oracle. Zero for integer (packed-bit) metrics."""
    if pts.dtype == np.uint32:
        return 0.0
    if metric == "euclidean":
        scale = float(np.max(np.abs(pts.astype(np.float64)))) ** 2
        return 1e-5 * (scale + eps ** 2) / max(eps, 1e-9)
    # additive float metrics (L1, user)
    scale = float(np.max(np.abs(pts.astype(np.float64))))
    return 1e-5 * (scale * pts.shape[1] + eps) + 1e-6


def _run_updates(args, pts, mesh, partition):
    """``--updates`` replay: build on a prefix, stream the reserved points
    in as insert batches interleaved with random deletes, report update
    throughput and delta-log state, optionally verify the final view."""
    from repro.stream import OnlineNNG

    rng = np.random.default_rng(args.seed)
    b = max(args.update_batch, 1)
    reserve = min(args.updates * b, len(pts) // 2)
    n0 = len(pts) - reserve
    o = OnlineNNG(pts[:n0], args.eps, metric=args.metric,
                  partition=partition, mesh=mesh, k_cap=args.k_cap,
                  seed=args.seed)
    print(f"online: built on {n0}, replaying {args.updates} updates "
          f"(batch {b})")
    cursor = n0
    for step in range(args.updates):
        if step % 3 == 2 and o.num_live > b:     # every third op: delete
            live = np.flatnonzero(o.live)
            o.delete(rng.choice(live, size=min(b, len(live) // 2),
                                replace=False))
            kind = "delete"
        elif cursor < len(pts):
            o.insert(pts[cursor:cursor + b])
            cursor = min(cursor + b, len(pts))
            kind = "insert"
        else:
            break
        st = o.last_update_stats
        print(f"  [{step}] {kind}: live={o.num_live} "
              f"delta_edges={o.graph.delta_edges} "
              f"dists={0 if st is None else st.dists_evaluated:.0f}")
    g = o.graph
    print(f"{g} after updates: update_s={g.stats.update_s:.2f}s "
          f"edges_added={g.stats.edges_added:.0f} "
          f"edges_removed={g.stats.edges_removed:.0f} "
          f"compactions={g.meta.get('compactions', 0)}")
    if args.verify:
        from repro.core.brute import brute_force_graph
        live = np.flatnonzero(o.live)
        gb = brute_force_graph(o.points[live], args.eps, args.metric)
        # compare on live ids: relabel brute's compact ids back to globals
        key = g.edge_key()
        src, dst = live[gb.src], live[gb.dst]
        bkey = np.sort(src * g.n + dst)
        if np.array_equal(key, bkey):
            print(f"verify vs brute force on live points: EXACT MATCH ({gb})")
        else:
            print(f"verify: {len(np.setxor1d(key, bkey))} differing edges "
                  "-> MISMATCH")
            raise SystemExit(1)
    return g


def main(argv=None):
    from repro.core.metrics import registered_metrics

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--eps", type=float, default=1.0)
    ap.add_argument("--metric", default="euclidean",
                    choices=list(registered_metrics()))
    ap.add_argument("--algo", default="landmark",
                    choices=["systolic", "landmark"],
                    help="partition strategy: systolic = point "
                         "partitioning, landmark = spatial partitioning")
    ap.add_argument("--k-cap", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--no-prune", action="store_true",
                    help="disable block-summary tile pruning (systolic)")
    ap.add_argument("--traversal", default="tiles", choices=["tiles", "tree"],
                    help="per-tile evaluation: dense bitmask tiles or "
                         "device-resident cover-tree traversal")
    ap.add_argument("--planner", default="device", choices=["device", "host"],
                    help="landmark capacity planning: one shard_map "
                         "counting pass (exact) or the host numpy pass")
    ap.add_argument("--ghost-mode", default="coll",
                    choices=["coll", "ring", "auto"],
                    help="landmark ε-ghost schedule: capacity-padded "
                         "all_to_all (coll), ghost-free block rotation "
                         "(ring), or the byte-model pick (auto)")
    ap.add_argument("--updates", type=int, default=0,
                    help="online-maintenance replay: reserve part of the "
                         "point set, build the graph on the rest, then run "
                         "this many randomized insert/delete batches "
                         "through repro.stream.OnlineNNG (--verify checks "
                         "the FINAL merged view against brute force)")
    ap.add_argument("--update-batch", type=int, default=32,
                    help="points per online insert/delete batch")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from repro.data import synthetic_pointset
    from repro.launch.mesh import make_ring_mesh
    from repro.nng import build_nng

    mesh = make_ring_mesh()
    partition = "point" if args.algo == "systolic" else "spatial"
    pts = synthetic_pointset(args.n, args.dim, args.metric, seed=args.seed)
    print(f"n={args.n} dim={args.dim} metric={args.metric} eps={args.eps} "
          f"ranks={mesh.size} partition={partition} "
          f"traversal={args.traversal}")

    if args.updates > 0:
        return _run_updates(args, pts, mesh, partition)

    g = build_nng(
        pts, args.eps, metric=args.metric, partition=partition,
        traversal=args.traversal, planner=args.planner, mesh=mesh,
        k_cap=args.k_cap, prune=not args.no_prune, seed=args.seed,
        ghost_mode=args.ghost_mode)
    if partition == "spatial":
        print(f"ghost_mode={g.meta['ghost_mode']}"
              + (" (auto)" if args.ghost_mode == "auto" else ""))
    st = g.stats
    print(f"tiles skipped={st.tiles_skipped:.0f}/{st.tiles_scheduled:.0f} "
          f"dists_evaluated={st.dists_evaluated:.0f} "
          f"nodes_pruned={st.nodes_pruned:.0f} "
          f"comm_bytes={st.total_comm_bytes:.0f} replans={st.replans}")
    print(f"{g} in {st.elapsed_s:.2f}s (plan={g.meta['plan']})")

    if args.verify:
        from repro.core.brute import brute_force_graph
        from repro.core.metrics_host import get_host_metric
        gb = brute_force_graph(pts, args.eps, args.metric)
        if g == gb:
            print(f"verify vs brute force: EXACT MATCH ({gb})")
        else:
            # device tiles evaluate fp32; allow only knife-edge differences
            # (|d - eps| within fp32 error) — the paper's float
            # implementations have the same boundary property
            met = get_host_metric(args.metric)
            n = g.n
            a = set(g.edge_key().tolist())
            bset = set(gb.edge_key().tolist())
            diff = np.array(sorted(a ^ bset), dtype=np.int64)
            ii, jj = diff // n, diff % n
            dd = np.asarray(met.true(met.rowwise(pts[ii], pts[jj])))
            tol = fp32_boundary_tol(pts, args.eps, args.metric)
            worst = float(np.max(np.abs(dd - args.eps)))
            ok = worst <= tol
            print(f"verify: {len(diff)} boundary edges, worst |d-eps|="
                  f"{worst:.2e} (tol {tol:.2e}) -> "
                  f"{'EXACT up to fp32 boundary' if ok else 'MISMATCH'}")
            if not ok:
                raise SystemExit(1)
    return g


if __name__ == "__main__":
    main()
