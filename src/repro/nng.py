"""The public NNG front-end: ``build_nng`` — "build me the ε-graph of these
points under this metric on this mesh".

One entry point over the two device engines, with every axis a keyword:

  - ``metric``     a registry name ("euclidean", "hamming", "manhattan")
                   or a ``repro.core.metrics.Metric`` object — user-defined
                   metrics run end-to-end, with or without Pallas kernels.
  - ``partition``  "point" (Algorithm 4: systolic ring over point blocks)
                   or "spatial" (Algorithms 5+6: Voronoi landmark cells
                   with ε-ghosts).
  - ``traversal``  "tiles" (fused bitmask distance tiles) or "tree"
                   (device-resident cover-tree traversal).
  - ``planner``    "device" (one exact shard_map counting pass) or "host"
                   (numpy heuristic pass) — spatial partition only.

Both engines run under ONE plan → run → grow-on-overflow driver
(``drive``): engine-specific re-planning (k_cap growth vs ``LandmarkPlan``
capacity doubling) sits behind the small ``Engine`` interface, so the
overflow loop, timing, and stats plumbing exist exactly once.

The result is a CSR ``NNGraph`` (symmetric adjacency + ``RunStats`` +
provenance ``meta``) — see ``repro.core.graph``.

Point counts that do not divide the mesh are handled by duplicate-padding:
the first ``(-n) % nranks`` points are appended again. A duplicate row
changes no true distance, its extra edges reference ids >= n and are
dropped when the CSR is assembled — exactness is preserved for ANY metric
(unlike far-away sentinel rows, which need metric-specific geometry).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.distributed import (LandmarkPlan, delta_bcast_bytes,
                                    delta_traverse_run, ghost_coll_bytes,
                                    ghost_ring_bytes, landmark_run,
                                    make_nng_mesh, plan_landmark_device,
                                    plan_ring_schedule, resolve_ghost_mode,
                                    systolic_run)
from repro.core.graph import NNGraph, RunStats, SENTINEL
from repro.core.landmark import ghost_membership, lpt_assignment, select_centers
from repro.core.metrics import Metric, get_metric, register_metric  # noqa: F401 (re-export)
from repro.obs import count, recording, span

__all__ = ["build_nng", "delta_run", "drive", "DeltaEngine", "Engine",
           "PointPartitionEngine", "SpatialPartitionEngine", "grow_plan",
           "Metric", "get_metric", "register_metric"]


# ---------------------------------------------------------------------------
# the Engine interface + the ONE re-plan driver
# ---------------------------------------------------------------------------

class Engine:
    """One distributed ε-NNG engine behind the shared driver.

    Implementations hold the problem (points, eps, mesh, metric, options)
    and expose: an initial capacity plan, one exact-or-overflowing run, the
    overflow predicate, the grow step, and result extraction."""

    name: str = "?"

    def initial_plan(self):
        raise NotImplementedError

    def run(self, plan):
        """One engine invocation under ``plan``; returns the raw outputs."""
        raise NotImplementedError

    def overflowed(self, out) -> bool:
        raise NotImplementedError

    def grow(self, plan, out):
        """A strictly larger plan after an overflow."""
        raise NotImplementedError

    def neighbor_tables(self, out):
        """[(ids, nbrs), ...] SENTINEL-padded tables for CSR assembly,
        copied to the host by ``_fetch``."""
        raise NotImplementedError

    def run_stats(self, out, plan) -> RunStats:
        raise NotImplementedError


def _run_engine(engine: Engine, plan):
    """One engine invocation, finished on the device."""
    count("engine_calls")
    out = engine.run(plan)
    with span("nng.wait"):
        return jax.block_until_ready(out)


def _fetch(*arrays) -> list[np.ndarray]:
    """Copy engine outputs to the host, counting the bytes."""
    host = [np.asarray(a) for a in arrays]
    count("fetch_bytes", sum(a.nbytes for a in host))
    return host


def drive(engine: Engine, max_grows: int = 8, *, steady_state: bool = True):
    """THE plan → run → grow-on-overflow loop (both partitions share it).

    Returns (out, plan, replans, elapsed_s): the first non-overflowing
    outputs, the plan that produced them, how many grows it took, and the
    STEADY-STATE wall clock of that final configuration. Every grow changes
    a static capacity knob, so the winning run is always a freshly traced +
    compiled program — its first invocation conflates compile with
    execution. The winner is therefore invoked a second time (a jit cache
    hit, the ``nng.rerun`` span) and THAT wall clock is reported:
    ``RunStats.elapsed_s`` and both bench JSONs measure engine execution,
    never compilation.

    ``steady_state=False`` skips the timing re-run and reports the warm
    (compile-inclusive) ``nng.run`` wall clock — for callers that only
    consume the neighbor tables, where doubling the winning run buys
    nothing."""
    with span("nng.plan"):
        plan = engine.initial_plan()
    for attempt in range(max_grows):
        with span("nng.run") as timed:      # warm: trace + compile
            out = _run_engine(engine, plan)
            with span("nng.check"):
                overflowed = engine.overflowed(out)
        if not overflowed:
            if steady_state:
                with span("nng.rerun") as timed:
                    out = _run_engine(engine, plan)
            return out, plan, attempt, timed.seconds
        with span("nng.grow"):
            plan = engine.grow(plan, out)
    raise RuntimeError(
        f"{engine.name} engine: overflow persists after {max_grows} grows "
        f"(last plan: {plan})")


# ---------------------------------------------------------------------------
# point partitioning (systolic ring, Algorithm 4)
# ---------------------------------------------------------------------------

class PointPartitionEngine(Engine):
    name = "point"

    def __init__(self, points, eps, mesh, metric, *, k_cap: int = 64,
                 prune: bool = True, traversal: str = "tiles",
                 forest: dict | None = None, axis: str = "ring",
                 forest_backend: str = "device"):
        self.metric = get_metric(metric)
        self.points = np.asarray(points)
        self.eps = float(eps)
        self.mesh = mesh
        self.k_cap = int(k_cap)
        self.prune = prune
        self.traversal = traversal
        self.axis = axis
        self.build_s = 0.0
        if traversal == "tree" and forest is None:
            from repro.core.flat_tree import (build_block_forests,
                                              stack_device_forests)
            with span("nng.forest") as timed:
                if forest_backend == "device":
                    forest = jax.block_until_ready(build_block_forests(
                        self.points, mesh.size, self.metric,
                        backend="device", mesh=mesh))
                else:
                    forest = stack_device_forests(build_block_forests(
                        self.points, mesh.size, self.metric.host))
            self.build_s = timed.seconds
        self.forest = forest
        # the split ring schedule is static (part of the compiled program),
        # so plan it once per engine — the grow loop only changes k_cap
        self.ring_schedule = None
        if traversal == "tree":
            self.ring_schedule = plan_ring_schedule(
                self.points, mesh.size, self.eps, metric=self.metric,
                prune=self.prune)

    def initial_plan(self):
        return self.k_cap

    def run(self, k_cap):
        count("ring_bytes", self._ring_hop_bytes(k_cap))
        return systolic_run(
            self.points, self.eps, self.mesh, metric=self.metric,
            k_cap=k_cap, prune=self.prune, traversal=self.traversal,
            forest=self.forest, axis=self.axis,
            ring_schedule=self.ring_schedule)

    def overflowed(self, out):
        return bool(np.asarray(out[2]).any())

    def grow(self, k_cap, out):
        # cnt is exact even on overflow: one grow always suffices
        return max(2 * k_cap, int(np.asarray(out[1]).max()))

    def neighbor_tables(self, out):
        [nbrs] = _fetch(out[0])
        return [(np.arange(len(nbrs), dtype=np.int64), nbrs)]

    def _ring_comm_bytes(self, k_cap: int) -> dict:
        """Per-channel ring bytes, counting EVERY array that actually
        rotates (summed over ranks for the full run; hop counts mirror the
        device schedules in ``device.py`` exactly):

        - ``ring_points``: the visiting block each hop — point rows plus
          the block-id payload (one int32 ``id0`` scalar on the tiles
          flavor, the (n_loc,) id vector on the tree flavor). Double
          buffering pays one extra priming hop on the tiles flavor; the
          tree flavor makes exactly ``rounds`` point hops.
        - ``ring_forest`` (tree only): the levelized forest tables — one
          jump-permute per "forest"-mode round of the split schedule (a
          jump costs one hop's bytes no matter how many positions it
          covers).
        - ``ring_mirror``: the visiting block's neighbor accumulator
          ((n_loc, k_cap) ids + (n_loc,) counts) — ``rounds`` in-loop hops
          plus the final shift-``rounds`` return home.
        - ``ring_summary`` (prune only): the one-shot block-summary
          all_gather in ``_round_skip_flags`` — each rank contributes its
          (dim,) center plus the scalar radius.
        """
        nranks = self.mesh.size
        rounds = nranks // 2
        if rounds == 0:
            return {"ring_points": 0.0, "ring_mirror": 0.0}
        n, dim = self.points.shape
        n_loc = n // nranks
        item = self.points.dtype.itemsize
        mirror_hop = n_loc * k_cap * 4 + n_loc * 4
        bytes_ = {"ring_mirror": float(nranks * (rounds + 1) * mirror_hop)}
        if self.prune:
            bytes_["ring_summary"] = float(nranks * (dim * item + 4))
        if self.traversal == "tree":
            pt_hop = n_loc * dim * item + n_loc * 4
            bytes_["ring_points"] = float(nranks * rounds * pt_hop)
            forest_hop = sum(
                np.asarray(v).nbytes for v in self.forest.values()) / nranks
            fhops = sum(m == "forest" for m in self.ring_schedule)
            bytes_["ring_forest"] = float(nranks * fhops * forest_hop)
        else:
            pt_hop = n_loc * dim * item + 4
            bytes_["ring_points"] = float(nranks * (rounds + 1) * pt_hop)
        return bytes_

    def _ring_hop_bytes(self, k_cap: int) -> float:
        """Bytes each rank sends by ``ppermute`` in one engine call: the
        ring channels of ``_ring_comm_bytes`` (all but the block-summary
        all_gather), per rank."""
        per_run = self._ring_comm_bytes(k_cap)
        return sum(v for ch, v in per_run.items()
                   if ch != "ring_summary") / self.mesh.size

    def run_stats(self, out, k_cap) -> RunStats:
        nranks = self.mesh.size
        rounds = nranks // 2
        scheduled = nranks * (rounds + 1)
        if nranks % 2 == 0 and rounds > 0:
            scheduled -= nranks // 2      # halving round: one side per pair
        scanned, full = np.asarray(out[6], np.float64).sum(axis=0)
        return RunStats(
            tiles_scheduled=float(scheduled),
            tiles_skipped=float(np.asarray(out[3]).sum()),
            dists_evaluated=float(np.asarray(out[4]).sum()),
            nodes_pruned=float(np.asarray(out[5]).sum()),
            comm_bytes=self._ring_comm_bytes(k_cap),
            epilogue_scan_pct=100.0 * scanned / full if full else None,
        )


# ---------------------------------------------------------------------------
# spatial partitioning (Voronoi landmarks + ε-ghosts, Algorithms 5 + 6)
# ---------------------------------------------------------------------------

def grow_plan(plan: LandmarkPlan) -> LandmarkPlan:
    """Double every capacity knob of a LandmarkPlan (overflow re-plan)."""
    return LandmarkPlan(
        m_centers=plan.m_centers,
        cap_coal=2 * plan.cap_coal,
        cap_ghost=2 * plan.cap_ghost,
        g_per_pt=min(2 * plan.g_per_pt, plan.m_centers),
        k_cap=2 * plan.k_cap,
        cap_rank=max(2 * plan.cap_rank, 32) if plan.cap_rank else 0,
    )


class SpatialPartitionEngine(Engine):
    name = "spatial"

    def __init__(self, points, eps, mesh, metric, *, k_cap: int = 128,
                 planner: str = "device", m_centers: int | None = None,
                 traversal: str = "tiles", centers=None, f=None, cell=None,
                 plan: LandmarkPlan | None = None, forest: dict | None = None,
                 seed: int = 0, axis: str = "ring",
                 forest_backend: str = "device", ghost_mode: str = "coll"):
        if ghost_mode not in ("coll", "ring", "auto"):
            raise ValueError(f"unknown ghost_mode {ghost_mode!r} "
                             "(want 'coll', 'ring' or 'auto')")
        self.metric = get_metric(metric)
        self.points = np.asarray(points)
        self.eps = float(eps)
        self.mesh = mesh
        self.k_cap = int(k_cap)
        self.planner = planner
        self.traversal = traversal
        self.axis = axis
        self.plan = plan
        self.ghost_mode = ghost_mode
        n = len(self.points)
        nranks = mesh.size
        met = self.metric.host
        rng = np.random.default_rng(seed)
        if centers is None:
            m = m_centers or max(2 * nranks, 32)
            centers = self.points[select_centers(n, m, rng)]
        self.centers = np.asarray(centers)
        self.m_centers = len(self.centers)
        # the host (n x m) Voronoi argmin is only needed for the LPT
        # assignment, the host planner, or tree-forest scoping — legacy
        # tiles-flavor callers that supply (f, plan) skip it entirely
        if cell is None and (f is None or traversal == "tree"
                             or (plan is None and planner == "host")):
            cell = np.argmin(met.cdist(self.points, self.centers), axis=1)
        self.cell = None if cell is None else np.asarray(cell)
        if f is None:
            f = lpt_assignment(
                np.bincount(self.cell, minlength=self.m_centers), nranks)
        self.f = np.asarray(f, np.int32)
        self.build_s = 0.0
        if traversal == "tree" and forest is None:
            from repro.core.flat_tree import (build_cell_forests,
                                              stack_device_forests)
            with span("nng.forest") as timed:
                if forest_backend == "device":
                    forest = jax.block_until_ready(build_cell_forests(
                        self.points, self.cell, self.f, nranks, self.metric,
                        backend="device", mesh=mesh))
                else:
                    forest = stack_device_forests(build_cell_forests(
                        self.points, self.cell, self.f, nranks,
                        self.metric.host))
            self.build_s = timed.seconds
        self.forest = forest

    # -- planning -----------------------------------------------------------
    def _plan_host(self) -> LandmarkPlan:
        """Host numpy pass (float64 ghost bound — may undercount the
        engine's slacked test; the grow loop covers the gap)."""
        met = self.metric.host
        n = len(self.points)
        nranks = self.mesh.size
        m = self.m_centers
        if n % nranks != 0:
            raise ValueError(
                f"points are not shardable: n={n} is not divisible by the "
                f"mesh size {nranks} — pad to a multiple (build_nng's "
                f"duplicate padding does this automatically)")
        dmat = np.asarray(met.true(met.cdist(self.points, self.centers)))
        d_pC = dmat[np.arange(n), self.cell]
        gmask = ghost_membership(dmat, self.cell, d_pC, self.eps)
        g_per_pt = int(gmask.sum(axis=1).max())
        # row-to-rank map of the block-sharded input: exactly n // nranks
        # rows per rank (np.repeat with a scalar count would silently DROP
        # the remainder rows if the divisibility check above were absent)
        src_rank = np.repeat(np.arange(nranks), n // nranks)
        coal = np.zeros((nranks, nranks), np.int64)
        np.add.at(coal, (src_rank, self.f[self.cell]), 1)
        gsrc = np.repeat(src_rank, m).reshape(n, m)[gmask]
        gdst = np.broadcast_to(self.f[None, :], (n, m))[gmask]
        gcnt = np.zeros((nranks, nranks), np.int64)
        np.add.at(gcnt, (gsrc, gdst), 1)
        return LandmarkPlan(
            m_centers=m, cap_coal=int(coal.max()) + 8,
            cap_ghost=int(gcnt.max()) + 8, g_per_pt=max(g_per_pt, 1),
            k_cap=self.k_cap,
            cap_rank=int(coal.sum(axis=0).max()) + 8)

    def initial_plan(self) -> LandmarkPlan:
        if self.plan is not None:
            return self.plan
        if self.planner == "device":
            # ONE shard_map counting pass: exact coalesce/ghost capacities
            # (the same tests the engine applies) — the common case never
            # hits the grow loop
            return plan_landmark_device(
                self.points, self.centers, self.f, self.eps, self.mesh,
                metric=self.metric, k_cap=self.k_cap, axis=self.axis)
        if self.planner == "host":
            return self._plan_host()
        raise ValueError(f"unknown planner {self.planner!r}")

    # -- engine steps -------------------------------------------------------
    def resolved_ghost_mode(self, plan: LandmarkPlan) -> str:
        """The mode this plan actually runs: ``"auto"`` resolves per-plan
        from the exact byte models (``resolve_ghost_mode``), so a grown
        plan may legitimately flip the choice — each plan is a different
        compiled program anyway."""
        return resolve_ghost_mode(
            self.ghost_mode, plan, self.points.shape[1],
            self.points.dtype.itemsize, self.mesh.size)

    def run(self, plan):
        return landmark_run(
            self.points, self.eps, self.centers, self.f, self.mesh, plan,
            metric=self.metric, traversal=self.traversal,
            forest=self.forest, cell=self.cell, axis=self.axis,
            ghost_mode=self.resolved_ghost_mode(plan))

    def overflowed(self, out):
        return bool(np.asarray(out[6]).any())

    def grow(self, plan, out):
        return grow_plan(plan)

    def neighbor_tables(self, out):
        ids, nbrs, gids, gnbrs = _fetch(out[0], out[1], out[3], out[4])
        return [(ids, nbrs), (gids, gnbrs)]

    def _landmark_comm_bytes(self, plan: LandmarkPlan) -> dict:
        """Per-channel exchange bytes. ``coalesce`` moves three
        (nranks, cap, …) all_to_all operands per rank — point rows, global
        ids, cell assignments. The ghost channel depends on the resolved
        mode: ``ghost`` (capacity-padded all_to_all of ghost copies) or
        ``ghost_ring`` (nranks // 2 ppermute hops of the compacted block +
        ids + packed Lemma-1 bits) — both from the canonical formulas in
        ``device.py`` that ``resolve_ghost_mode`` compares."""
        nranks = self.mesh.size
        dim = self.points.shape[1]
        item = self.points.dtype.itemsize
        row_bytes = item * dim + 4 + 4   # pts + id + cell
        lw = nranks * plan.cap_coal
        out = {"coalesce": float(nranks * lw * row_bytes)}
        if self.resolved_ghost_mode(plan) == "ring":
            out["ghost_ring"] = float(ghost_ring_bytes(
                nranks, plan.cap_rank, dim, item, plan.m_centers))
        else:
            out["ghost"] = float(ghost_coll_bytes(
                nranks, plan.cap_ghost, dim, item))
        return out

    def run_stats(self, out, plan: LandmarkPlan) -> RunStats:
        return RunStats(
            tiles_scheduled=float(np.asarray(out[8]).sum()),
            tiles_skipped=float(np.asarray(out[7]).sum()),
            dists_evaluated=float(np.asarray(out[9]).sum()),
            nodes_pruned=float(np.asarray(out[10]).sum()),
            comm_bytes=self._landmark_comm_bytes(plan),
        )


# ---------------------------------------------------------------------------
# delta traversal (online maintenance — repro.stream's engine)
# ---------------------------------------------------------------------------

class DeltaEngine(Engine):
    """Query ONE inserted batch against the per-rank forests.

    The online-insert engine: instead of re-running a full systolic or
    landmark schedule over the corpus, the (tiny) batch is broadcast and
    every rank traverses its local forest once — work scales with the
    batch's frontier, not with n. Shares ``drive``'s grow-on-overflow
    loop; the only plan knob is ``k_cap``.
    """

    name = "delta"

    def __init__(self, batch_points, batch_ids, forest: dict, eps, mesh,
                 metric, *, k_cap: int = 64, axis: str = "ring"):
        self.metric = get_metric(metric)
        self.forest = forest
        self.eps = float(eps)
        self.mesh = mesh
        self.k_cap = int(k_cap)
        self.axis = axis
        self.build_s = 0.0
        qp = np.asarray(batch_points)
        ids = np.asarray(batch_ids, np.int64)
        assert len(qp) == len(ids) and len(qp) > 0
        # pad the batch to the next power of two (>= 8): arbitrary batch
        # sizes would retrace the jitted program per size; padded rows
        # carry SENTINEL ids, so their hits drop at CSR assembly
        m = 8
        while m < len(qp):
            m *= 2
        self.qp = np.concatenate(
            [qp, np.broadcast_to(qp[:1], (m - len(qp),) + qp.shape[1:])])
        self.qids = np.concatenate(
            [ids, np.full(m - len(ids), SENTINEL, np.int64)])

    def initial_plan(self):
        return self.k_cap

    def run(self, k_cap):
        return delta_traverse_run(
            self.qp, self.qids, self.forest, self.eps, self.mesh,
            metric=self.metric, k_cap=k_cap, axis=self.axis)

    def overflowed(self, out):
        # cnt is exact even on overflow (popcount of the full bitmask)
        return bool((np.asarray(out[1]) > np.asarray(out[0]).shape[1]).any())

    def grow(self, k_cap, out):
        return max(2 * k_cap, int(np.asarray(out[1]).max()))

    def neighbor_tables(self, out):
        nranks = self.mesh.shape[self.axis]
        [nbrs] = _fetch(out[0])
        return [(np.tile(self.qids, nranks), nbrs)]

    def run_stats(self, out, k_cap) -> RunStats:
        nranks = self.mesh.shape[self.axis]
        return RunStats(
            dists_evaluated=float(np.asarray(out[2]).sum()),
            nodes_pruned=float(np.asarray(out[3]).sum()),
            comm_bytes={"delta_bcast": float(delta_bcast_bytes(
                nranks, self.qp.shape[0], self.qp.shape[1],
                self.qp.dtype.itemsize))},
        )


def delta_run(batch_points, batch_ids, forest: dict, eps, mesh, *,
              metric="euclidean", k_cap: int = 64, axis: str = "ring",
              max_grows: int = 8):
    """Directed new-edge pairs of an inserted batch vs the current forest.

    Runs ``DeltaEngine`` under ``drive`` (without the steady-state timing
    re-run — update latency is what matters online) and flattens the
    rank-stacked neighbor tables to (src, dst) directed id pairs plus a
    ``RunStats``. Symmetrize downstream (``NNGraph.delta_add_edges``
    canonicalizes) — a batch-internal pair appears from both endpoints.
    """
    with recording() as rec:
        engine = DeltaEngine(batch_points, batch_ids, forest, eps, mesh,
                             metric, k_cap=k_cap, axis=axis)
        out, plan, replans, elapsed = drive(engine, max_grows=max_grows,
                                            steady_state=False)
        with span("nng.stats"):
            stats = engine.run_stats(out, plan)
        stats.replans = replans
        stats.elapsed_s = elapsed
        with span("nng.fetch"):
            [(ids, nbrs)] = engine.neighbor_tables(out)
    rec.into(stats)
    valid = ids != SENTINEL
    ii, kk = np.nonzero((nbrs != SENTINEL) & valid[:, None])
    return ids[ii], nbrs[ii, kk].astype(np.int64), stats


# ---------------------------------------------------------------------------
# the public entry point
# ---------------------------------------------------------------------------

def build_nng(
    points,
    eps: float,
    *,
    metric="euclidean",
    partition: str = "point",
    traversal: str = "tiles",
    planner: str = "device",
    mesh=None,
    k_cap: int | None = None,
    prune: bool = True,
    m_centers: int | None = None,
    seed: int = 0,
    max_grows: int = 8,
    forest_backend: str = "device",
    ghost_mode: str = "coll",
) -> NNGraph:
    """Build the exact ε-neighbor graph of ``points`` under ``metric``,
    distributed over ``mesh``. Returns a CSR ``NNGraph``.

    See the module docstring for the axes. ``k_cap`` seeds the neighbor
    list capacity (grown automatically on overflow); ``mesh`` defaults to
    a ring over all available devices; any ``n`` is accepted (duplicate
    padding up to the mesh size, stripped from the result).
    ``forest_backend`` ("device", the default, or "host")
    picks who runs the cover-forest construction for ``traversal="tree"``:
    the jit device builder (``flat_tree_device``, the end-to-end
    device-resident path) or the float64 host oracle; the forest phase is
    timed separately in ``RunStats.build_s``. ``ghost_mode`` (spatial
    partition only) selects the ε-ghost schedule: ``"coll"`` (capacity-
    padded all_to_all, the default), ``"ring"`` (ghost-free block
    rotation), or ``"auto"`` (per-plan pick from the exact byte models —
    the resolved choice lands in ``meta["ghost_mode"]``).

    ``g.stats`` also holds the host side of the build: its ``nng.*`` spans
    (``repro.obs``) and the counters ``engine_calls``, ``compiles``,
    ``compile_s``, ``fetch_bytes``, ``table_slots``, ``pairs_selected``,
    ``csr_mirror_added`` and, on the point partition, ``ring_bytes``.
    """
    with recording() as rec:
        with span("nng.prepare"):
            met = get_metric(metric)
            if mesh is None:
                mesh = make_nng_mesh()
            points = np.ascontiguousarray(np.asarray(points, met.host.dtype))
            n = len(points)
            if n == 0:
                return NNGraph(0, np.zeros(1, np.int64), np.zeros(0, np.int32),
                               meta={"metric": met.name, "eps": float(eps)})
            pad = (-n) % mesh.size
            if pad:
                # duplicate-pad by cycling the input (np.resize) — works even
                # when pad > n (tiny point sets on wide meshes)
                run_points = np.concatenate(
                    [points, np.resize(points, (pad,) + points.shape[1:])])
            else:
                run_points = points

            if partition == "point":
                engine = PointPartitionEngine(
                    run_points, eps, mesh, met, k_cap=k_cap or 64, prune=prune,
                    traversal=traversal, forest_backend=forest_backend)
            elif partition == "spatial":
                engine = SpatialPartitionEngine(
                    run_points, eps, mesh, met, k_cap=k_cap or 128,
                    planner=planner, m_centers=m_centers, traversal=traversal,
                    seed=seed, forest_backend=forest_backend,
                    ghost_mode=ghost_mode)
            else:
                raise ValueError(
                    f"unknown partition {partition!r} (want 'point' or "
                    "'spatial')")

        out, plan, replans, elapsed = drive(engine, max_grows=max_grows)
        with span("nng.stats"):
            stats = engine.run_stats(out, plan)
        stats.replans = replans
        stats.elapsed_s = elapsed
        stats.build_s = engine.build_s
        meta = {
            "metric": met.name, "eps": float(eps), "partition": partition,
            "traversal": traversal, "nranks": mesh.size, "padded": pad,
            "plan": plan,
        }
        if traversal == "tree":
            meta["forest_backend"] = forest_backend
        if partition == "point" and engine.ring_schedule is not None:
            meta["ring_schedule"] = tuple(engine.ring_schedule)
        if partition == "spatial":
            meta["planner"] = planner
            meta["m_centers"] = engine.m_centers
            # the RESOLVED mode, never "auto" — what the final plan compiled
            meta["ghost_mode"] = engine.resolved_ghost_mode(plan)
        with span("nng.fetch"):
            tables = engine.neighbor_tables(out)
        with span("nng.csr"):
            g = NNGraph.from_neighbor_tables(n, tables, stats=stats, meta=meta)
        rec.into(stats)
    return g
