"""Collective-traffic audit (RA201/RA202).

Walks the traced shard_map jaxprs of both engines, statically derives
per-channel collective bytes from operand shapes × loop multipliers, and
cross-checks them EXACTLY against the ``RunStats.comm_bytes`` formulas in
``repro.nng``. This is the static re-derivation of the PR 6 lesson (the
~10× under-reported ring-forest bytes): the byte accounting must follow
from the *program*, not from a hand-maintained formula that can drift.

Accounting convention (same as RunStats): a collective whose per-rank
operand is B bytes contributes ``nranks * B`` per execution — every rank
sends its operand once per hop.

Channel attribution works on the traced per-rank avals:

- ``ppermute`` of the (n_loc, dim) point block in the metric dtype
  anchors ``ring_points``; of the (n_loc, k_cap) int32 neighbor table,
  ``ring_mirror``; of a 3-d table (the (L, N, d) forest coords),
  ``ring_forest``.
- ``all_gather`` anchors ``ring_summary`` (the block-summary exchange in
  ``_round_skip_flags``).
- ``all_to_all`` is landmark-only: classified ``coalesce`` vs ``ghost``
  by the capacity axis (requires an audit plan with
  ``cap_coal != cap_ghost``).
- ``ppermute`` of a (cap_rank, dim) metric-dtype block anchors
  ``ghost_ring`` (the landmark ring ghost phase; the audit plan keeps
  ``cap_rank`` distinct from ``n_loc`` so the ring-points rule cannot
  shadow it) — the visiting ids and packed Lemma-1 ghost bits inherit.
- Anything else (id scalars/vectors, counts, the 7 non-coords forest
  tables) inherits the previous event's channel: the traced equation
  order follows the python call order of the engine bodies, and every
  payload group is permuted immediately after its anchor (verified
  against all four systolic body schedules).

An event with no anchor and no predecessor is RA201 (uncounted channel);
any derived-vs-formula key or value mismatch is RA202.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax

from .diagnostics import Diagnostic
from .jaxpr_walk import EqnWalk, aval_nbytes

__all__ = ["CollectiveEvent", "collect_collectives", "classify_events",
           "audit_systolic", "audit_landmark", "audit_all",
           "SYSTOLIC_CONFIGS", "LANDMARK_CONFIGS"]

_COLLECTIVES = {"ppermute", "all_gather", "all_to_all"}


@dataclass
class CollectiveEvent:
    prim: str
    shape: tuple
    dtype: np.dtype
    mult: float
    channel: str | None = field(default=None)

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * np.dtype(self.dtype).itemsize


def collect_collectives(jaxpr) -> tuple[list[CollectiveEvent], int]:
    """In-order collective events with static loop multipliers.

    Returns (events, unknown_loops); ``unknown_loops`` > 0 means a
    ``while`` body was walked at mult 1 and counts may be lower bounds
    (the engine programs contain none — every loop is a static fori_loop
    that lowers to ``scan`` with ``params['length']``)."""
    walk = EqnWalk(jaxpr)
    events = []
    for eqn, mult in walk:
        if eqn.primitive.name not in _COLLECTIVES:
            continue
        av = eqn.invars[0].aval
        events.append(CollectiveEvent(
            prim=eqn.primitive.name, shape=tuple(av.shape),
            dtype=np.dtype(av.dtype), mult=float(mult)))
    return events, walk.unknown_loops


def classify_events(events, *, n_loc, dim, k_cap, met_dtype,
                    coords_shape=None, cap_coal=None, cap_ghost=None,
                    cap_rank=None, subject="traffic") -> list[Diagnostic]:
    """Assign each event a channel in place; RA201 for unattributable."""
    diags = []
    met_dtype = np.dtype(met_dtype)
    prev = None
    for ev in events:
        ch = None
        if ev.prim == "all_gather":
            ch = "ring_summary"
        elif ev.prim == "all_to_all":
            if cap_coal is not None and len(ev.shape) >= 2:
                if ev.shape[1] == cap_coal:
                    ch = "coalesce"
                elif ev.shape[1] == cap_ghost:
                    ch = "ghost"
        elif ev.prim == "ppermute":
            if cap_rank is not None and ev.shape == (cap_rank, dim) \
                    and ev.dtype == met_dtype:
                ch = "ghost_ring"
            elif ev.shape == (n_loc, dim) and ev.dtype == met_dtype:
                ch = "ring_points"
            elif ev.shape == (n_loc, k_cap) and ev.dtype == np.int32:
                ch = "ring_mirror"
            elif coords_shape is not None and ev.shape == coords_shape:
                ch = "ring_forest"
        if ch is None:
            ch = prev
        if ch is None:
            diags.append(Diagnostic(
                "RA201", subject,
                f"collective '{ev.prim}' of {ev.dtype.name}{ev.shape} "
                f"(x{ev.mult:g}) not attributable to any accounted comm "
                f"channel — its bytes are invisible to RunStats"))
            continue
        ev.channel = ch
        prev = ch
    return diags


def _derived_bytes(events, nranks: int) -> dict:
    out: dict = {}
    for ev in events:
        if ev.channel is None:
            continue
        out[ev.channel] = out.get(ev.channel, 0.0) \
            + nranks * ev.mult * ev.nbytes
    return {k: float(v) for k, v in out.items()}


def _cross_check(derived: dict, formula: dict, subject: str
                 ) -> list[Diagnostic]:
    diags = []
    # zero-byte formula channels (e.g. rounds == 0) need no program events
    formula = {k: v for k, v in formula.items() if v != 0.0}
    for ch in sorted(set(derived) | set(formula)):
        d, f = derived.get(ch), formula.get(ch)
        if d is None:
            diags.append(Diagnostic(
                "RA202", subject,
                f"channel '{ch}': RunStats formula reports {f:.0f} bytes "
                f"but no program collective maps to it"))
        elif f is None:
            diags.append(Diagnostic(
                "RA202", subject,
                f"channel '{ch}': program moves {d:.0f} bytes but "
                f"RunStats has no such channel — uncounted traffic"))
        elif d != f:
            diags.append(Diagnostic(
                "RA202", subject,
                f"channel '{ch}': derived {d:.0f} bytes != RunStats "
                f"formula {f:.0f} (ratio {d / f:.4g})"))
    return diags


def _sds_like(arr):
    a = np.asarray(arr)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _audit_points(n, dim, nranks, seed=0):
    """Clustered-but-mixed layout: some block pairs prune, some don't, so
    the tree flavor gets a genuinely mixed forest/points ring schedule."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0.0, 1.0, (nranks, dim))
    # half the blocks tight (prunable vs far blocks), half diffuse
    spread = np.where(np.arange(nranks) % 2 == 0, 0.02, 0.6)
    pts = np.repeat(centers, n // nranks, axis=0) + \
        rng.normal(0.0, 1.0, (n, dim)) * np.repeat(spread, n // nranks)[:, None]
    return pts.astype(np.float32)


def audit_systolic(*, nranks=8, n=1024, dim=8, k_cap=64, eps=0.25,
                   prune=True, traversal="tiles"):
    """-> (diags, derived, formula, jaxpr, subject) for one ring config."""
    import jax.numpy as jnp
    from repro.core.distributed import device as dev
    from repro.nng import PointPartitionEngine

    subject = f"systolic[traversal={traversal},prune={prune}]"
    mesh = dev.make_nng_mesh(nranks)
    pts = _audit_points(n, dim, nranks)
    engine = PointPartitionEngine(
        pts, eps, mesh, "euclidean", k_cap=k_cap, prune=prune,
        traversal=traversal, forest_backend="host")
    formula = engine._ring_comm_bytes(k_cap)

    fn = dev._systolic_fn(mesh, float(eps), engine.metric, k_cap, "ring",
                          prune, dev._pallas_mode(), traversal,
                          engine.ring_schedule)
    args = [jax.ShapeDtypeStruct((n, dim), engine.metric.dtype),
            jax.ShapeDtypeStruct((n,), np.int32)]
    coords_shape = None
    if traversal == "tree":
        ftabs = dev.DeviceForest.from_tables(engine.forest)
        args += [_sds_like(t) for t in ftabs]
        c = np.asarray(engine.forest["coords"])
        coords_shape = tuple(c.shape[1:])  # per-rank (L, N, d)
    jaxpr = jax.make_jaxpr(fn)(*args)

    events, unknown = collect_collectives(jaxpr)
    diags = []
    if unknown:
        diags.append(Diagnostic(
            "RA201", subject,
            f"{unknown} while-loop(s) with unknown trip count — derived "
            f"bytes are lower bounds"))
    diags += classify_events(
        events, n_loc=n // nranks, dim=dim, k_cap=k_cap,
        met_dtype=engine.metric.dtype, coords_shape=coords_shape,
        subject=subject)
    derived = _derived_bytes(events, nranks)
    diags += _cross_check(derived, formula, subject)
    return diags, derived, formula, jaxpr, subject


def audit_landmark(*, nranks=8, n=1024, dim=8, eps=0.25,
                   traversal="tiles", ghost_mode="coll"):
    """-> (diags, derived, formula, jaxpr, subject) for one landmark
    config. The audit plan fixes cap_coal != cap_ghost so the two
    all_to_all groups are distinguishable by their capacity axis, and
    cap_rank != n_loc so the ring ghost block cannot shadow the
    ring-points rule."""
    from repro.core.distributed import device as dev
    from repro.nng import SpatialPartitionEngine

    subject = f"landmark[traversal={traversal},ghost={ghost_mode}]"
    mesh = dev.make_nng_mesh(nranks)
    pts = _audit_points(n, dim, nranks)
    plan = dev.LandmarkPlan(m_centers=16, cap_coal=48, cap_ghost=64,
                            g_per_pt=4, k_cap=32, cap_rank=96)
    engine = SpatialPartitionEngine(
        pts, eps, mesh, "euclidean", m_centers=plan.m_centers, plan=plan,
        traversal=traversal, forest_backend="host", ghost_mode=ghost_mode)
    formula = engine._landmark_comm_bytes(plan)

    fn = dev._landmark_fn(mesh, float(eps), engine.metric, plan, "ring",
                          dev._pallas_mode(), traversal, ghost_mode)
    args = [jax.ShapeDtypeStruct((n, dim), engine.metric.dtype),
            jax.ShapeDtypeStruct((n,), np.int32),
            _sds_like(engine.centers.astype(engine.metric.dtype)),
            jax.ShapeDtypeStruct((engine.m_centers,), np.int32)]
    if traversal == "tree":
        args.append(jax.ShapeDtypeStruct((n,), np.int32))  # cell
        ftabs = dev.DeviceForest.from_tables(engine.forest)
        args += [_sds_like(t) for t in ftabs]
    jaxpr = jax.make_jaxpr(fn)(*args)

    events, unknown = collect_collectives(jaxpr)
    diags = []
    if unknown:
        diags.append(Diagnostic(
            "RA201", subject,
            f"{unknown} while-loop(s) with unknown trip count — derived "
            f"bytes are lower bounds"))
    diags += classify_events(
        events, n_loc=n // nranks, dim=dim, k_cap=plan.k_cap,
        met_dtype=engine.metric.dtype, cap_coal=plan.cap_coal,
        cap_ghost=plan.cap_ghost, cap_rank=plan.cap_rank, subject=subject)
    derived = _derived_bytes(events, nranks)
    diags += _cross_check(derived, formula, subject)
    return diags, derived, formula, jaxpr, subject


SYSTOLIC_CONFIGS = (
    dict(traversal="tiles", prune=True),
    dict(traversal="tiles", prune=False),
    dict(traversal="tree", prune=True),
)
LANDMARK_CONFIGS = (
    dict(traversal="tiles", ghost_mode="coll"),
    dict(traversal="tree", ghost_mode="coll"),
    dict(traversal="tiles", ghost_mode="ring"),
    dict(traversal="tree", ghost_mode="ring"),
)


def audit_all(nranks: int = 8):
    """Run the full audit matrix. Returns (diags, table, jaxprs) where
    ``table`` maps subject -> {"derived": ..., "formula": ...} and
    ``jaxprs`` maps subject -> traced ClosedJaxpr (for the engine lints).
    """
    if len(jax.devices()) < nranks:
        raise RuntimeError(
            f"traffic audit needs {nranks} devices, have "
            f"{len(jax.devices())} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={nranks} "
            f"(the CLI sets this automatically)")
    diags, table, jaxprs = [], {}, {}
    for cfg in SYSTOLIC_CONFIGS:
        d, derived, formula, jaxpr, subject = audit_systolic(
            nranks=nranks, **cfg)
        diags += d
        table[subject] = {"derived": derived, "formula": formula}
        jaxprs[subject] = jaxpr
    for cfg in LANDMARK_CONFIGS:
        d, derived, formula, jaxpr, subject = audit_landmark(
            nranks=nranks, **cfg)
        diags += d
        table[subject] = {"derived": derived, "formula": formula}
        jaxprs[subject] = jaxpr
    return diags, table, jaxprs
