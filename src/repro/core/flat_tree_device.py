"""On-device cover-forest construction (Alg. 1 + 2, jit-compiled).

Port of ``covertree.build_covertree`` + ``flat_tree.flatten_forest`` to a
single jit program that emits the levelized ``FlatCoverTree`` SoA tables as
jnp arrays directly — the forest never exists as host objects, so repeated
/ streaming builds skip both the python group loop and the host->device
table transfer. The host path remains the float64 oracle
(``build_block_forests`` / ``build_cell_forests`` with ``backend="host"``).

Formulation (identical decision sequence to the host build, so the two
paths produce structurally identical tables at matching precision):

- Point state is the host's (D, L) pair plus ``pslot`` — the flat SLOT of
  the node that currently owns the point (hub slots during splitting, dump
  slots for members pending leaf emission, -1 once retired into a leaf).
- Alg. 1 runs as a ``while_loop``: one farthest-point pick per unfinished
  hub per iteration — segmented max of D over ``pslot`` (masked scatter-max
  instead of ``np.maximum.at``), first-point tie-break via scatter-min of
  the point index, then one batched rowwise TRUE-distance update through
  the ``Metric`` registry (diff-form where the metric provides it, so
  radii carry no BLAS3 cancellation at large coordinate scale).
- Alg. 2 groups points by (pslot, L) with a stable double argsort — the
  sort order IS the BFS child order of the host flatten (parent-slot
  major, center ascending) — and reduces per-group center / radius / size
  with segment scatters. Child slot ranges are the exclusive cumsum of
  per-parent child counts (leaf slots collapse to empty ranges at the
  running position, like the host BFS emit).
- Dump groups reuse the group machinery: members get (D, L) = (0, self),
  so each reappears one level down as a singleton leaf child in ascending
  point order — exactly the host's Alg. 2 lines 10-12 emission.
- DFS leaf ranges come from a bottom-up per-level leaf-count scan plus a
  top-down prefix-offset pass (leaf_lo[g] = leaf_lo[parent] + leaves of
  preceding siblings); leaf_ids scatter level by level.

Levels are bounded by a static ``max_levels``; an overflow flag triggers a
host-side regrow (double and re-jit, capped at 512). The default starts
SHALLOW (8 levels): the per-level cost is paid for every static level
whether used or not, so a tight start with doubling beats provisioning
for pathological aspect ratios up front. Trees stack over a leading rank axis via ``vmap``, producing
the same dict schema as ``flat_tree.stack_device_forests``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.compat import shard_map

from .metrics import Metric, get_metric

PAD = -1
SENTINEL_ID = 2**31 - 1


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _as_device_metric(metric) -> Metric:
    if isinstance(metric, Metric):
        return metric
    if isinstance(metric, str):
        return get_metric(metric)
    return get_metric(metric.name)        # HostMetric carries its name


# ---------------------------------------------------------------------------
# single-rank builder (vmapped over ranks by the jit wrapper)
# ---------------------------------------------------------------------------

def _build_rank_tables(pts, cells, gids, tslot, *, leaf_size: int,
                       max_levels: int, met: Metric):
    """One rank's padded member set -> its levelized device tables.

    pts (P, d) metric-dtype coordinates (local rows), cells (P,) int32
    per-point cell id (PAD rows = padding), gids (P,) int32 global point
    ids, tslot (P,) int32 level-0 tree slot per point (-1 = padding).
    P % 32 == 0. Trees must be slotted in ascending-cell order with each
    tree's root at its lowest local row (the host forest contract).
    """
    P = pts.shape[0]
    N = P                                    # level width bound: one slot
    pidx = jnp.arange(P, dtype=jnp.int32)    # per surviving point, max

    rowwise = met.rowwise_true

    # ---- level 0: one root slot per tree -----------------------------------
    valid = tslot >= 0
    ts = jnp.where(valid, tslot, N)
    root = jnp.full(N + 1, P, jnp.int32).at[ts].min(
        jnp.where(valid, pidx, P))[:N]
    tsize = jnp.zeros(N + 1, jnp.int32).at[ts].add(
        valid.astype(jnp.int32))[:N]
    rp = jnp.where(valid, root[jnp.clip(tslot, 0, N - 1)], 0)
    D = jnp.where(valid, rowwise(pts, pts[jnp.clip(rp, 0, P - 1)])
                  .astype(jnp.float32), 0.0)
    L = jnp.where(valid, rp, 0).astype(jnp.int32)
    hubr0 = jnp.zeros(N + 1, jnp.float32).at[ts].max(
        jnp.where(valid, D, 0.0))[:N]
    kind0 = jnp.where(tsize == 0, -1, jnp.where(tsize == 1, 0, 1))
    pslot = jnp.where(valid & (kind0[jnp.clip(tslot, 0, N - 1)] == 1),
                      tslot, -1).astype(jnp.int32)

    shape = (max_levels, N)
    ptidx_t = jnp.full(shape, -1, jnp.int32).at[0].set(
        jnp.where(kind0 >= 0, root, -1))
    rad_t = jnp.zeros(shape, jnp.float32).at[0].set(hubr0)
    cell_t = jnp.full(shape, PAD, jnp.int32).at[0].set(
        jnp.where(kind0 >= 0, cells[jnp.clip(root, 0, P - 1)], PAD))
    leaf_t = jnp.zeros(shape, jnp.int32).at[0].set(
        (kind0 == 0).astype(jnp.int32))
    par_t = jnp.zeros(shape, jnp.int32)
    clo_t = jnp.zeros(shape, jnp.int32)
    chi_t = jnp.zeros(shape, jnp.int32)

    # ---- level loop: produce level lvl+1 from level lvl --------------------
    def step(lvl, carry):
        (ptidx_t, rad_t, cell_t, leaf_t, par_t, clo_t, chi_t,
         D, L, pslot, kind, hubr) = carry
        psc = jnp.clip(pslot, 0, N - 1)
        active_pt = pslot >= 0

        # Alg. 1: one farthest-point pick per unfinished hub per iteration
        is_hub = kind == 1
        done0 = jnp.where(is_hub, hubr <= 0.0, True)

        def a1_cond(c):
            it, done, _, _ = c
            return (it < P) & jnp.any(~done)

        def a1_body(c):
            it, done, D, L = c
            pv = active_pt & is_hub[psc] & ~done[psc]
            hmax = jnp.full(N + 1, -1.0, jnp.float32).at[
                jnp.where(pv, pslot, N)].max(jnp.where(pv, D, -1.0))[:N]
            done = done | ((~done) & (hmax <= hubr * 0.5))
            act = is_hub & ~done
            pa = active_pt & act[psc]
            cand = pa & (D >= hmax[psc])
            cen = jnp.full(N + 1, P, jnp.int32).at[
                jnp.where(cand, pslot, N)].min(
                jnp.where(cand, pidx, P))[:N]
            cpt = jnp.where(pa, cen[psc], 0)
            dnew = rowwise(pts, pts[jnp.clip(cpt, 0, P - 1)]).astype(
                jnp.float32)
            upd = pa & (dnew < D)
            D = jnp.where(upd, dnew, D)
            L = jnp.where(upd, cpt, L)
            iscen = pa & (pidx == cpt)
            D = jnp.where(iscen, 0.0, D)
            L = jnp.where(iscen, pidx, L)
            return it + 1, done, D, L

        _, _, D, L = jax.lax.while_loop(
            a1_cond, a1_body, (jnp.int32(0), done0, D, L))

        # Alg. 2: group by (pslot, L) — stable double argsort = BFS order
        Lm = jnp.where(active_pt, L, P)
        Pm = jnp.where(active_pt, pslot, N)
        o1 = jnp.argsort(Lm, stable=True)
        o2 = jnp.argsort(Pm[o1], stable=True)
        order = o1[o2]
        s_ps = Pm[order]
        s_L = Lm[order]
        s_valid = active_pt[order]
        prev_ps = jnp.concatenate([jnp.full((1,), -9, jnp.int32), s_ps[:-1]])
        prev_L = jnp.concatenate([jnp.full((1,), -9, jnp.int32), s_L[:-1]])
        newg = s_valid & ((s_ps != prev_ps) | (s_L != prev_L))
        gidx = jnp.cumsum(newg.astype(jnp.int32)) - 1
        gsl = jnp.where(s_valid, gidx, N)
        sv = s_valid.astype(jnp.int32)
        gcen = jnp.full(N + 1, -1, jnp.int32).at[gsl].max(
            jnp.where(s_valid, s_L, -1))[:N]
        gpar = jnp.zeros(N + 1, jnp.int32).at[gsl].max(
            jnp.where(s_valid, s_ps, 0))[:N]
        grad = jnp.zeros(N + 1, jnp.float32).at[gsl].max(
            jnp.where(s_valid, D[order], 0.0))[:N]
        gsize = jnp.zeros(N + 1, jnp.int32).at[gsl].add(sv)[:N]
        gvalid = gsize > 0
        pgroup = jnp.zeros(P, jnp.int32).at[order].set(gsl)

        # child slot ranges on the current level (exclusive cumsum of
        # per-parent child counts — empty ranges at the running position)
        ccount = jnp.zeros(N + 1, jnp.int32).at[
            jnp.where(gvalid, gpar, N)].add(gvalid.astype(jnp.int32))[:N]
        clo_cur = jnp.cumsum(ccount) - ccount
        cur_valid = kind >= 0
        clo_t = clo_t.at[lvl].set(jnp.where(cur_valid, clo_cur, 0))
        chi_t = chi_t.at[lvl].set(
            jnp.where(cur_valid, clo_cur + ccount, 0))

        # classify: singleton -> leaf; big & spread -> hub; else dump
        gleaf = gvalid & (gsize == 1)
        ghub = gvalid & (gsize > leaf_size) & (grad > 0.0)
        kind_n = jnp.where(gleaf, 0, jnp.where(ghub, 1,
                           jnp.where(gvalid, 2, -1)))
        gparc = jnp.clip(gpar, 0, N - 1)
        ptidx_t = ptidx_t.at[lvl + 1].set(jnp.where(gvalid, gcen, -1))
        rad_t = rad_t.at[lvl + 1].set(grad)
        cell_t = cell_t.at[lvl + 1].set(
            jnp.where(gvalid, cell_t[lvl][gparc], PAD))
        leaf_t = leaf_t.at[lvl + 1].set(gleaf.astype(jnp.int32))
        par_t = par_t.at[lvl + 1].set(jnp.where(gvalid, gpar, 0))

        # point state: leaves retire; dump members become their own centers
        pgc = jnp.clip(pgroup, 0, N - 1)
        kp = jnp.where(active_pt, kind_n[pgc], -1)
        pslot = jnp.where(kp <= 0, -1, pgroup).astype(jnp.int32)
        dumpm = kp == 2
        L = jnp.where(dumpm, pidx, L)
        D = jnp.where(dumpm, 0.0, D)
        return (ptidx_t, rad_t, cell_t, leaf_t, par_t, clo_t, chi_t,
                D, L, pslot, kind_n, grad)

    carry = (ptidx_t, rad_t, cell_t, leaf_t, par_t, clo_t, chi_t,
             D, L, pslot, kind0, hubr0)
    (ptidx_t, rad_t, cell_t, leaf_t, par_t, clo_t, chi_t,
     _, _, pslot, _, _) = jax.lax.fori_loop(0, max_levels - 1, step, carry)
    overflow = jnp.any(pslot >= 0)

    # ---- DFS leaf ranges: bottom-up counts, top-down prefix offsets --------
    valid_n = cell_t != PAD

    def up_body(i, lc):
        lvl = max_levels - 1 - i
        nxt = jnp.clip(lvl + 1, 0, max_levels - 1)
        in_range = lvl + 1 < max_levels
        lcn = jnp.where(in_range, lc[nxt], 0)
        child = jnp.zeros(N + 1, jnp.int32).at[
            jnp.where(valid_n[nxt] & in_range, par_t[nxt], N)].add(lcn)[:N]
        own = (valid_n[lvl] & (leaf_t[lvl] != 0)).astype(jnp.int32)
        return lc.at[lvl].set(own + child)

    lc = jax.lax.fori_loop(0, max_levels, up_body,
                           jnp.zeros((max_levels, N), jnp.int32))

    ll0 = jnp.cumsum(lc[0]) - lc[0]
    ll = jnp.zeros((max_levels, N), jnp.int32).at[0].set(ll0)

    def down_body(lvl, ll):
        C = jnp.cumsum(lc[lvl]) - lc[lvl]
        par = jnp.clip(par_t[lvl], 0, N - 1)
        first = jnp.clip(clo_t[lvl - 1][par], 0, N - 1)
        return ll.at[lvl].set(ll[lvl - 1][par] + C - C[first])

    ll = jax.lax.fori_loop(1, max_levels, down_body, ll)
    leaf_lo_t = jnp.where(valid_n, ll, 0)
    leaf_hi_t = jnp.where(valid_n, ll + lc, 0)

    def lid_body(lvl, lid):
        isleaf = valid_n[lvl] & (leaf_t[lvl] != 0)
        pos = jnp.where(isleaf, leaf_lo_t[lvl], P)
        gid_lvl = gids[jnp.clip(ptidx_t[lvl], 0, P - 1)]
        return lid.at[pos].set(
            jnp.where(isleaf, gid_lvl, SENTINEL_ID), mode="drop")

    leaf_ids = jax.lax.fori_loop(
        0, max_levels, lid_body,
        jnp.full(P + 1, SENTINEL_ID, jnp.int32))[:P]

    coords = pts[jnp.clip(ptidx_t, 0, P - 1)]
    levels_used = jnp.sum(jnp.any(valid_n, axis=1).astype(jnp.int32))
    width_used = jnp.max(jnp.sum(valid_n.astype(jnp.int32), axis=1))
    return {
        "coords": coords,
        "radius": rad_t,
        "cell": cell_t,
        "leaf": leaf_t,
        "parent": par_t,
        "child_lo": clo_t,
        "child_hi": chi_t,
        "leaf_lo": leaf_lo_t,
        "leaf_hi": leaf_hi_t,
        "leaf_ids": leaf_ids,
        "overflow": overflow,
        "levels": levels_used,
        "width": width_used,
    }


@functools.partial(jax.jit, static_argnames=("leaf_size", "max_levels",
                                             "met", "mesh"))
def _forest_tables_jit(pts, cells, gids, tslot, *, leaf_size, max_levels,
                       met, mesh):
    build = jax.vmap(functools.partial(
        _build_rank_tables, leaf_size=leaf_size, max_levels=max_levels,
        met=met))
    if mesh is None:
        return build(pts, cells, gids, tslot)
    # one rank's forest per device of the ring, built where it is used
    spec = P(mesh.axis_names[0])
    return shard_map(build, mesh, in_specs=(spec,) * 4,
                     out_specs=spec)(pts, cells, gids, tslot)


def _build_stacked(ptsb, cellsb, gidsb, tslotb, met, leaf_size,
                   max_levels=8, include_child_ranges=False, mesh=None):
    """Run the jit builder, regrow on level overflow, trim empty levels.

    Returns the ``stack_device_forests`` dict schema — all jnp arrays with
    a leading rank axis, ready for the engines' shard_map
    (``DeviceForest.from_tables``). ``include_child_ranges`` additionally
    keeps ``child_lo``/``child_hi`` (the device traversal is parent-
    pointer-based and doesn't consume them; the structural parity tests
    do). With a 1-D ``mesh`` of ``nranks`` devices, rank r's forest is
    built on device r; without, all of them on the default device.
    """
    if mesh is not None:
        assert ptsb.shape[0] == mesh.size, (ptsb.shape, mesh.size)
    while True:
        out = _forest_tables_jit(ptsb, cellsb, gidsb, tslotb,
                                 leaf_size=int(leaf_size),
                                 max_levels=int(max_levels), met=met,
                                 mesh=mesh)
        if not bool(np.any(np.asarray(out["overflow"]))):
            break
        if max_levels >= 512:
            raise RuntimeError("device forest build exceeded 512 levels")
        max_levels = min(max_levels * 2, 512)
    L = max(int(np.max(np.asarray(out["levels"]))), 1)
    # valid slots are contiguous from 0 on every level, so trimming the
    # level width to the forest-wide max (padded to 32) is range-safe
    W = _round_up(max(int(np.max(np.asarray(out["width"]))), 1), 32)
    keys = ["coords", "radius", "cell", "leaf", "parent",
            "leaf_lo", "leaf_hi"]
    if include_child_ranges:
        keys += ["child_lo", "child_hi"]
    tabs = {k: out[k][:, :L, :W] for k in keys}
    tabs["leaf_ids"] = out["leaf_ids"]
    return tabs


# ---------------------------------------------------------------------------
# public builders (the backend="device" paths of flat_tree.build_*_forests)
# ---------------------------------------------------------------------------

def estimate_max_levels(points, met, sample: int = 256) -> int:
    """Host-side warm start for the regrow loop.

    The hub split halves the radius every level (Alg. 1 terminates a hub
    at ``hmax <= hubr * 0.5``), so the forest depth is ~log2(span /
    leaf spacing). Both scales come from a small sample: ``r0`` = max
    true distance from the sample's first point, ``delta`` = median
    nearest-neighbor distance within the sample. Build cost is linear in
    ``max_levels`` (the level loop runs to the cap even when lower
    levels are empty), so slack is expensive: +1 level of headroom,
    clamped to [4, 64]. Underestimates are safe but slow — the regrow
    loop doubles and rebuilds on EVERY call, so a chronic undershoot
    pays ~3x — which is why the slack is not 0.
    """
    pts = np.asarray(points)
    if len(pts) < 2:
        return 4
    idx = np.linspace(0, len(pts) - 1, min(sample, len(pts))).astype(np.int64)
    hm = met.host
    dm = np.asarray(hm.true(hm.cdist(pts[idx], pts[idx])), np.float64)
    r0 = float(dm[0].max())
    np.fill_diagonal(dm, np.inf)
    delta = float(np.median(dm.min(axis=1)))
    if not np.isfinite(delta) or delta <= 0.0 or r0 <= delta:
        return 8
    return int(np.clip(int(np.ceil(np.log2(r0 / delta))) + 1, 4, 64))


def build_block_forests_device(points, nranks: int, metric="euclidean",
                               leaf_size: int = 10,
                               max_levels: int | None = None,
                               *, include_child_ranges: bool = False,
                               mesh=None):
    """Systolic engine forests on device: one tree per contiguous block.

    Same partitioning contract as ``flat_tree.build_block_forests``;
    returns the stacked device-tables dict (jnp arrays, leading rank axis)
    that ``stack_device_forests`` would produce from the host path.
    ``mesh`` (the engine's ring, ``nranks`` devices) builds each rank's
    tree on its own device.
    """
    met = _as_device_metric(metric)
    pts = np.asarray(points)
    if max_levels is None:
        max_levels = estimate_max_levels(pts, met)
    n = len(pts)
    assert n % nranks == 0, (n, nranks)
    n_loc = n // nranks
    P = _round_up(n_loc, 32)
    dt = np.dtype(met.dtype)
    ptsb = np.zeros((nranks, P) + pts.shape[1:], dt)
    cellsb = np.full((nranks, P), PAD, np.int32)
    gidsb = np.zeros((nranks, P), np.int32)
    tslotb = np.full((nranks, P), -1, np.int32)
    for r in range(nranks):
        ptsb[r, :n_loc] = pts[r * n_loc:(r + 1) * n_loc]
        cellsb[r, :n_loc] = 0
        gidsb[r, :n_loc] = np.arange(n_loc, dtype=np.int32) + r * n_loc
        tslotb[r, :n_loc] = 0
    return _build_stacked(ptsb, cellsb, gidsb, tslotb, met, leaf_size,
                          max_levels, include_child_ranges, mesh)


@jax.jit
def _insert_roots_jit(tabs, ridx, newp, newg, newc, newrank):
    """Scatter each batch point as a singleton root of its owning rank.

    Returns (tables, overflow (nranks,) bool). Out-of-capacity scatters
    drop (jnp ``mode="drop"``), so on overflow the caller regrows padding
    and simply re-runs on the ORIGINAL tables."""
    def one(tab, r):
        coords, rad, cell, leaf, par, llo, lhi, lid = (
            tab["coords"], tab["radius"], tab["cell"], tab["leaf"],
            tab["parent"], tab["leaf_lo"], tab["leaf_hi"], tab["leaf_ids"])
        N = cell.shape[1]
        nl = lid.shape[0]
        used0 = jnp.sum((cell[0] != PAD).astype(jnp.int32))
        usedl = jnp.max(lhi)
        mask = newrank == r
        k = jnp.cumsum(mask.astype(jnp.int32)) - 1
        cnt = jnp.sum(mask.astype(jnp.int32))
        overflow = (used0 + cnt > N) | (usedl + cnt > nl)
        sl = jnp.where(mask, used0 + k, N)          # drop when not ours
        lp = jnp.where(mask, usedl + k, nl)
        b = mask.shape[0]
        out = {
            "coords": coords.at[0, sl].set(newp, mode="drop"),
            "radius": rad.at[0, sl].set(jnp.zeros(b, rad.dtype),
                                        mode="drop"),
            "cell": cell.at[0, sl].set(newc, mode="drop"),
            "leaf": leaf.at[0, sl].set(jnp.ones(b, leaf.dtype),
                                       mode="drop"),
            "parent": par.at[0, sl].set(jnp.zeros(b, par.dtype),
                                        mode="drop"),
            "leaf_lo": llo.at[0, sl].set(lp.astype(llo.dtype), mode="drop"),
            "leaf_hi": lhi.at[0, sl].set((lp + 1).astype(lhi.dtype),
                                         mode="drop"),
            "leaf_ids": lid.at[lp].set(newg, mode="drop"),
        }
        return out, overflow

    return jax.vmap(one, in_axes=(0, 0))(tabs, ridx)


def _grow_stacked(tabs):
    """Double both the level width and the leaf capacity (host-side pad,
    mirroring the builder's regrow-on-overflow doubling)."""
    out = {}
    for k, a in tabs.items():
        a = np.asarray(a)
        if k == "leaf_ids":
            pad = np.full((a.shape[0], a.shape[1]), SENTINEL_ID, a.dtype)
            out[k] = np.concatenate([a, pad], axis=1)
        else:
            fill = PAD if k == "cell" else 0
            pad = np.full(a.shape[:2] + (a.shape[2],) + a.shape[3:], fill,
                          a.dtype)
            out[k] = np.concatenate([a, pad], axis=2)
    return {k: jnp.asarray(v) for k, v in out.items()}


def insert_stacked_device(tabs, new_points, new_gids, new_ranks,
                          new_cells=None):
    """Batched device-side incremental insert into stacked forest tables.

    Each new point is appended as a singleton ROOT of its owning rank's
    forest — exact by construction (roots are always on the traversal
    frontier) at the cost of one extra root per insert until the next full
    rebuild; the host descent path (``FlatCoverTree.insert_host``) is the
    structure-preserving variant. Overflowing the padded width regrows by
    doubling and retries, like the builder.
    """
    nranks = int(np.asarray(tabs["cell"]).shape[0])
    dt = tabs["coords"].dtype
    newp = jnp.asarray(new_points, dt)
    newg = jnp.asarray(new_gids, jnp.int32)
    newr = jnp.asarray(new_ranks, jnp.int32)
    newc = (jnp.zeros(len(newg), jnp.int32) if new_cells is None
            else jnp.asarray(new_cells, jnp.int32))
    ridx = jnp.arange(nranks, dtype=jnp.int32)
    while True:
        out, overflow = _insert_roots_jit(tabs, ridx, newp, newg, newc,
                                          newr)
        if not bool(np.any(np.asarray(overflow))):
            return out
        tabs = _grow_stacked(tabs)


def tombstone_stacked_device(tabs, dead_ids):
    """Mask deleted points in stacked tables: every device emission flows
    through leaf ranges (``leaf_range_pack`` drops SENTINEL entries), so
    rewriting ``leaf_ids`` alone fully hides them; dead singleton-root
    coordinates stay as harmless routing pivots."""
    dead = jnp.asarray(np.asarray(dead_ids, np.int64), jnp.int32)
    lid = tabs["leaf_ids"]
    out = dict(tabs)
    out["leaf_ids"] = jnp.where(jnp.isin(lid, dead),
                                jnp.int32(SENTINEL_ID), lid)
    return out


def build_cell_forests_device(points, cell, f, nranks: int,
                              metric="euclidean", leaf_size: int = 10,
                              max_levels: int | None = None,
                              *, include_child_ranges: bool = False,
                              mesh=None):
    """Landmark engine forests on device: per rank, one tree per owned
    cell (ascending cell id), nodes stamped with their cell — the same
    forest ``flat_tree.build_cell_forests`` builds on the host. Ranks
    owning no points get the 1-node unmatchable-cell placeholder.
    ``mesh`` as in ``build_block_forests_device``.
    """
    met = _as_device_metric(metric)
    pts = np.asarray(points)
    if max_levels is None:
        max_levels = estimate_max_levels(pts, met)
    cell = np.asarray(cell)
    f = np.asarray(f)
    members_r, cells_r, tslot_r = [], [], []
    for r in range(nranks):
        mem, cel, tsl = [], [], []
        t = 0
        for ci in np.flatnonzero(f == r):
            m = np.flatnonzero(cell == ci)
            if len(m) == 0:
                continue
            mem.append(m)
            cel.append(np.full(len(m), int(ci), np.int32))
            tsl.append(np.full(len(m), t, np.int32))
            t += 1
        if not mem:      # placeholder: queries never match cell -2
            mem = [np.zeros(1, np.int64)]
            cel = [np.full(1, -2, np.int32)]
            tsl = [np.zeros(1, np.int32)]
        members_r.append(np.concatenate(mem))
        cells_r.append(np.concatenate(cel))
        tslot_r.append(np.concatenate(tsl))
    P = _round_up(max(len(m) for m in members_r), 32)
    dt = np.dtype(met.dtype)
    ptsb = np.zeros((nranks, P) + pts.shape[1:], dt)
    cellsb = np.full((nranks, P), PAD, np.int32)
    gidsb = np.zeros((nranks, P), np.int32)
    tslotb = np.full((nranks, P), -1, np.int32)
    for r in range(nranks):
        m = members_r[r]
        ptsb[r, :len(m)] = pts[m]
        cellsb[r, :len(m)] = cells_r[r]
        gidsb[r, :len(m)] = m.astype(np.int32)
        tslotb[r, :len(m)] = tslot_r[r]
    return _build_stacked(ptsb, cellsb, gidsb, tslotb, met, leaf_size,
                          max_levels, include_child_ranges, mesh)
