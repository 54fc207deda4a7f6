"""On-device forest construction + fused epilogue kernel tests:

- device-built forest vs the host ``flatten_forest`` oracle: structural
  parity (validity masks, cells, leaf flags, parent positions, child and
  DFS leaf ranges, leaf id order, coordinates) on both metrics and both
  partition shapes, radii to fp32 tolerance,
- the collinear scale~1e8 regression built AND traversed on the device
  path (diff-form rowwise radii keep the boundary neighbors),
- interpret-mode vs jnp-oracle parity for both epilogue kernels, plus the
  popcount/bit-order identities,
- bit-identity of the fused bitmask→ids epilogue against the two-pass
  ``lax.top_k`` extraction it replaced (reimplemented here as the spec),
- an 8-simulated-device ``build_nng`` run with ``forest_backend="device"``
  equal to float64 brute force, with ``build_s`` reported.
"""
import os

import numpy as np
import pytest

from repro.core.flat_tree import (PAD, SENTINEL_ID, build_block_forests,
                                  build_cell_forests, stack_device_forests)
from repro.core.flat_tree_device import (build_block_forests_device,
                                         build_cell_forests_device)
from tests.helpers import run_subprocess


# ---------------------------------------------------------------------------
# device builder vs host flatten: structural parity
# ---------------------------------------------------------------------------

def _assert_forest_parity(host_forests, dev, tag):
    """Stacked host tables vs device dict: same levels, same valid slots,
    identical structure on every valid slot, radii to fp32 tolerance."""
    host = stack_device_forests(host_forests)
    R, Lh, Nh = host["radius"].shape
    Ld, Nd = dev["radius"].shape[1:3]
    assert Ld == Lh, (tag, "levels", Lh, Ld)
    N = min(Nh, Nd)     # both pad to %32; trailing width must be all-pad
    vh = host["cell"][:, :, :N] != PAD
    vd = np.asarray(dev["cell"])[:, :, :N] != PAD
    assert np.array_equal(vh, vd), (tag, "validity mask")
    if Nd > N:
        assert (np.asarray(dev["cell"])[:, :, N:] == PAD).all(), tag
    if Nh > N:
        assert (host["cell"][:, :, N:] == PAD).all(), tag
    for key in ("cell", "leaf", "parent", "leaf_lo", "leaf_hi"):
        assert np.array_equal(host[key][:, :, :N][vh],
                              np.asarray(dev[key])[:, :, :N][vh]), (tag, key)
    assert np.array_equal(host["coords"][:, :, :N][vh],
                          np.asarray(dev["coords"])[:, :, :N][vh]), tag
    assert np.array_equal(host["leaf_ids"],
                          np.asarray(dev["leaf_ids"])), (tag, "leaf_ids")
    rh = host["radius"][:, :, :N][vh]
    rd = np.asarray(dev["radius"])[:, :, :N][vh]
    assert np.abs(rh - rd).max() <= 1e-5 * max(1.0, float(np.abs(rh).max())
                                               ), (tag, "radius")
    # child slot ranges against the per-rank host FlatCoverTree tables
    for r, ft in enumerate(host_forests):
        L0, N0 = ft.node_gid.shape
        m = ft.node_cell != PAD
        for key, hostt in (("child_lo", ft.child_lo),
                           ("child_hi", ft.child_hi)):
            got = np.asarray(dev[key])[r, :L0, :N0]
            assert np.array_equal(hostt[m], got[m]), (tag, r, key)


@pytest.mark.parametrize("metric", ["euclidean", "hamming"])
def test_device_forest_structural_parity(metric):
    rng = np.random.default_rng(17)
    if metric == "hamming":
        pts = rng.integers(0, 2**32, size=(512, 4), dtype=np.uint32)
    else:
        pts = rng.normal(size=(512, 8)).astype(np.float32)

    host = build_block_forests(pts, 4, metric, leaf_size=7)
    dev = build_block_forests_device(pts, 4, metric, leaf_size=7,
                                     include_child_ranges=True)
    _assert_forest_parity(host, dev, f"block/{metric}")

    # cell forests with one rank owning no points (placeholder tree)
    cell = rng.integers(0, 13, size=len(pts)).astype(np.int64)
    f = np.arange(13) % 5
    f = np.where(f == 3, 0, f)          # rank 3 owns nothing
    host = build_cell_forests(pts, cell, f, 5, metric, leaf_size=5)
    dev = build_cell_forests_device(pts, cell, f, 5, metric, leaf_size=5,
                                    include_child_ranges=True)
    _assert_forest_parity(host, dev, f"cell/{metric}")


def test_backend_switch_matches_device_builder():
    """``build_*_forests(..., backend="device")`` is the device builder."""
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(128, 4)).astype(np.float32)
    via_switch = build_block_forests(pts, 2, "euclidean", backend="device")
    direct = build_block_forests_device(pts, 2, "euclidean")
    assert set(via_switch) == set(direct)
    for k in direct:
        assert np.array_equal(np.asarray(via_switch[k]),
                              np.asarray(direct[k])), k


def test_device_build_collinear_scale_regression():
    """Collinear fp32 points at coordinate scale ~1e8: the device builder's
    diff-form rowwise distances must keep radii exact enough that the
    device traversal (fp32 slack) drops no boundary neighbors."""
    import jax.numpy as jnp
    from repro.core.distributed import DeviceForest, tree_traverse

    S = float(2**17)
    M = 80
    rng = np.random.default_rng(0)
    ms = np.sort(rng.choice(400, size=200, replace=False))
    pts = (ms[:, None] * S * np.ones((1, 2))).astype(np.float32)
    eps = float(np.sqrt(2.0 * (M * S) ** 2))
    want = int((np.abs(ms[:, None] - ms[None, :]) <= M).sum() - len(ms))

    tabs = build_block_forests_device(pts, 1, "euclidean", leaf_size=4)
    fr = DeviceForest.from_tables({k: v[0] for k, v in tabs.items()})
    n = len(pts)
    nbrs, cnt, _, _ = tree_traverse(
        jnp.asarray(pts), jnp.arange(n, dtype=jnp.int32),
        jnp.zeros(n, jnp.int32), fr, eps, 256, "euclidean")
    got = int(np.asarray(cnt).sum())
    assert got == want, f"dropped {want - got} collinear boundary neighbors"
    nbrs = np.asarray(nbrs)
    ii, kk = np.nonzero(nbrs != SENTINEL_ID)
    d = np.abs(ms[ii] - ms[nbrs[ii, kk]])
    assert (d <= M).all()               # and no spurious far pairs


# ---------------------------------------------------------------------------
# epilogue kernels: interpret vs jnp parity + identities
# ---------------------------------------------------------------------------

def _random_bits(rng, m, w, density=0.15):
    mask = rng.random((m, 32 * w)) < density
    words = np.zeros((m, w), np.uint32)
    for b in range(32):
        words |= mask[:, b::32].astype(np.uint32) << np.uint32(b)
    return words, mask


def _topk_cols_reference(bits, k):
    """The replaced two-pass ``lax.top_k`` extraction (device.py pre-PR 7),
    reimplemented as the output spec: k lowest set columns, ascending,
    NOCOL-padded."""
    m, w = bits.shape
    out = np.full((m, k), 2**30, np.int32)
    for i in range(m):
        cols = np.flatnonzero(
            (bits[i][:, None] >> np.arange(32, dtype=np.uint32)[None, :]) & 1)
        cols = (cols // 32) * 32 + cols % 32
        cols.sort()
        take = min(k, len(cols))
        out[i, :take] = cols[:take]
    return out


@pytest.mark.parametrize("m,w,k", [(8, 2, 16), (100, 7, 32), (256, 16, 128)])
def test_bits_to_cols_interpret_matches_jnp(m, w, k):
    from repro.kernels.ops import NOCOL, bits_to_cols

    rng = np.random.default_rng(m + w)
    bits, mask = _random_bits(rng, m, w)
    prev = os.environ.get("REPRO_PALLAS", "")
    try:
        os.environ["REPRO_PALLAS"] = "interpret"
        ci = np.asarray(bits_to_cols(bits, k))
        os.environ["REPRO_PALLAS"] = "jnp"
        cj = np.asarray(bits_to_cols(bits, k))
    finally:
        os.environ["REPRO_PALLAS"] = prev
    assert np.array_equal(ci, cj)
    # popcount identity: exactly min(popcount, k) real columns per row
    pc = mask.sum(axis=1)
    assert np.array_equal((ci < NOCOL).sum(axis=1), np.minimum(pc, k))
    # bit order: ascending real columns, and exactly the set bits
    assert np.array_equal(ci, _topk_cols_reference(bits, k))


@pytest.mark.parametrize("nq,nl", [(16, 64), (130, 352), (256, 1024)])
def test_leaf_range_pack_interpret_matches_jnp(nq, nl):
    from repro.kernels.ops import leaf_range_pack

    rng = np.random.default_rng(nq)
    # synthetic ±1 range-delta scatters (nested/overlapping ranges), with
    # the traversal's trailing overflow column
    delta = np.zeros((nq, nl + 1), np.int32)
    for _ in range(4):
        lo = rng.integers(0, nl, size=nq)
        hi = lo + rng.integers(0, nl // 2, size=nq)
        np.add.at(delta, (np.arange(nq), lo), 1)
        np.add.at(delta, (np.arange(nq), np.minimum(hi, nl)), -1)
    leaf_ids = rng.permutation(nl).astype(np.int32)
    leaf_ids[rng.random(nl) < 0.1] = SENTINEL_ID        # padding slots
    qids = rng.integers(0, nl, size=nq).astype(np.int32)
    prev = os.environ.get("REPRO_PALLAS", "")
    try:
        os.environ["REPRO_PALLAS"] = "interpret"
        cnt_i, bits_i = leaf_range_pack(delta, leaf_ids, qids)
        os.environ["REPRO_PALLAS"] = "jnp"
        cnt_j, bits_j = leaf_range_pack(delta, leaf_ids, qids)
    finally:
        os.environ["REPRO_PALLAS"] = prev
    cnt_i, bits_i = np.asarray(cnt_i), np.asarray(bits_i)
    assert np.array_equal(bits_i, np.asarray(bits_j))
    assert np.array_equal(cnt_i, np.asarray(cnt_j))
    # popcount identity: cnt IS the mask's popcount
    pc = sum(((bits_i >> b) & 1).sum(axis=1) for b in range(32))
    assert np.array_equal(cnt_i, pc)
    # semantics: cover = running prefix > 0, minus invalid + self slots
    cover = np.cumsum(delta[:, :nl], axis=1) > 0
    cover &= (leaf_ids != SENTINEL_ID)[None, :]
    cover &= qids[:, None] != leaf_ids[None, :]
    got = np.zeros_like(cover)
    for b in range(32):
        got[:, b::32] = ((bits_i >> b) & 1)[:, :cover[:, b::32].shape[1]]
    assert np.array_equal(got, cover)


@pytest.mark.parametrize("mode", ["jnp", "interpret"])
def test_epilogue_bit_identity_vs_topk_extraction(mode):
    """The fused epilogues must be BIT-identical to the ``top_k``
    extraction they replaced, on both engines' id conventions."""
    from repro.kernels.ops import SENTINEL, bits_to_gathered_ids, bits_to_ids

    rng = np.random.default_rng(42)
    m, w, k = 96, 6, 32
    bits, _ = _random_bits(rng, m, w, density=0.2)
    cols = _topk_cols_reference(bits, k)
    id0 = 1000
    want_ids = np.where(cols < 2**30, id0 + cols, SENTINEL).astype(np.int32)
    ids_row = rng.permutation(32 * w).astype(np.int32) + 7
    g = np.where(cols < 32 * w, ids_row[np.minimum(cols, 32 * w - 1)],
                 SENTINEL).astype(np.int32)
    want_gathered = np.sort(g, axis=-1)
    prev = os.environ.get("REPRO_PALLAS", "")
    try:
        os.environ["REPRO_PALLAS"] = mode
        got_ids = np.asarray(bits_to_ids(bits, id0, k))
        got_gathered = np.asarray(bits_to_gathered_ids(bits, ids_row, k))
    finally:
        os.environ["REPRO_PALLAS"] = prev
    assert np.array_equal(got_ids, want_ids)
    assert np.array_equal(got_gathered, want_gathered)


# ---------------------------------------------------------------------------
# 8 simulated devices: build_nng end to end with device-built forests
# ---------------------------------------------------------------------------

_DEVICE_BUILD_8DEV_CODE = r"""
import numpy as np
from repro.nng import build_nng
from repro.core.brute import brute_force_graph
from repro.data import synthetic_pointset

def gap_safe_eps(pts, target=1.0):
    d2 = ((pts[:, None, :].astype(np.float64)
           - pts[None, :, :].astype(np.float64)) ** 2).sum(-1)
    vals = np.sort(np.sqrt(d2[np.triu_indices(len(pts), 1)]))
    i = int(np.searchsorted(vals, target))
    lo, hi = max(i - 2000, 0), min(i + 2000, len(vals) - 1)
    j = lo + int(np.argmax(vals[lo + 1:hi + 1] - vals[lo:hi]))
    assert vals[j + 1] - vals[j] > 1e-5
    return 0.5 * (vals[j] + vals[j + 1])

n = 1024
pts = synthetic_pointset(n, 6, "euclidean", seed=3)
eps = gap_safe_eps(pts)
gb = brute_force_graph(pts, eps, "euclidean")
for partition in ("point", "spatial"):
    g = build_nng(pts, eps, partition=partition, traversal="tree",
                  k_cap=512, forest_backend="device")
    assert g == gb, partition
    assert g.meta["forest_backend"] == "device", partition
    assert g.stats.build_s > 0.0, partition
    gh = build_nng(pts, eps, partition=partition, traversal="tree",
                   k_cap=512, forest_backend="host")
    assert gh == gb, partition
    assert gh.meta["forest_backend"] == "host", partition
print("DEVICE_BUILD_8DEV_OK")
"""


def test_build_nng_device_forests_8dev():
    out = run_subprocess(_DEVICE_BUILD_8DEV_CODE, devices=8, timeout=1200)
    assert "DEVICE_BUILD_8DEV_OK" in out


_RING_FOREST_4DEV_CODE = r"""
import numpy as np
from repro.core.flat_tree import build_block_forests, build_cell_forests
from repro.launch.mesh import make_ring_mesh

mesh = make_ring_mesh(4)
rng = np.random.default_rng(5)
pts = rng.normal(size=(512, 8)).astype(np.float32)
cell = rng.integers(0, 9, size=len(pts))
f = np.arange(9) % 4
for name, build in [
        ("block", lambda **kw: build_block_forests(
            pts, 4, "euclidean", backend="device", **kw)),
        ("cell", lambda **kw: build_cell_forests(
            pts, cell, f, 4, "euclidean", backend="device", **kw))]:
    on_ring, on_one = build(mesh=mesh), build()
    assert set(on_ring) == set(on_one), name
    for k in on_one:
        assert np.array_equal(np.asarray(on_ring[k]),
                              np.asarray(on_one[k])), (name, k)
        # rank r's tables live on ring device r, not all on device 0
        assert on_ring[k].sharding.device_set == set(mesh.devices.flat), (
            name, k, on_ring[k].sharding)
print("RING_FOREST_4DEV_OK")
"""


def test_device_forests_built_on_their_ring_devices_4dev():
    """With the engine's ring mesh, each rank's forest is built on that
    rank's device, and the tables equal the single-device build."""
    out = run_subprocess(_RING_FOREST_4DEV_CODE, devices=4, timeout=600)
    assert "RING_FOREST_4DEV_OK" in out
