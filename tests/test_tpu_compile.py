"""Compile every engine-path Pallas kernel for a described TPU v5e.

Interpret-mode parity (``test_kernels.py``) cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, primitives Mosaic
does not lower, VMEM over budget. These tests lower each kernel through
its ``repro.kernels.ops`` wrapper (``REPRO_PALLAS=compiled``, so the real
padding and tile geometry apply) for one chip of a described ``v5e:2x2``
topology, and compile one whole systolic tiles program on a 1-device mesh
of it. Nothing runs: a pass means the chip's compiler accepts the program.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every xdist worker imports
this file.
"""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "compiled")


def _compile(fn, sharding, *specs):
    args = [jax.ShapeDtypeStruct(s, np.dtype(d), sharding=sharding)
            for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


f32, i32, u32 = np.float32, np.int32, np.uint32

# (metric, point dtype, row width): sift-1m width, word2bits width (800 bits
# = 25 words), and L1 at sift width
_METRICS = [("euclidean", f32, 128), ("hamming", u32, 25),
            ("manhattan", f32, 128)]
# both tile_shape regimes: full engine tiles, and sub-tile operands that
# round up to one block; plus the euclidean feature-axis pad (dim 6 -> 128)
_SIZES = [(4096, 4096), (200, 300)]


def _cases():
    for metric, dt, width in _METRICS:
        for q, p in _SIZES:
            yield pytest.param(metric, dt, width, q, p,
                               id=f"{metric}-{q}x{p}")
    yield pytest.param("euclidean", f32, 6, 200, 300, id="euclidean-dim6")


_CASES = list(_cases())


@pytest.mark.parametrize("metric,dt,width,q,p", _CASES)
def test_tile_kernel_compiles(one_chip, compiled_mode, metric, dt, width, q,
                              p):
    from repro.kernels import nng_tile_bits
    _compile(lambda x, y, v: nng_tile_bits(x, y, v, 1.0, metric=metric),
             one_chip, ((q, width), dt), ((p, width), dt), ((p,), i32))


@pytest.mark.parametrize("metric,dt,width,q,p", _CASES)
def test_grouped_kernel_compiles(one_chip, compiled_mode, metric, dt, width,
                                 q, p):
    from repro.kernels import nng_tile_bits_grouped
    _compile(lambda x, y, xg, yg, xi, yi: nng_tile_bits_grouped(
        x, y, xg, yg, xi, yi, 1.0, metric=metric),
        one_chip, ((q, width), dt), ((p, width), dt), ((q,), i32),
        ((p,), i32), ((q,), i32), ((p,), i32))


@pytest.mark.parametrize("metric,dt,width,q,p", _CASES)
def test_ghost_kernel_compiles(one_chip, compiled_mode, metric, dt, width,
                               q, p):
    from repro.kernels import nng_tile_bits_ghost
    _compile(lambda x, y, gb, yg: nng_tile_bits_ghost(
        x, y, gb, yg, 1.0, metric=metric),
        one_chip, ((q, width), dt), ((p, width), dt), ((q, 2), u32),
        ((p,), i32))


@pytest.mark.parametrize("metric,dt,width,q,p", _CASES)
def test_frontier_kernel_compiles(one_chip, compiled_mode, metric, dt,
                                  width, q, p):
    from repro.kernels import tree_frontier_step
    n = -(-p // 32) * 32
    _compile(lambda qq, c, r, lf, a: tree_frontier_step(
        qq, c, r, lf, a, 1.0, metric=metric),
        one_chip, ((q, width), dt), ((n, width), dt), ((n,), f32),
        ((n,), i32), ((q, n // 32), u32))


# the benchmark cells' self tiles (2^17 rows, 4096 words) at their k_cap:
# sift sparse 640, the ring 896, sift dense 1408, word2bits 1536
@pytest.mark.parametrize("m,w,k", [(4096, 128, 256), (200, 10, 64),
                                   (16384, 512, 512), (131072, 4096, 640),
                                   (131072, 4096, 896), (131072, 4096, 1408),
                                   (131072, 4096, 1536)])
def test_bits_to_cols_compiles(one_chip, compiled_mode, m, w, k):
    from repro.kernels.ops import bits_to_cols
    _compile(lambda b: bits_to_cols(b, k), one_chip, ((m, w), u32))


@pytest.mark.parametrize("nq,nl", [(4096, 4096), (200, 96), (1000, 1312)])
def test_leaf_range_pack_compiles(one_chip, compiled_mode, nq, nl):
    from repro.kernels.ops import leaf_range_pack
    _compile(leaf_range_pack, one_chip, ((nq, nl + 1), i32), ((nl,), i32),
             ((nq,), i32))


def test_systolic_tiles_program_compiles(topo, one_chip, compiled_mode):
    """One whole jitted ``systolic_run`` tiles program (ring of one chip):
    the L2 tile kernel, the diagonal clear, the bitmask epilogue and the
    merges, as the engine traces them."""
    from repro.core.distributed.device import _systolic_fn
    from repro.core.metrics import get_metric
    from repro.kernels.ops import pallas_mode
    mesh = Mesh(np.asarray(topo.devices[:1]), ("ring",))
    fn = _systolic_fn(mesh, 1.0, get_metric("euclidean"), 128, "ring", True,
                      pallas_mode(), "tiles")
    n = 4096
    compiled = _compile(fn, one_chip, ((n, 128), f32), ((n,), i32))
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 * 2**30


def test_systolic_stage_scopes_keep_kernel_names(topo, one_chip,
                                                 compiled_mode):
    """The systolic tiles program on a ring of two chips: its stages carry
    their ``nng.*`` named scopes in ``op_name``, and the kernels' custom
    calls keep the names the benchmark's matchers look for."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.kernels import is_epilogue, is_tile
    from repro.core.distributed.device import _systolic_fn
    from repro.core.metrics import get_metric
    from repro.kernels.ops import pallas_mode
    mesh = Mesh(np.asarray(topo.devices[:2]), ("ring",))
    fn = _systolic_fn(mesh, 1.0, get_metric("euclidean"), 128, "ring", True,
                      pallas_mode(), "tiles")
    n = 2 * 4096
    args = [jax.ShapeDtypeStruct((n, 128), f32, sharding=NamedSharding(
                mesh, P("ring", None))),
            jax.ShapeDtypeStruct((n,), i32, sharding=NamedSharding(
                mesh, P("ring")))]
    text = fn.lower(*args).compile().as_text()
    lines = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    # the self tile, then the pair round's forward and mirror tiles
    assert sum(map(is_tile, lines)) == 3
    assert sum(map(is_epilogue, lines)) == 3
    assert all(is_tile(ln) or is_epilogue(ln) for ln in lines), lines
    scopes = set(re.findall(r'op_name="[^"]*?/(nng\.[a-z_]+)/', text))
    assert scopes == {"nng.tile", "nng.epilogue", "nng.merge", "nng.ring",
                      "nng.mirror_home"}


_SHAPE = re.compile(r"^%\S+ = [a-z]+(\d+)\[([\d,]*)\]")
# in a compiled module's text operands are bare ``%names``: the opcode is
# the one word before ``(%``
_PERMUTE = re.compile(r" (collective-permute(?:-start|-done)?)\(%")


def _computations(text):
    """{name: [operation lines]} of a compiled module's text."""
    comps, cur = {}, None
    for ln in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%\S+) .*\{$", ln)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif ln.startswith("  ") and cur is not None:
            cur.append(ln.strip().removeprefix("ROOT "))
    return comps


def _hop_bytes(line):
    """Bytes one ``collective-permute`` moves: its result, which has the
    operand's shape (the ``-done`` half or the synchronous operation)."""
    bits, dims = _SHAPE.match(line).groups()
    return int(bits) // 8 * int(np.prod([int(d) for d in dims.split(",")
                                         if d]))


def test_ring4_sift_program_fits_and_its_hops_match_the_counter(
        topo, one_chip, compiled_mode):
    """The ``sift-sparse-ring4`` cell's own program: 2^19 x 128 points on
    a ring of the four chips of a described ``v5e:2x2``, k_cap 896. It
    fits a chip's 16 GiB; its ring hops are ``collective-permute``
    operations that ``ring_exposed_ms`` matches and nothing else is; and
    their bytes per rank, loop trips counted, are ``RunStats.ring_bytes``
    of one engine call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.ring import is_ring_hop
    from repro.core.distributed.device import _systolic_fn
    from repro.core.metrics import get_metric
    from repro.kernels.ops import pallas_mode
    from repro.nng import PointPartitionEngine
    n, dim, k_cap, nranks = 2**19, 128, 896, 4
    mesh = Mesh(np.asarray(topo.devices[:nranks]), ("ring",))
    fn = _systolic_fn(mesh, 3.07, get_metric("euclidean"), k_cap, "ring",
                      True, pallas_mode(), "tiles")
    args = [jax.ShapeDtypeStruct((n, dim), f32, sharding=NamedSharding(
                mesh, P("ring", None))),
            jax.ShapeDtypeStruct((n,), i32, sharding=NamedSharding(
                mesh, P("ring")))]
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16 * 2**30

    comps = _computations(compiled.as_text())
    ops = [ln for lines in comps.values() for ln in lines]
    hops = [ln for ln in ops if _PERMUTE.search(ln)]
    assert len(hops) == 16                  # 8 start / done pairs
    assert [ln for ln in ops if is_ring_hop(ln)] == hops
    # the ring's loop runs rounds = nranks // 2 trips: a hop in its body
    # counts once per trip
    trips = {}
    for ln in ops:
        m = re.search(r" while\(.*condition=(%\S+), body=(%\S+?),", ln)
        if m:
            [bound] = re.findall(r"constant\((\d+)\)",
                                 "\n".join(comps[m.group(1)]))
            trips[m.group(2)] = int(bound)
    assert set(trips.values()) == {nranks // 2}
    sent = sum(trips.get(name, 1) * _hop_bytes(ln)
               for name, lines in comps.items() for ln in lines
               if _PERMUTE.search(ln) and "-start(" not in ln)
    engine = PointPartitionEngine(
        np.broadcast_to(f32(0), (n, dim)), 3.07, mesh, "euclidean",
        k_cap=k_cap)
    assert sent == engine._ring_hop_bytes(k_cap) == 1_612_185_612
