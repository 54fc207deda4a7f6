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


@pytest.mark.parametrize("m,w,k", [(4096, 128, 256), (200, 10, 64),
                                   (16384, 512, 512)])
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
