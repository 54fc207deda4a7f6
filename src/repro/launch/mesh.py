"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init; dryrun.py must set
XLA_FLAGS before any jax call). Every axis is ``AxisType.Auto``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_axis_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axis_kw(len(axes)))


def make_ring_mesh(nranks: int | None = None) -> Mesh:
    """1D ring over all devices — used by the ε-NNG engine."""
    devs = jax.devices()
    n = nranks or len(devs)
    return Mesh(np.asarray(devs[:n]), ("ring",), **_auto_axis_kw(1))


def make_nng_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """NNG runs on the flattened device ring of the production topology."""
    n = 512 if multi_pod else 256
    return make_ring_mesh(n)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    return jax.make_mesh(shape, axes, **_auto_axis_kw(len(axes)))
