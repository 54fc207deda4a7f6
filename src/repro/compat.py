"""One spelling for the jax constructs whose public home moved.

The codebase targets jax >= 0.9: ``jax.shard_map`` and the jaxpr IR types
in ``jax.extend.core`` (``jax.core`` no longer exports ``ClosedJaxpr``,
``Jaxpr``, ``Literal`` or ``Var``). Engine, analyzer and test code import
them from here.
"""
from __future__ import annotations

import jax
from jax.extend import core as jcore  # noqa: F401  (re-exported)


def shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off (the engines' ring
    bodies return per-rank values the checker cannot type)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
