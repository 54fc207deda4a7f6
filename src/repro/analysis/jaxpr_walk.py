"""Jaxpr walking utilities shared by every analyzer pass.

``iter_eqns`` is the workhorse: a depth-first, *in-order* traversal of a
(Closed)Jaxpr and every jaxpr nested in its equations' params — scan/while
bodies, cond branches, pjit/shard_map/pallas_call inner programs — yielding
``(eqn, mult)`` where ``mult`` is the static execution-count multiplier.

Multipliers matter for the traffic audit: a ``fori_loop`` with static
bounds lowers to ``scan`` carrying its trip count in
``params["length"]``, so a ``ppermute`` inside a ring loop contributes
``rounds`` hops, not one. In-order matters for channel classification: the
equation order of a traced jaxpr follows the python call order of the
traced function, which is what the adjacency-inheritance rule in
``traffic.py`` relies on.

``while`` bodies have no static trip count; they are walked at mult 1 and
counted in the ``unknown_loops`` attribute callers can inspect (the engine
programs contain none — every loop is a static-bound ``fori_loop``).
"""
from __future__ import annotations

import numpy as np
from repro.compat import jcore

__all__ = ["iter_eqns", "iter_jaxprs", "outvar_producer", "literal_float",
           "resolve_scalar", "resolve_scalar_float", "aval_nbytes", "EqnWalk"]


def _collect_jaxprs(v, out):
    if isinstance(v, jcore.ClosedJaxpr):
        out.append(v.jaxpr)
    elif isinstance(v, jcore.Jaxpr):
        out.append(v)
    elif isinstance(v, (tuple, list)):
        for x in v:
            _collect_jaxprs(x, out)
    elif isinstance(v, dict):
        for x in v.values():
            _collect_jaxprs(x, out)


def _param_jaxprs(eqn) -> list:
    out: list = []
    for v in eqn.params.values():
        _collect_jaxprs(v, out)
    return out


class EqnWalk:
    """Iterator object so callers can read ``unknown_loops`` afterwards."""

    def __init__(self, jaxpr, mult: float = 1.0):
        self._root = getattr(jaxpr, "jaxpr", jaxpr)
        self._mult = mult
        self.unknown_loops = 0

    def __iter__(self):
        yield from self._walk(self._root, self._mult)

    def _walk(self, jaxpr, mult):
        for eqn in jaxpr.eqns:
            yield eqn, mult
            sub_mult = mult
            if eqn.primitive.name == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1))
            elif eqn.primitive.name == "while":
                self.unknown_loops += 1
            for j in _param_jaxprs(eqn):
                yield from self._walk(j, sub_mult)


def iter_eqns(jaxpr, mult: float = 1.0):
    """Yield (eqn, mult) over the jaxpr and all nested jaxprs, in order."""
    yield from EqnWalk(jaxpr, mult)


def outvar_producer(jaxpr, var):
    """The equation producing ``var`` in this (non-nested) jaxpr body, or
    None when the variable is a pass-through input / constant."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            if ov is var:
                return eqn
    return None


def literal_float(v):
    """float(value) when ``v`` is a float-dtype Literal, else None."""
    if not isinstance(v, jcore.Literal):
        return None
    arr = np.asarray(v.val)
    if arr.dtype.kind != "f" or arr.ndim != 0:
        return None
    return float(arr)


def iter_jaxprs(jaxpr):
    """Yield the root jaxpr body and every nested body (scan/cond/pjit/
    shard_map/pallas_call inner programs)."""
    root = getattr(jaxpr, "jaxpr", jaxpr)
    stack = [root]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(_param_jaxprs(eqn))


# pure scalar ops the resolver folds, evaluated in the OUTPUT dtype —
# np.float32(0.1) * np.float32(0.1) must give the exact f32 product the
# compiled program compares against, not the f64 one.
_FOLD_OPS = {
    "mul": np.multiply, "add": np.add, "sub": np.subtract,
    "div": np.divide, "max": np.maximum, "min": np.minimum,
    "neg": np.negative, "abs": np.abs, "sqrt": np.sqrt,
    "integer_pow": None,  # handled explicitly (exponent is a param)
}


def resolve_scalar(jaxpr_body, v, depth: int = 8):
    """Fold a 0-d numeric operand to a concrete numpy scalar when it is a
    Literal or a short chain of pure scalar ops over Literals (jax leaves
    trace-time products like ``jnp.float32(eps) ** 2`` as ``mul`` eqns in
    the jaxpr rather than folding them). Returns None when unresolvable.
    """
    if isinstance(v, jcore.Literal):
        arr = np.asarray(v.val)
        return arr if arr.ndim == 0 and arr.dtype.kind in "fiu" else None
    if depth <= 0 or not isinstance(v, jcore.Var):
        return None
    aval = getattr(v, "aval", None)
    if getattr(aval, "ndim", None) != 0:
        return None
    eqn = outvar_producer(jaxpr_body, v)
    if eqn is None:
        return None
    name = eqn.primitive.name
    out_dtype = np.dtype(eqn.outvars[0].aval.dtype)
    if name == "convert_element_type":
        x = resolve_scalar(jaxpr_body, eqn.invars[0], depth - 1)
        return None if x is None else x.astype(out_dtype)
    if name == "integer_pow":
        x = resolve_scalar(jaxpr_body, eqn.invars[0], depth - 1)
        if x is None:
            return None
        return np.asarray(x.astype(out_dtype) ** int(eqn.params["y"]),
                          out_dtype)
    fn = _FOLD_OPS.get(name)
    if fn is None:
        return None
    xs = [resolve_scalar(jaxpr_body, iv, depth - 1) for iv in eqn.invars]
    if any(x is None for x in xs):
        return None
    with np.errstate(all="ignore"):
        out = fn(*[x.astype(out_dtype) for x in xs])
    return np.asarray(out, out_dtype)


def resolve_scalar_float(jaxpr_body, v, depth: int = 8):
    """``resolve_scalar`` restricted to float results -> python float."""
    x = resolve_scalar(jaxpr_body, v, depth)
    if x is None or x.dtype.kind != "f":
        return None
    return float(x)


def aval_nbytes(aval) -> int:
    """Static byte size of a shaped aval."""
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * np.dtype(aval.dtype).itemsize
