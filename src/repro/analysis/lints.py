"""Lint passes over traced jaxprs (RA101–RA104).

Each pass takes a traced (Closed)Jaxpr plus a ``subject`` string that names
what was traced (kernel or engine config) and returns Diagnostics. The
passes are pure jaxpr inspection — nothing executes.
"""
from __future__ import annotations

import numpy as np
from repro.compat import jcore

from .diagnostics import Diagnostic
from .jaxpr_walk import (iter_eqns, iter_jaxprs, outvar_producer,
                         resolve_scalar_float)

_COMPARE_PRIMS = {"le", "lt", "ge", "gt"}


def float_compare_literals(jaxpr) -> list[float]:
    """Every statically resolvable scalar float threshold appearing as an
    operand of an ordered compare, anywhere in the program (including
    pallas kernel bodies). Thresholds are resolved through short pure-op
    chains — jax leaves ``jnp.float32(eps) ** 2`` as a ``mul`` of two
    literals in the jaxpr rather than folding it."""
    out = []
    for body in iter_jaxprs(jaxpr):
        for eqn in body.eqns:
            if eqn.primitive.name not in _COMPARE_PRIMS:
                continue
            for v in eqn.invars:
                f = resolve_scalar_float(body, v)
                if f is not None:
                    out.append(f)
    return out


def lint_threshold_literals(jaxpr, canonical, *, subject: str,
                            rel_tol: float = 1e-3) -> list[Diagnostic]:
    """RA101 — the ``float(eps) ** 2`` bug class.

    ``canonical`` is the set of threshold values the kernel MUST embed as
    exact compare literals (e.g. ``_eps2_f32(eps)``). Two failure shapes:

    - a compare literal lands *near* a canonical value but not ON it — the
      signature of a python-float (f64) fold of the same expression being
      cast to fp32 (1-ulp threshold skew vs the oracle);
    - the canonical value never appears at all — the threshold was computed
      some other way and knife-edge parity with the oracle is unverified.

    Literals far from every canonical value (slacks, 0.5 cutoffs, inf
    sentinels) are ignored — the pass only polices declared thresholds.
    """
    canonical = tuple(canonical)
    if not canonical:
        return []
    diags = []
    lits = float_compare_literals(jaxpr)
    matched = set()
    for val in lits:
        hit = False
        for c in canonical:
            if val == c:
                matched.add(c)
                hit = True
                break
        if hit:
            continue
        for c in canonical:
            denom = max(abs(c), 1e-30)
            if abs(val - c) <= rel_tol * denom:
                diags.append(Diagnostic(
                    "RA101", subject,
                    f"compare literal {val!r} is a near-miss of the "
                    f"canonical threshold {c!r} (rel err "
                    f"{abs(val - c) / denom:.2e}) — python-float folding "
                    f"into an fp32 compare; compute the threshold in fp32 "
                    f"(_eps2_f32 / np.float32) so kernel and oracle agree "
                    f"on knife-edge pairs"))
                break
    for c in canonical:
        if c not in matched:
            diags.append(Diagnostic(
                "RA101", subject,
                f"canonical threshold {c!r} not found among compare "
                f"literals {sorted(set(lits))!r} — threshold provenance "
                f"unverifiable"))
    return diags


def lint_int_accumulators(jaxpr, *, subject: str) -> list[Diagnostic]:
    """RA102 — scalar integer loop carries fed by data-dependent adds.

    The int32 tile-counter wrap (fixed in PR 4 by moving every device
    counter to float32) as a static check: inspect every scan/while carry;
    a 0-d integer carry whose body-producer is an add/sub with NO literal
    operand grows by a data-dependent amount each iteration and can wrap
    silently. Literal increments (``i = i + 1`` loop counters) are bounded
    by the trip count and exempt.
    """
    diags = []
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            ncar = int(eqn.params["num_carry"])
            carries_out = body.outvars[:ncar]
        elif name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            carries_out = body.outvars
        else:
            continue
        for i, ov in enumerate(carries_out):
            aval = getattr(ov, "aval", None)
            if aval is None or getattr(aval, "ndim", None) != 0:
                continue
            if np.dtype(aval.dtype).kind not in "iu":
                continue
            prod = outvar_producer(body, ov)
            if prod is None or prod.primitive.name not in ("add", "sub"):
                continue
            if any(isinstance(v, jcore.Literal) for v in prod.invars):
                continue  # bounded literal-increment counter
            diags.append(Diagnostic(
                "RA102", subject,
                f"scalar {np.dtype(aval.dtype).name} loop carry #{i} "
                f"accumulates via data-dependent "
                f"'{prod.primitive.name}' — wraps silently at paper "
                f"scale; use a float32 counter (exact below 2^24) like "
                f"the engine counters"))
    return diags


_HOST_SYNC_MARKERS = ("callback", "infeed", "outfeed", "host_local")


def lint_host_sync(jaxpr, *, subject: str) -> list[Diagnostic]:
    """RA103 — host transfer / sync primitives inside a jitted body.

    A callback (pure/io/debug) or infeed/outfeed in a shard_map engine body
    serializes every rank on the host each step — fatal for the systolic
    overlap story and invisible in small-scale tests."""
    diags = []
    seen = set()
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(marker in name for marker in _HOST_SYNC_MARKERS):
            if name in seen:
                continue
            seen.add(name)
            diags.append(Diagnostic(
                "RA103", subject,
                f"host sync primitive '{name}' inside jitted body — "
                f"forces a device→host round-trip every invocation"))
    return diags


def lint_f64(jaxpr, *, subject: str) -> list[Diagnostic]:
    """RA104 — float64 values inside the (declared-fp32) device programs.

    The repo's exactness story is 'declared fp32 arithmetic, float64 only
    in host oracles'; an f64 aval on device means an accidental x64 leak
    (silently 2× memory + no TPU support)."""
    hits = 0
    first = None
    for eqn, _ in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt) in (np.float64, np.complex128):
                hits += 1
                if first is None:
                    first = eqn.primitive.name
    if hits:
        return [Diagnostic(
            "RA104", subject,
            f"{hits} float64 operand/result aval(s) in the program (first "
            f"at primitive '{first}') — device programs are declared fp32; "
            f"float64 belongs in host oracles only")]
    return []
