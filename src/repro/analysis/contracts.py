"""Kernel contract registry + checker (RA001–RA004, RA101, RA104).

A ``KernelContract`` is the static promise a Pallas kernel makes to the
rest of the system: which jnp/numpy oracle defines its semantics, which
tile/%32 padding invariants its launch shapes must satisfy, which dtypes
it emits, and which canonical fp32 threshold literal(s) it must embed.
``check_contract`` verifies everything tracing can see without executing:

- RA003  declared shape invariants (``value % multiple == 0``)
- RA004  contract declares no oracle at all
- RA001  kernel or oracle fails to abstract-trace
- RA002  kernel vs oracle output avals disagree, or kernel outputs break
         the declared dtype policy
- RA101  canonical-threshold literal check on the traced kernel jaxpr
- RA104  float64 leak in the traced kernel jaxpr

Traces run in interpret-free abstract mode (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` args) so the checker works on CPU CI with no
accelerator present.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .diagnostics import Diagnostic
from .lints import lint_f64, lint_threshold_literals

__all__ = ["KernelContract", "check_contract", "check_all", "default_contracts"]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


@dataclass(frozen=True)
class KernelContract:
    """Static contract for one Pallas kernel entry point.

    ``kernel_trace`` / ``oracle_trace`` are zero-arg closures returning
    ``(fn, sds_args)`` — the callable with every *static* argument (eps,
    tile sizes) already bound, plus ShapeDtypeStructs for the traced
    array arguments. Binding eps statically mirrors how the engines call
    the kernels: eps is folded into the program as a literal, which is
    exactly what the RA101 pass inspects.
    """

    name: str
    kernel_trace: Callable[[], tuple]
    oracle_trace: Callable[[], tuple] | None
    # canonical fp32 threshold literal(s) the kernel must embed (empty for
    # integer-threshold kernels like hamming).
    canonical_thresholds: tuple = ()
    # (value, multiple, label) padding/tiling invariants, checked statically.
    shape_invariants: tuple = ()
    # expected output dtypes, in output order.
    out_dtypes: tuple = ()
    notes: str = field(default="", compare=False)


def _trace(fn, args):
    return jax.make_jaxpr(fn)(*args)


def check_contract(c: KernelContract) -> list[Diagnostic]:
    diags: list[Diagnostic] = []

    for value, multiple, label in c.shape_invariants:
        if int(value) % int(multiple) != 0:
            diags.append(Diagnostic(
                "RA003", c.name,
                f"invariant '{label}' violated: {value} % {multiple} = "
                f"{int(value) % int(multiple)}"))

    if c.oracle_trace is None:
        diags.append(Diagnostic(
            "RA004", c.name,
            "contract declares no jnp oracle — fp32 kernel semantics "
            "unverifiable against float64 ground truth"))

    try:
        kfn, kargs = c.kernel_trace()
        kjaxpr = _trace(kfn, kargs)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        diags.append(Diagnostic(
            "RA001", c.name, f"kernel failed to trace: {type(e).__name__}: {e}"))
        return diags

    if c.out_dtypes:
        kouts = kjaxpr.out_avals
        if len(kouts) != len(c.out_dtypes):
            diags.append(Diagnostic(
                "RA002", c.name,
                f"kernel emits {len(kouts)} outputs, contract declares "
                f"{len(c.out_dtypes)} dtypes"))
        else:
            for i, (av, want) in enumerate(zip(kouts, c.out_dtypes)):
                if np.dtype(av.dtype) != np.dtype(want):
                    diags.append(Diagnostic(
                        "RA002", c.name,
                        f"output #{i} dtype {np.dtype(av.dtype).name} "
                        f"violates declared policy {np.dtype(want).name}"))

    if c.oracle_trace is not None:
        try:
            ofn, oargs = c.oracle_trace()
            ojaxpr = _trace(ofn, oargs)
        except Exception as e:  # noqa: BLE001
            diags.append(Diagnostic(
                "RA001", c.name,
                f"oracle failed to trace: {type(e).__name__}: {e}"))
        else:
            kouts = [(tuple(a.shape), np.dtype(a.dtype))
                     for a in kjaxpr.out_avals]
            oouts = [(tuple(a.shape), np.dtype(a.dtype))
                     for a in ojaxpr.out_avals]
            if kouts != oouts:
                diags.append(Diagnostic(
                    "RA002", c.name,
                    f"kernel outputs {kouts} != oracle outputs {oouts}"))

    diags += lint_threshold_literals(
        kjaxpr, c.canonical_thresholds, subject=c.name)
    diags += lint_f64(kjaxpr, subject=c.name)
    return diags


def check_all(contracts: Sequence[KernelContract] | None = None
              ) -> tuple[list[Diagnostic], list[KernelContract]]:
    cs = list(contracts) if contracts is not None else default_contracts()
    diags: list[Diagnostic] = []
    for c in cs:
        diags += check_contract(c)
    return diags, cs


# ---------------------------------------------------------------------------
# Registry: every Pallas entry point in repro.kernels.
# ---------------------------------------------------------------------------

_EPS_L2 = 0.1   # probe radius for float-metric kernels
_EPS_HAM = 5    # integer probe radius for hamming kernels


def default_contracts() -> list[KernelContract]:
    # importlib, not `from repro.kernels import ...`: kernels/__init__
    # re-exports ops wrappers named `eps_count` / `pairwise_hamming` that
    # shadow the submodules on attribute lookup
    import importlib
    be = importlib.import_module("repro.kernels.bits_epilogue")
    ec = importlib.import_module("repro.kernels.eps_count")
    nt = importlib.import_module("repro.kernels.nng_tile")
    ph = importlib.import_module("repro.kernels.pairwise_hamming")
    pl = importlib.import_module("repro.kernels.pairwise_l2")
    ref = importlib.import_module("repro.kernels.ref")
    tf = importlib.import_module("repro.kernels.tree_frontier")
    _eps2_f32 = nt._eps2_f32

    eps2 = _eps2_f32(_EPS_L2)
    eps_f32 = float(np.float32(_EPS_L2))

    f32, i32, u32 = np.float32, np.int32, np.uint32

    contracts = [
        KernelContract(
            name="nng_tile",
            kernel_trace=lambda: (
                lambda x, y, v: nt.nng_tile_pallas(x, y, v, _EPS_L2,
                                                   tq=256, tp=512),
                (_sds((256, 8), f32), _sds((512, 8), f32), _sds((512,), i32))),
            oracle_trace=lambda: (
                lambda x, y, v: nt.nng_tile_ref(x, y, v, _EPS_L2),
                (_sds((256, 8), f32), _sds((512, 8), f32), _sds((512,), i32))),
            canonical_thresholds=(eps2,),
            shape_invariants=((256, 256, "q % tq"), (512, 512, "p % tp"),
                              (512, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_hamming",
            kernel_trace=lambda: (
                lambda x, y, v: nt.nng_tile_hamming_pallas(
                    x, y, v, _EPS_HAM, tq=128, tp=256),
                (_sds((128, 8), u32), _sds((256, 8), u32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, v: nt.nng_tile_hamming_ref(x, y, v, _EPS_HAM),
                (_sds((128, 8), u32), _sds((256, 8), u32), _sds((256,), i32))),
            canonical_thresholds=(),  # integer threshold — exact by nature
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_l1",
            kernel_trace=lambda: (
                lambda x, y, v: nt.nng_tile_l1_pallas(
                    x, y, v, _EPS_L2, tq=128, tp=256),
                (_sds((128, 8), f32), _sds((256, 8), f32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, v: nt.nng_tile_l1_ref(x, y, v, _EPS_L2),
                (_sds((128, 8), f32), _sds((256, 8), f32), _sds((256,), i32))),
            canonical_thresholds=(eps_f32,),
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_grouped",
            kernel_trace=lambda: (
                lambda x, y, xg, yg, xi, yi: nt.nng_tile_grouped_pallas(
                    x, y, xg, yg, xi, yi, _EPS_L2, tq=256, tp=512),
                (_sds((256, 8), f32), _sds((512, 8), f32),
                 _sds((256,), i32), _sds((512,), i32),
                 _sds((256,), i32), _sds((512,), i32))),
            oracle_trace=lambda: (
                lambda x, y, xg, yg, xi, yi: nt.nng_tile_grouped_ref(
                    x, y, xg, yg, xi, yi, _EPS_L2),
                (_sds((256, 8), f32), _sds((512, 8), f32),
                 _sds((256,), i32), _sds((512,), i32),
                 _sds((256,), i32), _sds((512,), i32))),
            canonical_thresholds=(eps2,),
            shape_invariants=((256, 256, "q % tq"), (512, 512, "p % tp"),
                              (512, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_grouped_hamming",
            kernel_trace=lambda: (
                lambda x, y, xg, yg, xi, yi:
                nt.nng_tile_grouped_hamming_pallas(
                    x, y, xg, yg, xi, yi, _EPS_HAM,
                    tq=128, tp=256),
                (_sds((128, 8), u32), _sds((256, 8), u32),
                 _sds((128,), i32), _sds((256,), i32),
                 _sds((128,), i32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, xg, yg, xi, yi: nt.nng_tile_grouped_hamming_ref(
                    x, y, xg, yg, xi, yi, _EPS_HAM),
                (_sds((128, 8), u32), _sds((256, 8), u32),
                 _sds((128,), i32), _sds((256,), i32),
                 _sds((128,), i32), _sds((256,), i32))),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_grouped_l1",
            kernel_trace=lambda: (
                lambda x, y, xg, yg, xi, yi: nt.nng_tile_grouped_l1_pallas(
                    x, y, xg, yg, xi, yi, _EPS_L2,
                    tq=128, tp=256),
                (_sds((128, 8), f32), _sds((256, 8), f32),
                 _sds((128,), i32), _sds((256,), i32),
                 _sds((128,), i32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, xg, yg, xi, yi: nt.nng_tile_grouped_l1_ref(
                    x, y, xg, yg, xi, yi, _EPS_L2),
                (_sds((128, 8), f32), _sds((256, 8), f32),
                 _sds((128,), i32), _sds((256,), i32),
                 _sds((128,), i32), _sds((256,), i32))),
            canonical_thresholds=(eps_f32,),
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_ghost",
            kernel_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_pallas(
                    x, y, gb, yg, _EPS_L2, tq=256, tp=512),
                (_sds((256, 8), f32), _sds((512, 8), f32),
                 _sds((256, 1), u32), _sds((512,), i32))),
            oracle_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_ref(
                    x, y, gb, yg, _EPS_L2),
                (_sds((256, 8), f32), _sds((512, 8), f32),
                 _sds((256, 1), u32), _sds((512,), i32))),
            canonical_thresholds=(eps2,),
            shape_invariants=((256, 256, "q % tq"), (512, 512, "p % tp"),
                              (512, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_ghost_hamming",
            kernel_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_hamming_pallas(
                    x, y, gb, yg, _EPS_HAM, tq=128, tp=256),
                (_sds((128, 8), u32), _sds((256, 8), u32),
                 _sds((128, 1), u32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_hamming_ref(
                    x, y, gb, yg, _EPS_HAM),
                (_sds((128, 8), u32), _sds((256, 8), u32),
                 _sds((128, 1), u32), _sds((256,), i32))),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="nng_tile_ghost_l1",
            kernel_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_l1_pallas(
                    x, y, gb, yg, _EPS_L2, tq=128, tp=256),
                (_sds((128, 8), f32), _sds((256, 8), f32),
                 _sds((128, 1), u32), _sds((256,), i32))),
            oracle_trace=lambda: (
                lambda x, y, gb, yg: nt.nng_tile_ghost_l1_ref(
                    x, y, gb, yg, _EPS_L2),
                (_sds((128, 8), f32), _sds((256, 8), f32),
                 _sds((128, 1), u32), _sds((256,), i32))),
            canonical_thresholds=(eps_f32,),
            shape_invariants=((128, 128, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="tree_frontier",
            kernel_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_pallas(
                    q, c, rad, leaf, act, _EPS_L2, tq=256, tn=512),
                (_sds((256, 8), f32), _sds((512, 8), f32), _sds((512,), f32),
                 _sds((512,), i32), _sds((256, 16), u32))),
            oracle_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_ref(
                    q, c, rad, leaf, act, _EPS_L2),
                (_sds((256, 8), f32), _sds((512, 8), f32), _sds((512,), f32),
                 _sds((512,), i32), _sds((256, 16), u32))),
            canonical_thresholds=(eps2,),
            shape_invariants=((256, 256, "nq % tq"), (512, 512, "N % tn"),
                              (512, 32, "tn % 32")),
            out_dtypes=(u32, u32),
        ),
        KernelContract(
            name="tree_frontier_hamming",
            kernel_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_hamming_pallas(
                    q, c, rad, leaf, act, _EPS_HAM,
                    tq=128, tn=256),
                (_sds((128, 8), u32), _sds((256, 8), u32), _sds((256,), f32),
                 _sds((256,), i32), _sds((128, 8), u32))),
            oracle_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_hamming_ref(
                    q, c, rad, leaf, act, _EPS_HAM),
                (_sds((128, 8), u32), _sds((256, 8), u32), _sds((256,), f32),
                 _sds((256,), i32), _sds((128, 8), u32))),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "nq % tq"), (256, 256, "N % tn"),
                              (256, 32, "tn % 32")),
            out_dtypes=(u32, u32),
        ),
        KernelContract(
            name="tree_frontier_l1",
            kernel_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_l1_pallas(
                    q, c, rad, leaf, act, _EPS_L2,
                    tq=128, tn=256),
                (_sds((128, 8), f32), _sds((256, 8), f32), _sds((256,), f32),
                 _sds((256,), i32), _sds((128, 8), u32))),
            oracle_trace=lambda: (
                lambda q, c, rad, leaf, act: tf.tree_frontier_l1_ref(
                    q, c, rad, leaf, act, _EPS_L2),
                (_sds((128, 8), f32), _sds((256, 8), f32), _sds((256,), f32),
                 _sds((256,), i32), _sds((128, 8), u32))),
            canonical_thresholds=(eps_f32,),
            shape_invariants=((128, 128, "nq % tq"), (256, 256, "N % tn"),
                              (256, 32, "tn % 32")),
            out_dtypes=(u32, u32),
        ),
        KernelContract(
            name="bits_to_cols",
            kernel_trace=lambda: (
                lambda b: be.bits_to_cols_pallas(b, 128, tq=128)[0],
                (_sds((128, 128), u32),)),
            oracle_trace=lambda: (
                lambda b: be.bits_to_cols_ref(b, 128),
                (_sds((128, 128), u32),)),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "m % tq"), (128, 128, "w % 128")),
            out_dtypes=(i32,),
        ),
        KernelContract(
            name="leaf_range_pack",
            kernel_trace=lambda: (
                lambda d, li, qi: be.leaf_range_pack_pallas(
                    d, li, qi, tq=128, tn=512),
                (_sds((128, 512), i32), _sds((512,), i32), _sds((128,), i32))),
            oracle_trace=lambda: (
                lambda d, li, qi: be.leaf_range_pack_ref(d, li, qi),
                (_sds((128, 512), i32), _sds((512,), i32), _sds((128,), i32))),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "nq % tq"), (512, 512, "nl % tn"),
                              (512, 32, "tn % 32")),
            out_dtypes=(i32, u32),
        ),
        KernelContract(
            name="pairwise_sqdist",
            kernel_trace=lambda: (
                lambda x, y: pl.pairwise_sqdist_pallas(
                    x, y, tq=256, tp=256, td=512),
                (_sds((256, 512), f32), _sds((256, 512), f32))),
            oracle_trace=lambda: (
                lambda x, y: ref.pairwise_sqdist_blas3_ref(x, y),
                (_sds((256, 512), f32), _sds((256, 512), f32))),
            canonical_thresholds=(),
            shape_invariants=((256, 256, "q % tq"), (256, 256, "p % tp"),
                              (512, 512, "d % td")),
            out_dtypes=(f32,),
        ),
        KernelContract(
            name="pairwise_hamming",
            kernel_trace=lambda: (
                lambda x, y: ph.pairwise_hamming_pallas(
                    x, y, tq=128, tp=128, tw=8),
                (_sds((128, 8), u32), _sds((128, 8), u32))),
            oracle_trace=lambda: (
                lambda x, y: ref.pairwise_hamming_ref(x, y),
                (_sds((128, 8), u32), _sds((128, 8), u32))),
            canonical_thresholds=(),
            shape_invariants=((128, 128, "q % tq"), (128, 128, "p % tp"),
                              (8, 8, "w % tw")),
            out_dtypes=(i32,),
        ),
        KernelContract(
            name="eps_count",
            kernel_trace=lambda: (
                lambda x, y, m: ec.eps_count_pallas(x, y, m, _EPS_L2,
                                                    tq=256, tp=256),
                (_sds((256, 8), f32), _sds((256, 8), f32), _sds((256,), i32))),
            # The host oracle eps_count_ref(x, y, eps) takes no mask; wrap
            # with an all-valid mask assumption by tracing the kernel-arity
            # shape against the maskless oracle's output aval.
            oracle_trace=lambda: (
                lambda x, y: ref.eps_count_ref(x, y, _EPS_L2),
                (_sds((256, 8), f32), _sds((256, 8), f32))),
            canonical_thresholds=(_eps2_f32(_EPS_L2),),
            shape_invariants=((256, 256, "q % tq"), (256, 256, "p % tp"),
                              (256, 32, "tp % 32")),
            out_dtypes=(i32,),
        ),
    ]
    return contracts
