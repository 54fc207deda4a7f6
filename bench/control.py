"""The lower-precision control of the check, and the readings its limits
are set from.

The control is the reference put in the program's place, one precision
below what the configuration states:

- euclidean (float32 with the contraction at Precision.HIGHEST): the same
  expansion |x|^2 + |y|^2 - 2 x.y in float32, with the contraction at
  ``high``, three bfloat16 passes (x = hi + lo in bfloat16; hi.hi + hi.lo
  + lo.hi, each product exact, summed in float32);
- hamming (exact integer distances, no precision stated): the guarantee of
  exactness broken the way a move of the popcount onto the MXU would
  break it, the 0/1 match count rounded to bfloat16.

Both are written out explicitly, so they compute the same on any backend;
the rounding to bfloat16 is integer arithmetic on the bits, which no
compiler may leave out.
The control's rows go through the same comparison as a build's.

On the chip, at a cell's own size, ``main`` reads the program and the
control on the same seeds, in one process::

    python -m bench.control --workload sift-sparse-point-tiles --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from .reference import BLOCK, RowSets, compare_rows

CONTROL_NUMBER = {"euclidean": "max_gap_ulp", "hamming": "mismatched_pairs"}


def _round_to_bf16(x):
    """float32 rounded to the nearest bfloat16 (ties to even), by integer
    arithmetic on its bits: a compiler that keeps excess precision cannot
    leave the rounding out, as it may a float32 -> bfloat16 -> float32
    round trip of converts."""
    import jax
    import jax.numpy as jnp
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _unpack_bits(words):
    import jax.numpy as jnp
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(words.shape[0], -1)


def control_rows(points, rows, eps: float, metric: str) -> RowSets:
    """The sampled rows' neighbour sets as the control computes them, on
    JAX's default device, a block of rows at a time."""
    import jax
    import jax.numpy as jnp

    bf16, f32 = jnp.bfloat16, jnp.float32

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    if metric == "euclidean":
        x = jnp.asarray(points, f32)
        sq = jnp.sum(x * x, axis=1)
        hi32 = _round_to_bf16(x)
        hi = hi32.astype(bf16)                              # exact
        lo = _round_to_bf16(x - hi32).astype(bf16)          # exact
        thr = np.float32(eps) * np.float32(eps)

        @jax.jit
        def block(r):
            prod = dot(hi[r], hi) + dot(hi[r], lo) + dot(lo[r], hi)
            d2 = sq[r][:, None] + sq[None, :] - 2.0 * prod
            return d2 <= thr
    elif metric == "hamming":
        bits = _unpack_bits(jnp.asarray(points, jnp.uint32)).astype(bf16)
        ones = jnp.sum(bits.astype(f32), axis=1)

        @jax.jit
        def block(r):
            same = _round_to_bf16(dot(bits[r], bits))
            return ones[r][:, None] + ones[None, :] - 2.0 * same <= eps
    else:
        raise ValueError(f"no control for metric {metric!r}")

    rows = np.asarray(rows)
    sets = []
    for lo_ in range(0, len(rows), BLOCK):
        r = rows[lo_:lo_ + BLOCK]
        pad = np.concatenate([r, np.full(BLOCK - len(r), r[0])])
        hit = np.array(block(jnp.asarray(pad)))[:len(r)]
        hit[np.arange(len(r)), r] = False
        sets.append(RowSets.from_mask(hit))
    off = np.cumsum([0] + [s.ptr[-1] for s in sets[:-1]])
    ptr = np.concatenate([[0]] + [s.ptr[1:] + o for s, o in zip(sets, off)])
    return RowSets(ptr, np.concatenate([s.ids for s in sets]))


def control_reading(points, rows, eps: float, metric: str) -> float:
    """The control's reading of the number it is meant to fail."""
    [res] = compare_rows([control_rows(points, rows, eps, metric)], points,
                         rows, eps, metric)
    return res[CONTROL_NUMBER[metric]]


def main(argv=None) -> int:
    from .harness import SAMPLE_ROWS, load_cell
    from .data import run_inputs
    from .reference import check_graphs

    ap = argparse.ArgumentParser(description="program and control readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)

    from .harness import CHECKOUT
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    from jax.sharding import Mesh

    from repro.launch.cache import enable_compile_cache
    from repro.nng import build_nng

    enable_compile_cache()
    cell = load_cell(args.workload)
    cfg, params = cell.config, cell.params
    metric, eps = cfg["metric"], params["eps"]
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:cell.chips]), ("ring",))
    name = CONTROL_NUMBER[metric]
    program, control = [], []
    for k, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        pts, rows = run_inputs(cfg, seed, SAMPLE_ROWS)
        g = build_nng(pts, eps, metric=metric,
                      partition=cell.traffic["partition"],
                      traversal=cell.traffic["traversal"], mesh=mesh,
                      k_cap=params["k_cap"])
        [r] = check_graphs([(g.row_ptr, g.col_ids)], pts, rows, eps, metric)
        line = {"seed": seed, "program": r, "edges": g.num_edges,
                "max_degree": int(np.diff(g.row_ptr).max()),
                "k_cap": g.meta.get("plan")}
        program.append(r[name])
        del g
        if k < args.control_seeds:
            c = control_reading(pts, rows, eps, metric)
            line["control"] = c
            control.append(c)
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line, default=str), flush=True)
    print(json.dumps({"workload": args.workload, "number": name,
                      "lower": max(program), "upper": min(control),
                      "device": devs[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
