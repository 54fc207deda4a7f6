#!/usr/bin/env python3
"""Chip benchmark of the exact ε-graph build: one cell, one run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The cell, its configuration, traffic and metrics are found
by name from ``BENCHMARK.json`` (see ``harness.py``). The last line of
standard output is the result object; the line before it holds the
program's counters; the last lines of standard error give each number the
check compared, beside its limit. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from bench.harness import BenchError, emit, load_cell, run
    try:
        cell = load_cell(args.workload)
        result, counters = run(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result, counters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
