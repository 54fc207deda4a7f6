"""Shared pieces of the benchmark's tests: paths, tiny cells, a harness
run on the CPU."""
from __future__ import annotations

import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import load_cell, run  # noqa: E402



def tiny_cell(name: str, n: int | None = None, **load):
    """The cell ``name`` cut to a size a CPU test holds; every other
    setting as committed."""
    cell = load_cell(name, **load)
    cell.config = dict(cell.config, n=n or 512)
    return cell


def run_tiny(cell, *, seed: int = 2**31 + 11, trace: bool = False,
             build=None, seconds: float = 0.0):
    """One harness run on the CPU, the chip check skipped."""
    return run(cell, seed, seconds, trace, t_start=time.perf_counter(),
               require_tpu=False, build=build)
