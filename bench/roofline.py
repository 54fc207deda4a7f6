"""Peaks of the chip and the work of one kernel call, for roofline shares.

``peaks`` reads ``peaks.json``, keyed by the ``device_kind`` JAX reports;
a chip that is not in the table is an error. ``l2_tile_cost`` counts the
operations and bytes that one call of the fused L2 tile kernel needs.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: known {sorted(table)}")
    return table[device_kind]


def l2_tile_cost(q: int, p: int, d: int) -> tuple[float, float]:
    """(operations, bytes) of one fused L2 tile call over q x p pairs of
    width d: the 2qpd multiply-adds of the distance contraction, and the
    least traffic, which reads both fp32 operands once and writes the
    packed q x p/32 bitmask and the q int32 counts once. The threshold
    and the bit packing are VPU work the MXU peak does not count."""
    ops = 2.0 * q * p * d
    moved = 4.0 * (q + p) * d + q * p / 8.0 + 4.0 * q
    return ops, moved


def least_seconds(ops: float, moved: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = moved / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
