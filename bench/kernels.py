"""How the traced window names the program's kernels, and per-build sums
of their device time.

A chip's operations are named by their HLO text, ``%<name> = <shape>
<opcode>(<operands>), ...``. The program gives its kernels no stable
names yet, so these match what the trace shows today: the fused tile
kernel is the custom call named after its jitted wrapper
``_tile_padded_call``, the bitmask epilogue the one named after
``_bits_cols_padded``.
"""
from __future__ import annotations

import re

_TILE = re.compile(r"^%_tile_padded_call[.\d]* = ")
_EPILOGUE = re.compile(r"^%_bits_cols_padded[.\d]* = ")
# the L2 tile call: (s32[1,q], s32[p/32,q]) custom-call(f32[d,q] ...
_L2_TILE_SHAPES = re.compile(
    r"= \(s32\[1,(\d+)\]\S*, s32\[(\d+),\d+\]\S*\) "
    r"custom-call\(f32\[(\d+),\d+\]")


def is_tile(name: str) -> bool:
    return bool(_TILE.search(name))


def is_epilogue(name: str) -> bool:
    return bool(_EPILOGUE.search(name))


def l2_tile_shape(name: str) -> tuple[int, int, int] | None:
    """(q, p, d) of an L2 tile call, read from its operand and result
    shapes; None for any other operation."""
    m = _L2_TILE_SHAPES.search(name) if is_tile(name) else None
    return (int(m.group(1)), 32 * int(m.group(2)), int(m.group(3))) if m \
        else None


def per_build_ms(run, match) -> float | None:
    """Device milliseconds of the matching operations, per chip and per
    build; nothing when the trace has none."""
    tr = run.trace
    if tr is None or not run.stats:
        return None
    devs = tr.device_ids()[:run.cell.chips]
    if not any(tr.op_count(d, match) for d in devs):
        return None
    total = sum(tr.op_seconds(d, match) for d in devs)
    return 1e3 * total / len(devs) / len(run.stats)
