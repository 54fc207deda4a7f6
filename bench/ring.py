"""How the traced window names the ring's hops.

The point partition's ring moves its blocks, their mirror accumulators
and the final return home with ``ppermute``, which the chip runs as
``collective-permute`` operations: an asynchronous ``-start`` / ``-done``
pair, or one synchronous operation. A chip's operation is named by its HLO
text, ``%<name> = <shape> <opcode>(<operands>), ...``; this matches on the
opcode, as ``kernels.py`` matches the kernels' custom calls.
"""
from __future__ import annotations

import re

# the opcode: the first `` <opcode>(`` after `` = ``
_OPCODE = re.compile(r"^%\S+ = .*? ([a-z][a-z0-9-]*)\(")
_RING = {"collective-permute", "collective-permute-start",
         "collective-permute-done"}


def is_ring_hop(name: str) -> bool:
    """A ``collective-permute`` operation, asynchronous or not."""
    m = _OPCODE.match(name)
    return bool(m) and m.group(1) in _RING
