"""ring_exposed_ms: device milliseconds per build that a chip's operation
stream spent on the ring's ``collective-permute`` operations (their
``-start`` and ``-done`` halves, or a synchronous one): the time it spent
issuing and waiting on the ring's hops and the mirrors' hop home instead
of computing. The highest over the cell's chips; nothing where no chip
ran such an operation."""
from bench.ring import is_ring_hop


def read(run):
    tr = run.trace
    if tr is None or not run.stats:
        return None
    devs = tr.device_ids()[:run.cell.chips]
    if not any(tr.op_count(d, is_ring_hop) for d in devs):
        return None
    worst = max(tr.op_seconds(d, is_ring_hop) for d in devs)
    return 1e3 * worst / len(run.stats)
