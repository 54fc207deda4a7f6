"""ring_busy_spread_pct: how unevenly the ring's chips were kept busy
computing in the traced window, 100 x (busiest chip's compute time - least
busy chip's) / busiest chip's. A chip's compute time is the union of its
operation intervals other than the ring's ``collective-permute`` hops: a
chip blocked in a hop, waiting for its peer, is not computing. The halving
round of an even ring evaluates one side of each pair only, so its lower
ranks run more tiles. Nothing on fewer than two chips."""
from bench.ring import is_ring_hop
from bench.trace import covered


def read(run):
    tr = run.trace
    if tr is None:
        return None
    busy = [covered(tr.intervals(d, lambda name: not is_ring_hop(name)))
            for d in tr.device_ids()[:run.cell.chips]]
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
