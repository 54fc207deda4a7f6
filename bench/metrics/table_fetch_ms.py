"""table_fetch_ms: host milliseconds per build in the program's
``nng.fetch`` spans (the device-to-host copy of the padded neighbour
tables), clipped to the traced window."""
from bench.trace import clip, covered

SPAN = "nng.fetch"


def read(run):
    tr = run.trace
    if tr is None or not run.stats:
        return None
    spans = [(e.start_ns, e.end_ns) for e in tr.host if e.name == SPAN]
    if not spans:
        return None
    return 1e-6 * covered(clip(spans, tr.lo, tr.hi)) / len(run.stats)
