"""unattributed_idle_ms: milliseconds per build in which the chip was idle
and no ``nng.*`` span of the program was open on the host, in the traced
window; on several chips the highest."""
import re

from bench.trace import clip, covered, minus, union

PROGRAM_SPAN = re.compile(r"^nng\.[a-z_.]+$")


def read(run):
    tr = run.trace
    if tr is None or not run.stats or not tr.device_ids():
        return None
    spans = [(e.start_ns, e.end_ns) for e in tr.host
             if PROGRAM_SPAN.match(e.name)]
    if not spans:
        return None
    named = union(clip(spans, tr.lo, tr.hi))
    worst = max(covered(minus(minus([(tr.lo, tr.hi)], tr.intervals(d)),
                              named))
                for d in tr.device_ids()[:run.cell.chips])
    return 1e-6 * worst / len(run.stats)
