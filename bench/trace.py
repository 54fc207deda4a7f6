"""From a profiler trace to the intervals the per-layer readers use.

A traced run writes JAX's profiler trace of the measured window. ``load``
turns its ``.xplane.pb`` into plain events, ``Trace`` groups them: the
device operations of each chip and the host's spans, on one clock. The
readers under ``metrics/`` take their numbers from a ``Trace``, and
``breakdown`` names the operations that took most time and what the host
was doing in the longest idle gaps. ``save`` and ``from_json`` keep a
trace's events as JSON, so the reduction can be checked on a recorded
trace.
"""
from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"              # the operations each chip runs
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
BUILD_SPAN = "bench.build"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load(log_dir) -> list[Event]:
    """Every event of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"want one trace file under {log_dir}, found "
                           f"{[str(f) for f in files]}")
    data = ProfileData.from_file(str(files[0]))
    out = []
    for plane in data.planes:
        keep = DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE
        if not keep:
            continue
        for line in plane.lines:
            if plane.name != HOST_PLANE and line.name != OPS_LINE:
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.end_ns)))
    return out


def save(events: list[Event], path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def from_json(path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, ascending."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[float, float]]:
    """Parts of the disjoint intervals ``a`` that the disjoint ``b`` does
    not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    """A traced window: the operations each chip ran (``ops``), the host's
    spans on the thread that ran the window (``host``, the harness's spans
    and the Python calls under them), and the window's bounds, all on the
    trace's one clock."""

    def __init__(self, events: list[Event]):
        self.ops: dict[int, list[Event]] = {}
        win = [e for e in events
               if e.plane == HOST_PLANE and e.name == WINDOW_SPAN]
        if len(win) != 1:
            raise RuntimeError(f"want one {WINDOW_SPAN} span, found "
                               f"{len(win)}")
        self.lo, self.hi = win[0].start_ns, win[0].end_ns
        self.host = [e for e in events
                     if e.plane == HOST_PLANE and e.line == win[0].line]
        for e in events:
            m = DEVICE_PLANE.match(e.plane)
            if m:
                self.ops.setdefault(int(m.group(1)), []).append(e)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def device_ids(self) -> list[int]:
        return sorted(self.ops)

    def intervals(self, dev: int, match=None) -> list[tuple[float, float]]:
        """Disjoint intervals in the window in which an operation of chip
        ``dev`` ran whose name ``match`` accepts (any, by default)."""
        return clip(union((e.start_ns, e.end_ns) for e in self.ops[dev]
                          if match is None or match(e.name)),
                    self.lo, self.hi)

    def op_seconds(self, dev: int, match) -> float:
        """Summed durations of chip ``dev``'s matching operations in the
        window (overlapping operations count each)."""
        return sum(e - s for op in self.ops[dev] if match(op.name)
                   for s, e in clip([(op.start_ns, op.end_ns)],
                                    self.lo, self.hi)) * 1e-9

    def op_count(self, dev: int, match) -> int:
        return sum(1 for op in self.ops[dev] if match(op.name)
                   and op.end_ns > self.lo and op.start_ns < self.hi)

    def busy_s(self, dev: int) -> float:
        return covered(self.intervals(dev)) * 1e-9

    def host_label(self, t: float) -> str:
        """What the host was doing at ``t``: the call under ``build_nng``
        and the innermost call, from the Python spans that cover it."""
        chain = sorted((e for e in self.host if e.start_ns <= t <= e.end_ns),
                       key=lambda e: e.start_ns - e.end_ns)
        names = [e.name.lstrip("$") for e in chain]
        if not names:
            return "(no host span)"
        top = next((i for i, nm in enumerate(names)
                    if nm.endswith(" build_nng")), None)
        if top is None or top + 1 >= len(names):
            return names[-1]
        if top + 2 >= len(names):
            return names[top + 1]
        return f"{names[top + 1]} > {names[-1]}"


_HLO = re.compile(r"^(%\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """``%<name> <opcode> <result shape>`` of an HLO operation's text,
    layouts left out; other names as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:160]


def breakdown(tr: Trace, chips: int, top: int = 10) -> dict:
    """The device operations that took most time on the first ``chips``
    chips (seconds per chip, mean over them) and the longest idle gaps of
    the least busy one, each named by the host span that covers its
    middle."""
    devs = tr.device_ids()[:chips]
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    tot: dict[str, float] = {}
    for d in devs:
        for op in tr.ops[d]:
            for s, e in clip([(op.start_ns, op.end_ns)], tr.lo, tr.hi):
                key = short_name(op.name)
                tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9 / len(devs)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    idlest = min(devs, key=tr.busy_s)
    gaps = minus([(tr.lo, tr.hi)], tr.intervals(idlest))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[tr.host_label((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps[:top]]}
