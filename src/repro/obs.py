"""Spans and per-build counters of a build's host side.

``span(name)`` marks one host phase twice: as a
``jax.profiler.TraceAnnotation``, so a profile shows it beside the chip's
operations on the device trace's clock (a no-op when no profiler runs),
and as a ``(name, parent, start_s, end_s)`` record (``time.perf_counter``)
in the recorder of the build in progress. ``count(name, value)`` adds to a
counter of that recorder. ``build_nng`` and ``delta_run`` open the
recorder (``recording``) and copy it into the ``RunStats`` they return;
with none open, a span still times itself and a count goes nowhere.

Spans are host-side only: inside a traced or jitted function one would
fire once, at trace time. Backend compiles (loads from the persistent
compile cache included) are counted into the active recorder by one
``jax.monitoring`` listener, registered when this module is imported.
"""
from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_recorder", default=None)


class Recorder:
    """The spans and counters of one build."""

    def __init__(self):
        self.spans: list[tuple[str, str | None, float, float]] = []
        self.counts: dict[str, float] = {}
        self.open: list[str] = []       # names of the spans open now

    def into(self, stats) -> None:
        """Copy the counters and the span list onto a ``RunStats``."""
        for name, value in self.counts.items():
            setattr(stats, name, value)
        stats.spans = self.spans


@contextmanager
def recording():
    """Make a fresh ``Recorder`` the active one for the ``with`` body."""
    rec = Recorder()
    token = _ACTIVE.set(rec)
    try:
        yield rec
    finally:
        _ACTIVE.reset(token)


def count(name: str, value: float = 1) -> None:
    rec = _ACTIVE.get()
    if rec is not None:
        rec.counts[name] = rec.counts.get(name, 0) + value


class span:
    """``with span("nng.fetch") as s: ...``; ``s.seconds`` after it."""

    __slots__ = ("name", "start_s", "end_s", "_ann", "_rec", "_parent")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._rec = rec = _ACTIVE.get()
        self._parent = rec.open[-1] if rec is not None and rec.open else None
        if rec is not None:
            rec.open.append(self.name)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_s = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end_s = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        if rec is not None:
            rec.open.pop()
            rec.spans.append((self.name, self._parent, self.start_s,
                              self.end_s))

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


def totals(spans) -> dict[str, float]:
    """Seconds per span name, summed over repeats, in order of first
    close (children before their parent)."""
    out: dict[str, float] = {}
    for name, _, start_s, end_s in spans:
        out[name] = out.get(name, 0.0) + end_s - start_s
    return out


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        count("compiles")
        count("compile_s", duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
