"""The readers of the program's own spans and counters: ``host_csr_ms``,
``table_fetch_ms``, ``unattributed_idle_ms``, ``engine_calls_per_build``
and ``table_fill_pct``, on synthetic traces, on the recorded trace of a
program without spans, and in a traced tiny run on the CPU."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import load_cell, load_reader
from bench.tests.util import run_tiny, tiny_cell
from bench.trace import (BUILD_SPAN, HOST_PLANE, OPS_LINE, WINDOW_SPAN,
                         Event, Trace, breakdown, from_json)

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "sift_sparse_2builds.json.gz"
# two sift-width builds (2^17 x 128, eps 3.3, k_cap 640) of the program
# with its spans, recorded on a TPU v5 lite: the device operations, the
# harness's and the program's spans, host events of 0.2 ms and longer
RECORDED_SPANS = DATA / "sift_sparse_2builds_spans.json.gz"
CELLS = ["sift-sparse-point-tiles", "w2b-sparse-point-tiles",
         "sift-dense-point-tiles"]
SPAN_METRICS = ["host_csr_ms", "table_fetch_ms", "unattributed_idle_ms"]
HOST_METRICS = ["host_csr_ms", "table_fetch_ms", "engine_calls_per_build",
                "table_fill_pct"]
LINE = "python3"


def _host(name, start, end):
    return Event(HOST_PLANE, LINE, name, float(start), float(end))


def _op(dev, start, end, name="%fusion.1 = f32[8] fusion()"):
    return Event(f"/device:TPU:{dev}", OPS_LINE, name, float(start),
                 float(end))


def _run(events, builds=1, chips=1, stats=None):
    """What a reader sees of a traced window from 1000 to 11000 ns."""
    events = [_host(WINDOW_SPAN, 1000, 11000)] + events
    cell = SimpleNamespace(name="synthetic", chips=chips)
    return SimpleNamespace(cell=cell, device_kind="TPU v5 lite",
                           stats=stats or [SimpleNamespace()] * builds,
                           trace=Trace(events))


def _read(metric, run):
    return load_reader(load_cell(CELLS[0]), metric)(run)


def test_spans_crossing_the_window_are_clipped():
    run = _run([_host("nng.csr", 500, 2000), _host("nng.csr", 10000, 12000),
                _host("nng.fetch", 0, 500), _host("nng.fetch", 4000, 4600),
                _host("$graph.py:241 from_neighbor_tables", 2000, 3000)],
               builds=2)
    assert _read("host_csr_ms", run) == pytest.approx(2000 / 2 * 1e-6)
    assert _read("table_fetch_ms", run) == pytest.approx(600 / 2 * 1e-6)


def test_idle_under_nested_spans_counts_once():
    # chip busy 1000-3000 and 9000-9500; nng.csr and its child overlap, and
    # a Python call named after nng.py is no span of the program
    run = _run([_op(0, 1000, 3000), _op(0, 9000, 9500),
                _host("nng.csr", 3000, 6000),
                _host("nng.csr.sort", 4000, 5000),
                _host("nng.fetch", 5500, 7000),
                _host("$nng.py:88 drive", 7000, 11000),
                _host("nng.py:88 drive", 7000, 11000)])
    # idle 3000-9000 and 9500-11000; named 3000-7000
    assert _read("unattributed_idle_ms", run) == pytest.approx(3500 * 1e-6)


def test_unattributed_idle_takes_the_idlest_chip():
    events = [_op(0, 1000, 11000), _op(1, 1000, 2000),
              _host("nng.run", 2000, 8000), _host("nng.wait", 2500, 7000)]
    two = _run(events, builds=3, chips=2)
    assert _read("unattributed_idle_ms", two) == pytest.approx(
        3000 / 3 * 1e-6)
    assert _read("unattributed_idle_ms", _run(events, chips=1)) == 0.0


def test_readers_read_nothing_without_the_programs_spans():
    run = _run([_op(0, 1000, 2000),
                _host("$graph.py:241 from_neighbor_tables", 2000, 9000)])
    for metric in SPAN_METRICS:
        assert _read(metric, run) is None, metric
    spans_only = _run([_host("nng.csr", 2000, 9000)])  # no device plane
    assert _read("unattributed_idle_ms", spans_only) is None
    untraced = SimpleNamespace(trace=None, stats=[SimpleNamespace()],
                               cell=spans_only.cell)
    for metric in SPAN_METRICS:
        assert _read(metric, untraced) is None, metric


def test_readers_read_nothing_on_a_program_without_spans():
    """The trace recorded from the program before it had spans, and the
    counters it reported then."""
    parent = SimpleNamespace(elapsed_s=0.94, replans=0, build_s=0.0)
    run = SimpleNamespace(cell=load_cell(CELLS[0]),
                          device_kind="TPU v5 lite", stats=[parent] * 2,
                          trace=Trace(from_json(RECORDED)))
    for metric in SPAN_METRICS + ["engine_calls_per_build",
                                  "table_fill_pct"]:
        assert _read(metric, run) is None, metric


@pytest.fixture(scope="module")
def recorded_spans():
    events = from_json(RECORDED_SPANS)
    run = SimpleNamespace(cell=load_cell(CELLS[0]),
                          device_kind="TPU v5 lite", stats=[1, 2],
                          trace=Trace(events))
    return events, run


def _span_ms(events, name):
    return sum(e.end_ns - e.start_ns for e in events
               if e.plane == HOST_PLANE and e.name == name) * 1e-6


def test_recorded_spans_account_for_the_host_gap(recorded_spans):
    events, run = recorded_spans
    tr = run.trace
    csr = _read("host_csr_ms", run)
    fetch = _read("table_fetch_ms", run)
    assert csr == pytest.approx(_span_ms(events, "nng.csr") / 2)
    assert fetch == pytest.approx(_span_ms(events, "nng.fetch") / 2)
    assert 2000 < csr < 3500 and 20 < fetch < 500
    # the longest idle gap of each build lies inside the fetch and the CSR
    gaps = breakdown(tr, 1)["idle_gaps"]
    assert [label for label, _ in gaps[:2]] == ["nng.csr > nng.csr.sort"] * 2
    assert csr + fetch >= 0.9 * 1e3 * gaps[1][1]
    # sorting the keys is the largest part of the CSR assembly
    parts = {n: _span_ms(events, n) for n in
             ("nng.csr.select", "nng.csr.sort", "nng.csr.rows")}
    assert max(parts, key=parts.get) == "nng.csr.sort"
    assert sum(parts.values()) <= _span_ms(events, "nng.csr")


def test_recorded_spans_leave_little_idle_unnamed(recorded_spans):
    _, run = recorded_spans
    per_build_ms = 1e3 * run.trace.window_s / 2
    got = _read("unattributed_idle_ms", run)
    assert 0 <= got < 0.05 * per_build_ms


def test_counter_readers():
    stats = [SimpleNamespace(engine_calls=2, pairs_selected=30,
                             table_slots=400),
             SimpleNamespace(engine_calls=3, pairs_selected=10,
                             table_slots=400)]
    run = SimpleNamespace(stats=stats, trace=None)
    assert _read("engine_calls_per_build", run) == 2.5
    assert _read("table_fill_pct", run) == pytest.approx(5.0)
    empty = SimpleNamespace(stats=[], trace=None)
    assert _read("engine_calls_per_build", empty) is None
    assert _read("table_fill_pct", empty) is None


def test_idle_gaps_are_named_after_the_programs_spans():
    run = _run([_host(BUILD_SPAN, 1000, 11000),
                _host("$nng.py:527 build_nng", 1000, 11000),
                _op(0, 1000, 4000), _op(0, 4950, 5050),
                _host("nng.run", 1500, 4200),
                _host("nng.fetch", 4200, 5000),
                _host("$array.py:436 __array__", 4300, 4900),
                _host("nng.csr", 5000, 11000),
                _host("$graph.py:241 from_neighbor_tables", 5001, 10999),
                _host("nng.csr.sort", 6000, 10000),
                _host("$_arraysetops_impl.py:144 unique", 6001, 9999)])
    gaps = breakdown(run.trace, 1)["idle_gaps"]
    assert [label for label, _ in gaps] == [
        "nng.csr > _arraysetops_impl.py:144 unique",
        "nng.fetch > array.py:436 __array__"]
    assert all(label.startswith("nng.") for label, _ in gaps)


@pytest.mark.parametrize("name", CELLS)
def test_traced_tiny_run_reads_the_host_metrics(name):
    """A CPU trace has no chip plane, so unattributed idle reads nothing."""
    result, counters = run_tiny(tiny_cell(name), trace=True)
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for metric in HOST_METRICS:
        assert got.get(metric) is not None, metric
    assert "unattributed_idle_ms" not in got
    assert got["engine_calls_per_build"] == 2.0
    assert got["host_csr_ms"] > 0 and got["table_fetch_ms"] > 0
    slots = 512 * counters["k_cap"]
    assert got["table_fill_pct"] == pytest.approx(
        100 * 2 * counters["edges"] / slots)
