"""The reader of ``epilogue_scan_pct``: the share of the bitmask
epilogue's (slot, chunk) pairs scanned per build, on recorded counters,
on a program without the counter, and in a traced tiny run on the CPU."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench.harness import load_cell, load_reader
from bench.tests.util import run_tiny, tiny_cell


def _read(run):
    return load_reader(load_cell("sift-sparse-point-tiles"),
                       "epilogue_scan_pct")(run)


def test_reader_takes_the_mean_per_build():
    stats = [SimpleNamespace(epilogue_scan_pct=30.0),
             SimpleNamespace(epilogue_scan_pct=26.0)]
    assert _read(SimpleNamespace(stats=stats, trace=None)) == 28.0
    assert _read(SimpleNamespace(stats=[], trace=None)) is None


@pytest.mark.parametrize("stats", [
    [SimpleNamespace(engine_calls=2, ring_bytes=0.0)] * 2,
    [SimpleNamespace(epilogue_scan_pct=None)],
], ids=["parent", "not-counted"])
def test_reader_reads_nothing_where_the_program_does_not_count(stats):
    assert _read(SimpleNamespace(stats=stats, trace=None)) is None


def test_traced_tiny_run_reads_the_scan_share():
    result, _ = run_tiny(tiny_cell("sift-sparse-point-tiles"), trace=True)
    assert result["correct"], result["checks"]
    got = result["metrics"]["epilogue_scan_pct"]
    assert got["unit"] == "%" and 0 < got["value"] <= 100
