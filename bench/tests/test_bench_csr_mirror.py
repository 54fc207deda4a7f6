"""The reader of ``csr_mirror_added``: the mirror entries the row-table
CSR path added per build, on synthetic counters, on a program without
the counter, and in a traced tiny run of each cell on the CPU."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench.harness import load_cell, load_reader
from bench.tests.util import run_tiny, tiny_cell

CELLS = ["sift-sparse-point-tiles", "w2b-sparse-point-tiles",
         "sift-dense-point-tiles"]


def _read(run):
    return load_reader(load_cell(CELLS[0]), "csr_mirror_added")(run)


def test_reader_takes_the_mean_per_build():
    stats = [SimpleNamespace(csr_mirror_added=3),
             SimpleNamespace(csr_mirror_added=0)]
    assert _read(SimpleNamespace(stats=stats, trace=None)) == 1.5
    assert _read(SimpleNamespace(stats=[], trace=None)) is None


def test_reader_reads_nothing_on_a_program_without_the_counter():
    parent = SimpleNamespace(engine_calls=2, pairs_selected=30,
                             table_slots=400)
    assert _read(SimpleNamespace(stats=[parent] * 2, trace=None)) is None


@pytest.mark.parametrize("name", CELLS)
def test_traced_tiny_run_reads_the_mirror_count(name):
    result, counters = run_tiny(tiny_cell(name), trace=True)
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["csr_mirror_added"] >= 0
    if name.startswith("w2b"):      # Hamming distances are symmetric
        assert got["csr_mirror_added"] == 0
    assert got["host_csr_ms"] > 0
