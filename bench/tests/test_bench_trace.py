"""The reduction from a trace to the per-layer metrics, on a small trace
recorded on a TPU v5 lite: two sift-width builds (2^17 x 128, eps 3.3,
k_cap 640) under the harness's spans, the Python calls of the main
thread longer than 0.2 ms kept."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import load_cell, load_reader
from bench.kernels import is_epilogue, is_tile, l2_tile_shape
from bench.trace import (Trace, breakdown, covered, from_json, minus, save,
                         short_name, union)

RECORDED = Path(__file__).parent / "data" / "sift_sparse_2builds.json.gz"


@pytest.fixture(scope="module")
def recorded():
    events = from_json(RECORDED)
    cell = load_cell("sift-sparse-point-tiles")
    run = SimpleNamespace(cell=cell, device_kind="TPU v5 lite", stats=[1, 2],
                          trace=Trace(events))
    return events, run


def _read(run, metric):
    return load_reader(run.cell, metric)(run)


def _sum_ms(events, match):
    return sum(e.end_ns - e.start_ns for e in events
               if e.line == "XLA Ops" and match(e.name)) * 1e-6


def test_interval_arithmetic():
    assert union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert covered(minus([(0, 4), (6, 8)], [(1, 7)])) == 2


def test_kernel_times_per_build(recorded):
    events, run = recorded
    tile = _read(run, "tile_kernel_ms")
    epi = _read(run, "epilogue_ms")
    assert tile == pytest.approx(_sum_ms(events, is_tile) / 2)
    assert epi == pytest.approx(_sum_ms(events, is_epilogue) / 2)
    # four engine calls (two per build), one tile and one epilogue each
    assert sum(is_tile(e.name) for e in events) == 4
    assert 200 < tile < 400 and 1000 < epi < 1500


def test_tile_roofline(recorded):
    events, run = recorded
    tiles = [e for e in events if is_tile(e.name)]
    assert {l2_tile_shape(e.name) for e in tiles} == {(2**17, 2**17, 128)}
    least = 2 * 2**34 * 128 / 197e12          # compute-bound, per call
    spent = sum(e.end_ns - e.start_ns for e in tiles) * 1e-9
    got = _read(run, "tile_roofline_pct")
    assert got == pytest.approx(100 * 4 * least / spent)
    assert 0 < got < 100


def test_idle_share_and_busy_time(recorded):
    events, run = recorded
    tr = run.trace
    assert tr.window_s == pytest.approx(9.04110413)
    ops = union((e.start_ns, e.end_ns) for e in events
                if e.line == "XLA Ops")
    assert tr.busy_s(0) == pytest.approx(covered(ops) * 1e-9)
    idle = _read(run, "device_idle_pct")
    assert idle == pytest.approx(100 * (1 - tr.busy_s(0) / tr.window_s))
    assert 40 < idle < 80


def test_breakdown_names_ops_and_gaps(recorded):
    _, run = recorded
    b = breakdown(run.trace, 1)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("%_bits_cols_padded")
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert "from_neighbor_tables" in b["idle_gaps"][0][0]


def test_save_round_trip(recorded, tmp_path):
    events, _ = recorded
    save(events, tmp_path / "t.json.gz")
    assert from_json(tmp_path / "t.json.gz") == events


def test_short_name():
    name = ("%sort.6 = (s32[131072,1280]{0,1:T(8,128)}, s32[131072,1280]"
            "{0,1:T(8,128)}) sort(s32[131072,1280]{0,1:T(8,128)} %pad.2)")
    assert short_name(name) == ("%sort.6 sort (s32[131072,1280], "
                                "s32[131072,1280])")
    assert short_name("bench.build") == "bench.build"
