"""Configurations, cells and metrics are found by name from their files,
and a new cell or metric is added by adding files only."""
from __future__ import annotations

import json
import shutil

import pytest

from bench.harness import BenchError, load_cell, load_reader
from bench.tests.util import CHECKOUT, run_tiny, tiny_cell

BENCH_JSON = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = load_cell(name)
    w = next(w for w in BENCH_JSON["workloads"] if w["name"] == name)
    assert cell.chips == w["chips"]
    assert cell.config["name"] == w["config"]
    assert cell.traffic["partition"] == "point"
    assert {"eps", "k_cap", "limits"} <= set(cell.params)
    assert {m["name"] for m in cell.end_to_end} >= {"graph_s", "setup_s"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(load_reader(cell, m["name"]))


def test_config_files_match_benchmark():
    for c in BENCH_JSON["configs"]:
        cfg = json.loads((CHECKOUT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
        assert changed == set(c["reduced"])


def test_per_layer_metrics_name_their_cells():
    reported = {m["name"] for m in BENCH_JSON["end_to_end"]}
    for m in BENCH_JSON["per_layer"]:
        assert m["moves"] in reported
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert "tile_roofline_pct" not in {
        m["name"] for m in load_cell("w2b-sparse-point-tiles").per_layer}
    assert "device_idle_pct" in {
        m["name"] for m in load_cell("w2b-sparse-point-tiles").per_layer}


def test_unknown_cell_is_an_error():
    with pytest.raises(BenchError):
        load_cell("no-such-cell")


def test_new_cell_and_metric_by_adding_files(tmp_path):
    """A copy of the benchmark gains a traffic mix, a cell and a per-layer
    metric by new files and new entries only, and a run reports it."""
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "mid-point-tiles.json").write_text(
        json.dumps({"partition": "point", "traversal": "tiles",
                    "regime": "mid", "target_mean_degree": 200}))
    (tmp_path / "bench" / "cells" / "sift-mid-point-tiles.json").write_text(
        json.dumps({"eps": 3.8, "k_cap": 256, "limits": {
            "csr_faults": 0, "asym_pairs": 0, "max_gap_ulp": 30}}))
    (tmp_path / "bench" / "metrics" / "edges_per_build.py").write_text(
        "def read(run):\n    return float(len(run.stats))\n")
    bench["workloads"].append({
        "name": "sift-mid-point-tiles", "config": "sift-128d-1chip",
        "traffic": "mid-point-tiles", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "edges_per_build", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "graph_s",
        "workloads": ["sift-mid-point-tiles"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = tiny_cell("sift-mid-point-tiles",
                     bench_file=tmp_path / "BENCHMARK.json")
    assert cell.params["eps"] == 3.8
    assert "edges_per_build" in {m["name"] for m in cell.per_layer}
    result, counters = run_tiny(cell, trace=True)
    assert result["correct"], result["checks"]
    assert result["metrics"]["edges_per_build"]["value"] >= 1.0
    assert counters["window_compiles"] == 0
