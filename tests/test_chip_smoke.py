"""``chip_smoke.py`` off the chip.

Its phases run here at a tiny size on the CPU (the Pallas interpreter
stands in for the compiled kernels; the guarded TPU checks of ``main`` are
bypassed by calling the phases directly), its four-chip phase on four host
devices, and ``main`` itself must refuse a CPU backend and a directory
without the repo.
"""
from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

from tests.helpers import run_subprocess

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"
# tiny stand-ins of the sift and word2bits sets: same widths and
# generator, eps raised so the 512-point sets still have edges
TINY_SIFT = dict(n=512, dim=128, metric="euclidean", eps=4.0, k_cap=64)
TINY_WORD2BITS = dict(n=512, dim=25, metric="hamming", eps=44, k_cap=64)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu_backend():
    r = _run_smoke(ROOT, SMOKE)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_one_chip_phase(monkeypatch, capsys):
    import json

    from repro.launch.mesh import make_ring_mesh
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    smoke = _load_smoke()
    with smoke.CompileClock() as clock:
        smoke.run_one_chip(make_ring_mesh(1), clock, sift=TINY_SIFT,
                           word2bits=TINY_WORD2BITS)
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert [(d["phase"], d["partition"], d["traversal"]) for d in lines] == [
        ("sift", "point", "tiles"), ("sift", "point", "tree"),
        ("sift", "spatial", "tiles"), ("sift", "spatial", "tree"),
        ("word2bits", "point", "tiles")]
    assert len({d["edges"] for d in lines[:4]}) == 1
    assert all(d["edges"] > 0 and d["sample_rows"] == 512 for d in lines)
    assert clock.total > 0


def test_chip_smoke_oracle_check_catches_a_wrong_edge():
    import numpy as np
    import pytest

    from repro.core.graph import NNGraph
    from repro.data import synthetic_pointset
    smoke = _load_smoke()
    pts = synthetic_pointset(64, 25, "hamming", seed=0)
    rows = smoke.sample_rows(64)
    dist = smoke.oracle_distances(pts, rows, "hamming")
    far = np.unravel_index(np.argmax(dist), dist.shape)
    g = NNGraph.from_directed_pairs(64, np.array([rows[far[0]]]),
                                    np.array([far[1]]))
    with pytest.raises(smoke.SmokeFailure, match="differ from the float64"):
        smoke.check_graph(g, pts, 44, "hamming", rows, dist)


_CROSS_CHIP_CODE = f"""
import importlib.util, os
os.environ["REPRO_PALLAS"] = "interpret"
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SMOKE)!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.launch.mesh import make_ring_mesh
with smoke.CompileClock() as clock:
    smoke.run_cross_chip(make_ring_mesh(4), make_ring_mesh(1), clock,
                         sift={TINY_SIFT!r})
print("CROSS_CHIP_OK")
"""


def test_chip_smoke_cross_chip_phase_4dev():
    out = run_subprocess(_CROSS_CHIP_CODE, devices=4, timeout=900)
    assert "CROSS_CHIP_OK" in out
    assert out.count('"phase": "sift-4dev"') == 6
