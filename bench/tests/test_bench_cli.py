"""The entry point refuses a CPU backend and a directory without the
program, printing no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench.tests.util import CHECKOUT

ARGS = ["--workload", "sift-sparse-point-tiles", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_cpu_backend():
    r = _run(CHECKOUT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no TPU" in r.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_unknown_workload_exits_non_zero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=CHECKOUT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0 and '"correct"' not in r.stdout
