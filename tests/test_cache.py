"""``repro.launch.cache.enable_compile_cache``: one fixed cache directory."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_CODE = """
import jax
from repro.launch.cache import DEFAULT_DIR, enable_compile_cache
got = enable_compile_cache()
assert got == jax.config.jax_compilation_cache_dir, (got, jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
print("DIR", got, "DEFAULT", DEFAULT_DIR)
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CODE], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    line = next(s for s in r.stdout.splitlines() if s.startswith("DIR "))
    _, got, _, default = line.split()
    return got, default


def test_compile_cache_uses_the_env_dir(tmp_path):
    cache = tmp_path / "cache"
    got, default = _run(cache)
    assert got == str(cache)
    assert any(cache.iterdir())          # the compile was written there


def test_compile_cache_default_is_fixed_in_checkout():
    got, default = _run(None)
    assert got == default == str(ROOT / ".jax_cache")
    assert _run(None)[0] == got          # the same path on every run
