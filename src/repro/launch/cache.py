"""The persistent compile cache, configured once per entry point.

A cache whose directory moves between runs is never found again, so the
directory is fixed: never a temp name, a pid or a time. Every entry point
(``nng_run.main``, ``chip_smoke.py``, ``bench/harness.py``,
``bench/control.py``) calls ``enable_compile_cache()`` before its first
compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored): src/repro/launch/cache.py -> checkout
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and nothing else is set. Otherwise the cache lives at the fixed
    in-checkout ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
