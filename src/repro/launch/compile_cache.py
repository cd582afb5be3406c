"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``, the
benchmarks) call :func:`use_compile_cache` before their first compile;
importing the package never touches it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed path inside the checkout (gitignored): a cache that moves never hits
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    it itself) and nothing else is configured; otherwise the cache lives
    at ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
