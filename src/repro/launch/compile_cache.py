"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call ``enable_compile_cache`` once at start-up; nothing
calls it at import. A compiled program is found again only under the same
cache directory, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads that variable itself), else ``.jax_cache`` at the
root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
