"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``) call
``use_compile_cache()`` once, before their first compile; importing a
module never touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it on its own and nothing is set in code. Otherwise the
cache lands at a fixed ``.jax_cache`` inside the checkout (git-ignored),
so the next run from the same checkout looks where this one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
