"""JAX's persistent compilation cache for this repo's entry points.

Called from `main()` of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at a fixed path inside the checkout,
so every process of the same checkout finds what an earlier one compiled
(the path is part of the cache key, so it must not move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — src/repro/launch/ is three levels down.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
