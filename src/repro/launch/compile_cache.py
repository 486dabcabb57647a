"""Persistent XLA compilation cache for the entry points.

Called by `chip_smoke.py`, `launch.serve_genomics.main` and
`benchmarks/run.py` — never at library import, so importing `repro`
changes no JAX setting.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and no other directory is set; otherwise the cache
lives at a fixed ``<checkout>/.jax_cache`` (the path is part of the
cache key, so it must not move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
