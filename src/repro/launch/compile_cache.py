"""Persistent XLA compilation cache for the repository's entry points.

Called by entry-point scripts (`chip_smoke.py`, `benchmarks/run.py`) before
their first compile — never on import and never from tests, which must not
share compiled state between runs.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this sets
nothing. Otherwise the cache goes to `<repo>/.jax_cache`: a fixed path,
because the directory is part of the cache key and a per-run path would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
