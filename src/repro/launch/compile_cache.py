"""Where the entry points keep JAX's persistent compilation cache.

``chip_smoke.py``, ``repro.launch.train`` and the benchmarks call
:func:`enable_compile_cache` once at start-up; importing the package never
touches the cache (the tests' described-topology compiles must stay off
it). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Otherwise the cache sits at one fixed path in
the checkout, ``<repo>/.jax_cache`` (gitignored) — a fixed path, because
the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
