"""Persistent XLA compilation cache for the entry points that run on a chip.

``enable_compile_cache()`` is called once, before the first compile, by
``chip_smoke.py``, ``repro.launch.serve_embed.main`` and
``benchmarks.serve_latency.main``:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no other path;
* otherwise the cache lives at the fixed ``<repo>/.jax_cache``. The path is
  part of the cache key, so it never depends on a temp dir, pid or time.

Every compile is cached, however short: the kernels compile in well under
the default one-second floor, and those are the compiles a cold chip run
repeats most.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
