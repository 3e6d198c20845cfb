"""Persistent XLA compilation cache for the program's entry points.

The engine compiles one program per prefill bucket, chunk bucket and
decode bucket; a process that starts cold pays for every one of them.
Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once at start-up so
a second process on the same machine reads those programs back.  Library
code and tests never call it.

Placement:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no directory of its own.
- otherwise: ``<repo>/.jax_cache`` — a fixed path (the path is part of
  what makes a later process find the entries), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, not only those that took over a second: the
    # serving loop's many small jits add up on a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]
