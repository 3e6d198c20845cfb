"""Persistent XLA compilation cache for the program's entry points, and
compile accounting by program name.

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

:class:`CompileMeter` reads JAX's own monitoring events.  Every engine step
is jitted under its own name (``decode_packed``, ``prefill_chunk``, ...),
so the ``fun_name`` a backend compile carries (``jit(decode_packed)``)
says which step compiled.
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


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Backend-compile seconds and counts per program name, and
    persistent-cache hits and misses.  A cache hit passes through the
    backend-compile event too, timed at its retrieval."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        self.by_name: dict[str, list] = {}   # fun_name -> [compiles, secs]

        def on_duration(event, duration, fun_name="?", **kw):
            if event == BACKEND_COMPILE_EVENT:
                self.secs += duration
                got = self.by_name.setdefault(fun_name, [0, 0.0])
                got[0] += 1
                got[1] += duration

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return (self.secs, self.hits, self.misses,
                {k: tuple(v) for k, v in self.by_name.items()})

    def programs_since(self, snap) -> dict[str, tuple[int, float]]:
        """``{fun_name: (compiles, seconds)}`` since ``snap``, the longest
        first."""
        before = snap[3]
        got = {}
        for name, (n, secs) in self.by_name.items():
            n0, s0 = before.get(name, (0, 0.0))
            if n > n0:
                got[name] = (n - n0, secs - s0)
        return dict(sorted(got.items(), key=lambda kv: -kv[1][1]))

    def since(self, snap) -> str:
        s, h, m, _ = snap
        progs = ", ".join(f"{name} {n} x {secs:.2f} s" for name, (n, secs)
                          in self.programs_since(snap).items())
        return (f"compile {self.secs - s:.2f} s (cache hits {self.hits - h}, "
                f"misses {self.misses - m})" + (f": {progs}" if progs else ""))


__all__ = ["enable_compile_cache", "REPO_CACHE_DIR", "CompileMeter"]
