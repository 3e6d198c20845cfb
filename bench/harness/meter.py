"""Compile accounting from JAX's own monitoring events (a persistent-cache
hit counts at its retrieval time)."""
from __future__ import annotations


class CompileMeter:
    """Sums backend-compile seconds and counts compiles and cache hits and
    misses."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, int, int, int]:
        return self.secs, self.compiles, self.hits, self.misses

    def since(self, snap) -> dict:
        s, c, h, m = snap
        return {"compile_s": self.secs - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}
