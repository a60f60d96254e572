"""Compile seconds and persistent-cache hits from JAX's own events."""
from __future__ import annotations


class CompileMeter:
    """Counts backend compiles (and their seconds) and persistent-cache
    hits and misses; a cache hit replaces a compile by a read."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
