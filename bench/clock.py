"""Compilations counted from JAX's monitoring events (copied from the
repo's chip smoke run)."""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Counts backend compilations, their seconds, and persistent-cache
    hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._hit)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _hit(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits
