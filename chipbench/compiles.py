"""XLA compilations and persistent-cache lookups, as ``jax.monitoring``
reports them (the counter of the simulator's smoke check, kept here so the
benchmark's own count cannot change with the program)."""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class Compiles:
    def __init__(self, jax):
        self.count = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HITS:
            self.hits += 1
        elif event == CACHE_MISSES:
            self.misses += 1

    def mark(self):
        return self.count, self.seconds, self.hits, self.misses

    def since(self, mark) -> dict:
        count, seconds, hits, misses = (
            now - then for now, then in zip(self.mark(), mark))
        return {"xla_compiles": count, "compile_s": seconds,
                "cache_hits": hits, "cache_misses": misses}
