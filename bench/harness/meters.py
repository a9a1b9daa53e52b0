"""Compile meter, copied from ``chip_smoke.py`` so the yardstick stays
with the benchmark: counts XLA backend compiles and their seconds, and
persistent-cache hits and misses, while active (``jax.monitoring``)."""
from __future__ import annotations


class CompileMeter:
    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
