"""Compile seconds and counts from JAX's monitoring events (the
``CompileClock`` of ``chip_smoke.py``, copied so the program cannot move
it)."""
from __future__ import annotations


class CompileClock:
    """Seconds and count of XLA backend compiles (persistent-cache reads
    included) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds, self.compiles, self.hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.compiles, self.hits
