"""Compile accounting: how many programs XLA built (its backend-compile
event, which fires for a program read from the persistent compilation
cache too), how long that took, and how many of them came from the
cache. One listener per process, registered through `jax.monitoring`."""
from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}
