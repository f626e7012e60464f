"""Compile accounting from jax's own monitoring events (copied from
``chip_smoke.CompileMeter``): seconds the backend spent compiling or
loading cached executables, the persistent cache's hits and misses, and
a mark/since pair so that compilations inside the measured window can be
counted (there should be none)."""

from __future__ import annotations


class CompileMeter:
    def __init__(self):
        import jax

        self.backend_seconds = 0.0
        self.retrieval_seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.by_function: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, fun_name: str = "?",
                  **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_seconds += seconds
            self.programs += 1
            self.by_function[fun_name] = (
                self.by_function.get(fun_name, 0.0) + seconds)
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieval_seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self) -> dict:
        return {"compile_s": self.backend_seconds - self.retrieval_seconds,
                "cache_load_s": self.retrieval_seconds,
                "programs": self.programs, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "slowest": dict(sorted(self.by_function.items(),
                                       key=lambda kv: -kv[1])[:5])}
