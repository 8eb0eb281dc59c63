"""Compile seconds, compile events and persistent-cache hits/misses from
JAX's own monitoring events (copied from chip_smoke.py's CompileWatch; no
code of the engine is on this path).

`/jax/core/compile/backend_compile_duration` fires once for every program
that is compiled OR loaded from the persistent cache, so its count over the
measured window is the number of programs that were not yet in memory
there: it must read 0."""

from __future__ import annotations


class CompileWatch:
    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": self.compile_s,
            "compiles": self.compiles,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
