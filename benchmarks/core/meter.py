"""Compile seconds and persistent-cache traffic of this process, from JAX's
own monitoring events. Copied from ``chip_smoke.py`` (PR 21): backend compile
time covers the cache lookup, so a warm cache shows as hits and a small
number."""

from __future__ import annotations


class CompileMeter:
    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def row(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses, "compiles": self.compiles}
