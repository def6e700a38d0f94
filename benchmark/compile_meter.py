"""Backend-compile seconds and persistent-cache hits and misses, from the
events JAX itself records (a copy of ``chip_smoke.CompileMeter``)."""

import functools


class CompileMeter:
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self._COMPILE:
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1


@functools.cache
def meter() -> CompileMeter:
    """The process's one meter: jax.monitoring listeners cannot be removed,
    so a second registration would count every event twice."""
    return CompileMeter()
