"""Backend compiles and persistent-cache hits, from JAX's own monitoring
events (a cache hit still reports a backend compile event)."""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == _HIT:
            self.hits += 1
