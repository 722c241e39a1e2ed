"""Compile counter: JAX's backend-compile and compile-cache events,
counted from the moment it is made (copied from the program's
``chip_smoke.Clock``)."""
from __future__ import annotations

import threading

# one event per executable: tracing and lowering nest (an outer jit traces
# its inner ones), so their durations are left out
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Clock:
    """Seconds compiling, backend compiles, and compile-cache hits and
    misses, since this object was made."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in (COMPILE_EVENT, CACHE_READ_EVENT):
            with self._lock:
                self.compile_s += duration
                self.compiles += event == COMPILE_EVENT

    def _event(self, event, **_):
        key = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}.get(event)
        if key:
            with self._lock:
                self.cache[key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    **self.cache}
