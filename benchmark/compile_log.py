"""What jax compiles, from jax's own monitoring events.

The arithmetic of ``chip_smoke.py::CompileLog`` (copied so the yardstick
does not move with the program): every backend compile request (a hit in
the persistent cache is still a request), the seconds spent tracing,
lowering and compiling, and the cache's hits and misses. Listeners fire on
whichever thread compiles.
"""

from __future__ import annotations

import threading

_TIMED = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileLog:
    def __init__(self, jax):
        self._lock = threading.Lock()
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **meta):
        if event not in _TIMED:
            return
        with self._lock:
            self.seconds += seconds
            if event.endswith("backend_compile_duration"):
                self.requests += 1
                self.names.append(str(meta.get("fun_name", "?")))

    def _event(self, event, **meta):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> tuple[int, float, int, int]:
        with self._lock:
            return (self.requests, self.seconds, self.hits, self.misses)

    def names_since(self, requests_before: int) -> list[str]:
        with self._lock:
            return self.names[requests_before:]
