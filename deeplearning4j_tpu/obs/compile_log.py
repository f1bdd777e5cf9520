"""What jax compiles in this process, from jax's own monitoring events.

Compilation is the largest part of a cold start and the one thing that
must never happen inside a warm serving loop, so the program counts it
where it happens instead of leaving it to whoever benchmarks it:

- every backend compile **request** (a hit in the persistent cache is
  still a request: the executable is loaded, not rebuilt);
- the **seconds** of each request, split into the three stages jax
  times: ``trace`` (Python to jaxpr), ``lower`` (jaxpr to an MLIR
  module) and ``backend`` (XLA compile, or the cache load that stands
  in for it);
- the persistent cache's **hits** and **misses** (a miss is counted by
  jax when it writes the new entry, so a program under the cache's
  size or time threshold is neither);

requests and seconds also by ``fun_name``, the name of the jitted
function as jax reports it (``step``, ``prefill``, ...).

jax times stages that nest: tracing ``step`` traces every jitted
``jax.numpy`` function it calls, and each reports its own duration
inside the outer one's; constants folded while tracing compile small
programs of their own. Summing every event (as ``chip_smoke.py`` did and
``benchmark/compile_log.py`` does) counts those seconds two or three
times over. This log follows the nesting per thread, from the events jax
sends when a stage begins, and adds only the outermost stage's seconds,
under the outermost function's name: its seconds never exceed the wall
time the thread spent compiling.

The log is process-wide, because ``jax.monitoring`` listeners are:
:func:`install` registers them once and returns the one
:class:`CompileLog`; later calls return the same object.
``ServingEngine`` and the ``transformer_train_step`` builder call it, so
whatever they compile is counted; a caller that wants earlier compiles
counted too (weights, a correctness check) calls it first. Listeners run
on whichever thread compiles.
"""

from __future__ import annotations

import collections
import threading

#: jax's duration events, by the stage name used here
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
STAGES = tuple(_STAGES.values())
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def _fun_name(meta: dict) -> str:
    """``fun_name`` as jax reports it, without the ``jit(...)`` the
    lowering and backend stages wrap around the name the tracing stage
    gives bare."""
    name = str(meta.get("fun_name", "?"))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


#: how many of the latest requests keep their function's name
NAMES_KEPT = 256


class CompileLog:
    """Exact totals of the compile events seen since :func:`install`."""

    def __init__(self):
        self._lock = threading.Lock()
        # per compiling thread: ``depth`` of the stages in progress
        self._tls = threading.local()
        self.requests = 0  # guarded-by: _lock
        self.seconds = dict.fromkeys(STAGES, 0.0)  # guarded-by: _lock
        self.cache_hits = 0  # guarded-by: _lock
        self.cache_misses = 0  # guarded-by: _lock
        #: fun_name of the latest requests, oldest first: enough to name
        #: what compiled since a snapshot, bounded however long the
        #: process lives
        self.names = collections.deque(maxlen=NAMES_KEPT)  # guarded-by: _lock
        self._by_fun: dict[str, dict] = {}  # guarded-by: _lock

    def _begun(self, event, value, **meta):
        if event in _STAGES:
            self._tls.depth = getattr(self._tls, "depth", 0) + 1

    def _duration(self, event, seconds, **meta):
        stage = _STAGES.get(event)
        if stage is None:
            return
        depth = self._tls.depth = max(0, getattr(self._tls, "depth", 1) - 1)
        if depth and stage != "backend":
            return  # inside another stage, whose seconds hold these
        fun = _fun_name(meta)
        with self._lock:
            per = self._by_fun.get(fun)
            if per is None:
                per = self._by_fun[fun] = {"requests": 0, "seconds": 0.0}
            if not depth:
                self.seconds[stage] += seconds
                per["seconds"] += seconds
            if stage == "backend":
                self.requests += 1
                per["requests"] += 1
                self.names.append(fun)

    def _event(self, event, **meta):
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with self._lock:
                setattr(self, key, getattr(self, key) + 1)

    def snapshot(self) -> tuple[int, float, int, int]:
        """``(requests, seconds over all stages, cache hits, misses)``."""
        with self._lock:
            return (self.requests, sum(self.seconds.values()),
                    self.cache_hits, self.cache_misses)

    def names_since(self, requests_before: int) -> list[str]:
        """The functions of the requests after the first
        ``requests_before``: what compiled since a snapshot (the latest
        :data:`NAMES_KEPT` of them, should there be more)."""
        with self._lock:
            n = min(self.requests - requests_before, len(self.names))
            return list(self.names)[len(self.names) - n:] if n > 0 else []

    def totals(self) -> dict:
        """The whole log as plain data: totals, the stage split, and
        requests and seconds per ``fun_name``."""
        with self._lock:
            return {
                "requests": self.requests,
                "seconds": sum(self.seconds.values()),
                "stage_seconds": dict(self.seconds),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "by_fun": {f: dict(v) for f, v in self._by_fun.items()},
            }


_INSTALLED: CompileLog | None = None
_INSTALL_LOCK = threading.Lock()


def install() -> CompileLog:
    """The process's compile log, registering its listeners with
    ``jax.monitoring`` on the first call."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if _INSTALLED is None:
            from jax import monitoring

            log = CompileLog()
            monitoring.register_scalar_listener(log._begun)
            monitoring.register_event_duration_secs_listener(log._duration)
            monitoring.register_event_listener(log._event)
            _INSTALLED = log
        return _INSTALLED
