"""Step-scoped XLA profiling trigger.

``jax.profiler.trace`` captures everything between start and stop —
useful only if start/stop land on meaningful boundaries. For a serving
engine the meaningful unit is the *engine step* (one admission sweep +
one fused decode horizon), so :class:`ProfileTrigger` arms a capture of
the NEXT ``n`` steps: the engine calls ``step_start``/``step_end``
around each step, and the trigger starts the XLA trace at the first
armed step and stops it after the n-th. Disarmed cost is one integer
compare per step — safe to leave wired in production.

Armed remotely via ``POST /profile?s=N`` on the serving server, or at
launch via the ``serve --profile-steps N`` flag. The capture lands in a
fresh subdirectory of ``log_dir`` (XPlane protobufs; open the
directory in TensorBoard's profile plugin, or convert with
``tensorboard_plugin_profile``'s tooling), and ``GET /profile/report``
reduces the last finished one (:func:`..obs.capture.loop_report`).

``stop_trace`` runs on the loop's thread in ``step_end``, where every
slot waits while the capture is written: on GPT-2 large on a v5e a
capture of 60 steps (34 MB) held the loop 8-11 s, and as long without
the Python tracer's frames (30 MB, 7-10 s; three warm captures a side),
so the capture keeps jax's default options. Capture few steps.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path


class ProfileTrigger:
    def __init__(self, log_dir: str | Path = "/tmp/dl4j_tpu_profile"):
        self.log_dir = Path(log_dir)
        self._lock = threading.Lock()
        self._remaining = 0
        self._active = False
        self.n_captures = 0
        self.last_capture_dir: Path | None = None

    @property
    def armed(self) -> bool:
        return self._remaining > 0 or self._active

    def finished_capture(self) -> Path | None:
        """The directory of the last finished capture, ``None`` before
        the first; raises ``RuntimeError`` while one is armed or runs
        (it waits out a ``stop_trace`` that is writing the capture)."""
        with self._lock:
            if self.armed:
                raise RuntimeError("a profile capture is armed or running")
            return self.last_capture_dir if self.n_captures else None

    def arm(self, n_steps: int, log_dir: str | Path | None = None) -> Path:
        """Arm a capture of the next ``n_steps`` engine steps; returns
        the directory the capture will land in. Raises while a capture
        is already armed or running (one at a time — the XLA profiler
        is a process-global singleton)."""
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        with self._lock:
            if self.armed:
                raise RuntimeError("a profile capture is already armed")
            d = Path(log_dir) if log_dir is not None else self.log_dir
            d = d / f"capture-{self.n_captures}-{int(time.time())}"
            self.last_capture_dir = d
            self._remaining = int(n_steps)
        return d

    def step_start(self) -> None:
        """Engine hook, before a step. Starts the XLA trace on the
        first armed step; plain no-op when disarmed."""
        if self._remaining <= 0 or self._active:
            return
        with self._lock:
            if self._remaining <= 0 or self._active:
                return
            import jax

            self.last_capture_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.last_capture_dir))
            self._active = True

    def step_end(self) -> None:
        """Engine hook, after a step. Stops the trace once the armed
        step budget is spent."""
        if not self._active:
            return
        with self._lock:
            if not self._active:
                return
            self._remaining -= 1
            if self._remaining <= 0:
                import jax

                jax.profiler.stop_trace()
                self._active = False
                self._remaining = 0
                self.n_captures += 1
