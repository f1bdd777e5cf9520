"""Share of the time between the window's two snapshots that the engine
loop's thread spent on its own work, in percent: the self seconds of
``sweep``, ``admit``, ``prefill``, ``dispatch`` and ``process``. Left out
are the two phases in which the thread waits for the device: ``sync``, the
designated readback, and ``key_sync``, the readback of a new slot's
sampling key inside ``admit`` (a program that lacks that region still
counts the wait under ``admit``, and reads high). Source:
``ServingMetrics.summary()["loop_seconds"]``, exact totals the program's
phase regions keep whether or not anything traces; each snapshot is
stamped, because the closing one is taken when the profiler has finished
writing, which can be after the window's nominal end. A program without
the regions reports nothing."""

import time

_HOST_PHASES = ("sweep", "admit", "prefill", "dispatch", "process")


def snapshot(engine):
    loop = engine.metrics.summary().get("loop_seconds")
    return None if loop is None else {"t": time.perf_counter(), "loop": loop}


def read(m):
    if m.before is None or m.after is None:
        return None
    busy = sum(m.after["loop"][p] - m.before["loop"][p] for p in _HOST_PHASES)
    return 100.0 * busy / (m.after["t"] - m.before["t"])
