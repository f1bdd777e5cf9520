"""Median wait from arrival to admission of the requests admitted in the
window, in milliseconds.

Source: ``ServingMetrics.queue_delay``, the series behind
``summary()["queue_delay_p50_s"]``. The series keeps every sample, in
order, until it outgrows its reservoir; past that the window's own samples
cannot be told apart and the reader reports nothing.
"""

import statistics


def snapshot(engine):
    series = engine.metrics.queue_delay
    return {"n": series.n, "cap": series.cap, "values": series.values}


def read(m):
    if m.after["n"] > m.after["cap"]:
        return None
    mine = m.after["values"][m.before["n"]:]
    return 1e3 * statistics.median(mine) if mine else None
