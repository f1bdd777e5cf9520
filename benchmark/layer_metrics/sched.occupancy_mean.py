"""Mean number of slots decoding per dispatched horizon, over the window.

Source: ``ServingMetrics.summary()``: ``occupancy_mean`` is an exact mean
of one count per dispatch and ``steps`` counts the dispatches, so the
window's own mean follows from the two snapshots.
"""


def snapshot(engine):
    s = engine.metrics.summary()
    return {"steps": s["steps"], "mean": s.get("occupancy_mean", 0.0)}


def read(m):
    steps = m.after["steps"] - m.before["steps"]
    if steps <= 0:
        return None
    total = (m.after["mean"] * m.after["steps"]
             - m.before["mean"] * m.before["steps"])
    return total / steps
