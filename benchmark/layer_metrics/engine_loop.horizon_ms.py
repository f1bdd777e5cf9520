"""Wall time per dispatched decode horizon: window / horizons dispatched
in it, in milliseconds. Source: ``ServingMetrics.summary()["steps"]``,
one count per dispatch."""


def snapshot(engine):
    return engine.metrics.summary()["steps"]


def read(m):
    steps = m.after - m.before
    return 1e3 * m.window_s / steps if steps > 0 else None
