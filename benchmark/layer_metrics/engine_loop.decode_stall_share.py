"""Share of the window in which occupied decode slots waited on an
admission's prefill, in percent. Source:
``ServingMetrics.summary()["decode_stall_s"]`` (absent until the first
stall)."""


def snapshot(engine):
    return engine.metrics.summary().get("decode_stall_s", 0.0)


def read(m):
    return 100.0 * (m.after - m.before) / m.window_s
