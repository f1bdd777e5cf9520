"""Share of the cache rows the decode step programs read that a request
held, over the window, in percent: the growth of ``kv_rows_live`` over
the growth of ``kv_rows_streamed``, both counted by the engine per
dispatched substep (``ServingMetrics.summary()``). What the slab kernel
reads beyond a slot's position is the rest. A program without the
counters reports nothing."""


def snapshot(engine):
    s = engine.metrics.summary()
    if "kv_rows_streamed" not in s:
        return None
    return (s["kv_rows_live"], s["kv_rows_streamed"])


def read(m):
    if m.before is None or m.after is None:
        return None
    streamed = m.after[1] - m.before[1]
    if streamed <= 0:
        return None
    return 100.0 * (m.after[0] - m.before[0]) / streamed
