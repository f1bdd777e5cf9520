"""Token-expert pairs a held expert computes each time it is hit, over
the window: the growth of ``moe_assignments_local`` over the growth of
``moe_experts_hit`` (``ServingMetrics.summary()``, counted on the device
inside the step program). Every hit streams the expert's weights once, so
more pairs a hit is more work for the same bytes: 64 slots x 10 / 256
experts is 2.5 pairs an expert a substep, and a hit expert holds about
2.7. A program without the counters reports nothing."""


def snapshot(engine):
    s = engine.metrics.summary()
    if "moe_experts_hit" not in s:
        return None
    return (s["moe_assignments_local"], s["moe_experts_hit"])


def read(m):
    if m.before is None or m.after is None:
        return None
    hit = m.after[1] - m.before[1]
    if hit <= 0:
        return None
    return (m.after[0] - m.before[0]) / hit
