"""The grouped expert products against the memory roofline, in percent.

Needed: the weights of every held expert that saw a token, once a layer
and substep: the growth of the engine's ``moe_experts_hit`` over the
window, per dispatched horizon, times ``costs_laguna.expert_bytes``, over
the chip's published HBM bandwidth. Taken: the device time of the grouped
products (``lax.ragged_dot``: operations named ``ragged-dot`` in the
trace) inside ``jit_step`` programs, per executed ``jit_step``. Weight
streaming bounds it: 2.5 rows an expert do 5 FLOPs a byte. A program
without the counter, or a trace without such operations, reports nothing.
"""

from benchmark import costs_laguna, trace_reduce


def is_grouped_product(op_name: str) -> bool:
    return "ragged-dot" in op_name or "ragged_dot" in op_name


def snapshot(engine):
    s = engine.metrics.summary()
    if "moe_experts_hit" not in s:
        return None
    return (s["moe_experts_hit"], s["steps"])


def read(m):
    if m.before is None or m.after is None or not m.peaks:
        return None
    steps = m.after[1] - m.before[1]
    seconds, calls = trace_reduce.op_seconds(
        m.trace, is_grouped_product, "jit_step"
    )
    programs = len(trace_reduce.module_durations(m.trace, "jit_step"))
    if steps <= 0 or not calls or not programs:
        return None
    hit_per_step = (m.after[0] - m.before[0]) / steps
    routed = next(p for p in m.system.params["layers"] if "we_gate" in p)
    needed = costs_laguna.experts_hit_bytes(
        hit_per_step, m.model["d_model"], m.model["d_expert"],
        routed["we_gate"].dtype.itemsize,
    )
    return 100.0 * needed / m.peaks["hbm_bytes_per_s"] / (seconds / programs)
