"""The SSD decode kernel against its roofline, in percent.

Needed: every decode token delivered while the trace ran advanced its
slot's state in every mamba layer once, and an update reads and writes
``d_state x heads x d_head`` float32 values; those bytes over the chip's
published HBM bandwidth (``costs_granite_hybrid.ssd_decode_floor_seconds``;
the operations, 5 a value, bind nowhere near). A first token, made by the
prefill, updates nothing. Taken: the summed device time of the
``ssd_decode`` Mosaic calls inside ``jit_step`` programs. A model without
mamba layers, or a trace without the kernel, reports nothing.
"""

from benchmark import costs_granite_hybrid, trace_reduce


def is_ssd_kernel(op_name: str) -> bool:
    return "ssd_decode" in op_name and trace_reduce.is_mosaic_call(op_name)


def read(m):
    kinds = m.model.get("layer_types") or ()
    if "mamba" not in kinds or not m.peaks:
        return None
    seconds, calls = trace_reduce.op_seconds(m.trace, is_ssd_kernel, "jit_step")
    if not calls:
        return None
    t0, t1 = m.trace_host_span
    tokens = sum(1 for t, rows in m.deliveries if t0 <= t < t1 and rows)
    floor = costs_granite_hybrid.ssd_decode_floor_seconds(
        tokens * kinds.count("mamba"),
        m.model["mamba_d_state"],
        m.model["mamba_n_heads"] * m.model["mamba_d_head"],
        m.peaks,
    )
    return 100.0 * floor / seconds
