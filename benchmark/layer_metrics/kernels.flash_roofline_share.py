"""The flash attention kernels of a training step against the compute
roofline, in percent.

Needed: the matmul FLOPs of causal flash attention, forward and fused
backward, from the step's shapes (``costs.flash_train_flops``) times the
steps in the trace, over the chip's published bf16 peak. Taken: the summed
device time of the Mosaic custom calls inside ``jit_step`` programs (the
trainer runs no other kernel). Compute-bound at T=1024.
"""

from benchmark import costs, trace_reduce


def read(m):
    seconds, calls = trace_reduce.op_seconds(
        m.trace, trace_reduce.is_mosaic_call, "jit_step"
    )
    steps = len(trace_reduce.module_durations(m.trace, "jit_step"))
    if not calls or not steps or not m.peaks:
        return None
    model, trainer = m.model, m.geometry
    flops = steps * costs.flash_train_flops(
        trainer["batch"], model["n_heads"], trainer["seq_len"],
        model["d_model"] // model["n_heads"], model["n_layers"],
    )
    return 100.0 * flops / m.peaks["flops_per_s"] / seconds
