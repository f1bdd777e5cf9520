"""The decode attention kernel over a cache of two leaves against the
memory roofline, in percent.

Needed: for every token delivered while the trace ran, the cache rows its
request held then, K and V: all of them in the full layers, the last
``sliding_window`` at most in the window layers
(``costs_laguna.mixed_decode_needed_bytes``), over the chip's published
HBM bandwidth. Taken: the summed device time of the ``decode_attn`` Mosaic
calls (both leaves' kernels carry that name) inside ``jit_step``
programs. A model without layer kinds, or a trace without the kernel,
reports nothing.
"""

from benchmark import costs_laguna, trace_reduce


def is_decode_kernel(op_name: str) -> bool:
    return "decode_attn" in op_name and trace_reduce.is_mosaic_call(op_name)


def read(m):
    kinds = m.model.get("layer_types")
    if not kinds or not m.peaks:
        return None
    seconds, calls = trace_reduce.op_seconds(
        m.trace, is_decode_kernel, "jit_step"
    )
    if not calls:
        return None
    t0, t1 = m.trace_host_span
    contexts = [rows for t, rows in m.deliveries if t0 <= t < t1]
    itemsize = m.system.pool.caches["full"].dtype.itemsize
    needed = costs_laguna.mixed_decode_needed_bytes(
        contexts, kinds.count("full_attention"),
        kinds.count("sliding_attention"), m.model.get("sliding_window") or 0,
        m.model["n_kv_heads"] * m.model["head_size"], itemsize,
    )
    return 100.0 * needed / m.peaks["hbm_bytes_per_s"] / seconds
