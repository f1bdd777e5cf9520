"""The latent decode kernel against its roofline, in percent.

Needed: for every token delivered while the trace ran, the cache rows its
request held then, in every layer: ``r_kv + rope`` values a row as bytes
over the chip's published HBM bandwidth, and ``H x (2 (r_kv + rope) + 2
r_kv)`` operations a row over its published peak; the larger of the two
times (``costs_pangu.latent_decode_floor_seconds``; at 128 heads over 576
values the two are within a percent of each other on a v5e). Taken: the
summed device time of the ``latent_decode_attn`` Mosaic calls inside
``jit_step`` programs. A model without latent layers, a pool without a
latent leaf, or a trace without the kernel, reports nothing.
"""

from benchmark import costs_pangu, trace_reduce


def is_latent_kernel(op_name: str) -> bool:
    return "latent_decode_attn" in op_name and trace_reduce.is_mosaic_call(
        op_name)


def read(m):
    kinds = m.model.get("layer_types") or ()
    if "latent_attention" not in kinds or not m.peaks:
        return None
    caches = getattr(getattr(m.system, "pool", None), "caches", None)
    if not isinstance(caches, dict) or "latent" not in caches:
        return None
    seconds, calls = trace_reduce.op_seconds(
        m.trace, is_latent_kernel, "jit_step"
    )
    if not calls:
        return None
    t0, t1 = m.trace_host_span
    contexts = [rows for t, rows in m.deliveries if t0 <= t < t1]
    floor = costs_pangu.latent_decode_floor_seconds(
        contexts, kinds.count("latent_attention"), m.model["n_heads"],
        m.model["kv_lora_rank"], m.model["qk_rope_head_dim"],
        caches["latent"].dtype.itemsize, m.peaks,
    )
    return 100.0 * floor / seconds
