"""The decode attention kernel against the memory roofline, in percent.

Needed: for every token delivered while the trace ran, the cache rows its
request held then, K and V in every layer (``costs.decode_needed_bytes``),
over the chip's published HBM bandwidth. Taken: the summed device time of
the Mosaic custom calls inside ``jit_step`` programs. Memory-bound: the
kernel does two FLOPs per byte. Rows the kernel reads beyond a request's
position are time taken and not bytes needed, so reading whole slabs shows
as a low share.
"""

from benchmark import costs, trace_reduce


def read(m):
    seconds, calls = trace_reduce.op_seconds(
        m.trace, trace_reduce.is_mosaic_call, "jit_step"
    )
    if not calls or not m.peaks:
        return None
    t0, t1 = m.trace_host_span
    rows = sum(r for t, r in m.deliveries if t0 <= t < t1)
    model = m.model
    d_kv = (model.get("n_kv_heads") or model["n_heads"]) * (
        model["d_model"] // model["n_heads"]
    )
    cache = m.system.pool.caches
    itemsize = (cache["kv"] if isinstance(cache, dict) else cache).dtype.itemsize
    needed = costs.decode_needed_bytes(rows, model["n_layers"], d_kv, itemsize)
    return 100.0 * needed / m.peaks["hbm_bytes_per_s"] / seconds
