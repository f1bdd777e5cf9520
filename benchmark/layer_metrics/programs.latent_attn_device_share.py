"""Share of the device's busy time latent attention takes inside the
decode step, in percent: over the union of all device operations, the
operations of ``jit_step`` programs that are

- the kernel (``latent_decode_attn`` Mosaic calls),
- the low-rank projections only this attention has, keyed on the sizes of
  their results: a dimension of ``q_lora_rank`` (the query latent and its
  norm), of ``n_heads x (qk_nope_head_dim + qk_rope_head_dim)`` (the
  query heads, where a fusion flattens them), of ``kv_lora_rank +
  qk_rope_head_dim`` (the key-value latent with its rotary part, and the
  folded queries ``[slots, H, 576]``) or of the row as stored (``[slots,
  H, 640]``, ``[slots, 1, 640]``),
- the absorbed products: a result ``[slots, n_heads, kv_lora_rank]``
  (queries folded onto the latent) or ``[slots, n_heads, x]`` with x one
  of the three head parts (the query heads split, the attended latent
  taken to the heads' values).

The output projection's ``[slots, d_model]`` result has the shape of
every other product of the layer and is left out, so the share is a
lower bound. Shapes, because XLA names a fusion by number: the reader
keys on sizes only this attention has (``programs.moe_device_share``
does the same for the expert layer). A model without latent layers, or a
trace without the kernel, reports nothing.
"""

import re

from benchmark import trace_reduce

_SHAPE = re.compile(r"\[([\d,]*)\]")


def _result_shapes(op_name: str):
    """The result's shape, or a tuple result's shapes, as int tuples."""
    text = trace_reduce._COMMENT.sub("", trace_reduce._LAYOUT.sub("", op_name))
    m = trace_reduce._HLO.match(text)
    return [
        tuple(int(d) for d in dims.split(",") if d)
        for dims in _SHAPE.findall(m.group(1) if m else "")
    ]


def read(m):
    model = m.model
    if "latent_attention" not in (model.get("layer_types") or ()):
        return None
    busy_s, _ = trace_reduce.busy_and_window(m.trace)
    if busy_s <= 0:
        return None
    heads, r_kv = model["n_heads"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    row = r_kv + rope
    own = {model["q_lora_rank"], heads * (nope + rope), row,
           -(-row // 128) * 128}
    slots = m.geometry["n_slots"]
    per_head = {r_kv, nope, rope, v, nope + rope}

    def wanted(name: str) -> bool:
        if "latent_decode_attn" in name:
            return True
        for shape in _result_shapes(name):
            if own & set(shape):
                return True
            if len(shape) >= 3 and shape[0] == slots and heads in shape[1:] \
                    and shape[-1] in per_head:
                return True
        return False

    _, kernel_calls = trace_reduce.op_seconds(
        m.trace, lambda n: "latent_decode_attn" in n, "jit_step")
    if not kernel_calls:
        return None
    seconds, _ = trace_reduce.op_seconds(m.trace, wanted, "jit_step")
    return 100.0 * seconds / len(m.trace.devices) / busy_s
