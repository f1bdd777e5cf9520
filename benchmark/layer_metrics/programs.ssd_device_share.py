"""Share of the device's busy time the Mamba-2 mixers take inside the
decode step, in percent: over the union of all device operations, the
operations of ``jit_step`` programs that are

- the kernel (``ssd_decode`` Mosaic calls),
- the operations only a mamba layer has, keyed on the sizes of their
  results: a dimension of ``W_in``'s width (z | xBC | dt: 2 x heads x
  d_head + 2 x groups x d_state + heads), of the convolution's (xBC: the
  window and the convolution) or of heads x d_head (z, the kernel's
  output, the skip, the gate and the gated norm); or, with the sizes of
  1 dropped, a result ``[slots, heads]`` (dt, the decay) or ``[slots,
  heads, d_head]`` (x by heads, dt x, D x). An attention layer's heads
  are ``[slots, 32, 64]`` and ``[slots, 8, 64]`` at the cell's widths,
  so they do not match while the mamba heads (64) differ from the query
  heads.

Shapes, because the trace names an operation by its HLO text, which
carries no named scope (the prefill's ``ssd_chunk_scan`` is outside
``jit_step`` anyway), and XLA names a fusion by number. ``W_out``'s
``[slots, d_model]`` result has the shape of every other product of the
layer and is left out, so the share is a lower bound. A model without
mamba layers, or a trace without the kernel, reports nothing.
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
    if "mamba" not in (model.get("layer_types") or ()):
        return None
    busy_s, _ = trace_reduce.busy_and_window(m.trace)
    if busy_s <= 0:
        return None
    heads, hd = model["mamba_n_heads"], model["mamba_d_head"]
    inner = heads * hd
    conv = inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    own = {inner + conv + heads, conv, inner}
    slots = m.geometry["n_slots"]
    per_head = {(slots, heads), (slots, heads, hd)}

    def wanted(name: str) -> bool:
        if "ssd_decode" in name:
            return True
        for shape in _result_shapes(name):
            if own & set(shape):
                return True
            if shape and (shape[0],) + tuple(d for d in shape[1:] if d != 1) \
                    in per_head:
                return True
        return False

    _, kernel_calls = trace_reduce.op_seconds(
        m.trace, lambda n: "ssd_decode" in n, "jit_step")
    if not kernel_calls:
        return None
    seconds, _ = trace_reduce.op_seconds(m.trace, wanted, "jit_step")
    return 100.0 * seconds / len(m.trace.devices) / busy_s
