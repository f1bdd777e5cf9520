"""Share of the device's busy time the expert layers take inside the
decode step, in percent: over the union of all device operations, the
operations of ``jit_step`` programs that are

- the grouped products (named ``ragged-dot``),
- the router and the routing: a result with a dimension of
  ``n_experts_total`` (logits, softmax, top-k),
- the dispatch and the combine: a result whose leading dimension is
  ``n_slots x moe_k`` (the sort, the gathered rows, the weighted outputs),
- the shared expert's gate and up products: a result ``[n_slots,
  d_shared]``. Its down projection has the shape of every other
  ``[n_slots, d_model]`` product and is left out.

Shapes, because XLA names a fusion by number: the reader keys on the
sizes only this layer has. A model without routed experts reports
nothing.
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
    total = m.model.get("n_experts_total")
    if not total or "moe_k" not in m.model:
        return None
    busy_s, _ = trace_reduce.busy_and_window(m.trace)
    if busy_s <= 0:
        return None
    slots = m.geometry["n_slots"]
    pairs = slots * m.model["moe_k"]
    shared = (slots, m.model.get("d_shared") or -1)

    def wanted(name: str) -> bool:
        if "ragged-dot" in name or "ragged_dot" in name:
            return True
        for shape in _result_shapes(name):
            if total in shape or (shape and shape[0] == pairs) or (
                    shape == shared):
                return True
        return False

    seconds, calls = trace_reduce.op_seconds(m.trace, wanted, "jit_step")
    if not calls:
        return None
    return 100.0 * seconds / len(m.trace.devices) / busy_s
