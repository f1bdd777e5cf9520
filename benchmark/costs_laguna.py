"""Bytes the Laguna cell's two new device paths need, from shapes alone.

Kept with the benchmark, like ``costs.py``, so that no later change to the
program can move a roofline share by recounting its own work.
"""

from __future__ import annotations


def expert_bytes(d_model: int, d_expert: int, itemsize: int) -> int:
    """Weight bytes of ONE routed expert: the gate, up and down
    projections of a SwiGLU of width ``d_expert``. An expert that is hit
    by at least one token streams them once, whatever the tokens."""
    return 3 * d_model * d_expert * itemsize


def experts_hit_bytes(experts_hit: float, d_model: int, d_expert: int,
                      itemsize: int) -> float:
    """Weight bytes the grouped products have to read for ``experts_hit``
    (expert, layer, substep) triples with at least one token. The rows'
    own bytes (a few MB a step beside GBs of weights) are not counted."""
    return experts_hit * expert_bytes(d_model, d_expert, itemsize)


def mixed_decode_needed_bytes(contexts, n_full: int, n_window: int,
                              window: int, d_kv: int, itemsize: int) -> int:
    """Cache bytes decode attention has to read to produce one token at
    each of ``contexts`` (the rows the request holds then): K and V of
    every row in the full layers, of the last ``window`` rows at most in
    the window layers. ``d_kv`` = KV heads x head size. A context of 0
    (a first token, made by the prefill) needs nothing."""
    return sum(
        2 * d_kv * itemsize * (n_full * c + n_window * min(c, window))
        for c in contexts
    )
