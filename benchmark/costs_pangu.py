"""Bytes and operations the latent decode kernel needs, from shapes alone.

Kept with the benchmark, like ``costs.py`` and ``costs_laguna.py``, so
that no later change to the program can move a roofline share by
recounting its own work. A cache row of a latent layer is the normed
latent (``r_kv`` values) followed by the rotated key part all heads share
(``rope`` values): key and value of every head, read once.
"""

from __future__ import annotations


def latent_decode_needed_bytes(contexts, n_layers: int, r_kv: int, rope: int,
                               itemsize: int) -> int:
    """Cache bytes decode attention has to read to produce one token at
    each of ``contexts`` (the rows the request holds then): ``r_kv +
    rope`` values of every row in every layer, whatever width the row is
    stored at. A context of 0 (a first token, made by the prefill) needs
    nothing."""
    return sum(n_layers * c * (r_kv + rope) * itemsize for c in contexts)


def latent_decode_needed_flops(contexts, n_layers: int, n_heads: int,
                               r_kv: int, rope: int) -> int:
    """Operations of the same tokens in the absorbed form: every head's
    score against a row is a product over ``r_kv + rope`` values and its
    weighted sum one over ``r_kv``, two operations a multiply-add: ``H x
    (2 (r_kv + rope) + 2 r_kv)`` a row and layer (278,528 at 128 heads,
    512 + 64)."""
    per_row = n_heads * (2 * (r_kv + rope) + 2 * r_kv)
    return sum(n_layers * c * per_row for c in contexts)


def latent_decode_floor_seconds(contexts, n_layers: int, n_heads: int,
                                r_kv: int, rope: int, itemsize: int,
                                peaks: dict) -> float:
    """The least time the chip could take for those tokens: the larger
    of bytes over the memory bandwidth and operations over the peak."""
    return max(
        latent_decode_needed_bytes(contexts, n_layers, r_kv, rope, itemsize)
        / peaks["hbm_bytes_per_s"],
        latent_decode_needed_flops(contexts, n_layers, n_heads, r_kv, rope)
        / peaks["flops_per_s"],
    )
