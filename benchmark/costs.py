"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no later change to the program can move a
roofline share by recounting its own work.
"""

from __future__ import annotations


def kv_row_bytes(n_layers: int, d_kv: int, itemsize: int) -> int:
    """Bytes of ONE cached position across all layers: a K row and a V
    row of ``d_kv`` = kv_heads x head size elements in every layer."""
    return 2 * n_layers * d_kv * itemsize


def decode_needed_bytes(context_rows: int, n_layers: int, d_kv: int,
                        itemsize: int) -> int:
    """Cache bytes decode attention has to read to produce tokens whose
    contexts sum to ``context_rows`` positions: every live row once per
    layer, K and V. Rows past a request's position are not needed."""
    return context_rows * kv_row_bytes(n_layers, d_kv, itemsize)


def flash_train_flops(batch: int, n_heads: int, seq: int, head_dim: int,
                      n_layers: int) -> float:
    """Matmul FLOPs of causal flash attention, forward plus the fused
    backward, for one training step. Forward: QK^T and PV. Backward:
    QK^T again (flash keeps no scores), dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q. Seven products of 2*T*T*hd FLOPs per head,
    halved by the causal mask. Softmax and scaling are not counted."""
    return 7.0 * batch * n_heads * seq * seq * head_dim * n_layers


def lm_train_flops_per_token(d_model: int, n_layers: int, d_ff: int,
                             vocab: int, seq: int) -> float:
    """Analytic training FLOPs per token of a dense decoder-only LM, the
    arithmetic of ``bench.py::_lm_flops_per_token`` (copied; original
    listed in PERF.md for deletion): 6 x matmul parameters (qkv + out
    4d^2, MLP 2*d*d_ff per layer, an untied head d*V) plus causal
    attention 6*T*d per layer. Recomputation is not credited."""
    per_layer = 4 * d_model * d_model + 2 * d_model * d_ff
    return (6.0 * (n_layers * per_layer + d_model * vocab)
            + 6.0 * seq * d_model * n_layers)
