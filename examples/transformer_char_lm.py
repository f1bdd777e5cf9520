"""Composed-parallelism transformer char-LM with sampled generation.

Beyond the reference (its only sequence model is the serial LSTM): a
byte-level decoder trained over a (data, model) mesh — Megatron tensor
parallelism via pjit shardings, optional MoE experts and FSDP — then
KV-cached sampling.

Run (any host; uses however many devices jax exposes):
  python examples/transformer_char_lm.py
"""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    transformer_generate,
    transformer_train_step,
)
from deeplearning4j_tpu.parallel.mesh import dp_mp_mesh

CORPUS = (
    b"the quick brown fox jumps over the lazy dog. "
    b"pack my box with five dozen liquor jugs. "
) * 200


def main():
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 and n > 1 else 1
    mesh = dp_mp_mesh(max(1, n // tp), tp)
    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=256,
        max_len=129,
    )
    step, init_state, shard_tokens = transformer_train_step(mesh, cfg)
    params, opt_state = init_state(jax.random.key(0))

    arr = np.frombuffer(CORPUS, np.uint8).astype(np.int32)
    rng = np.random.default_rng(0)
    for i in range(200):
        starts = rng.integers(0, len(arr) - 129, 16)
        toks = np.stack([arr[s : s + 129] for s in starts])
        params, opt_state, loss = step(
            params, opt_state, shard_tokens(jnp.asarray(toks))
        )
        if (i + 1) % 50 == 0:
            print(f"step {i + 1}: loss {float(loss):.3f}")

    # params live on the mesh and transformer_generate takes none: the
    # Pallas decode kernel cannot sit bare in a multi-device jit on TPU
    # (dense path under SPMD, as TransformerConfig.decode_kernel says)
    gen = transformer_generate(dataclasses.replace(cfg, decode_kernel=False))
    out = gen(params, jnp.asarray(arr[None, :16]), jax.random.key(1), 64,
              temperature=0.8, top_k=20)
    print("sample:", bytes(np.asarray(out[0], np.uint8).tolist()).decode("latin-1"))

    # int8 serving: weight-only quantization (per-channel scales, dequant
    # fused into the matmul reads) over the float KV cache — the winning
    # production composite on TPU (PERF.md r5 crossover analysis)
    from deeplearning4j_tpu.models.transformer import quantize_decode_params

    qparams = quantize_decode_params(params, cfg)
    out_q = gen(qparams, jnp.asarray(arr[None, :16]), jax.random.key(1), 64,
                temperature=0.8, top_k=20)
    print(
        "int8 sample:",
        bytes(np.asarray(out_q[0], np.uint8).tolist()).decode("latin-1"),
    )


if __name__ == "__main__":
    main()
