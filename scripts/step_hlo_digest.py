#!/usr/bin/env python3
"""Digests of the serving programs a checkout lowers, to tell whether a
change left another configuration's programs as they were.

    python3 scripts/step_hlo_digest.py [CHECKOUT]     (default: this one)

For GPT-2 (MHA, learned positions, flash prefill), a GQA + RoPE
configuration, GPT-2 with the int8 cache, and Laguna's gated stack,
openPangu's latent one, Olmo-Hybrid's linear one and Granite's Mamba-2
one (each configuration file's ``rehearse`` widths), each at the
vocabulary its cell serves so that the sampler takes the branch it takes
there: the step (K = 4, temperature 1.0, top-k 40), prefill and chunk
programs are lowered from abstract values on the CPU (nothing is
compiled or run, about 30 s) and the SHA-256 of each StableHLO text is
printed; for GPT-2 on the dense decode path (``decode_kernel=False``,
what tensor parallelism, a LoRA bank and the speculative draft take)
the step alone, since its prefill and chunk are GPT-2's own. Then the
loss and gradients of two training configurations: a GPT-2 whose heads
fill whole lane groups, so that training takes the packed flash kernels
as ``gpt2-medium.train-1k`` does, and a GQA + RoPE one, which takes the
(B, H, T, K) flash kernels. Run it on two checkouts and compare the
lines: equal digests are the same program, byte for byte.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else
                       os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    init_transformer,
    transformer_loss,
)
from deeplearning4j_tpu.serving.engine import (  # noqa: E402
    build_chunk_program,
    build_prefill_program,
    build_step_program,
)

S = jax.ShapeDtypeStruct
SLOTS, ROWS = 4, 256
PROGRAMS = ("step", "prefill", "chunk")


def rehearsed(name: str) -> TransformerConfig:
    """A configuration file's ``rehearse`` widths at its served
    vocabulary, bf16."""
    with open(os.path.join(ROOT, f"benchmark/configs/{name}.json")) as f:
        config = json.load(f)
    model = dict(config["model"], **config["rehearse"]["model"])
    model.update(vocab_size=config["model"]["vocab_size"],
                 compute_dtype=jnp.bfloat16)
    return TransformerConfig(**model)


def configurations() -> dict:
    toy = dict(d_model=64, n_heads=4, n_layers=2, d_ff=128, max_len=ROWS,
               compute_dtype=jnp.bfloat16)
    return {
        "gpt2": TransformerConfig(vocab_size=50257, use_flash=True, **toy),
        "gqa-rope": TransformerConfig(vocab_size=50257, n_kv_heads=2,
                                      rope=True, **toy),
        "gpt2-int8": TransformerConfig(vocab_size=50257, use_flash=True,
                                       decode_int8=True, **toy),
        "gpt2-dense": TransformerConfig(vocab_size=50257, use_flash=True,
                                        decode_kernel=False, **toy),
        "laguna": rehearsed("laguna-s-2.1"),
        "pangu": rehearsed("openpangu-ultra-moe-718b"),
        "olmo-hybrid": rehearsed("olmo-hybrid-7b"),
        "granite": rehearsed("granite-4.0-h-micro"),
    }


# gpt2-dense's prefill and chunk lower to gpt2's own
KINDS = {"gpt2-dense": ("step",)}


def programs(cfg, kinds) -> dict:
    fwd1, init_caches, prefill, cast = _decode_builder(cfg)
    params = jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0))
    caches = jax.eval_shape(lambda: init_caches(SLOTS, ROWS))
    one = jax.eval_shape(lambda: init_caches(1, ROWS))
    state = (S((SLOTS, cfg.vocab_size), jnp.float32), S((SLOTS,), jnp.int32),
             S((SLOTS,), jnp.bool_), S((SLOTS,), jnp.int32),
             S((SLOTS,), jnp.int32))
    sc = S((), jnp.int32)
    lowerings = {
        "step": lambda: jax.jit(
            build_step_program(fwd1, 4, 1.0, 40, False)).lower(
            params, caches, *state, S((SLOTS, 2), jnp.uint32),
            S((SLOTS,), jnp.int32)),
        "prefill": lambda: jax.jit(
            build_prefill_program(prefill, init_caches, ROWS)).lower(
            caches, *state, params, S((1, 64), jnp.int32), sc, sc, sc, sc,
            sc, S((1,), jnp.int32)),
        "chunk": lambda: jax.jit(
            build_chunk_program(_chunk_builder(cfg))).lower(
            params, one, S((1, 32), jnp.int32), sc, sc, S((1,), jnp.int32)),
    }
    return {kind: lowerings[kind]() for kind in kinds}


def train_programs() -> dict:
    """Loss and gradients: ``gpt2-packed`` takes the packed flash
    kernels, ``gqa-rope`` the (B, H, T, K) ones."""
    widths = dict(vocab_size=50257, d_model=256, n_heads=4, n_layers=2,
                  d_ff=512, max_len=ROWS, use_flash=True,
                  compute_dtype=jnp.bfloat16)
    out = {}
    for name, cfg in (
            ("gpt2-packed", TransformerConfig(**widths)),
            ("gqa-rope", TransformerConfig(n_kv_heads=2, rope=True,
                                           **widths))):
        params = jax.eval_shape(
            lambda key: init_transformer(key, cfg), jax.random.key(0))
        out[name] = jax.jit(jax.value_and_grad(transformer_loss(cfg))).lower(
            params, S((2, ROWS + 1), jnp.int32))
    return out


def main() -> int:
    import deeplearning4j_tpu

    print("package:", os.path.dirname(deeplearning4j_tpu.__file__),
          file=sys.stderr)

    def show(name, kind, lowered):
        text = lowered.as_text()
        print(name, kind, len(text),
              hashlib.sha256(text.encode()).hexdigest()[:16], flush=True)

    for name, cfg in configurations().items():
        for kind, lowered in programs(cfg, KINDS.get(name, PROGRAMS)).items():
            show(name, kind, lowered)
    for name, lowered in train_programs().items():
        show(name, "train", lowered)
    return 0


if __name__ == "__main__":
    sys.exit(main())
