"""Decoder-only transformer LM — flagship beyond-parity model.

The reference's only sequence model is a serial-timestep LSTM
(models/classifiers/lstm/LSTM.java:36); this is the modern counterpart,
built TPU-first to exercise the framework's composed parallelism:

- Parameters are stacked over a leading layer axis and the blocks run
  under one ``lax.scan`` — one compiled block body regardless of depth.
- Tensor parallelism is expressed as pjit shardings (Megatron layout:
  QKV/MLP-in column-split on heads/ffn dim, attention-out/MLP-out
  row-split) via :func:`transformer_shardings`; XLA's SPMD partitioner
  inserts the collectives, nothing is hand-scheduled.
- Data parallelism is the batch axis of the same 2-D ``(data, model)``
  mesh; gradient AllReduce falls out of pjit.
- Optional ``remat`` wraps each block in ``jax.checkpoint`` to trade
  recompute for HBM.
- Compute can run in bf16 (MXU native) with f32 params/softmax.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops.attention import attention
from deeplearning4j_tpu.parallel import mesh as mesh_lib
from deeplearning4j_tpu.parallel.expert_parallel import MoEParams, moe_ffn
from deeplearning4j_tpu.parallel.sequence_parallel import ring_attention


#: a layer's published type -> the cache leaf its kind shares
_KINDS = {
    "full_attention": "full", "sliding_attention": "window",
    "latent_attention": "latent",
}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 256
    remat: bool = False
    # remat granularity when remat=True: "full" recomputes the whole
    # block in backward (max memory saving); "dots_no_batch" saves the
    # projection/MLP matmul outputs and recomputes only elementwise ops
    # and the (B,H,T,T) attention scores (jax
    # dots_with_no_batch_dims_saveable policy). The selective policy is
    # the single biggest single-chip perf lever at GPT-2 scale: without
    # it the layer scan stacks two full (L,B,H,T,T) attention-prob
    # tensors (~10GB at B=8/T=1024) plus six (L,B,T,4d) gelu
    # intermediates into HBM every step, measured via xplane profile.
    remat_policy: str = "dots_no_batch"
    # True (default): run the blocks under one lax.scan — one compiled
    # block body regardless of depth, fast compiles. False: unroll the
    # layer loop in Python; ~10% faster steps at GPT-2-small scale (the
    # scan's dynamic-slice/stack bookkeeping measured ~26ms/step at
    # B=16/T=1024) at the cost of depth-proportional compile time. The
    # bench uses False; training CLIs default to True.
    scan_layers: bool = True
    compute_dtype: Any = jnp.float32
    # expert parallelism: n_experts > 0 swaps the dense MLP for a routed
    # MoE FFN with experts one-per-device on the mesh's model axis
    n_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 2.0
    aux_coef: float = 0.01
    # sequence parallelism: shard the sequence over the data axis and run
    # ring attention (heads stay TP-sharded on the model axis)
    sequence_parallel: bool = False
    # pallas flash-attention kernels (causal, custom-vjp backward, O(T)
    # memory) in place of dense attention; needs T <= 128 or T % 128 == 0
    use_flash: bool = False
    # rotary position embeddings on q/k (RoPE) instead of relying solely
    # on the learned absolute table — the modern long-context scheme
    rope: bool = False
    # grouped-query attention: number of KV heads (None = n_heads, plain
    # MHA). Shrinks the decode KV cache n_heads/n_kv_heads-fold
    n_kv_heads: int | None = None
    # decode attention via the pallas flash-decode kernel over the packed
    # (B, T, Hkv*K) cache (lane-aligned: ~1x HBM bytes vs the 2.67x
    # tile-padding tax of a (B, T, H, K) cache). False falls back to the
    # dense einsum path (useful under SPMD sharding or for debugging).
    decode_kernel: bool = True
    # int8 serving mode (r5): decode expects params produced by
    # :func:`quantize_decode_params` (weight-only int8, per-output-
    # channel scales, dequant fused into the matmul reads) AND stores
    # the KV cache int8 with per-row scales (the kernel dequantizes
    # in-register). Halves the two HBM streams that bound decode —
    # the 247MB/step weight stream and the ~345MB/step cache stream at
    # B=16 (PERF.md "0.60-MBU wall"). Training paths ignore this flag.
    decode_int8: bool = False
    # -- the gated block with layers of two kinds (serving only) ---------
    # ``layer_types`` set (one of "full_attention" / "sliding_attention"
    # per layer) switches the whole stack to :func:`_gated_block`:
    # RMSNorm (``norm_eps``), no biases, no learned positions, rotary
    # (``rope`` must be on), a SwiGLU MLP of width ``d_ff`` in
    # ``dense_layers`` and a routed expert layer everywhere else, KV
    # caches grouped by layer kind. Parameters are one dict a layer
    # (``params["layers"][l]``): shapes differ by layer.
    layer_types: tuple | None = None
    # explicit head size (None: d_model // n_heads)
    head_size: int | None = None
    # query heads per layer (None: n_heads everywhere); KV heads are
    # n_kv_heads in every layer
    layer_heads: tuple | None = None
    # keys a sliding_attention layer sees, the query's own included
    sliding_window: int | None = None
    # per-head sigmoid gate on the attention output, from the normed
    # layer input, applied before the output projection
    attn_gate: bool = False
    norm_eps: float = 1e-5
    # rotary base of sliding_attention layers (whole head, plain)
    rope_theta: float = 10000.0
    # YaRN settings of full_attention layers: rope_theta, factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor, partial_rotary_factor (a dict; frozen to sorted
    # pairs so the config stays hashable). None: as sliding layers
    rope_full: Any = None
    # layers whose MLP is dense (mlp_only_layers); the rest route
    dense_layers: tuple = ()
    # routed experts: ``n_experts`` above counts the experts HELD here,
    # ids expert_first .. expert_first + n_experts - 1 of the
    # n_experts_total the router scores; moe_k per token, weights
    # renormalised over the top k and scaled by moe_scale
    n_experts_total: int = 0
    expert_first: int = 0
    moe_scale: float = 1.0
    d_expert: int = 0  # width of one routed expert
    d_shared: int = 0  # width of the shared expert (0: none)
    # the router's score over all published experts: "softmax", or
    # "sigmoid" (each expert scored alone; the same selected set)
    moe_score: str = "softmax"
    # -- latent attention ("latent_attention" layers; every layer or none)
    # Low-rank query and key-value projections, each with an RMSNorm of
    # its own; a head's query and key are ``qk_nope_head_dim`` channels
    # from the latent plus ``qk_rope_head_dim`` rotary channels whose key
    # part is ONE vector shared by all heads; values are ``v_head_dim``
    # wide. The cache row of a position is the normed latent followed by
    # the rotated shared key: ``kv_lora_rank + qk_rope_head_dim`` values,
    # one plane. ``rope_theta`` is the base over the rotary channels.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # four RMSNorms a layer: the attention's and the MLP's outputs are
    # normed before they join the residual stream
    sandwich_norm: bool = False

    @property
    def head_dim(self) -> int:
        if self.head_size is not None:
            return self.head_size
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def gated(self) -> bool:
        """The stack of :func:`_gated_block` layers (``layer_types``
        set): parameters and caches grouped by layer, not stacked."""
        return self.layer_types is not None

    def heads_of(self, layer: int) -> int:
        return self.layer_heads[layer] if self.layer_heads else self.n_heads

    @property
    def latent(self) -> bool:
        """A gated stack whose layers attend over a latent cache row."""
        return self.gated and "latent_attention" in self.layer_types

    @property
    def latent_row(self) -> int:
        """Values of one latent cache row: the latent, then the rotated
        key part all heads share."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def layers_of(self, kind: str) -> tuple:
        """Indices of the layers of one kind ("full" / "window" /
        "latent"), in
        order: a layer's place in this tuple is its place in the
        cache leaf of that kind."""
        return tuple(
            l for l, t in enumerate(self.layer_types or ())
            if _KINDS[t] == kind
        )

    @property
    def rope_full_settings(self) -> dict | None:
        return None if self.rope_full is None else dict(self.rope_full)

    # JSON round-trip, matching the framework's config story (nn/conf.py
    # ≙ NeuralNetConfiguration.toJson): dtypes serialize by name
    def to_json(self) -> str:
        import json

        d = dataclasses.asdict(self)
        d["compute_dtype"] = jnp.dtype(self.compute_dtype).name
        return json.dumps(d)

    @classmethod
    def from_json(cls, s: str) -> "TransformerConfig":
        import json

        d = json.loads(s)
        # tolerant like nn/conf.py's from_dict: ignore unknown keys
        # (forward compatibility) and fall back to defaults for missing
        # ones — the checkpoint-config round-trip must survive version
        # skew in either direction
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        if "compute_dtype" in d:
            d["compute_dtype"] = jnp.dtype(d["compute_dtype"])
        return cls(**d)

    def __post_init__(self):
        # lists and dicts arrive from JSON (``TransformerConfig(**model)``,
        # ``from_json``); the dataclass is frozen and hashed
        for name in ("layer_types", "layer_heads", "dense_layers"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))
        if isinstance(self.rope_full, dict):
            object.__setattr__(
                self, "rope_full", tuple(sorted(self.rope_full.items()))
            )
        elif isinstance(self.rope_full, list):
            object.__setattr__(
                self, "rope_full", tuple((k, v) for k, v in self.rope_full)
            )
        if self.gated:
            self._check_gated()
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_kv_heads ({self.kv_heads}) must divide n_heads "
                f"({self.n_heads})"
            )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def _check_gated(self):
        if len(self.layer_types) != self.n_layers or not (
            set(self.layer_types) <= set(_KINDS)
        ):
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as one of "
                f"{sorted(_KINDS)}, got {self.layer_types}"
            )
        if self.layer_heads and len(self.layer_heads) != self.n_layers:
            raise ValueError("layer_heads must give one count a layer")
        for l in range(self.n_layers):
            if self.heads_of(l) % self.kv_heads:
                raise ValueError(
                    f"n_kv_heads ({self.kv_heads}) must divide layer {l}'s "
                    f"{self.heads_of(l)} query heads"
                )
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_score is 'softmax' or 'sigmoid', got {self.moe_score!r}"
            )
        if self.latent:
            self._check_latent()
        elif self.sandwich_norm or any((
            self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim,
        )):
            raise ValueError(
                "sandwich_norm, q_lora_rank, kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim belong to latent_attention "
                "layers: layer_types names none"
            )
        if "sliding_attention" in self.layer_types and (
            not self.sliding_window or self.sliding_window % 8
        ):
            raise ValueError(
                "sliding_attention layers need a sliding_window that is a "
                "multiple of 8 (the ring leaf's rows)"
            )
        if not self.rope:
            raise ValueError(
                "the gated block has no learned positions: set rope=True"
            )
        self._check_routed()

    def _check_latent(self):
        if set(self.layer_types) != {"latent_attention"}:
            raise ValueError(
                "latent_attention layers share no stack with full_attention "
                "or sliding_attention ones: the cache is one latent leaf, "
                f"got {self.layer_types}"
            )
        for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                     "qk_rope_head_dim", "v_head_dim"):
            if getattr(self, name) <= 0:
                raise ValueError(f"latent_attention layers need {name} > 0")
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) rotates in "
                "pairs: it must be even"
            )
        for name, asked in (
            ("n_kv_heads", self.n_kv_heads not in (None, self.n_heads)),
            ("head_size", self.head_size is not None),
            ("layer_heads", bool(self.layer_heads)),
            ("sliding_window", bool(self.sliding_window)),
            ("attn_gate", self.attn_gate),
            ("rope_full", self.rope_full is not None),
        ):
            if asked:
                raise ValueError(
                    f"{name} does not apply to latent_attention layers: "
                    "every head reads the one latent row, head sizes are "
                    "qk_nope_head_dim / qk_rope_head_dim / v_head_dim, and "
                    "the rotary is plain rope_theta"
                )

    def _check_routed(self):
        routed = [l for l in range(self.n_layers) if l not in self.dense_layers]
        if routed and not (
            0 < self.n_experts <= self.n_experts_total - self.expert_first
            and 0 < self.moe_k <= self.n_experts_total and self.d_expert
        ):
            raise ValueError(
                "routed layers need n_experts (held) within n_experts_total "
                "from expert_first, moe_k and d_expert"
            )


def init_transformer(key, cfg: TransformerConfig):
    """Params pytree; block tensors carry a leading (n_layers, ...) axis
    (a gated stack: one dict a layer, :func:`_init_gated`)."""
    if cfg.gated:
        return _init_gated(key, cfg)
    ks = jax.random.split(key, 8)  # ks[7] only consumed by the MoE branch
    d, h, k, f, nl = (
        cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
    )
    s_d = 1.0 / jnp.sqrt(d)
    s_f = 1.0 / jnp.sqrt(f)

    def norm(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    if cfg.n_experts:
        e = cfg.n_experts
        ffn = {
            "moe": MoEParams(
                wg=norm(ks[4], (nl, d, e), s_d),
                w1=norm(ks[5], (nl, e, d, f), s_d),
                b1=jnp.zeros((nl, e, f)),
                w2=norm(ks[7], (nl, e, f, d), s_f),
                b2=jnp.zeros((nl, e, d)),
            )
        }
    else:
        ffn = {
            "w1": norm(ks[4], (nl, d, f), s_d),
            "b1": jnp.zeros((nl, f)),
            "w2": norm(ks[5], (nl, f, d), s_f),
            "b2": jnp.zeros((nl, d)),
        }
    if cfg.kv_heads == h:
        attn = {"wqkv": norm(ks[2], (nl, d, 3, h, k), s_d)}
    else:  # GQA: separate projections, fewer KV heads
        kq, kk = jax.random.split(ks[2])
        attn = {
            "wq": norm(kq, (nl, d, h, k), s_d),
            "wkv": norm(kk, (nl, d, 2, cfg.kv_heads, k), s_d),
        }
    return {
        "embed": norm(ks[0], (cfg.vocab_size, d), 0.02),
        "pos": norm(ks[1], (cfg.max_len, d), 0.02),
        "blocks": {
            "ln1_scale": jnp.ones((nl, d)),
            "ln1_bias": jnp.zeros((nl, d)),
            **attn,
            "wo": norm(ks[3], (nl, h, k, d), s_d),
            "ln2_scale": jnp.ones((nl, d)),
            "ln2_bias": jnp.zeros((nl, d)),
            **ffn,
        },
        "lnf_scale": jnp.ones((d,)),
        "lnf_bias": jnp.zeros((d,)),
        "head": norm(ks[6], (d, cfg.vocab_size), s_d),
    }


# block-weight leaves quantized for int8 decode, with the axes reduced
# by their matmuls (the scale is per-OUTPUT-channel: max|w| over the
# contraction axes). head contracts d (axis 0).
_INT8_BLOCK_AXES = {
    "wqkv": (1,), "wq": (1,), "wkv": (1,),
    "wo": (1, 2), "w1": (1,), "w2": (1,),
}


def _quantize_int8(w, axes):
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_decode_params(params, cfg: TransformerConfig):
    """Weight-only int8 quantization of the decode-streamed matmul
    weights (block projections/MLP + head), per-output-channel scales.

    Returns a params pytree of the same structure with each quantized
    leaf ``name`` stored int8 and a sibling ``name_scale`` f32 leaf;
    embeddings/positions (gather-read, not streamed per step) and
    norm scales/biases stay float. Decode paths dequantize inside the
    jitted program — XLA fuses the int8 read + convert + scale into the
    matmul operand, so the per-step HBM weight stream halves vs bf16.
    Pair with ``dataclasses.replace(cfg, decode_int8=True)`` for the
    fully-quantized path (int8 KV cache + int8 kernel). Leaving
    ``decode_int8=False`` with quantized params is the supported
    weight-only split: ``_w`` dequantizes int8 leaves by dtype, the KV
    cache stays at the compute dtype and the bf16 decode kernel runs
    unchanged — the winning composite under GQA, where the cache is
    already 3x smaller and the weight stream dominates (PERF.md r5
    crossover analysis).
    """
    if cfg.n_experts or cfg.latent:
        raise NotImplementedError(
            "int8 decode quantization does not cover MoE experts or "
            "latent attention yet: neither MoEParams (moe_ffn) nor the "
            "held experts' we_gate / we_up / we_down of a gated layer "
            "(moe_held_ffn) have scale leaves or a dequantising grouped "
            "product, nor do a latent layer's wq_a / wq_b / wkv_a / w_uk "
            "/ w_uv"
        )
    blocks = dict(params["blocks"])
    for name, axes in _INT8_BLOCK_AXES.items():
        if name in blocks:
            q, s = _quantize_int8(blocks[name], axes)
            blocks[name] = q
            blocks[name + "_scale"] = s
    out = dict(params)
    out["blocks"] = blocks
    hq, hs = _quantize_int8(params["head"], (0,))
    out["head"] = hq
    out["head_scale"] = hs
    return out


def _w(p, name, dtype):
    """Read a (possibly int8-quantized) weight leaf at compute dtype.

    For quantized leaves the dequant (convert + per-channel scale) is
    expressed inline so XLA fuses it into the consuming matmul's operand
    read — the HBM traffic is the int8 bytes, not a dequantized copy."""
    w = p[name]
    if w.dtype == jnp.int8:
        return (w.astype(jnp.float32) * p[name + "_scale"]).astype(dtype)
    return w.astype(dtype)


def transformer_shardings(mesh: Mesh, cfg: TransformerConfig | None = None):
    """Megatron TP layout over the mesh's model axis, as a shardings pytree
    mirroring ``init_transformer``'s output."""
    m = mesh_lib.MODEL_AXIS

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    rep = ns()
    if cfg is not None and cfg.n_experts:
        # experts one-per-device on the model axis; router replicated
        ffn = {
            "moe": MoEParams(
                wg=rep,
                w1=ns(None, m, None, None),
                b1=ns(None, m, None),
                w2=ns(None, m, None, None),
                b2=ns(None, m, None),
            )
        }
    else:
        ffn = {
            "w1": ns(None, None, m),  # column-parallel on d_ff
            "b1": ns(None, m),
            "w2": ns(None, m, None),  # row-parallel
            "b2": rep,
        }
    if cfg is not None and cfg.kv_heads != cfg.n_heads:
        # GQA: q column-parallel on heads; KV sharded on its head dim
        # when it divides the model axis, else replicated (the standard
        # MQA-on-TP layout — every rank holds the single KV head)
        kv_fits = cfg.kv_heads % mesh.shape[m] == 0
        attn = {
            "wq": ns(None, None, m, None),
            "wkv": ns(None, None, None, m, None) if kv_fits else rep,
        }
    else:
        attn = {"wqkv": ns(None, None, None, m, None)}
    return {
        # embed/pos sharded on d_model over the model axis (the
        # activation-sharded Megatron layout): the embedding cotangent
        # is produced d_model-sharded by the backward pass, so this
        # keeps grad and param shardings aligned — with replicated (or
        # data-dim0 FSDP) embeddings XLA has to full-rematerialize the
        # (V, D) grad to reshard it (the SPMD warning the round-1
        # multichip dryrun recorded)
        "embed": ns(None, m),
        "pos": ns(None, m),
        "blocks": {
            "ln1_scale": rep,
            "ln1_bias": rep,
            # column-parallel on heads: each model shard owns H/tp heads
            **attn,
            # row-parallel back to d_model (psum inserted by XLA)
            "wo": ns(None, m, None, None),
            "ln2_scale": rep,
            "ln2_bias": rep,
            **ffn,
        },
        "lnf_scale": rep,
        "lnf_bias": rep,
        "head": ns(None, m),  # vocab-sharded logits
    }


def _quantized_leaf_sharding(mesh: Mesh, weight_sharding, axes):
    """Sharding for an int8 leaf's per-channel scale: the weight's spec
    with the quantized (size-1 keepdims) axes unsharded. Scales are
    computed over the FULL reduction axis before placement, so a scale
    whose weight is sharded along that axis is a single global value —
    replicated there by construction."""
    spec = list(weight_sharding.spec)
    for ax in axes:
        if ax < len(spec):
            spec[ax] = None
    return NamedSharding(mesh, P(*spec))


def place_transformer_params(mesh: Mesh, params, cfg=None):
    """Place a params pytree (float or int8-quantized serving params)
    with the Megatron layout. Quantized pytrees (extra ``name_scale``
    leaves from :func:`quantize_decode_params`) get scale shardings
    derived from their weight's spec, so int8 serving runs under the
    same dp x tp mesh as bf16."""
    shardings = transformer_shardings(mesh, cfg)
    blocks = params["blocks"]
    if any(
        name in blocks and blocks[name].dtype == jnp.int8
        for name in _INT8_BLOCK_AXES
    ):
        sblocks = dict(shardings["blocks"])
        for name, axes in _INT8_BLOCK_AXES.items():
            if name + "_scale" in blocks:
                sblocks[name + "_scale"] = _quantized_leaf_sharding(
                    mesh, sblocks[name], axes
                )
        shardings = dict(shardings)
        shardings["blocks"] = sblocks
        if "head_scale" in params:
            shardings["head_scale"] = _quantized_leaf_sharding(
                mesh, shardings["head"], (0,)
            )
    return jax.tree.map(mesh_lib.place_global, params, shardings)


def serving_tp_shardings(mesh: Mesh, cfg: TransformerConfig,
                         lora: bool = False):
    """Exact-parity tensor-parallel SERVING layout over the mesh's model
    axis, as a shardings pytree mirroring ``init_transformer``.

    This is deliberately NOT :func:`transformer_shardings` (the training
    Megatron layout): row-parallel ``wo``/``w2`` there make XLA psum
    partial contractions, and the reassociated reduction drifts ~1e-6
    from the single-chip result — enough to flip sampled draws and
    break the serving engine's byte-identical parity bar. Here every
    COLUMN projection is sharded (wq/wqkv/wkv on heads, w1/b1 on d_ff,
    head on vocab) — each output element still reduces over the full
    replicated contraction dim in single-chip order — while every ROW
    projection (wo, w2) stays replicated and its sharded input
    activation is all-gathered first (:func:`_tp_replicate` inside the
    decode builders). Gathers are exact concatenations, so the whole
    forward is bitwise identical to TP=1; the price is shipping
    (B, D)/(B, d_ff) activations per layer instead of Megatron's one
    psum, plus replicated wo/w2 weight streams — the sharded attention
    (the part that scales with batch x context) is where the TP win
    lives."""
    m = mesh_lib.MODEL_AXIS
    tp = mesh.shape[m]
    if cfg.n_heads % tp or cfg.kv_heads % tp:
        raise ValueError(
            f"exact-TP serving needs tp ({tp}) dividing n_heads "
            f"({cfg.n_heads}) and kv_heads ({cfg.kv_heads})"
        )
    if cfg.n_experts:
        raise ValueError("exact-TP serving does not support MoE configs")

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    rep = ns()
    if cfg.kv_heads != cfg.n_heads:
        attn = {
            "wq": ns(None, None, m, None),
            "wkv": ns(None, None, None, m, None),
        }
    else:
        attn = {"wqkv": ns(None, None, None, m, None)}
    out = {
        "embed": rep,
        "pos": rep,
        "blocks": {
            "ln1_scale": rep,
            "ln1_bias": rep,
            **attn,
            "wo": rep,  # row projection: replicated, input gathered
            "ln2_scale": rep,
            "ln2_bias": rep,
            "w1": ns(None, None, m),  # column-parallel on d_ff
            "b1": ns(None, m),
            "w2": rep,  # row projection: replicated, input gathered
            "b2": rep,
        },
        "lnf_scale": rep,
        "lnf_bias": rep,
        "head": ns(None, m),  # vocab-sharded logits, gathered at the tail
    }
    if lora:
        # the LoRA attach points are both COLUMN projections, so the
        # bank follows the column layout: A factors replicated (their
        # r-dim contraction runs fully on every rank), B factors
        # sharded on the output dim — b_q's packed n_heads*head_dim
        # minor splits head-major, matching wq's head sharding; b_mlp
        # splits d_ff, matching w1. Deltas land shard-local before the
        # forced gathers, so batched LoRA under TP stays bitwise exact.
        out["lora"] = {
            "a_q": rep,
            "b_q": ns(None, None, None, m),
            "a_mlp": rep,
            "b_mlp": ns(None, None, None, m),
        }
    return out


def place_serving_tp_params(mesh: Mesh, params, cfg: TransformerConfig):
    """Place a (float or int8-quantized) serving params pytree with the
    exact-TP layout of :func:`serving_tp_shardings`; int8 ``name_scale``
    leaves get shardings derived from their weight's spec, exactly as
    :func:`place_transformer_params` does for the training layout."""
    shardings = serving_tp_shardings(mesh, cfg, lora="lora" in params)
    blocks = params["blocks"]
    if any(
        name in blocks and blocks[name].dtype == jnp.int8
        for name in _INT8_BLOCK_AXES
    ):
        sblocks = dict(shardings["blocks"])
        for name, axes in _INT8_BLOCK_AXES.items():
            if name + "_scale" in blocks:
                sblocks[name + "_scale"] = _quantized_leaf_sharding(
                    mesh, sblocks[name], axes
                )
        shardings = dict(shardings)
        shardings["blocks"] = sblocks
        if "head_scale" in params:
            shardings["head_scale"] = _quantized_leaf_sharding(
                mesh, shardings["head"], (0,)
            )
    return jax.tree.map(mesh_lib.place_global, params, shardings)


def serving_tp_cache_sharding(mesh: Mesh, cfg: TransformerConfig):
    """Sharding pytree for an ``init_caches`` allocation under exact-TP
    serving: the packed (nl, 2, B, Tpad, Hkv*K) buffer sharded on its
    head-major minor dim (each rank owns its kv heads' rows — writes
    and attention reads stay rank-local). The int8 per-row scale plane
    has a size-1 minor dim (one scale across ALL heads of a row,
    computed via an exact cross-shard max) and is replicated."""
    kv = NamedSharding(
        mesh, P(None, None, None, None, mesh_lib.MODEL_AXIS)
    )
    if cfg.decode_int8:
        return {"kv": kv, "scale": NamedSharding(mesh, P())}
    return kv


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def _rope_tables(positions, head_dim: int, dtype, base: float = 10000.0):
    """(cos, sin) tables for RoPE at the given positions: (..., head_dim/2)."""
    positions = jnp.asarray(positions)  # accept plain int positions
    half = head_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., half)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def _apply_rope(x, cos, sin):
    """Rotate pairs of head-dim channels. x: (..., head_dim); cos/sin
    broadcastable to (..., head_dim/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


# KV caches are padded to a multiple of this row count (the sublane tile;
# masked rows beyond `pos` contribute nothing, so padding is only wasted
# bandwidth — 8 keeps it under 1.5% at serving lengths)
_DECODE_PAD_T = 8


def _decode_tpad(total: int) -> int:
    """Rows of a decode slab that holds ``total`` positions (the rule
    ``init_caches`` explains)."""
    if total <= 1024:
        return -(-total // _DECODE_PAD_T) * _DECODE_PAD_T
    return -(-total // 512) * 512


def kv_row_write(cfg: "TransformerConfig") -> str:
    """Who places a decode substep's new K and V rows (a latent stack's
    one latent row) in the cache: ``"kernel"`` where the bf16 / f32 walk
    kernel runs (it writes the row it is about to read,
    ``flash_decode_attention_write`` / ``latent_decode_attention_write``),
    ``"xla"`` for the int8 slab (a scatter, or a
    ``dynamic_update_slice``, before the grid kernel) and on the dense
    path (``decode_kernel=False``: tp, LoRA banks). A fact of the
    configuration the step programs are traced with; the engine
    reports it."""
    return "kernel" if cfg.decode_kernel and not cfg.decode_int8 else "xla"


def kv_cache_rows(cfg: "TransformerConfig") -> str:
    """What a position's cache row is: ``"kv"`` a K and a V plane of
    ``Hkv x K`` values a layer, ``"kv+ring"`` the same in two leaves (a
    slab and a ring of ``sliding_window`` rows), ``"latent"`` one plane
    of ``kv_lora_rank + qk_rope_head_dim`` values that is key and value
    of every head. A fact of the configuration, like
    :func:`kv_row_write`; the engine reports it."""
    if cfg.latent:
        return "latent"
    return "kv+ring" if cfg.gated and cfg.layers_of("window") else "kv"


def flash_layout(cfg: "TransformerConfig", mesh: Mesh | None = None) -> str:
    """The layout in which the training block (``transformer_apply`` with
    ``use_flash``) hands q, k and v to the flash kernels: ``"packed"``,
    (B, T, H*K) as the projections write them, a kernel block being one
    128-lane group of whole heads (``flash_attention_packed``; no
    transpose between the products and the kernels); or ``"bhtd"``,
    (B, H, T, K) (``flash_attention_trainable``). Packed wherever the
    block can see that it holds: plain multi-head attention, no rotary
    (which turns pairs of lanes inside a head), a head size that divides
    128, and whole 128-lane groups on every device of the mesh's model
    axis. A fact of the configuration and the mesh, and the one the
    block itself branches on; a trace shows it as the kernels' names
    (``flash_fwd_packed`` / ``flash_bwd_packed``)."""
    shards = 1
    if mesh is not None and mesh_lib.MODEL_AXIS in mesh.axis_names:
        shards = mesh.shape[mesh_lib.MODEL_AXIS]
    packed = (
        cfg.kv_heads == cfg.n_heads
        and not cfg.rope
        and not cfg.sequence_parallel
        and 128 % cfg.head_dim == 0
        and (cfg.n_heads * cfg.head_dim) % (128 * shards) == 0
    )
    return "packed" if packed else "bhtd"


def _decode_write_at(pos, ring: bool, leaf):
    """The cache row of ``leaf`` (layers, planes, B, rows, width) that
    position ``pos`` is written to: ``pos % rows`` on a ring, ``pos`` on
    a slab. A scalar ``pos`` past a slab's end writes the last row, as
    the ``dynamic_update_slice`` it replaces clamped (speculative
    decoding's scratch positions); a per-row ``pos`` there writes
    nothing, as the scatter it replaces dropped it."""
    rows = leaf.shape[3]
    if ring:
        return pos % rows
    return jnp.minimum(pos, rows - 1) if jnp.ndim(pos) == 0 else pos


def _flash_seq_ok(t: int) -> bool:
    """Sequence lengths the training flash kernel accepts: sublane-
    aligned (%8 — Mosaic rejects e.g. a 100-row block shape on real
    TPU) and either one block (<=128) or lane-block-aligned (%128). ONE
    predicate shared by the training block (which raises) and bulk
    prefill (which falls back to dense) so the rule cannot drift."""
    return t % 8 == 0 and (t <= 128 or t % 128 == 0)


def _flash_blocks(t: int) -> tuple[int, int]:
    """(block_q, block_k) for the flash kernel at sequence length t:
    1024/1024 preferred (measured fastest on v5e at T=1024 AND T=8192
    with the fused backward kernel — the r2 512/1024 winner predates
    it), falling back to the largest candidate that divides t — callers
    only guarantee t <= 128 or t % 128 == 0. ONE implementation shared
    by the training block and bulk prefill so kernel selection cannot
    drift. The packed kernels (``flash_layout``) were measured on their
    own at 8 x 16 x 1,024 x 64 and prefer the same
    (``scripts/flash_train_bench.py``, forward + backward: 1,024/1,024
    1,663 us a call, 512/1,024 1,868, 512/512 1,849 though it skips a
    tile of four, 1,024/512 2,173; PERF.md, PR 32). Since PR 35 a
    1,024/1,024 tile that crosses the diagonal is walked in four causal
    bands of 256 rows inside the kernel body (``pallas_kernels.
    _band_rows``), and the forward of a tile that is alone in its rows
    holds its scores transposed: 1,154-1,160 us a call where the whole
    square read 1,654-1,665; bands of 512 read 1,243, of 128 1,429
    (PERF.md, PR 35)."""

    def pick(pref: int) -> int:
        if t <= pref:
            return t
        for b in (pref, 512, 256, 128):
            if b <= pref and t % b == 0:
                return b
        return 128  # t % 128 == 0 guaranteed by the callers

    # (r4: a chained-harness sweep preferred 512/1024 for the long-T
    # forward by -11%, but the full bench measured it 3% SLOWER in situ —
    # standalone ordering does not transfer; the bench window is the
    # arbiter, so the forward keeps 1024/1024.)
    return pick(1024), pick(1024)


def _flash_bwd_blocks(t: int) -> tuple[int, int] | tuple[None, None]:
    """Backward-kernel blocks: 512/2048 at long T (measured -18% kernel
    time vs 1024/1024 at T=8192 on v5e with the fused backward — the
    wide KV block quarters the dq HBM revisit count and halves the
    invisible-cell DMA; the short Q block keeps the f32 s/p tiles small
    enough that Mosaic doesn't spill). (None, None) = inherit the
    forward blocks (r3 sweep: 1024/1024 still wins at T=1024)."""
    if t >= 4096 and t % 2048 == 0:
        return 512, 2048
    return None, None


def _project_qkv(cfg: TransformerConfig, p, h_in):
    """Shared QKV projection for all sequence-shaped forwards (training
    block and bulk prefill): h_in (B, T, D) -> q (B, H, T, K) and the
    UNexpanded k/v (B, H_kv, T, K). One implementation so GQA/MHA
    layouts cannot drift between the paths."""
    if cfg.kv_heads != cfg.n_heads:
        q = jnp.einsum("btd,dhk->bhtk", h_in, _w(p, "wq", h_in.dtype))
        kv = jnp.einsum(
            "btd,dshk->sbhtk", h_in, _w(p, "wkv", h_in.dtype)
        )
        return q, kv[0], kv[1]
    qkv = jnp.einsum(
        "btd,dshk->sbhtk", h_in, _w(p, "wqkv", h_in.dtype)
    )
    return qkv[0], qkv[1], qkv[2]


def _project_qkv_packed(p, h_in):
    """The training block's projection under ``flash_layout`` "packed":
    h_in (B, T, D) -> q, k, v, each (B, T, H*K), heads side by side on
    the minor dimension, which is what a ``[B*T, D] x [D, H*K]`` product
    writes. Three products over the planes of the ``wqkv`` leaf viewed
    ``[D, 3, H*K]`` (a free reshape: the leaf keeps its shape, its
    sharding and its checkpoints), so that neither the kernels' operands
    nor their cotangents are slices of a wider array. (Sliced as
    ``[D, 3, H, K]``, XLA:TPU folded the reshape into dq's
    weight-gradient product and transposed dq for it.)"""
    w = _w(p, "wqkv", h_in.dtype)  # (D, 3, H, K)
    w = w.reshape(w.shape[0], 3, -1)
    return tuple(
        jnp.einsum("btd,df->btf", h_in, w[:, s]) for s in range(3)
    )


def _expand_kv(cfg: TransformerConfig, k_r, v_r):
    """GQA group-repeat (no-op for MHA): (B, H_kv, T, K) -> (B, H, T, K)."""
    g = cfg.n_heads // cfg.kv_heads
    if g == 1:
        return k_r, v_r
    return jnp.repeat(k_r, g, axis=1), jnp.repeat(v_r, g, axis=1)


def _tp_replicate(x, tp_mesh):
    """Force ``x`` replicated (an all-gather of its sharded axis) under
    the exact-TP serving layout; identity when no mesh is given.

    This is the load-bearing primitive of byte-exact tensor parallelism:
    every matmul whose CONTRACTION dim would otherwise arrive sharded
    (attention out @ wo, gelu hidden @ w2) gathers its activation first
    and contracts against a REPLICATED weight, so the reduction runs in
    the single-chip flop order. Left to GSPMD, a sharded contraction
    becomes partial-sums + psum — a different association that drifts
    ~1e-6 (measured on this backend), which breaks the engine's
    byte-identical parity bar."""
    if tp_mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(tp_mesh, P())
    )


def tp_collective_contract(
    cfg: TransformerConfig, n_substeps: int = 1,
    scanned: bool = False,
) -> dict[str, int]:
    """The DECLARED collective signature of one TP serving program with
    ``n_substeps`` fused decode substeps (the contract the static
    auditor enforces — see ``analysis/audit.py``).

    The exact-TP layout emits exactly one replication constraint
    (:func:`_tp_replicate`, lowering to ``sharding_constraint``) per
    sharded contraction: the attention output and the gelu hidden in
    each layer, plus the logits at the tail — ``2 * n_layers + 1`` per
    substep. ``scanned`` is for programs that run the blocks under one
    ``lax.scan`` (prefill with ``cfg.scan_layers``): the two per-layer
    constraints then appear ONCE in the scan body jaxpr, so the
    syntactic count is ``2 + 1`` regardless of depth. Anything else (a
    stray ``psum``, an extra gather, a dropped constraint) changes the
    flop association and silently breaks the byte-exact TP=N ≡ TP=1
    parity bar, so drift from this count is a hard audit failure, not
    a tunable."""
    per_layer = 1 if scanned else cfg.n_layers
    return {
        "sharding_constraint": n_substeps * (2 * per_layer + 1),
    }


def _mlp(p, h_in, tp_mesh=None, delta1=None, sel=None):
    """Shared dense FFN (gelu) over (..., D) activations.

    Under the exact-TP serving layout (``tp_mesh`` set) ``w1``/``b1``
    are column-sharded on d_ff and the gelu hidden is all-gathered
    before the ``w2`` matmul against a REPLICATED ``w2`` — the d_ff
    reduction then runs in the single-chip order, so the output is
    bitwise identical to the unsharded path (a row-parallel ``w2``
    would psum partial sums in a different association).

    ``delta1`` (optional) is a batched-LoRA pre-activation delta added
    to the w1 projection before the gelu, gated per row by ``sel``
    (bool, broadcastable to the hidden): rows with ``sel`` False keep
    the exact base activations — adding an all-zero delta instead
    would still flip -0.0 bits and break the adapter-0 parity bar."""
    h = (
        jnp.einsum("...d,df->...f", h_in, _w(p, "w1", h_in.dtype))
        + p["b1"].astype(h_in.dtype)
    )
    if delta1 is not None:
        h = jnp.where(sel, h + delta1, h)
    h = jax.nn.gelu(h)
    h = _tp_replicate(h, tp_mesh)
    return (
        jnp.einsum("...f,fd->...d", h, _w(p, "w2", h_in.dtype))
        + p["b2"].astype(h_in.dtype)
    )


def init_lora_bank(
    key, cfg: TransformerConfig, n_adapters: int, rank: int,
    scale: float = 0.5,
):
    """Stacked low-rank adapter bank for batched-LoRA serving: N
    adapters' (A, B) factors for the q projection and the MLP w1
    projection of every layer, as FOUR stacked device arrays so one
    fused decode step can gather each KV slot's adapter rows by index
    (S-LoRA/Punica style) instead of swapping weights per request.

    Layout (``nl`` layers, ``N`` adapters, rank ``r``)::

        a_q   (nl, N, d_model, r)    b_q   (nl, N, r, n_heads*head_dim)
        a_mlp (nl, N, d_model, r)    b_mlp (nl, N, r, d_ff)

    The leading layer axis matches ``params["blocks"]`` so prefill's
    ``lax.scan`` scans the bank alongside the blocks. Adapter index 0
    is the ZERO adapter (both factors zeroed): slots carrying 0 take
    the base-model path bitwise (the forward selects, not adds — see
    ``_mlp``). Unlike training-style LoRA init (B=0), adapters 1..N-1
    get random nonzero A *and* B so distinct adapters produce distinct
    outputs out of the box — the serving tests and the bench need
    observable divergence without a training loop.

    Attach points are activation-level deltas (q after projection /
    pre-RoPE, MLP pre-gelu), so GQA (wq) and MHA (wqkv) configs share
    one code path; both are COLUMN projections under the exact-TP
    layout, so the bank shards with ``serving_tp_shardings`` (A
    replicated, B on its output dim) and stays bitwise exact.
    """
    if cfg.n_experts:
        raise ValueError("batched LoRA does not support MoE configs")
    if n_adapters < 2:
        raise ValueError(
            f"n_adapters must be >= 2 (index 0 is the zero adapter), "
            f"got {n_adapters}"
        )
    if rank < 1:
        raise ValueError(f"rank must be >= 1, got {rank}")
    nl, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    hk = cfg.n_heads * cfg.head_dim
    ks = jax.random.split(key, 4)

    def factor(k, shape):
        a = scale * jax.random.normal(k, shape, jnp.float32)
        return a.at[:, 0].set(0.0)  # adapter 0 = zero adapter

    return {
        "a_q": factor(ks[0], (nl, n_adapters, d, rank)),
        "b_q": factor(ks[1], (nl, n_adapters, rank, hk)),
        "a_mlp": factor(ks[2], (nl, n_adapters, d, rank)),
        "b_mlp": factor(ks[3], (nl, n_adapters, rank, f)),
    }


def _lora_delta(h_in, a, b):
    """Per-row low-rank delta: activations ``h_in`` (B, T, D) through
    each row's gathered adapter factors ``a`` (B, D, r), ``b``
    (B, r, O) -> (B, T, O). Two thin einsums (rank r contraction) —
    decode-step cost is O(B*r*(D+O)), noise next to the weight
    stream."""
    u = jnp.einsum("btd,bdr->btr", h_in, a.astype(h_in.dtype))
    return jnp.einsum("btr,bro->bto", u, b.astype(h_in.dtype))


def transformer_apply(
    cfg: TransformerConfig, mesh: Mesh | None = None,
    upcast_logits: bool = True,
):
    """Build apply(params, tokens) -> (logits (B, T, V), aux_loss), causal.

    ``mesh`` is required for the MoE (``cfg.n_experts``) and
    ``cfg.sequence_parallel`` modes — both embed shard_map collectives
    inside the jitted forward; the dense/dp-only model needs no mesh.
    ``upcast_logits=False`` returns logits in the compute dtype — the
    training path pairs it with the fused CE
    (:mod:`deeplearning4j_tpu.ops.fused_ce`) so no f32 (B, T, V) copy is
    ever materialized.
    """
    _gated_refuses(
        cfg, "training (transformer_apply / transformer_train_step)",
        "_gated_block has no backward-ready attention for a window or a "
        "latent row, moe_held_ffn no auxiliary load-balancing loss, and "
        "transformer_shardings no layout for per-layer parameter dicts",
    )
    if (cfg.n_experts or cfg.sequence_parallel) and mesh is None:
        raise ValueError("MoE / sequence-parallel modes need a mesh")
    if cfg.use_flash and cfg.sequence_parallel:
        raise ValueError(
            "use_flash and sequence_parallel are mutually exclusive: the "
            "sequence-parallel path attends via the ring, not the local "
            "flash kernel"
        )
    if cfg.rope and cfg.head_dim % 2:
        raise ValueError(
            f"rope needs an even head_dim, got {cfg.head_dim} "
            f"(d_model {cfg.d_model} / n_heads {cfg.n_heads})"
        )
    if cfg.n_experts:
        if cfg.n_experts != mesh.shape[mesh_lib.MODEL_AXIS]:
            raise ValueError(
                f"n_experts ({cfg.n_experts}) must equal the mesh's model "
                f"axis size ({mesh.shape[mesh_lib.MODEL_AXIS]})"
            )
        token_spec = (
            P(None, mesh_lib.DATA_AXIS, None)
            if cfg.sequence_parallel
            else P(mesh_lib.DATA_AXIS, None, None)
        )
        moe = moe_ffn(
            mesh,
            k=cfg.moe_k,
            capacity_factor=cfg.moe_capacity_factor,
            token_spec=token_spec,
        )
    if cfg.sequence_parallel:
        # sequence ring over the data axis; heads stay on the model axis
        ring = ring_attention(
            mesh, causal=True, head_axis=mesh_lib.MODEL_AXIS
        )

    packed = cfg.use_flash and flash_layout(cfg, mesh) == "packed"

    def flash_attend(t: int):
        """The flash entry of this configuration's layout at sequence
        length ``t``: (q, k, v) -> o, all in that layout."""
        from deeplearning4j_tpu.ops.pallas_kernels import (
            flash_attention_packed,
            flash_attention_trainable,
        )

        if not _flash_seq_ok(t):
            raise ValueError(
                f"use_flash needs a seq len that is a multiple of 8 "
                f"and either <= 128 or a multiple of 128, got {t}"
            )
        bq, bk = _flash_blocks(t)
        bbq, bbk = _flash_bwd_blocks(t)
        blocks = dict(
            causal=True, block_q=bq, block_k=bk,
            bwd_block_q=bbq, bwd_block_k=bbk,
        )
        if packed:
            flash = functools.partial(
                flash_attention_packed, head_dim=cfg.head_dim, **blocks
            )
        else:
            flash = functools.partial(
                flash_attention_trainable, layout="bhtd", **blocks
            )
        if mesh is not None:
            # compiled, the kernel is a custom call that GSPMD
            # cannot partition (the TPU lowering refuses it outside
            # a fully manual region): each device runs it on its
            # own (batch, heads) shard — attention mixes neither.
            # Packed, the heads are the minor dimension, sharded in
            # whole 128-lane groups (flash_layout)
            axes = mesh.axis_names
            data = mesh_lib.DATA_AXIS if mesh_lib.DATA_AXIS in axes else None
            model = (
                mesh_lib.MODEL_AXIS if mesh_lib.MODEL_AXIS in axes else None
            )
            spec = P(data, None, model) if packed else P(data, model)
            flash = jax.shard_map(
                flash, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
        return flash

    def attend_bhtd(p, h_in):
        # attention sublayer — internally (B, H, T, K) layout so the
        # flash kernel's (B*H, T, K) view is a free reshape; the bthd
        # layout cost ~3ms/step of physical transposes at GPT-2-small
        # scale (B=16, T=1024)
        q_h, k_r, v_r = _project_qkv(cfg, p, h_in)
        if cfg.rope:
            t = q_h.shape[2]
            cos, sin = _rope_tables(
                jnp.arange(t), cfg.head_dim, q_h.dtype
            )  # (T, hd/2)
            cos = cos[None, None, :, :]
            sin = sin[None, None, :, :]
            q_h = _apply_rope(q_h, cos, sin)
            k_r = _apply_rope(k_r, cos, sin)
        k_h, v_h = _expand_kv(cfg, k_r, v_r)
        if cfg.sequence_parallel:
            # the ring path works on (B, T, H, K) — the sequence axis is
            # the sharded one; transposes here are per-shard and cheap
            # next to the ring collectives. Named so remat saves the
            # ring output instead of re-running its collectives in the
            # backward pass.
            return checkpoint_name(
                ring(
                    q_h.transpose(0, 2, 1, 3),
                    k_h.transpose(0, 2, 1, 3),
                    v_h.transpose(0, 2, 1, 3),
                ).transpose(0, 2, 1, 3),
                "attn_out",
            )
        if cfg.use_flash:
            # no attn_out naming here: the kernel's own flash_out
            # residual is the saveable (naming both would store the
            # same tensor twice and cost ~450MB at GPT-2-small scale)
            return flash_attend(q_h.shape[2])(q_h, k_h, v_h)
        return checkpoint_name(
            attention(q_h, k_h, v_h, causal=True, layout="bhtd"),
            "attn_out",
        )

    def block(x, p):
        h_in = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        if packed:
            # (B, T, H*K) from the projections through the kernels to
            # wo: no layout op on an activation, forward or backward
            # (the (B, H, T, K) path's layout copies were 8% of the
            # gpt2-medium step: PERF.md, PR 32)
            q, k, v = _project_qkv_packed(p, h_in)
            o = flash_attend(x.shape[1])(q, k, v)
            wo = _w(p, "wo", x.dtype)
            attn = jnp.einsum(
                "btf,fd->btd", o, wo.reshape(-1, wo.shape[-1])
            )
        else:
            attn = jnp.einsum(
                "bhtk,hkd->btd", attend_bhtd(p, h_in),
                _w(p, "wo", x.dtype),
            )
        x = x + attn
        # ffn sublayer: dense MLP or routed MoE
        h_in = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
        if cfg.n_experts:
            moe_params = jax.tree.map(
                lambda a: a.astype(x.dtype), p["moe"]
            )
            y, aux = moe(moe_params, h_in)
            x = x + y
        else:
            x = x + _mlp(p, h_in)
            aux = jnp.zeros((), x.dtype)
        return x, aux

    if cfg.remat:
        if cfg.remat_policy == "dots_no_batch":
            # also save the flash-attention custom-call outputs by name
            # (attn_out plus the kernel's internal out/lse residuals —
            # they are not dots, and without the names the policy
            # re-runs the whole pallas forward inside the backward pass)
            body = jax.checkpoint(
                block,
                policy=jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        "attn_out", "flash_out", "flash_lse"
                    ),
                ),
            )
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(block)
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} "
                "(expected 'dots_no_batch' or 'full')"
            )
    else:
        body = block

    def apply(params, tokens):
        b, t = tokens.shape
        x = params["embed"][tokens] + params["pos"][:t]
        x = x.astype(cfg.compute_dtype)
        if cfg.scan_layers:
            x, aux = lax.scan(body, x, params["blocks"])
        else:
            auxes = []
            for i in range(cfg.n_layers):
                p_i = jax.tree.map(lambda a: a[i], params["blocks"])
                x, a = body(x, p_i)
                auxes.append(a)
            aux = jnp.stack(auxes)
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        # head matmul in compute dtype (bf16 hits the MXU at full rate —
        # the f32-weight variant measured ~3x slower fwd+bwd on v5e and
        # the head is ~30% of GPT-2-small's FLOPs), then upcast so the
        # softmax/CE runs in f32. The upcast also keeps the backward
        # fast: d_logits arrives f32 and is cast to bf16 *before* the
        # two backward matmuls.
        logits = jnp.einsum(
            "btd,dv->btv", x, params["head"].astype(x.dtype)
        )
        if upcast_logits:
            logits = logits.astype(jnp.float32)
        return logits, jnp.sum(aux.astype(jnp.float32))

    return apply


def transformer_loss(cfg: TransformerConfig, mesh: Mesh | None = None):
    """Next-token cross-entropy (+ MoE aux term): loss(params, tokens)
    with tokens (B, T+1). Uses the memory-fused CE on compute-dtype
    logits — no f32 (B, T, V) materialization in either direction."""
    from deeplearning4j_tpu.ops.fused_ce import (
        cross_entropy_with_integer_labels,
    )

    apply = transformer_apply(cfg, mesh, upcast_logits=False)

    if cfg.sequence_parallel:
        # keep the model's T equal to the (shard-divisible) input length:
        # feed all T tokens and mask the final position instead of
        # slicing the sequence-sharded axis to an uneven T-1
        def loss(params, tokens):
            b, t = tokens.shape
            logits, aux = apply(params, tokens)
            targets = jnp.roll(tokens, -1, axis=1)
            ce_tok = cross_entropy_with_integer_labels(logits, targets)
            mask = (jnp.arange(t) < t - 1).astype(ce_tok.dtype)[None, :]
            ce = jnp.sum(ce_tok * mask) / (jnp.sum(mask) * b)
            return ce + cfg.aux_coef * aux
    else:
        def loss(params, tokens):
            logits, aux = apply(params, tokens[:, :-1])
            ce = cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]
            ).mean()
            return ce + cfg.aux_coef * aux

    return loss


# -- the gated block: layers of two kinds, experts held as a share ----------
#
# One stack (``cfg.layer_types`` set) whose layers differ in query heads,
# rotary scheme, attention span and MLP: RMSNorm, GQA at an explicit head
# size, a per-head sigmoid gate on the attention output, a SwiGLU MLP in
# the dense layers and routed experts plus a shared expert elsewhere
# (``parallel/expert_parallel.py: moe_held_ffn``). The arithmetic of a
# layer is ONE function, :func:`_gated_block`; ``forward_one``, ``prefill``
# and ``forward_chunk`` hand it their own way to attend (one row against
# the cache, the whole sequence, a chunk against the cache). Serving only.
#
# The cache is two leaves, one a layer kind, each (layers of that kind, 2,
# B, rows, Hkv*K): "full" at the decode length, "window" a ring of
# ``sliding_window`` rows in which position t lives at t % window. Keys are
# cached rotated, so a ring's order does not matter to the softmax.
# A stack of latent layers has ONE leaf, "latent": (layers, 1, B, rows,
# latent_row_width): a position's normed latent and rotated shared key in
# one plane, which is the key and the value of every head.

def full_cache_leaf(caches):
    """The leaf of a cache pytree (arrays or shapes) whose rows run to
    the decode length: the array itself, ``kv`` of an int8 cache,
    ``full`` of a cache grouped by layer kind, ``latent`` of a stack of
    latent layers."""
    if isinstance(caches, dict):
        return next(caches[k] for k in ("kv", "full", "latent") if k in caches)
    return caches


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def yarn_inv_freq(rot_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float):
    """YaRN inverse frequencies of ``rot_dim // 2`` channel pairs, as
    ``transformers``' ``_compute_yarn_parameters`` gives them: pair i
    keeps its own frequency ``theta^(-2i/rot_dim)`` below the pair whose
    wavelength makes ``beta_fast`` turns in ``original_max`` positions,
    is divided by ``factor`` above the pair that makes ``beta_slow``, and
    blends linearly between. float64 on the host: a constant table."""
    half = rot_dim // 2
    i = np.arange(half, dtype=np.float64)
    freq = theta ** (-i / half)

    def pair_of(turns):
        return (rot_dim * np.log(original_max / (2 * np.pi * turns))
                / (2 * np.log(theta)))

    low = max(np.floor(pair_of(beta_fast)), 0)
    high = min(np.ceil(pair_of(beta_slow)), rot_dim - 1)
    keep = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - keep) * freq / factor + keep * freq


def _gated_rope(cfg: TransformerConfig, kind: str, positions, dtype):
    """(cos, sin) of one layer kind at ``positions`` (...,): (..., rot/2)
    tables in ``dtype``; rot = 2 x the last axis is how many leading
    channels of a head rotate. Window layers: plain, whole head. Full
    layers: ``cfg.rope_full`` (YaRN over part of the head, tables
    scaled by its attention_factor), or as window layers without it.
    Latent layers: plain over the ``qk_rope_head_dim`` rotary channels."""
    full = cfg.rope_full_settings if kind == "full" else None
    if full is None:
        half = (cfg.qk_rope_head_dim if kind == "latent"
                else cfg.head_dim) // 2
        inv = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
        mscale = 1.0
    else:
        rot = int(cfg.head_dim * full.get("partial_rotary_factor", 1.0))
        inv = yarn_inv_freq(
            rot, full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"],
            full["beta_fast"], full["beta_slow"],
        )
        mscale = full.get("attention_factor")
        if mscale is None:
            mscale = 0.1 * np.log(full["factor"]) + 1.0
    ang = (jnp.asarray(positions)[..., None].astype(jnp.float32)
           * jnp.asarray(inv, jnp.float32))
    return ((jnp.cos(ang) * mscale).astype(dtype),
            (jnp.sin(ang) * mscale).astype(dtype))


def _rotate(x, cos, sin):
    """Rotate-half over the first ``2 * cos.shape[-1]`` channels of
    ``x`` (..., K); the rest pass."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return _apply_rope(x, cos, sin)
    return jnp.concatenate(
        [_apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1
    )


# scores of one dense attention above this many elements go one KV head
# at a time (a 1,024-row chunk against 4,096 rows is 200 M scores)
_DENSE_SCORES_AT_ONCE = 32 * 1024 * 1024


def _attend_dense(q, k, v, mask):
    """Masked attention without a kernel. ``q`` (B, C, H, K); ``k``
    (B, T, Hkv, K) and ``v`` (B, T, Hkv, Kv), query head j reading KV
    head j // (H / Hkv); ``mask`` (B or 1, C, T) bool, True where the
    key is visible (every query sees at least one). Softmax in float32.
    Returns (B, C, H, Kv)."""
    b, c, h, kd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    qg = q.reshape(b, c, hkv, h // hkv, kd)
    scale = 1.0 / float(np.sqrt(kd))

    def one_kv_head(qkv):
        qh, kh, vh = qkv  # (B, C, G, K), (B, T, K), (B, T, K)
        att = jnp.einsum(
            "bcgk,btk->bgct", qh, kh, preferred_element_type=jnp.float32
        ) * scale
        att = jnp.where(mask[:, None], att, -jnp.inf)
        w = jax.nn.softmax(att, axis=-1).astype(vh.dtype)
        return jnp.einsum("bgct,btk->bcgk", w, vh)

    heads_first = (
        qg.transpose(2, 0, 1, 3, 4), k.transpose(2, 0, 1, 3),
        v.transpose(2, 0, 1, 3),
    )
    if b * h * c * t > _DENSE_SCORES_AT_ONCE:
        o = lax.map(one_kv_head, heads_first)
    else:
        o = jax.vmap(one_kv_head)(heads_first)
    return o.transpose(1, 2, 0, 3, 4).reshape(b, c, h, v.shape[-1])


def _attend_window(q, k, v, w: int):
    """Causal attention over the last ``w`` keys (the query's own
    included) of a whole sequence from position 0: ``q`` (B, T, H, K),
    ``k``, ``v`` (B, T, Hkv, K). With T a multiple of ``w`` past it, the
    queries go in blocks of ``w`` rows against their own block and the
    one before: T x 2w scores instead of T x T."""
    b, t, h, kd = q.shape
    own = jnp.arange(t)
    if t <= w or t % w:
        mask = (own[None, :] <= own[:, None]) & (own[:, None] - own[None, :] < w)
        return _attend_dense(q, k, v, mask[None])
    nb = t // w

    def blocks(x):  # (B, T, ...) -> (B * nb, w, ...)
        return x.reshape((b * nb, w) + x.shape[2:])

    def with_previous(x):  # (B, T, ...) -> (B * nb, 2w, ...)
        before = jnp.concatenate([jnp.zeros_like(x[:, :w]), x[:, :-w]], axis=1)
        return jnp.concatenate([blocks(before), blocks(x)], axis=1)

    i, j = jnp.arange(w)[:, None], jnp.arange(2 * w)[None, :]
    band = (j > i) & (j <= i + w)  # key j - w of the block, query i
    first = band & (j >= w)  # block 0 has nothing before it
    mask = jnp.tile(
        jnp.where((jnp.arange(nb) == 0)[:, None, None], first, band), (b, 1, 1)
    )
    o = _attend_dense(blocks(q), with_previous(k), with_previous(v), mask)
    return o.reshape(b, t, h, kd)


def latent_row_width(cfg: TransformerConfig) -> int:
    """Stored width of a latent cache row: ``cfg.latent_row`` values
    rounded up to whole 128-lane tiles (576 -> 640), the padding zero.
    The TPU tiles the minor dimension by 128 lanes whatever is asked, so
    the bytes are the same; stating them makes every slice of the decode
    kernel lane-aligned."""
    return -(-cfg.latent_row // 128) * 128


def _latent_scale(cfg: TransformerConfig) -> float:
    """The softmax scale of latent attention, ``1 / sqrt(n + p)``."""
    return 1.0 / float(np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))


def _latent_dense(q_lat, rows, mask, r_kv: int):
    """Latent attention in its absorbed form, without a kernel.
    ``q_lat`` (B, C, H, R): a head's query folded onto the latent, then
    its rotated part, the softmax scale included, then zeros; ``rows``
    (B, T, R): cache rows, the normed latent (``r_kv`` values), the
    rotated shared key, then the same lane padding; ``mask`` (B or 1, C, T) bool. One row is key (all R values)
    and value (its first ``r_kv``) of every head. Softmax in float32;
    heads go in groups that keep the scores under
    ``_DENSE_SCORES_AT_ONCE``. Returns (B, C, H, r_kv)."""
    b, c, h, r = q_lat.shape
    t = rows.shape[1]
    keys, vals = rows, rows[..., :r_kv]
    group = max(
        g for g in range(1, h + 1)
        if h % g == 0 and (g == 1 or b * g * c * t <= _DENSE_SCORES_AT_ONCE)
    )

    def some_heads(qh):  # (B, C, G, R)
        att = jnp.einsum(
            "bcgr,btr->bgct", qh, keys, preferred_element_type=jnp.float32
        )
        att = jnp.where(mask[:, None], att, -jnp.inf)
        w = jax.nn.softmax(att, axis=-1).astype(vals.dtype)
        return jnp.einsum("bgct,btr->bcgr", w, vals)

    qg = q_lat.reshape(b, c, h // group, group, r).transpose(2, 0, 1, 3, 4)
    o = lax.map(some_heads, qg) if group < h else some_heads(qg[0])[None]
    return o.transpose(1, 2, 0, 3, 4).reshape(b, c, h, r_kv)


class LatentAttend(NamedTuple):
    """A caller's way to reach a latent layer's keys, and to cache them
    (:func:`_gated_block`'s ``attend`` for such a layer). ``absorbed``
    False: ``fn(q, k, v, row)`` over keys and values expanded a head
    (:func:`_latent_expanded`); True: ``fn(q_lat, row)`` over the cached
    rows themselves (:func:`_latent_absorbed`). ``row`` (B, T, r_kv + p)
    is what the position caches; either returns a head's part of the
    output, (B, T, H, v) or (B, T, H, r_kv)."""

    absorbed: bool
    fn: Callable


def _latent_expanded(cfg: TransformerConfig, p, qn, qp, row, attend_qkv):
    """Latent attention as published (prefill's form): the latent rows
    are expanded to a key and a value a head, ``k = [c W_uk, k_p]`` (the
    rotated part shared by all heads) and ``v = c W_uv``, and
    ``attend_qkv(q, k, v)`` (q, k (B, T, H, n + p), v (B, T, H, v) ->
    (B, T, H, v)) attends over them."""
    dt = qn.dtype
    r = cfg.kv_lora_rank
    c, kp = row[..., :r], row[..., r:]
    kn = jnp.einsum("btc,chn->bthn", c, _w(p, "w_uk", dt))
    v = jnp.einsum("btc,chv->bthv", c, _w(p, "w_uv", dt))
    kp = jnp.broadcast_to(kp[:, :, None, :], kn.shape[:3] + kp.shape[-1:])
    return attend_qkv(
        jnp.concatenate([qn, qp], axis=-1),
        jnp.concatenate([kn, kp], axis=-1), v,
    )


def _latent_absorbed(cfg: TransformerConfig, p, qn, qp, attend_rows):
    """The same attention with the up-projections absorbed (decode's and
    a chunk's form): ``q_c = q_n W_uk^T`` folds a head's query onto the
    latent, ``attend_rows(q_lat)`` (q_lat (B, T, H, latent_row_width):
    the folded query, its rotated part, zeros in the lane padding -> (B,
    T, H, r_kv)) attends over the cached rows themselves, and ``W_uv``
    takes the attended latent to the head's value. ``qn`` and ``qp``
    carry the softmax scale ``1 / sqrt(n + p)`` already
    (:func:`_gated_block` puts it into the query norm's gain for this
    form), so ``attend_rows`` takes plain dot products."""
    dt = qn.dtype
    qc = jnp.einsum("bthn,chn->bthc", qn, _w(p, "w_uk", dt))
    pad = latent_row_width(cfg) - cfg.latent_row
    oc = attend_rows(jnp.concatenate(
        [qc, qp, jnp.zeros(qc.shape[:-1] + (pad,), dt)], axis=-1))
    return jnp.einsum("bthc,chv->bthv", oc, _w(p, "w_uv", dt))


def _ring_write(leaf, idx: int, k, v, positions, last):
    """Write the rows of ``k``, ``v`` (B, C, Hkv*K) that a ring of
    ``leaf.shape[3]`` rows must hold once position ``last`` is its
    newest: those at ``positions`` (B or 1, C) within (last - rows,
    last]. ``last`` (B or 1, 1). Rows past ``last`` (a bucket's
    padding) and rows the window has left are not written: they would
    land on rows decode still needs."""
    rows = leaf.shape[3]
    b = k.shape[0]
    keep = (positions <= last) & (positions > last - rows)
    slot = jnp.broadcast_to(
        jnp.where(keep, positions % rows, rows), (b, k.shape[1])
    )  # rows: out of bounds, dropped
    bidx = jnp.arange(b)[:, None]
    for plane, x in enumerate((k, v)):
        leaf = leaf.at[idx, plane, bidx, slot].set(
            x.astype(leaf.dtype), mode="drop"
        )
    return leaf


def _gated_block(cfg: TransformerConfig, l: int, p, x, positions, attend,
                 live=None):
    """Layer ``l`` over ``x`` (B, T, D) at ``positions`` ((T,) shared or
    (B, T) per row): RMSNorm, projections, the layer kind's rotary,
    ``attend(q, k, v)`` (q (B, T, H_l, K), k / v (B, T, Hkv, K) rotated
    -> (B, T, H_l, K); the caller's way to reach keys, and to cache
    them), the per-head gate, the output projection, RMSNorm, then the
    dense SwiGLU or the held experts' part plus the shared expert. A
    latent layer's ``attend`` is a :class:`LatentAttend`: the caller
    picks the form (expanded keys and values, or the cached rows
    themselves) and is handed the position's cache row ([normed latent,
    rotated shared key], (B, T, r_kv + p)) with the queries.
    With ``sandwich_norm`` the attention's and the MLP's outputs are
    normed before they join the residual stream.
    ``live`` (B,) bool: rows somebody reads (others route nowhere).
    Returns (x, counts): ``moe_held_ffn``'s int32 (3,), zeros in a dense
    layer."""
    from deeplearning4j_tpu.parallel.expert_parallel import (
        moe_held_ffn,
        swiglu,
    )

    dt = x.dtype
    kind = _KINDS[cfg.layer_types[l]]
    h = _rms_norm(x, p["ln1_scale"], cfg.norm_eps)
    if kind == "latent":
        n, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        gain = p["q_norm_scale"]
        if attend.absorbed:
            # the absorbed form takes plain dot products of queries with
            # the softmax scale in them; both query parts are linear in
            # the query norm's gain, so it goes there, in float32, free
            gain = gain.astype(jnp.float32) * _latent_scale(cfg)
        cq = _rms_norm(h @ _w(p, "wq_a", dt), gain, cfg.norm_eps)
        q = jnp.einsum("btr,rhk->bthk", cq, _w(p, "wq_b", dt))
        ckp = h @ _w(p, "wkv_a", dt)  # (B, T, r_kv + p)
        cos, sin = _gated_rope(cfg, kind, positions, dt)
        row = jnp.concatenate([
            _rms_norm(ckp[..., :r], p["kv_norm_scale"], cfg.norm_eps),
            _rotate(ckp[..., r:], cos, sin),
        ], axis=-1)
        qn = q[..., :n]
        qp = _rotate(q[..., n:], cos[..., None, :], sin[..., None, :])
        if attend.absorbed:
            o = _latent_absorbed(
                cfg, p, qn, qp, lambda q_lat: attend.fn(q_lat, row))
        else:
            o = _latent_expanded(
                cfg, p, qn, qp, row,
                lambda q, k, v: attend.fn(q, k, v, row))
    else:
        q = jnp.einsum("btd,dhk->bthk", h, _w(p, "wq", dt))
        kv = jnp.einsum("btd,dshk->sbthk", h, _w(p, "wkv", dt))
        cos, sin = _gated_rope(cfg, kind, positions, dt)
        cos, sin = cos[..., None, :], sin[..., None, :]  # over the head axis
        q = _rotate(q, cos, sin)
        k = _rotate(kv[0], cos, sin)
        o = attend(q, k, kv[1])
    if cfg.attn_gate:
        gate = jax.nn.sigmoid(
            jnp.einsum("btd,dh->bth", h, _w(p, "wg", dt)).astype(jnp.float32)
        )
        o = o * gate[..., None].astype(dt)
    a = jnp.einsum("bthk,hkd->btd", o, _w(p, "wo", dt))
    if cfg.sandwich_norm:
        a = _rms_norm(a, p["ln1_post_scale"], cfg.norm_eps)
    x = x + a
    h2 = _rms_norm(x, p["ln2_scale"], cfg.norm_eps)
    counts = None
    if l in cfg.dense_layers:
        y = swiglu(h2, _w(p, "w_gate", dt), _w(p, "w_up", dt),
                   _w(p, "w_down", dt))
    else:
        b, t, d = h2.shape
        y, counts = moe_held_ffn(
            h2.reshape(b * t, d), p["router"], _w(p, "we_gate", dt),
            _w(p, "we_up", dt), _w(p, "we_down", dt), first=cfg.expert_first,
            k=cfg.moe_k, scale=cfg.moe_scale, score=cfg.moe_score,
            live=None if live is None else jnp.repeat(live, t),
        )
        y = y.reshape(b, t, d)
        if cfg.d_shared:
            y = y + swiglu(h2, _w(p, "ws_gate", dt), _w(p, "ws_up", dt),
                           _w(p, "ws_down", dt))
    if cfg.sandwich_norm:
        y = _rms_norm(y, p["ln2_post_scale"], cfg.norm_eps)
    x = x + y
    return x, jnp.zeros((3,), jnp.int32) if counts is None else counts


def _init_gated(key, cfg: TransformerConfig):
    """Params of a gated stack: ``layers`` is one dict a layer."""
    d, kd, hkv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def norm(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    def mlp(keys, prefix, lead, width):
        return {
            prefix + "_gate": norm(keys[0], (*lead, d, width), d),
            prefix + "_up": norm(keys[1], (*lead, d, width), d),
            prefix + "_down": norm(keys[2], (*lead, width, d), width),
        }

    layers = []
    for l, kl in enumerate(jax.random.split(k_layers, cfg.n_layers)):
        ks = jax.random.split(kl, 12)
        h = cfg.heads_of(l)
        p = {"ln1_scale": jnp.ones((d,)), "ln2_scale": jnp.ones((d,))}
        if cfg.sandwich_norm:
            p["ln1_post_scale"] = jnp.ones((d,))
            p["ln2_post_scale"] = jnp.ones((d,))
        if cfg.latent:
            # W_kvb is held as its two halves: the key part W_uk and the
            # value part W_uv are separate operands of the absorbed form
            rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
            n, rp, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
            ka = jax.random.split(ks[11], 3)
            p.update({
                "wq_a": norm(ks[0], (d, rq), d),
                "q_norm_scale": jnp.ones((rq,)),
                "wq_b": norm(ka[0], (rq, h, n + rp), rq),
                "wkv_a": norm(ks[1], (d, rkv + rp), d),
                "kv_norm_scale": jnp.ones((rkv,)),
                "w_uk": norm(ka[1], (rkv, h, n), rkv),
                "w_uv": norm(ka[2], (rkv, h, vd), rkv),
                "wo": norm(ks[2], (h, vd, d), h * vd),
            })
        else:
            p.update({
                "wq": norm(ks[0], (d, h, kd), d),
                "wkv": norm(ks[1], (d, 2, hkv, kd), d),
                "wo": norm(ks[2], (h, kd, d), h * kd),
            })
        if cfg.attn_gate:
            p["wg"] = norm(ks[3], (d, h), d)
        if l in cfg.dense_layers:
            p.update(mlp(ks[4:7], "w", (), cfg.d_ff))
        else:
            p["router"] = norm(ks[4], (d, cfg.n_experts_total), d)
            p.update(mlp(ks[5:8], "we", (cfg.n_experts,), cfg.d_expert))
            if cfg.d_shared:
                p.update(mlp(ks[8:11], "ws", (), cfg.d_shared))
        layers.append(p)
    return {
        "embed": jax.random.normal(
            k_embed, (cfg.vocab_size, d), jnp.float32) * 0.02,
        "layers": layers,
        "lnf_scale": jnp.ones((d,)),
        "head": norm(k_head, (d, cfg.vocab_size), d),
    }


def _gated_refuses(cfg: TransformerConfig, what: str, lacks: str):
    if cfg.gated:
        raise NotImplementedError(
            f"{what} is not built for a stack of gated layers "
            f"(layer_types set): {lacks}"
        )


def _gated_builder(cfg: TransformerConfig):
    """``(forward_one, init_caches, prefill, cast_params, forward_chunk)``
    of a gated stack: :func:`_decode_builder`'s and
    :func:`_chunk_builder`'s contracts over a cache of two leaves."""
    if cfg.decode_int8:
        _gated_refuses(
            cfg, "decode_int8",
            "neither its ring leaf nor its latent leaf has int8 rows or "
            "scale planes, and quantize_decode_params covers neither its "
            "experts nor its latent projections",
        )
    d, kd, hkv = cfg.d_model, cfg.head_dim, cfg.kv_heads
    hk = hkv * kd
    place = {  # layer -> (kind, index in that kind's leaf)
        l: (kind, i) for kind in ("full", "window", "latent")
        for i, l in enumerate(cfg.layers_of(kind))
    }
    def init_caches(batch: int, total: int):
        tpad = _decode_tpad(total)
        if cfg.latent:  # one plane: a row is key and value of every head
            return {"latent": jnp.zeros(
                (cfg.n_layers, 1, batch, tpad, latent_row_width(cfg)),
                cfg.compute_dtype)}
        out = {"full": jnp.zeros(
            (len(cfg.layers_of("full")), 2, batch, tpad, hk),
            cfg.compute_dtype)}
        if cfg.layers_of("window"):
            out["window"] = jnp.zeros(
                (len(cfg.layers_of("window")), 2, batch,
                 cfg.sliding_window, hk), cfg.compute_dtype)
        return out

    def cast_params(params):
        """Streamed weights to the compute dtype, once; the router
        stays float32 (its product is float32, see ``route_top_k``)."""
        def cast(path, a):
            name = getattr(path[-1], "key", None)
            if name == "router" or not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            return a.astype(cfg.compute_dtype)
        return jax.tree_util.tree_map_with_path(cast, params)

    def run_layers(params, x, positions, attend_of, live=None):
        counts = jnp.zeros((3,), jnp.int32)
        for l in range(cfg.n_layers):
            x, c = _gated_block(
                cfg, l, params["layers"][l], x, positions, attend_of(l),
                live=live,
            )
            counts = counts + c
        return x, counts

    def logits_of(params, x_last):
        x_last = _rms_norm(x_last, params["lnf_scale"], cfg.norm_eps)
        return jnp.einsum(
            "...d,dv->...v", x_last, _w(params, "head", x_last.dtype),
            preferred_element_type=jnp.float32,
        )  # bf16 operands, f32 accumulation: see _decode_builder

    def row_at(x, last_idx):  # (B, T, D) -> (B, D): the named row
        if last_idx is None:
            return x[:, -1]
        if jnp.ndim(last_idx) == 1:
            return jnp.take_along_axis(
                x, last_idx[:, None, None], axis=1)[:, 0]
        return lax.dynamic_index_in_dim(x, last_idx, axis=1, keepdims=False)

    def packed(x):  # (B, C, Hkv, K) -> (B, C, Hkv*K)
        return x.reshape(x.shape[0], x.shape[1], hk)

    def rows_of(leaf, idx, plane):  # -> (B, rows, Hkv, K)
        x = leaf[idx, plane]
        return x.reshape(x.shape[0], x.shape[1], hkv, kd)

    def lane_padded(x):  # (..., latent_row) -> (..., latent_row_width)
        pad = latent_row_width(cfg) - x.shape[-1]
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def latent_chunk_attend(caches, idx, positions):
        """A latent layer's ``attend`` for C rows at ``positions``: the
        rows go to the leaf, then the absorbed form over the slab."""
        def attend(q_lat, row):
            leaf = caches["latent"]
            b, c = row.shape[:2]
            leaf = leaf.at[
                idx, 0, jnp.arange(b)[:, None],
                jnp.broadcast_to(positions, (b, c)),
            ].set(lane_padded(row).astype(leaf.dtype), mode="drop")
            caches["latent"] = leaf
            mask = jnp.arange(leaf.shape[3]) <= positions[..., None]
            return _latent_dense(q_lat, leaf[idx, 0], mask, cfg.kv_lora_rank)

        return LatentAttend(True, attend)

    def chunk_attend(caches, l, positions, last):
        """``attend`` of layer ``l`` for C rows at ``positions`` (B or 1,
        C) against the cache, rows up to ``last`` (B or 1, 1) real. A
        full layer writes its rows and reads the slab; a window layer
        reads the ring as it stood plus its own rows, then writes."""
        kind, idx = place[l]
        if kind == "latent":
            return latent_chunk_attend(caches, idx, positions)

        def attend(q, k, v):
            leaf = caches[kind]
            b, c = q.shape[:2]
            own = jnp.arange(c)
            if kind == "full":
                pos_b = jnp.broadcast_to(positions, (b, c))
                bidx = jnp.arange(b)[:, None]
                for plane, x in enumerate((k, v)):
                    leaf = leaf.at[idx, plane, bidx, pos_b].set(
                        packed(x).astype(leaf.dtype), mode="drop"
                    )
                caches[kind] = leaf
                mask = jnp.arange(leaf.shape[3]) <= positions[..., None]
                return _attend_dense(
                    q, rows_of(leaf, idx, 0), rows_of(leaf, idx, 1), mask
                )
            ring = leaf.shape[3]
            w = cfg.sliding_window
            # ring row s holds the newest position before this chunk
            # that is congruent to s, if there is one
            before = positions[..., :1] - 1  # (B or 1, 1)
            held = before - (before - jnp.arange(ring)) % ring
            seen = (held >= 0)[..., None, :] & (
                positions[..., None] - held[..., None, :] < w
            )
            mine = (own[None, :] <= own[:, None]) & (
                own[:, None] - own[None, :] < w
            )
            mask = jnp.concatenate(
                [seen, jnp.broadcast_to(mine, seen.shape[:-1] + (c,))],
                axis=-1,
            )
            o = _attend_dense(
                q,
                jnp.concatenate([rows_of(leaf, idx, 0), k], axis=1),
                jnp.concatenate([rows_of(leaf, idx, 1), v], axis=1),
                mask,
            )
            caches[kind] = _ring_write(
                leaf, idx, packed(k), packed(v), positions, last
            )
            return o

        return attend

    def kernel_attend(caches, l, pos, active):
        """``attend`` of layer ``l`` for one row a slot through the
        decode kernel, which places the row (a ring's at ``pos % rows``)
        in the block of its walk that holds it and attends to it from
        there. The kernel caps ``pos`` at the leaf's last row, so it
        reads ``min(pos + 1, rows)`` rows of a ring, rounded up to its
        block, and neither reads nor writes for a row that is not
        active."""
        from deeplearning4j_tpu.ops.pallas_kernels import (
            flash_decode_attention_write,
            latent_decode_attention_write,
        )

        kind, idx = place[l]

        def attend_latent(q_lat, row):  # (B, 1, H, width), (B, 1, r_kv + p)
            o, caches[kind] = latent_decode_attention_write(
                q_lat[:, 0], caches[kind], lane_padded(row),
                pos, value_width=cfg.kv_lora_rank, layer=idx,
                write_at=_decode_write_at(pos, False, caches[kind]),
                active=active,
            )
            return o[:, None]

        if kind == "latent":
            return LatentAttend(True, attend_latent)

        def attend(q, k, v):
            leaf = caches[kind]
            b, _, h, _ = q.shape
            grp = h // hkv
            # query head j = kv * G + g: (B, G, Hkv*K), packed head-major
            qp = q[:, 0].reshape(b, hkv, grp, kd).transpose(
                0, 2, 1, 3).reshape(b, grp, hk)
            o, caches[kind] = flash_decode_attention_write(
                qp, leaf, jnp.concatenate([packed(k), packed(v)], axis=1),
                pos, n_kv_heads=hkv, layer=idx,
                write_at=_decode_write_at(pos, kind == "window", leaf),
                active=active,
            )
            return o.reshape(b, grp, hkv, kd).transpose(
                0, 2, 1, 3).reshape(b, 1, h, kd)

        return attend

    def forward_one(params, caches, token, pos, adapter=None, active=None,
                    stats=None):
        """:func:`_decode_builder`'s ``forward_one`` contract. ``stats``:
        a list that receives this call's MoE counters (int32 (3,):
        pairs computed here, pairs routed, held experts hit), summed
        over the expert layers, for a step program to carry out."""
        del adapter  # no LoRA bank: the engine refuses one
        caches = dict(caches)
        x = params["embed"][token].astype(cfg.compute_dtype)[:, None, :]
        positions = jnp.reshape(pos, (-1, 1))  # (B or 1, 1)
        if cfg.decode_kernel:
            def attend_of(l):
                return kernel_attend(caches, l, pos, active)
        else:
            def attend_of(l):
                return chunk_attend(caches, l, positions, positions)
        x, counts = run_layers(params, x, positions, attend_of, live=active)
        if stats is not None:
            stats.append(counts)
        return logits_of(params, x[:, 0]), caches

    # what serving/engine.py: tallied() asks before it passes ``stats``
    forward_one.counts_moe = bool(
        set(range(cfg.n_layers)) - set(cfg.dense_layers)
    )

    def prefill(params, caches, prompt, last_idx=None, adapter=None):
        """:func:`_decode_builder`'s ``prefill`` contract: one causal
        pass over the bucket from position 0. Full layers cache every
        row; a ring keeps the last ``rows`` real ones (``last_idx``
        says where the padding starts)."""
        del adapter
        b, tp = prompt.shape
        if tp == 0:
            return caches, jnp.zeros((b, cfg.vocab_size), jnp.float32)
        caches = dict(caches)
        positions = jnp.arange(tp)
        last = jnp.reshape(
            tp - 1 if last_idx is None else last_idx, (-1, 1)
        )
        w = cfg.sliding_window
        flash = cfg.use_flash and _flash_seq_ok(tp)

        def flash_attend(q, k, v, grp: int):
            """Causal flash attention over (B, T, H, K) heads, ``k`` and
            ``v`` repeated ``grp`` times; the kernel has one head size,
            so a narrower ``v`` is zero-padded to K and the result cut."""
            from deeplearning4j_tpu.ops.pallas_kernels import (
                flash_attention_trainable,
            )

            kd_, vd = q.shape[-1], v.shape[-1]
            if vd < kd_:
                v = jnp.pad(v, [(0, 0)] * 3 + [(0, kd_ - vd)])
            bq, bk = _flash_blocks(tp)
            o = flash_attention_trainable(
                q.transpose(0, 2, 1, 3),
                jnp.repeat(k.transpose(0, 2, 1, 3), grp, axis=1),
                jnp.repeat(v.transpose(0, 2, 1, 3), grp, axis=1),
                causal=True, block_q=bq, block_k=bk, layout="bhtd",
            )
            o = o.transpose(0, 2, 1, 3)
            return o[..., :vd] if vd < kd_ else o

        def whole_sequence(q, k, v, grp: int):
            if flash:
                return flash_attend(q, k, v, grp)
            mask = positions[None, :] <= positions[:, None]
            return _attend_dense(q, k, v, mask[None])

        def attend_of(l):
            kind, idx = place[l]

            def attend_latent(q, k, v, row):
                # the expanded form over the whole bucket; what is
                # cached is the latent row, not K and V
                caches[kind] = lax.dynamic_update_slice(
                    caches[kind],
                    lane_padded(row)[None, None].astype(caches[kind].dtype),
                    (idx, 0, 0, 0, 0),
                )
                return whole_sequence(q, k, v, 1)

            if kind == "latent":
                return LatentAttend(False, attend_latent)

            def attend(q, k, v):
                leaf = caches[kind]
                if kind == "full":
                    caches[kind] = lax.dynamic_update_slice(
                        leaf,
                        jnp.stack([packed(k), packed(v)])[None].astype(
                            leaf.dtype),
                        (idx, 0, 0, 0, 0),
                    )
                else:
                    caches[kind] = _ring_write(
                        leaf, idx, packed(k), packed(v), positions[None],
                        last,
                    )
                if kind == "window" and tp > w:
                    return _attend_window(q, k, v, w)
                return whole_sequence(q, k, v, q.shape[2] // hkv)

            return attend

        x = params["embed"][prompt].astype(cfg.compute_dtype)
        x, _ = run_layers(params, x, positions, attend_of)
        return caches, logits_of(params, row_at(x, last_idx))

    def forward_chunk(params, caches, toks, pos0, last_idx=None,
                      adapter=None):
        """:func:`_chunk_builder`'s contract: C consecutive positions
        from ``pos0`` (scalar or (B,)) against the cache. ``last_idx``:
        the last real row of the chunk (the rest is a bucket's padding
        and must not reach a ring); with it the logits are that row's,
        (B, V), else every row's, (B, C, V)."""
        del adapter
        b, c = toks.shape
        caches = dict(caches)
        positions = jnp.reshape(pos0, (-1, 1)) + jnp.arange(c)  # (B|1, C)
        last = positions[..., :1] + jnp.reshape(
            c - 1 if last_idx is None else last_idx, (-1, 1)
        )
        x = params["embed"][toks].astype(cfg.compute_dtype)
        x, _ = run_layers(
            params, x, positions,
            lambda l: chunk_attend(caches, l, positions, last),
        )
        if last_idx is None:
            return logits_of(params, x), caches
        return logits_of(params, row_at(x, last_idx)), caches

    return forward_one, init_caches, prefill, cast_params, forward_chunk


def _decode_builder(cfg: TransformerConfig, tp_mesh=None):
    """Shared KV-cache decode machinery: returns
    ``(forward_one, init_caches, prefill)`` used by sampling and beam
    search. ``forward_one(params, caches, token, pos)`` advances one
    position through all layers.

    ``tp_mesh`` (a 1-D model-axis mesh) builds the exact-TP serving
    variant: params placed per :func:`serving_tp_shardings`, caches per
    :func:`serving_tp_cache_sharding`, sharded activations gathered
    before every row projection (:func:`_tp_replicate`) so outputs are
    bitwise identical to the unsharded program. Requires the dense
    decode path (``decode_kernel=False``) — the Pallas decode kernel is
    a custom call GSPMD cannot partition."""
    if tp_mesh is not None:
        _gated_refuses(
            cfg, "tensor-parallel serving (tp > 1)",
            "serving_tp_shardings has no layout for per-layer head "
            "counts, held experts, a ring leaf or latent projections",
        )
    if cfg.gated:
        return _gated_builder(cfg)[:4]
    if tp_mesh is not None and cfg.decode_kernel:
        raise ValueError(
            "tensor-parallel decode requires decode_kernel=False "
            "(the Pallas kernel cannot be GSPMD-partitioned)"
        )

    def quantize_kv_rows(rows):
        """Per-row int8 quantization of new cache rows: ``rows``
        (..., hk) -> (int8 rows, f32 scales (..., 1)). The row (one
        position's packed heads) is the finest granularity the kernel
        can rescale without per-head bookkeeping; measured logits error
        vs bf16 cache is ~0.3% on random models."""
        return _quantize_int8(rows.astype(jnp.float32), (-1,))

    def write_kv_rows_int8(kv_all, i, pos, rows):
        """Write one decode step's K/V ``rows`` (2, B, Hkv*K) into the
        stacked int8 cache and its scale planes at layer ``i`` (the
        bf16 / f32 cache's rows are placed by the decode kernel
        itself). Scalar ``pos`` writes every batch row at the same
        position with a single fused ``dynamic_update_slice`` (the
        generate/beam path — XLA aliases it in place); an (B,) vector
        scatters each row at its own position (the serving engine's
        per-slot decode depths)."""
        kv_buf, sc_buf = kv_all["kv"], kv_all["scale"]
        q_rows, s_rows = quantize_kv_rows(rows)
        if jnp.ndim(pos) == 0:
            kv_buf = lax.dynamic_update_slice(
                kv_buf, q_rows[None, :, :, None, :], (i, 0, 0, pos, 0)
            )
            sc_buf = lax.dynamic_update_slice(
                sc_buf, s_rows[None, :, :, None, :], (i, 0, 0, pos, 0)
            )
            return {"kv": kv_buf, "scale": sc_buf}
        bidx = jnp.arange(rows.shape[1])
        for plane in range(2):
            kv_buf = kv_buf.at[i, plane, bidx, pos].set(q_rows[plane])
            sc_buf = sc_buf.at[i, plane, bidx, pos].set(s_rows[plane])
        return {"kv": kv_buf, "scale": sc_buf}

    def block_decode(x, p, kv_all, i, pos, lora=None, adapter=None,
                     active=None):
        # x: (B, D) one position; kv_all: the ONE stacked packed cache
        # (nl, 2, B, Tpad, Hkv*K) (axis 1: K then V) — this layer's new
        # K and V rows are placed by the decode kernel itself, in the
        # block of its walk that holds them, the cache aliased in place
        # through the call (int8: by XLA before the kernel, a
        # dynamic_update_slice or a scatter). (The round-1 per-layer scan
        # carried the whole cache stack and restacked it every layer:
        # ~126ms/call of dynamic-update-slice + squeeze bookkeeping at
        # GPT-2-small B=16, measured.) The packed minor dim is the perf
        # story: a (B, T, H, K) cache tiles on (12, 64) -> (16, 128) and
        # streams 2.67x the logical bytes every step (601us/step for the
        # QK read alone, measured r2). Under GQA the cache holds only
        # kv_heads — the memory win.
        if not cfg.decode_kernel:
            # the dense fallback IS the C=1 chunk block — one code path
            # (no separate copy to drift), used under SPMD sharding,
            # for debugging, and as speculative decoding's
            # numerics-matched draft mode — and batched LoRA's decode
            # path (adapter deltas ride the same chunk block)
            y, kv_all = _block_chunk(
                cfg, x[:, None, :], p, kv_all, i, pos, tp_mesh=tp_mesh,
                lora=lora, adapter=adapter,
            )
            return y[:, 0], kv_all
        b = x.shape[0]
        kd = cfg.head_dim
        grp = cfg.n_heads // cfg.kv_heads
        h_in = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        if cfg.kv_heads != cfg.n_heads:
            q = jnp.einsum("bd,dhk->bhk", h_in, _w(p, "wq", x.dtype))
            kv = jnp.einsum("bd,dshk->sbhk", h_in, _w(p, "wkv", x.dtype))
            k, v = kv[0], kv[1]
        else:
            qkv = jnp.einsum(
                "bd,dshk->sbhk", h_in, _w(p, "wqkv", x.dtype)
            )
            q, k, v = qkv[0], qkv[1], qkv[2]
        if cfg.rope:
            cos, sin = _rope_tables(pos, cfg.head_dim, x.dtype)
            if jnp.ndim(pos) == 1:
                # per-slot positions (serving): (B, hd/2) tables, one
                # rotation per batch row
                cos, sin = cos[:, None, :], sin[:, None, :]
            else:
                cos, sin = cos[None, None], sin[None, None]  # (hd/2,)
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
        from deeplearning4j_tpu.ops.pallas_kernels import (
            flash_decode_attention,
            flash_decode_attention_write,
        )

        # query head h = kv*G + g (the _expand_kv repeat order):
        # group into (B, G, Hkv*K) so each group is packed head-major
        qp = (
            q.reshape(b, cfg.kv_heads, grp, kd)
            .transpose(0, 2, 1, 3)
            .reshape(b, grp, cfg.kv_heads * kd)
        )
        # the kernel takes the STACKED cache and selects the layer
        # inside — slicing here would materialize a full-cache copy per
        # layer (custom calls need dense operands)
        kv_rows = [k.reshape(b, -1), v.reshape(b, -1)]  # (B, Hkv*K) each
        if cfg.decode_int8:
            kv_all = write_kv_rows_int8(kv_all, i, pos, jnp.stack(kv_rows))
            o = flash_decode_attention(
                qp, kv_all["kv"], pos, n_kv_heads=cfg.kv_heads, layer=i,
                kv_scales=kv_all["scale"], active=active,
            )
        else:
            o, kv_all = flash_decode_attention_write(
                qp, kv_all, jnp.stack(kv_rows, axis=1), pos,
                n_kv_heads=cfg.kv_heads, layer=i,
                write_at=_decode_write_at(pos, False, kv_all),
                active=active,
            )
        o_flat = (
            o.reshape(b, grp, cfg.kv_heads, kd)
            .transpose(0, 2, 1, 3)
            .reshape(b, cfg.n_heads * kd)
        )
        x = x + o_flat @ _w(p, "wo", x.dtype).reshape(
            cfg.n_heads * kd, -1
        )
        h_in = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
        if cfg.n_experts:
            from deeplearning4j_tpu.parallel.expert_parallel import (
                moe_reference,
            )

            moe_params = jax.tree.map(
                lambda a: a.astype(x.dtype), p["moe"]
            )
            # activation must match moe_ffn's (gelu), or decode runs a
            # different model than was trained
            x = x + moe_reference(
                moe_params, h_in, k=cfg.moe_k, activation=jax.nn.gelu
            )
        else:
            x = x + _mlp(p, h_in)
        return x, kv_all

    def forward_one(params, caches, token, pos, adapter=None, active=None):
        """One position through all layers; returns (logits, caches).

        ``pos`` is a scalar (every batch row at the same depth — the
        generate/beam/speculative paths) or an (B,) int vector of
        per-row positions (the serving engine, where each slot decodes
        at its own depth).

        ``active`` (B,) bool, optional: rows whose result somebody
        reads. The decode kernel neither reads nor writes a cache row
        for a row that is not active (its attention output is zeros,
        its logits are whatever the residual stream then gives, and
        nobody samples from them). Where XLA places the new rows (int8
        cache, dense path) such a row's K and V are still WRITTEN at
        ``pos``, and the dense path reads every row whatever the mask
        says. Default: every row active.

        ``adapter`` (B,) int rows (with a ``params["lora"]`` bank
        present) applies batched-LoRA deltas per row — dense path only;
        the serving engine forces ``decode_kernel=False`` when a bank
        is loaded.

        The layer loop is UNROLLED (n_layers static python loop): the
        round-1 lax.scan spent a third of decode wall time in while-loop
        bookkeeping alone (measured via hlo_stats), and its cache carry
        defeated in-place updates.
        """
        kv_all = caches
        lora = params.get("lora") if adapter is not None else None
        if lora is not None and cfg.decode_kernel:
            raise ValueError(
                "batched LoRA decode requires decode_kernel=False"
            )
        # explicit clamp, matching forward_chunk's mode='clip': the
        # speculative draft legitimately calls at pos up to total+k-2
        # (scratch slots whose outputs are discarded) and must not rely
        # on XLA's implicit out-of-bounds gather clamping
        emb_pos = jnp.minimum(pos, cfg.max_len - 1)
        x = (params["embed"][token] + params["pos"][emb_pos]).astype(
            cfg.compute_dtype
        )
        for i in range(cfg.n_layers):
            p_i = jax.tree.map(lambda a: a[i], params["blocks"])
            l_i = (None if lora is None
                   else jax.tree.map(lambda a: a[i], lora))
            x, kv_all = block_decode(
                x, p_i, kv_all, i, pos, lora=l_i, adapter=adapter,
                active=active,
            )
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        # head matmul with bf16 (or dequantized-int8) OPERANDS — half/
        # quarter the weight stream and the MXU fast path; decode is
        # weight-streaming-bound — but f32 ACCUMULATION: a bf16-output
        # dot would quantize the logits to 8 mantissa bits, creating
        # arbitrary ties at the top-k threshold and in beam scores
        logits = jnp.einsum(
            "bd,dv->bv", x, _w(params, "head", x.dtype),
            preferred_element_type=jnp.float32,
        )
        # TP: vocab-sharded logits gather here (exact concatenation) so
        # the host-visible logits buffer — and everything sampling reads
        # — is replicated and bitwise identical to TP=1
        return _tp_replicate(logits, tp_mesh), kv_all

    def cast_params(params):
        """One-time cast of the streamed weights to the compute dtype.

        Decode is HBM-bound on the weight stream: without this, every
        per-step fused matmul re-reads f32 weights and converts inline —
        2x the bytes of the bf16 stream. Called once at the top of the
        jitted generate/beam program; a no-op at f32. int8-quantized
        leaves (and their f32 per-channel scales) pass through
        untouched: the int8 bytes ARE the stream, and the scales must
        stay f32 for the fused dequant."""

        quant_scales = {n + "_scale" for n in _INT8_BLOCK_AXES}

        def cast_leaf(a):
            if jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(cfg.compute_dtype)
            return a

        def cast(name, a):
            if name in quant_scales:  # NOT ln1_scale/ln2_scale
                return a
            # a may itself be a pytree (MoEParams): cast its leaves
            return jax.tree.map(cast_leaf, a)
        out = dict(params)
        out["blocks"] = {
            name: cast(name, a) for name, a in params["blocks"].items()
        }
        if params["head"].dtype != jnp.int8:
            out["head"] = params["head"].astype(cfg.compute_dtype)
        return out

    def init_caches(batch: int, total: int):
        nl, h, kd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
        # ONE stacked cache (nl, 2, B, Tpad, Hkv*K) — K and V planes in
        # one buffer so each decode layer issues a single fused write.
        # Sized (and thus every step's attention span) to the actual
        # decode length, not max_len, rounded up to the sublane tile —
        # and, above the kernel's 1024-row block cap, to a 512 multiple
        # so the length always factors into large 8-aligned blocks (a
        # Tpad like 8*prime would otherwise degenerate the kernel's
        # block search to 8-row blocks: ~100x the per-cell fixed cost).
        # Packed (Tpad, Hkv*K) minor layout: see block_decode.
        tpad = _decode_tpad(total)
        if cfg.decode_int8:
            # int8 rows + per-row f32 scales (trailing singleton keeps
            # the scale blocks Mosaic-legal: last dim 1 = full dim)
            return {
                "kv": jnp.zeros(
                    (nl, 2, batch, tpad, h * kd), jnp.int8
                ),
                "scale": jnp.zeros(
                    (nl, 2, batch, tpad, 1), jnp.float32
                ),
            }
        return jnp.zeros(
            (nl, 2, batch, tpad, h * kd), cfg.compute_dtype
        )

    def prefill(params, caches, prompt, last_idx=None, adapter=None):
        """Bulk prefill: ONE causal forward over the whole prompt fills
        every layer's KV cache and yields the last-position logits —
        the standard inference split (parallel prefill, serial decode).
        Round 1 walked the prompt through ``forward_one`` position by
        position: T_p sequential layer scans; this is a single
        training-shaped pass (T_p-way parallel on the MXU).

        ``last_idx`` (traced int, default ``tp - 1``) selects which row
        the returned logits come from — callers that right-pad the
        prompt to a length bucket (the serving engine) pass the true
        last-token index. Causal masking makes the padded rows
        invisible to rows <= last_idx, so the logits are bitwise
        identical to an exact-length prefill. A (B,) VECTOR ``last_idx``
        selects a per-row last index — the batched-admission path,
        where rows of one dispatch carry prompts of different true
        lengths inside the same bucket; the per-row gather copies the
        same values the scalar program reads, so logits stay row-wise
        bitwise identical to B=1 prefills.

        ``adapter`` (B,) int rows (with a ``params["lora"]`` bank
        present) applies each row's batched-LoRA deltas; the bank's
        leading layer axis scans alongside ``params["blocks"]``.
        """
        b, tp = prompt.shape
        if tp == 0:
            # empty prompt: nothing to prefill — decode starts from
            # uniform logits, as the round-1 per-position walk did
            return caches, jnp.zeros((b, cfg.vocab_size), jnp.float32)
        lora = params.get("lora") if adapter is not None else None
        kv_all = caches  # (nl, 2, B, Tpad, Hkv*K) packed
        x = (params["embed"][prompt] + params["pos"][:tp]).astype(
            cfg.compute_dtype
        )
        if cfg.rope:
            cos, sin = _rope_tables(
                jnp.arange(tp), cfg.head_dim, cfg.compute_dtype
            )  # (Tp, hd/2)
            cos_b = cos[None, None, :, :]
            sin_b = sin[None, None, :, :]

        def layer(x, xs):
            if lora is None:
                p, kv = xs  # kv: (2, B, Tpad, Hkv*K); int8 mode: dict
                lo = None
            else:
                p, lo, kv = xs
            h_in = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
            q, k_r, v_r = _project_qkv(cfg, p, h_in)
            if lo is not None:
                # same attach point as _block_chunk: q delta pre-RoPE,
                # adapter-0 rows select the untouched base projection
                dq = _lora_delta(
                    h_in,
                    jnp.take(lo["a_q"], adapter, axis=0),
                    jnp.take(lo["b_q"], adapter, axis=0),
                ).reshape(
                    b, tp, cfg.n_heads, cfg.head_dim
                ).transpose(0, 2, 1, 3)
                q = jnp.where(
                    (adapter > 0)[:, None, None, None], q + dq, q
                )
            if cfg.rope:
                q = _apply_rope(q, cos_b, sin_b)
                k_r = _apply_rope(k_r, cos_b, sin_b)
            # cache holds the UNexpanded kv heads packed (B, T, Hkv*K)
            kv_rows = jnp.stack(
                [
                    k_r.transpose(0, 2, 1, 3).reshape(b, tp, -1),
                    v_r.transpose(0, 2, 1, 3).reshape(b, tp, -1),
                ]
            )
            if cfg.decode_int8:
                q_rows, s_rows = quantize_kv_rows(kv_rows)
                kv = {
                    "kv": lax.dynamic_update_slice(
                        kv["kv"], q_rows, (0, 0, 0, 0)
                    ),
                    "scale": lax.dynamic_update_slice(
                        kv["scale"], s_rows, (0, 0, 0, 0)
                    ),
                }
            else:
                kv = lax.dynamic_update_slice(
                    kv, kv_rows.astype(kv.dtype), (0, 0, 0, 0)
                )
            k_h, v_h = _expand_kv(cfg, k_r, v_r)
            if cfg.use_flash and _flash_seq_ok(tp) and tp_mesh is None:
                # keep long-prompt prefill O(T) like training — dense
                # attention would materialize (B, H, Tp, Tp) scores.
                # Prompts of other lengths fall back to dense (inference
                # inputs are arbitrary; training raises instead).
                from deeplearning4j_tpu.ops.pallas_kernels import (
                    flash_attention_trainable,
                )

                # forward-only (prefill never differentiates): no
                # backward block overrides
                bq, bk = _flash_blocks(tp)
                o = flash_attention_trainable(
                    q, k_h, v_h, causal=True,
                    block_q=bq, block_k=bk, layout="bhtd",
                )
            else:
                o = attention(q, k_h, v_h, causal=True, layout="bhtd")
            # TP: gather the head-sharded attention output before the
            # row projection so the h*k reduction keeps single-chip
            # order (see _tp_replicate)
            o = _tp_replicate(o, tp_mesh)
            x = x + jnp.einsum("bhtk,hkd->btd", o, _w(p, "wo", x.dtype))
            h_in = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
            if cfg.n_experts:
                from deeplearning4j_tpu.parallel.expert_parallel import (
                    moe_reference,
                )

                moe_params = jax.tree.map(
                    lambda a: a.astype(x.dtype), p["moe"]
                )
                # per-token dense routing, matching block_decode
                flat = h_in.reshape(-1, h_in.shape[-1])
                y = moe_reference(
                    moe_params, flat, k=cfg.moe_k, activation=jax.nn.gelu
                )
                x = x + y.reshape(h_in.shape)
            elif lo is not None:
                dm = _lora_delta(
                    h_in,
                    jnp.take(lo["a_mlp"], adapter, axis=0),
                    jnp.take(lo["b_mlp"], adapter, axis=0),
                )
                x = x + _mlp(p, h_in, tp_mesh, delta1=dm,
                             sel=(adapter > 0)[:, None, None])
            else:
                x = x + _mlp(p, h_in, tp_mesh)
            return x, kv

        if lora is None:
            xs = (params["blocks"], kv_all)
        else:
            xs = (params["blocks"], lora, kv_all)
        x, kv_all = lax.scan(layer, x, xs)
        if last_idx is None:
            x_last = x[:, -1]
        elif jnp.ndim(last_idx) == 1:
            x_last = jnp.take_along_axis(
                x, last_idx[:, None, None], axis=1
            )[:, 0]
        else:
            x_last = lax.dynamic_index_in_dim(
                x, last_idx, axis=1, keepdims=False
            )
        x = _layer_norm(
            x_last, params["lnf_scale"], params["lnf_bias"]
        )
        logits = jnp.einsum(
            "bd,dv->bv", x, _w(params, "head", x.dtype),
            preferred_element_type=jnp.float32,
        )  # bf16 operands, f32 accumulation — see forward_one
        return kv_all, _tp_replicate(logits, tp_mesh)

    return forward_one, init_caches, prefill, cast_params


# -- block-paged KV views ---------------------------------------------------
#
# The paged serving pool (serving/cache_pool.py:PagedKVPool) stores KV
# as one shared pool of fixed-size blocks addressed by per-slot int32
# block tables. These helpers bridge that layout and the slab-shaped
# programs _decode_builder emits: gather the table's blocks into a
# contiguous per-slot view, run the UNCHANGED slab program, scatter the
# view back block-by-block. Gather/scatter are pure data movement, so
# the slab program's arithmetic — and therefore its token streams — is
# byte-identical by construction; tests/test_serving_paged.py pins
# exactly that. Block 0 is the permanently-zero SENTINEL: unallocated
# table entries point at it, inactive slots' dead decode writes land in
# it, and every scatter re-zeroes it in the same program.


def paged_gather(blocks, tables):
    """Contiguous (n_layers, 2, n_slots, Tpad, Hkv*K) slab view of a
    paged pool: leafwise take of every slot's blocks in table order.
    Sentinel entries contribute exact-zero rows, matching the zero rows
    a slab cache holds beyond each slot's writes."""
    def g(x):
        nl, two, _, bs, hk = x.shape
        b, bps = tables.shape
        v = jnp.take(x, tables.reshape(-1), axis=2)
        return v.reshape(nl, two, b, bps * bs, hk)
    return jax.tree.map(g, blocks)


def paged_scatter(blocks, tables, view):
    """Write a slab view back into the block pool (leafwise scatter in
    table order), then re-zero the sentinel. Duplicate table entries —
    prefix blocks byte-shared across slots — receive identical bytes
    from every writer (their view rows were gathered from the same
    block and decode only rewrites each slot's own position row), so
    the scatter is order-independent; the sentinel is the one target
    that can collect differing garbage (inactive slots' dead rows) and
    is re-zeroed here."""
    def s(x, v):
        nl, two, _, bs, hk = x.shape
        b, bps = tables.shape
        rows = v.reshape(nl, two, b * bps, bs, hk)
        out = x.at[:, :, tables.reshape(-1)].set(rows)
        return out.at[:, :, 0].set(0)
    return jax.tree.map(s, blocks, view)


def paged_slot_gather(blocks, table_row):
    """One slot's contiguous batch-1 slab (the paged seg_fetch /
    partial-hit scratch view): take of a single (blocks_per_slot,)
    table row."""
    def g(x):
        nl, two, _, bs, hk = x.shape
        bps = table_row.shape[0]
        v = jnp.take(x, table_row, axis=2)
        return v.reshape(nl, two, 1, bps * bs, hk)
    return jax.tree.map(g, blocks)


def paged_slot_scatter(blocks, table_row, slab):
    """Land a batch-1 slab (a prefill/chunk scratch cache) in the
    blocks one table row names, re-zeroing the sentinel. The slab
    covers the FULL Tpad rows — zeros beyond the prompt included — so
    the write wipes any stale bytes a reused block carried, exactly as
    the slab insert wiped whole slabs."""
    def s(x, v):
        nl, two, _, bs, hk = x.shape
        bps = table_row.shape[0]
        rows = v.reshape(nl, two, bps, bs, hk)
        out = x.at[:, :, table_row].set(rows)
        return out.at[:, :, 0].set(0)
    return jax.tree.map(s, blocks, slab)


def paged_block_copy(blocks, src, dst):
    """Copy one block's rows (``src`` → ``dst``) across every leaf —
    the full-hit tail-copy / block-zeroing primitive (``src=0`` copies
    the sentinel, i.e. zeroes ``dst``)."""
    return jax.tree.map(
        lambda x: x.at[:, :, dst].set(x[:, :, src]), blocks
    )


def decode_rows_streamed(cfg: TransformerConfig, batch: int, tpad: int,
                         held, paged: bool = False) -> int:
    """Cache rows (of one layer's K or V plane, or of its one latent
    plane) that one ``forward_one``
    call over ``batch`` slots of ``tpad`` rows reads. ``held``: for each
    ACTIVE batch row, the rows it attends to (its position + 1); a row
    that is not active has no entry.

    The decode kernel walks an active row's slab in blocks and stops at
    the block that holds its position, so it reads ``held`` rounded up
    to ``decode_block_rows`` (the kernel's own rule) and nothing for a
    row that is not active. The dense path (``decode_kernel=False``)
    contracts over the whole cache, and the paged wrapper below gathers
    every table entry (sentinel blocks too) into a slab before the
    step: both read every row of every slot whatever it holds. The
    engine books this as ``kv_rows_streamed`` beside the rows the
    requests needed."""
    def leaf(rows: int) -> int:
        if paged or not cfg.decode_kernel:
            return batch * rows
        from deeplearning4j_tpu.ops.pallas_kernels import (
            decode_block_rows,
            latent_block_rows,
        )

        itemsize = (1 if cfg.decode_int8
                    else jnp.dtype(cfg.compute_dtype).itemsize)
        # the block rows are the kernel's own rule over the leaf's own
        # row width: K and V planes of Hkv x K, or the one latent plane
        if cfg.latent:
            block = latent_block_rows(rows, latent_row_width(cfg), itemsize)
        else:
            block = decode_block_rows(
                rows, cfg.kv_heads * cfg.head_dim, itemsize)
        return sum(min(-(-h // block) * block, rows) for h in held)

    if not cfg.gated or cfg.latent:
        return leaf(tpad)
    # two leaves: a layer-weighted mean, so that the sum over the layers
    # is what the kernels of both leaves read (a ring never more than
    # its own rows: the kernel caps the position there)
    n_full, n_win = len(cfg.layers_of("full")), len(cfg.layers_of("window"))
    return (n_full * leaf(tpad)
            + (n_win and n_win * leaf(cfg.sliding_window))) // cfg.n_layers


def decode_rows_live(cfg: TransformerConfig, held) -> int:
    """Cache rows (of one layer's K or V plane) the requests of one
    ``forward_one`` call NEED: ``held`` summed; in a gated stack a
    window layer needs at most ``sliding_window`` of them, and the count
    is the same layer-weighted mean as :func:`decode_rows_streamed`'s."""
    if not cfg.gated or cfg.latent:
        return sum(held)
    n_full, n_win = len(cfg.layers_of("full")), len(cfg.layers_of("window"))
    return sum(
        n_full * h + n_win * min(h, cfg.sliding_window) for h in held
    ) // cfg.n_layers


def make_paged_fwd1(fwd1):
    """Paged wrapper of a ``_decode_builder`` ``forward_one``: gather
    the block pool into the slab view, run the IDENTICAL slab step
    (same kernel, same arithmetic), scatter back. The paged caches
    pytree is ``{"blocks": pool leaves, "tables": (n_slots,
    blocks_per_slot) int32}`` — tables thread through the jitted
    programs as traced data, so ONE compiled program serves every
    block mapping."""
    def paged_fwd1(params, pcaches, token, pos, adapter=None, active=None):
        tables = pcaches["tables"]
        view = paged_gather(pcaches["blocks"], tables)
        logits, view = fwd1(
            params, view, token, pos, adapter=adapter, active=active
        )
        return logits, {
            "blocks": paged_scatter(pcaches["blocks"], tables, view),
            "tables": tables,
        }
    return paged_fwd1


def _check_decode_len(cfg, tp, max_new):
    total = tp + max_new
    if total > cfg.max_len:
        raise ValueError(
            f"prompt+max_new ({total}) exceeds max_len ({cfg.max_len})"
        )
    return total


def transformer_generate(cfg: TransformerConfig):
    """Autoregressive sampling with a per-layer KV cache.

    ≙ the reference's LSTM sampling decode capability
    (models/classifiers/lstm/LSTM.java:219) at the transformer level.
    Returns ``generate(params, prompt, key, max_new, temperature, top_k)
    -> tokens (B, Tp + max_new)``; the whole decode (prefill + sampling)
    is two ``lax.scan``s inside one jittable function. ``temperature=0``
    decodes greedily. MoE configs decode through the dense per-token
    routing (generation is single-chip; capacity buffers are pointless
    at T=1).
    """
    forward_one, init_caches, do_prefill, cast_params = _decode_builder(cfg)

    def generate(params, prompt, key, max_new: int,
                 temperature: float = 1.0, top_k: int | None = None,
                 approx_top_k: bool = False):
        b, tp = prompt.shape
        total = _check_decode_len(cfg, tp, max_new)
        params = cast_params(params)
        caches, logits = do_prefill(params, init_caches(b, total), prompt)

        def sample(logits, key):
            logits = _top_k_filter(logits, top_k, approx_top_k)
            if temperature == 0:
                return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
            return jax.random.categorical(
                key, logits / temperature, axis=-1
            ).astype(prompt.dtype)

        def step(carry, i):
            caches, logits, key = carry
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)
            logits, caches = forward_one(params, caches, tok, tp + i)
            return (caches, logits, key), tok

        (_, _, _), new_tokens = lax.scan(
            step, (caches, logits, key), jnp.arange(max_new)
        )
        return jnp.concatenate([prompt, new_tokens.T], axis=1)

    return generate


def transformer_beam_search(cfg: TransformerConfig):
    """KV-cached beam-search decoding.

    ≙ the reference's LSTM ``BeamSearch`` (models/classifiers/lstm/
    LSTM.java:241-336) at the transformer level. Returns
    ``beam(params, prompt, beam_width, max_new) ->
    (tokens (B, W, Tp+max_new), log_probs (B, W))`` with beams sorted
    best-first. The whole search is one ``lax.scan``: each step flattens
    the (B, W) beams into the cache batch dim, expands the top W
    continuations of each beam from the W*V candidate pool, and gathers
    the caches of the surviving parents.
    """
    _gated_refuses(
        cfg, "beam search",
        "the beam reorder gathers one stacked cache along its batch axis "
        "and has not been tried on leaves grouped by layer kind (a slab "
        "and a ring, or latent rows)",
    )
    forward_one, init_caches, do_prefill, cast_params = _decode_builder(cfg)

    def beam(params, prompt, beam_width: int, max_new: int):
        b, tp = prompt.shape
        w = beam_width
        v = cfg.vocab_size
        total = _check_decode_len(cfg, tp, max_new)

        # prefill once at batch B, then tile caches/logits to B*W beams
        params = cast_params(params)
        caches, logits = do_prefill(params, init_caches(b, total), prompt)
        # tree-mapped: int8 mode carries {"kv", "scale"}, both with the
        # cache batch on axis 2
        caches = jax.tree.map(
            lambda a: jnp.repeat(a, w, axis=2), caches
        )  # (nl, 2, B*W, Tpad, ...)
        logp = jax.nn.log_softmax(logits, axis=-1)  # (B, V)
        # beam 0 holds the live hypothesis; the rest start at -inf so the
        # first expansion draws W distinct tokens from beam 0's logits
        scores = jnp.full((b, w), -jnp.inf).at[:, 0].set(0.0)
        logp = jnp.repeat(logp[:, None], w, axis=1)  # (B, W, V)
        tokens = jnp.zeros((b, w, max_new), prompt.dtype)

        def step(carry, i):
            caches, logp, scores, tokens = carry
            cand = scores[:, :, None] + logp  # (B, W, V)
            top_scores, flat_idx = lax.top_k(
                cand.reshape(b, w * v), w
            )  # (B, W)
            parent = flat_idx // v  # (B, W) surviving beam index
            tok = (flat_idx % v).astype(tokens.dtype)  # (B, W)
            # reorder history + caches to the surviving parents
            tokens = jnp.take_along_axis(
                tokens, parent[:, :, None], axis=1
            )
            tokens = lax.dynamic_update_index_in_dim(
                tokens, tok, i, axis=2
            )
            flat_parent = (
                jnp.arange(b)[:, None] * w + parent
            ).reshape(-1)  # (B*W,) into the cache batch dim
            caches = jax.tree.map(
                lambda a: jnp.take(a, flat_parent, axis=2), caches
            )
            logits, caches = forward_one(
                params, caches, tok.reshape(-1), tp + i
            )
            logp = jax.nn.log_softmax(logits, axis=-1).reshape(b, w, v)
            return (caches, logp, top_scores, tokens), None

        (caches, logp, scores, tokens), _ = lax.scan(
            step, (caches, logp, scores, tokens), jnp.arange(max_new)
        )
        # sort beams best-first
        order = jnp.argsort(-scores, axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        tokens = jnp.take_along_axis(tokens, order[:, :, None], axis=1)
        full = jnp.concatenate(
            [jnp.repeat(prompt[:, None], w, axis=1), tokens], axis=2
        )
        return full, scores

    return beam


# chunk widths of the selection's levels: the lane width of the TPU's
# vector registers over the row, then the sublane count over what the
# first level leaves
_SELECT_CHUNKS = (128, 8)


def select_chunks(vocab: int, top_k: int) -> tuple:
    """The chunk widths ``_kth_largest`` narrows a row of ``vocab``
    logits by: first 128 where ``top_k`` chunks of 128 hold at most a
    quarter of the row (``top_k * 128 * 4 <= vocab``), else 64 under
    the same condition (a 19,200-id slice of a vocabulary at k = 40,
    where the sort of the row cost 4 ms a substep at 224 slots:
    PERF.md, PR 31), then 8. Empty: the row is too short for a level
    to pay."""
    for chunk in (_SELECT_CHUNKS[0], 64):
        if top_k * chunk * 4 <= vocab:
            return (chunk, _SELECT_CHUNKS[1])
    return ()


def topk_select(vocab: int, top_k: int | None,
                approx_top_k: bool = False) -> str:
    """Which branch of ``_top_k_filter`` these static sizes take:
    ``"none"`` (no filter), ``"approx"`` (``approx_max_k``), and for
    the exact threshold ``"chunked"`` (the selection by chunks) where
    :func:`select_chunks` finds a first level whose candidates are at
    most a quarter of the row, ``"sort"`` (``lax.top_k`` over the row)
    otherwise. The engine reports it."""
    if top_k is None:
        return "none"
    if approx_top_k:
        return "approx"
    return "chunked" if select_chunks(vocab, top_k) else "sort"


def _chunk_candidates(x, k: int, chunk: int):
    """``[..., k * chunk]``: the k chunks of ``chunk`` neighbours (the
    tail padded with ``-inf``) whose maxima are largest. They hold the
    row's k-th largest value as their own k-th largest: every element
    above the k-th largest chunk maximum lies in a chosen chunk, and
    the chosen chunks hold at least k elements at or above it (their
    maxima), ties and ``-inf`` rows included."""
    lead, v = x.shape[:-1], x.shape[-1]
    n = -(-v // chunk)
    pad = [(0, 0)] * len(lead) + [(0, n * chunk - v)]
    chunks = jnp.pad(x, pad, constant_values=-jnp.inf).reshape(
        lead + (n, chunk)
    )
    top = lax.top_k(chunks.max(axis=-1), k)[1]
    cand = jnp.take_along_axis(chunks, top[..., None], axis=-2)
    return cand.reshape(lead + (k * chunk,))


def _kth_largest(logits, k: int, chunks: tuple = ()):
    """The k-th largest value of each row, ``[..., 1]``: the float
    ``lax.top_k(logits, k)[0][..., -1:]`` returns, which is what this
    is without ``chunks``. Each chunk width first keeps the k chunks
    with the largest maxima (one pass and a sort of the chunk maxima),
    which leaves the k-th largest value what it was, so the row is
    never ordered: with ``_SELECT_CHUNKS`` at ``V = 50257``, ``k = 40``
    the sorts are of 393, 640 and 320 a row."""
    for chunk in chunks:
        logits = _chunk_candidates(logits, k, chunk)
    return lax.top_k(logits, k)[0][..., -1:]


def _top_k_filter(logits, top_k: int | None, approx_top_k: bool):
    """Top-k threshold filter on logits — ONE implementation shared by
    ``transformer_generate``'s sampler, speculative decoding's
    draft/verify distributions and the serving step programs, so the
    filter semantics (kth-logit tie handling; exact against the
    TPU-native ``approx_max_k`` threshold, recall~0.95) cannot drift
    between the paths the bench compares row-to-row. The filter needs
    one number of a row, the value of its k-th largest logit, and that
    value is unique, so any exact selection filters alike. XLA:TPU
    lowers ``lax.top_k`` to a key/value sort of the whole row (2.3-2.7
    ms a substep at ``[48, 50257]``): ``topk_select`` reads the static
    ``V`` and ``k`` and sends rows long enough for it to pay through
    the chunk levels of ``_kth_largest`` instead (0.2 ms there)."""
    how = topk_select(logits.shape[-1], top_k, approx_top_k)
    if how == "none":
        return logits
    if how == "approx":
        kth = lax.approx_max_k(logits, top_k)[0][..., -1:]
    else:
        kth = _kth_largest(
            logits, top_k, select_chunks(logits.shape[-1], top_k)
        )
    return jnp.where(logits < kth, -jnp.inf, logits)


def _filtered_probs(logits, temperature: float, top_k: int | None,
                    approx_top_k: bool = False):
    """The sampling distribution as explicit probabilities (f32):
    top-k filter then temperature softmax; ``temperature=0`` is a
    one-hot argmax. Shared by speculative decoding's draft and verify
    sides so the acceptance ratio compares the same family of filtered
    distributions the plain sampler uses (the filter DEFINES the target
    distribution, so exactness is w.r.t. the filtered target)."""
    logits = logits.astype(jnp.float32)
    if temperature == 0:
        return jax.nn.one_hot(
            jnp.argmax(logits, -1), logits.shape[-1], dtype=jnp.float32
        )
    logits = _top_k_filter(logits, top_k, approx_top_k)
    return jax.nn.softmax(logits / temperature, axis=-1)


def _block_chunk(cfg: TransformerConfig, x, p, kv_all, i, pos0,
                 tp_mesh=None, lora=None, adapter=None):
    """One transformer block over C consecutive cached-decode positions
    (x: (B, C, D), rows pos0..pos0+C-1): projection, RoPE, cache write,
    dense masked attention against the cache, MLP/MoE tail. ONE
    implementation serving both ``block_decode``'s non-kernel path
    (C=1) and the speculative verify chunk — the dense decode numerics
    cannot drift from the verify numerics because they are the same
    code. ``pos0`` is a scalar start position or an (B,) vector of
    per-row starts (the serving engine's per-slot decode depths).

    ``lora`` (this layer's slice of an :func:`init_lora_bank` bank —
    leaves (N, ...)) with ``adapter`` (B,) int rows adds each row's
    low-rank q and MLP deltas, gathered by adapter index inside the
    traced program so one dispatch serves mixed adapters. Rows with
    adapter 0 SELECT the untouched base activations (``jnp.where``,
    not an add of zeros) so their output is bitwise the base model's."""
    b, c, _ = x.shape
    kd = cfg.head_dim
    grp = cfg.n_heads // cfg.kv_heads
    vec_pos = jnp.ndim(pos0) == 1
    # (C,) shared positions, or (B, C) per-row positions
    positions = (pos0[:, None] if vec_pos else pos0) + jnp.arange(c)
    h_in = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    q, k_r, v_r = _project_qkv(cfg, p, h_in)  # (B,H,C,K), (B,Hkv,C,K)
    if lora is not None:
        # q delta BEFORE RoPE — where a merged wq+AB would land it, so
        # a slot's stream matches a single-adapter engine's flop order
        dq = _lora_delta(
            h_in,
            jnp.take(lora["a_q"], adapter, axis=0),
            jnp.take(lora["b_q"], adapter, axis=0),
        ).reshape(b, c, cfg.n_heads, kd).transpose(0, 2, 1, 3)
        q = jnp.where((adapter > 0)[:, None, None, None], q + dq, q)
    if cfg.rope:
        cos, sin = _rope_tables(positions, cfg.head_dim, x.dtype)
        if vec_pos:  # (B, C, hd/2): per-row tables over the head axis
            cos, sin = cos[:, None], sin[:, None]
        else:  # (C, hd/2)
            cos, sin = cos[None, None], sin[None, None]
        q = _apply_rope(q, cos, sin)
        k_r = _apply_rope(k_r, cos, sin)
    kv_rows = jnp.stack(
        [
            k_r.transpose(0, 2, 1, 3).reshape(b, c, -1),
            v_r.transpose(0, 2, 1, 3).reshape(b, c, -1),
        ]
    )[None]  # (1, 2, B, C, Hkv*K)
    if cfg.decode_int8:
        kv_buf, sc_buf = kv_all["kv"], kv_all["scale"]
        q_rows, s_rows = _quantize_int8(
            kv_rows.astype(jnp.float32), (-1,)
        )
        if vec_pos:
            bidx = jnp.arange(b)[:, None]
            for plane in range(2):
                kv_buf = kv_buf.at[i, plane, bidx, positions].set(
                    q_rows[0, plane]
                )
                sc_buf = sc_buf.at[i, plane, bidx, positions].set(
                    s_rows[0, plane]
                )
        else:
            kv_buf = lax.dynamic_update_slice(
                kv_buf, q_rows, (i, 0, 0, pos0, 0)
            )
            sc_buf = lax.dynamic_update_slice(
                sc_buf, s_rows, (i, 0, 0, pos0, 0)
            )
        kv_all = {"kv": kv_buf, "scale": sc_buf}
        ck = (kv_buf[i, 0].astype(jnp.float32)
              * sc_buf[i, 0]).astype(x.dtype)
        cv = (kv_buf[i, 1].astype(jnp.float32)
              * sc_buf[i, 1]).astype(x.dtype)
    else:
        if vec_pos:
            bidx = jnp.arange(b)[:, None]
            rows = kv_rows.astype(kv_all.dtype)
            for plane in range(2):
                kv_all = kv_all.at[i, plane, bidx, positions].set(
                    rows[0, plane]
                )
        else:
            kv_all = lax.dynamic_update_slice(
                kv_all, kv_rows.astype(kv_all.dtype), (i, 0, 0, pos0, 0)
            )
        ck, cv = kv_all[i, 0], kv_all[i, 1]
    tpad = ck.shape[1]
    ck4 = ck.reshape(b, tpad, cfg.kv_heads, kd)
    cv4 = cv.reshape(b, tpad, cfg.kv_heads, kd)
    qg = q.reshape(b, cfg.kv_heads, grp, c, kd)  # head = kv*G + g
    att = jnp.einsum(
        "bhgck,bthk->bhgct", qg, ck4
    ) / jnp.sqrt(kd).astype(x.dtype)
    # causal against the cache: (C, Tpad) shared, or (B, C, Tpad)
    mask = jnp.arange(tpad)[None, :] <= positions[..., None]
    att = jnp.where(
        mask[:, None, None] if vec_pos else mask[None, None, None], att,
        -jnp.inf,
    )
    w_att = jax.nn.softmax(att, axis=-1)
    o = jnp.einsum("bhgct,bthk->bhgck", w_att, cv4)
    o_flat = o.transpose(0, 3, 1, 2, 4).reshape(
        b, c, cfg.n_heads * kd
    )
    # TP: gather the head-sharded attention output before the row
    # projection so the reduction keeps single-chip order
    o_flat = _tp_replicate(o_flat, tp_mesh)
    x = x + jnp.einsum(
        "bch,hd->bcd", o_flat,
        _w(p, "wo", x.dtype).reshape(cfg.n_heads * kd, -1),
    )
    h_in = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    if cfg.n_experts:
        from deeplearning4j_tpu.parallel.expert_parallel import (
            moe_reference,
        )

        moe_params = jax.tree.map(
            lambda a: a.astype(x.dtype), p["moe"]
        )
        flat = h_in.reshape(-1, h_in.shape[-1])
        y = moe_reference(
            moe_params, flat, k=cfg.moe_k, activation=jax.nn.gelu
        )
        x = x + y.reshape(h_in.shape)
    elif lora is not None:
        dm = _lora_delta(
            h_in,
            jnp.take(lora["a_mlp"], adapter, axis=0),
            jnp.take(lora["b_mlp"], adapter, axis=0),
        )
        x = x + _mlp(p, h_in, tp_mesh, delta1=dm,
                     sel=(adapter > 0)[:, None, None])
    else:
        x = x + _mlp(p, h_in, tp_mesh)
    return x, kv_all

def _chunk_builder(cfg: TransformerConfig, tp_mesh=None):
    """Chunked cached forward — the verify side of speculative decoding:
    ``forward_chunk(params, caches, toks (B, C), pos0)`` advances C
    consecutive positions (pos0..pos0+C-1) through all layers against
    the live KV cache in ONE pass and returns (logits (B, C, V),
    caches). Decode is weight-stream-bound, so verifying C=k+1 draft
    positions costs ~one decode step of HBM traffic, not k: the C
    queries ride the same streamed weights as a single wide MXU dot.
    Per-layer work delegates to :func:`_block_chunk` — the same code
    ``block_decode``'s non-kernel path runs at C=1."""
    if cfg.gated:
        return _gated_builder(cfg)[4]

    def forward_chunk(params, caches, toks, pos0, last_idx=None,
                      adapter=None):
        b, c = toks.shape
        # per-index clip: positions past max_len (possible only for
        # slots whose outputs are discarded at the buffer slice) clamp
        # individually instead of shifting the whole slice
        pos_rows = jnp.take(
            params["pos"], pos0 + jnp.arange(c), axis=0, mode="clip"
        )
        x = (params["embed"][toks] + pos_rows[None]).astype(
            cfg.compute_dtype
        )
        kv_all = caches
        lora = params.get("lora") if adapter is not None else None
        for i in range(cfg.n_layers):
            p_i = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
            l_i = (None if lora is None
                   else jax.tree.map(lambda a, i=i: a[i], lora))
            x, kv_all = _block_chunk(
                cfg, x, p_i, kv_all, i, pos0, tp_mesh=tp_mesh,
                lora=l_i, adapter=adapter,
            )
        if last_idx is not None:
            # single-row logits (bucketed-prefill chunking: only the
            # true last token's row matters; skips the (C, V) head).
            # Vector last_idx = per-row last index, for the batched
            # suffix-prefill of prefix-cache hits.
            if jnp.ndim(last_idx) == 1:
                x_last = jnp.take_along_axis(
                    x, last_idx[:, None, None], axis=1
                )[:, 0]
            else:
                x_last = lax.dynamic_index_in_dim(
                    x, last_idx, axis=1, keepdims=False
                )
            x_last = _layer_norm(
                x_last, params["lnf_scale"], params["lnf_bias"]
            )
            logits = jnp.einsum(
                "bd,dv->bv", x_last, _w(params, "head", x_last.dtype),
                preferred_element_type=jnp.float32,
            )
            return _tp_replicate(logits, tp_mesh), kv_all
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        logits = jnp.einsum(
            "bcd,dv->bcv", x, _w(params, "head", x.dtype),
            preferred_element_type=jnp.float32,
        )
        return _tp_replicate(logits, tp_mesh), kv_all

    return forward_chunk


def transformer_speculative_generate(
    cfg: TransformerConfig, draft_cfg: TransformerConfig | None = None
):
    """Speculative decoding: a cheap draft model proposes ``draft_k``
    tokens autoregressively, the target model verifies all of them in
    one chunked forward, and rejection sampling keeps the output an
    exact sample from the target's (filtered) distribution
    [Leviathan et al. 2023; Chen et al. 2023 — the published
    algorithm, implemented here from the math].

    Exactness caveat (true of ANY floating-point implementation of the
    algorithm): "the target's distribution" means the target weights as
    computed by the chunked verify program. That program is a
    differently-scheduled XLA lowering than ``transformer_generate``'s
    serial decode, so their logits agree only to float-reassociation
    level (~1e-2 relative on random-init models) — at temperature 0
    the two decoders emit identical tokens except where the top-2
    logit margin is inside that band (near-ties). The acceptance MATH
    is exact for whatever p the verify program produces; the
    guarantee is distribution-level w.r.t. that program, not bitwise
    token equality with the serial decoder.

    TPU-first shape: the natural draft on one chip is the SAME model
    weight-only int8 quantized (``quantize_decode_params`` with
    ``draft_cfg`` = the int8 variant) — near-1 acceptance because
    draft≈target, ~half the weight stream per draft step, and
    target-distribution outputs, turning the lossy quantization
    speedup into a distribution-preserving one at B=1 (the latency
    row PERF.md's wall analysis says no byte savings can otherwise
    reach).

    Returns ``generate(params, draft_params, prompt, key, max_new,
    draft_k, temperature, top_k, approx_top_k, return_stats) ->
    tokens (1, Tp + max_new)`` (with ``return_stats`` also a
    ``{"rounds": n}`` dict — rounds ≈ max_new/(k+1) at perfect
    acceptance, the efficiency diagnostic). Batch is fixed at 1:
    acceptance lengths are ragged across batch rows, and the feature
    targets interactive latency (the B>=16 throughput rows are already
    weight-amortized). Prompts need >= 2 tokens (each round's first
    draft step is a 2-token catch-up chunk). The whole loop is one
    jittable ``lax.while_loop``; both caches stay device-resident.

    ≙ the serving capability the reference's era lacked entirely; the
    sampling surface matches ``transformer_generate``
    (LSTM.java:219 ≙ sampleDoc at the transformer level).
    """
    if draft_cfg is None:
        draft_cfg = cfg
    for c in (cfg, draft_cfg):
        _gated_refuses(
            c, "speculative decoding",
            "a rejected draft rewinds the position: a ring leaf has "
            "already overwritten the rows the rewound window needs, and "
            "the verify chunk has not been tried on a latent leaf",
        )
    _, t_init, t_prefill, t_cast = _decode_builder(cfg)
    t_chunk = _chunk_builder(cfg)
    d_fwd1, d_init, d_prefill, d_cast = _decode_builder(draft_cfg)
    d_chunk = _chunk_builder(draft_cfg)

    def generate(params, draft_params, prompt, key, max_new: int,
                 draft_k: int = 4, temperature: float = 1.0,
                 top_k: int | None = None, approx_top_k: bool = False,
                 return_stats: bool = False):
        b, tp = prompt.shape
        if b != 1:
            raise ValueError(
                "speculative decode is the B=1 latency path (acceptance "
                "lengths are ragged across batch rows)"
            )
        if tp < 2:
            raise ValueError(
                "speculative decode needs a prompt of >= 2 tokens (each "
                "round's first draft step is a 2-token catch-up chunk)"
            )
        k = int(draft_k)
        assert k >= 1
        total = _check_decode_len(cfg, tp, max_new)
        _check_decode_len(draft_cfg, tp, max_new)
        v = cfg.vocab_size
        params = t_cast(params)
        draft_params = d_cast(draft_params)
        # caches padded by k+1 rows: a round may write (and later
        # overwrite) up to k+1 positions past the accepted prefix
        caches_t = t_init(b, total + k + 1)
        caches_d = d_init(b, total + k + 1)
        # lag-one prefill: the last prompt token is NOT consumed — each
        # round's chunk/draft feeds it first, so the target cache always
        # trails the emitted prefix by exactly one row. The lag would
        # push a flash-aligned prompt (%128 above one block —
        # _flash_seq_ok) off the kernel path, so bulk-prefill the
        # aligned PREFIX and chunk-forward the <=127-token remainder.
        pre = tp - 1  # >= 1: the tp >= 2 guard above
        aligned = pre - (pre % 128) if pre > 128 else pre
        if aligned:
            caches_t, _ = t_prefill(
                params, caches_t, prompt[:, :aligned]
            )
            caches_d, _ = d_prefill(
                draft_params, caches_d, prompt[:, :aligned]
            )
        if pre - aligned:
            rest = prompt[:, aligned:pre]
            _, caches_t = t_chunk(params, caches_t, rest, aligned)
            _, caches_d = d_chunk(draft_params, caches_d, rest, aligned)
        c_prev2 = prompt[:, -2].astype(jnp.int32)
        c_prev = prompt[:, -1].astype(jnp.int32)
        buf = jnp.zeros((b, total + k + 1), jnp.int32)
        buf = lax.dynamic_update_slice(
            buf, prompt.astype(jnp.int32), (0, 0)
        )

        def pick(p, kk):
            if temperature == 0:
                return jnp.argmax(p, -1).astype(jnp.int32)
            return jax.random.categorical(
                kk, jnp.log(p + 1e-30), axis=-1
            ).astype(jnp.int32)

        def cond(carry):
            return carry[3] < total

        def body(carry):
            caches_t, caches_d, buf, pos, c_prev2, c_prev, key, rounds = carry
            key, kd1, kdr, ku, kc = jax.random.split(key, 5)

            # first draft step is a 2-token catch-up chunk over the two
            # tokens behind the cursor: after a fully-accepted round the
            # draft cache is missing d_k's row (the draft sampled d_k
            # but never fed it) AND the correction token's row — this
            # chunk writes both (rewrites are idempotent: a row is a
            # deterministic function of its token, position, and the
            # rows before it), so no permanent zero row can enter the
            # attention window and erode acceptance
            pair = jnp.concatenate(
                [c_prev2[:, None], c_prev[:, None]], axis=1
            )
            lg2, caches_d = d_chunk(draft_params, caches_d, pair, pos - 2)
            q1 = _filtered_probs(
                lg2[:, 1], temperature, top_k, approx_top_k
            )  # (B, V)
            d1 = pick(q1, kd1)

            # remaining k-1 draft tokens serially (each step ~the
            # quantized weight stream), recording proposal distributions
            def dstep(dc, i):
                caches_d, tok, kk = dc
                kk, ks = jax.random.split(kk)
                lg, caches_d = d_fwd1(
                    draft_params, caches_d, tok, pos - 1 + i
                )
                qv = _filtered_probs(
                    lg, temperature, top_k, approx_top_k
                )  # (B, V)
                d = pick(qv, ks)
                return (caches_d, d, kk), (d, qv)

            (caches_d, _, _), (ds_rest, qs_rest) = lax.scan(
                dstep, (caches_d, d1, kdr), jnp.arange(1, k)
            )
            ds_t = jnp.concatenate(
                [d1[:, None], ds_rest.T], axis=1
            )  # (B, k)
            qs_t = jnp.concatenate(
                [q1[:, None], jnp.transpose(qs_rest, (1, 0, 2))], axis=1
            )  # (B, k, V)

            # verify: ONE chunked target forward over
            # [c_prev, d_1..d_k] yields p for every draft slot + bonus
            chunk_toks = jnp.concatenate(
                [c_prev[:, None], ds_t], axis=1
            )  # (B, k+1)
            vlg, caches_t = t_chunk(params, caches_t, chunk_toks, pos - 1)
            ps = _filtered_probs(
                vlg, temperature, top_k, approx_top_k
            )  # (B, k+1, V)

            # rejection sampling: accept d_i with prob min(1, p/q);
            # u*q < p is the division-free form
            p_d = jnp.take_along_axis(
                ps[:, :k], ds_t[..., None], -1
            )[..., 0]  # (B, k)
            q_d = jnp.take_along_axis(qs_t, ds_t[..., None], -1)[..., 0]
            u = jax.random.uniform(ku, (b, k))
            accept = u * jnp.maximum(q_d, 1e-30) < p_d
            n = jnp.sum(
                jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
            )  # (B,) accepted count; k = all accepted

            # correction token: on reject at slot n sample the residual
            # max(p-q, 0)/Z; with n=k the padded q row is zero so the
            # SAME formula samples the bonus token from p directly
            qs_pad = jnp.concatenate(
                [qs_t, jnp.zeros((b, 1, v), qs_t.dtype)], axis=1
            )
            pn = jnp.take_along_axis(ps, n[:, None, None], axis=1)[:, 0]
            qn = jnp.take_along_axis(
                qs_pad, n[:, None, None], axis=1
            )[:, 0]
            resid = jnp.maximum(pn - qn, 0.0)
            rs = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(rs > 0, resid / rs, pn)
            ctok = pick(resid, kc)  # (B,)

            # emit d_1..d_n then the correction token at slot n; slots
            # past n are scratch (overwritten by later rounds, sliced
            # off at the end)
            jj = jnp.arange(k + 1)[None, :]
            ds_pad = jnp.concatenate(
                [ds_t, jnp.zeros((b, 1), ds_t.dtype)], axis=1
            )
            tile = jnp.where(
                jj < n[:, None], ds_pad,
                jnp.where(jj == n[:, None], ctok[:, None], 0),
            ).astype(jnp.int32)
            buf = lax.dynamic_update_slice(buf, tile, (0, pos))
            # the new cursor is pos+n+1; the token two behind it is d_n
            # (n>=1) or the incoming c_prev (n==0)
            prev2_new = jnp.where(
                n == 0, c_prev,
                jnp.take_along_axis(
                    ds_t, jnp.maximum(n - 1, 0)[:, None], axis=1
                )[:, 0],
            )
            return (caches_t, caches_d, buf, pos + n[0] + 1,
                    prev2_new, ctok, key, rounds + 1)

        init = (caches_t, caches_d, buf, jnp.int32(tp), c_prev2, c_prev,
                key, jnp.int32(0))
        fin = lax.while_loop(cond, body, init)
        out = fin[2][:, :total]
        if return_stats:
            return out, {"rounds": fin[7]}
        return out

    return generate


def fsdp_shardings(mesh: Mesh, cfg: TransformerConfig):
    """ZeRO-3-style augmentation of the TP layout: additionally shard
    each large param leaf over the *data* axis (first dim that the data
    axis divides and that isn't already sharded), so params — and the
    optimizer state, which mirrors them — consume 1/dp of the HBM per
    device. XLA inserts the all-gathers at use sites and reduce-scatters
    the matching gradient shards; nothing is hand-scheduled.
    """
    dp = mesh.shape[mesh_lib.DATA_AXIS]
    base = transformer_shardings(mesh, cfg)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), cfg)
    )

    def augment(sharding, shape):
        spec = list(sharding.spec) + [None] * (
            len(shape.shape) - len(sharding.spec)
        )
        if int(np.prod(shape.shape)) < 2 * dp:
            return sharding  # tiny leaf: replication is cheaper
        for i, (dim, s) in enumerate(zip(shape.shape, spec)):
            if s is None and dim % dp == 0 and dim >= dp:
                spec[i] = mesh_lib.DATA_AXIS
                return NamedSharding(mesh, P(*spec))
        return sharding

    return jax.tree.map(augment, base, shapes)


# param leaves exempt from AdamW weight decay: layernorm scales/biases,
# biases, and the learned position table — the standard LM recipe decays
# only the matmul weights. Matched by leaf *name* because the stacked
# (n_layers, ...) leading axis makes block biases 2-D, so an ndim test
# would misclassify them.
_NO_DECAY = frozenset({
    "ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
    "lnf_scale", "lnf_bias", "b1", "b2", "pos",
})


def _decay_mask(params):
    """True where AdamW weight decay applies (matmul weights only)."""

    def leaf_name(path):
        last = path[-1]
        return getattr(last, "key", None) or getattr(last, "name", "")

    return jax.tree_util.tree_map_with_path(
        lambda path, _: leaf_name(path) not in _NO_DECAY, params
    )


def lm_optimizer(
    peak_lr: float = 3e-4,
    total_steps: int = 10_000,
    warmup_steps: int | None = None,
    clip_norm: float = 1.0,
    weight_decay: float = 0.01,
) -> optax.GradientTransformation:
    """Standard LM training recipe: global-norm clipping + AdamW on a
    linear-warmup / cosine-decay schedule. Pass to
    ``transformer_train_step(optimizer=...)``; the state mirrors the
    param tree, so TP/FSDP shardings carry over unchanged. Weight decay
    is masked off norm scales/biases, biases, and the position table
    (``_decay_mask``), matching the standard LM recipe."""
    warmup = warmup_steps if warmup_steps is not None else max(
        1, total_steps // 20
    )
    sched = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=peak_lr,
        warmup_steps=warmup,
        # optax needs decay_steps > warmup_steps; tiny smoke runs
        # (total_steps <= warmup) must still construct
        decay_steps=max(total_steps, warmup + 1),
        end_value=peak_lr * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.adamw(sched, weight_decay=weight_decay, mask=_decay_mask),
    )


def transformer_train_step(
    mesh: Mesh, cfg: TransformerConfig, optimizer=None, fsdp: bool = False
):
    """Jitted composed dp x tp train step over a 2-D (data, model) mesh.

    Returns ``(step, init_state, shard_tokens)``:
    ``step(params, opt_state, tokens) -> (params, opt_state, loss)`` with
    params TP-sharded, tokens batch-sharded; both factory helpers place
    their outputs with the right shardings. ``fsdp=True`` additionally
    shards params/optimizer state over the data axis (ZeRO-3 layout via
    :func:`fsdp_shardings`).
    """
    from deeplearning4j_tpu.obs import compile_log

    compile_log.install()  # the step's compile is counted by the program
    optimizer = optimizer or optax.adamw(3e-4)
    loss_fn = transformer_loss(cfg, mesh)
    shardings = (
        fsdp_shardings(mesh, cfg) if fsdp else transformer_shardings(mesh, cfg)
    )
    batch_sh = NamedSharding(
        mesh,
        P(None, mesh_lib.DATA_AXIS)
        if cfg.sequence_parallel
        else P(mesh_lib.DATA_AXIS, None),
    )

    def init_state(key):
        # place_global handles the multi-process case (device_put cannot
        # address remote shards)
        params = jax.tree.map(
            mesh_lib.place_global, init_transformer(key, cfg), shardings
        )
        # adamw state mirrors the param tree, so it inherits the TP shardings
        opt_state = optimizer.init(params)
        return params, opt_state

    def shard_tokens(tokens):
        return mesh_lib.place_global(tokens, batch_sh)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        l, g = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(g, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, l

    return step, init_state, shard_tokens
