"""Laguna-S-2.1's decoder in plain float32 ``jax.numpy``: the yardstick for
``correct``.

Written from the published ``config.json`` (poolside/Laguna-S-2.1) and
section 1 of ISSUE 27; imports nothing from the program under test. No
biases anywhere, ``eps`` 1e-6, ``d`` = hidden size.

Layer ``l`` with ``H_l`` query heads (``num_attention_heads_per_layer``),
``Hkv`` KV heads, head size ``K``:

1. ``h = RMS_1(x)``; ``q = h Wq`` (T, H_l, K); ``k = h Wk``, ``v = h Wv``
   (T, Hkv, K); ``gate = sigmoid(h Wg)`` (T, H_l) (``gating: per-head``).
2. Rotary, rotate-half. ``sliding_attention``: ``rope_theta`` over all K
   channels. ``full_attention``: YaRN inverse frequencies over the first
   ``partial_rotary_factor * K`` channels (``transformers``'
   ``_compute_yarn_parameters``), ``cos`` and ``sin`` multiplied by
   ``attention_factor``; the other channels pass unrotated.
3. ``scores = q k^T / sqrt(K)``; query head j reads KV head j // (H_l /
   Hkv); key s is visible to query t iff s <= t and, in a sliding layer,
   t - s < ``sliding_window``. Softmax; ``a <- gate * a`` head by head;
   ``x <- x + concat(a) Wo``.
4. ``h2 = RMS_2(x)``. ``mlp_only_layers``: ``x <- x + Wd(silu(Wg' h2) * Wu
   h2)``. Every other layer: ``p = softmax(h2 Wr)`` over all published
   experts, ``S`` = the ``num_experts_per_tok`` largest, ``w_e =
   moe_routed_scaling_factor * p_e / sum_{S} p``, ``x <- x + sum_{e in S,
   e held} w_e F_e(h2) + F_shared(h2)``, every ``F`` a SwiGLU.
5. ``RMS_f``, then the untied head.

**The chip's share.** The tree holds experts ``expert_first`` ..
``expert_first + E_held - 1`` of the router's ``E_total``; the sum in 4
runs over the held ones only and nothing stands in for the others. The
embedding and the head hold a slice of the vocabulary.

**What no shape carries** (window, rotary constants, top-k, scale, eps,
the layer kinds, which experts are held) comes from the configuration's
own file, ``benchmark/configs/laguna-s-2.1.json``: ``forward(params,
tokens)`` is all ``benchmark/serve.py`` calls. The file's ``rehearse``
sizes are chosen when the tree's hidden size is the toy's.

The program's parameter tree (read as it is; any float type is upcast
where it is used, one expert at a time, so a bf16 tree is judged as the
bf16 weights it is and a held layer's 4.8 GB of float32 never exists):

    embed (V, d)   lnf_scale (d,)   head (d, V)
    layers[l]: ln1_scale ln2_scale (d,)   wq (d, H_l, K)
      wkv (d, 2, Hkv, K)   wg (d, H_l)   wo (H_l, K, d)
      dense:  w_gate w_up (d, F)   w_down (F, d)
      routed: router (d, E_total)   we_gate we_up (E_held, d, f)
              we_down (E_held, f, d)   ws_gate ws_up (d, fs)  ws_down (fs, d)

Departures from the release, all the program's and listed in the
configuration file under ``assumed``: SwiGLU with ``silu``;
softmax-then-top-k routing, scale applied after renormalising; the shared
expert added without a gate of its own; the attention gate from the
normed layer input, applied before ``Wo``; no QK-norm; pre-norm
residuals; random weights.

``precision`` (not used by ``correct``): a float type name; weights and
the activations entering every product are rounded to it first. This is
how the tolerances in the configuration file were set: ``float8_e4m3fn``
must fail them, ``bfloat16`` shows what rounding alone moves.
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONFIG_FILE = Path(__file__).resolve().parents[1] / "configs" / "laguna-s-2.1.json"


def settings_for(params) -> dict:
    """The ``model`` group of the configuration file, with its
    ``rehearse`` sizes laid over it when the tree is the toy's."""
    with open(CONFIG_FILE) as f:
        config = json.load(f)
    model = dict(config["model"])
    toy = config["rehearse"]["model"]
    if params["embed"].shape[1] == toy["d_model"]:
        model.update(toy)
    return model


def yarn_inv_freq(rot_dim, theta, factor, original_max, beta_fast, beta_slow):
    """``rot_dim // 2`` inverse frequencies. Pair i: ``f_i =
    theta^(-2i / rot_dim)``; ``i(n) = rot_dim ln(original_max / (2 pi n))
    / (2 ln theta)`` is the pair whose wavelength makes n turns in the
    original context; ``low = floor(i(beta_fast))``, ``high =
    ceil(i(beta_slow))``; ``r_i = 1 - clip((i - low) / (high - low), 0,
    1)``; the result ``(1 - r_i) f_i / factor + r_i f_i``."""
    half = rot_dim // 2
    out = []
    low = max(math.floor(rot_dim * math.log(original_max / (2 * math.pi * beta_fast))
                         / (2 * math.log(theta))), 0)
    high = min(math.ceil(rot_dim * math.log(original_max / (2 * math.pi * beta_slow))
                         / (2 * math.log(theta))), rot_dim - 1)
    for i in range(half):
        f = theta ** (-i / half)
        r = 1.0 - min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append((1.0 - r) * f / factor + r * f)
    return np.asarray(out, np.float64)


def _rope_tables(settings: dict, kind: str, t: int):
    """(cos, sin, rotated channels) at positions 0..t-1."""
    head = settings["head_size"]
    full = settings.get("rope_full") if kind == "full_attention" else None
    if full is None:
        half = head // 2
        inv = np.asarray(
            [settings["rope_theta"] ** (-i / half) for i in range(half)]
        )
        factor, rot = 1.0, head
    else:
        rot = int(head * full["partial_rotary_factor"])
        inv = yarn_inv_freq(
            rot, full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"],
        )
        factor = full["attention_factor"]
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32), rot)


def _rotate(x, cos, sin, rot):
    """x (T, H, K): rotate-half over the first ``rot`` channels."""
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]], axis=-1
    )


def _forward_row(params, tokens, settings: dict, precision):
    """Logits (T, V) of one sequence ``tokens`` (T,)."""
    eps = settings["norm_eps"]
    low = None if precision is None else jnp.dtype(precision)

    def f32(x):  # a weight or an activation as a product reads it
        if low is not None:
            x = x.astype(low)
        return x.astype(jnp.float32)

    def rms(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
            g.astype(jnp.float32))

    def swiglu(h, wg, wu, wd):
        a = jax.nn.silu(f32(h) @ f32(wg)) * (f32(h) @ f32(wu))
        return f32(a) @ f32(wd)

    t = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    s_idx = jnp.arange(t)
    causal = s_idx[None, :] <= s_idx[:, None]
    span = settings.get("sliding_window")
    window = (causal & (s_idx[:, None] - s_idx[None, :] < span)
              if span else causal)
    routing = []
    for l, p in enumerate(params["layers"]):
        kind = settings["layer_types"][l]
        h = rms(x, p["ln1_scale"])
        q = jnp.einsum("td,dhk->thk", f32(h), f32(p["wq"]))
        kv = jnp.einsum("td,dshk->sthk", f32(h), f32(p["wkv"]))
        cos, sin, rot = _rope_tables(settings, kind, t)
        q = _rotate(q, cos, sin, rot)
        k = _rotate(kv[0], cos, sin, rot)
        v = kv[1]
        heads, size = q.shape[1], q.shape[2]
        group = heads // k.shape[1]
        k = jnp.repeat(k, group, axis=1)  # query head j reads j // group
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhk,shk->hqs", f32(q), f32(k)) / math.sqrt(size)
        mask = causal if kind == "full_attention" else window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        a = jnp.einsum("hqs,shk->qhk", f32(jax.nn.softmax(scores, -1)), f32(v))
        if "wg" in p:
            gate = jax.nn.sigmoid(f32(h) @ f32(p["wg"]))  # (T, H)
            a = a * gate[:, :, None]
        x = x + jnp.einsum("qhk,hkd->qd", f32(a), f32(p["wo"]))
        h2 = rms(x, p["ln2_scale"])
        if "router" not in p:
            x = x + swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])
            continue
        # the router's product is float32 on both sides of ``correct``
        probs = jax.nn.softmax(
            h2 @ p["router"].astype(jnp.float32), axis=-1)
        top_p, top_i = lax.top_k(probs, settings["moe_k"])
        routing.append(top_i)
        top_w = settings["moe_scale"] * top_p / jnp.sum(
            top_p, axis=-1, keepdims=True)
        first = settings.get("expert_first", 0)

        def one_expert(y, e, p=p, h2=h2, top_i=top_i, top_w=top_w,
                       first=first):
            # w_e for the tokens that chose expert first + e, else 0
            w = jnp.sum(jnp.where(top_i == first + e, top_w, 0.0), axis=-1)
            out = swiglu(h2, p["we_gate"][e], p["we_up"][e], p["we_down"][e])
            return y + w[:, None] * out, None

        y, _ = lax.scan(
            one_expert, jnp.zeros_like(x), jnp.arange(p["we_gate"].shape[0])
        )
        if "ws_gate" in p:
            y = y + swiglu(h2, p["ws_gate"], p["ws_up"], p["ws_down"])
        x = x + y
    x = rms(x, params["lnf_scale"])
    logits = f32(x) @ f32(params["head"])
    return logits, (jnp.stack(routing) if routing else jnp.zeros((0,), jnp.int32))


@functools.lru_cache(maxsize=None)
def _row_program(settings_json: str, precision):
    settings = json.loads(settings_json)

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return _forward_row(params, tokens, settings, precision)

    return jax.jit(run)


def forward(params, tokens, settings: dict | None = None, precision=None,
            with_routing: bool = False):
    """Logits (B, T, V) float32 of ``tokens`` (B, T) int32, one sequence
    at a time (a numpy array: at the benchmark's size the four rows are
    0.8 GB). ``with_routing`` also returns the experts each token chose,
    (B, expert layers, T, k)."""
    settings = settings_for(params) if settings is None else settings
    run = _row_program(json.dumps(settings, sort_keys=True), precision)
    rows = [run(params, row) for row in jnp.asarray(tokens)]
    logits = np.stack([np.asarray(lg) for lg, _ in rows])
    if with_routing:
        return logits, np.stack([np.asarray(r) for _, r in rows])
    return logits
