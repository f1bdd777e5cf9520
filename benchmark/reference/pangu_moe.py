"""openPangu-Ultra-MoE-718B's decoder in plain float32 ``jax.numpy``: the
yardstick for ``correct``.

Written from the published ``config.json`` (FreedomIntelligence/
openPangu-Ultra-MoE-718B) and section 1 of ISSUE 31; imports nothing from
the program under test. No biases anywhere, ``eps`` 1e-5, ``d`` = hidden
size, ``H`` heads, ``r_q`` / ``r_kv`` = ``q_lora_rank`` / ``kv_lora_rank``,
``n`` / ``p`` / ``v`` = ``qk_nope_head_dim`` / ``qk_rope_head_dim`` /
``v_head_dim``. ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``.

Every layer, in the EXPANDED form of the attention only (the program
decodes in the absorbed form; agreeing with this file is what shows the
two are one attention):

1. ``h = RMS_in(x)``.
2. ``c_q = RMS_q(h W_qa)`` (r_q); ``[q_n, q_p] = c_q W_qb`` per head
   (n and p channels).
3. ``[c, k_p] = h W_kva`` (r_kv + p); ``c <- RMS_kv(c)``; ``k_p`` is ONE
   key part for all heads. Per head ``k_n = c W_uk`` (n), ``v = c W_uv``
   (v): ``W_kvb``'s two halves, which the tree holds as two leaves.
4. Rotary, rotate-half, plain ``rope_theta`` over the p channels, on
   ``q_p`` (every head) and ``k_p`` only. No scaling: the config has no
   ``rope_scaling``.
5. ``scores[t, s, j] = (q_n[t, j] . k_n[s, j] + q_p[t, j] . k_p[s]) /
   sqrt(n + p)``, causal, softmax, ``o[t, j] = sum_s P v[s, j]``, ``a =
   concat_j(o) W_o``.
6. Sandwich norms: ``x <- x + RMS_post_attn(a)``; ``h3 = RMS_pre_mlp(x)``;
   ``x <- x + RMS_post_mlp(F(h3))``.
7. ``F``: in ``dense_layers`` a SwiGLU (silu). Every other layer: ``s =
   sigmoid(h3 W_r)`` over all published experts, ``S`` = the ``moe_k``
   largest, ``w_e = moe_scale * s_e / (sum_{S} s + 1e-20)``, ``F(h3) =
   sum_{e in S, e held} w_e F_e(h3) + F_shared(h3)``, every ``F`` a SwiGLU.
8. ``RMS_f``, then the untied head.

**The chip's share.** The tree holds experts ``expert_first`` ..
``expert_first + E_held - 1`` of the router's ``E_total``; the sum in 7
runs over the held ones only and nothing stands in for the others. The
embedding and the head hold a slice of the vocabulary. Attention and the
shared expert are whole.

**What no shape carries** (rotary base, head parts, top-k, scale, eps,
which layers are dense, which experts are held) comes from the
configuration's own file, ``benchmark/configs/openpangu-ultra-moe-718b
.json``: ``forward(params, tokens)`` is all ``benchmark/serve.py`` calls.
The file's ``rehearse`` sizes are chosen when the tree's hidden size is
the toy's.

The program's parameter tree (read as it is; any float type is upcast
where it is used, one matrix or one expert at a time, and a layer is one
jitted program, so a bf16 tree is judged as the bf16 weights it is and the
float32 copy of more than one layer never exists):

    embed (V, d)   lnf_scale (d,)   head (d, V)
    layers[l]: ln1_scale ln1_post_scale ln2_scale ln2_post_scale (d,)
      wq_a (d, r_q)   q_norm_scale (r_q,)   wq_b (r_q, H, n + p)
      wkv_a (d, r_kv + p)   kv_norm_scale (r_kv,)
      w_uk (r_kv, H, n)   w_uv (r_kv, H, v)   wo (H, v, d)
      dense:  w_gate w_up (d, F)   w_down (F, d)
      routed: router (d, E_total)   we_gate we_up (E_held, d, f)
              we_down (E_held, f, d)   ws_gate ws_up (d, fs)  ws_down (fs, d)

Departures from the release, listed in the configuration file under
``assumed`` and ``departures``: sigmoid scores with no group limit and no
selection bias; the ``1e-20``; the placement of the sandwich norms;
RMSNorm on ``c_q`` and ``c``; rotate-half pairing; random weights; the
multi-token-prediction module (``num_nextn_predict_layers``) is not
computed: the model's own next-token logits do not depend on it.

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

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "openpangu-ultra-moe-718b.json")
#: heads whose (T, T) scores exist at once
_HEADS_AT_ONCE = 16


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


def _rope_tables(theta: float, rot: int, t: int):
    """(cos, sin) (t, rot / 2) at positions 0..t-1."""
    half = rot // 2
    inv = np.asarray([theta ** (-i / half) for i in range(half)])
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rotate(x, cos, sin):
    """Rotate-half over all channels of ``x`` (T, ..., rot); ``cos``,
    ``sin`` (T, rot / 2) broadcast over the middle axes."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(p, x, settings: dict, dense: bool, precision):
    """One layer over one sequence ``x`` (T, d) -> (x, chosen experts
    (T, k) or None)."""
    eps = settings["norm_eps"]
    n, v = settings["qk_nope_head_dim"], settings["v_head_dim"]
    rot = settings["qk_rope_head_dim"]
    r_kv = settings["kv_lora_rank"]
    low = None if precision is None else jnp.dtype(precision)

    def f32(a):  # a weight or an activation as a product reads it
        if low is not None:
            a = a.astype(low)
        return a.astype(jnp.float32)

    def rms(a, g):
        return a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * (
            g.astype(jnp.float32))

    def swiglu(h, wg, wu, wd):
        a = jax.nn.silu(f32(h) @ f32(wg)) * (f32(h) @ f32(wu))
        return f32(a) @ f32(wd)

    t = x.shape[0]
    h = rms(x, p["ln1_scale"])
    c_q = rms(f32(h) @ f32(p["wq_a"]), p["q_norm_scale"])
    q = jnp.einsum("tr,rhk->thk", f32(c_q), f32(p["wq_b"]))
    ckp = f32(h) @ f32(p["wkv_a"])
    c = rms(ckp[:, :r_kv], p["kv_norm_scale"])
    cos, sin = _rope_tables(settings["rope_theta"], rot, t)
    q_n, q_p = q[..., :n], _rotate(q[..., n:], cos, sin)
    k_p = _rotate(ckp[:, r_kv:], cos, sin)  # (T, p): one for all heads
    k_n = jnp.einsum("tc,chn->thn", f32(c), f32(p["w_uk"]))
    val = jnp.einsum("tc,chv->thv", f32(c), f32(p["w_uv"]))
    heads = q.shape[1]
    s_idx = jnp.arange(t)
    causal = s_idx[None, :] <= s_idx[:, None]

    def some_heads(args):
        qn, qp, kn, vv = args  # (G, T, n) (G, T, p) (G, T, n) (G, T, v)
        scores = (
            jnp.einsum("gtn,gsn->gts", f32(qn), f32(kn))
            + jnp.einsum("gtp,sp->gts", f32(qp), f32(k_p))
        ) / math.sqrt(n + rot)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum(
            "gts,gsv->gtv", f32(jax.nn.softmax(scores, -1)), f32(vv))

    group = math.gcd(heads, _HEADS_AT_ONCE)

    def grouped(a):  # (T, H, k) -> (H / G, G, T, k)
        return a.transpose(1, 0, 2).reshape(
            heads // group, group, t, a.shape[-1])

    o = lax.map(some_heads, (grouped(q_n), grouped(q_p), grouped(k_n),
                             grouped(val)))
    o = o.reshape(heads, t, v).transpose(1, 0, 2)  # (T, H, v)
    a = jnp.einsum("thv,hvd->td", f32(o), f32(p["wo"]))
    x = x + rms(a, p["ln1_post_scale"])
    h3 = rms(x, p["ln2_scale"])
    if dense:
        y, top_i = swiglu(h3, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        # the router's product is float32 on both sides of ``correct``
        score = jax.nn.sigmoid(h3 @ p["router"].astype(jnp.float32))
        top_s, top_i = lax.top_k(score, settings["moe_k"])
        top_w = settings["moe_scale"] * top_s / (
            jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
        first = settings.get("expert_first", 0)

        def one_expert(y, e):
            # w_e for the tokens that chose expert first + e, else 0
            w = jnp.sum(jnp.where(top_i == first + e, top_w, 0.0), axis=-1)
            out = swiglu(h3, p["we_gate"][e], p["we_up"][e], p["we_down"][e])
            return y + w[:, None] * out, None

        y, _ = lax.scan(
            one_expert, jnp.zeros_like(x), jnp.arange(p["we_gate"].shape[0])
        )
        if "ws_gate" in p:
            y = y + swiglu(h3, p["ws_gate"], p["ws_up"], p["ws_down"])
    return x + rms(y, p["ln2_post_scale"]), top_i


@functools.lru_cache(maxsize=None)
def _programs(settings_json: str, precision):
    """(layer(p, x, dense) jitted, head(params, x) jitted)."""
    settings = json.loads(settings_json)
    eps = settings["norm_eps"]
    low = None if precision is None else jnp.dtype(precision)

    @functools.partial(jax.jit, static_argnames="dense")
    def layer(p, x, dense):
        with jax.default_matmul_precision("highest"):
            return _layer(p, x, settings, dense, precision)

    @jax.jit
    def head(lnf_scale, w, x):
        with jax.default_matmul_precision("highest"):
            x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
                lnf_scale.astype(jnp.float32))
            if low is not None:
                x, w = x.astype(low), w.astype(low)
            return x.astype(jnp.float32) @ w.astype(jnp.float32)

    return layer, head


def forward(params, tokens, settings: dict | None = None, precision=None,
            with_routing: bool = False):
    """Logits (B, T, V) float32 of ``tokens`` (B, T) int32, one sequence
    and one layer at a time (a numpy array: at the benchmark's size the
    four rows are 0.3 GB). ``with_routing`` also returns the experts each
    token chose, (B, expert layers, T, k)."""
    settings = settings_for(params) if settings is None else settings
    layer, head = _programs(json.dumps(settings, sort_keys=True), precision)
    dense_layers = set(settings.get("dense_layers", ()))
    logits, routing = [], []
    for row in jnp.asarray(tokens):
        x = params["embed"][row].astype(jnp.float32)
        chose = []
        for l, p in enumerate(params["layers"]):
            x, top_i = layer(p, x, dense=l in dense_layers)
            if top_i is not None:
                chose.append(np.asarray(top_i))
        logits.append(np.asarray(head(params["lnf_scale"], params["head"], x)))
        routing.append(np.stack(chose) if chose else np.zeros((0,), np.int32))
    if with_routing:
        return np.stack(logits), np.stack(routing)
    return np.stack(logits)
