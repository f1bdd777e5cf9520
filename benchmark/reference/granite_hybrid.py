"""Granite-4.0-H-Micro's decoder in plain float32 ``jax.numpy``: the
yardstick for ``correct``.

Written from the published ``config.json`` (ibm-granite/granite-4.0-h-micro,
``model_type`` granitemoehybrid) and the Mamba-2 (SSD) recurrence; imports
nothing from the program under test. No biases but the convolution's,
RMSNorm with ``eps`` = ``rms_norm_eps``, ``d`` = hidden size, a SwiGLU MLP
(``silu``, ``shared_intermediate_size`` wide; no experts) in every layer, a
head tied to the embedding, no positions anywhere
(``position_embedding_type`` "nope").

**Embedding:** ``x = E[ids] * embedding_multiplier``.

**Block** (pre-norm): ``x <- x + m * mix(RMS_1(x))``, then ``x <- x + m *
MLP(RMS_2(x))`` with ``m`` = ``residual_multiplier`` and ``MLP(h) =
(silu(h W_g) * (h W_u)) W_d``.

**attention** (GQA, ``H`` query heads over ``Hkv`` KV heads of ``K``):
``q = h Wq``, ``k, v = h Wkv``; ``softmax(q k^T * attention_multiplier)``
causal (not ``K^-1/2``), query head j reading KV head ``j // (H / Hkv)``;
``concat(heads) Wo``.

**mamba** (Mamba-2, ``mamba_*`` keys), per token ``t``, with ``I`` =
heads x d_head, ``G`` groups of ``N`` = d_state:

1. ``[z | xBC | dt] = h W_in`` (widths I | I + 2 G N | heads).
2. ``xBC = SiLU(conv(xBC) + b)``: one causal depthwise convolution of
   width ``mamba_d_conv``, ``c_t = sum_i w_i p_{t - K + 1 + i}`` (zero
   before position 0); split into ``x`` (heads, d_head), ``B`` and ``C``
   (G, N).
3. ``dt = softplus(dt + dt_bias)`` (no ``time_step_limit`` clamp),
   ``a_h = exp(-exp(A_log_h) dt_h)``.
4. ``S_h`` (N x d_head, float32, zero at position 0): ``S_h <- a_h S_h +
   B_g (dt_h x_h)^T``, ``y_h = S_h^T C_g + D_h x_h``, g head h's group,
   token by token (a ``lax.scan`` over positions: the recurrence as
   published, not the program's chunked form).
5. ``y = RMSNorm(y * SiLU(z)) * w``, the norm over each group's I / G
   channels (gate first); then ``W_out``.

**Final:** ``logits = RMS_f(x) E^T / logits_scaling``.

Departures, none of which changes a result the comparison can see: the
MLP's ``input_linear`` (gate first) is held as its two halves ``w_gate``
and ``w_up``; ``W_out`` and ``Wo`` are held by heads, (heads, d_head, d).

**What no shape carries** (the layer kinds, the multipliers, eps) comes
from the configuration's own file,
``benchmark/configs/granite-4.0-h-micro.json``; its ``rehearse`` sizes are
chosen when the tree's hidden size is the toy's.

The program's parameter tree, read as it is (any float type upcast where
it is used, so a bf16 tree is judged as the bf16 weights it is):

    embed (V, d)   lnf_scale (d,)   (no head: tied)
    layers[l]: ln1_scale ln2_scale (d,)
      w_gate w_up (d, F)   w_down (F, d)
      attention: wq (d, H, K)   wkv (d, 2, Hkv, K)   wo (H, K, d)
      mamba:     w_in (d, I + (I + 2 G N) + heads)   conv_w (K_conv, I + 2 G N)
                 conv_b (I + 2 G N,)   A_log dt_bias D (heads,)
                 ssm_norm_scale (I,)   wo (heads, d_head, d)

``forward`` runs one layer a jitted call, so that only one layer's weights
are float32 at a time: at the published widths the reference fits beside
the program's bf16 weights on one chip.

``precision`` (not used by ``correct``): a float type name; weights and the
activations entering every product, B, C and dt x entering the recurrence
among them, are rounded to it first (the state is not: it is float32 by
the configuration). ``state_dtype``: the state rounded to that type after
every position, to see whether a narrower stored state can be told apart.
Both set the tolerances in the configuration file
(``benchmark/tools/granite_hybrid_precision.py``).
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "configs"
               / "granite-4.0-h-micro.json")


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


def _rounding(precision):
    low = None if precision is None else jnp.dtype(precision)

    def r(x):  # a weight or an activation as a product reads it
        if low is not None:
            x = x.astype(low)
        return x.astype(jnp.float32)

    return r


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        g.astype(jnp.float32))


def _attention(p, h, settings, r):
    """(T, d) -> (T, d): causal GQA without positions."""
    t = h.shape[0]
    q = jnp.einsum("td,dhk->thk", r(h), r(p["wq"]))
    kv = jnp.einsum("td,dshk->sthk", r(h), r(p["wkv"]))
    rep = q.shape[1] // kv.shape[2]
    k = jnp.repeat(kv[0], rep, axis=1)
    v = jnp.repeat(kv[1], rep, axis=1)
    scores = jnp.einsum("qhk,shk->hqs", r(q), r(k)) * settings[
        "attention_multiplier"]
    pos = np.arange(t)
    causal = jnp.asarray(pos[None, :] <= pos[:, None])
    scores = jnp.where(causal[None], scores, -jnp.inf)
    a = jnp.einsum("hqs,shk->qhk", r(jax.nn.softmax(scores, -1)), r(v))
    return jnp.einsum("thk,hkd->td", r(a), r(p["wo"]))


def _mamba(p, h, settings, r, state_dtype):
    """(T, d) -> (T, d): Mamba-2, token by token."""
    f32 = jnp.float32
    t = h.shape[0]
    heads, hd = settings["mamba_n_heads"], settings["mamba_d_head"]
    n, groups = settings["mamba_d_state"], settings["mamba_n_groups"]
    inner = heads * hd
    width = inner + 2 * groups * n
    proj = r(h) @ r(p["w_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + width],
                  proj[:, inner + width:])
    w = p["conv_w"].astype(f32)
    k_conv = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((k_conv - 1, width), f32), xbc])
    c = sum(w[i] * padded[i:i + t] for i in range(k_conv))
    if settings.get("mamba_conv_bias"):
        c = c + p["conv_b"].astype(f32)
    c = jax.nn.silu(c)
    x = c[:, :inner].reshape(t, heads, hd)
    per = heads // groups  # heads a group's B and C serve
    b_ = jnp.repeat(c[:, inner:inner + groups * n].reshape(t, groups, n),
                    per, axis=1)
    c_ = jnp.repeat(c[:, inner + groups * n:].reshape(t, groups, n),
                    per, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))  # (T, heads)
    decay = jnp.exp(-jnp.exp(p["A_log"].astype(f32)) * dt)

    def step(s, inp):
        at, bt, ct, ut = inp  # (H,) (H, N) (H, N) (H, P)
        s = at[:, None, None] * s + bt[:, :, None] * ut[:, None, :]
        if state_dtype is not None:
            # an explicit rounding: XLA may drop a convert pair (excess
            # precision), never a reduce_precision
            fi = jnp.finfo(state_dtype)
            s = lax.reduce_precision(s, exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
        return s, jnp.einsum("hnp,hn->hp", s, ct)

    _, y = lax.scan(step, jnp.zeros((heads, n, hd), f32),
                    (decay, r(b_), r(c_), r(dt[..., None] * x)))
    y = y + p["D"].astype(f32)[:, None] * x
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(
        t, groups, inner // groups)
    normed = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True)
        + settings["norm_eps"])
    y = normed.reshape(t, inner) * p["ssm_norm_scale"].astype(f32)
    return r(y) @ r(p["wo"].reshape(inner, -1))


@functools.lru_cache(maxsize=None)
def _layer_program(kind: str, settings_json: str, precision, state_dtype):
    settings = json.loads(settings_json)
    eps, m = settings["norm_eps"], settings["residual_multiplier"]
    r = _rounding(precision)

    def run(p, x):
        with jax.default_matmul_precision("highest"):
            h = _rms(x, p["ln1_scale"], eps)
            if kind == "mamba":
                mixed = _mamba(p, h, settings, r, state_dtype)
            else:
                mixed = _attention(p, h, settings, r)
            x = x + m * mixed
            h = _rms(x, p["ln2_scale"], eps)
            y = jax.nn.silu(r(h) @ r(p["w_gate"])) * (r(h) @ r(p["w_up"]))
            return x + m * (r(y) @ r(p["w_down"]))

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _ends(settings_json: str, precision):
    settings = json.loads(settings_json)
    r = _rounding(precision)

    def embed(table, tokens):
        return table[tokens].astype(jnp.float32) * settings[
            "embedding_multiplier"]

    def logits(table, lnf, x):
        with jax.default_matmul_precision("highest"):
            x = _rms(x, lnf, settings["norm_eps"])
            return r(x) @ r(table).T / settings["logits_scaling"]

    return jax.jit(embed), jax.jit(logits)


def forward(params, tokens, settings: dict | None = None, precision=None,
            state_dtype=None):
    """Logits (B, T, V) float32 of ``tokens`` (B, T) int32, one sequence
    and one layer at a time (a numpy array)."""
    settings = settings_for(params) if settings is None else settings
    key = json.dumps(settings, sort_keys=True)
    embed, logits = _ends(key, precision)
    out = []
    for row in jnp.asarray(tokens):
        x = embed(params["embed"], row)
        for l, p in enumerate(params["layers"]):
            x = _layer_program(settings["layer_types"][l], key, precision,
                               state_dtype)(p, x)
        out.append(np.asarray(logits(params["embed"], params["lnf_scale"],
                                     x)))
    return np.stack(out)
