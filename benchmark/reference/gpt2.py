"""GPT-2 in plain float32 ``jax.numpy``: the yardstick for ``correct``.

Written from the published description (Radford et al. 2019; the
``transformers`` GPT2 ``config.json`` keys): token + learned absolute
position embeddings, pre-LayerNorm blocks (LayerNorm with bias, eps 1e-5),
multi-head causal attention scaled by 1/sqrt(head size), a 4x MLP with the
tanh GELU (``gelu_new``), a final LayerNorm and a linear head. Imports
nothing from the program under test; takes the program's parameter tree:

    embed (V, d)   pos (P, d)   lnf_scale, lnf_bias (d,)   head (d, V)
    blocks, every leaf with a leading layer axis:
      ln1_scale ln1_bias ln2_scale ln2_bias (L, d)
      wqkv (L, d, 3, H, hd)   wo (L, H, hd, d)
      w1 (L, d, f)  b1 (L, f)  w2 (L, f, d)  b2 (L, d)

Departures from the release, all the program's and listed in the
configuration files: no q/k/v/o biases, the head is not tied to the
embedding. Weights of any float type are upcast leaf by leaf inside the
layer scan, so a bf16 tree is judged as the bf16 weights it is and the
temporaries stay one layer large. Every matmul runs under
``jax.default_matmul_precision("highest")``: on a TPU a float32 dot is
otherwise computed in bf16 passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 1e-5


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + _EPS) * _f32(scale) + _f32(bias)


def _gelu_new(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3))
    )


def _block(x, p):
    """One pre-LN block on x (B, T, d) with this layer's leaves ``p``."""
    t = x.shape[1]
    hd = p["wqkv"].shape[-1]
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = jnp.einsum("btd,dshk->sbhtk", h, _f32(p["wqkv"]))
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = jnp.einsum("bhqk,bhtk->bhqt", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("bhtk,hkd->btd", attn, _f32(p["wo"]))
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    h = _gelu_new(jnp.einsum("btd,df->btf", h, _f32(p["w1"])) + _f32(p["b1"]))
    return x + jnp.einsum("btf,fd->btd", h, _f32(p["w2"])) + _f32(p["b2"])


def _forward(params, tokens):
    t = tokens.shape[1]
    x = _f32(params["embed"])[tokens] + _f32(params["pos"])[:t]
    x, _ = lax.scan(lambda x, p: (_block(x, p), None), x, params["blocks"])
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    return jnp.einsum("btd,dv->btv", x, _f32(params["head"]))


def _loss(params, tokens):
    logits = _forward(params, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return jax.jit(wrapped)


#: logits (B, T, V) float32 of tokens (B, T) int32
forward = _highest(_forward)
#: (loss, gradient tree) of next-token cross-entropy on tokens (B, T+1)
loss_and_grads = _highest(jax.value_and_grad(_loss))
