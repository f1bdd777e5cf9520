"""Sequence/context parallelism: ring attention + sequence-sharded RNN.

Long-context is a first-class design axis here (the reference has nothing
— its LSTM materializes whole sequences per host, SURVEY §5):

- **Ring attention**: Q, K, V are sharded over the mesh's data axis along
  the *sequence* dimension.  Each device holds one Q shard and streams
  every KV shard past it around the ICI ring (``lax.ppermute``),
  accumulating exact attention via online softmax.  Peak memory per chip
  is O(T/n) and the KV transfer overlaps compute — the standard TPU
  long-context recipe.
- **Sequence-sharded LSTM scan**: the recurrence is inherently serial in
  time, so devices process their time-chunk in ring order, passing the
  (h, c) carry to the next device.  No wall-clock speedup (the carry is a
  chain), but activations/inputs are sharded — sequences n× longer than
  one chip's HBM fit, which is the capability that matters for the
  framework's RNN-era models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.ops.attention import (
    finalize_online_softmax,
    online_softmax_block,
)
from deeplearning4j_tpu.parallel import mesh as mesh_lib


def ring_attention(mesh, causal: bool = False, head_axis: str | None = None):
    """Build a jitted ring-attention fn over the mesh's data axis.

    Returns ``fn(q, k, v) -> out`` where q/k/v are (B, T, H, D) with T
    sharded over the axis.  Exact (not approximate) attention.

    ``head_axis`` optionally names a second mesh axis the head dim stays
    sharded on (tensor parallelism): the sequence ring then runs within
    each head-shard subgroup, composing SP x TP without gathering heads.
    """
    axis = mesh_lib.DATA_AXIS
    n = mesh.shape[axis]

    def per_device(q, k, v):
        # block shapes: (B, T/n, H, D)
        b, t_local, h, d = q.shape
        me = lax.axis_index(axis)
        m = jnp.full((b, h, t_local), -jnp.inf, q.dtype)
        l = jnp.zeros((b, h, t_local), q.dtype)
        o = jnp.zeros_like(q)

        def body(i, carry):
            m, l, o, k_cur, v_cur = carry
            # the KV block currently held arrived from device (me - i)
            src = (me - i) % n
            if causal:
                pos_q = me * t_local + jnp.arange(t_local)
                pos_k = src * t_local + jnp.arange(t_local)
                bias = jnp.where(
                    pos_q[:, None] >= pos_k[None, :], 0.0, -jnp.inf
                )[None, None, :, :]
            else:
                bias = None
            m, l, o = online_softmax_block(q, k_cur, v_cur, m, l, o, bias)
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return m, l, o, k_nxt, v_nxt

        m, l, o, _, _ = lax.fori_loop(0, n, body, (m, l, o, k, v))
        return finalize_online_softmax(l, o)

    seq = P(None, axis, head_axis, None)
    fn = shard_map(
        per_device, mesh=mesh, in_specs=(seq, seq, seq), out_specs=seq,
        check_vma=False,
    )
    return jax.jit(fn)


def sequence_sharded_lstm(mesh, lstm_module, conf):
    """Build ``fn(params, x) -> (hs, cs)`` with x (B, T, F), T sharded.

    Devices run their chunk's ``lax.scan`` after receiving the carry from
    the previous device over the ring (≙ chunked-pipeline RNN execution).
    """
    axis = mesh_lib.DATA_AXIS
    n = mesh.devices.size

    def per_device(params, x):
        b = x.shape[0]
        d = lstm_module.hidden_size(conf)
        me = lax.axis_index(axis)
        h = jnp.zeros((b, d), x.dtype)
        c = jnp.zeros((b, d), x.dtype)
        perm = [(j, (j + 1) % n) for j in range(n)]

        # Chain the carry through devices: device i runs its real scan on
        # ring step i; before that it forwards zeros, after it forwards
        # its final carry.  n ppermute rounds serialize the time chunks.
        hs = jnp.zeros((b, x.shape[1], d), x.dtype)
        cs = jnp.zeros((b, x.shape[1], d), x.dtype)

        def body(i, carry):
            h, c, hs, cs = carry
            is_mine = i == me

            def run(_):
                out_hs, out_cs = _scan_chunk(params, x, h, c)
                return out_hs[:, -1, :], out_cs[:, -1, :], out_hs, out_cs

            def skip(_):
                return h, c, hs, cs

            h2, c2, hs2, cs2 = lax.cond(is_mine, run, skip, None)
            h3 = lax.ppermute(h2, axis, perm)
            c3 = lax.ppermute(c2, axis, perm)
            return h3, c3, hs2, cs2

        def _scan_chunk(params, x, h0, c0):
            wr = params[
                "recurrentweights"
            ]

            def step(carry, x_t):
                h_prev, c_prev = carry
                i_g, f_g, o_g, g_g = lstm_module._gates(conf, wr, x_t, h_prev)
                c_t = i_g * g_g + f_g * c_prev
                h_t = lstm_module._hout(conf, o_g, c_t)
                return (h_t, c_t), (h_t, c_t)

            (_, _), (hs, cs) = lax.scan(step, (h0, c0), jnp.swapaxes(x, 0, 1))
            return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)

        h, c, hs, cs = lax.fori_loop(0, n, body, (h, c, hs, cs))
        return hs, cs

    seq = P(None, axis, None)
    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), seq),
        out_specs=(seq, seq),
        check_vma=False,
    )
    return jax.jit(fn)
