"""Pallas kernels validated in interpret mode against the XLA references
(the lowered TPU path runs the identical kernel code on real chips)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import attention
from deeplearning4j_tpu.ops.pallas_kernels import flash_attention, fused_embedding_dot


def test_flash_attention_matches_dense():
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 64, 2, 16)) for kk in ks)
    out = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = attention(q, k, v)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


def test_fused_embedding_dot_matches_xla():
    ks = jax.random.split(jax.random.key(1), 3)
    b, L, d = 64, 7, 32
    h = jax.random.normal(ks[0], (b, d))
    w = jax.random.normal(ks[1], (b, L, d))
    mask = (jax.random.uniform(ks[2], (b, L)) > 0.3).astype(jnp.float32)
    out = fused_embedding_dot(h, w, mask, block_b=32, interpret=True)
    ref = jax.nn.sigmoid(jnp.clip(jnp.einsum("bd,bld->bl", h, w), -6, 6)) * mask
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_flash_attention_trainable_grads_match_dense():
    """custom_vjp backward kernels (dQ, dK/dV) == autodiff through dense."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_trainable

    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (2, 32, 2, 8)) for kk in ks)

    def loss_flash(q, k, v):
        o = flash_attention_trainable(q, k, v, block_q=8, block_k=8, interpret=True)
        return jnp.sum(jnp.sin(o) * o)

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(attention(q, k, v)) * attention(q, k, v))

    out_f = loss_flash(q, k, v)
    out_d = loss_dense(q, k, v)
    assert abs(float(out_f) - float(out_d)) < 1e-3
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def test_flash_attention_causal_matches_dense():
    from deeplearning4j_tpu.ops.attention import attention
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 256, 2, 16)).astype(np.float32))
        for _ in range(3)
    )
    out = flash_attention(q, k, v, block_q=64, block_k=64, causal=True)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_trainable_causal_grads_match_dense():
    from deeplearning4j_tpu.ops.attention import attention
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_trainable

    rng = np.random.default_rng(8)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 128, 2, 8)).astype(np.float32))
        for _ in range(3)
    )

    def loss_flash(q, k, v):
        o = flash_attention_trainable(q, k, v, block_q=32, block_k=32, causal=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = attention(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(o))

    np.testing.assert_allclose(
        float(loss_flash(q, k, v)), float(loss_dense(q, k, v)), rtol=1e-5
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_backward_f32_partials_escape_hatch():
    """The _DQ_PARTIALS_F32 debug flag (ADVICE r4) must produce correct
    grads through the f32-plane path so it is actually usable when
    triaging suspected device grad corruption. Inputs are bf16 — with
    f32 inputs the plane dtype is f32 either way and the flag would be
    a no-op (the flag's whole point is bf16-storage runs)."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.ops.attention import attention

    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 128, 2, 8)).astype(np.float32))
        .astype(jnp.bfloat16)
        for _ in range(3)
    )

    def loss_dense(q, k, v):
        o = attention(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(o))

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    old = pk._DQ_PARTIALS_F32
    pk._DQ_PARTIALS_F32 = True
    try:
        def loss_flash(q, k, v):
            o = pk.flash_attention_trainable(
                q, k, v, block_q=32, block_k=32, causal=True
            )
            return jnp.sum(o * jnp.cos(o))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        pk._DQ_PARTIALS_F32 = old
    # bf16 storage: tolerance scaled to bf16 resolution; grads of the
    # two paths must agree to within rounding, not diverge structurally
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.06, atol=3e-2,
        )


def _dense_decode_ref(q, kvcache, pos, n_kv_heads, layer):
    """Dense einsum oracle for one decode step against the packed cache."""
    b, g, hk = q.shape
    hd = hk // n_kv_heads
    kk = np.asarray(kvcache[layer, 0], np.float32)  # (B, T, hk)
    vv = np.asarray(kvcache[layer, 1], np.float32)
    t = kk.shape[1]
    qr = np.asarray(q, np.float32).reshape(b, g, n_kv_heads, hd)
    kr = kk.reshape(b, t, n_kv_heads, hd)
    vr = vv.reshape(b, t, n_kv_heads, hd)
    s = np.einsum("bghd,bthd->bght", qr, kr) / np.sqrt(hd)
    # a scalar pos broadcasts; a (B,) vector masks each row at its own
    cols = np.arange(t)[None, None, None, :]
    s = np.where(cols > np.reshape(pos, (-1, 1, 1, 1)), -np.inf, s)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bght,bthd->bghd", p, vr).reshape(b, g, hk)


def test_flash_decode_attention_matches_dense():
    """Direct interpret-mode gate on the decode kernel (GQA packing,
    pos masking at cache-padding rows, multi-block streaming) — the
    generate/decode parity tests exercise it only indirectly and mostly
    in the slow lane."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    rng = np.random.default_rng(11)
    for b, g, n_kv, t, pos, layer in [
        (2, 1, 2, 32, 0, 0),       # pos at the first row (MHA)
        (2, 1, 2, 32, 31, 0),      # pos at the last valid row
        (1, 4, 2, 32, 13, 1),      # GQA groups, padded cache, layer 1
        (2, 2, 3, 24, 7, 0),       # non-pow2 head count, padding
    ]:
        hk = n_kv * 16
        n_layers = 2
        q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
        cache = jnp.asarray(
            rng.normal(size=(n_layers, 2, b, t, hk)).astype(np.float32)
        )
        out = flash_decode_attention(
            q, cache, jnp.int32(pos), n_kv, layer=layer, block_t=8,
            interpret=True,
        )
        ref = _dense_decode_ref(q, cache, pos, n_kv, layer)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def _int8_cache(cache):
    """Per-row int8 quantization of a float cache: (int8 rows, f32
    scales (..., 1))."""
    raw = np.asarray(cache)
    amax = np.maximum(np.abs(raw).max(-1, keepdims=True), 1e-8)
    scales = (amax / 127.0).astype(np.float32)
    qcache = np.clip(np.round(raw / scales), -127, 127).astype(np.int8)
    return jnp.asarray(qcache), jnp.asarray(scales)


def _int8_decode_ref(q, qcache, scales, pos, n_kv_heads, layer):
    """The dense oracle on the dequantized cache, with q quantized
    exactly as the kernel does (one scale per group row)."""
    qn = np.asarray(q)
    qs = np.maximum(np.abs(qn).max(-1, keepdims=True), 1e-8) / 127.0
    q_deq = (np.clip(np.round(qn / qs), -127, 127) * qs).astype(np.float32)
    dequant = np.asarray(qcache, np.float32) * np.asarray(scales)
    return _dense_decode_ref(
        jnp.asarray(q_deq), jnp.asarray(dequant), pos, n_kv_heads, layer
    )


def test_flash_decode_attention_int8_cache_matches_dequant_oracle():
    """int8-cache mode (r5 serving path): the kernel runs BOTH cache
    dots natively int8 on the MXU — the query row is quantized
    in-register (one scale per group) and the softmax weights are
    quantized per tile for the V contraction. The oracle applies the
    same q/k/v quantization explicitly; the residual difference is the
    in-kernel p-quantization (bounded by pmax/254 per weight, ~0.5% of
    the output scale here — measured 5.3e-3 at stamp time)."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    rng = np.random.default_rng(3)
    for b, g, n_kv, t, pos, layer in [
        (2, 1, 2, 32, 31, 0),
        (1, 4, 2, 32, 13, 1),
        (2, 2, 3, 24, 7, 0),
    ]:
        hk = n_kv * 16
        n_layers = 2
        q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
        qcache, scales = _int8_cache(
            rng.normal(size=(n_layers, 2, b, t, hk)).astype(np.float32)
        )
        out = flash_decode_attention(
            q, qcache, jnp.int32(pos), n_kv, layer=layer, block_t=8,
            interpret=True, kv_scales=scales,
        )
        # residual = in-kernel softmax-weight quantization, which the
        # oracle does not model (bounded by pmax/254 per weight)
        np.testing.assert_allclose(
            np.asarray(out),
            _int8_decode_ref(q, qcache, scales, pos, n_kv, layer),
            atol=2.5e-2,
        )


# -- the bounded walk: a row reads the blocks up to its position, a row that
# -- is not active reads nothing (PR 26)

_WALK_T, _WALK_BLOCK = 32, 8
# per-row positions that end in the first block, on both sides of a block
# edge, and in the last block
_WALK_POS = {
    "first_block": [0, 3, 7],
    "block_edge": [_WALK_BLOCK - 1, _WALK_BLOCK, 2 * _WALK_BLOCK],
    "last_block": [_WALK_T - 1, _WALK_T - _WALK_BLOCK, 5],
}


def _walk_case(seed, b, g=2, n_kv=2, t=_WALK_T, n_layers=2):
    rng = np.random.default_rng(seed)
    hk = n_kv * 16
    q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
    cache = jnp.asarray(
        rng.normal(size=(n_layers, 2, b, t, hk)).astype(np.float32)
    )
    return q, cache, n_kv


@pytest.mark.parametrize("where", sorted(_WALK_POS))
def test_flash_decode_bounded_walk_matches_dense(where):
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    pos = np.asarray(_WALK_POS[where], np.int32)
    q, cache, n_kv = _walk_case(21, len(pos))
    out = flash_decode_attention(
        q, cache, jnp.asarray(pos), n_kv, layer=1, block_t=_WALK_BLOCK,
        interpret=True,
    )
    ref = _dense_decode_ref(q, cache, pos, n_kv, 1)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


@pytest.mark.parametrize("where", sorted(_WALK_POS))
def test_flash_decode_bounded_walk_int8_matches_dequant_oracle(where):
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    pos = np.asarray(_WALK_POS[where], np.int32)
    q, cache, n_kv = _walk_case(22, len(pos))
    qcache, scales = _int8_cache(cache)
    out = flash_decode_attention(
        q, qcache, jnp.asarray(pos), n_kv, layer=1, block_t=_WALK_BLOCK,
        interpret=True, kv_scales=scales,
    )
    ref = _int8_decode_ref(q, qcache, scales, pos, n_kv, 1)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2.5e-2)


@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("mask", [
    [True, False, True, True],    # a free slot between live ones
    [False, False, True, True],   # the first rows free
    [True, True, False, False],   # the last rows free
])
def test_flash_decode_inactive_row_is_zero_and_moves_no_other(mask, int8):
    """A row that is not active returns zeros whatever position it was
    frozen at, and the rows beside it are bit-equal to a run in which
    every row is active."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    pos = jnp.asarray([5, 17, _WALK_T - 1, _WALK_BLOCK], jnp.int32)
    q, cache, n_kv = _walk_case(23, 4)
    scales = None
    if int8:
        cache, scales = _int8_cache(cache)
    kw = dict(layer=0, block_t=_WALK_BLOCK, interpret=True, kv_scales=scales)
    full = np.asarray(flash_decode_attention(q, cache, pos, n_kv, **kw))
    mask = np.asarray(mask)
    out = np.asarray(flash_decode_attention(
        q, cache, pos, n_kv, active=jnp.asarray(mask), **kw
    ))
    assert np.all(out[~mask] == 0.0)
    np.testing.assert_array_equal(out[mask], full[mask])


def test_flash_decode_scalar_pos_broadcasts_through_the_walk():
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    q, cache, n_kv = _walk_case(24, 3)
    kw = dict(layer=1, block_t=_WALK_BLOCK, interpret=True)
    scalar = flash_decode_attention(q, cache, jnp.int32(13), n_kv, **kw)
    vector = flash_decode_attention(
        q, cache, jnp.full((3,), 13, jnp.int32), n_kv, **kw
    )
    np.testing.assert_array_equal(np.asarray(scalar), np.asarray(vector))
    ref = _dense_decode_ref(q, cache, 13, n_kv, 1)
    np.testing.assert_allclose(np.asarray(scalar), ref, atol=2e-5)


# -- the writing walk: the kernel places the row it is about to read (PR 30)


def _scatter_then_read(q, cache, new, pos, n_kv, layer, at, active, block_t):
    """What the writing kernel replaces: XLA's scatter of the live rows'
    K and V at ``at``, then the read-only kernel."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_decode_attention

    b = q.shape[0]
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), (b,))
    if active is not None:  # a row that is not active: out of bounds
        at = jnp.where(jnp.asarray(active), at, cache.shape[3])
    for plane in range(2):
        cache = cache.at[layer, plane, jnp.arange(b), at].set(
            new[:, plane].astype(cache.dtype), mode="drop"
        )
    out = flash_decode_attention(
        q, cache, pos, n_kv, layer=layer, block_t=block_t, interpret=True,
        active=active,
    )
    return out, cache


def _write_case(seed, b, g, dtype=jnp.float32, t=_WALK_T):
    q, cache, n_kv = _walk_case(seed, b, g=g, t=t)
    new = jnp.asarray(
        np.random.default_rng(seed + 1).normal(size=(b, 2, q.shape[-1])),
        dtype,
    )
    return q.astype(dtype), cache.astype(dtype), new, n_kv


# one slot a case, so that each position counts as a test of its own:
# the slab's first row, both sides of a block edge, the slab's last row
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("groups", [1, 6, 9])
@pytest.mark.parametrize("pos", [0, _WALK_BLOCK - 1, _WALK_BLOCK, _WALK_T - 1])
def test_writing_walk_equals_scatter_then_read_bitwise(pos, groups, dtype):
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_write,
    )

    # the case's slot beside two at other depths
    where = jnp.asarray([pos, 13, _WALK_T - 2], jnp.int32)
    q, cache, new, n_kv = _write_case(40 + pos, 3, groups, dtype)
    out, written = flash_decode_attention_write(
        q, cache, new, where, n_kv, layer=1, block_t=_WALK_BLOCK,
        interpret=True,
    )
    ref_out, ref_cache = _scatter_then_read(
        q, cache, new, where, n_kv, 1, where, None, _WALK_BLOCK
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(written), np.asarray(ref_cache))
    # and the fresh row is where it belongs, in the cache's dtype
    np.testing.assert_array_equal(
        np.asarray(written[1, :, 0, pos]), np.asarray(new[0])
    )


@pytest.mark.parametrize("pos", [_WALK_T + 3, 2 * _WALK_T + _WALK_BLOCK, 3 * _WALK_T + 9])
def test_writing_walk_on_a_ring_writes_a_block_that_is_not_the_last_read(pos):
    """A ring that has wrapped reads every row (``pos`` is capped at the
    last one) and writes row ``pos % rows``, in a block before the last
    of its walk; a ring that has not yet wrapped writes its last row."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_write,
    )

    where = jnp.asarray([pos, 5, pos + 11], jnp.int32)  # row 1: not wrapped
    at = where % _WALK_T
    assert int(at[0]) // _WALK_BLOCK < _WALK_T // _WALK_BLOCK - 1
    q, cache, new, n_kv = _write_case(60, 3, 9)
    out, written = flash_decode_attention_write(
        q, cache, new, where, n_kv, layer=0, write_at=at,
        block_t=_WALK_BLOCK, interpret=True,
    )
    ref_out, ref_cache = _scatter_then_read(
        q, cache, new, where, n_kv, 0, at, None, _WALK_BLOCK
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(written), np.asarray(ref_cache))


@pytest.mark.parametrize("pos", [0, 13, _WALK_T - 1, _WALK_T + 4])
def test_writing_walk_scalar_pos_writes_every_row_at_one_depth(pos):
    """Scalar ``pos`` (generate, beam, speculative decoding) broadcasts
    for the write as it does for the read; past the slab the caller
    names the last row, as ``dynamic_update_slice`` clamped."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_write,
    )

    q, cache, new, n_kv = _write_case(70 + pos, 3, 1)
    at = min(pos, _WALK_T - 1)
    out, written = flash_decode_attention_write(
        q, cache, new, jnp.int32(pos), n_kv, layer=1,
        write_at=jnp.int32(at), block_t=_WALK_BLOCK, interpret=True,
    )
    ref_cache = jax.lax.dynamic_update_slice(
        cache, new.transpose(1, 0, 2)[None, :, :, None, :], (1, 0, 0, pos, 0)
    )
    np.testing.assert_array_equal(np.asarray(written), np.asarray(ref_cache))
    ref_out, _ = _scatter_then_read(
        q, cache, new, pos, n_kv, 1, at, None, _WALK_BLOCK
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))


@pytest.mark.parametrize("mask", [
    [True, False, True, True],    # a free slot between live ones
    [False, False, True, True],   # the first rows free
    [True, True, False, False],   # the last rows free
    [False, False, False, False],  # nobody home
])
def test_writing_walk_inactive_row_leaves_its_slab_and_moves_no_other(mask):
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_write,
    )

    pos = jnp.asarray([5, 17, _WALK_T - 1, _WALK_BLOCK], jnp.int32)
    q, cache, new, n_kv = _write_case(80, 4, 2)
    mask = np.asarray(mask)
    kw = dict(layer=0, block_t=_WALK_BLOCK, interpret=True)
    full_out, full_cache = flash_decode_attention_write(
        q, cache, new, pos, n_kv, **kw)
    out, written = flash_decode_attention_write(
        q, cache, new, pos, n_kv, active=jnp.asarray(mask), **kw)
    out, written = np.asarray(out), np.asarray(written)
    assert np.all(out[~mask] == 0.0)
    np.testing.assert_array_equal(out[mask], np.asarray(full_out)[mask])
    # a free slot's slab is as it was, a live one's as with every row live
    np.testing.assert_array_equal(
        written[:, :, ~mask], np.asarray(cache)[:, :, ~mask])
    np.testing.assert_array_equal(
        written[:, :, mask], np.asarray(full_cache)[:, :, mask])
    # the layer beside it is untouched
    np.testing.assert_array_equal(written[1], np.asarray(cache)[1])


def test_writing_walk_drops_a_row_outside_the_slab():
    """A per-row ``write_at`` past the slab writes nothing, as the
    scatter it replaces dropped it; the row still attends to its
    slab."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention,
        flash_decode_attention_write,
    )

    pos = jnp.asarray([_WALK_T + 2, 9], jnp.int32)
    q, cache, new, n_kv = _write_case(90, 2, 1)
    kw = dict(layer=1, block_t=_WALK_BLOCK, interpret=True)
    out, written = flash_decode_attention_write(q, cache, new, pos, n_kv, **kw)
    np.testing.assert_array_equal(
        np.asarray(written[:, :, 0]), np.asarray(cache[:, :, 0]))
    np.testing.assert_array_equal(
        np.asarray(written[1, :, 1, 9]), np.asarray(new[1]))
    np.testing.assert_array_equal(
        np.asarray(out[0]),
        np.asarray(flash_decode_attention(q, cache, pos, n_kv, **kw)[0]),
    )


def test_flash_decode_default_block_at_one_block_is_the_whole_slab():
    """A slab no longer than one block of the rule is walked as one
    block: bit-equal to ``block_t=T``, the kernel every toy geometry
    (and every CPU test) ran before the walk was bounded."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        decode_block_rows,
        flash_decode_attention,
    )

    q, cache, n_kv = _walk_case(25, 3)
    assert decode_block_rows(_WALK_T, q.shape[-1], 4) == _WALK_T
    pos = jnp.asarray([0, 9, _WALK_T - 1], jnp.int32)
    default = flash_decode_attention(q, cache, pos, n_kv, interpret=True)
    whole = flash_decode_attention(
        q, cache, pos, n_kv, block_t=_WALK_T, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(default), np.asarray(whole))


@pytest.mark.parametrize("t,hk,itemsize", [
    (1024, 1280, 2), (1024, 1280, 1), (1024, 256, 2), (8704, 256, 2),
    (8704, 256, 1), (584, 256, 2), (4096, 4096, 2), (48, 32, 4),
    (8 * 1031, 256, 2),
])
def test_decode_block_rows_divides_the_slab_and_fits_vmem(t, hk, itemsize):
    from deeplearning4j_tpu.ops.pallas_kernels import decode_block_rows

    r = decode_block_rows(t, hk, itemsize)
    assert t % r == 0 and r % 8 == 0
    # K and V planes under the scoped-VMEM budget: three buffers deep in
    # the walk, two in the int8 grid (at 3 bytes an element)
    planes_bytes = 4 * 3 if itemsize == 1 else 6 * itemsize
    assert planes_bytes * r * hk <= 14 * 2**20
    if t >= 1024 and t % 128 == 0:
        # a long slab is walked in several blocks, none so small that
        # it half-fills the MXU pass over its rows
        assert 128 <= r <= t // 2


def test_flash_attention_noncausal_unchanged():
    from deeplearning4j_tpu.ops.attention import attention
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

    rng = np.random.default_rng(9)
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, 128, 2, 16)).astype(np.float32))
        for _ in range(3)
    )
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _scatter_slab_to_blocks(slab, tables, block_size, n_blocks):
    """Pack a contiguous (nl, 2, B, T, HK) slab into a block pool per
    the given (B, T//bs) int32 table — the layout the paged serving
    pool maintains incrementally (block id 0 = zero sentinel)."""
    nl, two, b, t, hk = slab.shape
    blocks = np.zeros((nl, two, n_blocks, block_size, hk), slab.dtype)
    for i in range(b):
        for j in range(t // block_size):
            blocks[:, :, tables[i, j]] = (
                slab[:, :, i, j * block_size:(j + 1) * block_size]
            )
    return blocks


def test_flash_decode_paged_bitwise_matches_slab_kernel():
    """The paged kernel (scalar-prefetch block tables, block-by-block
    HBM gather) is BITWISE the slab kernel at block_t=block_size over
    the gathered cache — same tile partitioning, same accumulation
    order. Tables are shuffled and one block is aliased across rows,
    so the lookup path really is exercised."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention,
        flash_decode_attention_paged,
    )

    rng = np.random.default_rng(17)
    b, g, n_kv, t, bs, layer = 2, 2, 2, 32, 8, 1
    hk = n_kv * 16
    bps = t // bs
    q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
    slab = rng.normal(size=(2, 2, b, t, hk)).astype(np.float32)
    # shuffled 1-based ids; alias row 1's first block to row 0's (the
    # prefix-sharing case) AFTER building the slab view accordingly
    tables = (rng.permutation(b * bps) + 1).reshape(b, bps).astype(np.int32)
    tables[1, 0] = tables[0, 0]
    slab[:, :, 1, :bs] = slab[:, :, 0, :bs]
    blocks = _scatter_slab_to_blocks(slab, tables, bs, b * bps + 1)
    pos = jnp.asarray(np.array([31, 13], np.int32))
    out_paged = flash_decode_attention_paged(
        q, jnp.asarray(blocks), jnp.asarray(tables), pos, n_kv,
        layer=layer, interpret=True,
    )
    out_slab = flash_decode_attention(
        q, jnp.asarray(slab), pos, n_kv, layer=layer, block_t=bs,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out_paged),
                                  np.asarray(out_slab))


def test_flash_decode_paged_int8_bitwise_matches_slab_int8():
    """int8 paged mode: per-row dequant scales ride in their own block
    pool (same tables) and the fused dequant is bitwise the slab int8
    kernel's — the HBM stream stays int8 bytes + table ints."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention,
        flash_decode_attention_paged,
    )

    rng = np.random.default_rng(23)
    b, g, n_kv, t, bs, layer = 2, 1, 2, 24, 8, 0
    hk = n_kv * 16
    bps = t // bs
    q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
    raw = rng.normal(size=(2, 2, b, t, hk)).astype(np.float32)
    amax = np.maximum(np.abs(raw).max(-1, keepdims=True), 1e-8)
    scales = (amax / 127.0).astype(np.float32)
    qslab = np.clip(np.round(raw / scales), -127, 127).astype(np.int8)
    tables = (rng.permutation(b * bps) + 1).reshape(b, bps).astype(np.int32)
    n_blocks = b * bps + 1
    qblocks = _scatter_slab_to_blocks(qslab, tables, bs, n_blocks)
    sblocks = _scatter_slab_to_blocks(scales, tables, bs, n_blocks)
    pos = jnp.asarray(np.array([23, 7], np.int32))
    out_paged = flash_decode_attention_paged(
        q, jnp.asarray(qblocks), jnp.asarray(tables), pos, n_kv,
        layer=layer, interpret=True, block_scales=jnp.asarray(sblocks),
    )
    out_slab = flash_decode_attention(
        q, jnp.asarray(qslab), pos, n_kv, layer=layer, block_t=bs,
        interpret=True, kv_scales=jnp.asarray(scales),
    )
    np.testing.assert_array_equal(np.asarray(out_paged),
                                  np.asarray(out_slab))


def test_flash_decode_paged_sentinel_blocks_are_invisible():
    """Unallocated table entries point at the zero sentinel (id 0);
    rows past ``pos`` are masked anyway, so a short sequence in a
    sparsely-allocated table matches the dense reference."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_paged,
    )

    rng = np.random.default_rng(29)
    b, g, n_kv, t, bs = 1, 1, 2, 32, 8
    hk = n_kv * 16
    bps = t // bs
    q = jnp.asarray(rng.normal(size=(b, g, hk)).astype(np.float32))
    slab = rng.normal(size=(2, 2, b, t, hk)).astype(np.float32)
    pos = 5  # only the first block is live
    tables = np.zeros((b, bps), np.int32)
    tables[0, 0] = 3  # arbitrary pool slot; the rest stay sentinel
    blocks = np.zeros((2, 2, 8, bs, hk), np.float32)
    blocks[:, :, 3] = slab[:, :, 0, :bs]
    out = flash_decode_attention_paged(
        q, jnp.asarray(blocks), jnp.asarray(tables),
        jnp.asarray(np.array([pos], np.int32)), n_kv, layer=0,
        interpret=True,
    )
    ref = _dense_decode_ref(q, jnp.asarray(slab), pos, n_kv, 0)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


# -- the packed training entry: (B, T, H*K), a block is a 128-lane group ------


def _packed_case(seed, b, t, heads, head_dim):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, t, heads * head_dim)), jnp.float32)
        for _ in range(3)
    )


def _heads_first(x, head_dim):  # (B, T, H*K) -> (B, H, T, K)
    b, t, hk = x.shape
    return x.reshape(b, t, hk // head_dim, head_dim).transpose(0, 2, 1, 3)


def _rows_first(x):  # (B, H, T, K) -> (B, T, H*K)
    b, h, t, k = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * k)


# T of one block, and of several with forward and backward blocks that
# differ from each other: (t, block_q, block_k, bwd_block_q, bwd_block_k)
_PACKED_BLOCKS = {
    "one-block": (64, 64, 64, None, None),
    "several-blocks": (128, 32, 64, 64, 32),
    # tiles that cross the diagonal in causal bands (``_band_rows``): one
    # tile of two bands, one of four, and a banded tile beside a fully
    # visible and a skipped one, so that the online state folds by rows
    "two-bands": (256, 256, 256, None, None),
    "four-bands": (512, 512, 512, None, None),
    "banded-among-tiles": (512, 256, 256, None, None),
}


@pytest.mark.parametrize("blocks", sorted(_PACKED_BLOCKS))
@pytest.mark.parametrize("hk", [128, 384])
@pytest.mark.parametrize("head_dim", [64, 32])
def test_flash_packed_matches_dense_and_the_bhtd_entry(head_dim, hk, blocks):
    """Two (K = 64) and four (K = 32) heads to a 128-lane block, one and
    three lane groups: output and all three gradients of the packed
    entry against dense causal attention and against
    ``flash_attention_trainable(layout="bhtd")`` at the same blocks."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_attention_packed,
        flash_attention_trainable,
    )

    t, bq, bk, bbq, bbk = _PACKED_BLOCKS[blocks]
    q, k, v = _packed_case(11, 2, t, hk // head_dim, head_dim)
    kw = dict(block_q=bq, block_k=bk, bwd_block_q=bbq, bwd_block_k=bbk,
              causal=True, interpret=True)

    def packed(q, k, v):
        return flash_attention_packed(q, k, v, head_dim, **kw)

    def bhtd(q, k, v):
        return _rows_first(flash_attention_trainable(
            *(_heads_first(a, head_dim) for a in (q, k, v)),
            layout="bhtd", **kw))

    def dense(q, k, v):
        return _rows_first(attention(
            *(_heads_first(a, head_dim) for a in (q, k, v)),
            causal=True, layout="bhtd"))

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o * jnp.cos(o)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, o_p), g_p = loss(packed)(q, k, v)
    for other in (bhtd, dense):
        (_, o), g = loss(other)(q, k, v)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o), atol=2e-5)
        for a, b in zip(g_p, g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_packed_keeps_a_head_to_its_own_lanes():
    """A ``v`` that is zero in one head gives an ``o`` that is zero in
    that head's lanes and nowhere else; and dq, dk of that head are zero
    too (its output does not depend on its scores), while the other head
    of the same 128-lane block is what it is without its neighbour."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_packed

    head_dim, heads, t = 64, 4, 64
    q, k, v = _packed_case(12, 1, t, heads, head_dim)
    a = 1  # the second head of the first lane group
    lanes = slice(a * head_dim, (a + 1) * head_dim)
    v0 = v.at[:, :, lanes].set(0.0)

    def f(q, k, v):
        return flash_attention_packed(
            q, k, v, head_dim, block_q=32, block_k=32, causal=True,
            interpret=True)

    o, pull = jax.vjp(f, q, k, v0)
    o_full = f(q, k, v)
    assert not np.asarray(o[:, :, lanes]).any()
    others = np.ones(heads * head_dim, bool)
    others[lanes] = False
    assert np.asarray(o[:, :, others] != 0).all()
    np.testing.assert_array_equal(
        np.asarray(o[:, :, others]), np.asarray(o_full[:, :, others]))
    dq, dk, dv = pull(jnp.ones_like(o))
    assert not np.asarray(dq[:, :, lanes]).any()
    assert not np.asarray(dk[:, :, lanes]).any()
    assert np.asarray(dv[:, :, lanes]).any()  # dv = P^T dO does not see v


def test_flash_packed_refuses_heads_that_do_not_fill_lane_groups():
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_packed

    x = jnp.zeros((1, 16, 192), jnp.float32)
    with pytest.raises(ValueError, match="128"):
        flash_attention_packed(x, x, x, 64)  # 3 heads of 64: 1.5 groups
    y = jnp.zeros((1, 16, 384), jnp.float32)
    with pytest.raises(ValueError, match="128"):
        flash_attention_packed(y, y, y, 48)  # 48 does not divide 128


def _kernel_dot_flops(fn, name, *args):
    """The ``dot_general``s of the Pallas kernel called ``name`` in the
    trace of ``fn`` as (products, FLOPs): those of the body itself, if
    it multiplies outside any ``pl.when``, then a causal class each (a
    ``pl.when`` body that multiplies, in program order: fully visible,
    then diagonal-crossing)."""
    def dots(jaxpr, nested=True):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lhs_c, _), _ = eqn.params["dimension_numbers"]
                depth = math.prod(eqn.invars[0].aval.shape[d] for d in lhs_c)
                yield 2 * math.prod(eqn.outvars[0].aval.shape) * depth
            for sub in jax.core.jaxprs_in_params(eqn.params) if nested else ():
                yield from dots(sub)

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"] == name:
                    yield eqn.params["jaxpr"]
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from kernels(sub)

    (body,) = kernels(jax.make_jaxpr(fn)(*args).jaxpr)
    classes = [list(dots(body, nested=False))] + [
        [f for sub in jax.core.jaxprs_in_params(eqn.params)
         for f in dots(sub)]
        for eqn in body.eqns
    ]
    return [(len(flops), sum(flops)) for flops in classes if flops]


@pytest.mark.parametrize("block,share,bands", [
    (64, 1.0, 1), (128, 1.0, 1), (256, 0.75, 2), (512, 0.625, 4),
    (1024, 0.625, 4),
])
def test_flash_packed_multiplies_the_bands_of_a_diagonal_tile_and_no_more(
        block, share, bands):
    """The rule and the work it leaves. ``flash_computed_share`` of one
    causal tile is 1.0 / 0.75 / 0.625 / 0.625 at 128 / 256 / 512 / 1,024
    rows and 1.0 without ``causal``; the products of the traced forward
    and backward bodies multiply exactly that share of the square a
    head in the diagonal-crossing class and the whole square in the
    fully visible one (a forward tile in bands is the only tile of its
    rows here, and its body holds the bands' products and no class); a
    tile of 128 rows or fewer holds the products it held before the
    bands: 2 a head forward, 5 a head backward."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_attention_packed,
        flash_computed_share,
    )

    assert flash_computed_share(block, block, block, True) == share
    assert flash_computed_share(block, block, block, False) == 1.0
    head_dim, heads = 64, 2
    x = jax.ShapeDtypeStruct((1, block, heads * head_dim), jnp.float32)

    def forward(q, k, v):
        return flash_attention_packed(
            q, k, v, head_dim, block_q=block, block_k=block, causal=True,
            interpret=True)

    def backward(q, k, v):
        o, pull = jax.vjp(forward, q, k, v)
        return pull(o)

    # a product a head runs 128 lanes wide: the other head's are zeros
    square = 2 * block * block * 128 * heads
    for fn, name, products in ((forward, "flash_fwd_packed", 2),
                               (backward, "flash_bwd_packed", 5)):
        whole = (products * heads, products * square)
        banded = (products * heads * bands, products * square * share)
        lone = products == 2 and bands > 1
        assert _kernel_dot_flops(fn, name, x, x, x) == (
            [banded] if lone else [whole, banded]), name


def test_flash_computed_share_counts_every_tile_of_a_longer_sequence():
    """At ``T = 8192`` with the forward's 1,024 / 1,024 blocks the eight
    diagonal tiles are banded and the 28 below them whole; the
    backward's 512 / 2,048 blocks differ from each other, so their
    diagonal tiles keep the masked body over the whole tile."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_computed_share

    assert flash_computed_share(8192, 1024, 1024, True) == (
        (28 + 8 * 0.625) / 64)
    # key block j of four is seen by the 16 - 4 j query blocks from its
    # first column down: 16 + 12 + 8 + 4 = 40 whole tiles of 64
    assert flash_computed_share(8192, 512, 2048, True) == 40 / 64
    assert flash_computed_share(512, 256, 256, True) == (1 + 2 * 0.75) / 4
