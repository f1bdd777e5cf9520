"""Latent attention in the gated stack (``layer_types`` of
``latent_attention``): a cache of one plane whose row is key and value of
every head, the expanded form in prefill and the absorbed form in decode
and chunks, sandwich norms, sigmoid routing.

The yardstick is ``benchmark/reference/pangu_moe.py`` (plain float32, the
expanded form only, imports nothing from the program), at the toy sizes of
the ``rehearse`` group of ``benchmark/configs/openpangu-ultra-moe-718b
.json``.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import pangu_moe  # noqa: E402
from deeplearning4j_tpu.models import transformer as tr  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    decode_rows_live,
    decode_rows_streamed,
    init_transformer,
    kv_cache_rows,
    kv_row_write,
    transformer_generate,
)
from deeplearning4j_tpu.ops.pallas_kernels import (  # noqa: E402
    latent_block_rows,
    latent_decode_attention_write,
)
from deeplearning4j_tpu.parallel.expert_parallel import (  # noqa: E402
    moe_held_ffn,
    route_top_k,
    swiglu,
)
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402
from deeplearning4j_tpu.serving.engine import build_step_program  # noqa: E402
from deeplearning4j_tpu.serving.scheduler import Request  # noqa: E402

CONFIG = json.loads(
    (ROOT / "benchmark/configs/openpangu-ultra-moe-718b.json").read_text())
LAGUNA = json.loads((ROOT / "benchmark/configs/laguna-s-2.1.json").read_text())


def toy_model(**over) -> dict:
    model = dict(CONFIG["model"])
    model.update(CONFIG["rehearse"]["model"])
    model.update(over)
    return model


def toy_cfg(**over) -> TransformerConfig:
    return TransformerConfig(**dict(toy_model(**over),
                                    compute_dtype=jnp.float32))


@pytest.fixture(scope="module")
def cfg():
    return toy_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    tree = _decode_builder(cfg)[3](init_transformer(jax.random.key(31), cfg))
    # norm scales away from 1, so that a norm left out or misplaced shows
    rng = np.random.default_rng(31)

    def shake(path, a):
        name = getattr(path[-1], "key", "")
        if str(name).endswith("_scale"):
            return a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, tree)


@pytest.fixture(scope="module")
def seqs(cfg):
    return np.random.default_rng(3).integers(
        0, cfg.vocab_size, (4, 72), np.int32
    )


@pytest.fixture(scope="module")
def settings():
    return {k: v for k, v in toy_model().items() if k != "compute_dtype"}


@pytest.fixture(scope="module")
def reference_logits(params, seqs, settings):
    return pangu_moe.forward(params, jnp.asarray(seqs), settings=settings)


# -- the configuration --------------------------------------------------------


def test_config_fields_freeze_and_round_trip(cfg):
    assert cfg.gated and cfg.latent and cfg.latent_row == 24
    assert tr.latent_row_width(cfg) == 128
    assert tr.latent_row_width(TransformerConfig(
        **dict(CONFIG["model"], compute_dtype=jnp.bfloat16))) == 640
    hash(cfg)
    assert TransformerConfig.from_json(cfg.to_json()) == cfg
    assert cfg.layers_of("latent") == (0, 1, 2, 3, 4)
    assert cfg.layers_of("full") == () and cfg.layers_of("window") == ()
    assert kv_cache_rows(cfg) == "latent" and kv_row_write(cfg) == "kernel"
    assert kv_cache_rows(TransformerConfig()) == "kv"
    laguna = dict(LAGUNA["model"], **LAGUNA["rehearse"]["model"])
    laguna["compute_dtype"] = jnp.float32
    assert kv_cache_rows(TransformerConfig(**laguna)) == "kv+ring"
    assert not TransformerConfig().latent


INCONSISTENT = {
    "share no stack": dict(layer_types=["latent_attention"] * 4
                           + ["full_attention"]),
    "q_lora_rank": dict(q_lora_rank=0),
    "kv_lora_rank": dict(kv_lora_rank=0),
    "v_head_dim": dict(v_head_dim=0),
    "must be even": dict(qk_rope_head_dim=7),
    "n_kv_heads does not apply": dict(n_kv_heads=2),
    "head_size does not apply": dict(head_size=16),
    "attn_gate does not apply": dict(attn_gate=True),
    "sliding_window does not apply": dict(sliding_window=16),
    "moe_score": dict(moe_score="tanh"),
}


@pytest.mark.parametrize("what", sorted(INCONSISTENT))
def test_inconsistent_latent_fields_are_refused_by_name(what):
    with pytest.raises(ValueError, match=what):
        toy_cfg(**INCONSISTENT[what])


def test_latent_fields_on_another_stack_are_refused_by_name():
    laguna = dict(LAGUNA["model"], **LAGUNA["rehearse"]["model"])
    laguna["compute_dtype"] = jnp.float32
    for extra in (dict(sandwich_norm=True), dict(kv_lora_rank=16)):
        with pytest.raises(ValueError, match="belong to latent_attention"):
            TransformerConfig(**dict(laguna, **extra))


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_prefill_then_decode_matches_the_reference(
        cfg, params, seqs, reference_logits, kernel):
    """Rows of 9 to 64 tokens in one bucket of 64 (the expanded form),
    then 8 decode steps through the latent leaf (the absorbed form),
    against the reference's expanded full forward."""
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    lens = np.asarray([9, 20, 40, 64], np.int32)
    caches, lg = jax.jit(prefill)(
        params, init_caches(4, 96), jnp.asarray(seqs[:, :64]),
        jnp.asarray(lens - 1),
    )
    assert set(caches) == {"latent"}
    assert caches["latent"].shape == (5, 1, 4, 96, 128)
    assert not np.asarray(caches["latent"][..., cfg.latent_row:]).any()
    step = jax.jit(fwd1)
    for j in range(9):
        want = np.stack([reference_logits[r, n + j - 1]
                         for r, n in enumerate(lens)])
        np.testing.assert_allclose(np.asarray(lg), want, atol=2e-4)
        if j == 8:
            break
        toks = jnp.asarray([seqs[r, n + j] for r, n in enumerate(lens)])
        lg, caches = step(params, caches, toks, jnp.asarray(lens + j))


def test_flash_prefill_pads_the_values_to_the_key_width(
        cfg, params, seqs, reference_logits):
    """``use_flash``: one head size in the kernel, so V (8) is padded to
    the key's 16 and cut again; same logits."""
    flash = dataclasses.replace(cfg, use_flash=True)
    _, init_caches, prefill, _ = _decode_builder(flash)
    _, lg = jax.jit(prefill)(
        params, init_caches(2, 96), jnp.asarray(seqs[:2, :64]),
        jnp.asarray([39, 63]),
    )
    np.testing.assert_allclose(
        np.asarray(lg), reference_logits[[0, 1], [39, 63]], atol=2e-4)


@pytest.mark.parametrize("chunk", [8, 24])
def test_chunked_prompt_matches_the_reference(
        cfg, params, seqs, reference_logits, chunk):
    """A 61-token prompt walked in padded chunks in the absorbed form
    (the last holds 5 or 13 real rows), then decode."""
    fwd1, init_caches, _, _ = _decode_builder(cfg)
    fwd_chunk = jax.jit(_chunk_builder(cfg))
    n, tmp = 61, init_caches(1, 96)
    for t0 in range(0, n, chunk):
        ln = min(chunk, n - t0)
        pad = np.zeros((1, chunk), np.int32)
        pad[0, :ln] = seqs[0, t0:t0 + ln]
        lg, tmp = fwd_chunk(params, tmp, jnp.asarray(pad), jnp.int32(t0),
                            jnp.int32(ln - 1))
    np.testing.assert_allclose(
        np.asarray(lg)[0], reference_logits[0, n - 1], atol=2e-4)
    for j in range(4):
        lg, tmp = fwd1(params, tmp, jnp.asarray(seqs[0:1, n + j]),
                       jnp.asarray([n + j]))
        np.testing.assert_allclose(
            np.asarray(lg)[0], reference_logits[0, n + j], atol=2e-4)
    lg_all, _ = _chunk_builder(cfg)(
        params, init_caches(1, 96), jnp.asarray(seqs[0:1, :24]), jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(lg_all)[0], reference_logits[0, :24], atol=2e-4)


@pytest.mark.parametrize("layer", range(5))
def test_the_absorbed_form_equals_the_expanded_form(cfg, params, layer):
    """One layer, one input, the two algebraic forms of its attention:
    keys and values expanded a head, or the up-projections absorbed into
    the query and the output and the latent rows attended themselves."""
    rng = np.random.default_rng(layer)
    x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
    positions = jnp.arange(24)
    causal = (positions[None, :] <= positions[:, None])[None]
    p = params["layers"][layer]

    expanded = tr.LatentAttend(
        False, lambda q, k, v, row: tr._attend_dense(q, k, v, causal))
    def lane_padded(row):
        pad = tr.latent_row_width(cfg) - row.shape[-1]
        return jnp.pad(row, [(0, 0), (0, 0), (0, pad)])

    absorbed = tr.LatentAttend(
        True, lambda q_lat, row: tr._latent_dense(
            q_lat, lane_padded(row), causal, cfg.kv_lora_rank))
    a, _ = tr._gated_block(cfg, layer, p, x, positions, expanded)
    b, _ = tr._gated_block(cfg, layer, p, x, positions, absorbed)
    assert float(jnp.max(jnp.abs(a - x))) > 0.1  # the layer does something
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# -- the kernel, interpreted --------------------------------------------------

_T, _BLOCK, _H, _R, _W = 64, 16, 4, 16, 128


def _kernel_case(seed, batch, dtype=jnp.float32, heads=_H):
    rng = np.random.default_rng(seed)
    lanes = np.arange(_W) < 24  # 16 latent + 8 rotary values, then padding

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape) * lanes, dtype)

    return (draw(batch, heads, _W), draw(2, 1, batch, _T, _W),
            draw(batch, 1, _W))


def _dense_absorbed(q, slab, pos):
    """q (B, H, W), slab (B, T, W): the product in plain float64."""
    q, slab = np.asarray(q, np.float64), np.asarray(slab, np.float64)
    out = np.zeros(q.shape[:2] + (_R,))
    for b, n in enumerate(np.asarray(pos)):
        s = q[b] @ slab[b, :n + 1].T
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (w / w.sum(-1, keepdims=True)) @ slab[b, :n + 1, :_R]
    return out


@pytest.mark.parametrize("pos", [0, 7, 8, _BLOCK - 1, _BLOCK, _T - 1])
def test_latent_kernel_places_the_row_and_attends_to_it(pos):
    where = jnp.asarray([pos, 13, _T - 2], jnp.int32)
    q, cache, new = _kernel_case(40 + pos, 3)
    out, written = latent_decode_attention_write(
        q, cache, new, where, _R, layer=1, block_t=_BLOCK, interpret=True)
    # the cache is the scatter's, bit for bit, and nothing else moved
    want = cache.at[1, 0, jnp.arange(3), where].set(new[:, 0])
    np.testing.assert_array_equal(np.asarray(written), np.asarray(want))
    # the output is the read-only walk's over that cache, bit for bit
    read, same = latent_decode_attention_write(
        q, want, None, where, _R, layer=1, block_t=_BLOCK, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(read))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(want))
    # and the dense absorbed product's, to rounding
    np.testing.assert_allclose(
        np.asarray(out), _dense_absorbed(q, want[1, 0], where), atol=1e-5)


@pytest.mark.parametrize("mask", [
    [True, False, True, True],    # a free slot between live ones
    [False, False, True, True],   # the first rows free
    [True, True, False, False],   # the last rows free
    [False, False, False, False],  # nobody home
])
def test_latent_kernel_free_slot_reads_and_writes_nothing(mask):
    pos = jnp.asarray([5, 17, _T - 1, _BLOCK], jnp.int32)
    q, cache, new = _kernel_case(80, 4)
    mask = np.asarray(mask)
    kw = dict(layer=0, block_t=_BLOCK, interpret=True)
    full_out, full_cache = latent_decode_attention_write(
        q, cache, new, pos, _R, **kw)
    out, written = latent_decode_attention_write(
        q, cache, new, pos, _R, active=jnp.asarray(mask), **kw)
    out, written = np.asarray(out), np.asarray(written)
    assert np.all(out[~mask] == 0.0)
    np.testing.assert_array_equal(out[mask], np.asarray(full_out)[mask])
    np.testing.assert_array_equal(
        written[:, :, ~mask], np.asarray(cache)[:, :, ~mask])
    np.testing.assert_array_equal(
        written[:, :, mask], np.asarray(full_cache)[:, :, mask])
    np.testing.assert_array_equal(written[1], np.asarray(cache)[1])


def test_latent_kernel_in_bf16_stays_near_the_dense_product():
    pos = jnp.asarray([3, 40, _T - 1], jnp.int32)
    q, cache, new = _kernel_case(9, 3, jnp.bfloat16)
    out, written = latent_decode_attention_write(
        q * 0.25, cache, new, pos, _R, layer=0, block_t=_BLOCK,
        interpret=True)
    assert out.dtype == jnp.bfloat16 and written.dtype == jnp.bfloat16
    want = _dense_absorbed(
        (q * 0.25).astype(jnp.float32), written[0, 0].astype(jnp.float32),
        pos)
    np.testing.assert_allclose(
        np.asarray(out, np.float64), want, atol=0.03)


# what the body must get right, with a block's rows cut in two parts each
# under a maximum of its own (PR 37): (pos, active, write_at) of three
# slots; ``None``: every slot live, the row at ``pos``
WALKS = {
    "ends_on_a_blocks_last_row": ([2 * _BLOCK - 1, 13, _T - 2], None, None),
    "ends_on_a_blocks_first_row": ([2 * _BLOCK, 13, _T - 2], None, None),
    "one_block_exactly": ([_BLOCK - 1] * 3, None, None),
    "ends_on_a_parts_last_and_first_row": (
        [_BLOCK // 2 - 1, _BLOCK // 2, 2 * _BLOCK + _BLOCK // 2], None, None),
    "a_free_slot_between_two_live_ones": (
        [5, 17, _T - 1], [True, False, True], None),
    "the_row_lands_in_the_first_walked_block": (
        [3 * _BLOCK + 2, 2 * _BLOCK, _T - 1], None, [3, 0, _BLOCK - 1]),
    "the_row_lands_in_the_last_walked_block": (
        [3 * _BLOCK + 5, 2 * _BLOCK, _T - 1], None,
        [3 * _BLOCK, 2 * _BLOCK, _T - 1]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("heads", [4, 128])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_latent_kernel_walks_are_the_dense_product(walk, heads, dtype):
    """Contexts that end on a part's or a block's edge, free slots and
    rows placed in the first and the last walked block give the dense
    absorbed product at the cell's 128 heads and at a few, in both
    cache dtypes, and the cache is the scatter's, bit for bit."""
    pos, active, write_at = WALKS[walk]
    pos = jnp.asarray(pos, jnp.int32)
    live = np.ones(3, bool) if active is None else np.asarray(active)
    at = pos if write_at is None else jnp.asarray(write_at, jnp.int32)
    q, cache, new = _kernel_case(sorted(WALKS).index(walk), 3, dtype, heads)
    q = q * jnp.asarray(0.25, dtype)
    out, written = latent_decode_attention_write(
        q, cache, new, pos, _R, layer=1, write_at=at,
        active=jnp.asarray(live), block_t=_BLOCK, interpret=True)
    assert out.dtype == dtype and out.shape == (3, heads, _R)
    want = cache.at[1, 0, jnp.arange(3)[live], at[live]].set(new[live, 0])
    np.testing.assert_array_equal(
        np.asarray(written, np.float32), np.asarray(want, np.float32))
    dense = _dense_absorbed(
        q.astype(jnp.float32), want[1, 0].astype(jnp.float32), pos)
    out = np.asarray(out, np.float64)
    assert np.all(out[~live] == 0.0)
    np.testing.assert_allclose(
        out[live], dense[live], atol=1e-5 if dtype == jnp.float32 else 0.03)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("row", [5, _BLOCK - 3, 2 * _BLOCK + 3, _T - 1])
def test_latent_kernel_holds_a_score_that_leaps_over_the_rest(row, dtype):
    """One row whose score lies 200 over every other, more than
    float32's exp holds, in the first part of a block, in the second, in
    a later block and at the context's end: each part's weights are
    taken against a maximum that holds the part's own scores, so the
    answer is the dense product's, no inf, no nan."""
    pos = jnp.asarray([_T - 1, _T - 1, row], jnp.int32)
    q, cache, new = _kernel_case(200 + row, 3, dtype)
    q = q * jnp.asarray(0.25, dtype)
    q0 = np.asarray(q[:, 0], np.float64)
    leap = 200.0 * q0 / np.sum(q0 * q0, axis=-1, keepdims=True)
    cache = cache.at[0, 0, :, row].set(jnp.asarray(leap, dtype))
    out, written = latent_decode_attention_write(
        q, cache, None, pos, _R, layer=0, block_t=_BLOCK, interpret=True)
    out = np.asarray(out, np.float64)
    assert np.all(np.isfinite(out))
    dense = _dense_absorbed(
        q.astype(jnp.float32), written[0, 0].astype(jnp.float32), pos)
    # head 0 sees the leaping row alone
    tol = (dict(atol=1e-4) if dtype == jnp.float32
           else dict(atol=0.06, rtol=2.0 ** -7))  # bf16 rounds the output
    np.testing.assert_allclose(
        out[:, 0], np.asarray(written[0, 0, :, row, :_R], np.float64), **tol)
    np.testing.assert_allclose(out, dense, **tol)


@pytest.mark.parametrize("pos", [3, _BLOCK // 2, _BLOCK + 2])
def test_latent_kernel_short_context_with_scores_far_below_zero(pos):
    """A part of a slot's first block that lies past the slot's position
    sees no row. Head 0 scores every row -200: its weights are uniform,
    whatever a part that saw nothing took for its reference."""
    where = jnp.asarray([pos, pos, pos], jnp.int32)
    q, cache, new = _kernel_case(300 + pos, 3)
    q0 = np.asarray(q[:, 0], np.float64)
    low = -200.0 * q0 / np.sum(q0 * q0, axis=-1, keepdims=True)
    rows = cache[0, 0] * jnp.asarray(np.arange(_W) >= 8, jnp.float32)
    q = q.at[:, 0, 8:].set(0.0)  # head 0 reads lanes 0-7 alone
    cache = cache.at[0, 0].set(
        rows + jnp.asarray(low * (np.arange(_W) < 8), jnp.float32)[:, None])
    out, written = latent_decode_attention_write(
        q, cache, None, where, _R, layer=0, block_t=_BLOCK, interpret=True)
    got = np.asarray(out, np.float64)
    want = _dense_absorbed(q, written[0, 0], where)
    uniform = np.asarray(written[0, 0, :, :pos + 1, :_R], np.float64).mean(1)
    np.testing.assert_allclose(want[:, 0], uniform, atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("pos", [0, 5, _BLOCK // 2 - 1, _BLOCK + 3])
def test_latent_kernel_never_reads_what_lies_past_the_position(pos):
    """Rows past a slot's position are whatever its last tenant left:
    values there so large that their scores overflow, in the part of the
    block that is walked and in the part after it, reach no output (the
    mask is a select on the scores, not a bias added to them)."""
    where = jnp.asarray([pos, pos + 1, pos], jnp.int32)
    q, cache, new = _kernel_case(400 + pos, 3)
    stale = jnp.arange(_T)[None, :, None] > where[:, None, None]
    cache = cache.at[1, 0].set(jnp.where(stale, 3e38, cache[1, 0]))
    out, written = latent_decode_attention_write(
        q, cache, None, where, _R, layer=1, block_t=_BLOCK, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), _dense_absorbed(q, written[1, 0], where), atol=1e-5)


def test_latent_block_rows_is_the_walk_rule_for_the_same_bytes():
    from deeplearning4j_tpu.ops.pallas_kernels import decode_block_rows

    assert latent_block_rows(4096, 640, 2) == decode_block_rows(4096, 320, 2)
    assert 4096 % latent_block_rows(4096, 640, 2) == 0
    assert latent_block_rows(96, 128, 4) == 96  # no divisor >= 128: one block


# -- routing ------------------------------------------------------------------


def _layer_inputs(cfg, seed=0, n=24):
    rng = np.random.default_rng(seed)
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts_total
    w = {
        "router": rng.normal(size=(d, e)) / np.sqrt(d),
        "we_gate": rng.normal(size=(e, d, f)) / np.sqrt(d),
        "we_up": rng.normal(size=(e, d, f)) / np.sqrt(d),
        "we_down": rng.normal(size=(e, f, d)) / np.sqrt(f),
        "ws_gate": rng.normal(size=(d, f)) / np.sqrt(d),
        "ws_up": rng.normal(size=(d, f)) / np.sqrt(d),
        "ws_down": rng.normal(size=(f, d)) / np.sqrt(f),
    }
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32), w


def _uncut_layer(cfg, h, w):
    """The whole layer in plain numpy: all 16 experts scored by a
    sigmoid, the top 4 renormalised and scaled, the shared one."""
    h, w = np.asarray(h, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in w.items()}

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(x, g, u, dn):
        return (silu(x @ g) * (x @ u)) @ dn

    s = 1 / (1 + np.exp(-(h @ w["router"])))
    out = ffn(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    for t in range(h.shape[0]):
        top = np.argsort(-s[t])[:cfg.moe_k]
        for e in top:
            out[t] += cfg.moe_scale * s[t, e] / (s[t, top].sum() + 1e-20) * (
                ffn(h[t], w["we_gate"][e], w["we_up"][e], w["we_down"][e]))
    return out


def test_sigmoid_weights_by_hand_and_the_same_set_as_softmax():
    h = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    router = jnp.asarray([[2.0, -1.0, 0.5, 0.0, 1.0],
                          [-3.0, 0.25, 0.3, 4.0, -0.5]], jnp.float32)
    ids, w = route_top_k(h, router, k=2, scale=2.5, score="sigmoid")

    def sig(x):
        return 1 / (1 + np.exp(-x))

    assert ids.tolist() == [[0, 4], [3, 2]]
    want = [[sig(2.0), sig(1.0)], [sig(4.0), sig(0.3)]]
    want = [[2.5 * a / (a + b + 1e-20), 2.5 * b / (a + b + 1e-20)]
            for a, b in want]
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)
    soft_ids, soft_w = route_top_k(h, router, k=2, scale=2.5)
    assert soft_ids.tolist() == ids.tolist()
    assert np.abs(np.asarray(soft_w) - np.asarray(w)).max() > 0.05
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    # over random rows too: both scores grow with the logit
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    a, _ = route_top_k(h, router, k=4, scale=1.0, score="sigmoid")
    b, _ = route_top_k(h, router, k=4, scale=1.0, score="softmax")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="softmax.*sigmoid"):
        route_top_k(h, router, k=4, scale=1.0, score="tanh")


def test_the_four_shares_add_up_to_the_uncut_layer(cfg):
    """The guide's share test: what the four chips holding experts 0-3,
    4-7, 8-11 and 12-15 compute, with the shared expert counted once, is
    the uncut layer."""
    h, w = _layer_inputs(cfg)
    held_n = cfg.n_experts
    assert held_n * 4 == cfg.n_experts_total
    total, pairs = 0.0, 0
    for first in range(0, cfg.n_experts_total, held_n):
        held = slice(first, first + held_n)
        y, counts = moe_held_ffn(
            h, w["router"], w["we_gate"][held], w["we_up"][held],
            w["we_down"][held], first=first, k=cfg.moe_k,
            scale=cfg.moe_scale, score="sigmoid",
        )
        total = total + np.asarray(y, np.float64)
        pairs += int(counts[0])
        assert int(counts[1]) == cfg.moe_k * h.shape[0]
    shared = np.asarray(swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"]))
    assert pairs == cfg.moe_k * h.shape[0]  # every pair on exactly one chip
    np.testing.assert_allclose(
        total + shared, _uncut_layer(cfg, h, w), rtol=2e-4, atol=2e-4)


def test_no_token_dropped_when_routing_piles_onto_one_held_expert(cfg):
    """Every token's first choice is held expert 3: no capacity, no
    drop, and the rows that belong to no group change nothing."""
    h, w = _layer_inputs(cfg, seed=1, n=40)
    h = jnp.abs(h)
    router = np.asarray(w["router"]).copy()
    router[:, 3] = 0.3  # h > 0: expert 3 wins every row
    w["router"] = jnp.asarray(router)
    y, counts = moe_held_ffn(
        h, w["router"], w["we_gate"][:4], w["we_up"][:4], w["we_down"][:4],
        first=0, k=cfg.moe_k, scale=cfg.moe_scale, score="sigmoid",
    )
    ids = np.argsort(-np.asarray(h @ w["router"]), axis=-1)[:, :cfg.moe_k]
    assert (ids[:, 0] == 3).all()
    assert int(counts[0]) == int((ids < 4).sum()) >= 40
    assert int(counts[2]) >= 1
    full, _ = moe_held_ffn(
        h, w["router"], w["we_gate"], w["we_up"], w["we_down"], first=0,
        k=cfg.moe_k, scale=cfg.moe_scale, score="sigmoid",
    )
    shared = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    np.testing.assert_allclose(
        np.asarray(full + shared), _uncut_layer(cfg, h, w),
        rtol=2e-4, atol=2e-4)
    assert np.abs(np.asarray(y)).min(axis=-1).max() > 0  # every row served


# -- the pool, the engine, its counters ---------------------------------------


def test_cache_rows_read_and_needed_over_one_plane(cfg):
    held = [5, 16, 40]
    assert decode_rows_live(cfg, held) == 61
    # 96 rows a slot: one block, every active row reads it whole
    assert decode_rows_streamed(cfg, 4, 96, held) == 3 * 96
    # 256 rows: blocks of latent_block_rows, from the latent row's width
    block = latent_block_rows(256, 128, 4)
    assert block in (128, 256)
    assert decode_rows_streamed(cfg, 4, 256, [5, 200]) == (
        block + min(-(-200 // block) * block, 256))
    dense = dataclasses.replace(cfg, decode_kernel=False)
    assert decode_rows_streamed(dense, 4, 96, held) == 4 * 96


@pytest.fixture(scope="module")
def served(cfg, params):
    engine = ServingEngine(
        cfg, params, n_slots=3, max_total=128, decode_horizon=4,
        prefill_max_bucket=32, temperature=0.0, batch_admission=False,
        chunked_replay=False, max_queue_depth=4,
    )
    rng = np.random.default_rng(5)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new=m)
        for n, m in ((5, 9), (40, 18), (70, 12), (33, 7))
    ]
    for r in reqs:
        engine.submit(r)
    while not engine.idle:
        engine.step()
    return engine, reqs


def test_engine_serves_bucketed_and_chunked_prompts_through_the_latent_leaf(
        cfg, params, served):
    """Greedy streams equal ``transformer_generate``'s, whatever the
    admission path (one bucket in the expanded form, chunks in the
    absorbed one)."""
    engine, reqs = served
    assert engine.pool.tpad == 128
    assert set(engine.pool.caches) == {"latent"}
    assert engine.pool.caches["latent"].shape == (5, 1, 3, 128, 128)
    assert engine.pool.nbytes() == 5 * 3 * 128 * 128 * 4
    assert engine.scheduler.max_queue_depth == 4
    gen = jax.jit(transformer_generate(cfg),
                  static_argnames=("max_new", "temperature"))
    for r in reqs:
        want = np.asarray(gen(
            params, jnp.asarray(r.prompt[None]), jax.random.key(0),
            max_new=r.max_new, temperature=0.0,
        ))[0, len(r.prompt):]
        got = np.asarray(engine.pop_result(r.id))[-r.max_new:]
        np.testing.assert_array_equal(got, want)


def test_engine_books_rows_experts_and_the_kind_of_row(cfg, served):
    engine, reqs = served
    s = engine.metrics.summary()
    assert s["kv_cache_rows"] == "latent" and s["kv_row_write"] == "kernel"
    text = engine.metrics.registry.render()
    assert 'serve_kv_cache_rows{how="latent"} 1' in text
    assert 'serve_kv_cache_rows{how="kv"}' not in text
    routed = cfg.n_layers - len(cfg.dense_layers)
    substeps = s["moe_assignments_total"] // (cfg.moe_k * routed)
    assert s["moe_assignments_total"] == substeps * cfg.moe_k * routed
    assert sum(r.max_new - 1 for r in reqs) <= substeps <= sum(
        r.max_new + 4 for r in reqs)
    assert 0 < s["moe_assignments_local"] < s["moe_assignments_total"]
    assert 0 < s["moe_experts_hit"] <= s["moe_assignments_local"]
    # one block a slot at 128 rows: every counted substep of a slot
    # needs its rows and reads the whole slab
    assert 0 < s["kv_rows_live"] < s["kv_rows_streamed"]
    assert s["kv_rows_streamed"] % 128 == 0


def test_row_counters_exact_on_a_scripted_batch(cfg, params):
    """One request of 5 + 6 tokens, horizon 2: the six substeps hold 6 to
    11 rows (each writes its own first), and each reads the slot's
    32-row slab, one block; the free slot reads nothing."""
    engine = ServingEngine(
        cfg, params, n_slots=2, max_total=32, decode_horizon=2,
        prefill_max_bucket=8, temperature=0.0, batch_admission=False,
        chunked_replay=False,
    )
    engine.submit(Request(prompt=np.arange(5, dtype=np.int32), max_new=6))
    while not engine.idle:
        engine.step()
    s = engine.metrics.summary()
    assert s["kv_rows_live"] == sum(range(6, 12))
    assert s["kv_rows_streamed"] == 6 * 32
    assert s["kv_cache_rows"] == "latent"


def _engine(cfg, params, **kw):
    return ServingEngine(cfg, params, n_slots=2, max_total=64,
                         batch_admission=False, chunked_replay=False, **kw)


REFUSED = {
    "paged pool": lambda cfg, p: _engine(cfg, p, paged=True),
    "prefix cache": lambda cfg, p: _engine(cfg, p, prefix_cache=True),
    "tensor-parallel": lambda cfg, p: _engine(cfg, p, tp=2),
    "LoRA bank": lambda cfg, p: _engine(cfg, p, lora_bank={"a_q": None}),
    "decode_int8": lambda cfg, p: _decode_builder(
        dataclasses.replace(cfg, decode_int8=True)),
    "beam search": lambda cfg, p: tr.transformer_beam_search(cfg),
    "speculative decoding": lambda cfg, p:
        tr.transformer_speculative_generate(cfg),
    "training": lambda cfg, p: tr.transformer_apply(cfg),
    "int8 decode quantization": lambda cfg, p:
        tr.quantize_decode_params(p, cfg),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_the_latent_stack_cannot_do_raises_by_name(cfg, params, what):
    with pytest.raises(NotImplementedError, match=what) as e:
        REFUSED[what](cfg, params)
    assert "latent" in str(e.value)


def test_kvsg_frames_are_refused_by_name(cfg, served):
    engine, _ = served
    with pytest.raises(NotImplementedError, match="KVSG.*latent"):
        engine.export_sessions()
    req = Request(prompt=np.zeros(4, np.int32), max_new=1)
    req.kind = "kv_export"
    with pytest.raises(NotImplementedError, match="KVSG.*latent"):
        engine.submit(req)


def test_queue_depth_for_the_engines_own_scheduler_only(cfg, params):
    """``max_queue_depth`` is the depth of the scheduler the engine
    builds (the 5th waiting request is refused at 4); beside a
    scheduler of the caller's it is one setting too many."""
    from deeplearning4j_tpu.serving.scheduler import (
        Backpressure,
        RequestScheduler,
    )

    engine = _engine(cfg, params, max_queue_depth=4)
    for _ in range(4):
        engine.submit(Request(prompt=np.zeros(4, np.int32), max_new=1))
    with pytest.raises(Backpressure):
        engine.submit(Request(prompt=np.zeros(4, np.int32), max_new=1))
    with pytest.raises(ValueError, match="max_queue_depth"):
        _engine(cfg, params, scheduler=RequestScheduler(),
                max_queue_depth=8)


# -- the other stacks build the programs they built --------------------------


def _step_text(cfg, slots=3):
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    params = jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0))
    S = jax.ShapeDtypeStruct
    avals = (
        params, jax.eval_shape(lambda: init_caches(slots, 64)),
        S((slots, cfg.vocab_size), jnp.float32), S((slots,), jnp.int32),
        S((slots,), jnp.bool_), S((slots,), jnp.int32),
        S((slots,), jnp.int32), S((slots, 2), jnp.uint32),
        S((slots,), jnp.int32),
    )
    return jax.jit(build_step_program(fwd1, 2, 0.0, None, False)).lower(
        *avals).as_text()


def test_the_other_stacks_steps_call_the_kernel_they_called(cfg):
    """A GPT-2 and a Laguna step trace the K/V walk and nothing of the
    latent path; the latent stack's step calls its own kernel and never
    the K/V one."""
    gpt2 = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_layers=2, d_ff=64, max_len=64)
    laguna = dict(LAGUNA["model"], **LAGUNA["rehearse"]["model"])
    laguna["compute_dtype"] = jnp.float32
    for other in (gpt2, TransformerConfig(**laguna)):
        text = _step_text(other)
        assert "@_decode_attention" in text
        assert "_latent_decode_attention" not in text
    text = _step_text(cfg)
    assert "@_latent_decode_attention" in text
    assert "@_decode_attention" not in text
