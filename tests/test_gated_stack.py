"""The gated stack (``TransformerConfig.layer_types``): layers of two
kinds in one slot pool, experts held as a chip's share.

The yardstick is ``benchmark/reference/laguna.py`` (plain float32, imports
nothing from the program), at the toy sizes of the ``rehearse`` group of
``benchmark/configs/laguna-s-2.1.json``: window 16, so every prompt past
16 tokens wraps a ring.
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import laguna  # noqa: E402
from deeplearning4j_tpu.models import transformer as tr  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    decode_rows_live,
    decode_rows_streamed,
    init_transformer,
    moe_grouped,
    transformer_generate,
)
from deeplearning4j_tpu.parallel.expert_parallel import (  # noqa: E402
    moe_held_ffn,
    swiglu,
)
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402
from deeplearning4j_tpu.serving import engine as engine_mod  # noqa: E402
from deeplearning4j_tpu.serving.scheduler import Request  # noqa: E402

CONFIG = json.loads((ROOT / "benchmark/configs/laguna-s-2.1.json").read_text())


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
    return _decode_builder(cfg)[3](init_transformer(jax.random.key(7), cfg))


@pytest.fixture(scope="module")
def seqs(cfg):
    return np.random.default_rng(3).integers(
        0, cfg.vocab_size, (4, 72), np.int32
    )


@pytest.fixture(scope="module")
def reference_logits(cfg, params, seqs):
    settings = {k: v for k, v in toy_model().items() if k != "compute_dtype"}
    return laguna.forward(params, jnp.asarray(seqs), settings=settings)


def test_config_fields_freeze_and_round_trip(cfg):
    assert cfg.gated and cfg.head_dim == 16 and cfg.d_model % 6  # 64 / 6
    assert isinstance(cfg.layer_types, tuple)
    assert isinstance(cfg.rope_full, tuple)
    hash(cfg)
    assert TransformerConfig.from_json(cfg.to_json()) == cfg
    assert cfg.layers_of("full") == (0, 4)
    assert cfg.layers_of("window") == (1, 2, 3)
    assert [cfg.heads_of(l) for l in range(5)] == [4, 6, 6, 6, 4]
    # a head size that does not divide d_model is fine when it is given
    assert TransformerConfig(d_model=100, n_heads=3, head_size=32).head_dim == 32
    with pytest.raises(ValueError, match="multiple of 8"):
        toy_cfg(sliding_window=12)


def test_yarn_tables_against_hand_computed_values():
    """Base 500,000, factor 128, 8,192 original positions, beta 32 / 1
    over 64 rotated channels: ``low`` 9, ``high`` 18 (ISSUE 27)."""
    def pair(turns):
        return 64 * math.log(8192 / (2 * math.pi * turns)) / (
            2 * math.log(500000))

    assert math.floor(pair(32)) == 9 and math.ceil(pair(1)) == 18
    inv = tr.yarn_inv_freq(64, 500000, 128, 8192, 32, 1)
    assert inv.shape == (32,)
    f = [500000 ** (-i / 32) for i in range(32)]
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-12)  # kept
    np.testing.assert_allclose(inv[18:], np.asarray(f[18:]) / 128, rtol=1e-12)
    r12 = 1 - (12 - 9) / 9  # pair 12: a third of the way down the ramp
    np.testing.assert_allclose(
        inv[12], (1 - r12) * f[12] / 128 + r12 * f[12], rtol=1e-12)
    np.testing.assert_allclose(
        inv, laguna.yarn_inv_freq(64, 500000, 128, 8192, 32, 1), rtol=1e-12)
    assert abs(0.1 * math.log(128) + 1 - 1.4852030263919618) < 1e-12
    # the tables: scaled by attention_factor, half the head rotated
    cfg = toy_cfg()
    cos, sin = tr._gated_rope(cfg, "full", jnp.arange(5), jnp.float32)
    assert cos.shape == (5, cfg.head_dim // 4)
    np.testing.assert_allclose(cos[0], 1.4852030263919618, rtol=1e-6)
    cos_w, _ = tr._gated_rope(cfg, "window", jnp.arange(5), jnp.float32)
    assert cos_w.shape == (5, cfg.head_dim // 2)
    np.testing.assert_allclose(cos_w[0], 1.0)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_prefill_then_decode_matches_the_reference(
        cfg, params, seqs, reference_logits, kernel):
    """Rows of 9 to 64 tokens in one bucket of 64, then 8 decode steps
    through both leaves: every ring has wrapped (window 16)."""
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    # the decode steps' 4 rows take the streamed expert kernel, the
    # prefill's 4 rows of 64 positions lax.ragged_dot
    assert moe_grouped(cfg, 1) == ("streamed" if kernel else "ragged")
    assert moe_grouped(cfg, 64) == "ragged"
    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    lens = np.asarray([9, 20, 40, 64], np.int32)
    caches, lg = jax.jit(prefill)(
        params, init_caches(4, 96), jnp.asarray(seqs[:, :64]),
        jnp.asarray(lens - 1),
    )
    assert caches["window"].shape[3] == 16 and caches["full"].shape[3] == 96
    step = jax.jit(fwd1)
    for j in range(9):
        want = np.stack([reference_logits[r, n + j - 1]
                         for r, n in enumerate(lens)])
        np.testing.assert_allclose(np.asarray(lg), want, atol=2e-4)
        if j == 8:
            break
        toks = jnp.asarray([seqs[r, n + j] for r, n in enumerate(lens)])
        lg, caches = step(params, caches, toks, jnp.asarray(lens + j))


@pytest.mark.parametrize("chunk", [8, 24], ids=["under_window", "over_window"])
def test_chunked_prompt_matches_the_reference(
        cfg, params, seqs, reference_logits, chunk):
    """A 61-token prompt walked in padded chunks (the last holds 5 or 13
    real rows), then decode: the padding must not reach a ring."""
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
    # every row's logits when no last row is named (a verify chunk)
    lg_all, _ = _chunk_builder(cfg)(
        params, init_caches(1, 96), jnp.asarray(seqs[0:1, :24]), jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(lg_all)[0], reference_logits[0, :24], atol=2e-4)


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
    """The whole layer in plain numpy: all 16 experts, the shared one."""
    h, w = np.asarray(h, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in w.items()}

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(x, g, u, dn):
        return (silu(x @ g) * (x @ u)) @ dn

    logits = h @ w["router"]
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = ffn(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    for t in range(h.shape[0]):
        top = np.argsort(-p[t])[:cfg.moe_k]
        for e in top:
            out[t] += cfg.moe_scale * p[t, e] / p[t, top].sum() * ffn(
                h[t], w["we_gate"][e], w["we_up"][e], w["we_down"][e])
    return out


def test_the_two_shares_add_up_to_the_uncut_layer(cfg):
    """The guide's share test: what the chip holding experts 0-7 and the
    chip holding 8-15 compute, with the shared expert counted once, is
    the uncut layer."""
    h, w = _layer_inputs(cfg)
    half = cfg.n_experts_total // 2
    parts, pairs = [], 0
    for first in (0, half):
        held = slice(first, first + half)
        y, counts = moe_held_ffn(
            h, w["router"], w["we_gate"][held], w["we_up"][held],
            w["we_down"][held], first=first, k=cfg.moe_k,
            scale=cfg.moe_scale,
        )
        parts.append(np.asarray(y, np.float64))
        pairs += int(counts[0])
        assert int(counts[1]) == cfg.moe_k * h.shape[0]
    shared = np.asarray(swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"]))
    assert pairs == cfg.moe_k * h.shape[0]  # every pair on exactly one chip
    np.testing.assert_allclose(
        parts[0] + parts[1] + shared, _uncut_layer(cfg, h, w),
        rtol=2e-4, atol=2e-4)


def test_no_token_dropped_when_routing_piles_onto_one_expert(cfg):
    """Every token's first choice is expert 3: no capacity, no drop."""
    h, w = _layer_inputs(cfg, seed=1, n=40)
    h = jnp.abs(h)
    router = np.asarray(w["router"]).copy()
    router[:, 3] = 0.3  # h > 0: expert 3 wins every row, by about 15
    w["router"] = jnp.asarray(router)
    y, counts = moe_held_ffn(
        h, w["router"], w["we_gate"][:8], w["we_up"][:8], w["we_down"][:8],
        first=0, k=cfg.moe_k, scale=cfg.moe_scale,
    )
    ids = np.argsort(-np.asarray(h @ w["router"]), axis=-1)[:, :cfg.moe_k]
    assert (ids[:, 0] == 3).all()
    assert int(counts[0]) == int((ids < 8).sum()) >= 40
    full, _ = moe_held_ffn(
        h, w["router"], w["we_gate"], w["we_up"], w["we_down"], first=0,
        k=cfg.moe_k, scale=cfg.moe_scale,
    )
    shared = swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    np.testing.assert_allclose(
        np.asarray(full + shared), _uncut_layer(cfg, h, w),
        rtol=2e-4, atol=2e-4)
    assert np.abs(np.asarray(y)).min(axis=-1).max() > 0  # every row served


def test_moe_counters_exact_on_a_scripted_batch(cfg):
    """Six rows, two of them not live; the router is scripted so that
    each row's four experts are known."""
    d, e, k = cfg.d_model, cfg.n_experts_total, cfg.moe_k
    picks = [[0, 1, 2, 3], [0, 1, 8, 9], [8, 9, 10, 11], [4, 5, 6, 7],
             [0, 4, 12, 13], [1, 2, 3, 4]]
    h = np.zeros((6, d), np.float32)
    router = np.zeros((d, e), np.float32)
    for t, chosen in enumerate(picks):
        h[t, t] = 1.0
        router[t, chosen] = 4.0
    _, w = _layer_inputs(cfg)
    live = jnp.asarray([True, True, True, False, True, False])
    _, counts = moe_held_ffn(
        jnp.asarray(h), jnp.asarray(router), w["we_gate"][:8],
        w["we_up"][:8], w["we_down"][:8], first=0, k=k, scale=1.0, live=live,
    )
    # live rows 0, 1, 2, 4 hold 4 + 2 + 0 + 2 pairs here, on experts
    # {0, 1, 2, 3} + {0, 1} + {} + {0, 4}
    assert counts.tolist() == [8, 4 * k, 5]
    _, counts = moe_held_ffn(
        jnp.asarray(h), jnp.asarray(router), w["we_gate"][8:],
        w["we_up"][8:], w["we_down"][8:], first=8, k=k, scale=1.0, live=live,
    )
    assert counts.tolist() == [8, 4 * k, 6]  # 8, 9 twice; 10, 11, 12, 13


def test_forward_one_hands_its_counters_to_a_step_program(cfg, params):
    fwd1, init_caches, _, _ = _decode_builder(cfg)
    assert fwd1.counts_moe
    assert not getattr(_decode_builder(TransformerConfig())[0],
                       "counts_moe", False)
    stats = []
    active = jnp.asarray([True, False, True])
    fwd1(params, init_caches(3, 32), jnp.zeros((3,), jnp.int32),
         jnp.asarray([0, 0, 0]), active=active, stats=stats)
    (counts,) = stats
    routed = cfg.n_layers - len(cfg.dense_layers)
    assert int(counts[1]) == 2 * cfg.moe_k * routed
    assert 0 < int(counts[0]) <= int(counts[1])
    assert 0 < int(counts[2]) <= min(int(counts[0]), cfg.n_experts * routed)


def test_a_plain_step_program_is_the_one_it_was():
    """``tallied`` adds nothing to a program whose fwd1 does not count."""
    small = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                              n_layers=1, d_ff=32, max_len=16)
    fwd1, init_caches, _, _ = _decode_builder(small)
    same, ride = engine_mod.tallied(fwd1)
    assert same is fwd1
    block = jnp.zeros((3, 4), jnp.int32)
    assert ride(block) is block


def test_cache_rows_read_and_needed_over_two_leaves(cfg):
    """2 full layers at the slab's length and 3 rings of 16: the counts
    are layer-weighted means, so live / streamed is the share needed."""
    held = [5, 16, 40]
    # needed: full 5 + 16 + 40; a ring min(h, 16)
    assert decode_rows_live(cfg, held) == (2 * 61 + 3 * (5 + 16 + 16)) // 5
    # read: one block a leaf at this size, the row's rows rounded up to it
    assert decode_rows_streamed(cfg, 4, 96, held) == (
        2 * 3 * 96 + 3 * 3 * 16) // 5
    dense = dataclasses.replace(cfg, decode_kernel=False)
    assert decode_rows_streamed(dense, 4, 96, held) == (
        2 * 4 * 96 + 3 * 4 * 16) // 5
    plain = TransformerConfig()
    assert decode_rows_live(plain, held) == 61


@pytest.fixture(scope="module")
def served(cfg, params):
    engine = ServingEngine(
        cfg, params, n_slots=3, max_total=128, decode_horizon=4,
        prefill_max_bucket=32, temperature=0.0, batch_admission=False,
        chunked_replay=False,
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


def test_engine_serves_bucketed_and_chunked_prompts_through_both_leaves(
        cfg, params, served):
    """Greedy streams equal ``transformer_generate``'s, whatever the
    admission path (one bucket, chunks) and with rings long wrapped."""
    engine, reqs = served
    assert engine.pool.tpad == 128
    assert set(engine.pool.caches) == {"full", "window"}
    gen = jax.jit(transformer_generate(cfg),
                  static_argnames=("max_new", "temperature"))
    for r in reqs:
        want = np.asarray(gen(
            params, jnp.asarray(r.prompt[None]), jax.random.key(0),
            max_new=r.max_new, temperature=0.0,
        ))[0, len(r.prompt):]
        got = np.asarray(engine.pop_result(r.id))[-r.max_new:]
        np.testing.assert_array_equal(got, want)


def test_engine_books_the_expert_layers_and_both_leaves(cfg, served):
    engine, reqs = served
    s = engine.metrics.summary()
    routed = cfg.n_layers - len(cfg.dense_layers)
    # every substep of a live slot routes moe_k pairs a routed layer
    substeps = s["moe_assignments_total"] // (cfg.moe_k * routed)
    assert s["moe_assignments_total"] == substeps * cfg.moe_k * routed
    assert sum(r.max_new - 1 for r in reqs) <= substeps <= sum(
        r.max_new + 4 for r in reqs)
    assert 0 < s["moe_assignments_local"] < s["moe_assignments_total"]
    assert 0 < s["moe_experts_hit"] <= s["moe_assignments_local"]
    assert 0 < s["kv_rows_live"] <= s["kv_rows_streamed"]
    # the step programs' experts went through the streamed kernel
    assert s["moe_grouped"] == "streamed"
    text = engine.metrics.registry.render()
    assert 'serve_moe_grouped{how="streamed"} 1' in text
    for name in ("serve_moe_assignments_local_total",
                 "serve_moe_assignments_total", "serve_moe_experts_hit_total"):
        assert name in text


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
def test_what_the_gated_stack_cannot_do_raises_by_name(cfg, params, what):
    with pytest.raises(NotImplementedError, match=what):
        REFUSED[what](cfg, params)


def test_kvsg_frames_are_refused_by_name(cfg, served):
    engine, _ = served
    with pytest.raises(NotImplementedError, match="KVSG"):
        engine.export_sessions()
    req = Request(prompt=np.zeros(4, np.int32), max_new=1)
    req.kind = "kv_export"
    with pytest.raises(NotImplementedError, match="KVSG"):
        engine.submit(req)
