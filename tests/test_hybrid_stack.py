"""Linear attention in the gated stack (``layer_types`` with
``linear_attention``): the gated delta rule, whose float32 state lives in
the slot pool beside the full layers' K/V slab, a convolution window leaf,
post-norm blocks and QK-norm.

The yardstick is ``benchmark/reference/olmo_hybrid.py`` (plain float32,
the token-by-token recurrence, imports nothing from the program), at a toy
of d 64, three linear layers and one full one, two heads of dk 16 / dv 32
and a vocabulary of 97.
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

from benchmark.reference import olmo_hybrid  # noqa: E402
from deeplearning4j_tpu.models import transformer as tr  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    decode_rows_live,
    decode_rows_streamed,
    init_transformer,
    kv_cache_rows,
    state_rule_chunked,
    transformer_generate,
)
from deeplearning4j_tpu.ops.pallas_kernels import (  # noqa: E402
    linear_decode,
    linear_decode_dense,
    linear_heads_per_step,
)
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402
from deeplearning4j_tpu.serving.faults import FaultInjector  # noqa: E402
from deeplearning4j_tpu.serving.scheduler import Request  # noqa: E402

CONFIG = json.loads((ROOT / "benchmark/configs/olmo-hybrid-7b.json").read_text())
TOY = dict(vocab_size=97, d_model=64, n_heads=2, n_layers=4, d_ff=128,
           max_len=256, layer_types=["linear_attention"] * 3
           + ["full_attention"], dense_layers=[0, 1, 2, 3],
           linear_num_key_heads=2, linear_num_value_heads=2,
           linear_key_head_dim=16, linear_value_head_dim=32)


def toy_model(**over) -> dict:
    model = dict(CONFIG["model"])
    model.update(TOY)
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
    tree = _decode_builder(cfg)[3](init_transformer(jax.random.key(38), cfg))
    # norm scales away from 1, so that a norm left out or misplaced shows
    rng = np.random.default_rng(38)

    def shake(path, a):
        if str(getattr(path[-1], "key", "")).endswith("_scale"):
            return a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, tree)


@pytest.fixture(scope="module")
def settings():
    return {k: v for k, v in toy_model().items() if k != "compute_dtype"}


def _reference(params, seqs, settings):
    return olmo_hybrid.forward(params, jnp.asarray(seqs), settings=settings)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


# -- the configuration --------------------------------------------------------


def test_config_fields_freeze_and_round_trip(cfg):
    assert cfg.gated and cfg.linear and not cfg.latent
    assert cfg.layers_of("linear") == (0, 1, 2)
    assert cfg.layers_of("full") == (3,)
    assert cfg.linear_state_width == 64 and cfg.linear_conv_width == 128
    hash(cfg)
    assert TransformerConfig.from_json(cfg.to_json()) == cfg
    assert kv_cache_rows(cfg) == "kv+state"
    full = TransformerConfig(**dict(CONFIG["model"],
                                    compute_dtype=jnp.bfloat16))
    assert full.linear_state_width == 5760
    assert full.linear_conv_width == 11520


def test_the_state_leaf_is_float32_beside_a_bf16_slab():
    """The state's float32 is the configuration's stated precision: a
    bf16 stack keeps its K/V and its convolution window in bf16 and its
    state in float32, whatever the compute dtype."""
    cfg = TransformerConfig(**dict(toy_model(), compute_dtype=jnp.bfloat16))
    caches = jax.eval_shape(lambda: _decode_builder(cfg)[1](3, 64))
    assert set(caches) == {"full", "state", "conv"}
    assert caches["state"].dtype == jnp.float32
    assert caches["state"].shape == (3, 1, 3, 16, 64)
    assert caches["conv"].dtype == jnp.float32
    # a ring of K = 4 inputs, each 128 channels padded to one (8, 128) tile
    assert caches["conv"].shape == (3, 1, 3, 4, 8, 128)
    assert caches["full"].shape == (1, 2, 3, 64, 64)


INCONSISTENT = {
    "share no stack": dict(layer_types=["latent_attention"] * 3
                           + ["linear_attention"], q_lora_rank=8,
                           kv_lora_rank=8, qk_nope_head_dim=8,
                           qk_rope_head_dim=8, v_head_dim=8),
    "linear_key_head_dim > 0": dict(linear_key_head_dim=0),
    "linear_conv_kernel_dim > 0": dict(linear_conv_kernel_dim=0),
    "linear_num_key_heads .* != linear_num_value_heads": dict(
        linear_num_key_heads=1),
    "need a full_attention layer": dict(
        layer_types=["linear_attention"] * 4),
    "share no stack with sliding_attention": dict(
        layer_types=["linear_attention"] * 2
        + ["sliding_attention", "full_attention"], sliding_window=16),
    "belong to linear_attention": dict(
        layer_types=["full_attention"] * 4),
    "post_norm and sandwich_norm": dict(sandwich_norm=True),
}


@pytest.mark.parametrize("what", sorted(INCONSISTENT))
def test_inconsistent_linear_fields_are_refused_by_name(what):
    with pytest.raises(ValueError, match=what):
        toy_cfg(**INCONSISTENT[what])


@pytest.mark.parametrize("field", ["post_norm", "qk_norm",
                                   "linear_key_head_dim"])
def test_the_new_fields_belong_to_the_gated_stack(field):
    with pytest.raises(ValueError, match="belong to the gated stack"):
        TransformerConfig(**{field: 1})


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_prefill_then_decode_matches_the_reference(cfg, params, settings,
                                                   kernel):
    """Rows of different lengths in one bucket, then decode steps through
    the cache, against the reference's whole-sequence forward."""
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    rng = np.random.default_rng(7)
    lens = np.asarray([5, 33, 64, 20], np.int32)
    steps, bucket = 5, 64
    seqs = rng.integers(0, cfg.vocab_size, (4, bucket + steps), np.int32)
    forced = np.stack([seqs[r, n:n + steps] for r, n in enumerate(lens)])
    caches, lg = prefill(params, init_caches(4, 96),
                         jnp.asarray(seqs[:, :bucket]), jnp.asarray(lens - 1))
    got = [lg]
    for j in range(steps):
        lg, caches = fwd1(params, caches, jnp.asarray(forced[:, j]),
                          jnp.asarray(lens + j))
        got.append(lg)
    got = np.stack(got, axis=1)
    for r, n in enumerate(lens):
        full = np.concatenate([seqs[r, :n], forced[r]])
        row = _reference(params, full[None], settings)[0]
        _close(got[r], row[n - 1:n + steps])


def _recurrence(q, k, v, g, beta, s):
    """The rule token by token, float32 (B, T, H, ...)."""
    out = []
    for t in range(q.shape[1]):
        s = np.exp(g[:, t])[..., None, None] * s
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", s, k[:, t]))
        s = s + k[:, t][..., :, None] * u[..., None, :]
        out.append(np.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return np.stack(out, axis=1), s


def _rule_inputs(seed, t, b=2, h=2, dk=16, dv=32):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((b, t, h, dk))) / 4
    k = unit(rng.standard_normal((b, t, h, dk)))
    v = rng.standard_normal((b, t, h, dv))
    g = -rng.uniform(0.0, 2.0, (b, t, h))
    beta = rng.uniform(0.0, 2.0, (b, t, h))
    s0 = rng.standard_normal((b, h, dk, dv))
    return [x.astype(np.float32) for x in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("t", [1, 63, 64, 65, 130])
def test_the_chunked_form_is_the_token_recurrence(t):
    q, k, v, g, beta, s0 = _rule_inputs(t, t)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    o, s = jax.jit(state_rule_chunked)(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [1, 63, 64, 65, 130])
def test_padding_past_the_last_row_reaches_neither_state(cfg, params, n):
    """A prompt of n tokens in a bucket of 192 (padding after
    ``last_idx``) leaves the state and the convolution's ring an
    exact-length prefill of n leaves."""
    _, init_caches, prefill, _ = _decode_builder(cfg)
    rng = np.random.default_rng(n)
    toks = rng.integers(0, cfg.vocab_size, (1, 192), np.int32)
    exact, lg0 = jax.jit(prefill)(params, init_caches(1, 200),
                                  jnp.asarray(toks[:, :n]))
    padded, lg1 = jax.jit(prefill)(params, init_caches(1, 200),
                                   jnp.asarray(toks), jnp.asarray([n - 1]))
    for leaf in ("state", "conv"):
        _close(padded[leaf], exact[leaf], tol=1e-5)
    _close(lg1, lg0, tol=1e-5)
    # the ring holds the inputs at positions n - 4 .. n - 1, position p
    # at p % 4: none before position 0, and zeros in a position's padding
    ring = np.asarray(exact["conv"])
    if n < 4:
        assert not ring[:, 0, 0, n:].any()
        assert ring[:, 0, 0, :n].any()
    assert not ring[:, 0, 0, :, 1:].any()


def test_forward_chunk_over_three_chunks_is_one_prefill(cfg, params):
    _, init_caches, prefill, _ = _decode_builder(cfg)
    chunk = _chunk_builder(cfg)
    toks = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 90), np.int32)
    whole, lg = prefill(params, init_caches(2, 96), jnp.asarray(toks))
    caches = init_caches(2, 96)
    for a, b in ((0, 30), (30, 64), (64, 90)):
        lgc, caches = chunk(params, caches, jnp.asarray(toks[:, a:b]), a)
    _close(lgc[:, -1], lg, tol=1e-5)
    for leaf in ("state", "conv", "full"):
        _close(caches[leaf], whole[leaf], tol=1e-5)


# -- the decode kernel -------------------------------------------------------


def _kernel_case(seed, b=4, h=2, dk=16, dv=32, alpha=(0.0, 1.0),
                 beta=(0.0, 2.0), layers=2):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    q = unit(rng.standard_normal((b, dk, h))) / 4
    k = unit(rng.standard_normal((b, dk, h)))
    v = rng.standard_normal((b, h * dv))
    a = rng.uniform(*alpha, (b, h))
    be = rng.uniform(*beta, (b, h))
    state = rng.standard_normal((layers, 1, b, dk, h * dv))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, a, be, state)]


REGIMES = {
    "any": dict(),
    "beta near 2": dict(beta=(1.99, 2.0)),
    "alpha near 0": dict(alpha=(0.0, 1e-3)),
    "alpha near 1": dict(alpha=(0.999, 1.0)),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("heads", [2, 4], ids=["h2", "h4"])
def test_linear_decode_is_the_jnp_step(regime, heads):
    q, k, v, a, be, state = _kernel_case(
        len(regime), h=heads, **REGIMES[regime])
    want_o, want_s = linear_decode_dense(q, k, v, a, be, state, layer=1)
    o, s = linear_decode(q, k, v, a, be, state, layer=1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))


def test_linear_decode_steps_by_head_blocks():
    """At dv 64 two heads fill a 128-lane tile: a grid step of 2 of the 4
    heads gives what one of all 4 gives."""
    q, k, v, a, be, state = _kernel_case(9, h=4, dv=64)
    o1, s1 = linear_decode(q, k, v, a, be, state, heads_per_step=4)
    o2, s2 = linear_decode(q, k, v, a, be, state, heads_per_step=2)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
    assert linear_heads_per_step(30, 192, 96) == 10
    assert linear_heads_per_step(2, 32, 16) == 2


@pytest.mark.parametrize("mask", [
    [True, False, True, False], [False, True, True, True],
    [False, False, False, True], [False, False, False, False],
    [True, True, False, False],
], ids=lambda m: "".join("x" if a else "." for a in m))
def test_linear_decode_leaves_a_free_slot_bit_for_bit(mask):
    q, k, v, a, be, state = _kernel_case(3, h=4, dv=64)
    active = jnp.asarray(mask)
    want_o, want_s = linear_decode_dense(q, k, v, a, be, state, layer=0,
                                         active=active)
    for hps in (2, 4):
        o, s = linear_decode(q, k, v, a, be, state, layer=0, active=active,
                             heads_per_step=hps)
        np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                                   rtol=1e-5, atol=1e-5)
        for i, live in enumerate(mask):
            if not live:
                np.testing.assert_array_equal(np.asarray(s[0, 0, i]),
                                              np.asarray(state[0, 0, i]))
                assert not np.asarray(o[i]).any()
        np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(state[1]))


def test_cache_rows_count_the_full_slab_alone(cfg):
    held = [5, 16, 40]
    assert decode_rows_live(cfg, held) == 61
    dense = dataclasses.replace(cfg, decode_kernel=False)
    assert decode_rows_streamed(dense, 4, 96, held) == 4 * 96


# -- the engine --------------------------------------------------------------


def _engine(cfg, params, **kw):
    kw = {"n_slots": 2, "max_total": 64, "batch_admission": False,
          "chunked_replay": False, **kw}
    return ServingEngine(cfg, params, **kw)


def _requests(cfg, lens, seed=5):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new=m)
        for n, m in lens
    ]


def _generated(cfg, params, reqs):
    gen = jax.jit(transformer_generate(cfg),
                  static_argnames=("max_new", "temperature"))
    return [
        np.asarray(gen(params, jnp.asarray(r.prompt[None]), jax.random.key(0),
                       max_new=r.max_new, temperature=0.0))[0, len(r.prompt):]
        for r in reqs
    ]


@pytest.fixture(scope="module")
def served(cfg, params):
    """Three slots, four requests: one waits and takes a freed slot, so
    it starts where another request's state and ring were."""
    engine = _engine(cfg, params, n_slots=3, max_total=128, decode_horizon=4,
                     prefill_max_bucket=32, temperature=0.0,
                     max_queue_depth=4)
    reqs = _requests(cfg, ((5, 9), (40, 18), (70, 12), (33, 7)))
    for r in reqs:
        engine.submit(r)
    while not engine.idle:
        engine.step()
    return engine, reqs


def test_engine_serves_bucketed_and_chunked_prompts_through_the_state(
        cfg, params, served):
    """Greedy streams equal ``transformer_generate``'s, whatever the
    admission path (one bucket, or chunks over the bucket), and the
    request admitted into a freed slot starts from a zero state: the
    insert overwrites the state and the ring."""
    engine, reqs = served
    assert set(engine.pool.caches) == {"full", "state", "conv"}
    assert engine.pool.caches["state"].shape == (3, 1, 3, 16, 64)
    assert engine.pool.caches["state"].dtype == jnp.float32
    for r, want in zip(reqs, _generated(cfg, params, reqs)):
        got = np.asarray(engine.pop_result(r.id))[-r.max_new:]
        np.testing.assert_array_equal(got, want)


def test_engine_books_state_updates_and_the_kind_of_row(cfg, served):
    engine, reqs = served
    s = engine.metrics.summary()
    assert s["kv_cache_rows"] == "kv+state" and "moe_experts_hit" not in s
    assert s["moe_grouped"] == "none"
    # each state update also placed one convolution input in the ring
    assert s["conv_row_write"] == "kernel"
    n_linear = len(cfg.layers_of("linear"))
    assert s["state_slot_updates"] % n_linear == 0
    substeps = s["state_slot_updates"] // n_linear
    assert sum(r.max_new - 1 for r in reqs) <= substeps <= sum(
        r.max_new + 4 for r in reqs)
    text = engine.metrics.render_prometheus()
    assert 'serve_kv_cache_rows{how="kv+state"} 1' in text
    block = 4 * 16 * 64
    assert (f"serve_state_bytes_total {2 * block * s['state_slot_updates']}\n"
            in text)


def test_state_slot_updates_exact_on_a_scripted_batch(cfg, params):
    """One request of 5 + 6 tokens, horizon 2: six substeps with one live
    slot of two, three linear layers each: 18 state blocks advanced.
    The first token is the prefill's, so the six substeps decode the
    other five and one that the budget stops."""
    engine = _engine(cfg, params, max_total=32, decode_horizon=2,
                     prefill_max_bucket=8, temperature=0.0)
    engine.submit(Request(prompt=np.arange(5, dtype=np.int32), max_new=6))
    while not engine.idle:
        engine.step()
    s = engine.metrics.summary()
    assert s["kv_rows_live"] == sum(range(6, 12))
    assert s["state_slot_updates"] == 6 * 3


def test_crash_replay_rebuilds_the_state(cfg, params):
    """An engine crash mid-decode: recovery re-prefills and replays, and
    every stream is the clean one."""
    lens = ((9, 10), (20, 12), (3, 8))
    clean_reqs = _requests(cfg, lens, seed=8)
    want = _generated(cfg, params, clean_reqs)
    reqs = _requests(cfg, lens, seed=8)
    engine = _engine(cfg, params, temperature=0.0, decode_horizon=2,
                     prefill_max_bucket=32, retry_backoff_s=0.001,
                     max_backoff_s=0.004,
                     faults=FaultInjector().plan("step", at=3, kind="crash"))
    for r in reqs:
        engine.submit(r)
    out = engine.run()
    assert engine.metrics.n_restarts == 1
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(np.asarray(out[r.id])[-r.max_new:], w)


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
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_the_hybrid_stack_cannot_do_raises_by_name(cfg, params, what):
    with pytest.raises(NotImplementedError, match=what):
        REFUSED[what](cfg, params)


def test_the_engine_refusals_name_the_state_leaf(cfg, params, served):
    for what in ("paged pool", "prefix cache", "tensor-parallel"):
        with pytest.raises(NotImplementedError, match="state leaf"):
            REFUSED[what](cfg, params)
    engine, _ = served
    with pytest.raises(NotImplementedError, match="KVSG.*state leaf"):
        engine.export_sessions()
    req = Request(prompt=np.zeros(4, np.int32), max_new=1)
    req.kind = "kv_export"
    with pytest.raises(NotImplementedError, match="KVSG.*state leaf"):
        engine.submit(req)
