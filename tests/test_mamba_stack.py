"""Mamba-2 layers in the gated stack (``layer_types`` with ``mamba`` and
``attention``, Granite-4.0-H's): the additive rule, whose float32 state
shares the linear layers' state leaf beside the attention layers' K/V slab,
NoPE GQA, the muP multipliers and a tied head.

The yardstick is ``benchmark/reference/granite_hybrid.py`` (plain float32,
the token-by-token recurrence, imports nothing from the program), at a toy
of d 64, three mamba layers and one attention layer, 8 heads of 16, d_state
16, 4 query heads over 2 KV heads and a vocabulary of 97.
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

from benchmark.reference import granite_hybrid  # noqa: E402
from deeplearning4j_tpu.analysis.programs import ServingGeometry  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    init_transformer,
    kv_cache_rows,
    min_bucket,
    state_rule_chunked,
    transformer_generate,
)
from deeplearning4j_tpu.ops.pallas_kernels import (  # noqa: E402
    linear_decode,
    linear_decode_dense,
)
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402
from deeplearning4j_tpu.serving.faults import FaultInjector  # noqa: E402
from deeplearning4j_tpu.serving.scheduler import Request  # noqa: E402

CONFIG = json.loads(
    (ROOT / "benchmark/configs/granite-4.0-h-micro.json").read_text())
TOY = dict(CONFIG["rehearse"]["model"], vocab_size=97)


def toy_model(**over) -> dict:
    model = dict(CONFIG["model"])
    model.update(TOY)
    model.update(over)
    return model


def toy_cfg(**over) -> TransformerConfig:
    return TransformerConfig(**dict(toy_model(**over),
                                    compute_dtype=jnp.float32))


def _shaken(cfg, seed):
    """Served weights with norm scales away from 1 and the skip D away
    from 1, so that a norm or a skip left out or misplaced shows."""
    tree = _decode_builder(cfg)[3](init_transformer(jax.random.key(seed), cfg))
    rng = np.random.default_rng(seed)

    def shake(path, a):
        name = str(getattr(path[-1], "key", ""))
        if name.endswith("_scale") or name == "D":
            return a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, tree)


@pytest.fixture(scope="module")
def cfg():
    return toy_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return _shaken(cfg, 40)


def _settings(**over):
    return {k: v for k, v in toy_model(**over).items() if k != "compute_dtype"}


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


# -- the configuration --------------------------------------------------------


def test_config_fields_freeze_and_round_trip(cfg):
    assert cfg.gated and cfg.linear and cfg.mamba and not cfg.latent
    assert cfg.layers_of("linear") == (0, 1, 3)
    assert cfg.layers_of("full") == (2,)
    assert (cfg.state_rows, cfg.state_heads, cfg.state_head_dim) == (16, 8, 16)
    assert cfg.linear_state_width == 128 and cfg.linear_conv_width == 160
    hash(cfg)
    assert TransformerConfig.from_json(cfg.to_json()) == cfg
    assert kv_cache_rows(cfg) == "kv+state"
    full = TransformerConfig(**dict(CONFIG["model"],
                                    compute_dtype=jnp.bfloat16))
    assert full.linear_state_width == 4096 and full.state_rows == 128
    assert full.linear_conv_width == 4352  # x | B | C
    assert full.layers_of("linear") == tuple(
        l for l in range(40) if l not in (5, 15, 25, 35))


def test_the_published_widths_build_whole():
    """All 40 layers at every published width, the whole vocabulary, a
    tied head: the shapes of the served tree and of the slot pool, no
    arrays made."""
    cfg = TransformerConfig(**dict(CONFIG["model"],
                                   compute_dtype=jnp.bfloat16))
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    tree = jax.eval_shape(
        lambda: cast(init_transformer(jax.random.key(0), cfg)))
    assert "head" not in tree
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    assert abs(n - 3_191.4e6) < 0.1e6
    m = tree["layers"][0]
    assert m["w_in"].shape == (2048, 8512)
    assert m["conv_w"].shape == (4, 4352) and m["conv_b"].shape == (4352,)
    assert m["wo"].shape == (64, 64, 2048) and m["A_log"].dtype == jnp.float32
    a = tree["layers"][5]
    assert a["wq"].shape == (2048, 32, 64)
    assert a["wkv"].shape == (2048, 2, 8, 64)
    caches = jax.eval_shape(lambda: init_caches(64, 3072))
    assert caches["state"].shape == (36, 1, 64, 128, 4096)
    assert caches["state"].dtype == jnp.float32
    # a ring of K = 4 inputs, each 4,352 channels padded to 40 x 128
    assert caches["conv"].shape == (36, 1, 64, 4, 40, 128)
    assert caches["full"].shape == (4, 2, 64, 3072, 512)


def test_accepted_configurations_are_unchanged():
    """The new fields' neutral defaults: every accepted configuration
    file's model builds with them as it did, and a tree without the
    multipliers keeps its ``head`` leaf and its rotary."""
    for name in ("laguna-s-2.1", "olmo-hybrid-7b",
                 "openpangu-ultra-moe-718b"):
        model = json.loads(
            (ROOT / f"benchmark/configs/{name}.json").read_text())["model"]
        cfg = TransformerConfig(**dict(model, compute_dtype=jnp.bfloat16))
        assert not cfg.mamba and cfg.rope and not cfg.tie_word_embeddings
        assert (cfg.embedding_multiplier, cfg.residual_multiplier,
                cfg.attention_multiplier, cfg.logits_scaling) == (
                    1.0, 1.0, None, 1.0)
        assert cfg.mamba_n_heads == cfg.mamba_d_state == 0
        tree = jax.eval_shape(lambda c=cfg: init_transformer(
            jax.random.key(0), c))
        assert "head" in tree


INCONSISTENT = {
    "mamba_d_state > 0": dict(mamba_d_state=0),
    "mamba_n_groups .* must divide": dict(mamba_n_groups=3),
    "must be mamba_expand x d_model": dict(mamba_d_head=8),
    "mamba layers need a full_attention layer": dict(
        layer_types=["mamba"] * 4),
    "mamba layers share no stack with linear_attention": dict(
        layer_types=["mamba", "linear_attention", "attention", "mamba"],
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=16, linear_value_head_dim=32,
        linear_conv_kernel_dim=4),
    "mamba layers share no stack with sliding_attention": dict(
        layer_types=["mamba", "sliding_attention", "attention", "mamba"],
        sliding_window=16, rope=True),
    "belong to mamba layers": dict(
        layer_types=["attention"] * 4),
    "rope=False .* full layers only": dict(
        layer_types=["sliding_attention", "attention"] * 2,
        sliding_window=16, mamba_n_heads=0, mamba_d_head=0, mamba_d_state=0,
        mamba_n_groups=0, mamba_d_conv=0, mamba_expand=0,
        mamba_conv_bias=False),
}


@pytest.mark.parametrize("what", sorted(INCONSISTENT))
def test_inconsistent_mamba_fields_are_refused_by_name(what):
    with pytest.raises(ValueError, match=what):
        toy_cfg(**INCONSISTENT[what])


@pytest.mark.parametrize("field, value", [
    ("mamba_d_state", 16), ("mamba_conv_bias", True),
    ("embedding_multiplier", 12.0), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.015625), ("logits_scaling", 8.0),
    ("tie_word_embeddings", True),
])
def test_the_new_fields_belong_to_the_gated_stack(field, value):
    with pytest.raises(ValueError, match="belong to the gated stack"):
        TransformerConfig(**{field: value})


def test_grouped_keys_stay_refused_for_the_delta_rule():
    hybrid = json.loads(
        (ROOT / "benchmark/configs/olmo-hybrid-7b.json").read_text())
    model = dict(hybrid["model"], **hybrid["rehearse"]["model"])
    model["linear_num_key_heads"] = 1
    with pytest.raises(ValueError, match="grouped keys are built for mamba"):
        TransformerConfig(**model)


# -- the additive rule's chunked form ----------------------------------------


def _recurrence(q, k, v, g, s):
    """The additive rule token by token, float32: q, k (B, T, G, dk), v
    (B, T, H, dv), g (B, T, H), s (B, H, dk, dv)."""
    per = v.shape[2] // q.shape[2]
    q, k = np.repeat(q, per, axis=2), np.repeat(k, per, axis=2)
    out = []
    for t in range(q.shape[1]):
        s = (np.exp(g[:, t])[..., None, None] * s
             + k[:, t][..., :, None] * v[:, t][..., None, :])
        out.append(np.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return np.stack(out, axis=1), s


def _ssd_inputs(seed, t, b=2, groups=1, h=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, t, groups, dk)) / 4
    k = rng.standard_normal((b, t, groups, dk)) / 4
    v = rng.standard_normal((b, t, h, dv))
    g = -rng.uniform(0.0, 0.5, (b, t, h))
    s0 = rng.standard_normal((b, h, dk, dv))
    return [x.astype(np.float32) for x in (q, k, v, g, s0)]


@pytest.mark.parametrize("t, chunk, groups", [
    (1, 16, 1), (15, 16, 1), (16, 16, 1), (17, 16, 1), (50, 16, 1),
    (50, 16, 2), (33, 8, 4), (64, 256, 1),
], ids=lambda x: str(x))
def test_the_additive_chunked_form_is_the_token_recurrence(t, chunk, groups):
    """Across chunk boundaries, with the last chunk padded, from a
    carried-in state, with one key group or several."""
    q, k, v, g, s0 = _ssd_inputs(t, t, groups=groups)
    want_o, want_s = _recurrence(q, k, v, g, s0)
    o, s = jax.jit(lambda *a: state_rule_chunked(
        *a[:4], None, a[4], chunk=chunk, rule="additive"))(q, k, v, g, s0)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-4, atol=2e-4)


def test_padding_rows_with_zero_decay_and_value_leave_the_state():
    q, k, v, g, s0 = _ssd_inputs(3, 40)
    v[:, 25:], g[:, 25:] = 0.0, 0.0
    _, s_pad = state_rule_chunked(q, k, v, g, None, s0, chunk=16,
                                  rule="additive")
    _, s_cut = state_rule_chunked(q[:, :25], k[:, :25], v[:, :25],
                                  g[:, :25], None, s0, chunk=16,
                                  rule="additive")
    np.testing.assert_allclose(np.asarray(s_pad), np.asarray(s_cut),
                               rtol=1e-5, atol=1e-5)


# -- the system against the reference ----------------------------------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_prefill_then_decode_matches_the_reference(cfg, params, kernel):
    """Rows of different lengths in one bucket, then decode steps through
    the cache (``ssd_decode`` and the walk kernel, or their jnp twins),
    against the reference's whole-sequence forward."""
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
        row = granite_hybrid.forward(params, full[None],
                                     settings=_settings())[0]
        _close(got[r], row[n - 1:n + steps])


def test_a_chunk_after_prefill_then_decode_matches_the_reference(cfg, params):
    """A prompt longer than the bucket: a prefill of 64, a chunk program
    that carries the state and the ring on (padding after its last real
    row), then decode, against the reference on logits."""
    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    chunk = _chunk_builder(cfg)
    toks = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (1, 100 + 4), np.int32)
    caches, _ = prefill(params, init_caches(1, 128), jnp.asarray(toks[:, :64]))
    part = np.zeros((1, 64), np.int32)
    part[0, :36] = toks[0, 64:100]
    lg, caches = chunk(params, caches, jnp.asarray(part), 64,
                       last_idx=jnp.asarray([35]))
    got = [lg]
    for j in range(4):
        lg, caches = fwd1(params, caches, jnp.asarray(toks[:, 100 + j]),
                          jnp.asarray([100 + j]))
        got.append(lg)
    want = granite_hybrid.forward(params, toks, settings=_settings())[0]
    _close(np.concatenate(got), want[99:104])


@pytest.mark.parametrize("what", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "groups",
])
def test_each_multiplier_and_the_groups_reach_the_logits(what):
    """Each of the four multipliers, and two key groups in place of one,
    changes the logits, and the program follows the reference there:
    neither side may drop it."""
    over = {"embedding_multiplier": {"embedding_multiplier": 3.0},
            "residual_multiplier": {"residual_multiplier": 0.5},
            "attention_multiplier": {"attention_multiplier": 0.5},
            "logits_scaling": {"logits_scaling": 2.0},
            "groups": {"mamba_n_groups": 2}}[what]
    base_cfg, cfg = toy_cfg(), toy_cfg(**over)
    params = _shaken(cfg, 41)
    base = _shaken(base_cfg, 41)
    toks = np.random.default_rng(5).integers(0, 97, (1, 40), np.int32)
    _, _, prefill, _ = _decode_builder(cfg)
    _, lg = prefill(params, _decode_builder(cfg)[1](1, 48), jnp.asarray(toks))
    want = granite_hybrid.forward(params, toks, settings=_settings(**over))
    _close(lg[0], want[0, -1])
    other = granite_hybrid.forward(base, toks, settings=_settings())
    assert float(np.max(np.abs(want[0, -1] - other[0, -1]))) > 1e-3


def test_the_tied_head_is_the_embedding(cfg, params):
    """No ``head`` leaf: the logits move with the embedding table."""
    assert "head" not in params
    _, init_caches, prefill, _ = _decode_builder(cfg)
    toks = jnp.asarray(np.arange(12, dtype=np.int32)[None])
    _, lg = prefill(params, init_caches(1, 16), toks)
    bumped = dict(params, embed=params["embed"].at[50].multiply(3.0))
    _, lg2 = prefill(bumped, init_caches(1, 16), toks)
    moved = np.nonzero(np.abs(np.asarray(lg2 - lg)[0]) > 1e-6)[0]
    assert 50 in moved


@pytest.mark.parametrize("n", [1, 15, 64])
def test_padding_past_the_last_row_reaches_neither_state(cfg, params, n):
    _, init_caches, prefill, _ = _decode_builder(cfg)
    toks = np.random.default_rng(n).integers(
        0, cfg.vocab_size, (1, 128), np.int32)
    exact, lg0 = jax.jit(prefill)(params, init_caches(1, 136),
                                  jnp.asarray(toks[:, :n]))
    padded, lg1 = jax.jit(prefill)(params, init_caches(1, 136),
                                   jnp.asarray(toks), jnp.asarray([n - 1]))
    for leaf in ("state", "conv"):
        _close(padded[leaf], exact[leaf], tol=1e-5)
    _close(lg1, lg0, tol=1e-5)


# -- the decode kernel -------------------------------------------------------


def _kernel_case(seed, b=4, groups=1, h=8, dk=16, dv=16, layers=2):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, dk, groups))
    k = rng.standard_normal((b, dk, groups))
    v = rng.standard_normal((b, h * dv))
    a = rng.uniform(0.0, 1.0, (b, h))
    state = rng.standard_normal((layers, 1, b, dk, h * dv))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, a, state)]


@pytest.mark.parametrize("groups, heads_per_step", [
    (1, None), (1, 8), (2, 8), (4, 16),
], ids=lambda x: str(x))
def test_ssd_decode_is_the_jnp_step(groups, heads_per_step):
    q, k, v, a, state = _kernel_case(groups, groups=groups, h=16)
    want_o, want_s = linear_decode_dense(q, k, v, a, None, state, layer=1,
                                         rule="additive")
    o, s = linear_decode(q, k, v, a, None, state, layer=1, rule="additive",
                         heads_per_step=heads_per_step)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))


def test_the_jnp_step_is_the_rule():
    q, k, v, a, state = _kernel_case(5, groups=2, h=4, dk=8, dv=8)
    o, s = linear_decode_dense(q, k, v, a, None, state, layer=0,
                               rule="additive")
    s0 = np.asarray(state[0, 0]).reshape(4, 8, 4, 8).transpose(0, 2, 1, 3)
    kk = np.repeat(np.asarray(k).transpose(0, 2, 1), 2, axis=1)  # (B, H, dk)
    qq = np.repeat(np.asarray(q).transpose(0, 2, 1), 2, axis=1)
    vv = np.asarray(v).reshape(4, 4, 8)
    want = (np.asarray(a)[..., None, None] * s0
            + kk[..., :, None] * vv[..., None, :])
    np.testing.assert_allclose(
        np.asarray(s[0, 0]).reshape(4, 8, 4, 8).transpose(0, 2, 1, 3), want,
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(o).reshape(4, 4, 8),
        np.einsum("bhkv,bhk->bhv", want, qq), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mask", [
    [True, False, True, False], [False, True, True, True],
    [False, False, False, True], [False, False, False, False],
], ids=lambda m: "".join("x" if a else "." for a in m))
def test_ssd_decode_leaves_a_free_slot_bit_for_bit(mask):
    q, k, v, a, state = _kernel_case(3, h=16)
    active = jnp.asarray(mask)
    want_o, want_s = linear_decode_dense(q, k, v, a, None, state, layer=0,
                                         active=active, rule="additive")
    for hps in (8, 16):
        o, s = linear_decode(q, k, v, a, None, state, layer=0, active=active,
                             heads_per_step=hps, rule="additive")
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


def test_a_rule_and_its_beta_must_agree():
    q, k, v, a, state = _kernel_case(1)
    with pytest.raises(ValueError, match="'additive' none"):
        linear_decode(q, k, v, a, a, state, rule="additive")
    with pytest.raises(ValueError, match="'delta' takes a beta"):
        linear_decode(q, k, v, a, None, state)


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
    """Three slots, four requests: one prompt takes a prefill and a chunk,
    and one waits for a freed slot, so it starts where another request's
    state and ring were."""
    engine = _engine(cfg, params, n_slots=3, max_total=128, decode_horizon=4,
                     prefill_max_bucket=32, temperature=0.0,
                     max_queue_depth=4)
    reqs = _requests(cfg, ((5, 9), (40, 18), (70, 12), (33, 7)))
    for r in reqs:
        engine.submit(r)
    while not engine.idle:
        engine.step()
    out = {r.id: np.asarray(engine.pop_result(r.id))[-r.max_new:]
           for r in reqs}
    return engine, reqs, out


def test_engine_serves_bucketed_and_chunked_prompts_through_the_state(
        cfg, params, served):
    engine, reqs, out = served
    assert set(engine.pool.caches) == {"full", "state", "conv"}
    assert engine.pool.caches["state"].shape == (3, 1, 3, 16, 128)
    assert engine.pool.caches["state"].dtype == jnp.float32
    for r, want in zip(reqs, _generated(cfg, params, reqs)):
        np.testing.assert_array_equal(out[r.id], want)


def test_engine_streams_are_the_reference_s_greedy_choice(params, served):
    """Through ``ServingEngine`` (a prefill, a prefill and chunks, decode,
    a reused slot): every token the engine chose greedily is the largest
    of the reference's logits at its position, wherever the reference's
    top two are apart by more than the program's error."""
    _, reqs, out = served
    checked = 0
    for r in reqs:
        got = out[r.id]
        full = np.concatenate([r.prompt, got])[None]
        want = granite_hybrid.forward(params, full, settings=_settings())[0]
        for j, tok in enumerate(got):
            row = want[len(r.prompt) - 1 + j]
            top2 = np.sort(row)[-2:]
            if top2[1] - top2[0] > 1e-4 * float(np.max(np.abs(row))):
                assert int(np.argmax(row)) == int(tok)
                checked += 1
    assert checked >= 30


def test_engine_books_mamba_state_updates(cfg, served):
    engine, reqs, _ = served
    s = engine.metrics.summary()
    assert s["kv_cache_rows"] == "kv+state" and "moe_experts_hit" not in s
    # each state update also placed one convolution input in the ring
    assert s["conv_row_write"] == "kernel"
    assert s["state_slot_updates"] % 3 == 0  # three mamba layers
    substeps = s["state_slot_updates"] // 3
    assert sum(r.max_new - 1 for r in reqs) <= substeps <= sum(
        r.max_new + 4 for r in reqs)
    text = engine.metrics.render_prometheus()
    block = 4 * 16 * 128  # d_state x heads x d_head, float32
    assert (f"serve_state_bytes_total {2 * block * s['state_slot_updates']}\n"
            in text)


def test_the_smallest_bucket_is_one_chunk_of_the_chunked_form(cfg, params):
    """A stack with state layers pads a prompt to one chunk of the
    chunked form at least (Mamba-2's 256, the delta rule's 64), clipped
    to the largest bucket, in the engine and in the program registry;
    the streams are the reference's."""
    assert min_bucket(cfg) == 256
    assert min_bucket(TransformerConfig(vocab_size=97, d_model=32,
                                        n_heads=4, n_layers=2)) == 8
    assert ServingGeometry(max_total=2048, prefill_max_bucket=1024).buckets(
        toy_cfg(max_len=2048)) == [256, 512, 1024]
    lens = ((5, 6), (40, 7), (70, 5))
    want = _generated(cfg, params, _requests(cfg, lens, seed=9))
    engine = _engine(cfg, params, n_slots=3, max_total=128, decode_horizon=2,
                     prefill_max_bucket=32, temperature=0.0)
    assert engine._bucket_for(5) == 32
    assert engine._chunk_schedule(70) == [(0, 32, 32), (32, 32, 32),
                                          (64, 6, 32)]
    reqs = _requests(cfg, lens, seed=9)
    for r in reqs:
        engine.submit(r)
    out = engine.run()
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(np.asarray(out[r.id])[-r.max_new:], w)


def test_crash_replay_rebuilds_the_state(cfg, params):
    """An engine crash mid-decode: recovery re-prefills and replays, and
    every stream is the clean one."""
    lens = ((9, 10), (20, 12), (3, 8))
    want = _generated(cfg, params, _requests(cfg, lens, seed=8))
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
