"""The sampler's exact top-k threshold without sorting the vocabulary.

``_top_k_filter`` needs one number of a row, the value of its k-th
largest logit. Where the row is long enough (``topk_select``), it takes
it from a selection by chunks (of 128, then of 8); these tests hold that
selection to ``lax.top_k(x, k)[0][..., -1:]`` bit for bit, show in the
traced step program that no sort of the vocabulary is left, and compare
an engine's sampled stream at such a vocabulary with a reference that
filters by the old expression.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _decode_builder,
    _SELECT_CHUNKS,
    _kth_largest,
    _top_k_filter,
    init_transformer,
    topk_select,
)
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.serving.engine import build_step_program

# the shape rule's edge at k = 40: k * 128 * 4
EDGE = 40 * 128 * 4


def _rows(kind: str, shape, k: int) -> np.ndarray:
    rng = np.random.default_rng(zlib.crc32(repr((kind, shape, k)).encode()))
    x = rng.standard_normal(shape).astype(np.float32) * 3.0
    if kind == "ties":
        # quarter steps: dozens of equal values at the threshold
        x = np.round(x * 4) / 4
    elif kind == "few_finite":
        # fewer than k finite entries a row: the threshold is -inf
        keep = rng.random(shape) < (0.5 * k / shape[-1])
        x = np.where(keep, x, -np.inf).astype(np.float32)
    elif kind == "bf16":
        x = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    elif kind == "one_chunk":
        # everything that matters sits in one chunk of 128
        x[..., 256:384] += 100.0
    else:
        assert kind == "normal"
    return x


def _old_kth(x, k):
    return lax.top_k(x, k)[0][..., -1:]


def _old_filter(x, k):
    """The parent's expression."""
    return jnp.where(x < _old_kth(x, k), -jnp.inf, x)


@pytest.mark.parametrize("kind", [
    "normal", "ties", "few_finite", "bf16", "one_chunk",
])
@pytest.mark.parametrize("k", [1, 40, 64])
@pytest.mark.parametrize("shape", [
    (48, 50257), (64, 50176), (3, EDGE), (3, EDGE + 1), (2, 3, 33333),
])
def test_chunked_threshold_equals_top_k(shape, k, kind):
    """Bit for bit, whatever the rule would choose at this size: the
    selection is exact wherever it has k chunks to pick from."""
    x = jnp.asarray(_rows(kind, shape, k))
    got = jax.jit(_kth_largest, static_argnums=(1, 2))(
        x, k, _SELECT_CHUNKS
    )
    assert got.shape == shape[:-1] + (1,)
    assert np.array_equal(np.asarray(got), np.asarray(_old_kth(x, k)))


@pytest.mark.parametrize("vocab,k,how", [
    (50257, 40, "chunked"), (50176, 40, "chunked"), (EDGE, 40, "chunked"),
    (EDGE - 1, 40, "chunked"), (19200, 40, "chunked"), (10240, 40, "chunked"),
    (10239, 40, "sort"), (64, 40, "sort"), (50257, 64, "chunked"),
    (50257, 128, "chunked"), (50257, 256, "sort"), (512, 1, "chunked"),
    (511, 1, "chunked"), (255, 1, "sort"), (50257, None, "none"),
])
def test_topk_select_rule(vocab, k, how):
    assert topk_select(vocab, k) == how
    if k is not None:
        assert topk_select(vocab, k, approx_top_k=True) == "approx"


@pytest.mark.parametrize("vocab,k,chunks", [
    (50257, 40, (128, 8)), (EDGE, 40, (128, 8)), (EDGE - 1, 40, (64, 8)),
    (19200, 40, (64, 8)), (10240, 40, (64, 8)), (10239, 40, ()),
])
def test_first_chunk_level_narrows_with_the_row(vocab, k, chunks):
    """128, else 64, where k chunks of that width are a quarter of the
    row at most: the rows the benchmark serves (50,257 and 50,176 ids)
    keep chunks of 128, a 19,200-id slice of a vocabulary takes 64."""
    from deeplearning4j_tpu.models.transformer import select_chunks

    assert select_chunks(vocab, k) == chunks
    assert _SELECT_CHUNKS == (128, 8)


@pytest.mark.parametrize("kind", ["normal", "ties", "few_finite", "bf16"])
@pytest.mark.parametrize("vocab", [50257, EDGE, EDGE - 1, 5121])
def test_filter_equals_parent_expression(vocab, kind):
    """``_top_k_filter`` on both sides of the rule gives the parent's
    filtered logits, ``-inf`` for ``-inf``."""
    x = jnp.asarray(_rows(kind, (5, vocab), 40))
    got = jax.jit(lambda a: _top_k_filter(a, 40, False))(x)
    assert np.array_equal(np.asarray(got), np.asarray(_old_filter(x, 40)))
    assert _top_k_filter(x, None, False) is x


def _sorts_of_width(jaxpr, width: int) -> int:
    """``sort`` / ``top_k`` equations, nested jaxprs included, whose
    operand's last dimension is ``width``."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("sort", "top_k", "approx_top_k"):
            n += eqn.invars[0].aval.shape[-1] == width
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _sorts_of_width(sub, width)
    return n


def _step_jaxpr(vocab: int, horizon: int = 2):
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=16,
    )
    forward_one, init_caches, _, _ = _decode_builder(cfg)
    step = build_step_program(forward_one, horizon, 1.0, 40, False)
    slots = 2
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), cfg)
    )
    keys = jax.random.key_data(jax.random.split(jax.random.key(0), slots))
    return jax.make_jaxpr(step)(
        params, jax.eval_shape(lambda: init_caches(slots, cfg.max_len)),
        jnp.zeros((slots, vocab), jnp.float32),
        jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool),
        jnp.full((slots,), 4, jnp.int32), jnp.full((slots,), -1, jnp.int32),
        keys, jnp.zeros((slots,), jnp.int32),
    ).jaxpr


def test_step_program_sorts_no_vocabulary():
    """The traced step at GPT-2's vocabulary holds no sort of a row of
    it: per substep one of each level's chunk maxima (393 chunks of 128,
    then 640 of 8) and one of the 320 candidates left. At a toy
    vocabulary the old one is still there."""
    big = _step_jaxpr(50257)
    assert _sorts_of_width(big, 50257) == 0
    for width in (393, 40 * 128 // 8, 40 * 8):
        assert _sorts_of_width(big, width) == 2
    assert _sorts_of_width(_step_jaxpr(64), 64) == 2


@pytest.mark.parametrize("vocab,how", [(EDGE, "chunked"), (64, "sort")])
def test_engine_reports_the_selection(vocab, how):
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=16,
    )
    params = init_transformer(jax.random.key(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=2, temperature=1.0, top_k=40)
    assert eng.metrics.summary()["topk_select"] == how
    line = f'serve_topk_select{{how="{how}"}} 1'
    assert line in eng.metrics.registry.render()


def test_sampled_stream_equals_reference_with_the_old_filter():
    """One sampled request through the engine at a vocabulary that takes
    the chunked path: its tokens are those a ``transformer_generate``
    -style loop draws with the engine's keys (``fold_in(slot key,
    position)``) from logits filtered by the parent's expression."""
    cfg = TransformerConfig(
        vocab_size=EDGE + 77, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32,
    )
    params = init_transformer(jax.random.key(3), cfg)
    prompt = np.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (7,)), np.int32
    )
    max_new, temperature, seed = 12, 0.8, 11
    eng = ServingEngine(
        cfg, params, n_slots=2, temperature=temperature, top_k=40,
        decode_horizon=2, rng_seed=seed,
    )
    assert eng.metrics.summary()["topk_select"] == "chunked"
    req = Request(prompt=prompt, max_new=max_new)
    eng.submit(req)
    eng.run()
    got = np.asarray(eng.results[req.id])[len(prompt):]

    forward_one, init_caches, do_prefill, cast_params = _decode_builder(cfg)
    # the first admission's slot key: one split of the engine's master key
    slot_key = jax.random.split(jax.random.key(seed))[1]
    served = cast_params(params)
    caches, logits = do_prefill(
        served, init_caches(1, cfg.max_len), jnp.asarray(prompt[None])
    )
    want = []
    for i in range(max_new):
        pos = len(prompt) + i
        filt = _old_filter(logits.astype(jnp.float32), 40)
        tok = jax.random.categorical(
            jax.random.fold_in(slot_key, pos), filt[0] / temperature
        ).astype(jnp.int32)
        want.append(int(tok))
        logits, caches = forward_one(served, caches, tok[None], pos)
    assert got.tolist() == want
