"""The held experts' SwiGLU streamed by one kernel (``moe_ragged_dot``)
against the three ``lax.ragged_dot`` calls it replaces in a decode step,
and the rule that chooses between them (``models.transformer.moe_grouped``).

On the CPU the kernel is interpreted: these tests hold its numbers and
its control flow (passes, chunks, the block sequence) to the XLA form;
``tests/test_tpu_lowering.py`` compiles it for a v5e at the cells' widths.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _decode_builder,
    init_transformer,
    moe_grouped,
)
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402
from deeplearning4j_tpu.parallel.expert_parallel import (  # noqa: E402
    moe_held_ffn,
)
from deeplearning4j_tpu.serving.engine import build_step_program  # noqa: E402


def _config(name: str) -> dict:
    return json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())


LAGUNA = _config("laguna-s-2.1")
PANGU = _config("openpangu-ultra-moe-718b")


def _cfg(config: dict, toy: bool, **over) -> TransformerConfig:
    model = dict(config["model"])
    if toy:
        model.update(config["rehearse"]["model"])
    model.update(over)
    return TransformerConfig(**model)


def _weights(seed, e, d, f, dtype):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(w, dtype) for w in (
        rng.normal(size=(e, d, f)) / np.sqrt(d),
        rng.normal(size=(e, d, f)) / np.sqrt(d),
        rng.normal(size=(e, f, d)) / np.sqrt(f),
    ))


# -- the kernel against lax.ragged_dot ----------------------------------------


def _ragged(x, wg, wu, wd, sizes):
    a = jax.nn.silu(lax.ragged_dot(x, wg, sizes)) * lax.ragged_dot(
        x, wu, sizes)
    return lax.ragged_dot(a.astype(x.dtype), wd, sizes)


@pytest.mark.parametrize("sizes", [
    [3, 0, 5, 1],  # a few rows, an expert without any
    [0, 0, 0, 0],  # nothing to compute
    [70, 0, 1, 33],  # passes of 32 rows, each streaming the weights again
    [16, 8, 17, 15],  # whole chunks, and one row past one
], ids=["few", "none", "passes", "chunk_edges"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_rows_are_the_ragged_products(sizes, dtype, monkeypatch):
    """Every group's rows in the kernel's layout come out as the three
    ragged products give them (f32: to the float32 sums' order; bf16:
    within one rounding of the output). Blocks of 128 rows, so gate and
    up take two blocks an expert and down three, and the sequence wraps
    the buffers within and across experts."""
    monkeypatch.setattr(pk, "_MOE_BLOCK_BYTES", 1)
    sizes = np.asarray(sizes, np.int32)
    e, d, f = len(sizes), 256, 384
    wg, wu, wd = _weights(1, e, d, f, dtype)
    pairs = int(sizes.sum())
    rows = np.random.default_rng(2).normal(size=(max(pairs, 1), d))
    starts = np.asarray(pk.moe_group_starts(jnp.asarray(sizes)))
    at = np.concatenate([s + np.arange(n) for s, n in zip(starts, sizes)])
    # one layout for every case (room for 128 pairs): one kernel to build
    x = np.zeros((pk.moe_stream_rows(128, e), d))
    x[at.astype(int)] = rows[:pairs]
    got = np.asarray(pk.moe_ragged_dot(
        jnp.asarray(x, dtype), wg, wu, wd, jnp.asarray(sizes)), np.float32)
    want = np.asarray(_ragged(jnp.asarray(rows, dtype), wg, wu, wd,
                              jnp.asarray(sizes)), np.float32)[:pairs]
    got = got[at.astype(int)]
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_allclose(got, want, rtol=0.02, atol=0.02)


def test_block_rows_rule_at_the_cells_widths():
    """Gate and up blocks of 256 rows (1 MiB of both) at Laguna's d
    3,072 and 128 at openPangu's 7,680; down blocks of 128 rows (768 KB
    and 1.97 MB); a width 128 does not divide is one block."""
    assert pk._moe_block_rows(3072, 2 * 1024 * 2) == 256
    assert pk._moe_block_rows(1024, 3072 * 2) == 128
    assert pk._moe_block_rows(7680, 2 * 2048 * 2) == 128
    assert pk._moe_block_rows(2048, 7680 * 2) == 128
    assert pk._moe_block_rows(64, 4) == 64


def test_layout_rows_bound_every_routing():
    """The layout holds any sizes that sum to the pairs: the worst case
    is every group holding rows, each one past a whole chunk."""
    for pairs, groups in ((640, 128), (1792, 8), (5, 16), (1, 1)):
        p = pk.moe_stream_rows(pairs, groups)
        assert p % pk.MOE_CHUNK == 0
        hit = min(groups, pairs)
        worst = np.full(groups, 0)
        worst[:hit] = 1
        worst[0] += pairs - hit
        starts = np.asarray(pk.moe_group_starts(jnp.asarray(worst)))
        assert starts[-1] + worst[-1] <= p
        assert starts[0] + -(-worst[0] // pk.MOE_CHUNK) * pk.MOE_CHUNK <= p


def _layer(seed, n, d, e_total, score, router_fn=None):
    rng = np.random.default_rng(seed)
    h = rng.normal(size=(n, d))
    router = rng.normal(size=(d, e_total)) / np.sqrt(d)
    if router_fn is not None:
        h, router = router_fn(h, router)
    return jnp.asarray(h, jnp.float32), jnp.asarray(router, jnp.float32)


def _one_expert(h, router):
    """Every token's first choice is expert 3 (by about 15)."""
    router = router.copy()
    router[:, 3] = 0.3
    return np.abs(h), router


def _elsewhere(h, router):
    """Every token's four experts lie past the eight held ones."""
    router = router.copy()
    router[:, 8:12] = 0.5
    return np.abs(h), router


LAYERS = {
    # name: (tokens, score, router_fn, live)
    "softmax": (40, "softmax", None, None),
    "sigmoid": (40, "sigmoid", None, None),
    "one_expert_every_pair": (40, "softmax", _one_expert, None),
    "no_pair_held": (40, "sigmoid", _elsewhere, None),
    "live_masked": (40, "softmax", None, [i % 3 != 1 for i in range(40)]),
    "odd_tiles": (64, "sigmoid", None, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LAYERS))
def test_streamed_layer_is_the_ragged_layer(case, dtype):
    """``moe_held_ffn`` with ``grouped="streamed"`` against the ragged
    form, routing included: the same counters, and the same output to
    the float32 sums' order (bf16: within its rounding). Eight of 16
    experts held, top 4, d 64: an expert holds 40 rows when every token
    picks it (more than a pass), none when all go elsewhere; 64 tokens
    make 256 pairs, two row tiles that the ragged form pads to three."""
    n, score, router_fn, live = LAYERS[case]
    d, f, e_total, held, k = 64, 32, 16, 8, 4
    h, router = _layer(3, n, d, e_total, score, router_fn)
    wg, wu, wd = _weights(4, held, d, f, dtype)
    live = None if live is None else jnp.asarray(live)
    out = {}
    for grouped in ("ragged", "streamed"):
        out[grouped] = moe_held_ffn(
            h.astype(dtype), router, wg, wu, wd, first=0, k=k, scale=2.5,
            score=score, live=live, grouped=grouped)
    (y_r, c_r), (y_s, c_s) = out["ragged"], out["streamed"]
    assert c_s.tolist() == c_r.tolist()
    if case == "one_expert_every_pair":
        assert int(c_s[0]) >= n > pk.MOE_UNIT_ROWS
    if case == "no_pair_held":
        assert int(c_s[0]) == 0
        assert not np.asarray(y_s, np.float32).any()
    tol = 1e-5 if dtype == jnp.float32 else 0.03
    np.testing.assert_allclose(np.asarray(y_s, np.float32),
                               np.asarray(y_r, np.float32),
                               rtol=tol, atol=tol)


def test_grouped_names_what_it_takes():
    h, router = _layer(5, 4, 64, 16, "softmax")
    wg, wu, wd = _weights(6, 8, 64, 32, jnp.float32)
    with pytest.raises(ValueError, match="grouped"):
        moe_held_ffn(h, router, wg, wu, wd, first=0, k=4, scale=1.0,
                     grouped="dense")


# -- the rule -------------------------------------------------------------------


@pytest.mark.parametrize("name,config,slots", [
    ("laguna", LAGUNA, 64), ("pangu", PANGU, 224)])
def test_rule_streams_the_cells_decode_and_not_their_prefills(
        name, config, slots):
    """At the cells' widths: the decode step's rows (2.5 and 7 pairs an
    expert over their slots) stream; a prefill or chunk of any bucket
    keeps ``lax.ragged_dot``, and so does the dense path."""
    cfg = _cfg(config, toy=False)
    assert config["engine"]["n_slots"] == slots
    assert moe_grouped(cfg) == moe_grouped(cfg, 1) == "streamed"
    for bucket in (8, 256, 1024, 2048):
        assert moe_grouped(cfg, bucket) == "ragged"
    dense = dataclasses.replace(cfg, decode_kernel=False)
    assert moe_grouped(dense) == "ragged"


def test_rule_says_none_without_routed_layers():
    assert moe_grouped(TransformerConfig()) == "none"
    for name in ("olmo-hybrid-7b", "granite-4.0-h-micro"):
        assert moe_grouped(_cfg(_config(name), toy=True)) == "none"
    # the step-digest script's toy stacks: the step streams, the
    # 64-token prefill and 32-token chunk do not
    for config in (LAGUNA, PANGU):
        cfg = _cfg(config, toy=True)
        assert moe_grouped(cfg, 1) == "streamed"
        assert moe_grouped(cfg, 64) == moe_grouped(cfg, 32) == "ragged"


def _step_jaxpr(cfg, slots):
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
    return str(jax.make_jaxpr(build_step_program(fwd1, 2, 0.0, None, False))(
        *avals))


@pytest.mark.parametrize("config", [LAGUNA, PANGU], ids=["laguna", "pangu"])
def test_toy_step_calls_the_kernel_and_no_ragged_dot(config):
    """A step of the toy stack calls the streamed kernel (one jitted
    function, traced once for all layers and substeps) and no
    ``lax.ragged_dot``; on the dense path it is the other way round."""
    cfg = _cfg(config, toy=True, vocab_size=64, compute_dtype=jnp.float32)
    text = _step_jaxpr(cfg, 3)
    assert "name=_moe_call" in text and "ragged_dot_general" not in text
    assert "name=moe_ragged_dot" in text
    text = _step_jaxpr(dataclasses.replace(cfg, decode_kernel=False), 3)
    assert "_moe_call" not in text and "ragged_dot_general" in text
