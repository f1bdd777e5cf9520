"""The convolution ring of a stack with state layers, and who writes it.

The ring leaf is (state layers, 1, slots, K, R, 128) float32: position p
of a slot at p % K, one position R rows of 128 lanes (the convolution's
channels, zero-padded to whole (8, 128) tiles). A decode step reads the
K - 1 positions before the row, and the state kernel (``linear_decode``,
both rules) places the row itself with one aligned copy a live slot;
``linear_decode_dense`` does the same with a scatter. Prefill and chunk
programs read the ring first, then write their last K real inputs.

The yardsticks are the jnp twin (the ring bit for bit) and the plain
references of ``benchmark/reference`` (logits after prefill, a chunk and
decode steps that wrap the ring), at toy widths for both stacks.
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

from benchmark.reference import granite_hybrid, olmo_hybrid  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    conv_row_write,
    init_transformer,
)
from deeplearning4j_tpu.ops.pallas_kernels import (  # noqa: E402
    linear_decode,
    linear_decode_dense,
)
from deeplearning4j_tpu.serving import ServingEngine  # noqa: E402
from deeplearning4j_tpu.serving.metrics import ServingMetrics  # noqa: E402


def _config(name):
    return json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())


# the two stacks at toy widths: the delta rule's (convolution 128
# channels, one 8-row tile a position) and the additive rule's (160
# channels, two rows padded to 8)
STACKS = {
    "olmo": (olmo_hybrid, dict(
        _config("olmo-hybrid-7b")["model"], vocab_size=97, d_model=64,
        n_heads=2, n_layers=4, d_ff=128, max_len=256,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        dense_layers=[0, 1, 2, 3], linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32)),
    "granite": (granite_hybrid, dict(
        _config("granite-4.0-h-micro")["model"],
        **dict(_config("granite-4.0-h-micro")["rehearse"]["model"],
               vocab_size=97))),
}


def _cfg(stack, **over):
    return TransformerConfig(**dict(STACKS[stack][1], **over,
                                    compute_dtype=jnp.float32))


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request):
    cfg = _cfg(request.param)
    params = _decode_builder(cfg)[3](init_transformer(jax.random.key(43), cfg))
    return request.param, cfg, params


# -- the kernel against its jnp twin ------------------------------------------


def _rule_case(rule, seed, b=5, layers=2, dk=16, h=4, dv=64, rows=8):
    rng = np.random.default_rng(seed)
    keys = h if rule == "delta" else 1
    q = rng.standard_normal((b, dk, keys)) / 8
    k = rng.standard_normal((b, dk, keys)) / 4
    x = dict(
        q=q, k=k, v=rng.standard_normal((b, h * dv)),
        a=rng.uniform(0.0, 1.0, (b, h)),
        be=rng.uniform(0.0, 2.0, (b, h)) if rule == "delta" else None,
        state=rng.standard_normal((layers, 1, b, dk, h * dv)),
        ring=rng.standard_normal((layers, 1, b, 4, rows, 128)),
    )
    return {n: None if a is None else jnp.asarray(a, jnp.float32)
            for n, a in x.items()}


@pytest.mark.parametrize("heads_per_step", [2, 4])
@pytest.mark.parametrize("rule", ["delta", "additive"])
def test_the_kernel_places_the_ring_rows_the_jnp_step_places(rule,
                                                             heads_per_step):
    """Seven decode steps of slots at different positions (each wraps the
    ring of 4 at least once), a different mix of live and idle slots each
    step, on layer 1 and then layer 0: the ring equals the twin's bit for
    bit, the state and output to float32 rounding, and an idle slot's
    ring positions, like every other layer's, are as they were."""
    x = _rule_case(rule, 7)
    b = x["q"].shape[0]
    rng = np.random.default_rng(8)
    pos = jnp.asarray([0, 3, 5, 9, 130], jnp.int32)
    want = (x["state"], x["ring"])
    got = want
    for step in range(7):
        live = jnp.asarray(rng.random(b) < 0.6) if step else jnp.ones(
            (b,), bool)
        row = jnp.asarray(rng.standard_normal((b, 8, 128)), jnp.float32)
        layer = 1 if step < 4 else 0
        kw = dict(layer=layer, active=live, rule=rule, row=row, pos=pos)
        wo, *want = linear_decode_dense(
            x["q"], x["k"], x["v"], x["a"], x["be"], *want[:1],
            ring=want[1], **kw)
        before = np.asarray(got[1])
        go, *got = linear_decode(
            x["q"], x["k"], x["v"], x["a"], x["be"], *got[:1], ring=got[1],
            heads_per_step=heads_per_step, **kw)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(go), np.asarray(wo),
                                   rtol=1e-5, atol=1e-5)
        after = np.asarray(got[1])
        np.testing.assert_array_equal(after[1 - layer], before[1 - layer])
        for i in range(b):
            at = int(pos[i]) % 4
            if live[i]:
                np.testing.assert_array_equal(after[layer, 0, i, at],
                                              np.asarray(row[i]))
            else:
                np.testing.assert_array_equal(after[layer, 0, i],
                                              before[layer, 0, i])
            others = [p for p in range(4) if p != at]
            np.testing.assert_array_equal(after[layer, 0, i, others],
                                          before[layer, 0, i, others])
        pos = jnp.where(live, pos + 1, pos)


def test_no_live_slot_writes_no_ring_row():
    x = _rule_case("delta", 9)
    row = jnp.ones((5, 8, 128), jnp.float32)
    _, _, ring = linear_decode(
        x["q"], x["k"], x["v"], x["a"], x["be"], x["state"], layer=0,
        active=jnp.zeros((5,), bool), ring=x["ring"], row=row,
        pos=jnp.arange(5, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(x["ring"]))


# -- the layout ---------------------------------------------------------------


def test_a_ring_position_is_whole_tiles(stack):
    _, cfg, _ = stack
    caches = jax.eval_shape(lambda: _decode_builder(cfg)[1](3, 64))
    layers, planes, slots, ring, rows, lanes = caches["conv"].shape
    assert (layers, planes, slots) == (len(cfg.layers_of("linear")), 1, 3)
    assert ring == cfg.conv_kernel == 4
    assert lanes == 128 and rows % 8 == 0
    assert rows * 128 - 1024 < cfg.linear_conv_width <= rows * 128


@pytest.mark.parametrize("name, rows", [
    ("olmo-hybrid-7b", 96), ("granite-4.0-h-micro", 40)])
def test_the_cells_rings_are_whole_tiles(name, rows):
    """11,520 channels are 90 rows of 128 (96 in whole tiles), 4,352 are
    34 (40): the cells' rings hold K = 4 positions of those."""
    cfg = TransformerConfig(**dict(_config(name)["model"],
                                   compute_dtype=jnp.bfloat16))
    n = 6 if name.startswith("olmo") else 36
    caches = jax.eval_shape(lambda: _decode_builder(cfg)[1](8, 512))
    assert caches["conv"].shape == (n, 1, 8, 4, rows, 128)


# -- prefill, a chunk, then decode, against the reference ----------------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_prefill_chunk_then_decode_matches_the_reference(stack, kernel):
    """Two rows: a prefill of 64 positions (the second row padded past
    its 41st), a chunk of 64 that carries the state and the ring on (its
    padding past the 30th row), then six decode steps through the ring's
    four positions, against the reference's whole-sequence forward on
    logits."""
    name, cfg, params = stack
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    reference = STACKS[name][0]
    settings = {k: v for k, v in STACKS[name][1].items()
                if k != "compute_dtype"}
    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    chunk = _chunk_builder(cfg)
    rng = np.random.default_rng(44)
    lens = np.asarray([64, 41], np.int32)
    real, steps = 30, 6
    seqs = rng.integers(0, cfg.vocab_size, (2, 64 + real + steps), np.int32)
    caches, _ = prefill(params, init_caches(2, 112), jnp.asarray(seqs[:, :64]),
                        jnp.asarray(lens - 1))
    part = np.zeros((2, 64), np.int32)
    for r, n in enumerate(lens):
        part[r, :real] = seqs[r, n:n + real]
    lg, caches = chunk(params, caches, jnp.asarray(part), jnp.asarray(lens),
                       last_idx=jnp.asarray([real - 1] * 2))
    got = [lg]
    for j in range(steps):
        at = lens + real + j
        lg, caches = fwd1(params, caches,
                          jnp.asarray(seqs[np.arange(2), at]),
                          jnp.asarray(at))
        got.append(lg)
    got = np.stack(got, axis=1)
    for r, n in enumerate(lens):
        full = seqs[r, :n + real + steps]
        want = np.asarray(reference.forward(
            params, jnp.asarray(full[None]), settings=settings))[0]
        want = want[n + real - 1:n + real + steps]
        scale = float(np.max(np.abs(want)))
        assert float(np.max(np.abs(got[r] - want))) <= 2e-5 * scale


# -- the fact the engine reports ----------------------------------------------


def test_who_places_the_ring_row_is_a_fact_of_the_configuration(stack):
    _, cfg, _ = stack
    assert conv_row_write(cfg) == "kernel"
    assert conv_row_write(dataclasses.replace(cfg, decode_kernel=False)) == "xla"
    plain = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_len=64)
    assert conv_row_write(plain) is None


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
def test_the_engine_reports_who_places_the_ring_row(stack, kernel):
    _, cfg, params = stack
    how = "kernel" if kernel else "xla"
    cfg = dataclasses.replace(cfg, decode_kernel=kernel)
    engine = ServingEngine(cfg, params, n_slots=2, max_total=64,
                           temperature=0.0, batch_admission=False,
                           chunked_replay=False)
    assert engine.metrics.summary()["conv_row_write"] == how
    text = engine.metrics.render_prometheus()
    assert f'serve_conv_row_write{{how="{how}"}} 1' in text
    assert "serve_conv_row_write{" + (
        'how="xla"' if kernel else 'how="kernel"') not in text


def test_a_stack_without_state_layers_reports_no_ring():
    assert "conv_row_write" not in ServingMetrics().summary()
