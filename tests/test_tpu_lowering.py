"""Cross-lowering: every Pallas entry point and the serving programs that
carry one must LOWER for the TPU, compiled (``interpret=False``), on the
CPU runner.

Interpret mode accepts block shapes the TPU lowering refuses (PR 1's
``(1, 1)`` position block of a ``(B, 1)`` array lived 20 PRs that way),
so interpret-mode parity tests cannot stand in for this. Lowering is the
cheap half — Mosaic's own compile (VMEM limit, tiling) needs the chip,
which is what ``chip_smoke.py`` is for.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.analysis.programs import (
    ServingGeometry,
    enumerate_programs,
)
from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_loss,
    transformer_shardings,
)
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.parallel.mesh import batch_sharding, dp_mp_mesh

S = jax.ShapeDtypeStruct

# the GPT-2s-GQA serving geometry (bench.py's serve rows, chip_smoke.py)
SERVE_CFG = TransformerConfig(
    vocab_size=50304, d_model=768, n_heads=6, n_kv_heads=2, n_layers=12,
    d_ff=3072, max_len=577, rope=True, use_flash=True,
    compute_dtype=jnp.bfloat16, decode_kernel=True,
)
SERVE_GEOM = ServingGeometry(
    n_slots=16, max_total=577, decode_horizon=4, adaptive_horizon=False,
    prefill_max_bucket=128, paged=True, block_size=8,
)


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Kernels built with ``interpret=None`` take the compiled path, as
    they do on the chip."""
    monkeypatch.setattr(pk, "_default_interpret", lambda: False)


def lower_tpu(fn, *avals) -> str:
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the lowering"
    return text


def test_flash_forward_and_backward_lower():
    q = S((2, 6, 1024, 128), jnp.bfloat16)

    def fwd(q, k, v):
        return pk.flash_attention_trainable(
            q, k, v, causal=True, block_q=512, block_k=512, layout="bhtd",
        )

    lower_tpu(fwd, q, q, q)
    lower_tpu(
        jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)),
        q, q, q,
    )


@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("int8", [False, True])
def test_slab_decode_lowers(batch, int8):
    layers, t, n_kv, hk = 2, 584, 2, 256
    avals = [
        S((batch, 3, hk), jnp.bfloat16),
        S((layers, 2, batch, t, hk), jnp.int8 if int8 else jnp.bfloat16),
        None,
    ]
    if int8:
        avals.append(S((layers, 2, batch, t, 1), jnp.float32))
    # per-slot positions (serving), then a scalar (generate/beam)
    for pos_shape in ((batch,), ()):
        avals[2] = S(pos_shape, jnp.int32)
        lower_tpu(
            lambda q, c, pos, sc=None: pk.flash_decode_attention(
                q, c, pos, n_kv, layer=1, kv_scales=sc
            ),
            *avals,
        )


@pytest.mark.parametrize("masked", [False, True], ids=["all_active", "mask"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_bounded_decode_lowers_at_the_benchmark_geometry(int8, masked):
    """The gpt2-large serve slab (48 slots x 1,024 rows x 1,280), where
    the block rule walks several blocks a slot, with and without the
    per-row active mask the step families pass."""
    layers, batch, t, n_kv, hk = 2, 48, 1024, 20, 1280
    assert pk.decode_block_rows(t, hk, 1 if int8 else 2) < t
    avals = [
        S((batch, 1, hk), jnp.bfloat16),
        S((layers, 2, batch, t, hk), jnp.int8 if int8 else jnp.bfloat16),
        S((batch,), jnp.int32),
        S((batch,), jnp.bool_),
    ]
    if int8:
        avals.append(S((layers, 2, batch, t, 1), jnp.float32))
    lower_tpu(
        lambda q, c, pos, act, sc=None: pk.flash_decode_attention(
            q, c, pos, n_kv, layer=1, kv_scales=sc,
            active=act if masked else None,
        ),
        *avals,
    )


@pytest.mark.parametrize("block_size", [8, 16, 128])
@pytest.mark.parametrize("int8", [False, True])
def test_paged_decode_lowers(block_size, int8):
    layers, batch, n_blocks, bps, n_kv, hk = 2, 16, 64, 5, 2, 256
    avals = [
        S((batch, 3, hk), jnp.bfloat16),
        S((layers, 2, n_blocks, block_size, hk),
          jnp.int8 if int8 else jnp.bfloat16),
        S((batch, bps), jnp.int32),
        S((batch,), jnp.int32),
    ]
    if int8:
        avals.append(S((layers, 2, n_blocks, block_size, 1), jnp.float32))
    lower_tpu(
        lambda q, b, tbl, pos, sc=None: pk.flash_decode_attention_paged(
            q, b, tbl, pos, n_kv, layer=1, block_scales=sc
        ),
        *avals,
    )


def _lower_spec(spec) -> str:
    return spec.trace().lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("name", [
    "step[K=4]", "replay", "prefill[b=128]",
    "piggyback_step[b=128,K=4]", "paged_step[K=4]",
])
def test_serve_program_lowers_with_decode_kernel(name):
    """The families that were refused before the position vector moved
    to SMEM (``step``, ``replay``, every ``piggyback_step``), one
    prefill (the flash forward inside a serving program) and the paged
    step, at the serve geometry."""
    spec = next(
        s for s in enumerate_programs(SERVE_CFG, SERVE_GEOM)
        if s.name == name
    )
    assert "tpu_custom_call" in _lower_spec(spec)


@pytest.mark.slow
def test_every_serve_family_lowers():
    """All families of the serve geometry (slab + paged + sampling
    surface): nothing is refused by the TPU lowering."""
    geom = dataclasses.replace(SERVE_GEOM, sampling_surface=True)
    for spec in enumerate_programs(SERVE_CFG, geom):
        _lower_spec(spec)


@pytest.mark.parametrize("seq,batch,d,heads,layers,d_ff,vocab,remat", [
    (1024, 24, 768, 6, 12, 3072, 50304, True),   # bench "transformer"
    (8192, 2, 512, 4, 8, 2048, 8192, False),     # "transformer-flash-8k"
    (32768, 1, 512, 4, 8, 2048, 8192, False),    # "transformer-flash-32k"
])
def test_training_presets_lower(seq, batch, d, heads, layers, d_ff, vocab,
                                remat):
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=d, n_heads=heads, n_layers=layers,
        d_ff=d_ff, max_len=seq + 1, use_flash=True, remat=remat,
        scan_layers=False, compute_dtype=jnp.bfloat16,
    )
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), cfg)
    )
    lower_tpu(
        jax.value_and_grad(transformer_loss(cfg)),
        params, S((batch, seq + 1), jnp.int32),
    )


def test_flash_training_lowers_on_a_dp_tp_mesh(devices):
    """A Mosaic kernel bare inside a multi-device jit is refused by the
    TPU lowering ("cannot be automatically partitioned"); interpreted,
    it is plain ops GSPMD partitions, so the CPU suite never saw it.
    transformer_apply runs the kernel under a shard_map when it has a
    mesh."""
    mesh = dp_mp_mesh(2, 2)
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_heads=2, n_layers=2, d_ff=512,
        max_len=257, use_flash=True, remat=True, scan_layers=False,
        compute_dtype=jnp.bfloat16,
    )
    params = jax.tree.map(
        lambda a, sh: S(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(lambda: init_transformer(jax.random.key(0), cfg)),
        transformer_shardings(mesh, cfg),
    )
    lower_tpu(
        jax.value_and_grad(transformer_loss(cfg, mesh)),
        params, S((4, 257), jnp.int32, sharding=batch_sharding(mesh)),
    )


# the laguna-s-2.1 serve geometry (benchmark/configs/laguna-s-2.1.json):
# 64 slots, 8 KV heads x 128; a slab of 4,096 rows read by 6 query rows a
# KV head, a ring of 512 read by 9
@pytest.mark.parametrize(
    "layers,rows,groups", [(2, 4096, 6), (3, 512, 9)], ids=["full", "ring"])
def test_decode_kernel_lowers_at_the_gated_cell_s_two_leaves(
        layers, rows, groups):
    batch, n_kv, hk = 64, 8, 1024
    assert pk.decode_block_rows(rows, hk, 2) < rows
    lower_tpu(
        lambda q, c, pos, act: pk.flash_decode_attention(
            q, c, pos, n_kv, layer=layers - 1, active=act),
        S((batch, groups, hk), jnp.bfloat16),
        S((layers, 2, batch, rows, hk), jnp.bfloat16),
        S((batch,), jnp.int32), S((batch,), jnp.bool_),
    )


def test_gated_step_program_lowers_with_both_leaves_and_the_counters():
    """A step program of a gated stack at a quarter of the cell's widths:
    the kernel once a leaf, the grouped products, and three counter rows
    under the token block."""
    from deeplearning4j_tpu.models.transformer import _decode_builder
    from deeplearning4j_tpu.serving.engine import (
        MOE_COUNT_ROWS,
        build_step_program,
    )

    cfg = TransformerConfig(
        vocab_size=2048, d_model=768, n_heads=12, n_kv_heads=4, head_size=128,
        n_layers=3, d_ff=1024, max_len=1024, rope=True, use_flash=True,
        compute_dtype=jnp.bfloat16, decode_kernel=True,
        layer_types=("full_attention", "sliding_attention", "full_attention"),
        layer_heads=(12, 16, 12), sliding_window=256, attn_gate=True,
        norm_eps=1e-6, dense_layers=(0,), n_experts=8, n_experts_total=16,
        moe_k=4, moe_scale=2.5, d_expert=256, d_shared=256,
        rope_full={"rope_theta": 500000, "factor": 128,
                   "original_max_position_embeddings": 8192, "beta_fast": 32,
                   "beta_slow": 1, "partial_rotary_factor": 0.5},
    )
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    slots, k = 16, 2
    params = jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0))
    caches = jax.eval_shape(lambda: init_caches(slots, 1024))
    step = build_step_program(fwd1, k, 1.0, 40, False)
    avals = (
        params, caches, S((slots, cfg.vocab_size), jnp.float32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
    )
    text = lower_tpu(step, *avals)
    assert "ragged_dot" in text
    out = jax.eval_shape(step, *avals)
    assert out[5].shape == (slots + MOE_COUNT_ROWS, k)


# -- the writing walk (PR 30): the cache goes through the kernel in place ------

# layers, slots, rows, KV heads, packed width, query rows a KV head: the
# gpt2-large serve slab at full depth, then the gated cell's two leaves
WRITE_GEOMETRIES = {
    "gpt2-large": (36, 48, 1024, 20, 1280, 1),
    "laguna-full": (2, 64, 4096, 8, 1024, 6),
    "laguna-ring": (3, 64, 512, 8, 1024, 9),
}
_ALIAS = ("output_operand_alias<output_tuple_indices = [1], "
          "operand_index = 7, operand_tuple_indices = []>")


def _writing_avals(name, sharding=None):
    layers, batch, t, n_kv, hk, groups = WRITE_GEOMETRIES[name]

    def s(shape, dtype):
        return S(shape, dtype, sharding=sharding)

    def fn(q, c, new, pos, act):
        return pk.flash_decode_attention_write(
            q, c, new, pos, n_kv, layer=layers - 1,
            write_at=pos % t, active=act)

    return fn, (
        s((batch, groups, hk), jnp.bfloat16),
        s((layers, 2, batch, t, hk), jnp.bfloat16),
        s((batch, 2, hk), jnp.bfloat16),
        s((batch,), jnp.int32), s((batch,), jnp.bool_),
    )


@pytest.mark.parametrize("name", sorted(WRITE_GEOMETRIES))
def test_writing_decode_lowers_with_the_cache_aliased(name):
    """The kernel's custom call names the stacked cache, its eighth
    operand after the five prefetched vectors, ``q`` and the new rows,
    as its second result."""
    fn, avals = _writing_avals(name)
    assert _ALIAS in lower_tpu(fn, *avals)


@pytest.fixture(scope="module")
def one_chip():
    """One described (not attached) v5e chip to compile for: XLA:TPU and
    Mosaic run, nothing executes."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(WRITE_GEOMETRIES))
def test_writing_decode_compiles_in_place_for_a_v5e(name, one_chip):
    """Mosaic accepts the 8-row tile's copy at the real widths, and a
    donated cache goes through the call without a copy: the program's
    temporaries stay far under one slot's slab."""
    fn, avals = _writing_avals(name, one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*avals).compile()
    cache = avals[1]
    mem = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(cache.shape)
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // (cache.shape[0] * 2 * 48)


# the latent leaf of the openpangu cell: layers, slots, rows, stored row
# width, heads, latent values
LATENT_GEOMETRY = (5, 224, 4096, 640, 128, 512)


def _latent_avals(sharding=None):
    layers, batch, t, width, heads, r_kv = LATENT_GEOMETRY

    def s(shape, dtype):
        return S(shape, dtype, sharding=sharding)

    def fn(q, c, new, pos, act):
        return pk.latent_decode_attention_write(
            q, c, new, pos, r_kv, layer=layers - 1, active=act)

    return fn, (
        s((batch, heads, width), jnp.bfloat16),
        s((layers, 1, batch, t, width), jnp.bfloat16),
        s((batch, 1, width), jnp.bfloat16),
        s((batch,), jnp.int32), s((batch,), jnp.bool_),
    )


def test_latent_decode_lowers_with_the_cache_aliased():
    """The one-plane walk is the same call as the K/V walk: five
    prefetched vectors, ``q``, the new row, then the cache, which is its
    second result."""
    fn, avals = _latent_avals()
    text = lower_tpu(fn, *avals)
    assert _ALIAS in text
    assert "latent_decode_attn" in text


def test_latent_decode_compiles_in_place_for_a_v5e(one_chip):
    """Mosaic accepts the kernel at the cell's widths (128 query rows
    against 512-row blocks of 640 lanes cut in two parts, the row's
    8-row tile copied back), and the donated 5.9 GB leaf goes through
    without a copy. It is compiled with the scoped VMEM limit set to
    HALF a v5e's 16 MiB (Mosaic refuses a kernel whose stack asks for
    more: "Scoped allocation with size ... exceeded scoped vmem limit";
    PR 32 met that wall), so the three block buffers, both parts' score
    tiles and weights and the accumulator together ask for under 8
    MiB."""
    fn, avals = _latent_avals(one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*avals).compile(
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "8192"})
    cache = avals[1]
    mem = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(cache.shape)
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // (cache.shape[0] * 224)


def _cache_shaped_ops(hlo: str, shape: str) -> set[str]:
    """Opcodes of the compiled instructions whose result is ``shape``."""
    return set(re.findall(
        r"= " + re.escape(shape) + r"\{[^}]*\} ([a-z-]+)\(", hlo))


def test_serve_step_places_rows_in_the_kernel_and_copies_no_cache(one_chip):
    """The gpt2-large serve step (two layers, K=2, every width real)
    compiled for a v5e: the donated cache is aliased to the result, the
    kernel runs once a layer and substep, and no scatter, fusion or copy
    produces anything of the cache's shape — only the parameter and the
    kernel calls' second results do."""
    from deeplearning4j_tpu.models.transformer import _decode_builder
    from deeplearning4j_tpu.serving.engine import build_step_program

    cfg = TransformerConfig(
        vocab_size=50257, d_model=1280, n_heads=20, n_layers=2, d_ff=5120,
        max_len=1024, compute_dtype=jnp.bfloat16, use_flash=True,
        decode_kernel=True,
    )
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    slots, k = 48, 2

    def placed(tree):
        return jax.tree.map(
            lambda a: S(a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0)))
    caches = placed(jax.eval_shape(lambda: init_caches(slots, 1024)))
    avals = (
        params, caches, S((slots, cfg.vocab_size), jnp.float32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
    )
    avals = avals[:2] + placed(avals[2:])
    compiled = jax.jit(
        build_step_program(fwd1, k, 1.0, 40, False),
        donate_argnums=(1, 2, 3, 4, 5),
    ).lower(*avals).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * k
    assert _cache_shaped_ops(hlo, "bf16[2,2,48,1024,1280]") <= {
        "parameter", "get-tuple-element"}
    mem = compiled.memory_analysis()
    cache_bytes = 2 * 2 * 2 * 48 * 1024 * 1280
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 48


# a step program of each state stack at its cell's widths and slots, a
# vocabulary of 2,048, K = 2: (configuration, slots, rows a slot, the
# temporaries the step needed when XLA rewrote a ring of 8 positions a
# tile whole every substep). Granite's keeps its 36 mamba layers: a ring
# of a few layers fits VMEM, and XLA then stages the whole leaf there and
# back, which the cell's 189 MB ring does not
STATE_STEPS = {
    "olmo": (dict(
        d_model=3840, n_heads=30, n_layers=3, rope=True,
        layer_types=("linear_attention",) * 2 + ("full_attention",),
        norm_eps=1e-6, post_norm=True, qk_norm=True,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    ), 128, 2048, 145_675_776),
    "granite": (dict(
        d_model=2048, n_heads=32, n_kv_heads=8, n_layers=37, rope=False,
        layer_types=("mamba",) * 36 + ("attention",), mamba_n_heads=64,
        mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
        mamba_d_conv=4, mamba_expand=2, mamba_conv_bias=True,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0,
        tie_word_embeddings=True,
    ), 64, 3072, 163_793_920),
}


@pytest.mark.parametrize("stack", sorted(STATE_STEPS))
def test_state_step_places_ring_rows_in_the_kernel_and_copies_no_ring(
        stack, one_chip):
    """A state stack's serve step compiled for a v5e: the donated state
    leaf and ring are aliased to the results, the state kernel runs once
    a state layer and substep, and nothing of the ring's size is made by
    a scatter, a fusion or a copy: only parameters (the leaf, and the
    ring inside the fusions that read a step's K - 1 positions) and the
    kernel calls' third results carry it. The step's temporaries stay
    below what a ring rewritten whole every substep needed."""
    from deeplearning4j_tpu.models.transformer import _decode_builder
    from deeplearning4j_tpu.serving.engine import build_step_program

    model, slots, total, temp_before = STATE_STEPS[stack]
    n = model["n_layers"]
    cfg = TransformerConfig(
        vocab_size=2048, d_ff=1024, max_len=total, use_flash=True,
        compute_dtype=jnp.bfloat16, decode_kernel=True,
        dense_layers=tuple(range(n)), **model)
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    k = 2

    def placed(tree):
        return jax.tree.map(
            lambda a: S(a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0)))
    caches = placed(jax.eval_shape(lambda: init_caches(slots, total)))
    avals = (
        params, caches, S((slots, cfg.vocab_size), jnp.float32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
    )
    avals = avals[:2] + placed(avals[2:])
    compiled = jax.jit(
        build_step_program(fwd1, k, 1.0, 40, False),
        donate_argnums=(1, 2, 3, 4, 5),
    ).lower(*avals).compile()
    hlo = compiled.as_text()
    n_state = len(cfg.layers_of("linear"))
    assert hlo.count('custom_call_target="tpu_custom_call"') == n * k
    ring = caches["conv"]
    values = math.prod(ring.shape)
    made = {
        op for dims, op in re.findall(
            r"= f32\[([0-9,]+)\]\{[^}]*\} ([a-z-]+)\(", hlo)
        if math.prod(int(d) for d in dims.split(",")) == values
    }
    assert made == {"parameter", "get-tuple-element"}, made
    mem = compiled.memory_analysis()
    state_bytes = 4 * math.prod(caches["state"].shape)
    assert mem.alias_size_in_bytes >= state_bytes + 4 * values
    assert mem.temp_size_in_bytes < temp_before
    assert n_state == ring.shape[0]


def test_lowered_serve_steps_alias_the_cache_and_scatter_no_row():
    """The slab and the paged step at the serve geometry: the kernel's
    call carries the alias, and no scatter or update-slice of a slab's
    shape is left for XLA beside it (the paged step's own gather and
    scatter work on the pool's blocks)."""
    slab = "12x2x16x584x256xbf16"
    for name in ("step[K=4]", "paged_step[K=4]"):
        spec = next(
            s for s in enumerate_programs(SERVE_CFG, SERVE_GEOM)
            if s.name == name
        )
        text = _lower_spec(spec)
        assert _ALIAS in text
        writes = [
            line for line in text.splitlines()
            if ("stablehlo.scatter" in line
                or "stablehlo.dynamic_update_slice" in line)
            and line.rstrip().endswith(f"tensor<{slab}>")
        ]
        assert not writes, writes[:2]


def test_packed_train_step_compiles_for_a_v5e_and_copies_no_activation(
        one_chip):
    """Two layers of the gpt2-medium train step (8 x 1,024 tokens, 16
    heads x 64, bf16, ``remat`` with ``dots_no_batch``) compiled for a
    v5e, XLA:TPU and Mosaic: the packed kernels take 1,024 x 1,024
    blocks with two heads in one body inside the scoped VMEM, run once a
    layer forward and once backward (the policy saves ``flash_out`` and
    ``flash_lse``: no second forward), and no copy or transpose of the
    compiled step moves an activation the size of q (the (B, H, T, K)
    path had nine copies a layer: PERF.md, PR 32). Since PR 35 each of
    those tiles is walked in four causal bands inside the body."""
    from deeplearning4j_tpu.models.transformer import (
        _flash_blocks,
        flash_layout,
    )

    assert pk.flash_computed_share(1024, *_flash_blocks(1024), True) == 0.625

    cfg = TransformerConfig(
        vocab_size=2048, d_model=1024, n_heads=16, n_layers=2, d_ff=4096,
        max_len=1024, compute_dtype=jnp.bfloat16, use_flash=True,
        remat=True, scan_layers=False,
    )
    assert flash_layout(cfg) == "packed"
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_transformer(jax.random.key(0), cfg)),
    )
    hlo = jax.jit(jax.value_and_grad(transformer_loss(cfg))).lower(
        params, S((8, 1025), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    kernels = re.findall(r"%(\w+?)_?[.\d]* = .*tpu_custom_call", hlo)
    assert sorted(k.removeprefix("jvp_") for k in kernels) == (
        ["flash_bwd_packed"] * cfg.n_layers
        + ["flash_fwd_packed"] * cfg.n_layers), kernels
    q_size = 8 * 1024 * 16 * 64
    moved = [
        (op, dims)
        for dims, op in re.findall(
            r"= bf16\[([\d,]+)\]\{[^}]*\} (copy|transpose)\(",
            hlo[hlo.index("ENTRY "):])
        if math.prod(int(d) for d in dims.split(",")) % q_size == 0
    ]
    assert not moved, moved


def test_packed_flash_compiles_banded_and_unbanded_tiles_for_a_v5e(one_chip):
    """Forward and backward at 1 x 8,192 x 16 heads x 64 compiled for a
    v5e: 1,024 / 1,024 forward blocks (the eight diagonal tiles in
    bands, the rest whole) and 512 / 2,048 backward blocks (no bands:
    the blocks differ) in one call, inside the scoped VMEM."""
    from deeplearning4j_tpu.models.transformer import (
        _flash_blocks,
        _flash_bwd_blocks,
    )

    t = 8192
    (bq, bk), (bbq, bbk) = _flash_blocks(t), _flash_bwd_blocks(t)
    assert (bq, bk, bbq, bbk) == (1024, 1024, 512, 2048)
    assert pk.flash_computed_share(t, bq, bk, True) < (
        pk.flash_computed_share(t, bbq, bbk, True))

    def both(q, k, v):
        o, pull = jax.vjp(
            lambda q, k, v: pk.flash_attention_packed(
                q, k, v, 64, block_q=bq, block_k=bk, bwd_block_q=bbq,
                bwd_block_k=bbk, causal=True),
            q, k, v)
        return o, pull(o)

    x = S((1, t, 16 * 64), jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(both).lower(x, x, x).compile().as_text()
    kernels = re.findall(r"%([\w.]+) = .*tpu_custom_call", hlo)
    assert sorted(
        re.search(r"flash_(fwd|bwd)_packed", k).group() for k in kernels
    ) == ["flash_bwd_packed", "flash_fwd_packed"], kernels


# -- the linear decode kernel (PR 38): the state leaf updated in place --------

# linear layers, slots, key_head_dim, heads, value_head_dim: the state leaf
# of the olmo-hybrid-7b cell (1.7 GB of float32); its convolution ring is 4
# positions of 96 rows of 128 lanes (11,520 channels padded)
LINEAR_GEOMETRY = (6, 128, 96, 30, 192)
LINEAR_RING_ROWS = 96


def _ring_avals(s, layers, b, rows):
    """The ring leaf, the slots' fresh inputs and their positions."""
    return (s((layers, 1, b, 4, rows, 128)), s((b, rows, 128)),
            s((b,), jnp.int32))


def _linear_avals(sharding=None, heads_per_step=None, ring=True):
    layers, b, dk, h, dv = LINEAR_GEOMETRY
    f32 = jnp.float32

    def s(shape, dtype=f32):
        return S(shape, dtype, sharding=sharding)

    def fn(q, k, v, a, be, state, act, *conv):
        return pk.linear_decode(
            q, k, v, a, be, state, layer=layers - 1, active=act,
            heads_per_step=heads_per_step,
            **dict(zip(("ring", "row", "pos"), conv)))

    avals = (s((b, dk, h)), s((b, dk, h)), s((b, h * dv)), s((b, h)),
             s((b, h)), s((layers, 1, b, dk, h * dv)), s((b,), jnp.bool_))
    if ring:
        avals += _ring_avals(s, layers, b, LINEAR_RING_ROWS)
    return fn, avals


def _alias(result: int, operand: int) -> str:
    return (f"output_operand_alias<output_tuple_indices = [{result}], "
            f"operand_index = {operand}, operand_tuple_indices = []>")


@pytest.mark.parametrize("ring", [False, True], ids=["state", "state+ring"])
@pytest.mark.parametrize("heads_per_step", [2, 6, 30])
def test_linear_decode_lowers_with_the_state_aliased(heads_per_step, ring):
    """Three prefetched vectors (layer, source slot, job) and five small
    inputs, then the state leaf, which is the second result; with the
    ring, a fourth vector (each slot's ring position) and the fresh
    inputs come in too, and the ring after the state is the third."""
    fn, avals = _linear_avals(heads_per_step=heads_per_step, ring=ring)
    text = lower_tpu(fn, *avals)
    assert _alias(1, 10 if ring else 8) in text
    assert (_alias(2, 11) in text) == ring
    assert "linear_decode" in text


def test_linear_decode_compiles_in_place_for_a_v5e(one_chip):
    """Mosaic accepts the kernel at the cell's widths, 10 heads' 737 KB
    block a grid step, within HALF a v5e's default scoped VMEM (as the
    latent kernel's test compiles it), and the donated 1.7 GB state leaf
    and the 151 MB ring go through without a copy."""
    fn, avals = _linear_avals(one_chip)
    compiled = jax.jit(fn, donate_argnums=(5, 7)).lower(*avals).compile(
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "8192"})
    mem = compiled.memory_analysis()
    state_bytes = 4 * math.prod(avals[5].shape)
    ring_bytes = 4 * math.prod(avals[7].shape)
    assert mem.alias_size_in_bytes == state_bytes + ring_bytes
    assert mem.temp_size_in_bytes < state_bytes // 128


def test_hybrid_step_program_lowers_with_the_state_and_its_counter():
    """A step program of a stack with linear layers at a quarter of the
    cell's widths: the linear kernel once a linear layer and substep,
    the walk once a full layer and substep, and one counter row (state
    updates) under the token block."""
    from deeplearning4j_tpu.models.transformer import _decode_builder
    from deeplearning4j_tpu.serving.engine import build_step_program

    cfg = TransformerConfig(
        vocab_size=2048, d_model=960, n_heads=8, head_size=128, n_layers=4,
        d_ff=1024, max_len=1024, rope=True, use_flash=True,
        compute_dtype=jnp.bfloat16, decode_kernel=True,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        dense_layers=(0, 1, 2, 3), norm_eps=1e-6, post_norm=True,
        qk_norm=True, linear_num_key_heads=8, linear_num_value_heads=8,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    )
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    slots, k = 16, 2
    params = jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0))
    caches = jax.eval_shape(lambda: init_caches(slots, 1024))
    step = build_step_program(fwd1, k, 1.0, 40, False)
    avals = (
        params, caches, S((slots, cfg.vocab_size), jnp.float32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
    )
    text = lower_tpu(step, *avals)
    assert text.count("call @_linear_decode(") == 3 * k
    out = jax.eval_shape(step, *avals)
    assert out[5].shape == (slots + 1, k)


# mamba layers, slots, d_state, heads, d_head: the state leaf of the
# granite-4.0-h-micro cell (4.83 GB of float32), one key group; its ring is
# 4 positions of 40 rows of 128 lanes (4,352 channels padded)
SSD_GEOMETRY = (36, 64, 128, 64, 64)
SSD_RING_ROWS = 40


def _ssd_avals(sharding=None, heads_per_step=None, groups=1, ring=True):
    layers, b, dk, h, dv = SSD_GEOMETRY
    f32 = jnp.float32

    def s(shape, dtype=f32):
        return S(shape, dtype, sharding=sharding)

    def fn(q, k, v, a, state, act, *conv):
        return pk.linear_decode(
            q, k, v, a, None, state, layer=layers - 1, active=act,
            heads_per_step=heads_per_step, rule="additive",
            **dict(zip(("ring", "row", "pos"), conv)))

    avals = (s((b, dk, groups)), s((b, dk, groups)), s((b, h * dv)),
             s((b, h)), s((layers, 1, b, dk, h * dv)), s((b,), jnp.bool_))
    if ring:
        avals += _ring_avals(s, layers, b, SSD_RING_ROWS)
    return fn, avals


@pytest.mark.parametrize("ring", [False, True], ids=["state", "state+ring"])
@pytest.mark.parametrize("heads_per_step, groups", [
    (None, 1), (16, 1), (64, 1), (32, 8)])
def test_ssd_decode_lowers_with_the_state_aliased(heads_per_step, groups,
                                                  ring):
    """Three prefetched vectors (layer, source slot, job) and four small
    inputs (C, B, dt x, the decay), then the state leaf, which is the
    second result; with the ring, the ring positions and the fresh
    inputs too, and the ring as the third result. The call is named
    ``ssd_decode`` in the trace."""
    fn, avals = _ssd_avals(heads_per_step=heads_per_step, groups=groups,
                           ring=ring)
    text = lower_tpu(fn, *avals)
    assert _alias(1, 9 if ring else 7) in text
    assert (_alias(2, 10) in text) == ring
    assert "ssd_decode" in text and "linear_decode\"" not in text


def test_ssd_decode_compiles_in_place_for_a_v5e(one_chip):
    """Mosaic accepts the kernel at the cell's widths, 32 heads' 1 MB
    block a grid step, within half a v5e's default scoped VMEM, and the
    donated 4.83 GB state leaf and the 189 MB ring go through without a
    copy."""
    fn, avals = _ssd_avals(one_chip)
    compiled = jax.jit(fn, donate_argnums=(4, 6)).lower(*avals).compile(
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "8192"})
    mem = compiled.memory_analysis()
    state_bytes = 4 * math.prod(avals[4].shape)
    ring_bytes = 4 * math.prod(avals[6].shape)
    assert mem.alias_size_in_bytes == state_bytes + ring_bytes
    assert mem.temp_size_in_bytes < state_bytes // 128


def test_mamba_step_program_lowers_with_the_state_and_its_counter():
    """A step program of a stack with mamba layers at the cell's widths
    and four layers: the additive kernel once a mamba layer and substep,
    the walk once an attention layer and substep, and one counter row
    (state updates) under the token block."""
    from deeplearning4j_tpu.models.transformer import _decode_builder
    from deeplearning4j_tpu.serving.engine import build_step_program

    cfg = TransformerConfig(
        vocab_size=2048, d_model=2048, n_heads=32, n_kv_heads=8,
        n_layers=4, d_ff=1024, max_len=1024, rope=False, use_flash=True,
        compute_dtype=jnp.bfloat16, decode_kernel=True,
        layer_types=("mamba",) * 3 + ("attention",),
        dense_layers=(0, 1, 2, 3), mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
        mamba_conv_bias=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, tie_word_embeddings=True,
    )
    fwd1, init_caches, _, cast = _decode_builder(cfg)
    slots, k = 16, 2
    params = jax.eval_shape(
        lambda key: cast(init_transformer(key, cfg)), jax.random.key(0))
    caches = jax.eval_shape(lambda: init_caches(slots, 1024))
    step = build_step_program(fwd1, k, 1.0, 40, False)
    avals = (
        params, caches, S((slots, cfg.vocab_size), jnp.float32),
        S((slots,), jnp.int32), S((slots,), jnp.bool_),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots, 2), jnp.uint32), S((slots,), jnp.int32),
    )
    text = lower_tpu(step, *avals)
    assert text.count("call @_linear_decode(") == 3 * k
    assert "ssd_decode" in text
    out = jax.eval_shape(step, *avals)
    assert out[5].shape == (slots + 1, k)


# the expert cells' decode substep: token-expert pairs, held experts, d,
# expert width (laguna-s-2.1 and openpangu-ultra-moe-718b, one layer)
MOE_GEOMETRIES = {
    "laguna": (640, 128, 3072, 1024),
    "pangu": (1792, 8, 7680, 2048),
}


def _moe_avals(name, sharding=None):
    pairs, e, d, f = MOE_GEOMETRIES[name]

    def s(shape, dtype=jnp.bfloat16):
        return S(shape, dtype, sharding=sharding)

    return (s((pk.moe_stream_rows(pairs, e), d)), s((e, d, f)),
            s((e, d, f)), s((e, f, d)), s((e,), jnp.int32))


@pytest.mark.parametrize("name", sorted(MOE_GEOMETRIES))
def test_streamed_experts_lower(name):
    """Two prefetched vectors (the passes, their count), the rows and
    the three weight stacks in HBM: one Mosaic call named for the
    grouped products, which the benchmark's readers find by name."""
    assert "moe_ragged_dot" in lower_tpu(pk.moe_ragged_dot,
                                         *_moe_avals(name))


@pytest.mark.parametrize("name", sorted(MOE_GEOMETRIES))
def test_streamed_experts_compile_for_a_v5e(name, one_chip):
    """Mosaic accepts the kernel at the cells' widths (the rows' 8-row
    chunks at dynamic offsets, gate and up blocks of 256 / 128 rows and
    down blocks of 128, three of each kind) within a v5e's default 16
    MiB of scoped VMEM: Laguna's asks for about 7 MiB, openPangu's for
    11.1 MiB (its down blocks are 1.97 MB)."""
    compiled = jax.jit(pk.moe_ragged_dot).lower(
        *_moe_avals(name, one_chip)).compile(
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": "16384"})
    assert "moe_ragged_dot" in compiled.as_text()
