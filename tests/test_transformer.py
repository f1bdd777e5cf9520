"""Flagship transformer LM: causality, TP parity, composed dp x tp training.

The reference's only sequence model is the serial-loop LSTM
(models/classifiers/lstm/LSTM.java:36); the transformer is beyond-parity
and exists to exercise composed pjit sharding on the 2-D mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    place_transformer_params,
    transformer_apply,
    transformer_loss,
    transformer_train_step,
)
from deeplearning4j_tpu.parallel import mesh as mesh_lib

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)


def _tokens(b, t, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG.vocab_size, (b, t)), jnp.int32)


def test_forward_shape_and_causality():
    params = init_transformer(jax.random.key(0), CFG)
    apply = transformer_apply(CFG)
    toks = _tokens(2, 16)
    logits, _ = apply(params, toks)
    assert logits.shape == (2, 16, CFG.vocab_size)
    # causality: mutating a future token must not change earlier logits
    toks2 = toks.at[:, 10].set((toks[:, 10] + 1) % CFG.vocab_size)
    logits2, _ = apply(params, toks2)
    np.testing.assert_allclose(
        np.asarray(logits[:, :10]), np.asarray(logits2[:, :10]), atol=1e-5
    )
    assert float(jnp.max(jnp.abs(logits[:, 10:] - logits2[:, 10:]))) > 1e-4


def test_tp_sharded_forward_matches_replicated(devices):
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    params = init_transformer(jax.random.key(1), CFG)
    apply = jax.jit(transformer_apply(CFG))
    toks = _tokens(4, 16, seed=1)
    y_rep, _ = apply(params, toks)
    y_tp, _ = apply(place_transformer_params(mesh, params), toks)
    np.testing.assert_allclose(
        np.asarray(y_rep), np.asarray(y_tp), atol=2e-4
    )


@pytest.mark.slow
def test_remat_matches_no_remat():
    cfg_r = TransformerConfig(**{
        **CFG.__dict__, "remat": True
    })
    params = init_transformer(jax.random.key(2), CFG)
    toks = _tokens(2, 8, seed=2)
    l1 = transformer_loss(CFG)(params, toks)
    l2 = transformer_loss(cfg_r)(params, toks)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = jax.grad(transformer_loss(CFG))(params, toks)
    g2 = jax.grad(transformer_loss(cfg_r))(params, toks)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_composed_dp_tp_training_learns(devices):
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    step, init_state, shard_tokens = transformer_train_step(mesh, CFG)
    params, opt_state = init_state(jax.random.key(3))
    toks = shard_tokens(_tokens(8, 17, seed=3))  # fixed batch -> overfit
    losses = []
    for _ in range(30):
        params, opt_state, l = step(params, opt_state, toks)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def _cfg(**over):
    return TransformerConfig(**{**CFG.__dict__, **over})


@pytest.mark.slow
def test_moe_transformer_training_learns(devices):
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    cfg = _cfg(n_experts=4, moe_capacity_factor=4.0)
    step, init_state, shard_tokens = transformer_train_step(mesh, cfg)
    params, opt_state = init_state(jax.random.key(10))
    toks = shard_tokens(_tokens(8, 17, seed=10))
    losses = []
    for _ in range(30):
        params, opt_state, l = step(params, opt_state, toks)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses[::10]


def test_moe_transformer_data_sharding_invariance(devices):
    # same params/config on (2, 4) vs (1, 4) meshes: only the batch
    # sharding differs, so with ample capacity outputs must agree
    cfg = _cfg(n_experts=4, moe_capacity_factor=8.0)
    params = init_transformer(jax.random.key(11), cfg)
    toks = _tokens(4, 16, seed=11)
    outs = []
    for dp in (2, 1):
        mesh = mesh_lib.dp_mp_mesh(dp, 4)
        apply = jax.jit(transformer_apply(cfg, mesh))
        p = place_transformer_params(mesh, params, cfg)
        logits, aux = apply(p, toks)
        assert np.isfinite(float(aux))
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-4)


def test_sequence_parallel_matches_dense(devices):
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    cfg_sp = _cfg(sequence_parallel=True)
    params = init_transformer(jax.random.key(12), CFG)
    toks = _tokens(2, 16, seed=12)  # T divisible by the data axis
    y_dense, _ = transformer_apply(CFG)(params, toks)
    apply_sp = jax.jit(transformer_apply(cfg_sp, mesh))
    y_sp, _ = apply_sp(place_transformer_params(mesh, params), toks)
    np.testing.assert_allclose(
        np.asarray(y_dense), np.asarray(y_sp), atol=2e-4
    )


@pytest.mark.slow
def test_sp_moe_composed_train_step(devices):
    # sp x tp x ep in one step: sequence ring over data, heads + experts
    # over model
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    cfg = _cfg(n_experts=4, sequence_parallel=True, moe_capacity_factor=4.0)
    step, init_state, shard_tokens = transformer_train_step(mesh, cfg)
    params, opt_state = init_state(jax.random.key(13))
    toks = shard_tokens(_tokens(4, 16, seed=13))
    for _ in range(3):
        params, opt_state, l = step(params, opt_state, toks)
        assert np.isfinite(float(l))


@pytest.mark.slow
def test_fsdp_training_matches_replicated(devices):
    # ZeRO-3 layout: params + optimizer state sharded over the data axis;
    # must train identically (up to reduction reorder) to the plain layout
    from deeplearning4j_tpu.models.transformer import fsdp_shardings

    mesh = mesh_lib.dp_mp_mesh(2, 4)
    toks = _tokens(8, 17, seed=30)
    losses = {}
    for fsdp in (False, True):
        step, init_state, shard_tokens = transformer_train_step(
            mesh, CFG, fsdp=fsdp
        )
        params, opt_state = init_state(jax.random.key(30))
        ts = shard_tokens(toks)
        ls = []
        for _ in range(10):
            params, opt_state, l = step(params, opt_state, ts)
            ls.append(float(l))
        losses[fsdp] = ls
        if fsdp:
            # the big leaves must actually be data-sharded
            sh = fsdp_shardings(mesh, CFG)
            assert "data" in str(sh["embed"].spec)
            assert "data" in str(sh["blocks"]["wqkv"].spec)
    np.testing.assert_allclose(losses[False], losses[True], rtol=2e-3)


@pytest.mark.slow
def test_greedy_generate_matches_full_forward():
    from deeplearning4j_tpu.models.transformer import transformer_generate

    params = init_transformer(jax.random.key(20), CFG)
    gen = transformer_generate(CFG)
    apply = transformer_apply(CFG)
    prompt = _tokens(2, 5, seed=20)
    out = gen(params, prompt, jax.random.key(0), 6, temperature=0)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(out[:, :5]), np.asarray(prompt))
    # KV-cache greedy decode must equal re-running the full forward and
    # taking argmax of the last position each step
    seq = prompt
    for _ in range(6):
        logits, _ = apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


@pytest.mark.slow
def test_int8_decode_quality_gate():
    """Weight-only int8 params + int8 KV cache (VERDICT r4 #1 quality
    gate): the quantized decode program must track the float reference —
    logits within a few percent, greedy tokens mostly identical, and
    the dense-fallback path consistent with the kernel path."""
    import dataclasses
    import functools

    from deeplearning4j_tpu.models.transformer import (
        _decode_builder,
        quantize_decode_params,
        transformer_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96,
    )
    params = init_transformer(jax.random.key(0), cfg)
    cfg_q = dataclasses.replace(cfg, decode_int8=True)
    qparams = quantize_decode_params(params, cfg)
    # quantized leaves are int8 with f32 per-output-channel scales
    assert qparams["blocks"]["wqkv"].dtype == jnp.int8
    assert qparams["blocks"]["wqkv_scale"].dtype == jnp.float32
    assert qparams["head"].dtype == jnp.int8
    # dequantized weights approximate the originals (per-channel int8:
    # worst-case error = scale/2 = amax/254 per channel)
    deq = (
        qparams["blocks"]["wqkv"].astype(jnp.float32)
        * qparams["blocks"]["wqkv_scale"]
    )
    werr = float(jnp.max(jnp.abs(deq - params["blocks"]["wqkv"])))
    wmax = float(jnp.max(jnp.abs(params["blocks"]["wqkv"])))
    assert werr <= wmax / 127.0, (werr, wmax)

    prompt = _tokens(4, 24, seed=7)
    # logits parity: prefill + one cached step (stamp-time ~2.5% rel err)
    f1, ic, pf, cp = _decode_builder(cfg)
    fq1, icq, pfq, cpq = _decode_builder(cfg_q)
    caches, lg = pf(cp(params), ic(4, 40), prompt)
    caches_q, lgq = pfq(cpq(qparams), icq(4, 40), prompt)
    scale = float(jnp.max(jnp.abs(lg)))
    assert float(jnp.max(jnp.abs(lgq - lg))) < 0.06 * scale + 0.02
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    l2, _ = f1(cp(params), caches, tok, 24)
    l2q, _ = fq1(cpq(qparams), caches_q, tok, 24)
    scale2 = float(jnp.max(jnp.abs(l2)))
    assert float(jnp.max(jnp.abs(l2q - l2))) < 0.06 * scale2 + 0.02

    # greedy decode: high token agreement with the float reference
    # (random-weight logits are near-uniform, the hardest case for
    # argmax stability; stamp-time agreement 0.875)
    gen = jax.jit(functools.partial(
        transformer_generate(cfg), max_new=16, temperature=0.0
    ))
    gen_q = jax.jit(functools.partial(
        transformer_generate(cfg_q), max_new=16, temperature=0.0
    ))
    out = np.asarray(gen(params, prompt, jax.random.key(1)))
    out_q = np.asarray(gen_q(qparams, prompt, jax.random.key(1)))
    assert (out[:, 24:] == out_q[:, 24:]).mean() >= 0.7
    # kernel path vs dense-fallback path agree on the quantized cache
    cfg_qd = dataclasses.replace(cfg_q, decode_kernel=False)
    gen_qd = jax.jit(functools.partial(
        transformer_generate(cfg_qd), max_new=16, temperature=0.0
    ))
    out_qd = np.asarray(gen_qd(qparams, prompt, jax.random.key(1)))
    assert (out_q[:, 24:] == out_qd[:, 24:]).mean() >= 0.9

    # beam search runs through the int8 cache pytree (repeat/take paths)
    from deeplearning4j_tpu.models.transformer import transformer_beam_search

    beam = jax.jit(functools.partial(
        transformer_beam_search(cfg_q), beam_width=2, max_new=8
    ))
    toks, scores = beam(qparams, prompt[:2])
    assert toks.shape == (2, 2, 32)
    assert np.isfinite(np.asarray(scores)).all()


@pytest.mark.slow
def test_int8_weights_only_decode_over_bf16_cache():
    """The int8-weights/bf16-cache split (PERF.md r5 crossover: the
    winning composite under GQA): quantized params with
    ``decode_int8=False`` must run the unmodified bf16 cache/kernel path
    — ``_w`` dequantizes by leaf dtype — and track the float reference
    as closely as the fully-quantized path does."""
    import functools

    from deeplearning4j_tpu.models.transformer import (
        _decode_builder,
        quantize_decode_params,
        transformer_generate,
    )

    # production geometry: GQA (2 kv heads under 4 query heads) + RoPE
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96, n_kv_heads=2, rope=True,
    )
    params = init_transformer(jax.random.key(0), cfg)
    qparams = quantize_decode_params(params, cfg)  # cfg keeps decode_int8=False

    prompt = _tokens(4, 24, seed=7)
    f1, ic, pf, cp = _decode_builder(cfg)
    # same builder for both: only the params differ
    caches, lg = pf(cp(params), ic(4, 40), prompt)
    caches_q, lgq = pf(cp(qparams), ic(4, 40), prompt)
    # the bf16 cache is shared infrastructure: identical dtype/shape
    assert caches_q.dtype == caches.dtype and caches_q.shape == caches.shape
    scale = float(jnp.max(jnp.abs(lg)))
    assert float(jnp.max(jnp.abs(lgq - lg))) < 0.06 * scale + 0.02
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    l2, _ = f1(cp(params), caches, tok, 24)
    l2q, _ = f1(cp(qparams), caches_q, tok, 24)
    scale2 = float(jnp.max(jnp.abs(l2)))
    assert float(jnp.max(jnp.abs(l2q - l2))) < 0.06 * scale2 + 0.02

    # greedy decode through the full generate program
    gen = jax.jit(functools.partial(
        transformer_generate(cfg), max_new=16, temperature=0.0
    ))
    out = np.asarray(gen(params, prompt, jax.random.key(1)))
    out_q = np.asarray(gen(qparams, prompt, jax.random.key(1)))
    assert (out[:, 24:] == out_q[:, 24:]).mean() >= 0.7


@pytest.mark.slow
def test_int8_weights_decode_under_dp_tp_mesh():
    """int8-weight serving partitioned by GSPMD: quantized params placed
    with the Megatron layout (scale leaves derive their sharding from
    their weight's spec, unsharding the size-1 quantized axes) must
    decode on the dp x tp mesh and track the bf16 sharded run."""
    import functools

    from deeplearning4j_tpu.models.transformer import (
        quantize_decode_params,
        transformer_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96, n_kv_heads=2, rope=True,
    )
    mesh = mesh_lib.dp_mp_mesh(4, 2)
    params = init_transformer(jax.random.key(0), cfg)
    qparams = quantize_decode_params(params, cfg)
    gp = place_transformer_params(mesh, params, cfg)
    qp = place_transformer_params(mesh, qparams, cfg)
    # row-parallel weights quantize over their sharded input axis: the
    # (global, keepdims) scale must come out replicated on that axis
    assert all(
        s is None for s in qp["blocks"]["wo_scale"].sharding.spec
    )
    # column-parallel scales keep their weight's surviving sharded axis
    assert qp["blocks"]["w1_scale"].sharding.spec[-1] is not None

    prompt = _tokens(4, 24, seed=7)
    gen = jax.jit(functools.partial(
        transformer_generate(cfg), max_new=8, temperature=0.0
    ))
    out = np.asarray(gen(gp, prompt, jax.random.key(1)))
    out_q = np.asarray(gen(qp, prompt, jax.random.key(1)))
    assert ((out_q >= 0) & (out_q < cfg.vocab_size)).all()
    assert (out[:, 24:] == out_q[:, 24:]).mean() >= 0.5


@pytest.mark.slow
def test_chunk_forward_matches_sequential_decode():
    """The speculative-verify chunk forward must equal C sequential
    single-token decode steps — same cache layout, same logits — on
    both the GQA+RoPE geometry and the fully-int8 cache mode."""
    import dataclasses

    from deeplearning4j_tpu.models.transformer import (
        _chunk_builder,
        _decode_builder,
        quantize_decode_params,
    )

    C = 5
    base = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, n_kv_heads=2, rope=True,
    )
    for cfg, params in [
        (base, init_transformer(jax.random.key(0), base)),
        (
            # decode_kernel=False: the sequential side must use the
            # dense fallback — the int8 KERNEL quantizes q and the
            # softmax weights in-register (an extra ~1% error source
            # the dense chunk deliberately lacks), so kernel-vs-chunk
            # only agrees at the token level, not logits-atol level
            dataclasses.replace(
                base, decode_int8=True, decode_kernel=False
            ),
            quantize_decode_params(
                init_transformer(jax.random.key(0), base), base
            ),
        ),
    ]:
        f1, ic, _pf, cp = _decode_builder(cfg)
        chunk = _chunk_builder(cfg)
        toks = _tokens(2, C, seed=3)
        p = cp(params)
        seq_caches = ic(2, 16)
        seq_logits = []
        for i in range(C):
            lg, seq_caches = f1(p, seq_caches, toks[:, i], i)
            seq_logits.append(lg)
        ch_logits, ch_caches = chunk(p, ic(2, 16), toks, 0)
        for i in range(C):
            np.testing.assert_allclose(
                np.asarray(ch_logits[:, i]), np.asarray(seq_logits[i]),
                atol=2e-3, err_msg=f"slot {i} int8={cfg.decode_int8}",
            )
        # ...and against bulk prefill: block_chunk is a third copy of
        # the transformer block (prefill's layer / block_decode's dense
        # fallback are the others) — this pins chunk-vs-prefill so the
        # copies cannot drift (cache rows written must be identical)
        pf_caches, _ = _pf(cp(params), ic(2, 16), toks)

        def rows(c):
            # dequantize int8 caches: float-association differences
            # between the two paths may flip one quantization LSB, so
            # raw int8 planes are compared at value level
            if isinstance(c, dict):
                return (
                    np.asarray(c["kv"][:, :, :, :C], np.float32)
                    * np.asarray(c["scale"][:, :, :, :C], np.float32)
                )
            return np.asarray(c[:, :, :, :C], np.float32)

        np.testing.assert_allclose(
            rows(ch_caches), rows(pf_caches),
            # int8: float-association differences between the paths can
            # shift a row's amax (hence its scale) — allow ~2 quant LSBs
            atol=6e-2 if cfg.decode_int8 else 2e-2,
            err_msg=f"cache rows int8={cfg.decode_int8}",
        )


@pytest.mark.slow
def test_speculative_greedy_matches_plain_up_to_near_ties():
    """The greedy contract for ANY draft: the speculative chain must
    follow the plain greedy decode except where the plain decoder's
    top-2 logit margin is inside the cross-program float-reassociation
    band (the verify chunk is a differently-scheduled XLA program than
    the serial decoder — see the transformer_speculative_generate
    docstring). So: walk the plain chain teacher-forced; at the first
    speculative divergence the plain logits' top-2 margin must be
    small (a near-tie), and agreement before it must be total.
    Checked for an adversarial unrelated draft (worst case: near-zero
    acceptance) and the int8w-quantized self (production case)."""
    import functools

    from deeplearning4j_tpu.models.transformer import (
        quantize_decode_params,
        transformer_apply,
        transformer_generate,
        transformer_speculative_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96, n_kv_heads=2, rope=True,
    )
    params = init_transformer(jax.random.key(0), cfg)
    prompt = _tokens(1, 8, seed=11)
    new = 20
    ref = np.asarray(
        jax.jit(functools.partial(
            transformer_generate(cfg), max_new=new, temperature=0.0
        ))(params, prompt, jax.random.key(1))
    )
    apply = jax.jit(transformer_apply(cfg))

    def check(out):
        out = np.asarray(out)
        np.testing.assert_array_equal(out[:, :8], np.asarray(prompt))
        diff = np.nonzero(out[0, 8:] != ref[0, 8:])[0]
        if diff.size == 0:
            return  # bitwise-identical chain
        first = int(diff[0])
        # the full-forward logits at the divergence point: the two
        # candidate tokens must be a near-tie there
        ctx = jnp.asarray(ref[:, : 8 + first])
        logits, _ = apply(params, ctx)
        top2 = np.sort(np.asarray(logits[0, -1], np.float32))[-2:]
        margin = float(top2[1] - top2[0])
        assert margin < 0.05, (
            f"speculative chain left the greedy chain at +{first} with "
            f"a clear margin {margin:.3f} — not a near-tie flip"
        )

    sg = jax.jit(functools.partial(
        transformer_speculative_generate(cfg), max_new=new, draft_k=3,
        temperature=0.0,
    ))
    # adversarial draft: a different random init
    bad_draft = init_transformer(jax.random.key(99), cfg)
    check(sg(params, bad_draft, prompt, jax.random.key(2)))
    # production draft: the int8w-quantized self
    qdraft = quantize_decode_params(params, cfg)
    check(sg(params, qdraft, prompt, jax.random.key(3)))


@pytest.mark.slow
def test_speculative_sampled_determinism_and_guards():
    import functools

    from deeplearning4j_tpu.models.transformer import (
        quantize_decode_params,
        transformer_speculative_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96,
    )
    params = init_transformer(jax.random.key(7), cfg)
    qdraft = quantize_decode_params(params, cfg)
    sg = jax.jit(functools.partial(
        transformer_speculative_generate(cfg), max_new=24, draft_k=4,
        temperature=1.0, top_k=8,
    ))
    prompt = _tokens(1, 6, seed=7)
    a = np.asarray(sg(params, qdraft, prompt, jax.random.key(1)))
    b = np.asarray(sg(params, qdraft, prompt, jax.random.key(1)))
    c = np.asarray(sg(params, qdraft, prompt, jax.random.key(2)))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    assert a.shape == (1, 30)
    assert ((a >= 0) & (a < cfg.vocab_size)).all()
    # the prompt passes through untouched
    np.testing.assert_array_equal(a[:, :6], np.asarray(prompt))
    # ragged-batch guard
    with pytest.raises(ValueError, match="B=1"):
        transformer_speculative_generate(cfg)(
            params, qdraft, _tokens(2, 6, seed=7), jax.random.key(0), 4
        )


@pytest.mark.slow
def test_speculative_acceptance_efficiency_with_identical_draft():
    """With draft == target (same params, dense fallback both sides),
    greedy acceptance must be perfect: max_new tokens in
    ceil(max_new/(k+1)) rounds. This pins the draft-cache catch-up
    chunk — before it, every fully-accepted round left a permanent
    zero KV row (the sampled-but-never-fed d_k) in the draft cache,
    silently eroding acceptance while outputs stayed exact."""
    import functools

    from deeplearning4j_tpu.models.transformer import (
        transformer_speculative_generate,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=96, n_kv_heads=2, rope=True,
        decode_kernel=False,  # draft numerics == verify-chunk numerics
    )
    params = init_transformer(jax.random.key(0), cfg)
    k, new = 4, 30
    sg = jax.jit(functools.partial(
        transformer_speculative_generate(cfg), max_new=new, draft_k=k,
        temperature=0.0, return_stats=True,
    ))
    out, stats = sg(params, params, _tokens(1, 8, seed=5), jax.random.key(1))
    assert out.shape == (1, 38)
    # perfect acceptance: 30 tokens, 5 per round (k accepted + bonus)
    assert int(stats["rounds"]) == -(-new // (k + 1)), int(stats["rounds"])


def test_speculative_acceptance_math_matches_target_distribution():
    """The rejection-sampling identity the in-graph round implements:
    draft d~q, accept iff u*q[d] < p[d], else emit from max(p-q,0)/Z —
    the emitted marginal must equal p exactly (Leviathan et al. thm 1).
    Validated by Monte Carlo with the same division-free formulas."""
    rng = np.random.default_rng(0)
    v, n = 6, 200_000
    p = rng.dirichlet(np.ones(v))
    q = rng.dirichlet(np.ones(v))
    d = rng.choice(v, size=n, p=q)
    u = rng.uniform(size=n)
    accept = u * q[d] < p[d]
    resid = np.maximum(p - q, 0)
    resid = resid / resid.sum()
    out = np.where(accept, d, rng.choice(v, size=n, p=resid))
    emp = np.bincount(out, minlength=v) / n
    assert np.abs(emp - p).sum() < 0.02, (emp, p)


@pytest.mark.slow
def test_sampled_generate_is_deterministic_per_key_and_respects_top_k():
    from deeplearning4j_tpu.models.transformer import transformer_generate

    params = init_transformer(jax.random.key(21), CFG)
    gen = transformer_generate(CFG)
    prompt = _tokens(2, 4, seed=21)
    a = gen(params, prompt, jax.random.key(1), 8, temperature=1.0, top_k=5)
    b = gen(params, prompt, jax.random.key(1), 8, temperature=1.0, top_k=5)
    c = gen(params, prompt, jax.random.key(2), 8, temperature=1.0, top_k=5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()
    assert np.asarray(a).max() < CFG.vocab_size
    # top_k=1 collapses sampling to greedy regardless of key — this fails
    # if the top-k filter is inverted or dropped
    g1 = gen(params, prompt, jax.random.key(3), 8, temperature=1.0, top_k=1)
    g2 = gen(params, prompt, jax.random.key(4), 8, temperature=1.0, top_k=1)
    greedy = gen(params, prompt, jax.random.key(5), 8, temperature=0)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(greedy))


@pytest.mark.slow
def test_moe_generate_matches_full_forward(devices):
    # the decode path's per-token MoE must run the SAME model (activation
    # included) as the trained moe_ffn path
    from deeplearning4j_tpu.models.transformer import transformer_generate

    cfg = _cfg(n_experts=4, moe_capacity_factor=8.0)
    mesh = mesh_lib.dp_mp_mesh(2, 4)
    params = init_transformer(jax.random.key(22), cfg)
    gen = transformer_generate(cfg)
    prompt = _tokens(2, 4, seed=22)
    out = gen(params, prompt, jax.random.key(0), 4, temperature=0)
    assert out.shape == (2, 8)
    apply = jax.jit(transformer_apply(cfg, mesh))
    p_sharded = place_transformer_params(mesh, params, cfg)
    seq = prompt
    for _ in range(4):
        logits, _ = apply(p_sharded, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_flash_attention_transformer_matches_dense():
    cfg_flash = _cfg(use_flash=True)
    params = init_transformer(jax.random.key(40), CFG)
    toks = _tokens(2, 16, seed=40)
    y_dense, _ = transformer_apply(CFG)(params, toks)
    y_flash, _ = transformer_apply(cfg_flash)(params, toks)
    np.testing.assert_allclose(
        np.asarray(y_dense), np.asarray(y_flash), atol=2e-4
    )
    # gradients flow through the custom-vjp flash backward
    g = jax.grad(transformer_loss(cfg_flash))(params, _tokens(2, 17, seed=41))
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(g))


def test_generate_from_empty_prompt():
    """Bulk prefill must keep the round-1 contract: an empty prompt
    decodes from uniform logits instead of crashing on x[:, -1]."""
    from deeplearning4j_tpu.models.transformer import transformer_generate

    params = init_transformer(jax.random.key(60), CFG)
    out = transformer_generate(CFG)(
        params, jnp.zeros((2, 0), jnp.int32), jax.random.key(0), 4
    )
    assert out.shape == (2, 4)
    assert ((out >= 0) & (out < CFG.vocab_size)).all()


def test_flash_block_sizes_divide_any_legal_seq_len():
    """T only has to be a multiple of 128 — the block-size picker must
    not hand the kernel a block that doesn't divide T (T=1536 crashed
    when blocks were hardcoded 512/1024)."""
    import dataclasses

    cfg = dataclasses.replace(_cfg(use_flash=True), max_len=1537)
    params = init_transformer(jax.random.key(41), cfg)
    toks = _tokens(1, 1536, seed=42)
    y, _ = transformer_apply(cfg)(params, toks)
    assert np.isfinite(np.asarray(y)).all()


def test_beam_search_width1_equals_greedy():
    from deeplearning4j_tpu.models.transformer import (
        transformer_beam_search,
        transformer_generate,
    )

    params = init_transformer(jax.random.key(50), CFG)
    prompt = _tokens(2, 5, seed=50)
    greedy = transformer_generate(CFG)(
        params, prompt, jax.random.key(0), 6, temperature=0
    )
    beams, scores = transformer_beam_search(CFG)(params, prompt, 1, 6)
    np.testing.assert_array_equal(np.asarray(beams[:, 0]), np.asarray(greedy))
    assert np.isfinite(np.asarray(scores)).all()


@pytest.mark.slow
def test_beam_search_finds_higher_likelihood_than_greedy():
    from deeplearning4j_tpu.models.transformer import (
        transformer_beam_search,
        transformer_generate,
    )

    params = init_transformer(jax.random.key(51), CFG)
    prompt = _tokens(2, 4, seed=51)
    apply = transformer_apply(CFG)

    def seq_logprob(seq, tp):
        logits, _ = apply(params, seq[:, :-1])
        lp = jax.nn.log_softmax(logits, axis=-1)
        tgt = seq[:, 1:]
        tok_lp = jnp.take_along_axis(lp, tgt[:, :, None], axis=2)[..., 0]
        return jnp.sum(tok_lp[:, tp - 1 :], axis=1)  # new tokens only

    greedy = transformer_generate(CFG)(
        params, prompt, jax.random.key(0), 6, temperature=0
    )
    beams, scores = transformer_beam_search(CFG)(params, prompt, 4, 6)
    # scores sorted best-first and consistent with the true sequence
    # log-likelihood of the best beam
    s = np.asarray(scores)
    assert (np.diff(s, axis=1) <= 1e-5).all()
    best_lp = np.asarray(seq_logprob(beams[:, 0], 4))
    np.testing.assert_allclose(s[:, 0], best_lp, atol=1e-4)
    greedy_lp = np.asarray(seq_logprob(greedy, 4))
    assert (best_lp >= greedy_lp - 1e-5).all()


def test_bf16_compute_runs_and_is_close():
    cfg_bf16 = TransformerConfig(**{
        **CFG.__dict__, "compute_dtype": jnp.bfloat16
    })
    params = init_transformer(jax.random.key(4), CFG)
    toks = _tokens(2, 12, seed=4)
    y32, _ = transformer_apply(CFG)(params, toks)
    y16, _ = transformer_apply(cfg_bf16)(params, toks)
    assert y16.dtype == jnp.float32  # logits promoted for stable softmax
    assert float(jnp.mean(jnp.abs(y32 - y16))) < 0.1


@pytest.mark.slow
def test_rope_causality_and_decode_parity():
    from deeplearning4j_tpu.models.transformer import transformer_generate

    cfg = _cfg(rope=True)
    params = init_transformer(jax.random.key(60), cfg)
    apply = transformer_apply(cfg)
    toks = _tokens(2, 16, seed=60)
    logits, _ = apply(params, toks)
    # causality still holds with rotated q/k
    toks2 = toks.at[:, 10].set((toks[:, 10] + 1) % CFG.vocab_size)
    logits2, _ = apply(params, toks2)
    np.testing.assert_allclose(
        np.asarray(logits[:, :10]), np.asarray(logits2[:, :10]), atol=1e-5
    )
    # KV-cache decode applies the same rotation as the full forward
    prompt = toks[:, :5]
    out = transformer_generate(cfg)(
        params, prompt, jax.random.key(0), 6, temperature=0
    )
    seq = prompt
    for _ in range(6):
        lg, _ = apply(params, seq)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_rope_dot_products_depend_only_on_relative_offset():
    # the core RoPE property: <rope(q, m), rope(k, n)> is a function of
    # (m - n) only — shifting both positions by the same amount leaves
    # every attention logit unchanged
    from deeplearning4j_tpu.models.transformer import (
        _apply_rope,
        _rope_tables,
    )

    rng = np.random.default_rng(61)
    hd = 16
    q = jnp.asarray(rng.normal(size=(hd,)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(hd,)).astype(np.float32))

    def dot_at(m, n):
        cq, sq = _rope_tables(jnp.asarray(m), hd, jnp.float32)
        ck, sk = _rope_tables(jnp.asarray(n), hd, jnp.float32)
        return float(_apply_rope(q, cq, sq) @ _apply_rope(k, ck, sk))

    for m, n in ((3, 1), (7, 0), (5, 5)):
        for shift in (1, 11, 100):
            np.testing.assert_allclose(
                dot_at(m, n), dot_at(m + shift, n + shift), rtol=1e-5
            )
    # and it genuinely varies with the offset (not constant)
    assert abs(dot_at(3, 1) - dot_at(6, 1)) > 1e-4


def test_rope_rejects_odd_head_dim():
    cfg = TransformerConfig(d_model=96, n_heads=32, rope=True)
    with pytest.raises(ValueError, match="even head_dim"):
        transformer_apply(cfg)


@pytest.mark.slow
def test_gqa_forward_decode_and_tp_parity(devices):
    from deeplearning4j_tpu.models.transformer import transformer_generate

    cfg = _cfg(n_kv_heads=2, rope=True)  # 4 q heads, 2 kv heads
    params = init_transformer(jax.random.key(70), cfg)
    apply = transformer_apply(cfg)
    toks = _tokens(2, 16, seed=70)
    logits, _ = apply(params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # causality
    toks2 = toks.at[:, 10].set((toks[:, 10] + 1) % cfg.vocab_size)
    logits2, _ = apply(params, toks2)
    np.testing.assert_allclose(
        np.asarray(logits[:, :10]), np.asarray(logits2[:, :10]), atol=1e-5
    )
    # KV-cache decode (cache holds only 2 kv heads) == full forward
    prompt = toks[:, :5]
    out = transformer_generate(cfg)(
        params, prompt, jax.random.key(0), 6, temperature=0
    )
    seq = prompt
    for _ in range(6):
        lg, _ = apply(params, seq)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(seq.dtype)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))
    # TP over 2 model shards (2 kv heads -> 1 per shard) matches replicated
    mesh = mesh_lib.dp_mp_mesh(4, 2)
    y_tp, _ = jax.jit(transformer_apply(cfg))(
        place_transformer_params(mesh, params, cfg), toks
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(y_tp), atol=2e-4
    )


@pytest.mark.slow
def test_gqa_training_learns(devices):
    mesh = mesh_lib.dp_mp_mesh(4, 2)
    cfg = _cfg(n_kv_heads=2)
    step, init_state, shard_tokens = transformer_train_step(mesh, cfg)
    params, opt_state = init_state(jax.random.key(71))
    toks = shard_tokens(_tokens(8, 17, seed=71))
    losses = []
    for _ in range(30):
        params, opt_state, l = step(params, opt_state, toks)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses[::10]


def test_gqa_rejects_indivisible_heads():
    # validated at config construction, shared by every entry point
    with pytest.raises(ValueError, match="must divide"):
        _cfg(n_kv_heads=3)


def test_mqa_tp_replicated_kv(devices):
    # MQA (1 kv head) on 2-way TP: wkv replicated, outputs still match
    cfg = _cfg(n_kv_heads=1)
    params = init_transformer(jax.random.key(72), cfg)
    toks = _tokens(2, 16, seed=72)
    y_rep, _ = transformer_apply(cfg)(params, toks)
    mesh = mesh_lib.dp_mp_mesh(4, 2)
    y_tp, _ = jax.jit(transformer_apply(cfg))(
        place_transformer_params(mesh, params, cfg), toks
    )
    np.testing.assert_allclose(
        np.asarray(y_rep), np.asarray(y_tp), atol=2e-4
    )


@pytest.mark.slow
def test_lm_optimizer_trains_with_warmup_and_clipping(devices):
    from deeplearning4j_tpu.models.transformer import lm_optimizer

    mesh = mesh_lib.dp_mp_mesh(2, 4)
    step, init_state, shard_tokens = transformer_train_step(
        mesh, CFG, optimizer=lm_optimizer(peak_lr=1e-3, total_steps=40)
    )
    params, opt_state = init_state(jax.random.key(80))
    toks = shard_tokens(_tokens(8, 17, seed=80))
    losses = []
    for _ in range(40):
        params, opt_state, l = step(params, opt_state, toks)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_config_json_roundtrip():
    """TransformerConfig serializes like the rest of the framework's
    configs (nn/conf.py ≙ NeuralNetConfiguration.toJson) — dtypes by
    name, every field preserved."""
    cfg = TransformerConfig(
        d_model=64, n_heads=4, n_kv_heads=2, use_flash=True, rope=True,
        compute_dtype=jnp.bfloat16, n_experts=0, remat=True,
        scan_layers=False,
    )
    again = TransformerConfig.from_json(cfg.to_json())
    assert again == cfg
    assert again.compute_dtype == jnp.bfloat16


# -- the training block's flash layout (PR 32) -------------------------------

# MHA, 2 heads x 64: one whole 128-lane group
PACKED = TransformerConfig(
    vocab_size=64, d_model=128, n_heads=2, n_layers=2, d_ff=128,
    max_len=65, use_flash=True, scan_layers=False,
)


def _replace(cfg, **over):
    import dataclasses

    return dataclasses.replace(cfg, **over)


@pytest.mark.parametrize("over,want", [
    ({}, "packed"),
    ({"n_heads": 4, "n_kv_heads": 2}, "bhtd"),                 # GQA
    ({"rope": True}, "bhtd"),                                  # rotary
    ({"use_flash": False, "sequence_parallel": True}, "bhtd"),
    ({"d_model": 64, "n_heads": 4}, "bhtd"),  # heads of 16: 64 lanes in all
    ({"d_model": 192, "n_heads": 3}, "bhtd"),  # 1.5 lane groups
    ({"d_model": 256, "n_heads": 8}, "packed"),  # four heads of 32 a group
], ids=["mha-2x64", "gqa", "rope", "sequence-parallel", "64-wide",
        "3x64", "8x32"])
def test_flash_layout_is_packed_only_where_whole_lane_groups_of_heads(
        over, want):
    from deeplearning4j_tpu.models.transformer import flash_layout

    assert flash_layout(_replace(PACKED, **over)) == want


def test_flash_layout_counts_each_device_s_lanes(devices):
    from deeplearning4j_tpu.models.transformer import flash_layout

    assert flash_layout(PACKED, mesh_lib.dp_mp_mesh(2, 1)) == "packed"
    # 2 heads x 64 over a model axis of 2: 64 lanes a device
    assert flash_layout(PACKED, mesh_lib.dp_mp_mesh(1, 2)) == "bhtd"
    wide = _replace(PACKED, d_model=256, n_heads=4)
    assert flash_layout(wide, mesh_lib.dp_mp_mesh(1, 2)) == "packed"


def _as_bhtd(monkeypatch):
    """Send the same configuration through the (B, H, T, K) entry."""
    from deeplearning4j_tpu.models import transformer

    monkeypatch.setattr(
        transformer, "flash_layout", lambda cfg, mesh=None: "bhtd")


@pytest.mark.parametrize("with_mesh", [False, True], ids=["bare", "mesh-1x1"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_packed_block_matches_the_bhtd_block(remat, with_mesh, monkeypatch):
    """Loss and every gradient leaf of the packed block against the same
    configuration through the ``bhtd`` block (and the loss against dense
    attention), with and without ``remat``, bare and under the trainer's
    ``shard_map`` on ``dp_mp_mesh(1, 1)``."""
    cfg = _replace(PACKED, remat=remat)
    mesh = mesh_lib.dp_mp_mesh(1, 1) if with_mesh else None
    params = init_transformer(jax.random.key(32), cfg)
    toks = _tokens(2, 65, seed=32)
    l_p, g_p = jax.value_and_grad(transformer_loss(cfg, mesh))(params, toks)
    l_d = transformer_loss(_replace(cfg, use_flash=False), mesh)(params, toks)
    with monkeypatch.context() as m:
        _as_bhtd(m)
        l_b, g_b = jax.value_and_grad(transformer_loss(cfg, mesh))(
            params, toks)
    np.testing.assert_allclose(float(l_p), float(l_b), atol=2e-4)
    np.testing.assert_allclose(float(l_p), float(l_d), atol=2e-4)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_p), jax.tree.leaves(g_b)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=str(path))


def _activation_transposes(cfg, monkeypatch, t: int) -> list[str]:
    """``stablehlo.transpose`` lines of the train step lowered for the
    TPU (kernels compiled: Mosaic calls, not interpreted ops) whose
    result has four or more dimensions, one of them the sequence: the
    layout moves of q, k, v, o and their cotangents."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_default_interpret", lambda: False)
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), cfg))
    text = jax.jit(jax.value_and_grad(transformer_loss(cfg))).trace(
        params, jax.ShapeDtypeStruct((4, t + 1), jnp.int32)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    moves = []
    for line in text.splitlines():
        if "stablehlo.transpose" not in line:
            continue
        dims = line.rsplit("tensor<", 1)[1].split(">")[0].split("x")[:-1]
        if len(dims) >= 4 and str(t) in dims:
            moves.append(line.strip())
    return moves


def test_packed_train_step_transposes_no_activation(monkeypatch):
    """Between the QKV products and the kernels, and between the kernels
    and ``wo``, forward and backward, the packed step moves no
    activation; the ``bhtd`` step of the same configuration does (the
    test can fail)."""
    t = 256  # unlike every width of the configuration
    cfg = _replace(PACKED, max_len=t + 1, remat=True)
    assert _activation_transposes(cfg, monkeypatch, t) == []
    _as_bhtd(monkeypatch)
    assert len(_activation_transposes(cfg, monkeypatch, t)) >= 6


def test_packed_block_loads_a_tree_saved_before_it(tmp_path):
    """The leaves keep the shapes ``init_transformer`` gave them before
    PR 32 (``wqkv`` (layers, d, 3, heads, head size), ``wo`` (layers,
    heads, head size, d)): a tree written then goes through the packed
    block as it is."""
    nl, d, h, k, f, v = 2, 128, 2, 64, 128, 64
    before = {
        "embed": (v, d), "pos": (65, d), "lnf_scale": (d,), "lnf_bias": (d,),
        "head": (d, v),
        "blocks/ln1_scale": (nl, d), "blocks/ln1_bias": (nl, d),
        "blocks/wqkv": (nl, d, 3, h, k), "blocks/wo": (nl, h, k, d),
        "blocks/ln2_scale": (nl, d), "blocks/ln2_bias": (nl, d),
        "blocks/w1": (nl, d, f), "blocks/b1": (nl, f),
        "blocks/w2": (nl, f, d), "blocks/b2": (nl, d),
    }
    rng = np.random.default_rng(5)
    np.savez(tmp_path / "saved.npz", **{
        name: rng.normal(size=shape).astype(np.float32) * 0.05
        for name, shape in before.items()
    })
    params = {"blocks": {}}
    with np.load(tmp_path / "saved.npz") as saved:
        for name in saved.files:
            where, _, leaf = name.rpartition("/")
            (params[where] if where else params)[leaf] = jnp.asarray(
                saved[name])
    fresh = init_transformer(jax.random.key(0), PACKED)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, fresh)
    loss = transformer_loss(PACKED)(params, _tokens(2, 65, seed=6))
    assert np.isfinite(float(loss))


def test_packed_block_on_a_dp_tp_mesh_matches_one_device(devices):
    """Four heads of 64 over a model axis of 2: each device's kernel
    takes its own 128-lane group of the (B, T, H*K) activations, and the
    loss and gradients are the unsharded block's."""
    from deeplearning4j_tpu.models.transformer import flash_layout

    cfg = _replace(PACKED, d_model=256, n_heads=4)
    mesh = mesh_lib.dp_mp_mesh(2, 2)
    assert flash_layout(cfg, mesh) == "packed"
    params = init_transformer(jax.random.key(33), cfg)
    toks = _tokens(4, 65, seed=33)
    l_1, g_1 = jax.value_and_grad(transformer_loss(cfg))(params, toks)
    l_m, g_m = jax.jit(jax.value_and_grad(transformer_loss(cfg, mesh)))(
        place_transformer_params(mesh, params, cfg), toks)
    np.testing.assert_allclose(float(l_m), float(l_1), atol=2e-4)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_m), jax.tree.leaves(g_1)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=str(path))
