"""Benchmark harness: model training throughput + MFU.

Default (no ``--model``): runs EVERY workload and prints one JSON line
per workload — the driver's round record captures all of them:

- ``lenet``       LeNet-MNIST samples/sec/chip (f32, reference parity dtype)
- ``alexnet``     AlexNet-CIFAR10 samples/sec/chip (bf16 mixed)
- ``resnet``      ResNet-20 CIFAR samples/sec/chip (bf16, BN state
                  threaded through the scanned step)
- ``word2vec``    hierarchical-softmax kernel pairs/sec/chip
- ``transformer`` GPT-2-small-class LM (d768/12L/6H/T1024/V50304, bf16,
                  flash attention + selective remat) tokens/sec/chip with
                  an analytic-FLOPs ``mfu`` field. Head geometry is
                  TPU-first: 6 heads x d_head=128 (not GPT-2's 12 x 64)
                  — d_head=128 fills the MXU's 128-deep contraction;
                  identical d_model/params/FLOPs-per-token, measured
                  +26% MFU (PERF.md r4)
- ``transformer-flash-8k`` long-context flash workload (T=8192,
                  4 heads x d_head=128) so regressions in the pallas
                  kernel path are visible
- ``transformer-decode`` KV-cached sampling (bulk prefill + 64 decode
                  steps, B=16) — serving-convention tokens/sec/chip
- ``transformer-decode-b64`` the same at serving batch 64 (the
                  throughput point; weight stream amortized 4x)
- ``transformer-decode-int8`` / ``-b64-int8`` the int8 serving path
                  (weight-only int8 params + int8 KV cache with
                  per-row scales) — halves both HBM streams the bf16
                  decode wall analysis bounds (PERF.md)
- ``transformer-decode-gqa`` / ``-gqa-b64`` / ``-gqa-b64-int8`` the
                  production decode geometry (6 query heads over 2 KV
                  heads + RoPE): 3x smaller cache stream; the -int8
                  composite is the headline serving point
- ``transformer-decode-gqa-int8w`` / ``-gqa-b64-int8w`` weight-only
                  int8 over the bf16 GQA cache (the split PERF.md's r5
                  crossover analysis predicts as the winning composite:
                  halve the weight stream, keep the cheap bf16 cache
                  kernel)
- ``transformer-decode-gqa-b1`` / ``-gqa-b1-int8w`` the interactive-
                  latency point (batch 1): the step is almost purely the
                  weight stream, so this row isolates what quantization
                  buys a single-user session
- ``transformer-decode-gqa-8kctx`` / ``-8kctx-int8`` long-context
                  serving (prefill 8192 + 256 decode steps, B=16).
                  Adding the row surfaced (and fixed, +24.6%) the
                  decode kernel's short-T-tuned block cap; with the
                  VMEM-driven policy the int8-cache row still REFUTES
                  the r5 prediction that quantization pays most here:
                  bf16 sustains MBU 0.54 at 8k and the int8 kernel's
                  per-cell quantize/rescale work outruns its byte
                  savings — net 20% loss (PERF.md "8k-context
                  serving")
- ``transformer-decode-gqa-b1-spec`` speculative decoding at B=1:
                  the int8w-quantized self drafts k tokens, the bf16
                  target verifies them in one chunked forward, rejection
                  sampling keeps the output a bf16-target-distribution
                  sample (exact w.r.t. the verify program — see the
                  model docstring) — the distribution-preserving
                  version of the int8w latency win
- ``transformer-flash-32k`` long-context training at T=32768 (B=1) —
                  the regime where dense attention cannot compile
- ``transformer-decode-serve`` continuous-batching serving under a
                  seeded pseudo-Poisson arrival trace (aggregate tok/s
                  + TTFT p50/p99 + slot occupancy)
- ``transformer-decode-serve-faults`` the same offered load with a
                  seeded FaultInjector raising transient faults at a
                  fixed 2% per-boundary rate: prices the supervised
                  retry/backoff path and pins that throughput
                  degradation under faults is bounded
                  (``degradation_frac`` vs the clean replay in-row)
- ``transformer-decode-serve-prefix`` the serve trace with a swept
                  fraction of requests sharing one long prompt prefix,
                  served through the radix-tree prefix cache: headlines
                  TTFT p50 and prefill-tokens-saved, with the
                  cache-off replay in-row pricing what reuse buys
- ``transformer-decode-serve-piggyback`` the 0.5 shared-prefix serve
                  trace with a few injected 8k prompts, served with
                  chunked-prefill piggyback on vs blocking admission:
                  headlines p99 TPOT (decode streams stop stalling
                  behind monolithic prefills), p50/p99 TTFT on-vs-off,
                  and prefill-stall seconds in-row
- ``transformer-decode-serve-grammar`` the production sampling
                  surface: the unconstrained serve trace through the
                  masked decode program (surface armed) vs the plain
                  one — the fold-out overhead unconstrained traffic
                  pays — plus a mixed trace where a quarter of the
                  requests carry a JSON-schema response_format and must
                  emit parsing, validating JSON (validity 1.0 in-row)
- ``transformer-decode-serve-tp`` the serve trace at a fixed global
                  batch with the fused decode program + KV pool sharded
                  over TP in {1,2,4,8} devices: headlines per-chip
                  tok/s and scaling efficiency vs TP=1
- ``transformer-decode-serve-router`` two full serving replicas behind
                  the prefix-affinity router at 0.5 shared-prefix
                  traffic, driven over real HTTP: headlines routed
                  TTFT p50 speedup vs round-robin dispatch
- ``transformer-decode-serve-disagg`` disaggregated prefill/decode:
                  the mixed trace (half 8k prompts, half 512) served by
                  1 prefill + 1 decode behind the fleet controller (KV
                  segments pushed over the wire, seated zero-prefill)
                  vs the same engines as two monolithic replicas
                  behind the router — end-to-end p99 TTFT / p99 TPOT
                  deltas and transfer bytes/s in-row
- ``transformer-decode-serve-tenant`` multi-tenant serving: an
                  adversarial flood (one greedy tenant vs three paced)
                  replayed under deficit-round-robin fair scheduling vs
                  FIFO, reporting victim-tenant p99 normalized latency
                  improvement at equal aggregate throughput; plus a
                  4-adapter batched-LoRA batch vs the same traffic on
                  sequential single-adapter replicas (the S-LoRA/Punica
                  consolidation claim), which is the headline tok/s

``--model X`` runs a single workload. ``--scaling`` reports 1->N-chip
data-parallel efficiency (lenet/alexnet); ``--profile DIR`` captures an
XPlane trace (single-workload mode only).

Run on whatever accelerator the default environment exposes (one TPU chip
under the driver). Each output line is
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N[, "mfu": N]}

The reference publishes no numbers (BASELINE.md), so vs_baseline is the
ratio against the first recorded value of this harness itself (stored in
bench_baseline.json next to this file after the first run on TPU).

MFU = tokens/sec x analytic model FLOPs per token / peak chip FLOP/s,
with training FLOPs counted as 3x forward and causal attention at T/2 —
the standard (PaLM-appendix) accounting; rematerialisation recompute is
deliberately NOT credited. Peak table below; mfu is null off-TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time
from pathlib import Path

BASELINE_FILE = Path(__file__).parent / "bench_baseline.json"

BATCH = 1024
WARMUP = 10
# steps per dispatch for the scanned small workloads: one lax.scan'd
# program long enough that the per-dispatch round-trip is noise next to
# device time
STEPS = 300
MIN_TIMED_SECONDS = 1.0  # repeat until the window is long enough that
# dispatch overhead and timer noise are negligible

#: peak dense matmul FLOP/s per chip (bf16 inputs, f32 accumulation), by
#: jax device_kind prefix. MFU is reported against the bf16 peak — the
#: MXU-native rate — regardless of the workload's dtype, so numbers are
#: comparable across configs.
_PEAK_FLOPS = (
    ("TPU v6", 918e12),   # Trillium
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12),  # v5e
    ("TPU v5", 459e12),
    ("TPU v4", 275e12),
)


#: peak HBM bandwidth per chip (bytes/s), by device_kind prefix — the
#: denominator of MBU (memory-bandwidth utilization) for the decode
#: workload, which is weight/cache-streaming-bound rather than FLOP-bound
_PEAK_HBM_BW = (
    ("TPU v6", 1640e9),   # Trillium
    ("TPU v5p", 2765e9),
    ("TPU v5 lite", 819e9),  # v5e
    ("TPU v5", 2765e9),
    ("TPU v4", 1228e9),
)


def _peak_lookup(table):
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind
    for prefix, peak in table:
        if kind.startswith(prefix):
            return peak
    raise RuntimeError(f"no peak entry for TPU device_kind {kind!r}")


def _peak_flops():
    return _peak_lookup(_PEAK_FLOPS)


def _lm_flops_per_token(d: int, n_layers: int, d_ff: int, vocab: int,
                        seq: int) -> float:
    """Analytic training FLOPs/token for a dense decoder-only LM:
    6 x matmul params (qkv+out 4d^2, mlp 2*d*d_ff per layer, untied head
    d*V) + causal attention 6*T*d per layer (QK^T and AV at T/2 average
    visible length, x3 for fwd+bwd)."""
    per_layer = 4 * d * d + 2 * d * d_ff
    return 6.0 * (n_layers * per_layer + d * vocab) + 6.0 * seq * d * n_layers


# transformer workload presets. Single-chip perf notes (TPU v5e, 2026-07):
# the GPT-2-small config reaches ~40% MFU with flash attention, selective
# remat (dots_no_batch), unrolled layers, B=24; dense attention is
# HBM-bound streaming (B,H,T,T) probs and loses ~25% to flash at T=1024.
_TRANSFORMER_PRESETS = {
    "transformer": dict(
        # n_heads=6 (d_head=128), not GPT-2's 12x64: d_head=64 leaves the
        # 128-deep MXU contraction half-filled in every attention dot.
        # Same d_model/d_ff/params/FLOPs-per-token — the analytic MFU
        # accounting is head-count-invariant — measured 109K -> 137K
        # tok/s (r4). vs_baseline stays an honest same-FLOPs comparison.
        d_model=768, n_layers=12, n_heads=6, d_ff=3072, vocab=50304,
        seq=1024, batch=24, flash=True, remat=True, scan_layers=False,
        # metric base is versioned by shape so the round-1 d256-config
        # baseline key keeps its own history
        metric="transformer_gpt2s_h128",
    ),
    "transformer-flash-8k": dict(
        # wide heads for the same reason as the flagship (4x128 vs 8x64:
        # 174K -> 274K tok/s, r4); remat off — at B=2 the activations
        # fit HBM comfortably and the recompute was 44ms of a 103ms
        # step; unrolled layers — the scan carried ~20ms/step of
        # dynamic-slice/update traffic on the stacked block params
        d_model=512, n_layers=8, n_heads=4, d_ff=2048, vocab=8192,
        seq=8192, batch=2, flash=True, remat=False, scan_layers=False,
        metric="transformer_flash_8k_h128",
    ),
    "transformer-flash-32k": dict(
        # the regime where dense attention cannot even compile (the
        # (B, H, T, T) score tensor alone would be 8GB at B=1): the r4
        # streamed-grid flash kernels with the long-T backward blocks
        # (bwd 512/2048) are the only path. B=1 sizes the no-remat
        # activation footprint to HBM; same h128 head geometry as 8k
        d_model=512, n_layers=8, n_heads=4, d_ff=2048, vocab=8192,
        seq=32768, batch=1, flash=True, remat=False, scan_layers=False,
        metric="transformer_flash_32k_h128",
    ),
}


def _run_window(
    args, run, drain, min_reps: int = 1, windows: int = 1
) -> tuple[int, float]:
    """Shared timing harness: warmup, calibrate reps to >= MIN_TIMED_SECONDS,
    then the (optionally profiled) timed window.

    ``run(i)`` enqueues one unit of work; ``drain()`` forces completion by
    fetching values to the host, which provably drains the device
    queue. Returns (reps, seconds).

    ``windows > 1`` repeats the timed window and returns the FASTEST
    one: external contention only ever slows a window down, so min-of-N
    is the consistent estimator of the code's throughput — the standard
    sustained-throughput convention.
    """
    if args.profile:
        # one window under --profile: a multi-window trace would mix
        # contended windows into the per-op attribution and not match
        # the min-window number the invocation reports
        windows = 1
    run(0)
    drain()
    t0 = time.perf_counter()
    run(1)
    drain()
    once = time.perf_counter() - t0
    reps = max(min_reps, int(MIN_TIMED_SECONDS / max(once, 1e-6)) + 1)

    if args.profile:
        from deeplearning4j_tpu.utils import profiling

        prof = profiling.trace(args.profile)
    else:
        prof = contextlib.nullcontext()
    dts = []
    with prof:
        base = 2
        for w in range(windows):
            t0 = time.perf_counter()
            for r in range(reps):
                run(base + r)
            drain()
            dts.append(time.perf_counter() - t0)
            base += reps
    return reps, min(dts)


def _bench_word2vec(args):
    """Hierarchical-softmax kernel throughput (pairs/sec) — the hot loop
    the reference spends its NLP time in (InMemoryLookupTable.
    iterateSample:171-270, BLAS dot+axpy per Huffman bit); here it is the
    batched scatter-add `_hs_scan`, k folded batches per dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.word2vec import _SCAN_WIDTH, _hs_scan

    batch = args.batch
    v, d, depth = 10_000, 100, 16
    rng = np.random.default_rng(0)
    state = {
        "syn0": jnp.asarray(rng.normal(0, 0.1, (v, d)).astype(np.float32)),
        "syn1": jnp.zeros((v, d), jnp.float32),
    }
    codes = jnp.asarray(rng.integers(0, 2, (v, depth)).astype(np.float32))
    points = jnp.asarray(rng.integers(0, v, (v, depth)).astype(np.int32))
    mask = jnp.asarray(
        (np.arange(depth)[None, :] < rng.integers(8, depth, (v, 1)))
        .astype(np.float32)
    )
    k = _SCAN_WIDTH
    lrs = jnp.full((k,), 0.025, jnp.float32)
    r = np.random.default_rng(1)
    ins = jnp.asarray(r.integers(0, v, (k, batch)).astype(np.int32))
    tgts = jnp.asarray(r.integers(0, v, (k, batch)).astype(np.int32))

    def run(_i):
        state["syn0"], state["syn1"] = _hs_scan(
            state["syn0"], state["syn1"], ins, tgts, codes, points, mask, lrs
        )

    def drain():
        out = np.asarray(state["syn0"][0])
        assert np.isfinite(out).all(), "w2v bench produced non-finite rows"

    reps, dt = _run_window(args, run, drain, windows=4)
    # _hs_scan is a single-device kernel: the per-chip number is the raw
    # rate, NOT divided by the host's chip count
    return k * batch * reps / dt, "word2vec_hs_train_pairs_per_sec_per_chip"


def _verify_flash_grads() -> None:
    """On-TPU grad-parity gate for the fused flash backward (ADVICE r3).

    Two device-side failure modes have no CPU test coverage (interpret
    mode trivially passes): the rmw fallback's dq accumulation across
    NON-consecutive grid revisits, and the dq-partials path's
    (1, 1, block_q, d) plane writes at the production (512, 2048)
    backward blocks. This gate runs flash-vs-dense grads on the real
    device each bench round, once per config: the public-default small
    blocks (rmw fallback, >= 4 revisits) and the exact bwd geometry the
    long-context workload trains with (partials, bwd 512/2048).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        return

    from deeplearning4j_tpu.ops.attention import attention
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_trainable

    rng = np.random.default_rng(0)

    def check(label, t, heads, d, kw):
        q, k, v = (
            jnp.asarray(
                rng.normal(size=(1, t, heads, d)).astype(np.float32) * 0.5
            )
            for _ in range(3)
        )

        def loss_flash(q, k, v):
            o = flash_attention_trainable(q, k, v, causal=True, **kw)
            return jnp.sum(o * jnp.sin(o))

        def loss_dense(q, k, v):
            o = attention(q, k, v, causal=True)
            return jnp.sum(o * jnp.sin(o))

        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        # oracle at full matmul precision: default-precision dense
        # carries the same bf16 MXU noise as the kernel (measured: both
        # ~5e-3 from each other and from the f32 oracle), so a
        # flash-vs-default comparison can't separate noise from
        # corruption
        with jax.default_matmul_precision("highest"):
            gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip(("dQ", "dK", "dV"), gf, gd):
            err = float(jnp.max(jnp.abs(a - b)))
            scale = float(jnp.max(jnp.abs(b)))
            # a dropped/doubled dq KV-block contribution shows up at
            # grad scale; MXU rounding sits ~100x below this threshold
            if not err < 0.02 * scale + 0.01:
                raise AssertionError(
                    f"flash backward {name} diverges from dense autodiff "
                    f"({label}: max abs err {err:.2e}, grad scale "
                    f"{scale:.2e}) — the dq accumulation path may have "
                    "broken; do not trust flash training numbers"
                )

    # n_k = 16 > 8 forces the rmw fallback (partials would need a 16-
    # plane dq buffer); this is the branch with the undocumented
    # non-consecutive-revisit HBM accumulation
    check("rmw-fallback T=2048 blocks 128", 2048, 2, 64,
          dict(block_q=128, block_k=128))
    # the long-context production geometry: d_head=128, fwd 1024/1024,
    # bwd 512/2048 partials (n_k=2 planes)
    check("partials T=4096 bwd 512/2048", 4096, 2, 128,
          dict(block_q=1024, block_k=1024,
               bwd_block_q=512, bwd_block_k=2048))


def _bench_transformer(args, preset_name: str):
    """LM training throughput (tokens/sec/chip) + MFU for a transformer
    preset.

    Single-chip path:
    - params stay UNSHARDED (no mesh / NamedSharding) and the step is
      hand-rolled here, not ``transformer_train_step``. chip_smoke.py
      times both forms on the chip (PERF.md "Bring-up on v5e"); whether
      this one stays is the benchmark issue's decision;
    - one optimizer step per dispatch with donated state, NOT a lax.scan
      over steps: scanning the train step copies the ~2GB params+opt
      carry every iteration (~200ms/step of pure HBM copies, 2026-07
      record). Async dispatch pipelines the per-step launches.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import functools

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
        transformer_loss,
    )

    p = dict(_TRANSFORMER_PRESETS[preset_name])
    if args.flash is not None:
        p["flash"] = args.flash
    if preset_name == "transformer-flash-8k" and p["flash"]:
        # grad-parity gate on the device before trusting flash numbers
        _verify_flash_grads()
    seq, batch, vocab = p["seq"], p["batch"], p["vocab"]
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=p["d_model"], n_heads=p["n_heads"],
        n_layers=p["n_layers"], d_ff=p["d_ff"], max_len=seq + 1,
        use_flash=p["flash"], remat=p["remat"],
        scan_layers=p["scan_layers"],
        compute_dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
    )
    loss_fn = transformer_loss(cfg)
    optimizer = optax.adamw(3e-4)
    params = init_transformer(jax.random.key(0), cfg)
    opt_state = optimizer.init(params)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, toks):
        l, g = jax.value_and_grad(loss_fn)(params, toks)
        updates, opt_state = optimizer.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    holder = {"s": (params, opt_state), "l": None}

    def run(_i):
        p_, o_, l = step(holder["s"][0], holder["s"][1], toks)
        holder["s"] = (p_, o_)
        holder["l"] = l

    def drain():
        out = float(holder["l"])
        assert np.isfinite(out), "transformer bench loss non-finite"

    # per-dispatch work is one step (~100-250ms device time); require
    # enough pipelined steps that the first dispatch's latency is
    # amortized into the window
    reps, dt = _run_window(args, run, drain, min_reps=15)
    tokens_per_sec = batch * seq * reps / dt
    fpt = _lm_flops_per_token(
        p["d_model"], p["n_layers"], p["d_ff"], vocab, seq
    )
    peak = _peak_flops()
    mfu = (tokens_per_sec * fpt / peak) if peak else None
    return tokens_per_sec, f"{p['metric']}_train_tokens_per_sec_per_chip", mfu


_INT8_GATES_RAN = set()


def _verify_int8_decode(weights_only: bool = False,
                        gqa: bool = False) -> None:
    """On-TPU parity gate for the int8 serving paths: greedy logits from
    the quantized program must stay within a few percent of the bf16
    reference on a small config before any int8 throughput number is
    trusted. ``weights_only`` gates the int8-weights/bf16-cache split
    (decode_int8 stays False — the bf16 kernel path reads dequantized
    weights); default gates the fully-quantized path (weights + int8 KV
    cache). ``gqa`` gates the grouped geometry (groups=3 + RoPE): the
    rewritten kernel's wide-dot group batching is a distinct lowered
    path from MHA's, so the GQA presets must not ride an MHA-only gate.
    Mirrors the flash-grad gate: interpret-mode CPU tests cannot
    observe device-side kernel drift. Deterministic, so each mode runs
    once per process — remeasure attempts must not re-pay its
    compile+run cost."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = (weights_only, gqa)
    if key in _INT8_GATES_RAN or jax.devices()[0].platform != "tpu":
        return
    _INT8_GATES_RAN.add(key)

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        _decode_builder,
        init_transformer,
        quantize_decode_params,
    )

    # the GQA gate runs the production group shape (6 heads over 2 KV
    # heads, groups=3) so the kernel's grouped wide-dot path is the one
    # being checked; d_model keeps head_dim integral (384/6 = 64)
    cfg = TransformerConfig(
        vocab_size=256, d_model=384 if gqa else 256,
        n_heads=6 if gqa else 2, n_kv_heads=2 if gqa else None,
        rope=gqa, n_layers=2, d_ff=512, max_len=160,
        compute_dtype=jnp.bfloat16,
    )
    params = init_transformer(jax.random.key(0), cfg)
    qparams = quantize_decode_params(params, cfg)
    cfg_q = dataclasses.replace(cfg, decode_int8=not weights_only)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 128)).astype(np.int32)
    )

    def last_logits(c, pp, tok=None):
        f1, ic, pf, cp = _decode_builder(c)

        @jax.jit
        def run(pr, tok):
            caches, lg = pf(cp(pp), ic(4, 136), pr)
            if tok is None:
                # the reference path picks the continuation token; the
                # quantized path must be fed the SAME token, or an
                # argmax tie-flip on near-uniform random-init logits
                # would compare logits of two different contexts
                tok = jnp.argmax(lg, -1).astype(jnp.int32)
            lg2, _ = f1(cp(pp), caches, tok, 128)
            return lg, lg2, tok

        return run(prompt, tok)

    ref_pre, ref_step, tok = last_logits(cfg, params)
    got_pre, got_step, _ = last_logits(cfg_q, qparams, tok=tok)
    ref = (ref_pre, ref_step)
    got = (got_pre, got_step)
    for name, a, b in zip(("prefill", "decode-step"), got, ref):
        err = float(jnp.max(jnp.abs(a - b)))
        scale = float(jnp.max(jnp.abs(b)))
        if not err < 0.08 * scale + 0.02:
            mode = "int8w" if weights_only else "int8"
            raise AssertionError(
                f"{mode} decode {name} logits diverge from bf16 "
                f"(max abs err {err:.3e}, scale {scale:.3e}) — do not "
                f"trust {mode} serving numbers"
            )


#: serving bench geometry: bulk prefill + sampled decode steps per call
_DECODE_PROMPT_LEN, _DECODE_NEW = 512, 64


def _decode_bench_cfg(args, batch: int, gqa: bool, int8: str = "off",
                      prompt_len: int = _DECODE_PROMPT_LEN,
                      new: int = _DECODE_NEW):
    """ONE construction of the serving-bench model config + prompt,
    shared by the plain/int8 decode rows and the speculative row — so
    the spec row measures exactly the geometry of the rows it is
    documented as directly comparable to (a drift here would silently
    compare different models). Returns (cfg, prompt, preset)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    p = _TRANSFORMER_PRESETS["transformer"]
    flash = p["flash"] if args.flash is None else args.flash
    cfg = TransformerConfig(
        vocab_size=p["vocab"], d_model=p["d_model"], n_heads=p["n_heads"],
        n_layers=p["n_layers"], d_ff=p["d_ff"],
        max_len=prompt_len + new + 1,
        # flash is honored by the bulk-prefill path (every preset's
        # prompt_len — 512 default, 8192 longctx — satisfies the
        # kernel's %128 alignment); the per-token decode steps use the
        # KV-cache path either way
        use_flash=flash,
        compute_dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
        decode_int8=(int8 == "full"),
        n_kv_heads=2 if gqa else None,
        rope=gqa,
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, p["vocab"], (batch, prompt_len)).astype(np.int32)
    )
    return cfg, prompt, p


def _bench_decode(args, batch: int = 16, metric_suffix: str = "",
                  int8: str = "off", gqa: bool = False,
                  prompt_len: int = _DECODE_PROMPT_LEN,
                  new: int = _DECODE_NEW):
    """KV-cached autoregressive decode throughput on the GPT-2-small
    config: bulk prefill (``prompt_len``, default 512; 8192 for the
    8kctx rows) + ``new`` sampled steps (default 64; 256 for 8kctx —
    enough that the cache stream dominates the window) per call, all
    inside one jitted program. Reported rate counts only the NEW tokens
    (prefill attributed as overhead — the conservative convention), so
    the number is directly the serving-side tokens/sec/chip.

    ``batch=16`` is the round-1 workload definition (latency-leaning);
    the ``-b64`` variant is the throughput-serving point, where the
    weight stream amortizes over 4x the tokens. ``int8="full"`` is the
    fully-quantized serving path (r5): weight-only int8 params
    (per-output-channel scales, dequant fused into the matmul reads)
    plus an int8 KV cache with per-row scales dequantized in-register
    by the decode kernel — the two streams the decode wall analysis
    (PERF.md) identifies as the bf16 floor. ``int8="weights"`` is the
    split composite that analysis predicts wins under GQA: int8 weights
    over an untouched bf16 cache (the cache is already 3x smaller, so
    the remaining win is the weight stream and the bf16 kernel stays on
    its cheapest path). ``gqa=True`` is the
    production decode geometry (r5, VERDICT r4 #2): n_kv_heads=2 of 6
    query heads (3x smaller KV cache and cache stream) + RoPE — same
    d_model/d_head, so the non-attention work is identical to the MHA
    twin and the delta isolates the cache-stream effect."""
    import functools
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        init_transformer,
        quantize_decode_params,
        transformer_generate,
    )

    cfg, prompt, p = _decode_bench_cfg(
        args, batch, gqa, int8, prompt_len=prompt_len, new=new
    )
    params = init_transformer(jax.random.key(0), cfg)
    if int8 != "off":
        _verify_int8_decode(weights_only=(int8 == "weights"), gqa=gqa)
        params = quantize_decode_params(params, cfg)
    gen = jax.jit(
        functools.partial(
            transformer_generate(cfg), max_new=new, temperature=1.0,
            # approximate top-k (recall ~0.95): the exact sort over
            # V=50304 measured 758us/step, 29% of decode device time.
            # --exact-top-k restores the r01/r02 sampling semantics so
            # the two are separable (PERF.md records both).
            top_k=40, approx_top_k=not args.exact_top_k,
        )
    )
    holder = {"out": None}

    def run(i):
        holder["out"] = gen(params, prompt, jax.random.key(i))

    def drain():
        out = np.asarray(holder["out"][:, -1])
        assert ((out >= 0) & (out < p["vocab"])).all()

    reps, dt = _run_window(args, run, drain, min_reps=5)
    tok_per_sec = batch * new * reps / dt
    # MBU: analytic USEFUL bytes per decode step (streamed weight bytes +
    # the K/V rows logically visible at the average step) over achieved
    # step time, against the HBM peak — the serving-side analogue of MFU.
    # Cache padding, sampling tables and prefill are deliberately NOT
    # credited (prefill time IS in the denominator: conservative).
    d, nl, ff, v = p["d_model"], p["n_layers"], p["d_ff"], p["vocab"]
    bpe = 2 if args.dtype == "bf16" else 4
    kv_heads = cfg.n_kv_heads or cfg.n_heads
    # attention projections from the ACTUAL config: GQA's wkv is
    # d x (2*kv_heads*head_dim), not the MHA 3*d*d — crediting MHA
    # weights would inflate the GQA rows' MBU ~7%
    attn_params = d * cfg.n_heads * cfg.head_dim * 2  # wq (or q of wqkv) + wo
    attn_params += d * 2 * kv_heads * cfg.head_dim    # k and v projections
    matmul_params = nl * (attn_params + 2 * d * ff) + d * v
    float_params = nl * (4 * d + ff + d)  # ln scales/biases + b1/b2
    avg_vis = prompt_len + (new + 1) / 2
    if int8 != "off":
        # int8 matmul weights + their f32 per-output-channel scales +
        # the float leftovers
        attn_out_ch = (
            cfg.n_heads * cfg.head_dim           # q output channels
            + 2 * kv_heads * cfg.head_dim        # k/v output channels
            + d                                  # wo output channels
        )
        scale_count = nl * (attn_out_ch + ff + d) + v
        weight_bytes = (
            matmul_params * 1 + scale_count * 4 + float_params * bpe
        )
    else:
        weight_bytes = (matmul_params + float_params) * bpe
    if int8 == "full":
        # int8 cache rows + f32 per-row scales; "weights" mode keeps
        # the cache at the compute dtype
        cache_bytes = (
            2 * batch * avg_vis * kv_heads * cfg.head_dim * 1 * nl
            + 2 * batch * avg_vis * 4 * nl
        )
    else:
        cache_bytes = (
            2 * batch * avg_vis * kv_heads * cfg.head_dim * bpe * nl
        )
    peak_bw = _peak_lookup(_PEAK_HBM_BW)
    mbu = (
        (weight_bytes + cache_bytes) * tok_per_sec / batch / peak_bw
        if peak_bw
        else None
    )
    return (
        tok_per_sec,
        f"transformer_gpt2s_h128_decode{metric_suffix}_tokens_per_sec_per_chip",
        mbu,
    )


def _bench_decode_spec(args):
    """Speculative decode at the B=1 latency point: the GQA bf16 target
    verifies k=4 tokens drafted by its own weight-only-int8 quantization
    — output samples the bf16 (top-40, T=1) target distribution (exact
    w.r.t. the verify program; see transformer_speculative_generate's
    docstring for the float-reassociation caveat), so this row is
    directly comparable to ``transformer-decode-gqa-b1`` (the plain
    bf16 baseline) rather than to the lossy int8w row.
    Acceptance is near-1 because draft≈target; the win is bounded by
    draft-step cost (~the int8w step) + one chunked verify per round."""
    import functools
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        init_transformer,
        quantize_decode_params,
        transformer_speculative_generate,
    )

    new, k = _DECODE_NEW, 4
    cfg, prompt, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    _verify_int8_decode(weights_only=True, gqa=True)
    qdraft = quantize_decode_params(params, cfg)
    gen = jax.jit(
        functools.partial(
            transformer_speculative_generate(cfg), max_new=new,
            draft_k=k, temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
        )
    )
    holder = {"out": None}

    def run(i):
        holder["out"] = gen(params, qdraft, prompt, jax.random.key(i))

    def drain():
        out = np.asarray(holder["out"][:, -1])
        assert ((out >= 0) & (out < p["vocab"])).all()

    reps, dt = _run_window(args, run, drain, min_reps=5)
    tok_per_sec = new * reps / dt
    return (
        tok_per_sec,
        "transformer_gpt2s_h128_decode_gqa_b1_spec_tokens_per_sec_per_chip",
    )


def _bench_decode_serve(args, n_slots: int = 16, n_requests: int = 48,
                        mean_interarrival_s: float = 0.01,
                        fault_rate: float = 0.0):
    """Continuous-batching serving under load: the GQA bf16 production
    decode geometry behind the ``ServingEngine``, driven by a
    DETERMINISTIC pseudo-Poisson arrival trace (seeded exponential
    inter-arrivals, so every invocation replays the same offered load).
    The arrival rate intentionally oversubscribes the slot batch —
    requests queue, slots stay occupied, and the row reports what a
    loaded endpoint shows: aggregate tok/s across all in-flight
    requests plus p50/p99 time-to-first-token (queue wait INCLUDED —
    TTFT is measured from submission, the user-visible number) and mean
    slot occupancy (> 1 means iteration-level batching actually
    interleaved requests; near ``n_slots`` means the engine kept the
    batch full). Aggregate tok/s lands below the steady-state
    ``transformer-decode-gqa`` rows by construction: the serving loop
    pays per-step host scheduling + admission prefills inside the
    window, which is exactly the overhead this row exists to price.

    With ``fault_rate > 0`` (the ``transformer-decode-serve-faults``
    row) a seeded ``FaultInjector`` raises transient faults at engine
    boundaries at that per-check probability; the supervised loop
    retries with backoff, and the row reports the throughput next to
    the clean number (``clean_tok_per_sec`` / ``degradation_frac``) —
    the claim under test is that degradation at a fixed fault rate is
    BOUNDED by retry backoff, not a stall or a crash.

    The clean row SWEEPS the fused decode horizon K over {1, 2, 4, 8}
    (same trace, warmup + timed replay per K) and reports the winning
    horizon's throughput as the headline number, with the K=1 rate and
    the speedup alongside — the multi-step pipelining claim, priced on
    the same run. The faults row stays at K=1 so its boundary-check
    cadence (and therefore the seeded fault pattern) matches the chaos
    tests."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        FaultInjector,
        Request,
        RequestScheduler,
        ServingEngine,
        run_request_trace,
    )

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    prompts = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)

    def make_engine(rate, horizon=1):
        faults = (
            FaultInjector(seed=1234, transient_rate=rate) if rate else None
        )
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            decode_horizon=horizon,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
            faults=faults, retry_backoff_s=0.002, max_backoff_s=0.05,
        )

    def make_trace():
        return [
            (float(arrivals[i]),
             Request(prompt=prompts[i], max_new=_DECODE_NEW))
            for i in range(n_requests)
        ]

    def timed(engine):
        trace = make_trace()
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        # results may also hold warmup streams (reused engine): check
        # this trace's ids specifically
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s

    if fault_rate:
        # warmup: compiles the prefill + step programs
        run_request_trace(make_engine(0.0), make_trace())
        tok_per_sec, s = timed(make_engine(fault_rate))
        clean_tok_per_sec, _ = timed(make_engine(0.0))
        extra = {
            "ttft_p50_s": round(s["ttft_p50_s"], 4),
            "ttft_p99_s": round(s["ttft_p99_s"], 4),
            "occupancy_mean": round(s["occupancy_mean"], 2),
            "n_slots": n_slots,
            "n_requests": n_requests,
            "fault_rate": fault_rate,
            "n_retries": s["n_retries"],
            "n_restarts": s["n_restarts"],
            "clean_tok_per_sec": round(clean_tok_per_sec, 1),
            "degradation_frac": round(
                1.0 - tok_per_sec / clean_tok_per_sec, 4
            ),
            "phase_frac": s.get("phase_frac", {}),
            "phase_seconds": s.get("phase_seconds", {}),
        }
        metric = ("transformer_gpt2s_h128_decode_serve_faults_"
                  "tokens_per_sec_per_chip")
        return tok_per_sec, metric, extra

    # clean row: sweep the fused horizon, same trace per K. jit caches
    # are per-engine, so each K warms on ITS timed engine (one full
    # replay compiles that horizon's step/prefill programs), then the
    # metrics are reset and the same trace is replayed for the clock.
    from deeplearning4j_tpu.serving import ServingMetrics

    sweep = {}
    summaries = {}
    for k in (1, 2, 4, 8):
        engine = make_engine(0.0, k)
        run_request_trace(engine, make_trace())  # warmup/compile
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = k
        tps, s = timed(engine)
        sweep[k] = tps
        summaries[k] = s
    best_k = max(sweep, key=lambda k: sweep[k])
    tok_per_sec, s = sweep[best_k], summaries[best_k]
    extra = {
        "ttft_p50_s": round(s["ttft_p50_s"], 4),
        "ttft_p99_s": round(s["ttft_p99_s"], 4),
        "occupancy_mean": round(s["occupancy_mean"], 2),
        "n_slots": n_slots,
        "n_requests": n_requests,
        "decode_horizon": best_k,
        "horizon_sweep_tok_per_sec": {
            str(k): round(v, 1) for k, v in sweep.items()
        },
        "k1_tok_per_sec": round(sweep[1], 1),
        "horizon_speedup": round(tok_per_sec / sweep[1], 3),
        "dispatch_overlap_frac": round(
            s.get("dispatch_overlap_frac", 0.0), 3
        ),
        "phase_frac": s.get("phase_frac", {}),
        "phase_seconds": s.get("phase_seconds", {}),
    }
    metric = "transformer_gpt2s_h128_decode_serve_tokens_per_sec_per_chip"
    return tok_per_sec, metric, extra


def _bench_decode_serve_prefix(args, n_slots: int = 16,
                               n_requests: int = 48,
                               mean_interarrival_s: float = 0.01):
    """Serving under shared-prefix traffic with the radix-tree prefix
    cache: the serve trace re-run with a FRACTION of the requests
    sharing one long common prompt prefix (system-prompt traffic),
    swept over {0, 0.5, 0.9}. Each swept point runs with the cache ON;
    the 0.9 point also replays with the cache OFF so the row prices
    exactly what reuse buys. Headlines are TTFT p50 (the user-visible
    number a cached prefill shortens) and ``prefill_tokens_saved`` (the
    prompt rows admission never recomputed); the reported metric value
    is the cached 0.9-fraction aggregate tok/s. Byte-parity of cache
    on/off streams is pinned by tests/test_serving_prefix.py — this row
    only prices it."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        run_request_trace,
    )

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    # one shared prefix, bucket-grain aligned so partial hits reuse it
    # in full; unique suffixes keep every request's stream distinct
    sfx_len = 64
    pfx_len = _DECODE_PROMPT_LEN - sfx_len
    shared = rng.integers(0, p["vocab"], (pfx_len,)).astype(np.int32)
    uniq = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)

    def make_trace(frac):
        reqs = []
        for i in range(n_requests):
            if i < int(round(frac * n_requests)):
                prompt = np.concatenate([shared, uniq[i, :sfx_len]])
            else:
                prompt = uniq[i]
            reqs.append(
                (float(arrivals[i]),
                 Request(prompt=prompt, max_new=_DECODE_NEW))
            )
        return reqs

    def make_engine(cache):
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            prefix_cache=cache,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
        )

    def timed(engine, frac):
        trace = make_trace(frac)
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s

    def point(engine, frac):
        # warmup replay compiles this engine's programs (and, cache on,
        # runs the one-time parity probes), then metrics reset + timed
        run_request_trace(engine, make_trace(frac))
        if engine.prefix_cache is not None:
            engine.prefix_cache.reinit()
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = engine.decode_horizon
        return timed(engine, frac)

    sweep = {}
    for frac in (0.0, 0.5, 0.9):
        tps, s = point(make_engine(True), frac)
        sweep[frac] = {
            "tok_per_sec": round(tps, 1),
            "ttft_p50_s": round(s["ttft_p50_s"], 4),
            "ttft_p99_s": round(s["ttft_p99_s"], 4),
            "prefill_tokens_saved": s.get("prefix_tokens_saved", 0),
            "prefix_hit_rate": round(s.get("prefix_hit_rate", 0.0), 3),
        }
    off_tps, off_s = point(make_engine(False), 0.9)
    hot = sweep[0.9]
    tok_per_sec = hot["tok_per_sec"]
    extra = {
        "ttft_p50_s": hot["ttft_p50_s"],
        "ttft_p99_s": hot["ttft_p99_s"],
        "prefill_tokens_saved": hot["prefill_tokens_saved"],
        "prefix_hit_rate": hot["prefix_hit_rate"],
        "shared_prefix_frac": 0.9,
        "shared_prefix_sweep": {
            str(f): v for f, v in sweep.items()
        },
        "no_cache_tok_per_sec": round(off_tps, 1),
        "no_cache_ttft_p50_s": round(off_s["ttft_p50_s"], 4),
        "ttft_p50_speedup": round(
            off_s["ttft_p50_s"] / max(hot["ttft_p50_s"], 1e-9), 3
        ),
        "n_slots": n_slots,
        "n_requests": n_requests,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_prefix_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_decode_serve_piggyback(args, n_slots: int = 4,
                                  n_requests: int = 24,
                                  n_long: int = 4,
                                  long_len: int = 8192,
                                  mean_interarrival_s: float = 0.02):
    """Chunked-prefill piggyback vs blocking admission on a mixed
    trace: the 0.5 shared-prefix serve trace with a few 8k-token
    prompts injected. Off, each 8k admission runs one monolithic
    prefill while every active stream's next token waits behind it
    (head-of-line blocking inside a single engine); on, the prompt is
    split into pow2 chunks and at most ``prefill_budget`` chunk tokens
    ride along per decode horizon — the last budgeted chunk fused into
    the decode dispatch itself. Headlines are p99 TPOT (the stall the
    active streams stop paying) and p50/p99 TTFT on-vs-off (the 8k
    prompts now prefill incrementally, so their first token may arrive
    later — the row prices that trade), plus ``prefill_stall_s``
    (decode-blocked prefill seconds, measured identically in both
    modes). Byte-parity of on/off streams is pinned by
    tests/test_serving_piggyback.py — this row only prices it. The
    metric value is the piggyback engine's aggregate tok/s."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        run_request_trace,
    )

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True,
                                  prompt_len=long_len)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    sfx_len = 64
    pfx_len = _DECODE_PROMPT_LEN - sfx_len
    shared = rng.integers(0, p["vocab"], (pfx_len,)).astype(np.int32)
    uniq = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)
    longs = rng.integers(
        0, p["vocab"], (n_long, long_len)).astype(np.int32)
    # spread the long prompts through the middle of the trace so they
    # land while short streams are actively decoding
    long_at = set(
        np.linspace(n_requests // 4, 3 * n_requests // 4, n_long)
        .astype(int).tolist()
    )

    def make_trace():
        reqs = []
        for i in range(n_requests):
            if i in long_at:
                prompt = longs[len([j for j in long_at if j < i])]
            elif i % 2 == 0:
                prompt = np.concatenate([shared, uniq[i, :sfx_len]])
            else:
                prompt = uniq[i]
            reqs.append(
                (float(arrivals[i]),
                 Request(prompt=prompt, max_new=_DECODE_NEW))
            )
        return reqs

    def make_engine(pb):
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            max_total=long_len + _DECODE_NEW + 1,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            prefix_cache=True,
            prefill_max_bucket=_DECODE_PROMPT_LEN,
            piggyback=pb,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
        )

    def point(pb):
        engine = make_engine(pb)
        # warmup replay compiles this engine's programs (and the
        # one-time parity probes), then metrics reset + timed run
        run_request_trace(engine, make_trace())
        if engine.prefix_cache is not None:
            engine.prefix_cache.reinit()
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = engine.decode_horizon
        trace = make_trace()
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s, engine

    on_tps, on_s, on_eng = point(True)
    off_tps, off_s, _ = point(False)
    tok_per_sec = on_tps
    extra = {
        "tpot_p99_s": round(on_s["tpot_p99_s"], 5),
        "off_tpot_p99_s": round(off_s["tpot_p99_s"], 5),
        "tpot_p99_ratio": round(
            on_s["tpot_p99_s"] / max(off_s["tpot_p99_s"], 1e-9), 3),
        "ttft_p50_s": round(on_s["ttft_p50_s"], 4),
        "ttft_p99_s": round(on_s["ttft_p99_s"], 4),
        "off_ttft_p50_s": round(off_s["ttft_p50_s"], 4),
        "off_ttft_p99_s": round(off_s["ttft_p99_s"], 4),
        "ttft_p99_ratio": round(
            on_s["ttft_p99_s"] / max(off_s["ttft_p99_s"], 1e-9), 3),
        "prefill_stall_s": round(on_s.get("decode_stall_s", 0.0), 4),
        "off_prefill_stall_s": round(off_s.get("decode_stall_s", 0.0), 4),
        "prefill_chunks": on_s.get("prefill_chunks", 0),
        "prefill_budget_tokens": on_eng.prefill_budget,
        "off_tok_per_sec": round(off_tps, 1),
        "piggyback_armed": on_eng._piggyback,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "n_long_prompts": n_long,
        "long_prompt_len": long_len,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_piggyback_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_decode_serve_grammar(args, n_slots: int = 8,
                                n_requests: int = 32,
                                n_constrained: int = 8,
                                mean_interarrival_s: float = 0.01):
    """The production sampling surface priced two ways on the serve
    trace. (1) Overhead: the same all-unconstrained trace served by a
    plain engine vs a ``sampling_surface=True`` engine — every decode
    dispatch now runs the masked program (DFA mask gather, bias
    scatter, top_p sort, logprob gather all folded out as no-ops), so
    the tok/s ratio is the price unconstrained traffic pays for the
    surface being armed (byte-parity of the streams is pinned by
    tests/test_serving_grammar.py; this row only prices it). (2)
    Validity: a mixed trace where ``n_constrained`` requests carry a
    JSON-schema ``response_format`` and sample at the engine
    temperature — every constrained output must parse as JSON AND
    validate against its schema (validity 1.0 is the tentpole's
    guarantee, measured end-to-end here). Both engines use the exact
    top-k sort: ``lax.approx_max_k`` reorders ties, so the surface
    refuses to arm over it. The metric value is the surface-on
    engine's aggregate tok/s on the unconstrained trace."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        run_request_trace,
    )
    from deeplearning4j_tpu.serving.grammar import validate_json_value

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    prompts = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)
    # bounded-output schema: every field has a finite value set, so a
    # constrained stream always reaches the accepting state (and EOS)
    # within max_new tokens — an unbounded integer sampled at T=1.0
    # could out-digit the budget and be truncated mid-value
    schema = {
        "type": "object",
        "properties": {
            "a": {"type": "boolean"},
            "b": {"enum": ["low", "mid", "high"]},
        },
        "required": ["a", "b"],
    }
    eos = p["vocab"] - 1
    constrained_at = set(
        np.linspace(n_requests // 4, 3 * n_requests // 4, n_constrained)
        .astype(int).tolist()
    )

    def make_trace(constrained):
        reqs = []
        for i in range(n_requests):
            if constrained and i in constrained_at:
                r = Request(
                    prompt=prompts[i], max_new=_DECODE_NEW,
                    eos_token=eos,
                    response_format={
                        "type": "json_schema", "schema": schema,
                    },
                )
            else:
                r = Request(prompt=prompts[i], max_new=_DECODE_NEW)
            reqs.append((float(arrivals[i]), r))
        return reqs

    def make_engine(surface):
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            max_total=_DECODE_PROMPT_LEN + _DECODE_NEW + 1,
            temperature=1.0, top_k=40,
            approx_top_k=False,
            prefill_max_bucket=_DECODE_PROMPT_LEN,
            sampling_surface=surface,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
        )

    def point(surface, constrained):
        engine = make_engine(surface)
        run_request_trace(engine, make_trace(constrained))  # warmup
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = engine.decode_horizon
        trace = make_trace(constrained)
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s, engine, trace, results

    off_tps, _, _, _, _ = point(False, False)
    on_tps, on_s, on_eng, _, _ = point(True, False)
    mix_tps, _, _, mix_trace, mix_results = point(True, True)
    n_valid = 0
    for _, r in mix_trace:
        if r.response_format is None:
            continue
        # the trace result is the full sequence (prompt + generated
        # + eos); only the generated span is grammar-constrained
        toks = [int(t) for t in mix_results[r.id][len(r.prompt):]
                if int(t) != eos and int(t) < 256]
        try:
            value = json.loads(bytes(toks).decode("latin-1"))
            ok = validate_json_value(value, schema)
        except (ValueError, UnicodeDecodeError):
            ok = False
        n_valid += bool(ok)
    tok_per_sec = on_tps
    extra = {
        "off_tok_per_sec": round(off_tps, 1),
        "surface_overhead_ratio": round(
            on_tps / max(off_tps, 1e-9), 3),
        "mixed_tok_per_sec": round(mix_tps, 1),
        "constrained_validity": round(
            n_valid / max(n_constrained, 1), 3),
        "n_constrained": n_constrained,
        "n_requests": n_requests,
        "tpot_p99_s": round(on_s["tpot_p99_s"], 5),
        "surface_armed": on_eng._surface,
        "n_slots": n_slots,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_grammar_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_decode_serve_paged(args, n_slots: int = 16,
                              n_requests: int = 48,
                              mean_interarrival_s: float = 0.01):
    """Block-paged KV serving vs the slab pool, priced on the serve
    trace the prefix row uses (0.9 shared-prefix traffic, cache ON for
    both engines — streams are byte-identical by the paged parity
    probe, so the delta is pure allocator/layout cost). Three stories
    on one row:

    - ``tok_per_sec`` (the metric) vs ``slab_tok_per_sec``: what the
      gather-view paged step costs/buys at serving time on this host.
    - ``capacity``: max concurrent slots at FIXED HBM under an
      8k-prompt mix — exact metadata arithmetic over both layouts (no
      8k buffers are allocated): the slab pool strands a full
      Tpad-row slab per slot however short the request, the paged pool
      allocates ``ceil((prompt+max_new)/block)`` 512-row blocks and
      byte-shares the 2k-token common prefix via refcounted aliasing.
      This is the ``>= 2x`` headline and it is layout math, not a
      device measurement.
    - ``int8``: the fused-int8 paged engine's rate plus the exact
      KV-bytes-per-row ratio vs bf16 (~0.52: int8 bytes + f32 per-row
      scale planes) — the HBM-stream halving that carries the int8 MBU
      claim; MBU itself is a TPU-side measurement (see PERF.md).
    """
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        init_transformer,
        quantize_decode_params,
    )
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        run_request_trace,
    )

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    sfx_len = 64
    pfx_len = _DECODE_PROMPT_LEN - sfx_len
    shared = rng.integers(0, p["vocab"], (pfx_len,)).astype(np.int32)
    uniq = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)

    def make_trace(frac=0.9):
        reqs = []
        for i in range(n_requests):
            if i < int(round(frac * n_requests)):
                prompt = np.concatenate([shared, uniq[i, :sfx_len]])
            else:
                prompt = uniq[i]
            reqs.append(
                (float(arrivals[i]),
                 Request(prompt=prompt, max_new=_DECODE_NEW))
            )
        return reqs

    def make_engine(paged, engine_cfg=None, engine_params=None):
        return ServingEngine(
            engine_cfg or cfg, engine_params or params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            prefix_cache=True, paged=paged,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
        )

    def point(engine):
        run_request_trace(engine, make_trace())  # warmup/compile/probes
        if engine.prefix_cache is not None:
            engine.prefix_cache.reinit()
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = engine.decode_horizon
        trace = make_trace()
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s, engine

    paged_tps, s, eng = point(make_engine(True))
    assert eng._paged, "paged engine fell back to slab (probe failed)"
    slab_tps, _, _ = point(make_engine(False))

    # fused-int8 paged leg: same trace through the int8-KV engine
    cfg8, _, _ = _decode_bench_cfg(args, batch=1, gqa=True, int8="full")
    params8 = quantize_decode_params(
        init_transformer(jax.random.key(0), cfg8), cfg8
    )
    int8_tps, _, eng8 = point(make_engine(True, cfg8, params8))

    # -- capacity at fixed HBM, 8k-prompt mix (exact layout math) -----
    hk = (cfg.d_model // cfg.n_heads) * (cfg.n_kv_heads or cfg.n_heads)
    row_bytes = cfg.n_layers * 2 * hk * 2           # bf16 K+V per row
    new8k, blk = 256, 512                           # TPU-tile block
    tpad8k = -(-(8192 + new8k) // 512) * 512        # pool row rounding
    ref_slots = 16                                  # fixed reference pool
    budget = ref_slots * tpad8k * row_bytes         # that slab pool's HBM
    mix_rng = np.random.default_rng(1)
    lens = mix_rng.choice([2048, 4096, 8192], 256)  # the 8k-prompt mix
    shared_len, shared_frac = 2048, 0.9
    shared_blocks = shared_len // blk
    used_blocks, slots, shared_resident = 0, 0, False
    for i, plen in enumerate(lens):
        is_shared = (i % 10) < int(10 * shared_frac)
        need = -(-(int(plen) + new8k) // blk)
        if is_shared:
            need -= shared_blocks
            if not shared_resident:
                need += shared_blocks  # first copy pays for the prefix
        total = used_blocks + need
        if total * blk * row_bytes > budget:
            break
        used_blocks = total
        shared_resident = shared_resident or is_shared
        slots += 1
    capacity_lift = slots / ref_slots

    # -- int8 KV bytes per row (exact; drives the MBU claim) ----------
    row_bytes_int8 = cfg.n_layers * 2 * (hk * 1 + 4)  # int8 + f32 scale

    extra = {
        "slab_tok_per_sec": round(slab_tps, 1),
        "paged_over_slab": round(paged_tps / max(slab_tps, 1e-9), 3),
        "int8_paged_tok_per_sec": round(int8_tps, 1),
        "ttft_p50_s": round(s["ttft_p50_s"], 4),
        "ttft_p99_s": round(s["ttft_p99_s"], 4),
        "prefix_hit_rate": round(s.get("prefix_hit_rate", 0.0), 3),
        "shared_prefix_frac": 0.9,
        "n_slots": n_slots,
        "n_requests": n_requests,
        "block_size": eng.pool.block_size,
        "capacity": {
            "hbm_budget_gib": round(budget / 2**30, 2),
            "mix_prompt_lens": [2048, 4096, 8192],
            "block_size": blk,
            "max_slots_slab": ref_slots,
            "max_slots_paged": slots,
            "lift": round(capacity_lift, 2),
        },
        "kv_bytes_per_row_bf16": row_bytes,
        "kv_bytes_per_row_int8": row_bytes_int8,
        "int8_kv_bytes_frac": round(row_bytes_int8 / row_bytes, 3),
    }
    del eng8
    metric = ("transformer_gpt2s_h128_decode_serve_paged_"
              "tokens_per_sec_per_chip")
    return paged_tps, metric, extra


def _bench_decode_serve_tp(args, n_slots: int = 16, n_requests: int = 32,
                           mean_interarrival_s: float = 0.01):
    """Tensor-parallel serving scaling: the serve trace replayed at a
    FIXED global batch (same slots, same offered load, same streams)
    while the fused decode program and the KV slot pool shard over
    TP in {1, 2, 4, 8} devices. Reported per point: aggregate tok/s,
    tok/s PER CHIP, and scaling efficiency tps(N) / (N * tps(1)) — the
    honest number for weak-scaling-free sharding, since a fixed batch
    gives TP=N no extra work to amortize its collectives. The headline
    metric value is the widest point's per-chip rate.

    Geometry: MHA with n_heads=8 (d_head=96) instead of the flagship's
    6x128, because exact-TP sharding needs every swept width to divide
    the head count; the metric name is versioned ``h96tp`` so this
    row's history never mixes with the h128 rows. ``decode_kernel`` is
    off at EVERY width (TP forces the dense path — the Pallas decode
    kernel cannot GSPMD-partition — so TP=1 runs it too, keeping the
    efficiency ratio a sharding measurement, not kernel-vs-dense).
    Points whose width exceeds the host's device count (or fails the
    construction-time bitwise parity probe) are reported as skipped.
    Byte-parity of TP streams is pinned by tests/test_serving_tp.py —
    this row only prices the sharding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        init_transformer,
    )
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        run_request_trace,
    )

    p = _TRANSFORMER_PRESETS["transformer"]
    cfg = TransformerConfig(
        vocab_size=p["vocab"], d_model=p["d_model"], n_heads=8,
        n_layers=p["n_layers"], d_ff=p["d_ff"],
        max_len=_DECODE_PROMPT_LEN + _DECODE_NEW + 1,
        use_flash=False, decode_kernel=False,
        compute_dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    prompts = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)

    def make_trace():
        return [
            (float(arrivals[i]),
             Request(prompt=prompts[i], max_new=_DECODE_NEW))
            for i in range(n_requests)
        ]

    def point(tp):
        engine = ServingEngine(
            cfg, params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            decode_horizon=4,
            scheduler=RequestScheduler(max_queue_depth=n_requests),
            tp=tp,
        )
        if engine.tp != tp:
            return None  # parity probe fell back: report as skipped
        run_request_trace(engine, make_trace())  # warmup/compile
        engine.metrics = ServingMetrics()
        engine.metrics.decode_horizon = engine.decode_horizon
        trace = make_trace()
        t0 = time.perf_counter()
        results = run_request_trace(engine, trace)
        dt = time.perf_counter() - t0
        assert all(r.id in results for _, r in trace)
        s = engine.metrics.summary()
        return s["n_generated"] / dt, s

    n_dev = len(jax.devices())
    sweep, skipped = {}, []
    for tp in (1, 2, 4, 8):
        if tp > n_dev:
            skipped.append({"tp": tp, "why": f"host has {n_dev} devices"})
            continue
        r = point(tp)
        if r is None:
            skipped.append({"tp": tp, "why": "parity probe fell back"})
            continue
        tps, s = r
        sweep[tp] = {
            "tok_per_sec": round(tps, 1),
            "tok_per_sec_per_chip": round(tps / tp, 1),
            "scaling_efficiency": None,  # filled once tps(1) is known
            "ttft_p50_s": round(s["ttft_p50_s"], 4),
        }
    if not sweep:
        raise RuntimeError("no TP point ran (single-device host?)")
    base = sweep.get(1, sweep[min(sweep)])["tok_per_sec"]
    base_tp = 1 if 1 in sweep else min(sweep)
    for tp, row in sweep.items():
        row["scaling_efficiency"] = round(
            row["tok_per_sec"] / (tp / base_tp * base), 3
        )
    widest = max(sweep)
    tok_per_chip = sweep[widest]["tok_per_sec_per_chip"]
    extra = {
        "tp": widest,
        "tp_sweep": {str(k): v for k, v in sweep.items()},
        "skipped": skipped,
        "scaling_efficiency": sweep[widest]["scaling_efficiency"],
        "n_slots": n_slots,
        "n_requests": n_requests,
        "decode_horizon": 4,
        "n_devices": n_dev,
        "platform": jax.devices()[0].platform,
    }
    metric = "transformer_gpt2s_h96tp_decode_serve_tp_tokens_per_sec_per_chip"
    return tok_per_chip, metric, extra


def _bench_decode_serve_router(args, n_requests: int = 32,
                               n_slots: int = 8,
                               mean_interarrival_s: float = 0.01):
    """Replica routing under shared-prefix traffic: TWO full serving
    replicas (each a ``ServingServer`` with its own engine + radix
    prefix cache) behind the :class:`~.serving.router.ReplicaRouter`,
    driven over real HTTP with half the requests sharing one long
    prompt prefix (system-prompt traffic). The trace runs twice: once
    with prefix-affinity routing ON (shared-prefix requests pinned to
    the replica whose shadow trie — hence prefix cache — already holds
    the run) and once degraded to pure least-loaded/round-robin
    (affinity threshold set beyond any prompt length). The headline is
    ``ttft_p50_speedup``: affinity-routed TTFT p50 over round-robin
    TTFT p50, pooled from both replicas' engine reservoirs — the
    user-visible win of not splitting one prefix's traffic across
    caches that each re-prefill it. The metric value is the affinity
    run's aggregate routed tok/s."""
    import http.client
    import json as _json
    import threading

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        ServingServer,
    )
    from deeplearning4j_tpu.serving.router import ReplicaRouter

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    sfx_len = min(64, _DECODE_PROMPT_LEN // 2)
    pfx_len = _DECODE_PROMPT_LEN - sfx_len
    shared = rng.integers(0, p["vocab"], (pfx_len,)).tolist()
    uniq = rng.integers(
        0, p["vocab"], (n_requests, _DECODE_PROMPT_LEN)
    ).astype(np.int32)

    def make_bodies():
        bodies = []
        for i in range(n_requests):
            if i % 2 == 0:  # 0.5 shared-prefix fraction, interleaved
                prompt = shared + uniq[i, :sfx_len].tolist()
            else:
                prompt = uniq[i].tolist()
            bodies.append({"prompt": prompt, "max_new": _DECODE_NEW})
        return bodies

    def post(addr, body):
        conn = http.client.HTTPConnection(*addr, timeout=300)
        try:
            conn.request(
                "POST", "/v1/generate", body=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            ok = resp.status == 200
            n_tok = 0
            if ok:
                out = _json.loads(resp.read())
                n_tok = len(out["tokens"]) - len(body["prompt"])
            else:
                resp.read()
            return ok, n_tok
        finally:
            conn.close()

    def run_mode(affinity: bool):
        engines = [
            ServingEngine(
                cfg, params, n_slots=n_slots,
                temperature=1.0, top_k=40,
                approx_top_k=not args.exact_top_k,
                prefix_cache=True,
                scheduler=RequestScheduler(max_queue_depth=n_requests),
            )
            for _ in range(2)
        ]
        servers = [ServingServer(e, port=0).start() for e in engines]
        router = ReplicaRouter(
            [s.address for s in servers],
            # round-robin mode: a threshold no prompt can reach
            affinity_min_match=(8 if affinity
                                else _DECODE_PROMPT_LEN + 1),
        ).start()
        try:
            # warmup: compile both replicas' programs through the router
            for body in make_bodies()[:4]:
                post(router.address, body)
            for e in engines:
                if e.prefix_cache is not None:
                    e.prefix_cache.reinit()
                e.metrics = ServingMetrics()
                e.metrics.decode_horizon = e.decode_horizon
            bodies = make_bodies()
            results = [None] * n_requests
            threads = []
            t0 = time.perf_counter()

            def fire(i, body):
                results[i] = post(router.address, body)

            for i, body in enumerate(bodies):
                delay = arrivals[i] - (time.perf_counter() - t0)
                if delay > 0:
                    time.sleep(delay)
                t = threading.Thread(target=fire, args=(i, bodies[i]))
                t.start()
                threads.append(t)
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            assert all(ok for ok, _ in results), "routed request failed"
            n_generated = sum(n for _, n in results)
            ttft = [v for e in engines for v in e.metrics.ttft.values]
            saved = sum(
                e.metrics.prefix_tokens_saved for e in engines
            )
            per_replica = [e.metrics.summary()["n_finished"]
                           for e in engines]
            return {
                "tok_per_sec": n_generated / dt,
                "ttft_p50_s": float(np.percentile(ttft, 50)),
                "ttft_p99_s": float(np.percentile(ttft, 99)),
                "prefill_tokens_saved": saved,
                "per_replica_finished": per_replica,
            }
        finally:
            router.stop()
            for s in servers:
                s.stop()

    aff = run_mode(affinity=True)
    rr = run_mode(affinity=False)
    tok_per_sec = aff["tok_per_sec"]
    extra = {
        "ttft_p50_s": round(aff["ttft_p50_s"], 4),
        "ttft_p99_s": round(aff["ttft_p99_s"], 4),
        "ttft_p50_speedup": round(
            rr["ttft_p50_s"] / max(aff["ttft_p50_s"], 1e-9), 3
        ),
        "round_robin_ttft_p50_s": round(rr["ttft_p50_s"], 4),
        "round_robin_tok_per_sec": round(rr["tok_per_sec"], 1),
        "prefill_tokens_saved": aff["prefill_tokens_saved"],
        "round_robin_tokens_saved": rr["prefill_tokens_saved"],
        "per_replica_finished": aff["per_replica_finished"],
        "shared_prefix_frac": 0.5,
        "n_replicas": 2,
        "n_requests": n_requests,
        "n_slots": n_slots,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_router_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_decode_serve_disagg(args, n_requests: int = 24,
                               n_slots: int = 4,
                               mean_interarrival_s: float = 0.05,
                               long_len: int = 8192,
                               short_len: int = 512,
                               new: int = _DECODE_NEW):
    """Disaggregated prefill/decode vs monolithic replicas on a mixed
    long-prompt trace: half the requests carry an 8k prompt, half a
    512-token one, Poisson arrivals. The SAME two engines serve the
    trace twice — once as monolithic replicas behind the
    :class:`~.serving.router.ReplicaRouter` (every replica interleaves
    8k prefills with its decode batches), once as 1 prefill + 1 decode
    behind the :class:`~.serving.controller.FleetController` (long
    prompts prefill on the dedicated replica, the KV segment rides the
    wire to the decode replica and seats via the zero-prefill full-hit
    path). Per-request TTFT/TPOT are measured END TO END from the
    ``timing.decode_s`` the response carries: TTFT = request wall -
    decode_s, so the disagg numbers pay for their prefill leg, the
    transfer, and the seat — no engine-local accounting tricks. The
    claim priced: p99 TTFT improves (long prefills stop
    head-of-line-blocking decode batches) while p99 TPOT does not
    regress (the decode replica's step loop never yields to an 8k
    prefill); ``transfer_mb_per_s`` is what the wire costs. The metric
    value is the disagg fleet's aggregate tok/s."""
    import http.client
    import json as _json
    import threading

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import (
        FleetController,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        ServingServer,
    )
    from deeplearning4j_tpu.serving.router import ReplicaRouter

    cfg, _, p = _decode_bench_cfg(args, batch=1, gqa=True,
                                  prompt_len=long_len, new=new)
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_requests))
    longs = rng.integers(
        0, p["vocab"], (n_requests, long_len)).astype(np.int32)
    shorts = rng.integers(
        0, p["vocab"], (n_requests, short_len)).astype(np.int32)
    # room for several wire-seated 8k segments before eviction kicks in
    cache_tokens = 8 * (long_len + new + 1)
    threshold = max(short_len + 1, long_len // 2)

    def make_bodies():
        bodies = []
        for i in range(n_requests):
            prompt = (longs[i].tolist() if i % 2 == 0
                      else shorts[i].tolist())
            bodies.append({"prompt": prompt, "max_new": new})
        return bodies

    def post(addr, body):
        conn = http.client.HTTPConnection(*addr, timeout=600)
        t0 = time.perf_counter()
        try:
            conn.request(
                "POST", "/v1/generate", body=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            wall = time.perf_counter() - t0
            if resp.status != 200:
                return None
            out = _json.loads(raw)
            return {
                "n_new": len(out["tokens"]) - len(body["prompt"]),
                "wall": wall,
                "decode_s": out.get("timing", {}).get("decode_s"),
            }
        finally:
            conn.close()

    def make_engine(prefix: bool):
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            prefix_cache=prefix,
            prefix_cache_tokens=cache_tokens if prefix else None,
            scheduler=RequestScheduler(max_queue_depth=2 * n_requests),
        )

    def reset(engines):
        for e in engines:
            if e.prefix_cache is not None:
                e.prefix_cache.reinit()
            e.metrics = ServingMetrics()
            e.metrics.decode_horizon = e.decode_horizon

    def run_trace(front_addr):
        bodies = make_bodies()
        results = [None] * n_requests
        threads = []
        t0 = time.perf_counter()

        def fire(i, body):
            results[i] = post(front_addr, body)

        for i, body in enumerate(bodies):
            delay = arrivals[i] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=fire, args=(i, body))
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert all(r is not None and r["decode_s"] is not None
                   for r in results), "fleet request failed"
        ttft = [r["wall"] - r["decode_s"] for r in results]
        tpot = [r["decode_s"] / (r["n_new"] - 1) for r in results
                if r["n_new"] > 1]
        return {
            "tok_per_sec": sum(r["n_new"] for r in results) / dt,
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p99_s": float(np.percentile(ttft, 99)),
            "tpot_p99_s": float(np.percentile(tpot, 99)),
        }

    def run_mono():
        engines = [make_engine(prefix=True) for _ in range(2)]
        servers = [ServingServer(e, port=0).start() for e in engines]
        router = ReplicaRouter(
            [s.address for s in servers],
            # prompts are unique: pure least-loaded dispatch
            affinity_min_match=long_len + 1,
        ).start()
        try:
            for body in make_bodies()[:2]:  # compile: one long, one short
                post(router.address, body)
            reset(engines)
            return run_trace(router.address)
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def run_disagg():
        pf_eng = make_engine(prefix=False)
        dc_eng = make_engine(prefix=True)
        servers = [ServingServer(e, port=0).start()
                   for e in (pf_eng, dc_eng)]
        (ph, pp), (dh, dp) = servers[0].address, servers[1].address
        ctl = FleetController(
            [(ph, pp, "prefill"), (dh, dp, "decode")],
            disagg_threshold=threshold,
            rebalance_enabled=False,  # fixed roles: this row prices them
        ).start()
        try:
            ctl.poll_health()
            for body in make_bodies()[:2]:  # compile both legs
                post(ctl.address, body)
            reset((pf_eng, dc_eng))
            out = run_trace(ctl.address)
            dsum = pf_eng.metrics.summary().get("disagg", {})
            out["transfers"] = dsum.get("transfers", 0)
            out["transfer_failures"] = dsum.get("transfer_failures", 0)
            out["transfer_bytes"] = dsum.get("transfer_bytes", 0)
            out["transfer_bytes_per_s"] = dsum.get("transfer_bytes_per_s")
            ddis = dc_eng.metrics.summary().get("disagg", {})
            out["kv_ingests_declined"] = ddis.get("kv_ingests_declined", 0)
            return out
        finally:
            ctl.stop()
            for s in servers:
                s.stop()

    mono = run_mono()
    dis = run_disagg()
    assert dis["transfers"] >= 1, "no KV transfer in the timed window"
    tok_per_sec = dis["tok_per_sec"]
    extra = {
        "ttft_p50_s": round(dis["ttft_p50_s"], 4),
        "ttft_p99_s": round(dis["ttft_p99_s"], 4),
        "tpot_p99_s": round(dis["tpot_p99_s"], 5),
        "mono_ttft_p99_s": round(mono["ttft_p99_s"], 4),
        "mono_tpot_p99_s": round(mono["tpot_p99_s"], 5),
        "ttft_p99_speedup": round(
            mono["ttft_p99_s"] / max(dis["ttft_p99_s"], 1e-9), 3),
        "tpot_p99_ratio": round(
            dis["tpot_p99_s"] / max(mono["tpot_p99_s"], 1e-9), 3),
        "mono_tok_per_sec": round(mono["tok_per_sec"], 1),
        "transfers": dis["transfers"],
        "transfer_failures": dis["transfer_failures"],
        "transfer_bytes": dis["transfer_bytes"],
        "transfer_mb_per_s": (
            round(dis["transfer_bytes_per_s"] / 1e6, 1)
            if dis["transfer_bytes_per_s"] else None),
        "kv_ingests_declined": dis["kv_ingests_declined"],
        "long_prompt_len": long_len,
        "short_prompt_len": short_len,
        "long_frac": 0.5,
        "disagg_threshold": threshold,
        "n_requests": n_requests,
        "n_slots": n_slots,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_disagg_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_decode_serve_tenant(args, n_slots: int = 4,
                               n_flood: int = 16, n_victims: int = 3,
                               reqs_per_victim: int = 1,
                               prompt_len: int = 128, new: int = 32):
    """Multi-tenant serving, two claims priced in one row.

    **Fairness** — one greedy tenant floods ``n_flood`` requests at
    t=0 while three paced tenants each trickle ``reqs_per_victim``
    requests into the backlog (sparse — the interactive-user shape;
    give victims deep queues of their own and their p99 measures their
    own backlog, not the flood); the identical trace replays under (a)
    deficit-round-robin fair scheduling (equal weights, so the flooder
    is held to a 1/4 share while victims wait) and (b) plain FIFO (the
    flood drains first). The reported number is the victim tenants' p99
    NORMALIZED latency — (finish - arrival) / tokens generated, the
    end-to-end per-token time a victim user experiences, queue wait
    included (decode-phase TPOT alone cannot show starvation: a starved
    request decodes at full speed once finally admitted) — and
    ``fairness_improvement_x`` is FIFO p99 over fair p99. Aggregate
    tok/s of both replays is reported alongside; the scheduler only
    reorders, so they must agree (same engine, same work).

    **Batched LoRA** — the headline tok/s: 16 requests over 4 distinct
    adapters decoded as ONE mixed batch on one engine with a stacked
    (A, B) adapter bank (each fused step gathers per-slot adapter
    rows), vs the replica-per-fine-tune baseline: the same traffic on a
    single-adapter engine run once per adapter, sequentially (timing-
    equivalent to 4 idle-most-of-the-time replicas, without paying 4
    compiles in the bench). With per-adapter traffic below the slot
    count the fixed-shape step wastes idle slots in every sequential
    replay, so consolidation wins ~(n_slots / per-adapter-traffic)x —
    the S-LoRA/Punica claim. Per-slot stream parity vs a single-adapter
    engine is pinned by tests/test_serving_tenancy.py; this row only
    prices it."""
    import threading

    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        init_lora_bank,
        init_transformer,
    )
    from deeplearning4j_tpu.serving import (
        Request,
        RequestScheduler,
        ServingEngine,
        ServingMetrics,
        TenantConfig,
        TenantRegistry,
    )

    cfg, _, p = _decode_bench_cfg(
        args, batch=1, gqa=True, prompt_len=prompt_len, new=new
    )
    params = init_transformer(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    n_paced = n_victims * reqs_per_victim
    prompts = rng.integers(
        0, p["vocab"], (n_flood + n_paced, prompt_len)
    ).astype(np.int32)

    def make_requests(tagged):
        """(arrival_offset_s, tenant_id, Request) triples: the flood at
        t=0, each victim's requests staggered into the backlog.
        ``tagged=False`` blanks the requests' tenant ids — the DRR tier
        keys by ``tenant_id`` with or without a registry, so the honest
        FIFO baseline is untagged traffic (one implicit tenant, the
        pre-tenancy behavior); attribution rides the triple instead."""
        out = []
        for i in range(n_flood):
            out.append((0.0, "flood", Request(
                prompt=prompts[i], max_new=new,
                tenant_id="flood" if tagged else "",
                done=threading.Event(),
            )))
        for v in range(n_victims):
            for k in range(reqs_per_victim):
                i = n_flood + v * reqs_per_victim + k
                out.append((0.02 + 0.05 * k + 0.01 * v,
                            f"victim{v}", Request(
                                prompt=prompts[i], max_new=new,
                                tenant_id=f"victim{v}" if tagged else "",
                                done=threading.Event(),
                            )))
        return out

    def make_tenancy():
        return TenantRegistry(
            [TenantConfig("flood", api_key="f")]
            + [TenantConfig(f"victim{v}", api_key=f"v{v}")
               for v in range(n_victims)]
        )

    def replay(engine, fair):
        """Drive the trace, recording each request's submit->terminal
        wall time host-side (one-step granularity)."""
        trace = sorted(make_requests(tagged=fair), key=lambda x: x[0])
        t0 = time.perf_counter()
        i = 0
        live = []
        finished = {}
        while i < len(trace) or live or not engine.idle:
            now = time.perf_counter() - t0
            while i < len(trace) and trace[i][0] <= now:
                _, tid, req = trace[i]
                engine.submit(req)
                live.append((now, tid, req))
                i += 1
            engine.step()
            now = time.perf_counter() - t0
            still = []
            for t_arr, tid, req in live:
                if req.done.is_set():
                    finished.setdefault(tid, []).append(
                        (now - t_arr) / max(req.max_new, 1)
                    )
                else:
                    still.append((t_arr, tid, req))
            live = still
        dt = time.perf_counter() - t0
        s = engine.metrics.summary()
        victims = [x for tid, xs in finished.items()
                   if tid != "flood" for x in xs]
        return {
            "tok_per_sec": s["n_generated"] / dt,
            "victim_p99_s_per_tok": float(np.percentile(victims, 99)),
            "victim_p50_s_per_tok": float(np.percentile(victims, 50)),
        }

    def make_engine(fair: bool):
        tenancy = make_tenancy() if fair else None
        return ServingEngine(
            cfg, params, n_slots=n_slots,
            temperature=1.0, top_k=40,
            approx_top_k=not args.exact_top_k,
            scheduler=RequestScheduler(
                max_queue_depth=n_flood + n_paced, tenancy=tenancy,
            ),
            tenancy=tenancy,
        )

    # warm THE engines to be timed (one throwaway request compiles the
    # 128-bucket prefill + the fused step; a fresh engine would re-jit
    # inside the timed replay and compile latency would pollute every
    # wave-1 victim number), then reset metrics and replay
    fair_eng, fifo_eng = make_engine(True), make_engine(False)
    for eng in (fair_eng, fifo_eng):
        eng.submit(Request(prompt=prompts[0], max_new=2))
        eng.run()
        eng.metrics = ServingMetrics()
    fair_r = replay(fair_eng, True)
    fifo_r = replay(fifo_eng, False)

    # -- batched-LoRA consolidation point ------------------------------
    # per-adapter traffic (4) deliberately fills only HALF the slots
    # (8): the consolidation win is exactly the idle capacity a
    # replica-per-fine-tune deployment strands when each fine-tune's
    # traffic alone cannot fill a batch
    n_adapters, per_adapter = 4, 4
    lora_slots = 2 * per_adapter
    bank = init_lora_bank(
        jax.random.key(1), cfg, n_adapters=n_adapters + 1, rank=8
    )
    lora_prompts = rng.integers(
        0, p["vocab"], (n_adapters * per_adapter, prompt_len)
    ).astype(np.int32)

    def lora_requests(adapter=None):
        """Mixed batch by default; ``adapter`` filters to one
        fine-tune's share of the traffic."""
        reqs = []
        for i in range(n_adapters * per_adapter):
            a = 1 + i % n_adapters
            if adapter is not None and a != adapter:
                continue
            reqs.append(Request(
                prompt=lora_prompts[i], max_new=new, adapter=a,
            ))
        return reqs

    def run_flood(engine, reqs):
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        engine.run()
        return time.perf_counter() - t0

    batched = ServingEngine(cfg, params, n_slots=lora_slots,
                            temperature=1.0, top_k=40,
                            approx_top_k=not args.exact_top_k,
                            lora_bank=bank)
    replica = ServingEngine(cfg, params, n_slots=lora_slots,
                            temperature=1.0, top_k=40,
                            approx_top_k=not args.exact_top_k,
                            lora_bank=bank)
    run_flood(batched, lora_requests())  # warmup/compile
    run_flood(replica, lora_requests(adapter=1))
    batched.metrics = ServingMetrics()
    n_tok = n_adapters * per_adapter * new
    dt_batched = run_flood(batched, lora_requests())
    dt_seq = sum(
        run_flood(replica, lora_requests(adapter=a))
        for a in range(1, n_adapters + 1)
    )
    tok_per_sec = n_tok / dt_batched

    extra = {
        "victim_p99_s_per_tok_fair": round(
            fair_r["victim_p99_s_per_tok"], 4),
        "victim_p99_s_per_tok_fifo": round(
            fifo_r["victim_p99_s_per_tok"], 4),
        "fairness_improvement_x": round(
            fifo_r["victim_p99_s_per_tok"]
            / max(fair_r["victim_p99_s_per_tok"], 1e-9), 2),
        "fair_tok_per_sec": round(fair_r["tok_per_sec"], 1),
        "fifo_tok_per_sec": round(fifo_r["tok_per_sec"], 1),
        "lora_batched_tok_per_sec": round(tok_per_sec, 1),
        "lora_sequential_tok_per_sec": round(n_tok / dt_seq, 1),
        "lora_consolidation_speedup": round(dt_seq / dt_batched, 2),
        "n_adapters": n_adapters,
        "n_slots": n_slots,
        "lora_slots": lora_slots,
        "n_flood": n_flood,
        "n_paced": n_paced,
        "prompt_len": prompt_len,
        "max_new": new,
    }
    metric = ("transformer_gpt2s_h128_decode_serve_tenant_"
              "tokens_per_sec_per_chip")
    return tok_per_sec, metric, extra


def _bench_resnet(args):
    """ResNet-20 (He CIFAR recipe) training throughput — the modern CNN
    family the reference's era lacked (its conv story stops at
    forward-only ConvolutionDownSampleLayer.java:113). BN state threads
    through the scanned step, so this exercises the stateful-layer path
    the LeNet/AlexNet workloads don't."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.alexnet import synthetic_cifar
    from deeplearning4j_tpu.models.resnet import (
        ResNetConfig,
        init_resnet,
        resnet_run_steps,
    )
    import optax

    cfg = ResNetConfig()  # ResNet-20, 10 classes
    ds = synthetic_cifar(n=args.batch)
    x = jnp.asarray(
        np.asarray(ds.features, np.float32).reshape(-1, 32, 32, 3)
    )
    y = jnp.asarray(np.asarray(ds.labels, np.float32))
    optimizer = optax.sgd(0.1, momentum=0.9)
    run_steps = resnet_run_steps(cfg, optimizer)
    params, state = init_resnet(jax.random.key(0), cfg)
    holder = {"s": (params, state, optimizer.init(params)), "l": None}

    def run(_i):
        p, s, o, losses = run_steps(*holder["s"], x, y, STEPS)
        holder["s"] = (p, s, o)
        holder["l"] = losses

    def drain():
        out = np.asarray(holder["l"])
        assert np.isfinite(out).all(), "resnet bench loss non-finite"

    reps, dt = _run_window(args, run, drain, windows=4)
    return (
        args.batch * STEPS * reps / dt,
        "resnet20_cifar10_train_samples_per_sec_per_chip",
    )


def _build(model: str, batch: int):
    """(params, loss_fn, x, y, metric_name) for the chosen workload."""
    import jax.numpy as jnp

    if model == "lenet":
        from deeplearning4j_tpu.datasets import fetchers
        from deeplearning4j_tpu.models.lenet import build_lenet, lenet_loss

        net, params = build_lenet(seed=0)
        ds = fetchers.mnist(n=batch)
        loss = lenet_loss(net)
        metric = "lenet_mnist_train_samples_per_sec_per_chip"
    elif model == "alexnet":
        from deeplearning4j_tpu.models.alexnet import (
            build_alexnet,
            synthetic_cifar,
        )

        net, params = build_alexnet(seed=0)
        ds = synthetic_cifar(n=batch)

        def loss(params, x, y, key=None):
            return net.supervised_score_fn(params, x, y)

        metric = "alexnet_cifar10_train_samples_per_sec_per_chip"
    else:  # pragma: no cover - argparse restricts choices
        raise ValueError(model)
    return params, loss, jnp.asarray(ds.features), jnp.asarray(ds.labels), metric


_ALL_WORKLOADS = (
    "lenet", "alexnet", "resnet", "word2vec", "transformer",
    "transformer-flash-8k", "transformer-flash-32k",
    "transformer-decode", "transformer-decode-b64",
    "transformer-decode-int8", "transformer-decode-b64-int8",
    "transformer-decode-gqa", "transformer-decode-gqa-b64",
    "transformer-decode-gqa-b64-int8",
    "transformer-decode-gqa-int8w", "transformer-decode-gqa-b64-int8w",
    "transformer-decode-gqa-b1", "transformer-decode-gqa-b1-int8w",
    "transformer-decode-gqa-b1-spec",
    "transformer-decode-gqa-8kctx", "transformer-decode-gqa-8kctx-int8",
    "transformer-decode-serve", "transformer-decode-serve-faults",
    "transformer-decode-serve-prefix", "transformer-decode-serve-paged",
    "transformer-decode-serve-piggyback",
    "transformer-decode-serve-grammar",
    "transformer-decode-serve-tp", "transformer-decode-serve-router",
    "transformer-decode-serve-disagg",
    "transformer-decode-serve-tenant",
)

# measured-faster dtype per workload: bf16 for the MXU-bound ones, f32
# where the model is too small to be MXU-bound (lenet: bf16 measured
# 0.94x) or parity matters (word2vec exp-table semantics)
_AUTO_DTYPE = {
    "lenet": "f32", "alexnet": "bf16", "resnet": "bf16",
    "word2vec": "f32",
    "transformer": "bf16", "transformer-flash-8k": "bf16",
    "transformer-flash-32k": "bf16",
    "transformer-decode": "bf16", "transformer-decode-b64": "bf16",
    "transformer-decode-int8": "bf16", "transformer-decode-b64-int8": "bf16",
    "transformer-decode-gqa": "bf16", "transformer-decode-gqa-b64": "bf16",
    "transformer-decode-gqa-b64-int8": "bf16",
    "transformer-decode-gqa-int8w": "bf16",
    "transformer-decode-gqa-b64-int8w": "bf16",
    "transformer-decode-gqa-b1": "bf16",
    "transformer-decode-gqa-b1-int8w": "bf16",
    "transformer-decode-gqa-b1-spec": "bf16",
    "transformer-decode-gqa-8kctx": "bf16",
    "transformer-decode-gqa-8kctx-int8": "bf16",
    "transformer-decode-serve": "bf16",
    "transformer-decode-serve-faults": "bf16",
    "transformer-decode-serve-prefix": "bf16",
    "transformer-decode-serve-paged": "bf16",
    "transformer-decode-serve-piggyback": "bf16",
    "transformer-decode-serve-grammar": "bf16",
    "transformer-decode-serve-tp": "bf16",
    "transformer-decode-serve-router": "bf16",
    "transformer-decode-serve-disagg": "bf16",
    "transformer-decode-serve-tenant": "bf16",
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--model",
        choices=_ALL_WORKLOADS,
        default=None,
        help="run a single workload; default runs all of them, one JSON "
        "line each",
    )
    ap.add_argument(
        "--flash", action=argparse.BooleanOptionalAction, default=None,
        help="transformer workloads: force the pallas flash attention "
        "kernel on/off (default: preset choice — flash everywhere; with "
        "the 512/1024-block bf16 kernels flash beats dense from T=1024 "
        "up, and is the only path that compiles at T=32768)",
    )
    ap.add_argument(
        "--exact-top-k", action="store_true",
        help="transformer-decode: use the exact top-k sort instead of "
        "lax.approx_max_k (recall ~0.95) when filtering sampled logits — "
        "the r01/r02 semantics, ~0.75ms/step slower at V=50304",
    )
    ap.add_argument(
        "--scaling", action="store_true",
        help="measure data-parallel scaling efficiency 1 -> N local chips "
        "(throughput_N / (N * throughput_1)); 1.0 trivially on one chip",
    )
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument(
        "--profile", metavar="DIR", default=None,
        help="capture an XPlane/Perfetto trace of the timed window into "
        "DIR (view with tensorboard or ui.perfetto.dev); single-workload "
        "mode only",
    )
    ap.add_argument(
        "--dtype", choices=("auto", "bf16", "f32"), default="auto",
        help="bf16 = mixed precision (MXU-native compute, f32 params and "
        "loss); f32 matches the reference's forced float32. auto picks "
        "the measured-faster config per workload",
    )
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()

    if args.model is None:
        if args.profile:
            ap.error("--profile needs --model (one trace per workload)")
        if args.scaling:
            ap.error("--scaling needs --model lenet or alexnet")
        for model in _ALL_WORKLOADS:
            sub = argparse.Namespace(**vars(args))
            sub.model = model
            sub.dtype = _AUTO_DTYPE[model] if args.dtype == "auto" else args.dtype
            _run_one(sub, jax)
        return

    if args.dtype == "auto":
        args.dtype = _AUTO_DTYPE[args.model]
    _run_one(args, jax)


def _run_one(args, jax) -> None:
    from deeplearning4j_tpu import dtypes

    policy = dtypes.MIXED_BF16 if args.dtype == "bf16" else dtypes.FLOAT32
    with dtypes.policy(policy):
        _run_one_inner(args, jax)


def _run_one_inner(args, jax) -> None:
    import json as _json

    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel import mesh as mesh_lib

    n_chips = len(jax.devices())

    if args.model == "resnet":
        if args.scaling:
            raise SystemExit("--scaling is implemented for the "
                             "DataParallelTrainer workloads (lenet/alexnet)")
        per_chip, metric = _bench_resnet(args)
        _report(args, per_chip, metric, jax,
                remeasure=lambda: (_bench_resnet(args)[0], None))
        return

    if args.model == "word2vec":
        if args.scaling:
            raise SystemExit("--scaling applies to the trainer workloads, "
                             "not the single-device word2vec kernel")
        per_chip, metric = _bench_word2vec(args)
        _report(args, per_chip, metric, jax,
                remeasure=lambda: (_bench_word2vec(args)[0], None))
        return

    if args.model.startswith("transformer-decode"):
        if args.scaling:
            raise SystemExit("--scaling does not apply to decode")
        if args.model == "transformer-decode-serve-prefix":
            per_chip, metric, extra = _bench_decode_serve_prefix(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_prefix(args)[0], None))
            return
        if args.model == "transformer-decode-serve-paged":
            per_chip, metric, extra = _bench_decode_serve_paged(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_paged(args)[0], None))
            return
        if args.model == "transformer-decode-serve-piggyback":
            per_chip, metric, extra = _bench_decode_serve_piggyback(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_piggyback(args)[0], None))
            return
        if args.model == "transformer-decode-serve-grammar":
            per_chip, metric, extra = _bench_decode_serve_grammar(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_grammar(args)[0], None))
            return
        if args.model == "transformer-decode-serve-tp":
            per_chip, metric, extra = _bench_decode_serve_tp(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_tp(args)[0], None))
            return
        if args.model == "transformer-decode-serve-router":
            per_chip, metric, extra = _bench_decode_serve_router(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_router(args)[0], None))
            return
        if args.model == "transformer-decode-serve-disagg":
            per_chip, metric, extra = _bench_decode_serve_disagg(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_disagg(args)[0], None))
            return
        if args.model == "transformer-decode-serve-tenant":
            per_chip, metric, extra = _bench_decode_serve_tenant(args)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve_tenant(args)[0], None))
            return
        if args.model in ("transformer-decode-serve",
                          "transformer-decode-serve-faults"):
            # fixed injected transient-fault rate for the chaos row: high
            # enough that retries demonstrably happen inside the window,
            # low enough that the degradation bound is the story
            rate = 0.02 if args.model.endswith("-faults") else 0.0
            per_chip, metric, extra = _bench_decode_serve(
                args, fault_rate=rate)
            _report(args, per_chip, metric, jax, extra=extra,
                    remeasure=lambda: (
                        _bench_decode_serve(args, fault_rate=rate)[0], None))
            return
        if args.model.endswith("-spec"):
            per_chip, metric = _bench_decode_spec(args)
            _report(args, per_chip, metric, jax,
                    remeasure=lambda: (_bench_decode_spec(args)[0], None))
            return
        int8 = (
            "weights" if args.model.endswith("int8w")
            else "full" if args.model.endswith("int8")
            else "off"
        )
        b64 = "-b64" in args.model
        b1 = "-b1" in args.model
        longctx = "-8kctx" in args.model
        gqa = "-gqa" in args.model
        batch = 64 if b64 else 1 if b1 else 16
        # the long-context serving point: prefill 8192, then enough
        # decode steps (256) that the cache stream — the thing int8
        # halves — dominates the window rather than the prefill
        prompt_len = 8192 if longctx else _DECODE_PROMPT_LEN
        new = 256 if longctx else _DECODE_NEW
        suffix = (
            ("_gqa" if gqa else "")
            + ("_b64" if b64 else "_b1" if b1 else "")
            + ("_8kctx" if longctx else "")
            + {"off": "", "full": "_int8", "weights": "_int8w"}[int8]
        )

        def run_decode():
            v, _m, u = _bench_decode(
                args, batch=batch, metric_suffix=suffix,
                int8=int8, gqa=gqa, prompt_len=prompt_len, new=new,
            )
            return v, u

        per_chip, metric, mbu = _bench_decode(
            args, batch=batch, metric_suffix=suffix,
            int8=int8, gqa=gqa, prompt_len=prompt_len, new=new,
        )
        _report(args, per_chip, metric, jax, util=mbu, util_key="mbu",
                remeasure=run_decode)
        return

    if args.model in _TRANSFORMER_PRESETS:
        if args.scaling:
            raise SystemExit("--scaling is implemented for the "
                             "DataParallelTrainer workloads (lenet/alexnet)")
        total, metric, mfu = _bench_transformer(args, args.model)

        def run_tf():
            v, _m, u = _bench_transformer(args, args.model)
            return v, u

        # the transformer bench is a single-chip program: per-chip = raw
        _report(args, total, metric, jax, util=mfu, util_key="mfu",
                remeasure=run_tf)
        return

    if args.scaling and args.profile:
        raise SystemExit("--profile with --scaling would mix two traces "
                         "(N-chip and 1-chip windows) in one dump")

    if args.scaling and n_chips == 1:
        # nothing to compare on one chip — skip the measurement entirely
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_dp_scaling_efficiency_1_to_1",
                    "value": 1.0,
                    "unit": "efficiency",
                    "vs_baseline": None,
                }
            )
        )
        return

    mesh = mesh_lib.data_parallel_mesh(n_chips)

    def run_trainer():
        # fresh build each invocation: run_steps donates its state, so a
        # re-measure cannot reuse the previous invocation's buffers
        params_, loss_, x_, y_, _m = _build(args.model, args.batch)
        trainer_ = DataParallelTrainer(loss_, mesh=mesh)
        state_ = trainer_.init(params_)
        x_, y_ = trainer_.shard_batch(x_, y_)
        return _measure_trainer(args, trainer_, state_, x_, y_), None

    params, loss, x, y, metric = _build(args.model, args.batch)
    trainer = DataParallelTrainer(loss, mesh=mesh)
    state = trainer.init(params)
    x, y = trainer.shard_batch(x, y)

    samples_per_sec = _measure_trainer(args, trainer, state, x, y)

    if args.scaling:
        mesh1 = mesh_lib.data_parallel_mesh(1)
        params1, loss1, x1, y1, _ = _build(args.model, args.batch)
        trainer1 = DataParallelTrainer(loss1, mesh=mesh1)
        state1 = trainer1.init(params1)
        x1, y1 = trainer1.shard_batch(x1, y1)
        sps1 = _measure_trainer(args, trainer1, state1, x1, y1)
        eff = samples_per_sec / (n_chips * sps1)
        print(
            json.dumps(
                {
                    "metric": f"{args.model}_dp_scaling_efficiency"
                    f"_1_to_{n_chips}",
                    "value": round(eff, 4),
                    "unit": "efficiency",
                    "vs_baseline": None,
                }
            )
        )
        return

    _report(
        args, samples_per_sec / n_chips, metric, jax,
        remeasure=lambda: (run_trainer()[0] / n_chips, None),
    )


def _measure_trainer(args, trainer, state, x, y) -> float:
    """samples/sec over a >= MIN_TIMED_SECONDS window of run_steps calls.

    One dispatch covers the whole scanned loop (run_steps), so the number
    reflects device throughput, not Python launch overhead. (Scanning is
    right for these small models: the carry is a few MB, unlike the
    transformer's 2GB state, and per-step device time is far below the
    dispatch latency.)
    """
    import jax
    import numpy as np

    holder = {"state": state, "losses": None}

    def run(i):
        holder["state"], holder["losses"] = trainer.run_steps(
            holder["state"], x, y, jax.random.key(i), STEPS
        )

    def drain():
        out = np.asarray(holder["losses"])
        assert np.isfinite(out).all(), "bench produced non-finite loss"

    reps, dt = _run_window(args, run, drain, windows=4)
    return args.batch * STEPS * reps / dt


#: a reading below this ratio triggers the paired re-measure loop
#: (VERDICT r4 weak #1): a single contended invocation must not be
#: recorded as a regression. Re-measures are full fresh measurement
#: invocations separated by a pause — external contention only ever
#: slows a window down, so max-across-invocations estimates the code's
#: throughput.
_REMEASURE_BELOW = 0.95
_REMEASURE_ATTEMPTS = 2
_REMEASURE_PAUSE_S = 8.0


def _report(
    args, per_chip: float, metric: str, jax,
    util=None, util_key: str | None = None,
    remeasure=None, extra: dict | None = None,
) -> None:
    """``util``/``util_key`` attach a utilization ratio under an explicit
    JSON key — "mfu" for FLOP-bound training workloads, "mbu" for the
    bandwidth-bound decode workload. ``extra`` merges additional keys
    into the JSON record (the serving row's TTFT percentiles and slot
    occupancy ride here). ``remeasure`` (no-arg callable
    returning a fresh ``(per_chip, util)`` measurement) enables the
    paired protocol: when the reading lands below ``_REMEASURE_BELOW``
    of baseline, the harness re-runs the same workload after a pause —
    up to ``_REMEASURE_ATTEMPTS`` times — and records the best, so a
    contended window cannot masquerade as a code regression. Genuine
    regressions stay visible: they read low in every window."""
    platform = jax.devices()[0].platform
    records = (
        json.loads(BASELINE_FILE.read_text()) if BASELINE_FILE.exists() else {}
    )
    # Baseline semantics by workload family:
    # - lenet/alexnet: recorded at f32 (reference-parity dtype) and the
    #   default batch, so vs_baseline reads "chosen TPU config vs the
    #   reference dtype". Legacy key name (pre --model) holds LeNet.
    # - word2vec: first f32 recording.
    # - transformer presets: first recording of the preset AT its
    #   headline config (bf16) — vs_baseline then tracks round-over-round
    #   progress of the same workload.
    if args.model == "lenet":
        key = "samples_per_sec_per_chip"
    elif "tokens" in metric:
        key = metric.replace("_train_tokens", "_tokens")
    elif "pairs" in metric:
        key = f"{args.model}_pairs_per_sec_per_chip"
    else:
        key = f"{args.model}_samples_per_sec_per_chip"
    is_transformer = (
        args.model in _TRANSFORMER_PRESETS
        or args.model.startswith("transformer-decode")
    )
    comparable = is_transformer or args.batch == BATCH
    baseline = records.get(platform, {}).get(key) if comparable else None
    record_ok = args.dtype == "bf16" if is_transformer else args.dtype == "f32"
    if baseline is None and comparable and record_ok:
        records.setdefault(platform, {})[key] = per_chip
        records[platform][f"{key}_recorded"] = time.time()
        BASELINE_FILE.write_text(json.dumps(records))
        baseline = per_chip
    # null (not 1.0) when nothing was compared — a fake parity ratio would
    # be indistinguishable from a real one
    vs_baseline = round(per_chip / baseline, 3) if baseline else None
    remeasured = 0
    if baseline and remeasure is not None:
        while (
            per_chip / baseline < _REMEASURE_BELOW
            and remeasured < _REMEASURE_ATTEMPTS
        ):
            time.sleep(_REMEASURE_PAUSE_S)
            remeasured += 1
            new_chip, new_util = remeasure()
            if new_chip > per_chip:
                per_chip, util = new_chip, new_util
        vs_baseline = round(per_chip / baseline, 3)

    out = {
        "metric": metric,
        "value": round(per_chip, 1),
        "unit": (
            "pairs/sec/chip" if "pairs" in metric
            else "tokens/sec/chip" if "tokens" in metric
            else "samples/sec/chip"
        ),
        "vs_baseline": vs_baseline,
    }
    if util_key is not None:
        out[util_key] = round(util, 4) if util is not None else None
    if extra:
        out.update(extra)
    if remeasured:
        out["remeasured"] = remeasured
    print(json.dumps(out))


if __name__ == "__main__":
    main()
