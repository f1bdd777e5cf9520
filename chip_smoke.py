#!/usr/bin/env python3
"""Start the system on the chip: serve a few requests, train a few steps.

    python chip_smoke.py                    # one TPU chip; exits 0 and prints
                                            # {"ok": true, "device": ...} last
    python chip_smoke.py --legs features    # every optional serving feature
                                            # on against off, on logits
    python chip_smoke.py --legs multichip   # four chips: dryrun, dp x tp
                                            # training, tp=2 serving
    python chip_smoke.py --rehearse         # same code, toy geometry, CPU,
                                            # interpreted kernels: debug here
                                            # before spending chip time

One process, no children, no network beyond loopback. Without
``--rehearse`` anything but a TPU is refused before a leg runs. Every leg
prints its wall and compile seconds; a leg that raises ends the run with a
traceback and a non-zero exit, and the result line is printed only when
every requested leg passed.

Legs (default: kernels, serve, train):

- kernels: the slab decode kernel, bf16 and int8, at the serve geometry,
  at B=64 and at the 8k-context geometry, against a dense f32 reference.
- serve: the GPT-2s-GQA serving configuration of bench.py's serve rows
  (d768/12L/6q.2kv x128/RoPE/V50,304/bf16, flash prefill, decode kernel,
  16 slots, K=4, greedy) through TransformerConfig -> ServingEngine ->
  ServingServer.start() and real HTTP.
- train: the flagship training preset (B=24, T=1024, flash + selective
  remat, unrolled, bf16) through transformer_train_step on a one-device
  mesh, and bench.py's hand-rolled unsharded step beside it; then
  value_and_grad compiled at the flash-8k and flash-32k presets.
- features: the prefix cache (a full and a partial hit), batched
  admission, chunked crash replay, piggyback prefill, the paged pool,
  the sampling surface, a LoRA bank's adapter 0 and the KV wire: the
  same requests with the feature on and with it off (depth cut to
  ``feature_layers``, full width, K=1), the logits every token was
  drawn from compared row by row and held to FEATURE_TOL; then which
  block sizes Mosaic accepts for the paged decode kernel.
- multichip: ``dryrun_multichip(4)``, three dp x tp train steps at
  GPT-2s width on ``dp_mp_mesh(2, 2)``, ``ServingEngine(tp=2)`` over
  HTTP, shard placement asserted, ``tp=2`` against ``tp=1`` on logits
  as in the features leg, and what the compiled HLO does with the flash
  kernel under GSPMD.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import json
import os
import re
import sys
import threading
import time
import urllib.request

LEGS = ("kernels", "serve", "train", "features", "multichip")
DEFAULT_LEGS = ("kernels", "serve", "train")

#: kernel path vs dense einsum path, one decode step's logits at width:
#: max abs err < LOGITS_REL * max|dense| + LOGITS_ABS. Both paths compute
#: in bf16; the kernel rounds the softmax weights to bf16 before the PV
#: dot where the dense path rounds after it.
LOGITS_REL, LOGITS_ABS = 0.03, 0.02
#: decode kernel vs f32 reference on the attention output, by cache dtype
#: (int8: per-row cache scales plus in-kernel q and p quantisation)
KERNEL_REL = {"bfloat16": 0.02, "int8": 0.08}
KERNEL_ABS = 0.01
#: a feature on against the same requests with it off, over the logits
#: every token was drawn from. The off side is the path a benchmark
#: run's ``correct`` check holds to the float32 reference, so the on
#: side is held to that configuration's tolerances against it
#: (benchmark/configs/gpt2-large.json: an 8-bit path fails them, a
#: rounding change does not).
FEATURE_TOL = {"max_err_of_scale": 0.03, "rms_err_of_rms": 0.025}


@dataclasses.dataclass(frozen=True)
class Size:
    """One geometry for every leg. FULL is what the chip runs; REHEARSAL
    is the same code at a size the CPU interpreter finishes in minutes."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    n_layers: int
    d_ff: int
    n_slots: int
    bucket: int             # largest prefill bucket
    short: tuple[int, int]  # (prompt tokens, max_new): under a bucket
    exact: tuple[int, int]  # exactly the largest bucket
    long: tuple[int, int]   # several buckets: the chunked path
    pair: tuple[int, int]   # sent twice, concurrently: scheduling parity
    train_batch: int
    train_seq: int
    # (seq, batch, d_model, n_heads, n_layers, d_ff, vocab): compile only
    long_train: tuple[tuple[int, ...], ...]
    # (batch, cache rows) for the decode-kernel leg
    decode_shapes: tuple[tuple[int, int], ...]
    paged_block_sizes: tuple[int, ...]
    feature_layers: int

    @property
    def max_total(self) -> int:
        return self.long[0] + self.long[1] + 1


FULL = Size(
    vocab=50304, d_model=768, n_heads=6, n_kv_heads=2, n_layers=12,
    d_ff=3072, n_slots=16, bucket=128,
    short=(40, 24), exact=(128, 32), long=(512, 64), pair=(20, 32),
    train_batch=24, train_seq=1024,
    long_train=(
        (8192, 2, 512, 4, 8, 2048, 8192),
        (32768, 1, 512, 4, 8, 2048, 8192),
    ),
    # serve pool (Tpad of max_total 577), the B=64 throughput point, and
    # bench.py's 8kctx row (8192 prompt + 256 new, padded to 512s)
    decode_shapes=((16, 584), (64, 584), (16, 8704)),
    paged_block_sizes=(8, 16, 128),
    feature_layers=2,
)

REHEARSAL = Size(
    vocab=512, d_model=96, n_heads=6, n_kv_heads=2, n_layers=2, d_ff=192,
    n_slots=4, bucket=32,
    short=(5, 6), exact=(32, 8), long=(128, 16), pair=(12, 8),
    train_batch=2, train_seq=128,
    long_train=((256, 1, 64, 2, 2, 128, 256), (512, 1, 64, 2, 2, 128, 256)),
    decode_shapes=((4, 152), (8, 152), (2, 1536)),
    paged_block_sizes=(8, 16, 128),
    feature_layers=2,
)


@contextlib.contextmanager
def phase(log, name: str):
    """Print one line per phase: wall seconds, compile seconds, compile
    requests and persistent-cache hits/misses inside it. An exception
    passes through: the run ends there."""
    print(f"[{name}] ...", flush=True)
    c0, t0 = log.snapshot(), time.perf_counter()
    yield
    c1, wall = log.snapshot(), time.perf_counter() - t0
    print(
        f"[{name}] ok wall={wall:.1f}s compile={c1[1] - c0[1]:.1f}s "
        f"compile_requests={c1[0] - c0[0]} cache_hits={c1[2] - c0[2]} "
        f"cache_misses={c1[3] - c0[3]}",
        flush=True,
    )


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def serve_config(size: Size, n_layers: int | None = None):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=size.vocab, d_model=size.d_model, n_heads=size.n_heads,
        n_kv_heads=size.n_kv_heads, rope=True,
        n_layers=size.n_layers if n_layers is None else n_layers,
        d_ff=size.d_ff, max_len=size.max_total, use_flash=True,
        compute_dtype=jnp.bfloat16, decode_kernel=True,
    )


def train_config(seq, d_model, n_heads, n_layers, d_ff, vocab, remat):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_len=seq + 1, use_flash=True,
        remat=remat, scan_layers=False, compute_dtype=jnp.bfloat16,
    )


def engine_kwargs(size: Size) -> dict:
    """The serve geometry every engine in this file is built with."""
    return dict(
        n_slots=size.n_slots, max_total=size.max_total, temperature=0.0,
        decode_horizon=4, prefill_max_bucket=size.bucket,
    )


def flagship_train(size: Size):
    """(config, host token batch) of the flagship training preset."""
    import numpy as np

    cfg = train_config(
        size.train_seq, size.d_model, size.n_heads, size.n_layers,
        size.d_ff, size.vocab, remat=True,
    )
    toks = np.random.default_rng(0).integers(
        0, size.vocab, (size.train_batch, size.train_seq + 1)
    ).astype(np.int32)
    return cfg, toks


def decode_dims(size: Size) -> tuple[int, int, int]:
    """(kv heads, query groups, packed row width Hkv*K) of the decode
    kernels' operands."""
    n_kv = size.n_kv_heads
    return n_kv, size.n_heads // n_kv, n_kv * (size.d_model // size.n_heads)


def mosaic_calls(hlo_text: str) -> list[str]:
    """The compiled module's Mosaic (Pallas TPU) custom-call lines."""
    return [
        ln.strip() for ln in hlo_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    ]


def prompt_tokens(rng, size: Size, n: int) -> list[int]:
    return rng.integers(0, size.vocab, (n,)).tolist()


# -- kernels ------------------------------------------------------------------


def decode_reference(q, cache, pos, n_kv, scales=None):
    """Dense f32 single-position attention over layer 0 of a packed
    (L, 2, B, T, Hkv*K) cache; rows past ``pos`` are invisible."""
    import jax
    import jax.numpy as jnp

    k = cache[0, 0].astype(jnp.float32)
    v = cache[0, 1].astype(jnp.float32)
    if scales is not None:
        k, v = k * scales[0, 0], v * scales[0, 1]
    b, t, hk = k.shape
    g, kd = q.shape[1], hk // n_kv
    qh = q.astype(jnp.float32).reshape(b, g, n_kv, kd)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum(
            "bghk,bthk->bght", qh, k.reshape(b, t, n_kv, kd)
        ) / (kd ** 0.5)
        visible = jnp.arange(t)[None, :] <= pos[:, None]
        s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
        o = jnp.einsum(
            "bght,bthk->bghk", jax.nn.softmax(s, axis=-1),
            v.reshape(b, t, n_kv, kd),
        )
    return o.reshape(b, g, hk)


def kernels_leg(size: Size, log) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention,
        flash_decode_attention_write,
    )

    n_kv, groups, hk = decode_dims(size)
    rng = np.random.default_rng(0)
    for batch, rows in size.decode_shapes:
        for int8 in (False, True):
            dtype = "int8" if int8 else "bfloat16"
            with phase(log, f"kernels/slab B={batch} T={rows} {dtype}"):
                q = jnp.asarray(
                    rng.standard_normal((batch, groups, hk)), jnp.bfloat16
                )
                pos = rng.integers(0, rows, (batch,))
                pos[0], pos[-1] = rows - 1, 0  # both ends of the cache
                pos = jnp.asarray(pos, jnp.int32)
                if int8:
                    cache = jnp.asarray(
                        rng.integers(-127, 128, (1, 2, batch, rows, hk)),
                        jnp.int8,
                    )
                    scales = jnp.asarray(
                        rng.uniform(0.004, 0.012, (1, 2, batch, rows, 1)),
                        jnp.float32,
                    )
                else:
                    cache = jnp.asarray(
                        rng.standard_normal((1, 2, batch, rows, hk)),
                        jnp.bfloat16,
                    )
                    scales = None
                out = jax.jit(
                    lambda q, c, p, s: flash_decode_attention(
                        q, c, p, n_kv, kv_scales=s
                    )
                )(q, cache, pos, scales)
                ref = jax.jit(
                    lambda q, c, p, s: decode_reference(q, c, p, n_kv, s)
                )(q, cache, pos, scales)
                out = np.asarray(out, np.float32)
                ref = np.asarray(ref)
                check(out.shape == (batch, groups, hk)
                      and bool(np.isfinite(out).all()),
                      f"finite output of shape {out.shape}")
                err = float(np.max(np.abs(out - ref)))
                scale = float(np.max(np.abs(ref)))
                bound = KERNEL_REL[dtype] * scale + KERNEL_ABS
                check(err < bound,
                      f"kernel vs f32 reference: max abs err {err:.3e} "
                      f"< {bound:.3e} (scale {scale:.3e})")
                if int8:
                    continue
                # the walk that places the step's new rows itself
                # against XLA's scatter and then the read-only walk
                new = jnp.asarray(
                    rng.standard_normal((batch, 2, hk)), jnp.bfloat16
                )
                placed = cache
                for plane in range(2):
                    placed = placed.at[
                        0, plane, jnp.arange(batch), pos
                    ].set(new[:, plane])
                out_r = jax.jit(
                    lambda q, c, p: flash_decode_attention(q, c, p, n_kv)
                )(q, placed, pos)
                out_w, cache_w = jax.jit(
                    lambda q, c, n, p: flash_decode_attention_write(
                        q, c, n, p, n_kv
                    )
                )(q, cache, new, pos)
                check(bool(jnp.array_equal(cache_w, placed)),
                      "the writing kernel's cache equals the scatter's, "
                      "bit for bit")
                check(bool(jnp.array_equal(out_w, out_r)),
                      "the writing kernel's output equals scatter-then-"
                      "read's, bit for bit")


# -- serve --------------------------------------------------------------------


def http_json(url: str, body: dict | None = None, timeout: float = 900.0):
    """POST ``body`` (or GET when None); any non-2xx raises."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        kind = resp.headers.get("Content-Type", "")
    return json.loads(raw) if "json" in kind else raw.decode()


def check_stream(size: Size, name: str, prompt, max_new, tokens) -> None:
    check(
        len(tokens) == len(prompt) + max_new
        and tokens[:len(prompt)] == prompt
        and all(0 <= t < size.vocab for t in tokens),
        f"{name}: {len(prompt)} prompt + {max_new} new tokens, ids in "
        f"[0, {size.vocab})",
    )


def logits_kernel_vs_dense(size: Size, cfg, params) -> None:
    """One decode step at the serve width, all slots, per-slot position
    vector: the Pallas kernel path against the dense einsum path
    (``decode_kernel=False``) on the same prefilled cache and token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer import _decode_builder

    fwd_kernel, init_caches, prefill, cast = _decode_builder(cfg)
    fwd_dense = _decode_builder(
        dataclasses.replace(cfg, decode_kernel=False)
    )[0]
    b, n = size.n_slots, size.bucket
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, size.vocab, (b, n)), jnp.int32
    )
    pos = jnp.full((b,), n, jnp.int32)

    @jax.jit
    def run(params, prompt):
        p = cast(params)
        caches, lg = prefill(p, init_caches(b, size.max_total), prompt)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return (fwd_kernel(p, caches, tok, pos)[0],
                fwd_dense(p, caches, tok, pos)[0])

    got, ref = (np.asarray(a, np.float32) for a in run(params, prompt))
    check(got.shape == (b, size.vocab) and bool(np.isfinite(got).all()),
          f"kernel-path logits finite, shape {got.shape}")
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    bound = LOGITS_REL * scale + LOGITS_ABS
    check(err < bound,
          f"kernel vs dense decode-step logits: max abs err {err:.3e} < "
          f"{bound:.3e} (scale {scale:.3e})")


def compiled_step_has_mosaic(size: Size, cfg, rehearse: bool) -> None:
    """Compile the engine's ``step`` family from the program registry
    (the live program by construction, analysis/programs.py) and look
    for the Mosaic custom call: with it present neither an interpreted
    kernel nor the dense path can be standing in."""
    import jax

    from deeplearning4j_tpu.analysis.programs import (
        ServingGeometry,
        enumerate_programs,
    )

    geom = ServingGeometry(
        n_slots=size.n_slots, max_total=size.max_total, temperature=0.0,
        decode_horizon=4, adaptive_horizon=False,
        prefill_max_bucket=size.bucket,
    )
    spec = next(
        s for s in enumerate_programs(cfg, geom) if s.family == "step"
    )
    fn, avals = spec.build()
    compiled = jax.jit(fn, donate_argnums=spec.donate).trace(
        *avals
    ).lower().compile()
    n = len(mosaic_calls(compiled.as_text()))
    if rehearse:
        print(f"  rehearsal: kernels are interpreted, {n} Mosaic calls "
              f"in {spec.name} (not checked)")
        return
    check(n >= cfg.n_layers,
          f"compiled {spec.name} holds {n} Mosaic custom calls "
          f"(>= one per layer)")


def serve_leg(size: Size, log, rehearse: bool) -> None:
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving.scheduler import Request
    from deeplearning4j_tpu.serving.server import ServingServer

    cfg = serve_config(size)
    with phase(log, "serve/init"):
        params = init_transformer(jax.random.key(0), cfg)
        engine = ServingEngine(cfg, params, **engine_kwargs(size))
        # nothing this leg asked for may have been swapped out
        check(engine.cfg.decode_kernel and engine.cfg.use_flash
              and engine.tp == 1 and engine.decode_horizon == 4
              and engine._max_bucket == size.bucket,
              "engine kept decode_kernel, flash prefill, K=4, "
              f"bucket {size.bucket}")
        server = ServingServer(
            engine, request_timeout_s=900.0, hang_threshold_s=900.0
        )

    rng = np.random.default_rng(0)
    traffic = {
        name: (prompt_tokens(rng, size, n), max_new)
        for name, (n, max_new) in (
            ("short", size.short), ("exact", size.exact),
            ("long", size.long), ("pair", size.pair),
        )
    }
    try:
        with phase(log, "serve/warmup"):
            # two same-bucket requests queued BEFORE the loop starts are
            # admitted together: the group-of-2 prefill compiles here,
            # whatever the thread timing of the concurrent round below
            # turns out to be
            queued = [
                Request(prompt=traffic["pair"][0],
                        max_new=traffic["pair"][1],
                        done=threading.Event())
                for _ in range(2)
            ]
            for r in queued:
                engine.submit(r)
            server.start()
            base = "http://%s:%d" % server.address
            for r in queued:
                check(r.done.wait(900.0), f"{r.id} (queued before start) "
                      "finished")
                check_stream(size, r.id, *traffic["pair"],
                             engine.pop_result(r.id).tolist())
            alone = {}
            for name, (prompt, max_new) in traffic.items():
                alone[name] = http_json(
                    base + "/v1/generate",
                    {"prompt": prompt, "max_new": max_new},
                )["tokens"]
                check_stream(size, f"{name} (alone)", prompt, max_new,
                             alone[name])
        with phase(log, "serve/concurrent"):
            before = log.snapshot()[0]
            engine.mark_warm()  # from here a compile is a recompile
            names = ["short", "exact", "long", "pair", "pair"]
            with concurrent.futures.ThreadPoolExecutor(len(names)) as pool:
                futures = [
                    pool.submit(
                        http_json, base + "/v1/generate",
                        {"prompt": traffic[n][0], "max_new": traffic[n][1]},
                    )
                    for n in names
                ]
                together = [f.result()["tokens"] for f in futures]
            for n, toks in zip(names, together):
                check_stream(size, f"{n} (concurrent)", *traffic[n], toks)
            check(together[3] == together[4],
                  "the concurrent same-prompt greedy pair returned "
                  "identical streams")
            same = [n for n, t in zip(names, together) if t == alone[n]]
            print(f"  info: streams equal to the same prompt served alone: "
                  f"{len(same)}/{len(names)} {same}")
            compiled = log.snapshot()[0] - before
            check(compiled == 0,
                  f"{compiled} compile requests after warm-up "
                  f"{log.names_since(before) if compiled else ''}")
            check(not engine.metrics.recompiles,
                  f"serve_recompiles_total empty after mark_warm() "
                  f"{engine.metrics.recompiles or ''}")
        with phase(log, "serve/endpoints"):
            metrics = http_json(base + "/metrics")
            check("serve_requests_total" in metrics
                  and "serve_tokens_generated_total" in metrics,
                  "GET /metrics serves the request and token counters")
            health = http_json(base + "/healthz")
            check(health["ok"] and health["restarts"] == 0
                  and health["last_error"] is None,
                  "GET /healthz ok, 0 restarts, no error")
            summary = engine.metrics.summary()
            print("  engine summary: " + json.dumps({
                k: summary[k] for k in (
                    "n_finished", "n_generated", "ttft_p50_s",
                    "tpot_p50_s", "occupancy_mean",
                ) if k in summary
            }, default=float))
    finally:
        server.stop(drain_s=5.0)
    check(not server._engine_thread.is_alive() and engine.idle,
          "drained and stopped: engine loop exited, nothing in flight")
    with phase(log, "serve/kernel-vs-dense"):
        logits_kernel_vs_dense(size, cfg, params)
    with phase(log, "serve/mosaic-in-step"):
        compiled_step_has_mosaic(size, cfg, rehearse)


# -- train --------------------------------------------------------------------


def timed_steps(step, state, toks, n: int):
    """Run ``n`` steps, waiting for each loss; (state, losses, seconds)."""
    import jax

    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        *state, loss = step(*state, toks)
        losses.append(float(jax.block_until_ready(loss)))
        secs.append(time.perf_counter() - t0)
    return state, losses, secs


def train_leg(size: Size, log) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from deeplearning4j_tpu.models.transformer import (
        init_transformer,
        transformer_loss,
        transformer_train_step,
    )
    from deeplearning4j_tpu.parallel.mesh import dp_mp_mesh

    cfg, toks_host = flagship_train(size)

    with phase(log, "train/transformer_train_step (one-device mesh)"):
        step, init_state, shard_tokens = transformer_train_step(
            dp_mp_mesh(1, 1), cfg
        )
        state = init_state(jax.random.key(0))
        toks = shard_tokens(jnp.asarray(toks_host))
        state, losses, secs = timed_steps(step, state, toks, 3)
        check(all(np.isfinite(losses)) and len(set(losses)) == 3
              and losses[-1] < losses[0],
              f"three finite, falling losses {losses}")
        state, _, warm = timed_steps(step, state, toks, 3)
        print(f"  first step (compile included) {secs[0]:.1f}s; warm "
              f"steps {[round(s * 1e3, 1) for s in warm]} ms")
        sharded_ms = min(warm) * 1e3
        del state

    with phase(log, "train/bench.py's unsharded step"):
        # the form _bench_transformer times: no mesh, no NamedSharding
        loss_fn = transformer_loss(cfg)
        optimizer = optax.adamw(3e-4)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def plain_step(params, opt_state, toks):
            loss, grads = jax.value_and_grad(loss_fn)(params, toks)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        params = init_transformer(jax.random.key(0), cfg)
        state = [params, optimizer.init(params)]
        toks = jnp.asarray(toks_host)
        state, plain_losses, _ = timed_steps(plain_step, state, toks, 3)
        check(all(np.isfinite(plain_losses)),
              f"three finite losses {plain_losses}")
        state, _, warm = timed_steps(plain_step, state, toks, 3)
        plain_ms = min(warm) * 1e3
        del state, params
        print(f"  step time, best of 3 warm: transformer_train_step "
              f"{sharded_ms:.1f} ms, unsharded {plain_ms:.1f} ms "
              f"(B={size.train_batch}, T={size.train_seq})")

    for seq, batch, d_model, n_heads, n_layers, d_ff, vocab in size.long_train:
        with phase(log, f"train/compile value_and_grad T={seq}"):
            lcfg = train_config(
                seq, d_model, n_heads, n_layers, d_ff, vocab, remat=False
            )
            avals = jax.eval_shape(
                lambda: init_transformer(jax.random.key(0), lcfg)
            )
            compiled = jax.jit(
                jax.value_and_grad(transformer_loss(lcfg))
            ).lower(
                avals, jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)
            ).compile()
            mem = compiled.memory_analysis()
            print(f"  compiled; temp bytes "
                  f"{getattr(mem, 'temp_size_in_bytes', 'n/a')}, "
                  f"{len(mosaic_calls(compiled.as_text()))} Mosaic calls")


# -- features (builder's leg) -------------------------------------------------


def record_sampled_logits(engine) -> dict:
    """Every logits row a step program of ``engine`` draws a token from,
    as ``{(request id, position): row}``. At K=1 that is every token's.
    All four step families take ``(params, caches, logits, pos, active,
    ...)``; the getters are wrapped on this one engine object, once: a
    second call returns the same dict."""
    import numpy as np

    rows = getattr(engine, "sampled_rows", None)
    if rows is not None:
        return rows
    rows = engine.sampled_rows = {}

    def wrap(getter):
        def get(*key):
            fn = getter(*key)

            def call(params, caches, logits, pos, active, *rest):
                lg, po, live = (np.asarray(a) for a in (logits, pos, active))
                for slot, st in enumerate(engine._slots):
                    if st is not None and live[slot]:
                        rows[(st.req.id, int(po[slot]))] = lg[slot]
                return fn(params, caches, logits, pos, active, *rest)

            return call
        return get

    for name in ("_step_fn_for", "_masked_step_fn_for", "_piggyback_fn",
                 "_masked_piggyback_fn"):
        setattr(engine, name, wrap(getattr(engine, name)))
    return rows


def served(engine, waves, crash_after: int | None = None):
    """Serve ``waves`` (lists of requests; a wave is submitted whole,
    ``steps`` engine steps after the one before, and the last is run to
    the end) and return ``(rows, streams)``: the logits every token was
    drawn from and each request's tokens. With ``crash_after`` the engine
    loses its device state after that many steps of the last wave and
    recovers; only what was drawn after the recovery is returned."""
    rows = record_sampled_logits(engine)
    reqs = []
    for steps, wave in waves:
        for _ in range(steps):
            engine.step()
        for r in wave:
            engine.submit(r)
            reqs.append(r)
    if crash_after is not None:
        for _ in range(crash_after):
            engine.step()
        engine.recover()
        rows.clear()
    out = engine.run()
    return rows, {r.id: out[r.id].tolist() for r in reqs}


def logit_errors(name: str, on, off, prompts: dict) -> dict:
    """Compare what two engines drew the same requests' tokens from. A
    row is compared as long as the tokens before it agree (a row past a
    differing token answers another question); the first differing
    token is reported with the gap between the off side's two largest
    logits there."""
    import numpy as np

    (rows_on, toks_on), (rows_off, toks_off) = on, off
    got, want, diverged = [], [], []
    for rid in sorted(toks_off):
        n = len(prompts[rid])
        a, b = toks_on[rid][n:], toks_off[rid][n:]
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    min(len(a), len(b)))
        if same < min(len(a), len(b)):
            top = np.sort(rows_off[(rid, n + same)])[-2:]
            diverged.append({"request": rid, "token": same,
                             "top2_gap": float(top[1] - top[0])})
        for pos in range(n, n + same + 1):
            if (rid, pos) in rows_on and (rid, pos) in rows_off:
                got.append(rows_on[(rid, pos)])
                want.append(rows_off[(rid, pos)])
    got, want = np.stack(got), np.stack(want)
    return {
        "feature": name, "rows": len(got),
        "max_err_of_scale": float(np.max(np.abs(got - want)))
        / float(np.max(np.abs(want))),
        "rms_err_of_rms": float(np.sqrt(np.mean((got - want) ** 2)))
        / float(np.sqrt(np.mean(want ** 2))),
        "rows_bitwise": int(np.sum(np.all(got == want, axis=1))),
        "streams_equal": not diverged, "diverged": diverged,
    }


def report_features(results: list[dict], path: str) -> None:
    """Print every figure, leave them in ``chiprun_out/``, and only then
    hold each to FEATURE_TOL: one feature over it does not hide the
    others' numbers."""
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", path), "w") as f:
        json.dump(results, f, indent=1)
    for r in results:
        print("  " + json.dumps(r), flush=True)
    for r in results:
        check(all(r[k] <= tol for k, tol in FEATURE_TOL.items()),
              f"{r['feature']}: on against off over {r['rows']} rows, max "
              f"error {r['max_err_of_scale']:.3e} of the largest logit "
              f"(tol {FEATURE_TOL['max_err_of_scale']}), rms error "
              f"{r['rms_err_of_rms']:.3e} of the rms logit (tol "
              f"{FEATURE_TOL['rms_err_of_rms']}), {r['rows_bitwise']} rows "
              f"bitwise, streams equal={r['streams_equal']}")


def features_leg(size: Size, log) -> None:
    """Every optional serving feature on against off on this device.
    Depth is cut to ``feature_layers`` (a feature reschedules or reorders
    the same per-layer arithmetic), width is full, K=1 so that every
    token's logits pass through the recorder."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (
        init_lora_bank,
        init_transformer,
    )
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_decode_attention_paged,
    )
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving.disagg import (
        decode_segment,
        encode_segment,
    )
    from deeplearning4j_tpu.serving.scheduler import (
        KVExportRequest,
        KVIngestRequest,
        Request,
    )

    cfg = serve_config(size, n_layers=size.feature_layers)
    dense = dataclasses.replace(cfg, decode_kernel=False)
    params = init_transformer(jax.random.key(0), cfg)
    common = dict(engine_kwargs(size), decode_horizon=1)
    rng = np.random.default_rng(2)
    prompts = {
        name: prompt_tokens(rng, size, n)
        for name, n in (
            ("short", size.short[0]), ("exact", size.exact[0]),
            ("long", size.long[0]),
            # four lengths of one bucket, admitted together
            *((f"group{i}", size.bucket // 2 + 1 + 3 * i) for i in range(4)),
        )
    }
    prompts["again"] = list(prompts["exact"])  # a full hit
    prompts["longer"] = prompts["exact"] + prompt_tokens(rng, size, 12)
    max_new = size.short[1]

    def requests(*names, **kw):
        return [Request(prompt=prompts[n], max_new=max_new, id=n, **kw)
                for n in names]

    def pair(name, waves, on: dict, off: dict | None = None, *,
             off_cfg=cfg, crash_after=None, counted=None):
        """Both engines see the same requests in the same order;
        ``counted`` checks that the on side really took the path."""
        eng_on = ServingEngine(cfg, params, **common, **on)
        got = served(eng_on, waves(), crash_after)
        if counted is not None:
            counted(eng_on)
        eng_off = ServingEngine(off_cfg, params, **common, **(off or {}))
        return logit_errors(
            name, got, served(eng_off, waves(), crash_after), prompts
        )

    results = []
    with phase(log, "features/prefix_cache (full hit, partial hit)"):
        # one request at a time: the second finds the first's segment,
        # the third its first 128 rows
        on, off = {}, {}
        for side, kw in ((on, {"prefix_cache": True}), (off, {})):
            eng = ServingEngine(cfg, params, **common, **kw)
            for name in ("exact", "again", "longer"):
                side[name] = served(eng, [(0, requests(name))])
            if side is on:
                check(eng.metrics.n_prefix_hits_full == 1
                      and eng.metrics.n_prefix_hits_partial == 1,
                      "the cache served one full and one partial hit")
        for name, rid in (("prefix_cache full hit", "again"),
                          ("prefix_cache partial hit", "longer")):
            results.append(logit_errors(name, on[rid], off[rid], prompts))

    with phase(log, "features/batch_admission"):
        group = [f"group{i}" for i in range(4)]

        def counted(eng):
            check(eng.metrics.n_batched_admissions == 4,
                  "four same-bucket prompts were admitted in one program")

        results.append(pair(
            "batch_admission", lambda: [(0, requests(*group))],
            {"batch_admission": True}, {"batch_admission": False},
            counted=counted,
        ))

    with phase(log, "features/chunked_replay"):
        # both sides lose their device state at the same step; one
        # rebuilds it by bucketed prefill, the other step by step
        def counted(eng):
            check(eng.last_recover_mode == "chunked",
                  "the on side replayed by bucketed prefill")

        results.append(pair(
            "chunked_replay", lambda: [(0, requests("short", "exact"))],
            {"chunked_replay": True}, {"chunked_replay": False},
            crash_after=3, counted=counted,
        ))

    with phase(log, "features/piggyback"):
        # two streams decoding when a prompt of several buckets arrives
        def counted(eng):
            check(bool(eng._piggyback_fns),
                  "a chunk rode a decode dispatch")

        results.append(pair(
            "piggyback",
            lambda: [(0, requests("short", "exact")),
                     (3, requests("long"))],
            {"piggyback": True}, counted=counted,
        ))

    with phase(log, "features/paged"):
        results.append(pair(
            "paged",
            lambda: [(0, requests("short", "exact", "long"))],
            {"paged": True},
        ))

    with phase(log, "features/sampling_surface"):
        results.append(pair(
            "sampling_surface",
            lambda: [(0, requests("short", "exact"))],
            {"sampling_surface": True},
        ))

    with phase(log, "features/lora adapter 0"):
        # a bank switches the decode kernel off, so the side without
        # one runs the dense path too
        bank = init_lora_bank(jax.random.key(1), cfg, n_adapters=3, rank=4)
        results.append(pair(
            "lora adapter 0",
            lambda: [(0, requests("short", "exact", adapter=0))],
            {"lora_bank": bank}, off_cfg=dense,
        ))

    with phase(log, "features/KV wire"):
        # prefill on one engine, a real frame, seated on another, which
        # then decodes without a prefill of its own
        def through(eng, req):
            eng.submit(req)
            eng.run()
            return req.result

        res = through(ServingEngine(cfg, params, **common), KVExportRequest(
            prompt=np.asarray(prompts["exact"], np.int32),
        ))
        frame = encode_segment(**{k: res[k] for k in (
            "config_hash", "tokens", "leaves", "logits", "layout",
            "block_size",
        )})
        receiver = ServingEngine(cfg, params, prefix_cache=True, **common)
        seated = through(receiver, KVIngestRequest(
            segment=decode_segment(frame, expect_hash=receiver.config_hash),
        ))
        check(seated["stored"], f"the frame was seated ({seated['reason']})")
        on = served(receiver, [(0, requests("exact"))])
        check(receiver.prefill_dispatches == 0,
              "the receiver decoded without a prefill of its own")
        off = served(ServingEngine(cfg, params, **common),
                     [(0, requests("exact"))])
        results.append(logit_errors("KV wire", on, off, prompts))

    report_features(
        results,
        "features.json" if size is FULL else "features_rehearsal.json",
    )

    # which block sizes Mosaic accepts for the paged kernel (no engine
    # call site yet: S1). A refusal is recorded, not fatal — this is a
    # survey, and the only place in the smoke that catches.
    n_kv, groups, hk = decode_dims(size)
    batch = size.n_slots
    for bs in size.paged_block_sizes:
        for dtype in (jnp.bfloat16, jnp.int8):
            bps = -(-size.max_total // bs)
            n_blocks = batch * bps + 1
            avals = [
                jax.ShapeDtypeStruct((batch, groups, hk), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, 2, n_blocks, bs, hk), dtype),
                jax.ShapeDtypeStruct((batch, bps), jnp.int32),
                jax.ShapeDtypeStruct((batch,), jnp.int32),
            ]
            if dtype == jnp.int8:
                avals.append(jax.ShapeDtypeStruct(
                    (1, 2, n_blocks, bs, 1), jnp.float32
                ))
            lowered = jax.jit(
                lambda q, b, t, p, s=None: flash_decode_attention_paged(
                    q, b, t, p, n_kv, block_scales=s
                )
            ).lower(*avals)
            try:
                lowered.compile()
                result = "accepted"
            except Exception as e:  # noqa: BLE001 — the survey's subject
                result = "REFUSED: " + str(e).strip().splitlines()[0][:200]
            print(f"  paged kernel block_size={bs} "
                  f"{jnp.dtype(dtype).name}: {result}", flush=True)


# -- multichip (builder's leg) ------------------------------------------------


def devices_of(tree) -> set:
    import jax

    return {
        s.device for leaf in jax.tree.leaves(tree)
        for s in leaf.addressable_shards
    }


def split_leaves(tree) -> int:
    """Leaves whose shards hold different index ranges (really split,
    not replicated)."""
    import jax

    return sum(
        len({str(s.index) for s in leaf.addressable_shards}) > 1
        for leaf in jax.tree.leaves(tree)
    )


def tp2_against_tp1(size: Size, log) -> None:
    """As the features leg: the same requests sharded over two chips and
    on one (tp > 1 serves the dense path, so both sides do), the logits
    every token was drawn from."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import init_transformer
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving.scheduler import Request

    with phase(log, "multichip/tp=2 against tp=1"):
        cfg = dataclasses.replace(
            serve_config(size, n_layers=size.feature_layers),
            decode_kernel=False,
        )
        params = init_transformer(jax.random.key(0), cfg)
        rng = np.random.default_rng(3)
        prompts = {name: prompt_tokens(rng, size, getattr(size, name)[0])
                   for name in ("short", "exact", "long")}
        sides = []
        for tp in (2, 1):
            eng = ServingEngine(
                cfg, params, tp=tp,
                **dict(engine_kwargs(size), decode_horizon=1),
            )
            check(eng.tp == tp, f"engine serves with tp={tp}")
            sides.append(served(eng, [(0, [
                Request(prompt=p, max_new=size.short[1], id=name)
                for name, p in prompts.items()
            ])]))
        report_features(
            [logit_errors("tp=2", *sides, prompts)],
            "features_tp.json" if size is FULL
            else "features_tp_rehearsal.json",
        )


def multichip_leg(size: Size, log, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as graft
    from deeplearning4j_tpu.models.transformer import (
        init_transformer,
        transformer_train_step,
    )
    from deeplearning4j_tpu.parallel.mesh import dp_mp_mesh
    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving.server import ServingServer

    check(len(jax.devices()) >= 4,
          f"{len(jax.devices())} devices visible (need 4)")

    with phase(log, "multichip/train dp_mp_mesh(2, 2)"):
        cfg, toks_host = flagship_train(size)
        step, init_state, shard_tokens = transformer_train_step(
            dp_mp_mesh(2, 2), cfg
        )
        state = init_state(jax.random.key(0))
        check(len(devices_of(state[0])) == 4 and split_leaves(state[0]) > 0,
              f"params span 4 distinct devices, "
              f"{split_leaves(state[0])} leaves really split")
        toks = shard_tokens(jnp.asarray(toks_host))
        check(len(devices_of(toks)) == 4, "tokens span 4 devices")
        hlo = step.lower(*state, toks).compile().as_text()
        state, losses, _ = timed_steps(step, state, toks, 3)
        check(all(np.isfinite(losses)) and losses[-1] < losses[0],
              f"three finite, falling losses {losses}")
        state, _, warm = timed_steps(step, state, toks, 3)
        print(f"  warm steps {[round(s * 1e3, 1) for s in warm]} ms")
        # where the flash kernel runs: transformer_apply wraps it in a
        # shard_map over (batch, heads), so its compiled operands must
        # be one device's share, B*H/4 rows, not all B*H
        bh = size.train_batch * size.n_heads
        lead = sorted({
            int(m) for ln in mosaic_calls(hlo)
            for m in re.findall(r"bf16\[(\d+),%d," % size.train_seq, ln)
        })
        if rehearse:
            print("  rehearsal: kernels are interpreted, no Mosaic calls")
        else:
            check(lead == [bh // 4],
                  f"flash kernel operands hold B*H/4={bh // 4} rows per "
                  f"device (leading dims {lead}; all B*H would be {bh})")
        del state

    with phase(log, "multichip/serve tp=2"):
        scfg = serve_config(size)
        params = init_transformer(jax.random.key(0), scfg)
        common = engine_kwargs(size)
        engine = ServingEngine(scfg, params, tp=2, **common)
        check(engine.tp == 2, "engine serves with tp=2")
        print(f"  tp > 1 switches the decode kernel off: "
              f"decode_kernel={engine.cfg.decode_kernel}")
        check(len(devices_of(engine.params)) == 2
              and split_leaves(engine.params) > 0
              and len(devices_of(engine.pool.caches)) == 2
              and split_leaves(engine.pool.caches) > 0,
              "params and KV pool really split over 2 devices")
        server = ServingServer(
            engine, request_timeout_s=900.0, hang_threshold_s=900.0
        ).start()
        try:
            base = "http://%s:%d" % server.address
            rng = np.random.default_rng(0)
            for name in ("short", "exact", "long"):
                n, max_new = getattr(size, name)
                prompt = prompt_tokens(rng, size, n)
                toks = http_json(
                    base + "/v1/generate",
                    {"prompt": prompt, "max_new": max_new},
                )["tokens"]
                check_stream(size, f"tp=2 {name}", prompt, max_new, toks)
            health = http_json(base + "/healthz")
            check(health["ok"] and health["restarts"] == 0,
                  "GET /healthz ok, 0 restarts")
        finally:
            server.stop(drain_s=5.0)

    tp2_against_tp1(size, log)

    # last: eleven modes of tiny programs, the longest part on a chip
    with phase(log, "multichip/dryrun_multichip(4)"):
        graft.dryrun_multichip(4)


# -- main ---------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="toy geometry on the CPU with interpreted kernels (eight "
        "virtual devices): debugs this script, proves nothing about a chip",
    )
    ap.add_argument(
        "--legs", default=",".join(DEFAULT_LEGS),
        help=f"comma list of {', '.join(LEGS)} (default: %(default)s)",
    )
    args = ap.parse_args(argv)
    legs = [leg for leg in args.legs.split(",") if leg]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error(f"unknown legs {unknown}")

    if args.rehearse:
        # before jax starts: the CPU, with devices for the multichip leg,
        # and no persistent cache (reloading XLA:CPU executables is the
        # hazard tests/conftest.py documents)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}")
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"device_count={device['count']}")
    print(f"compile cache: {cache_dir}")
    print(f"legs: {legs}" + (" (REHEARSAL)" if args.rehearse else ""),
          flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; "
              "--rehearse runs the toy geometry on the CPU",
              file=sys.stderr)
        return 2

    size = REHEARSAL if args.rehearse else FULL
    from deeplearning4j_tpu.obs import compile_log

    log = compile_log.install()
    t0 = time.perf_counter()
    for leg in legs:
        if leg == "kernels":
            kernels_leg(size, log)
        elif leg == "serve":
            serve_leg(size, log, args.rehearse)
        elif leg == "train":
            train_leg(size, log)
        elif leg == "features":
            features_leg(size, log)
        elif leg == "multichip":
            multichip_leg(size, log, args.rehearse)
    requests, seconds, hits, misses = log.snapshot()
    print(f"total wall={time.perf_counter() - t0:.1f}s "
          f"compile={seconds:.1f}s compile_requests={requests} "
          f"cache_hits={hits} cache_misses={misses}", flush=True)
    if args.rehearse:
        # no result line: a rehearsal is not a chip run
        print("rehearsal finished: every leg passed")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
