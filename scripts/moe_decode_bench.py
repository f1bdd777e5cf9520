#!/usr/bin/env python
"""Time the held experts' SwiGLU of one decode substep alone, on the chip,
at the two expert cells' geometries (one layer, bf16):

- ``laguna``: 64 slots x top 10 of 256 experts, experts 0-127 held,
  d 3,072, expert width 1,024 (``laguna-s-2.1.long-decode``);
- ``pangu``: 224 slots x top 8 of 256 (sigmoid), experts 0-7 held, d
  7,680, width 2,048 (``openpangu-ultra-moe-718b.reasoning-decode``).

Variants, each on the same routed pairs (every token's k distinct experts
drawn uniformly, the pairs on held experts kept):

- ``ragged``: the sorted rows padded to an odd number of 128-row tiles
  and three ``lax.ragged_dot`` calls with the product between them, as
  ``moe_held_ffn(grouped="ragged")`` runs them (the parent's step);
- ``kernel``: ``moe_ragged_dot`` over the same rows in its own layout;
- ``ragged, own op`` / ``kernel, own op``: the same calls profiled, the
  device time of the grouped products' own operations alone (named
  ``ragged-dot`` / ``ragged_dot``, as the benchmark's
  ``kernels.moe_roofline_share`` finds them), ``other_us`` the rest of
  the call (the kernel's passes table, the padding of the rows);
- ``layer ragged`` / ``layer streamed``: the whole ``moe_held_ffn``
  (routing, sort, gather, products, weighted sum) from a random router;
- ``sweep``: ``ragged`` against ``kernel`` at more tokens, so more rows
  an expert (``rows``: routed pairs over all 256 experts): how far past a
  decode step's rows the kernel stays the faster, and why prefills and
  chunks keep ``lax.ragged_dot`` (``models.transformer.moe_grouped``).

    python scripts/moe_decode_bench.py      (on a TPU host)

Each variant runs ``CALLS`` calls chained in one jit (a row of each output
feeds the next input), so the figure is device time a call and not
dispatch: the median of ``REPEATS``. One JSON line per (geometry,
variant): us a call, the floor (hit experts x
``benchmark.costs_laguna.expert_bytes`` at the chip's published HBM
bandwidth) and the share of it, ``over_floor`` (the time above the floor
as a share of the floor), and how far the variant's rows lie from
``ragged``'s. A CPU run (``JAX_PLATFORMS=cpu``) checks agreement at a
toy size: its times are no speed.
"""

import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from benchmark import costs_laguna, peaks, trace_reduce  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402
from deeplearning4j_tpu.parallel.expert_parallel import (  # noqa: E402
    moe_held_ffn,
)

CALLS = 10
REPEATS = 5
# tokens, k, experts in all, held, d, width, score
CELLS = {
    "laguna": (64, 10, 256, 128, 3072, 1024, "softmax"),
    "pangu": (224, 8, 256, 8, 7680, 2048, "sigmoid"),
}
TOY = {
    "laguna": (8, 4, 16, 8, 256, 128, "softmax"),
    "pangu": (12, 4, 16, 4, 256, 128, "sigmoid"),
}
SWEEP_ROWS = (1, 2, 2.5, 4, 7, 8, 12, 16, 24, 32)


def routed_sizes(rng, tokens, k, total, held):
    """Pairs on each held expert when every token picks k distinct of
    ``total`` experts uniformly."""
    picks = np.argsort(rng.random((tokens, total)), axis=1)[:, :k]
    return np.bincount(picks[picks < held], minlength=held).astype(np.int32)


def ragged(x, wg, wu, wd, sizes):
    a = jax.nn.silu(lax.ragged_dot(x, wg, sizes)) * lax.ragged_dot(
        x, wu, sizes)
    return lax.ragged_dot(a.astype(x.dtype), wd, sizes)


def layouts(sizes, rows, dtype):
    """The sorted ``rows`` as ``ragged`` takes them (odd 128-row tiles)
    and as the kernel takes them (each group from a multiple of
    ``MOE_CHUNK`` rows), and where the pairs lie in the kernel's."""
    pairs, d = rows.shape
    tiles = -(-max(pairs, 1) // 128) | 1
    x_r = np.zeros((tiles * 128, d), np.float32)
    x_r[:pairs] = rows
    starts = np.asarray(pk.moe_group_starts(jnp.asarray(sizes)))
    at = np.concatenate([starts[e] + np.arange(s)
                         for e, s in enumerate(sizes)]).astype(np.int64)
    x_k = np.zeros(
        (pk.moe_stream_rows(max(pairs, 1), len(sizes)), d),
        np.float32)
    x_k[at] = rows
    return jnp.asarray(x_r, dtype), jnp.asarray(x_k, dtype), at


def chained(call):
    def run(x, *rest):
        def body(_, x):
            out = call(x, *rest)
            return x.at[0].set(jnp.where(jnp.isfinite(out[0]), out[0], x[0]))
        return lax.fori_loop(0, CALLS, body, x)
    return jax.jit(run)


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / CALLS * 1e6


def own_op(fn, *args):
    """Device us a call from a profile of ``CALLS`` calls of ``fn``: of
    the grouped products' own operations, and of every other operation."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(tmp))
    grouped = other = 0.0
    for dev in trace.devices:
        for e in dev.ops:
            if "ragged-dot" in e.name or "ragged_dot" in e.name:
                grouped += e.dur_s
            else:
                other += e.dur_s
    return grouped / CALLS * 1e6, other / CALLS * 1e6


def weights(key, e, d, f, dtype):
    kg, ku, kd = jax.random.split(key, 3)
    return (
        (jax.random.normal(kg, (e, d, f), jnp.float32) / d ** 0.5).astype(dtype),
        (jax.random.normal(ku, (e, d, f), jnp.float32) / d ** 0.5).astype(dtype),
        (jax.random.normal(kd, (e, f, d), jnp.float32) / f ** 0.5).astype(dtype),
    )


def main():
    on_chip = jax.default_backend() == "tpu"
    cells = CELLS if on_chip else TOY
    dtype = jnp.bfloat16
    bw = (peaks.peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
          if on_chip else None)
    interpret = not on_chip
    worst = 0.0
    for name, (tokens, k, total, held, d, f, score) in cells.items():
        rng = np.random.default_rng(44)
        wg, wu, wd = weights(jax.random.key(44), held, d, f, dtype)

        def emit(variant, us, pairs, hit, err=None, **extra):
            line = {"cell": name, "variant": variant, "us_a_call": us,
                    "pairs": pairs, "experts_hit": hit,
                    "platform": jax.devices()[0].platform, **extra}
            if bw is not None:
                floor = costs_laguna.experts_hit_bytes(hit, d, f, 2) / bw
                line["floor_us"] = floor * 1e6
                line["floor_share"] = 100 * floor * 1e6 / us
                line["over_floor"] = us / (floor * 1e6) - 1
            if err is not None:
                line["max_abs_from_ragged"] = err
            print(json.dumps(line), flush=True)

        def kernel(x, wg, wu, wd, s):
            return pk.moe_ragged_dot(x, wg, wu, wd, s, interpret=interpret)

        def compare(sizes, profile=False):
            """Time ``ragged`` and ``kernel`` on one draw of rows (and
            with ``profile`` their own operations); returns {label: (us,
            error or None, extra)}."""
            nonlocal worst
            rows = rng.standard_normal((int(sizes.sum()), d))
            s = jnp.asarray(sizes)
            x_r, x_k, at = layouts(sizes, rows, dtype)
            want = np.asarray(jax.jit(ragged)(x_r, wg, wu, wd, s),
                              np.float32)[:len(rows)]
            scale = max(float(np.abs(want).max(initial=0.0)), 1e-30)
            out = np.asarray(jax.jit(kernel)(x_k, wg, wu, wd, s),
                             np.float32)[at]
            err = float(np.abs(out - want).max(initial=0.0))
            worst = max(worst, err / scale)
            got = {
                "ragged": (timed(chained(ragged), x_r, wg, wu, wd, s),
                           None, {}),
                "kernel": (timed(chained(kernel), x_k, wg, wu, wd, s),
                           err, {}),
            }
            if profile:
                for label, fn, x in (("ragged", ragged, x_r),
                                     ("kernel", kernel, x_k)):
                    us, other = own_op(jax.jit(fn), x, wg, wu, wd, s)
                    got[f"{label}, own op"] = (us, None,
                                               {"other_us": other})
            return got

        # the cell's own substep
        sizes = routed_sizes(rng, tokens, k, total, held)
        pairs, hit = int(sizes.sum()), int((sizes > 0).sum())
        for label, (us, err, extra) in compare(sizes, on_chip).items():
            emit(label, us, pairs, hit, err, **extra)

        # the whole layer's expert part, routed by a random router
        kh, kr = jax.random.split(jax.random.key(45))
        h = jax.random.normal(kh, (tokens, d), jnp.float32).astype(dtype)
        router = jax.random.normal(kr, (d, total), jnp.float32) / d ** 0.5
        first = None
        for grouped in ("ragged", "streamed"):
            def layer(h, router, wg, wu, wd, grouped=grouped):
                return moe_held_ffn(h, router, wg, wu, wd, first=0, k=k,
                                    scale=2.5, score=score, grouped=grouped)

            y, counts = jax.jit(layer)(h, router, wg, wu, wd)
            y = np.asarray(y, np.float32)
            first = y if first is None else first
            err = float(np.abs(y - first).max())
            worst = max(worst, err / float(np.abs(first).max()))
            emit(f"layer {grouped}",
                 timed(chained(lambda *a, g=grouped: layer(*a, g)[0]),
                       h, router, wg, wu, wd),
                 int(counts[0]), int(counts[2]), err)

        # the crossover: more tokens, so more rows an expert
        for rows in (SWEEP_ROWS if on_chip else (2, 8)):
            n = int(round(rows * total / k))
            sizes = routed_sizes(rng, n, k, total, held)
            pairs, hit = int(sizes.sum()), int((sizes > 0).sum())
            for label, (us, err, _) in compare(sizes).items():
                emit(f"sweep {label}", us, pairs, hit, err, rows=rows,
                     tokens=n)
    ok = worst < 0.02
    print(json.dumps({"agree": bool(ok), "worst_of_scale": worst}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
