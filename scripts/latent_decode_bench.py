#!/usr/bin/env python
"""Time the latent decode kernel alone, on the chip, at the
``openpangu-ultra-moe-718b.reasoning-decode`` geometry (5 layers x 224
slots x 4,096 rows of one plane, 576 values stored 640 wide, bf16; 128
heads a slot), against the dense absorbed product XLA makes of the same
arithmetic:

- ``kernel<r>``: ``latent_decode_attention_write`` with ``r``-row blocks
  (``kernel`` alone: the rule's own block, ``latent_block_rows``);
- ``dense``: the new row scattered into the slab, then ``q . rows`` over
  the whole slab, masked softmax, ``P . rows[:, :512]`` (what
  ``decode_kernel=False`` runs).

    chiprun -- python scripts/latent_decode_bench.py

Each variant runs ``CALLS`` calls chained inside one jit (each call's
output feeds the next query, the layers in turn), the cache donated, so
the figure is device time a call and not dispatch. Lengths: the cell's
traffic in flight (a prompt of 256-1,024 and a uniform part of an output
of 1,536-3,000), every slot live and a quarter of them live. One JSON
line per (occupancy, variant): us a call, the roofline floor of
``benchmark/costs_pangu.py`` for those lengths and the share of it, and
how far the kernel's output of one call lies from the dense one's. A
CPU run
(``JAX_PLATFORMS=cpu``) checks agreement only, at a toy size: its times
are no speed.
"""

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import costs_pangu, peaks  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402

CALLS = 20  # kernel calls chained in one jit
REPEATS = 8

# layers, slots, rows, heads, latent, rotary part
CELL = (5, 224, 4096, 128, 512, 64)
TOY = (2, 4, 256, 4, 16, 8)


def dense(q, cache, new, pos, active, layer, r_kv):
    rows = jnp.arange(q.shape[0])
    cache = cache.at[layer, 0, rows, pos].set(new[:, 0])
    slab = cache[layer, 0]  # (B, T, W)
    s = jnp.einsum("bhw,btw->bht", q, slab,
                   preferred_element_type=jnp.float32)
    s = jnp.where(jnp.arange(slab.shape[1])[None, None] <= pos[:, None, None],
                  s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(slab.dtype)
    o = jnp.einsum("bht,btr->bhr", p, slab[..., :r_kv])
    return jnp.where(active[:, None, None], o, 0).astype(q.dtype), cache


def variant(block_t, n_layers, r_kv):
    def run(q, cache, new, pos, active):
        for c in range(CALLS):
            layer = c % n_layers
            if block_t == "dense":
                o, cache = dense(q, cache, new, pos, active, layer, r_kv)
            else:
                o, cache = pk.latent_decode_attention_write(
                    q, cache, new, pos, r_kv, layer=layer, active=active,
                    block_t=block_t)
            q = q.at[..., :r_kv].add((o * 0.125).astype(q.dtype))
            new = (new * 0.5 + q[:, :1] * 0.5).astype(new.dtype)
        return q, cache

    return jax.jit(run, donate_argnums=(1,))


def one_call(block_t, r_kv):
    def run(q, cache, new, pos, active):
        if block_t == "dense":
            return dense(q, cache, new, pos, active, 1, r_kv)[0]
        return pk.latent_decode_attention_write(
            q, cache, new, pos, r_kv, layer=1, active=active,
            block_t=block_t)[0]

    return jax.jit(run, donate_argnums=(1,))


def main():
    on_chip = jax.default_backend() == "tpu"
    nl, b, t, h, r_kv, rope = CELL if on_chip else TOY
    width = -(-(r_kv + rope) // 128) * 128
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    lanes = (jnp.arange(width) < r_kv + rope)
    kq, kn, kc = jax.random.split(jax.random.key(31), 3)
    q = (jax.random.normal(kq, (b, h, width)) * 0.3 * lanes).astype(dtype)
    new = (jax.random.normal(kn, (b, 1, width)) * lanes).astype(dtype)
    make_cache = jax.jit(lambda: (
        jax.random.normal(kc, (nl, 1, b, t, width), dtype) * lanes.astype(dtype)
    ))
    rng = np.random.default_rng(31)
    if on_chip:
        fill = (np.exp(rng.uniform(np.log(256), np.log(1024), b))
                + rng.uniform(0, 1, b) * rng.uniform(1536, 3000, b))
    else:
        fill = rng.uniform(t // 8, t - 8, b)
    pos = jnp.asarray(np.minimum(fill.astype(np.int64), t - 2), jnp.int32)
    live = np.zeros(b, bool)
    live[rng.permutation(b)[: b // 4]] = True
    blocks = ([None, 128, 256, 512, 1024] if on_chip else [None, 128])
    worst = 0.0
    for occ, active in (("all", np.ones(b, bool)), ("quarter", live)):
        contexts = [int(p) + 1 for p, a in zip(np.asarray(pos), active) if a]
        act = jnp.asarray(active)
        first = {}
        for block_t in ["dense"] + blocks:
            name = "dense" if block_t == "dense" else f"kernel{block_t or ''}"
            fn = variant(block_t, nl, r_kv)
            # agreement is judged on ONE call (a chain feeds each output
            # into the next query and grows a rounding with it)
            first[name] = np.asarray(one_call(block_t, r_kv)(
                q, make_cache(), new, pos, act)[act], np.float32)
            out, cache = fn(q, make_cache(), new, pos, act)
            times = []
            for _ in range(REPEATS if on_chip else 1):
                t0 = time.perf_counter()
                out, cache = fn(q, cache, new, pos, act)
                jax.block_until_ready((out, cache))
                times.append(time.perf_counter() - t0)
            del cache
            line = {
                "live": occ, "variant": name,
                "us_a_call": statistics.median(times) / CALLS * 1e6,
                "platform": jax.devices()[0].platform,
            }
            if on_chip:
                floor = costs_pangu.latent_decode_floor_seconds(
                    contexts, 1, h, r_kv, rope, 2,
                    peaks.peaks_for(jax.devices()[0].device_kind))
                line["floor_us"] = floor * 1e6
                line["roofline_share"] = 100 * floor / (
                    line["us_a_call"] * 1e-6)
            if name != "dense":
                line["max_abs_from_dense"] = float(
                    np.max(np.abs(first[name] - first["dense"])))
                line["scale"] = float(np.max(np.abs(first["dense"])))
                worst = max(worst, line["max_abs_from_dense"] / line["scale"])
            print(json.dumps(line), flush=True)
    ok = worst < (0.05 if on_chip else 1e-4)
    print(json.dumps({"agree": bool(ok), "worst_of_scale": worst}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
