#!/usr/bin/env python
"""Time the placing of a substep's new K and V rows alone, on the chip,
at the gpt2-large serve geometry (48 slots x 1,024 rows x 1,280 bf16,
MHA 20 x 64) and at the gated cell's two leaves:

- ``read``: the read-only walk kernel (no row is placed);
- ``scatter+read``: the parent's pair, one XLA scatter a plane
  (``kv.at[layer, plane, rows, pos].set``) and then the read-only walk;
- ``write8``: the walk that places the row itself, the aligned 8-row
  tile copied back (what ``flash_decode_attention_write`` ships);
- ``write16``: the same with a 16-row tile (ISSUE 30's upper size).

    chiprun -- python scripts/decode_write_bench.py

Each variant runs ``LAYERS`` calls chained inside one jit (a substep's
worth: each call's output feeds the next query), the cache donated, so
the figure is device time a call and not dispatch. Lengths: a slot holds
a quarter to a half of its slab (PR 26's lengths) with every slot live,
and the chat cell's 9 live slots of 48. One JSON line per (geometry,
occupancy, variant), us a call, and a last line that says whether the
writing kernel's output and cache equal the scatter's bit for bit. A
CPU run (``JAX_PLATFORMS=cpu``) checks agreement only, at a toy size:
its times are no speed.
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

from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402

LAYERS = 36  # kernel calls chained in one jit
CALLS = 12

# name -> (cache layers, slots, rows, kv heads, packed width, groups, ring)
GEOMETRIES = {
    "gpt2-large": (4, 48, 1024, 20, 1280, 1, False),
    "laguna-full": (2, 64, 4096, 8, 1024, 6, False),
    "laguna-ring": (3, 64, 512, 8, 1024, 9, True),
}
TOY = {
    "toy": (2, 4, 64, 2, 128, 3, False),
    "toy-ring": (2, 4, 32, 2, 128, 3, True),
}


def scatter(kv, layer, new, at):
    """The parent's write: every slot's row, live or free."""
    rows = jnp.arange(new.shape[0])
    for plane in range(2):
        kv = kv.at[layer, plane, rows, at].set(new[:, plane])
    return kv


def variant(name, n_kv, n_cache_layers, ring):
    def one(q, kv, new, pos, active, layer):
        at = pos % kv.shape[3] if ring else pos
        if name == "read":
            return pk.flash_decode_attention(
                q, kv, pos, n_kv, layer=layer, active=active), kv
        if name == "scatter+read":
            kv = scatter(kv, layer, new, at)
            return pk.flash_decode_attention(
                q, kv, pos, n_kv, layer=layer, active=active), kv
        return pk.flash_decode_attention_write(
            q, kv, new, pos, n_kv, layer=layer, write_at=at, active=active)

    def run(q, kv, new, pos, active):
        for l in range(LAYERS):
            o, kv = one(q, kv, new, pos, active, l % n_cache_layers)
            q = (q + o * 0.125).astype(q.dtype)
            new = (new * 0.5 + q[:, :1] * 0.5).astype(new.dtype)
        return q, kv

    return jax.jit(run, donate_argnums=(1,))


def main():
    on_chip = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16
    agree = True
    for geom, (nl, b, t, n_kv, hk, g, ring) in (
            GEOMETRIES if on_chip else TOY).items():
        rng = np.random.default_rng(30)
        q = jnp.asarray(rng.normal(size=(b, g, hk)), dtype)
        new = jnp.asarray(rng.normal(size=(b, 2, hk)), dtype)
        fill = rng.integers(t // 4, t // 2, size=b)
        if ring:  # most rings have wrapped: the write is not the last row
            fill = rng.integers(t // 2, 3 * t, size=b)
        pos = jnp.asarray(fill, jnp.int32)
        live = np.zeros(b, bool)
        live[rng.permutation(b)[: max(1, b * 9 // 48)]] = True
        cache = np.random.default_rng(31).standard_normal(
            size=(nl, 2, b, t, hk), dtype=np.float32)
        occupancies = [("all", jnp.ones(b, bool))]
        if not geom.startswith("laguna"):
            occupancies.append(("9of48", jnp.asarray(live)))
        for occ, active in occupancies:
            first = {}
            for name in ("scatter+read", "read", "write8", "write16"):
                pk._WRITE_ROWS = 16 if name == "write16" else 8
                pk._decode_attention.clear_cache()
                fn = variant(name, n_kv, nl, ring)
                out, kv = fn(q, jnp.asarray(cache, dtype), new, pos, active)
                # what an active slot owns: its output row and its slab
                first[name] = (out[active], kv[:, :, active])
                times = []
                for _ in range(CALLS if on_chip else 1):
                    t0 = time.perf_counter()
                    out, kv = fn(q, kv, new, pos, active)
                    jax.block_until_ready((out, kv))
                    times.append(time.perf_counter() - t0)
                del kv
                line = {
                    "geometry": geom, "live": occ, "variant": name,
                    "us_a_call": statistics.median(times) / LAYERS * 1e6,
                    "platform": jax.devices()[0].platform,
                }
                if name.startswith("write"):
                    line["bitwise"] = all(
                        bool(jnp.array_equal(x, y))
                        for x, y in zip(first[name], first["scatter+read"])
                    )
                    agree = agree and line["bitwise"]
                    del first[name]
                print(json.dumps(line), flush=True)
    pk._WRITE_ROWS = 8
    print(json.dumps({"agree": bool(agree)}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
