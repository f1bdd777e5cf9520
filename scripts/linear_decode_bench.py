#!/usr/bin/env python
"""Time the state decode kernel alone, on the chip, at the two state
cells' geometries, with and without the convolution ring's row write:

- ``olmo`` (``olmo-hybrid-7b.rollout-decode``, the delta rule,
  ``%linear_decode``): 6 linear layers x 128 slots, a slot's state 96 x
  30 heads x 192 float32 = 2.2 MB a layer, a ring position 96 x 128
  (11,520 channels padded);
- ``granite`` (``granite-4.0-h-micro.batch-decode``, the additive rule,
  ``%ssd_decode``): 36 mamba layers x 64 slots, 128 x 64 heads x 64 = 2.1
  MB, a ring position 40 x 128 (4,352 channels padded).

Variants:

- ``kernel<g>``: ``linear_decode`` taking ``g`` heads a grid step (olmo:
  2, 6, 10 or all 30; ``kernel``: the rule's own choice,
  ``linear_heads_per_step``), the state alone;
- ``kernel+ring``: the rule's own choice that also places each live
  slot's fresh convolution input in the ring leaf (what a decode step
  runs);
- ``dense`` (olmo): ``linear_decode_dense``, the same arithmetic as XLA
  makes it (the path without the kernel).

    python scripts/linear_decode_bench.py [olmo] [granite]   # on a TPU host

Each variant runs ``CALLS`` calls chained inside one jit (each call's
output feeds the next one's v, the layers in turn), the state and the
ring donated, so the figure is device time a call and not dispatch.
Occupancy: every slot live, a quarter live, none live. One JSON line per
(cell, occupancy, variant): us a call, the state's roofline floor
(``benchmark/costs_olmo_hybrid.py``, ``costs_granite_hybrid.py``; the
ring's bytes are not in it) and the share of it, and before that how far
each variant's results after one call lie from the dense step's (on the
first two layers; the ring compared bit for bit). A CPU run
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

from benchmark import costs_granite_hybrid, costs_olmo_hybrid, peaks  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402

CALLS = 24  # kernel calls chained in one jit
REPEATS = 8
RING = 4  # positions of the ring: the convolution's width

# state layers, slots, key rows, heads, value lanes a head, key groups,
# ring rows a position, the rule, its floor
CELLS = {
    "olmo": ((6, 128, 96, 30, 192, 30, 96), "delta",
             costs_olmo_hybrid.linear_decode_floor_seconds),
    "granite": ((36, 64, 128, 64, 64, 1, 40), "additive",
                costs_granite_hybrid.ssd_decode_floor_seconds),
}
TOYS = {"olmo": (2, 4, 16, 4, 64, 4, 8), "granite": (2, 4, 16, 8, 16, 1, 8)}


def inputs(geom, rule, seed=0):
    layers, b, dk, h, dv, keys, rows = geom
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    return dict(
        q=f32(unit(rng.standard_normal((b, dk, keys))) / 10),
        k=f32(unit(rng.standard_normal((b, dk, keys)))),
        v=f32(rng.standard_normal((b, h * dv))),
        a=f32(rng.uniform(0.9, 1.0, (b, h))),
        be=f32(rng.uniform(0.0, 2.0, (b, h))) if rule == "delta" else None,
        state=f32(rng.standard_normal((layers, 1, b, dk, h * dv))),
        ring=f32(rng.standard_normal((layers, 1, b, RING, rows, 128))),
        row=f32(rng.standard_normal((b, rows, 128))),
        pos=jnp.asarray(rng.integers(0, 4096, b), jnp.int32),
    )


def call(fn, x, state, ring, layer, active, rule, with_ring, v=None):
    """One step; returns (o, state, ring) (the ring as it came without
    the write)."""
    conv = dict(ring=ring, row=x["row"], pos=x["pos"]) if with_ring else {}
    out = fn(x["q"], x["k"], x["v"] if v is None else v, x["a"], x["be"],
             state, layer=layer, active=active, rule=rule, **conv)
    return out if with_ring else (*out, ring)


def chained(fn, layers, rule, with_ring):
    def run(x, state, ring, active):
        v = x["v"]
        for c in range(CALLS):
            o, state, ring = call(fn, x, state, ring, c % layers, active,
                                  rule, with_ring, v)
            v = x["v"] + 1e-3 * o
        return v, state, ring

    return jax.jit(run, donate_argnums=(1, 2))


def variants(geom, rule):
    layers, b, dk, h, dv, keys, rows = geom
    out = {"dense": (pk.linear_decode_dense, False)} if rule == "delta" else {}
    for g in ((None, 2, 6, 10, 30) if rule == "delta" else (None,)):
        if g is None or (h % g == 0 and (g * dv) % 128 == 0):
            out["kernel" if g is None else f"kernel{g}"] = (
                lambda *a, g=g, **kw: pk.linear_decode(
                    *a, heads_per_step=g, **kw), False)
    out["kernel+ring"] = (pk.linear_decode, True)
    return out


def bench(name, on_tpu):
    geom, rule, floor_of = CELLS[name]
    if not on_tpu:
        geom = TOYS[name]
    layers, b, dk, h, dv, keys, rows = geom
    x = inputs(geom, rule)
    found = variants(geom, rule)
    # agreement: one call of each against the dense step, half the slots
    # live, on the first two layers (three copies of granite's whole
    # state would not fit the chip)
    active = jnp.arange(b) % 2 == 0
    two = dict(x, state=x["state"][:2], ring=x["ring"][:2])
    want = call(pk.linear_decode_dense, two, two["state"], two["ring"], 1,
                active, rule, True)
    for variant, (fn, with_ring) in found.items():
        got = jax.jit(lambda x, fn=fn, w=with_ring: call(
            fn, x, x["state"], x["ring"], 1, active, rule, w))(two)
        line = {"cell": name, "variant": variant,
                "max_abs_diff_vs_dense": max(
                    float(jnp.max(jnp.abs(g - w)))
                    for g, w in zip(got[:2], want[:2]))}
        if with_ring:
            line["ring_equal_bit_for_bit"] = bool(jnp.all(got[2] == want[2]))
        print(json.dumps(line), flush=True)
    if not on_tpu:
        return
    table = peaks.peaks_for(jax.devices()[0].device_kind)
    for live in (b, b // 4, 0):
        active = jnp.arange(b) < live
        floor = floor_of(live, dk, h * dv, table)
        for variant, (fn, with_ring) in found.items():
            run = chained(fn, layers, rule, with_ring)
            state, ring = jnp.array(x["state"]), jnp.array(x["ring"])
            v, state, ring = run(x, state, ring, active)  # compile + warm
            jax.block_until_ready(v)
            times = []
            for _ in range(REPEATS):
                t = time.perf_counter()
                v, state, ring = run(x, state, ring, active)
                jax.block_until_ready(v)
                times.append((time.perf_counter() - t) / CALLS)
            us = 1e6 * statistics.median(times)
            print(json.dumps({
                "cell": name, "live": live, "slots": b, "variant": variant,
                "us_a_call": round(us, 2),
                "floor_us": round(1e6 * floor, 2),
                "roofline_share_pct": round(100 * 1e6 * floor / us, 1)
                if live else None,
            }), flush=True)
            del state, ring
    del x


def main() -> int:
    on_tpu = jax.default_backend() == "tpu"
    for name in sys.argv[1:] or list(CELLS):
        bench(name, on_tpu)
    if not on_tpu:
        print("not a TPU: agreement only", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
