#!/usr/bin/env python
"""Time the training flash kernels alone, forward plus backward, on the
chip, at the gpt2-medium train geometry (8 x 16 heads x 1,024 x 64 bf16,
causal), in the two layouts the training block can hand them:

- ``bhtd+copies``: what the block did before PR 32. q, k and v arrive
  (B, T, H*K) as the projection writes them, are moved to (B, H, T, K)
  for ``flash_attention_trainable(layout="bhtd")``, and dq, dk and dv
  are moved back: six layout copies a call;
- ``bhtd``: the same kernels with operands that are (B, H, T, K)
  already (the kernels' own time);
- ``packed bq/bk``: ``flash_attention_packed`` on the (B, T, H*K) arrays
  themselves, a block being a 128-lane group of two heads, at several
  forward and backward block sizes (``transformer._flash_blocks`` ships
  the first).

    chiprun -- python scripts/flash_train_bench.py

Each variant runs ``LAYERS`` calls chained inside one jit (a call's
output is its own cotangent, and its dq, dk, dv feed the next call's q,
k, v), so the figure is device time a call and not dispatch. One JSON
line per variant, us a call, and a last line that says how far the
packed entry's output and three gradients lie from the ``bhtd`` entry's.
A CPU run (``JAX_PLATFORMS=cpu``) checks agreement only, at a toy size:
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

from deeplearning4j_tpu.models.transformer import _flash_blocks  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402

LAYERS = 24  # kernel calls (forward + backward) chained in one jit
CALLS = 10

GEOMETRY = (8, 16, 1024, 64)  # batch, heads, rows, head size
TOY = (2, 4, 64, 32)
PACKED_BLOCKS = [(1024, 1024), (512, 1024), (1024, 512), (512, 512),
                 (256, 512), (256, 256)]


def heads_first(x, h):  # (B, T, H*K) -> (B, H, T, K)
    b, t, hk = x.shape
    return x.reshape(b, t, h, hk // h).transpose(0, 2, 1, 3)


def rows_first(x):  # (B, H, T, K) -> (B, T, H*K)
    b, h, t, k = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * k)


def attend(variant, h, k, blocks):
    bq, bk = blocks
    if variant == "packed":
        return lambda q, kk, v: pk.flash_attention_packed(
            q, kk, v, k, block_q=bq, block_k=bk, causal=True)
    flash = lambda q, kk, v: pk.flash_attention_trainable(  # noqa: E731
        q, kk, v, block_q=bq, block_k=bk, causal=True, layout="bhtd")
    if variant == "bhtd":
        return flash
    return lambda q, kk, v: flash(*(heads_first(a, h) for a in (q, kk, v)))


def chained(fn, layers):
    """``layers`` forward + backward calls, each fed by the one before."""
    def run(q, k, v):
        for _ in range(layers):
            # the barriers stand for the projection products on either
            # side: without them XLA folds a layout copy into the
            # elementwise glue of this loop, which the train step has not
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            o, pull = jax.vjp(fn, q, k, v)
            dq, dk, dv = jax.lax.optimization_barrier(pull(o))
            q = (q + dq * 0.125).astype(q.dtype)
            k = (k + dk * 0.125).astype(k.dtype)
            v = (v + dv * 0.125).astype(v.dtype)
        return q, k, v

    return jax.jit(run)


def main():
    on_chip = jax.default_backend() == "tpu"
    b, h, t, k = GEOMETRY if on_chip else TOY
    dtype = jnp.bfloat16
    rng = np.random.default_rng(32)
    rows = [jnp.asarray(rng.normal(size=(b, t, h * k)), dtype)
            for _ in range(3)]
    shipped = _flash_blocks(t)
    variants = [("bhtd+copies", shipped), ("bhtd", shipped)]
    variants += [("packed", blk) for blk in
                 (PACKED_BLOCKS if on_chip else [(t, t), (t // 2, t // 4)])]
    for name, blocks in variants:
        fn = attend(name, h, k, blocks)
        args = [heads_first(a, h) for a in rows] if name == "bhtd" else rows
        try:
            run = chained(fn, LAYERS if on_chip else 1)
            jax.block_until_ready(run(*args))
        except Exception as e:  # a block Mosaic refuses is a finding
            print(json.dumps({"variant": name, "blocks": blocks,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)
            continue
        times = []
        for _ in range(CALLS if on_chip else 1):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "variant": name, "blocks": blocks,
            "us_a_call": statistics.median(times)
            / (LAYERS if on_chip else 1) * 1e6,
            "platform": jax.devices()[0].platform,
        }), flush=True)

    # one call of each entry on the same operands: output and gradients
    def one(variant):
        fn = attend(variant, h, k, shipped)
        o, pull = jax.vjp(fn, *rows)
        do = o if variant == "packed" else rows_first(o)
        return (do,) + pull(o)

    names = ("o", "dq", "dk", "dv")
    got, want = jax.jit(lambda: (one("packed"), one("bhtd+copies")))()
    apart = {
        n: float(jnp.max(jnp.abs(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
        for n, x, y in zip(names, got, want)
    }
    scale = {n: float(jnp.max(jnp.abs(y.astype(jnp.float32))))
             for n, y in zip(names, want)}
    # one bf16 rounding of the largest value, where the two differ at all
    agree = all(apart[n] <= scale[n] * 2.0**-7 for n in names)
    print(json.dumps({"max_abs_apart": apart, "largest": scale,
                      "agree": agree}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
