#!/usr/bin/env python
"""Time the exact top-k threshold alone, on the chip: the full sort
(``lax.top_k`` over the row, the parent's expression), the selection by
chunks ``_top_k_filter`` ships (``models/transformer.py: _kth_largest``
with ``_SELECT_CHUNKS``), its first level alone (chunks of 128, then a
sort of the k * 128 candidates: ISSUE 28's recipe to the letter) and a
bisection of the threshold over the order-preserving integer image of
the floats (32 counting passes, also exact; the fallback ISSUE 28
names, kept here and not in the program).

    chiprun -- python scripts/topk_select_bench.py

Each selection runs ``REPEATS`` times chained inside one jit (each
round's filter feeds the next), so the per-round figure is device time
and not dispatch; the figure includes the ``where`` pass the filter
ends with. One JSON line per (shape, selection), ms a round, and a
last line that says whether they all agree bit for bit. A CPU run
(``JAX_PLATFORMS=cpu``) checks agreement only: its times are no speed.
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
from jax import lax  # noqa: E402

from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    _SELECT_CHUNKS,
    _kth_largest,
)

K = 40
REPEATS = 8
CALLS = 20
SHAPES = ((48, 50257), (64, 50176))


def kth_sort(x, k):
    """The yardstick, written out here."""
    return lax.top_k(x, k)[0][..., -1:]


def kth_bisect(x, k):
    """Largest t with at least k elements >= t, built bit by bit over
    the unsigned image of the floats whose order is the floats' own."""
    i = lax.bitcast_convert_type(x, jnp.int32)
    u = lax.bitcast_convert_type(
        i ^ ((i >> 31) & 0x7FFFFFFF), jnp.uint32
    ) ^ jnp.uint32(0x80000000)
    ans = jnp.zeros(x.shape[:-1] + (1,), jnp.uint32)
    for b in range(31, -1, -1):
        cand = ans | jnp.uint32(1 << b)
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True) >= k
        ans = jnp.where(enough, cand, ans)
    i = lax.bitcast_convert_type(ans ^ jnp.uint32(0x80000000), jnp.int32)
    return lax.bitcast_convert_type(
        i ^ ((i >> 31) & 0x7FFFFFFF), jnp.float32
    )


SELECTIONS = {
    "sort": kth_sort,
    "chunked": lambda x, k: _kth_largest(x, k, _SELECT_CHUNKS),
    "one_level": lambda x, k: _kth_largest(x, k, _SELECT_CHUNKS[:1]),
    "bisect": kth_bisect,
}


def chained(kth):
    def run(x):
        for _ in range(REPEATS):
            # what falls under the threshold moves down and stays
            # finite, so every round selects over a full row
            x = jnp.where(x < kth(x, K), x - 1.0, x)
        return x

    return jax.jit(run)


def main():
    dev = jax.devices()[0]
    agree = True
    for shape in SHAPES:
        x = jax.random.normal(jax.random.PRNGKey(28), shape, jnp.float32)
        want = np.asarray(jax.jit(lambda a: kth_sort(a, K))(x))
        for name, kth in SELECTIONS.items():
            got = np.asarray(jax.jit(lambda a, f=kth: f(a, K))(x))
            same = bool(np.array_equal(got, want))
            agree &= same
            run = chained(kth)
            run(x).block_until_ready()
            times = []
            for _ in range(CALLS):
                t0 = time.perf_counter()
                run(x).block_until_ready()
                times.append((time.perf_counter() - t0) / REPEATS * 1e3)
            q = statistics.quantiles(times, n=4)
            print(json.dumps({
                "shape": list(shape), "k": K, "selection": name,
                "ms_per_round_p50": round(q[1], 4),
                "ms_per_round_p25": round(q[0], 4),
                "ms_per_round_p75": round(q[2], 4),
                "equals_sort": same, "platform": dev.platform,
                "device_kind": dev.device_kind,
            }), flush=True)
    print(json.dumps({"all_equal_sort": agree}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
