#!/usr/bin/env python
"""Time the training flash kernels alone, forward plus backward, on the
chip, at the gpt2-medium train geometry (8 x 16 heads x 1,024 x 64 bf16,
causal), in the two layouts the training block can hand them:

- ``bhtd+copies``: what the block did before PR 32. q, k and v arrive
  (B, T, H*K) as the projection writes them, are moved to (B, H, T, K)
  for ``flash_attention_trainable(layout="bhtd")``, and o, its
  cotangent, dq, dk and dv are moved back: eight layout copies a call;
- ``bhtd``: the same kernels with operands that are (B, H, T, K)
  already (the kernels' own time);
- ``packed bq/bk``: ``flash_attention_packed`` on the (B, T, H*K) arrays
  themselves, a block being a 128-lane group of two heads, at several
  forward and backward block sizes (``transformer._flash_blocks`` ships
  the first);
- ``packed bands=S bwd-by=rows|cols``: the shipped blocks with the tile
  that crosses the diagonal walked in causal bands of ``S`` rows
  (``pk._band_rows`` ships one ``S``; ``S`` = the tile's rows is no
  bands), the backward cut by query rows (shipped) or by key columns
  (``bwd_by_columns_kernel`` below: measured, not shipped).

    chiprun -- python scripts/flash_train_bench.py

Each variant runs ``LAYERS`` calls chained inside one jit (a call's
output is its own cotangent, and its dq, dk, dv feed the next call's q,
k, v), so the figure is device time a call and not dispatch; the
forward is also timed alone. One JSON line per variant, us a call, with
``pk.flash_computed_share`` (the share of the T x T score square the
variant multiplies), and two last lines that say how far the packed
entry's output and three gradients lie from the ``bhtd`` entry's, at the
bench geometry and at twice its rows (a quarter of the batch).
A CPU run (``JAX_PLATFORMS=cpu``) checks agreement only, at a toy size:
its times are no speed.
"""

import contextlib
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
# rows of a causal band of the shipped diagonal tile (``pk._band_rows``
# decides; these stand in for it): the tile's own rows = no bands
BAND_ROWS = [1024, 512, 256, 128]


def heads_first(x, h):  # (B, T, H*K) -> (B, H, T, K)
    b, t, hk = x.shape
    return x.reshape(b, t, h, hk // h).transpose(0, 2, 1, 3)


def rows_first(x):  # (B, H, T, K) -> (B, T, H*K)
    b, h, t, k = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * k)


@contextlib.contextmanager
def patched(**names):
    """``pk``'s module names set for the time a variant is traced."""
    kept = {n: getattr(pk, n) for n in names}
    for n, value in names.items():
        setattr(pk, n, value)
    try:
        yield
    finally:
        for n, value in kept.items():
            setattr(pk, n, value)


def bwd_by_columns_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
    dq_ref, dk_ref, dv_ref, dk_s, dv_s,
    *, block_q, block_k, n_q, head_dim, scale, causal, dq_partials,
):
    """``pk._flash_bwd_packed_kernel`` for the one causal tile of the
    bench geometry, cut the other way: band ``j`` takes the key columns
    ``[j S, (j + 1) S)`` and the query rows from its first column down,
    so dk's and dv's rows are written once over long contractions and dq
    is summed over the bands in a float32 value. Same tile math
    (``pk._flash_bwd_tile``); stands in for the shipped kernel in the
    ``bwd-by=cols`` variants."""
    assert causal and dq_partials and n_q == 1 and block_q == block_k
    rows = pk._band_rows(block_q)
    heads = pk._PACK_LANES // head_dim
    k_blk, v_blk, do = k_ref[0], v_ref[0], do_ref[0]
    q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
    do_o = do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    dk_s[:] = jnp.zeros_like(dk_s)
    dv_s[:] = jnp.zeros_like(dv_s)
    corner = pk._causal_bias(0, 0, rows, rows)
    dq_c = None
    for c0 in range(0, block_q, rows):
        below = block_q - c0 - rows
        tile = corner if not below else jnp.concatenate(
            [corner, jnp.zeros((below, rows), corner.dtype)])
        band, cols = slice(c0, block_q), slice(c0, c0 + rows)
        q_r, do_r, do_o_r = q[band], do[band], do_o[band]
        dq_b = jnp.zeros(q_r.shape, jnp.float32)
        for a in range(heads):
            mine = pk._head_lanes(q_r.shape, head_dim, a)
            dq_a = pk._flash_bwd_tile(
                jnp.where(mine, q_r, jnp.zeros_like(q_r)),
                k_blk[cols], v_blk[cols],
                jnp.where(mine, do_r, jnp.zeros_like(do_r)),
                lse_ref[0, 0, band, a],
                jnp.sum(jnp.where(mine, do_o_r, 0.0), axis=-1),
                dk_s.at[cols], dv_s.at[cols], scale, lambda: tile,
            )
            dq_b = jnp.where(mine, dq_a, dq_b)
        dq_c = dq_b if dq_c is None else jnp.concatenate(
            [dq_c[:c0], dq_c[c0:] + dq_b])
    dq_ref[0, 0] = dq_c.astype(dq_ref.dtype)
    dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def attend(variant, h, k, blocks):
    """(q, k, v) -> o, all (B, T, H*K) but for ``bhtd``, whose four are
    (B, H, T, K)."""
    bq, bk = blocks
    if variant.startswith("packed"):
        return lambda q, kk, v: pk.flash_attention_packed(
            q, kk, v, k, block_q=bq, block_k=bk, causal=True)
    flash = lambda q, kk, v: pk.flash_attention_trainable(  # noqa: E731
        q, kk, v, block_q=bq, block_k=bk, causal=True, layout="bhtd")
    if variant == "bhtd":
        return flash
    return lambda q, kk, v: rows_first(
        flash(*(heads_first(a, h) for a in (q, kk, v))))


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


def chained_forward(fn, layers):
    """``layers`` forward calls alone, each fed by the one before."""
    def run(q, k, v):
        for _ in range(layers):
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            q = fn(q, k, v)
        return q

    return jax.jit(run)


def timed(run, args, calls):
    jax.block_until_ready(run(*args))  # compiles
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main():
    on_chip = jax.default_backend() == "tpu"
    b, h, t, k = GEOMETRY if on_chip else TOY
    dtype = jnp.bfloat16
    rng = np.random.default_rng(32)
    rows = [jnp.asarray(rng.normal(size=(b, t, h * k)), dtype)
             for _ in range(3)]
    shipped = _flash_blocks(t)
    layers, calls = (LAYERS, CALLS) if on_chip else (1, 1)
    no_bands = {"_band_rows": lambda block: block}  # the bhtd kernels'
    variants = [("bhtd+copies", shipped, no_bands),
                ("bhtd", shipped, no_bands)]
    # the shipped diagonal tile in causal bands of several heights, the
    # backward cut by query rows and by key columns
    for band in (BAND_ROWS if on_chip else [t, t // 2, t // 4]):
        names = {"_band_rows": lambda block, band=band: min(band, block)}
        variants.append((f"packed bands={band} bwd-by=rows", shipped, names))
        if band < t:
            variants.append((
                f"packed bands={band} bwd-by=cols", shipped,
                dict(names, _flash_bwd_packed_kernel=bwd_by_columns_kernel)))
    variants += [("packed", blk, {}) for blk in
                 (PACKED_BLOCKS if on_chip else [(t, t), (t // 2, t // 4)])]
    for name, blocks, names in variants:
        fn = attend(name, h, k, blocks)
        args = [heads_first(a, h) for a in rows] if name == "bhtd" else rows
        try:
            with patched(**names):
                share = pk.flash_computed_share(t, *blocks, True)
                both = timed(chained(fn, layers), args, calls)
                forward = timed(chained_forward(fn, layers), args, calls)
        except Exception as e:  # a block Mosaic refuses is a finding
            print(json.dumps({"variant": name, "blocks": blocks,
                              "refused": str(e).splitlines()[0][:300]}),
                  flush=True)
            continue
        print(json.dumps({
            "variant": name, "blocks": blocks,
            "us_a_call": both / layers * 1e6,
            "forward_us_a_call": forward / layers * 1e6,
            "computed_share": share,
            "platform": jax.devices()[0].platform,
        }), flush=True)

    # one call of each entry on the same operands, output and gradients:
    # at the bench geometry (one tile a lane group: the lone tile's
    # transposed forward) and at twice its rows (four tiles: a banded one
    # beside a whole and a skipped one, folded into the running state)
    names = ("o", "dq", "dk", "dv")
    agree = True
    longer = [jnp.asarray(rng.normal(size=(max(1, b // 4), 2 * t, h * k)),
                          dtype) for _ in range(3)]
    for operands in (rows, longer):
        batch, length, _ = operands[0].shape
        blocks = _flash_blocks(length)

        def one(variant):
            o, pull = jax.vjp(attend(variant, h, k, blocks), *operands)
            return (o,) + pull(o)

        got, want = jax.jit(lambda: (one("packed"), one("bhtd+copies")))()
        apart = {
            n: float(jnp.max(jnp.abs(
                x.astype(jnp.float32) - y.astype(jnp.float32))))
            for n, x, y in zip(names, got, want)
        }
        scale = {n: float(jnp.max(jnp.abs(y.astype(jnp.float32))))
                 for n, y in zip(names, want)}
        # one bf16 rounding of the largest value, where the two differ
        # (two on the CPU, whose interpreted products round more)
        within = 2.0**-7 if on_chip else 2.0**-6
        agree &= all(apart[n] <= scale[n] * within for n in names)
        print(json.dumps({
            "geometry": (batch, h, length, k), "blocks": blocks,
            "computed_share": pk.flash_computed_share(length, *blocks, True),
            "max_abs_apart": apart, "largest": scale, "agree": agree}))
    return 0 if agree else 1


if __name__ == "__main__":
    sys.exit(main())
