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
  ``decode_kernel=False`` runs);
- the priced parts, all slots live, the rule's block: the same walk
  (``pallas_kernels._walk_call``: copies, the placing of the new row,
  the loop) with a body that stops early, ``copies`` (no arithmetic),
  ``<form>+scores``, ``<form>+softmax``, ``<form>+values`` (the whole
  body), one softmax over the whole block, in three forms
  (:func:`trial_math`): ``rows`` (heads on sublanes, the cache block the
  MXU's stationary operand in both products: what shipped before PR 37;
  ``kernel`` is this with the block's rows cut in two parts whose
  softmaxes run under each other's products), ``lanes`` (heads on lanes,
  the block streamed in both products: ISSUE 37's design) and
  ``lanes_p`` (scores and state as ``lanes``, the weights turned back
  for a values product as in ``rows``: its fallback). Each again as
  ``..., no copies`` (:func:`held_call`: the body folding blocks that
  stay in VMEM, so what it costs the core when it never waits for
  HBM). Beside each: us a block, and what the form's MXU weight passes
  alone would take if no load were hidden (:func:`mxu_pass_us`), so
  the next reader sees which unit binds.

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


FORMS = ("rows", "lanes", "lanes_p")
STAGES = ("scores", "softmax", "values")
# a v5e's matrix units: four of 128 x 128, 197 TFLOP/s at 1.5 GHz
MXUS, MXU_TILE, MXU_HZ = 4, 128, 1.5e9


def mxu_pass_us(form, upto, block_t, h, width, r_kv):
    """us a block of a body in ``form`` that runs as far as ``upto``, if
    only the MXU's weight passes counted and nothing hid a tile's load
    (ISSUE 37's account of the kernel; the chip says the loads ARE
    hidden, PERF.md, PR 37): a 128 x 128 tile of the stationary operand
    takes 128 cycles to load and then a cycle for each row of the other
    operand streamed through it; the chip's four units share the tiles.
    ``rows`` holds the cache block still in both products (the H query
    rows stream), ``lanes`` streams it in both, ``lanes_p`` in the
    scores alone."""
    def tiles(n):
        return -(-n // MXU_TILE)

    def cycles(n_tiles, streamed):
        return n_tiles * (MXU_TILE + streamed)

    held = {
        "scores": cycles(tiles(width) * tiles(block_t), h),
        "values": cycles(tiles(block_t) * tiles(r_kv), h),
    }
    streamed = {
        "scores": cycles(tiles(width) * tiles(h), block_t),
        "values": cycles(tiles(block_t) * tiles(h), r_kv),
    }
    passes = (held if form == "rows" else streamed)["scores"]
    if upto == "values":
        passes += (streamed if form == "lanes" else held)["values"]
    return (0 if upto == "copies" else passes) / MXUS / MXU_HZ * 1e6


def trial_math(form, upto, r_kv):
    """The latent walk's arithmetic with ONE softmax over the whole
    block, in one of ``FORMS``, stopped after ``upto`` (``"copies"``:
    none of it; then ``STAGES``). ``rows``: heads on sublanes, the cache
    block the MXU's stationary operand in both products; what shipped
    before PR 37, and what ships since but for the block's rows cut in
    parts (``pallas_kernels._latent_walk_math``). ``lanes``: heads on
    lanes, scores (block_t, H), state (1, H), accumulator (r_kv, H), the
    block streamed through the MXU in both products: ISSUE 37's design.
    ``lanes_p``: scores and state as ``lanes``, the weights turned back
    for a values product as in ``rows``: ISSUE 37's fallback. The
    precision is the shipped body's everywhere (bf16 operands, float32
    products and state, ``p`` rounded to the cache's dtype). A body
    that stops early returns its state's sums, no attention."""
    rows_form = form == "rows"
    axis = 1 if rows_form else 0  # the score tile's axis of cache rows
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))

    def math(q_ref, dtype):
        q = q_ref[0]
        h = q.shape[0]
        q_t = None if rows_form else q.T

        def a_head(fill):
            return jnp.full((h, 1) if rows_form else (1, h), fill,
                            jnp.float32)

        def state0():
            acc = (r_kv, h) if form == "lanes" else (h, r_kv)
            return (a_head(-jnp.inf), a_head(0.0),
                    jnp.zeros(acc, jnp.float32))

        def fold(state, buf, slot, j, last):
            m_prev, l_prev, acc = state
            if upto == "copies":
                return state
            kb = buf[slot, 0]
            if rows_form:
                s = jax.lax.dot_general(
                    q, kb, nt, preferred_element_type=jnp.float32)
            else:
                s = jnp.dot(kb, q_t, preferred_element_type=jnp.float32)
            if upto == "scores":
                # every score is used (of a slice Mosaic computes only
                # the tiles that hold it): summed down the sublanes
                tally = jnp.sum(s, axis=0, keepdims=True)
                if rows_form:
                    tally = jnp.sum(tally, axis=1, keepdims=True)
                return m_prev, l_prev + tally, acc
            at = j * kb.shape[0] + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, axis)
            s = jnp.where(at > last, -jnp.inf, s)
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=axis, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=axis, keepdims=True)
            if upto == "softmax":
                return m_new, l_new, acc
            p = p.astype(kb.dtype)
            if form == "lanes":
                pv = jax.lax.dot_general(
                    kb[:, :r_kv], p, tn, preferred_element_type=jnp.float32)
            else:
                if form == "lanes_p":
                    p, corr = p.T, corr.T
                pv = jnp.dot(p, kb[:, :r_kv],
                             preferred_element_type=jnp.float32)
            return m_new, l_new, acc * corr + pv

        def finish(state):
            _, l, acc = state
            if form == "lanes_p":
                l = l.T
            l = jnp.maximum(l, 1e-30)
            out = acc / l if upto == "values" else acc + l
            return out.T if form == "lanes" else out

        return state0, fold, finish

    return math


def trial_call(form, upto, q, cache, new, pos, active, layer, r_kv, block_t):
    """``latent_decode_attention_write`` with :func:`trial_math` for its
    arithmetic: the same walk, copies and placing of the new row."""
    last = pk._decode_last_rows(pos, active, q.shape[0], cache.shape[3])
    return pk._walk_call(
        trial_math(form, upto, r_kv), q, cache, new, pos, last,
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), out_width=r_kv,
        block_t=block_t, interpret=pk._default_interpret(),
        name="latent_trial",
    )


HELD_BLOCKS = 4  # blocks a slot folds in ``held_call``


def held_call(form, upto, q, cache, layer, r_kv, block_t):
    """:func:`trial_math`'s arithmetic with NO copies: every slot folds
    ``HELD_BLOCKS`` blocks out of three that were brought into VMEM once
    (the first block of the first three slots), so the figure is what
    the body costs the core when it never waits for HBM."""
    b, h, width = q.shape
    math = trial_math(form, upto, r_kv)
    held = cache[layer, 0, :pk._WALK_BUFFERS, :block_t][:, None]

    def kernel(q_ref, buf, o_ref):
        state0, fold, finish = math(q_ref, buf.dtype)
        state = jax.lax.fori_loop(
            0, HELD_BLOCKS,
            lambda j, st: fold(st, buf, j % pk._WALK_BUFFERS, j,
                               HELD_BLOCKS * block_t - 3),
            state0())
        o_ref[0] = finish(state).astype(o_ref.dtype)

    interpret = pk._default_interpret()
    return pk.pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, r_kv), q.dtype),
        grid=(b,),
        in_specs=[
            pk.pl.BlockSpec((1, h, width), lambda i: (i, 0, 0)),
            pk.pl.BlockSpec(held.shape, lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pk.pl.BlockSpec((1, h, r_kv), lambda i: (i, 0, 0)),
        compiler_params=pk._dim_semantics(interpret, ("arbitrary",)),
        interpret=interpret,
        name="latent_held",
    )(q, held), cache


def attend(how, r_kv, q, cache, new, pos, active, layer):
    """One call of variant ``how``: ``"dense"``, a block size of the
    shipped kernel (``None``: the rule's), or a trial's ``(form, upto,
    block_t)``."""
    if how == "dense":
        return dense(q, cache, new, pos, active, layer, r_kv)
    if isinstance(how, tuple) and how[-1] == "held":
        return held_call(*how[:2], q, cache, layer, r_kv, how[2])
    if isinstance(how, tuple):
        return trial_call(*how[:2], q, cache, new, pos, active, layer, r_kv,
                          how[2])
    return pk.latent_decode_attention_write(
        q, cache, new, pos, r_kv, layer=layer, active=active, block_t=how)


def variant(how, n_layers, r_kv):
    def run(q, cache, new, pos, active):
        for c in range(CALLS):
            o, cache = attend(how, r_kv, q, cache, new, pos, active,
                              c % n_layers)
            q = q.at[..., :r_kv].add((o * 0.125).astype(q.dtype))
            new = (new * 0.5 + q[:, :1] * 0.5).astype(new.dtype)
        return q, cache

    return jax.jit(run, donate_argnums=(1,))


def one_call(how, r_kv):
    def run(q, cache, new, pos, active):
        return attend(how, r_kv, q, cache, new, pos, active, 1)[0]

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
    rule = pk.latent_block_rows(t, width, jnp.dtype(dtype).itemsize)
    kernels = [(f"kernel{r or ''}", r, ("rows", "values", r or rule))
               for r in ([None, 128, 256, 512, 1024] if on_chip
                         else [None, 128])]
    bodies = [("rows", "copies")] + [
        (form, upto) for form in FORMS for upto in STAGES
        # lanes_p differs from lanes in the values product alone
        if upto == "values" or form != "lanes_p"]
    trials = [("copies" if upto == "copies" else f"{form}+{upto}",
               (form, upto, rule)) for form, upto in bodies]
    trials += [(f"{form}+{upto}, no copies", (form, upto, rule, "held"))
               for form, upto in bodies[1:]]
    worst = 0.0
    for occ, active in (("all", np.ones(b, bool)), ("quarter", live)):
        contexts = [int(p) + 1 for p, a in zip(np.asarray(pos), active) if a]
        act = jnp.asarray(active)
        variants = [("dense", "dense", None)] + kernels
        if occ == "all":
            variants += [(name, how, how) for name, how in trials]
        first = {}
        for name, how, body in variants:
            fn = variant(how, nl, r_kv)
            whole = body is None or (
                body[1] == "values" and body[-1] != "held")
            if whole:
                # agreement is judged on ONE call (a chain feeds each
                # output into the next query and grows a rounding with it)
                first[name] = np.asarray(one_call(how, r_kv)(
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
            held = body is not None and body[-1] == "held"
            if on_chip and not held:  # no walk, no floor of this call's
                floor = costs_pangu.latent_decode_floor_seconds(
                    contexts, 1, h, r_kv, rope, 2,
                    peaks.peaks_for(jax.devices()[0].device_kind))
                line["floor_us"] = floor * 1e6
                line["roofline_share"] = 100 * floor / (
                    line["us_a_call"] * 1e-6)
            if body is not None:
                form, upto, block_t = body[:3]
                line["block_t"] = block_t
                line["us_a_block"] = line["us_a_call"] / (
                    b * HELD_BLOCKS if held
                    else sum(-(-c // block_t) for c in contexts))
                line["mxu_pass_us_a_block"] = mxu_pass_us(
                    form, upto, block_t, h, width, r_kv)
                if whole:
                    line["max_abs_from_dense"] = float(
                        np.max(np.abs(first[name] - first["dense"])))
                    line["scale"] = float(np.max(np.abs(first["dense"])))
                    worst = max(
                        worst, line["max_abs_from_dense"] / line["scale"])
            print(json.dumps(line), flush=True)
    ok = worst < (0.05 if on_chip else 1e-4)
    print(json.dumps({"agree": bool(ok), "worst_of_scale": worst}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
