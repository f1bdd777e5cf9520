#!/usr/bin/env python3
"""The readings behind ``granite-4.0-h-micro``'s tolerances, on the chip.

    python3 benchmark/tools/granite_hybrid_precision.py --seeds 1 2 \
        [--rehearse] [--decode-steps N] [--prefixes 9 65]

For each seed: the served weights, then (a) the comparison that decides
``correct`` (``benchmark/serve.py: check_logits``: the program's prefill,
the mamba layers in the chunked form of the additive rule, then decode
through the cache in a ``lax.scan``, the state advanced by ``ssd_decode``,
against the reference's token-by-token full forward, one layer at a time),
(b) the reference against itself with every product's operands rounded to
``bfloat16`` and to ``float8_e4m3fn`` (the state kept float32), (c) the
reference with its state rounded to ``bfloat16`` after every position
(every product float32), and (d) the program of (a) with its state leaf
rounded to ``bfloat16`` wherever a program stores it, after the prefill
and after every decode step: what a ``bfloat16`` state leaf would read.
Each reading is on the same compared positions and in the same two norms,
and each is held to the cell's own two limits by ``check_logits``'s rule
(both norms at or under their limit): ``correct`` and ``over`` (the limits
it failed). (a) and (d) are also read on the first ``--prefixes``
compared positions of each row. A tolerance belongs above (a)'s largest
reading and under the float8 reading and (d)'s smallest. One JSON line a
seed, last.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "granite-4.0-h-micro.batch-decode"


def norms(got, want) -> tuple[float, float]:
    """(max error of the largest logit, rms error of the rms logit)."""
    return (
        float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
        float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))),
    )


def program_logits(cfg, params, prompts, lens, forced, max_total,
                   state_dtype=None):
    """``check_logits``'s program run, (rows, steps + 1, V) float32: the
    prefill, then ``forward_one`` through the cache in a ``lax.scan``.
    ``state_dtype``: the state leaf rounded to it after the prefill and
    after every step, where a program with a leaf of that type stores it
    (the kernel computes in float32 from what it loads)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deeplearning4j_tpu.models.transformer import _decode_builder

    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    rows = len(lens)

    def stored(caches):
        if state_dtype is None:
            return caches
        fi = jnp.finfo(state_dtype)
        # reduce_precision, not a convert pair XLA may drop
        return dict(caches, state=lax.reduce_precision(
            caches["state"], exponent_bits=fi.nexp, mantissa_bits=fi.nmant))

    @jax.jit
    def through_cache(params, prompts, lens, forced):
        caches, lg0 = prefill(
            params, init_caches(rows, max_total), prompts, lens - 1)

        def one(carry, tok_j):
            caches, j = carry
            lg, caches = fwd1(params, caches, tok_j, lens + j)
            return (stored(caches), j + 1), lg

        _, lgs = lax.scan(one, (stored(caches), jnp.int32(0)), forced.T)
        return jnp.concatenate([lg0[None], lgs], axis=0)

    return np.asarray(
        through_cache(params, jnp.asarray(prompts), jnp.asarray(lens),
                      jnp.asarray(forced)), np.float32,
    ).transpose(1, 0, 2)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--decode-steps", type=int, default=None,
                    help="decode steps in place of the configuration's")
    ap.add_argument("--prefixes", type=int, nargs="*", default=[],
                    help="also read (a) and (d) on each row's first P "
                         "compared positions")
    ap.add_argument("--program-only", action="store_true",
                    help="reading (a) alone: many seeds in one call")
    args = ap.parse_args()

    from benchmark import run as run_lib
    from benchmark import serve

    _, _, config, _ = run_lib.load_cell(CELL)
    jax = run_lib.start_jax(args.rehearse)
    if jax is None:
        return 2
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid

    model, geometry, check, _ = serve.sizes(
        type("Ctx", (), {"config": config, "rehearse": args.rehearse}))
    if args.decode_steps is not None:
        check["decode_steps"] = args.decode_steps
    cfg = serve.transformer_config(model)
    max_total = int(geometry["max_total"])
    lens = np.asarray(check["prompt_lens"], np.int32)
    steps, bucket = int(check["decode_steps"]), int(check["bucket"])

    def held(name, got, want):
        mx, rms = norms(got, want)
        # check_logits's rule, on the limits the cell is run with
        over = [key for key, read in (("max_err_of_scale", mx),
                                      ("rms_err_of_rms", rms))
                if not read <= check[key]]
        reading = {"correct": not over, "over": over,
                   "max_err_of_scale": mx, "rms_err_of_rms": rms}
        print(f"seed {seed}: {name}: {reading}", flush=True)
        return reading

    lines = []
    for seed in args.seeds:
        params = serve.make_params(cfg, seed)
        ok, text = serve.check_logits(
            granite_hybrid, cfg, params, check, seed, max_total)
        print(f"seed {seed}: {text}", flush=True)
        line = {"seed": seed, "decode_steps": steps, "correct": bool(ok),
                "program": text}
        if args.program_only:
            del params
            lines.append(line)
            continue
        # the same sequences check_logits made, and its compared positions
        rng = np.random.default_rng([seed, 1])
        seqs = rng.integers(0, cfg.vocab_size, (len(lens), bucket + steps),
                            np.int32)
        forced = np.stack([seqs[r, n:n + steps] for r, n in enumerate(lens)])

        def compared(full):
            return np.stack([full[r, n - 1:n + steps]
                             for r, n in enumerate(lens)])

        want = compared(granite_hybrid.forward(params, jnp.asarray(seqs)))
        for name, kw in (("bfloat16", {"precision": "bfloat16"}),
                         ("float8_e4m3fn", {"precision": "float8_e4m3fn"}),
                         ("state_bfloat16", {"state_dtype": "bfloat16"})):
            got = compared(granite_hybrid.forward(params, jnp.asarray(seqs),
                                                  **kw))
            line[name] = held(f"reference, {name}", got, want)
        for name, dtype in (("program", None),
                            ("program_state_bfloat16", jnp.bfloat16)):
            got = program_logits(cfg, params, seqs[:, :bucket], lens, forced,
                                 max_total, dtype)
            line[name + "_all"] = held(name, got, want)
            for p in args.prefixes:
                line[f"{name}_first_{p}"] = held(
                    f"{name}, first {p}", got[:, :p], want[:, :p])
        del params
        lines.append(line)
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
