#!/usr/bin/env python3
"""The two readings behind ``openpangu-ultra-moe-718b``'s tolerances, on
the chip.

    python3 benchmark/tools/pangu_precision.py --seeds 1 2 [--rehearse]

For each seed: the served weights, then (a) the comparison that decides
``correct`` (``benchmark/serve.py: check_logits``: the program's prefill in
the expanded form and decode through the latent leaf in the absorbed form
against the reference's expanded full forward), and (b) the reference
against itself with every product's operands rounded to ``bfloat16`` and to
``float8_e4m3fn``, on the same compared positions and in the same two
norms, with the share of (token, expert layer) pairs whose set of chosen
experts moved (a token's eighth and ninth expert changing places). Each
rounded reference is then held to the cell's own two limits by
``check_logits``'s rule (both norms at or under their limit): ``correct``
and ``over`` (the limits it passed) say whether an 8-bit path fails the
comparison, and by which limit. A tolerance belongs between (a)'s largest
reading and the float8 reading. One JSON line a seed, last.
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

CELL = "openpangu-ultra-moe-718b.reasoning-decode"


def norms(got, want) -> tuple[float, float]:
    """(max error of the largest logit, rms error of the rms logit)."""
    return (
        float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
        float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
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

    from benchmark.reference import pangu_moe

    model, geometry = dict(config["model"]), dict(config["engine"])
    check = dict(config["correct"])
    if args.rehearse:
        toy = config["rehearse"]
        model.update(toy["model"])
        geometry.update(toy["engine"])
        check.update(toy["correct"])
    cfg = serve.transformer_config(model)
    lens = np.asarray(check["prompt_lens"])
    steps, bucket = int(check["decode_steps"]), int(check["bucket"])
    lines = []
    for seed in args.seeds:
        params = serve.make_params(cfg, seed)
        ok, text = serve.check_logits(
            pangu_moe, cfg, params, check, seed, int(geometry["max_total"]))
        print(f"seed {seed}: {text}", flush=True)
        line = {"seed": seed, "correct": bool(ok), "program": text}
        if args.program_only:
            del params
            lines.append(line)
            continue
        # the same sequences check_logits made, and its compared positions
        rng = np.random.default_rng([seed, 1])
        seqs = rng.integers(0, cfg.vocab_size, (len(lens), bucket + steps),
                            np.int32)

        def compared(full):
            return np.stack([full[r, n - 1:n + steps]
                             for r, n in enumerate(lens)])

        want, chose = pangu_moe.forward(params, jnp.asarray(seqs),
                                        with_routing=True)
        for low in ("bfloat16", "float8_e4m3fn"):
            got, moved = pangu_moe.forward(params, jnp.asarray(seqs),
                                           precision=low, with_routing=True)
            mx, rms = norms(compared(got), compared(want))
            a, b = np.sort(chose, -1), np.sort(moved, -1)
            # (rows, layers, T, k) -> the compared positions only
            differ = np.any(a != b, axis=-1)
            at = np.stack([differ[r, :, n - 1:n + steps]
                           for r, n in enumerate(lens)])
            # check_logits's rule, on the limits the cell is run with
            over = [key for key, read in (("max_err_of_scale", mx),
                                          ("rms_err_of_rms", rms))
                    if not read <= check[key]]
            line[low] = {"correct": not over, "over": over,
                         "max_err_of_scale": mx, "rms_err_of_rms": rms,
                         "expert_sets_moved_share": float(at.mean()),
                         "expert_sets_moved_share_all_positions":
                             float(differ.mean())}
            print(f"seed {seed}: reference in {low}: {line[low]}", flush=True)
        del params
        lines.append(line)
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
