#!/usr/bin/env python3
"""Find the knee of an open-loop mix: one set-up, then each rate in turn.

    python3 benchmark/tools/rate_sweep.py --workload gpt2-large.chat-steady \\
        --rates 12,16,18,20 --seconds 20 --seed 1

A tool, not a cell: it fixes the rate written into a traffic file, once,
when the cell is defined. For each rate it offers the cell's mix for the
ramp plus ``--seconds`` and prints one table row: requests due in the
window, how many failed, the backlog (requests due and not yet ended) at
the window's middle and end, TTFT and TPOT percentiles, tokens per second
delivered in the window and the mean occupancy. A rate is sustained when
the backlog at the end is no larger than at the middle.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def backlog(sent, t: float) -> int:
    return sum(1 for s in sent if s.due <= t and (s.ended is None or s.ended > t))


def main(argv=None) -> int:
    from benchmark import run as run_lib
    from benchmark import serve, stats, traffic
    from benchmark.compile_log import CompileLog

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma list, requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    rates = [float(r) for r in args.rates.split(",")]

    _, cell, config, mix = run_lib.load_cell(args.workload)
    if mix["kind"] != "open_loop":
        raise SystemExit("rate_sweep needs an open_loop mix")
    jax = run_lib.start_jax(args.rehearse)
    if jax is None:
        return 2
    ctx = run_lib.Context(args, cell, config, mix, {}, CompileLog(jax))

    def make_traces(vocab, max_total, divisor):
        return [
            traffic.serve_trace(dict(mix, rate_per_s=r), args.seed,
                                args.seconds, vocab, max_total, divisor)
            for r in rates
        ]

    stack, traces = serve.set_up(ctx, make_traces)
    print("rate_per_s due failed backlog_mid backlog_end ttft_p50_ms "
          "ttft_p95_ms tpot_p95_ms tokens_per_s occupancy late_p95_ms",
          flush=True)
    try:
        for rate, trace in zip(rates, traces):
            before = stack.engine.metrics.summary()
            load, win0, win1 = serve.offer(stack, trace, args.seconds)
            after = stack.engine.metrics.summary()
            sample, failed, good = serve.window_sample(trace, load, win0, win1)
            steps = after["steps"] - before["steps"]
            occupancy = (
                after["occupancy_mean"] * after["steps"]
                - before.get("occupancy_mean", 0.0) * before["steps"]
            ) / max(1, steps)
            tpots = [s.tpot_s() for s in good if s.tpot_s() is not None]
            ttfts = [s.ttft_s() for s in good]
            print(
                rate, len(sample), len(failed),
                backlog(load.sent, 0.5 * (win0 + win1)),
                backlog(load.sent, win1),
                round(1e3 * stats.percentile(ttfts, 50), 1),
                round(1e3 * stats.percentile(ttfts, 95, len(failed)), 1),
                round(1e3 * stats.percentile(tpots, 95, len(failed)), 2),
                round(serve.tokens_between(load, win0, win1) / args.seconds, 1),
                round(occupancy, 1),
                round(1e3 * stats.percentile(load.late_s, 95), 2),
                flush=True,
            )
            serve.wait_idle(stack.engine, timeout=120.0)
    finally:
        stack.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
