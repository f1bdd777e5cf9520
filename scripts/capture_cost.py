#!/usr/bin/env python
"""What an operator's capture costs the loop, and what it says: on the
flood cell's engine under the flood's closed-loop traffic, arm
``ProfileTrigger`` for ``--steps`` engine steps with the Python tracer off
and on (jax's default options: what ships), in turn, ``--rounds`` times
(the process's first round is cold), and print for each capture one JSON
line:

- ``start_s`` / ``stop_s``: the seconds ``step_start`` and ``step_end``
  held the loop's thread in ``start_trace`` and ``stop_trace`` (every slot
  waits these out);
- ``bytes``: the size of the ``.xplane.pb``;
- ``step_period_ms``: the capture's window over its decode steps, which
  says what the tracer costs the loop it watches;
- ``reduce_s``: the seconds ``obs.capture.loop_report`` took on it;
- ``report``: that report (idle seconds by phase, step gaps by program).

A last line gives each side's median over the warm rounds.

    chiprun -- python scripts/capture_cost.py [--workload CELL] [--seed N]

It builds the cell as ``benchmark/run.py`` does (weights, the ``correct``
check, warm-up), so it needs the TPU; ``--rehearse`` runs the toy size on
the CPU and measures nothing. The lines are also written to
``chiprun_out/capture_cost.json``.
"""

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import run as bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="gpt2-large.decode-flood")
    ap.add_argument("--seed", type=int, default=3600000707)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    args.seconds, args.trace = 30.0, 0

    _, cell, config, traffic_file = bench.load_cell(args.workload)
    jax = bench.start_jax(args.rehearse)
    if jax is None:
        return 2

    from benchmark import serve, traffic
    from benchmark.compile_log import CompileLog
    from deeplearning4j_tpu.obs import ProfileTrigger, capture

    class TimedTrigger(ProfileTrigger):
        """Times the two calls that hold the loop's thread."""

        start_s = stop_s = 0.0

        def step_start(self):
            t0 = time.perf_counter()
            super().step_start()
            self.start_s = max(self.start_s, time.perf_counter() - t0)

        def step_end(self):
            t0 = time.perf_counter()
            super().step_end()
            self.stop_s = max(self.stop_s, time.perf_counter() - t0)

    class WithoutPythonFrames(TimedTrigger):
        """The capture with ``python_tracer_level`` 0."""

        def step_start(self):
            real = jax.profiler.start_trace
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace = lambda log_dir: real(
                log_dir, profiler_options=options)
            try:
                super().step_start()
            finally:
                jax.profiler.start_trace = real

    ctx = bench.Context(args, cell, config, traffic_file, {}, CompileLog(jax))
    stack, (trace,) = serve.set_up(
        ctx, lambda vocab, max_total, divisor: [traffic.serve_trace(
            ctx.traffic, ctx.seed, ctx.seconds, vocab, max_total, divisor)],
    )
    lines = []
    load = serve.Load(stack.engine)
    try:
        load.start_closed(trace.requests, trace.outstanding, cycle=True)
        time.sleep(trace.ramp_s)
        for k in range(2 * args.rounds):
            kind = TimedTrigger if k % 2 else WithoutPythonFrames
            trigger = kind(bench.CACHE_DIR / "capture_cost")
            stack.engine.profile = trigger
            trigger.arm(args.steps)
            deadline = time.perf_counter() + 120.0
            while trigger.armed or not trigger.n_captures:
                if time.perf_counter() > deadline:
                    raise RuntimeError("the capture did not end")
                time.sleep(0.05)
            stack.check_well()
            path = capture.find_xplane(trigger.finished_capture())
            t0 = time.perf_counter()
            report = capture.loop_report(path)
            lines.append({
                "python_frames": bool(k % 2), "round": k // 2,
                "steps": args.steps, "start_s": trigger.start_s,
                "stop_s": trigger.stop_s, "bytes": path.stat().st_size,
                "step_period_ms": (1e3 * report["window_s"] / report["steps"]
                                   if report["steps"] else None),
                "reduce_s": time.perf_counter() - t0, "report": report,
                "platform": jax.devices()[0].platform,
            })
            print(json.dumps(lines[-1]), flush=True)
            time.sleep(1.0)
    finally:
        stack.engine.profile = None
        load.stop()
        stack.stop()
        # tens of MB a capture; the numbers are out
        shutil.rmtree(bench.CACHE_DIR / "capture_cost", ignore_errors=True)
    warm = [ln for ln in lines if ln["round"] > 0]
    if warm:
        lines.append({"warm_rounds": args.rounds - 1, "median": {
            ("python_frames" if frames else "no_python_frames"): {
                key: statistics.median(
                    ln[key] for ln in warm if ln["python_frames"] is frames
                ) for key in ("stop_s", "bytes", "step_period_ms")
                if all(ln[key] is not None for ln in warm)
            } for frames in (False, True)
        }})
        print(json.dumps(lines[-1]), flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "capture_cost.json").write_text(
        "".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
