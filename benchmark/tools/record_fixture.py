#!/usr/bin/env python3
"""Record the small trace that ``benchmark/tests`` checks the reduction on.

    python3 benchmark/tools/record_fixture.py <output directory>

On a TPU: a jitted ``step`` (two matmuls around the program's flash
attention kernel) runs five times with a pause between runs, under the
profiler. Writes ``tiny.xplane.pb`` and ``tiny.dump.txt``, every device
event with its start and duration, to check the fixture's expected numbers
by hand. The committed copy is ``benchmark/tests/data/tiny.xplane.pb``.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention_trainable

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 2
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def step(x, w):
        q = (x @ w).reshape(2, 256, 2, 64).transpose(0, 2, 1, 3)
        o = flash_attention_trainable(
            q, q, q, causal=True, block_q=128, block_k=128, layout="bhtd"
        )
        return o.transpose(0, 2, 1, 3).reshape(2, 256, 128) @ w.T

    x = jnp.ones((2, 256, 128), jnp.bfloat16)
    w = jnp.full((128, 128), 0.01, jnp.bfloat16)
    step(x, w).block_until_ready()
    trace_dir = out / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    for _ in range(5):
        step(x, w).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    shutil.copy(path, out / "tiny.xplane.pb")
    trace = trace_reduce.load(path)
    with open(out / "tiny.dump.txt", "w") as f:
        for d, dev in enumerate(trace.devices):
            for kind, events in (("module", dev.modules), ("op", dev.ops)):
                for e in events:
                    f.write(f"{d} {kind} {e.start_s:.9f} {e.dur_s:.9f} "
                            f"{e.name[:300]}\n")
        for thread, events in trace.host.items():
            f.write(f"host thread {thread}: {len(events)} calls, first "
                    f"{[e.name for e in events[:5]]}\n")
    print("fixture:", (out / "tiny.xplane.pb").stat().st_size, "bytes;",
          "busy, window", trace_reduce.busy_and_window(trace),
          "step durations", trace_reduce.module_durations(trace, "jit_step"),
          "mosaic", trace_reduce.op_seconds(trace, trace_reduce.is_mosaic_call),
          "top", trace_reduce.top_ops(trace, 5))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
