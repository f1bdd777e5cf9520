#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process that holds the chip: it builds the system from the cell's
configuration file, warms up every shape the cell's traffic uses (set-up),
measures for ``--seconds`` and prints one JSON object as its last line:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled stretch in
the middle of the window. Earlier lines, prefixed ``benchmark:``, say what
happened. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

``--rehearse`` (the benchmark's own flag) runs the same code at the toy
size of the configuration's ``rehearse`` group on the CPU, with
interpreted kernels, and says ``"platform": "cpu"``: it debugs the
harness and measures nothing.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.py``. The runner has two kinds, ``serve`` and
``train``, chosen by the configuration file.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import manifest as manifest_lib  # noqa: E402
from benchmark import traffic as traffic_lib  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
#: profiler traces until they are reduced: inside the checkout, git-ignored
CACHE_DIR = ROOT / ".bench_cache"


@dataclasses.dataclass
class Measured:
    """What a traced run hands each per-layer reader (``read(m)``)."""

    system: object  # the ServingEngine, or None for a trainer
    model: dict  # the model sizes as run
    geometry: dict  # the engine or trainer geometry as run
    window_s: float  # length of the measured window
    before: object  # this reader's snapshot(system) at the window's start
    after: object  # ... and at its end
    trace: object  # trace_reduce.Trace of the profiled stretch
    trace_host_span: tuple[float, float]  # its perf_counter start and stop
    deliveries: list  # serve: (perf_counter time, context rows) per token
    peaks: dict  # published peaks of this device kind


def load_reader(name: str):
    """The per-layer metric ``name``'s reader, ``layer_metrics/<name>.py``."""
    path = BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Context:
    """One run's arguments, clock and hooks, shared by both kinds."""

    def __init__(self, args, cell, config, traffic, readers, compile_log):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.config = config
        self.traffic = traffic
        self.readers = readers  # name -> module, empty unless tracing
        self.compile_log = compile_log
        self.setup_s = math.nan
        self.trace_dir = CACHE_DIR / "trace" / cell["name"]
        self.trace_host_span = (math.nan, math.nan)

    def note(self, text: str) -> None:
        print(f"benchmark: {text}", flush=True)

    def since_start(self) -> float:
        return time.perf_counter() - _T_PROCESS

    def window_opens(self, t: float) -> None:
        self.setup_s = t - _T_PROCESS

    def layer_snapshots(self, system) -> dict:
        """Each reader's ``snapshot(system)``, where it has one: taken
        when the window opens and when it closes."""
        return {
            name: mod.snapshot(system)
            for name, mod in self.readers.items() if hasattr(mod, "snapshot")
        }

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self._t_trace = time.perf_counter()

    def stop_trace(self) -> None:
        import jax

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace_host_span = (self._t_trace, t1)


def own_sources() -> frozenset[str]:
    """File names of the program's and the benchmark's Python sources:
    the calls an idle gap is named by."""
    return frozenset(
        p.name for d in (ROOT / "deeplearning4j_tpu", BENCH_DIR)
        for p in d.rglob("*.py")
    )


def device_report(jax) -> dict:
    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, the cell's entry, its configuration file, its traffic
    file), each found by the name the manifest gives."""
    manifest = manifest_lib.load(ROOT)
    cell = manifest_lib.cell(manifest, name)
    entry = manifest_lib.config_entry(manifest, cell["config"])
    with open(ROOT / entry["file"]) as f:
        config = json.load(f)
    return manifest, cell, config, traffic_lib.load(cell["traffic"])


def start_jax(rehearse: bool):
    """Import jax for this run: the TPU and the persistent compile cache,
    or, in a rehearsal, the CPU uncached. None when there is no TPU."""
    if rehearse:
        # before jax starts: the CPU, uncached (reloading XLA:CPU
        # executables from a persistent cache is a known hazard here)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    if not rehearse:
        # the program's one rule: JAX_COMPILATION_CACHE_DIR if it is set,
        # else <checkout>/.jax_cache, a fixed path
        from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

        print(f"benchmark: compile cache {use_compile_cache()}", flush=True)
        platform = jax.devices()[0].platform
        if platform != "tpu":
            print(f"benchmark: needs a TPU, found {platform!r} "
                  "(--rehearse runs the toy size on the CPU)", file=sys.stderr)
            return None
    return jax


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU: debugs the harness only")
    args = ap.parse_args(argv)

    manifest, cell, config, traffic = load_cell(args.workload)
    jax = start_jax(args.rehearse)
    if jax is None:
        return 2
    devices = jax.devices()
    platform = devices[0].platform
    if len(devices) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from benchmark.compile_log import CompileLog

    section = "per_layer" if args.trace else "end_to_end"
    wanted = manifest_lib.metrics_of(manifest, cell["name"], section)
    readers = {m["name"]: load_reader(m["name"]) for m in wanted} if args.trace else {}
    ctx = Context(args, cell, config, traffic, readers, CompileLog(jax))
    ctx.note(
        f"cell {cell['name']} seed {args.seed} seconds {args.seconds:g} trace "
        f"{args.trace} on {len(devices)} x {devices[0].device_kind}"
        f"{' (REHEARSAL)' if args.rehearse else ''}"
    )

    kind = config["kind"]
    if kind == "serve":
        from benchmark import serve as runner
    elif kind == "train":
        from benchmark import train as runner
    else:
        raise ValueError(f"configuration kind {kind!r} is neither serve nor train")
    out = runner.run(ctx)

    requests, seconds, hits, misses = ctx.compile_log.snapshot()
    ctx.note(
        f"set-up {ctx.setup_s:.1f} s; in all {requests} compile requests, "
        f"{seconds:.1f} s tracing, lowering and compiling, compile cache "
        f"{hits} hits {misses} misses"
    )
    device = device_report(jax)
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"],
    }
    values = dict(out["values"], setup_s=ctx.setup_s)
    if args.trace:
        from benchmark import peaks, trace_reduce

        trace = trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir)  # tens of MB a run; the numbers are out
        busy_s, window_s = trace_reduce.busy_and_window(trace)
        if busy_s <= 0:
            print("benchmark: the trace shows no operation on the device",
                  file=sys.stderr)
            return 3
        device["busy_s"], device["window_s"] = busy_s, window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace),
            "idle_gaps": trace_reduce.idle_gaps(trace, own_sources()),
        }
        inputs = out["layer_inputs"]
        values = {}
        for name, mod in readers.items():
            m = Measured(
                system=inputs["system"], model=inputs["model"],
                geometry=inputs["geometry"], window_s=ctx.seconds,
                before=inputs["begun"].get(name),
                after=inputs["ended"].get(name), trace=trace,
                trace_host_span=ctx.trace_host_span,
                deliveries=inputs.get("deliveries", []),
                peaks=(peaks.peaks_for(device["kind"])
                       if platform == "tpu" else {}),
            )
            value = mod.read(m)
            if value is not None:
                values[name] = value
    metrics = {}
    for m in wanted:
        value = values.get(m["name"])
        if value is None:
            continue  # a reader that found nothing to read
        if not math.isfinite(value):
            print(f"benchmark: {m['name']} is {value}: too many requests "
                  "failed for this tail to exist", file=sys.stderr)
            return 4
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
