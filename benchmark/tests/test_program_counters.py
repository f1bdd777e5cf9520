"""The readers of the counters the program keeps itself (PR 25): each on
a recorded pair of snapshots, each silent on a program that lacks its
counter, and the manifest's entries for them."""

import math
from pathlib import Path

import pytest

from benchmark import manifest
from benchmark.run import Measured, load_reader

ROOT = Path(__file__).resolve().parents[2]

#: ``ServingMetrics.summary()["loop_seconds"]`` at the two ends of a 30 s
#: window of the rehearsal (CPU, toy size): the form, not a measurement
LOOP_BEFORE = {"sweep": 0.0021, "admit": 0.1102, "prefill": 1.2764,
               "key_sync": 0.3000, "dispatch": 0.8853, "sync": 5.3311,
               "process": 0.2417}
LOOP_AFTER = {"sweep": 0.0102, "admit": 0.2511, "prefill": 2.0893,
              "key_sync": 0.4000, "dispatch": 3.1375, "sync": 29.6049,
              "process": 1.0432}

NEW = {
    "engine_loop.host_busy_share": ("engine_loop", "tpot_p95_ms", 2),
    "sched.kv_live_share": ("scheduler", "serve_tokens_per_s", 1),
    "programs.setup_compile_s": ("programs", "setup_s", 3),
    "programs.setup_compile_requests": ("programs", "setup_s", 3),
}


def measured(before, after, window_s=30.0):
    return Measured(
        system=None, model={}, geometry={}, window_s=window_s, before=before,
        after=after, trace=None, trace_host_span=(math.nan, math.nan),
        deliveries=[], peaks={},
    )


class _Engine:
    """An engine as a reader sees it: ``metrics.summary()``."""

    def __init__(self, summary):
        self.metrics = self
        self._summary = summary

    def summary(self):
        return self._summary


def test_host_busy_share_leaves_both_readbacks_out():
    reader = load_reader("engine_loop.host_busy_share")
    snap = reader.snapshot(_Engine({"loop_seconds": LOOP_AFTER}))
    assert snap["loop"] == LOOP_AFTER and snap["t"] > 0
    host = sum(LOOP_AFTER[p] - LOOP_BEFORE[p]
               for p in ("sweep", "admit", "prefill", "dispatch", "process"))
    # the closing snapshot came 1.5 s after the window's nominal end
    got = reader.read(measured({"t": 100.0, "loop": LOOP_BEFORE},
                               {"t": 131.5, "loop": LOOP_AFTER}))
    assert got == pytest.approx(100.0 * host / 31.5)
    waits = sum(LOOP_AFTER[p] - LOOP_BEFORE[p] for p in ("sync", "key_sync"))
    assert got + 100.0 * waits / 31.5 == pytest.approx(
        100.0 * (sum(LOOP_AFTER.values()) - sum(LOOP_BEFORE.values())) / 31.5)


def test_kv_live_share_is_the_ratio_of_the_two_growths():
    reader = load_reader("sched.kv_live_share")
    snap = reader.snapshot(_Engine(
        {"kv_rows_live": 1_200, "kv_rows_streamed": 4_096}))
    assert snap == (1_200, 4_096)
    # 48 slots x 1,024 rows x 4 substeps a horizon, 250 of them live
    after = (1_200 + 48 * 250 * 4 * 10, 4_096 + 48 * 1_024 * 4 * 10)
    assert reader.read(measured(snap, after)) == pytest.approx(
        100.0 * 250 / 1_024)
    assert reader.read(measured(snap, snap)) is None  # nothing dispatched


@pytest.mark.parametrize("name, column", [
    ("programs.setup_compile_s", 1),
    ("programs.setup_compile_requests", 0),
])
def test_setup_compile_readers_take_the_log_at_the_windows_opening(
        name, column):
    import jax
    import jax.numpy as jnp

    reader = load_reader(name)
    x = jnp.arange(5.0)
    # the train cell's call: no system, the process-wide log
    before = reader.snapshot(None)

    def compiled_for_this_test(x):
        return x * 2 - 1

    jax.jit(compiled_for_this_test)(x).block_until_ready()
    opening = reader.snapshot(None)
    assert opening[0] == before[0] + 1 and opening[1] > before[1]
    later = (opening[0] + 9, opening[1] + 9.0, 0, 0)
    assert reader.read(measured(opening, later)) == opening[column]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counter_reports_nothing(name):
    """The parent commit's program: its summary has no such key and its
    package no compile log; the reader returns None and raises nothing."""
    reader = load_reader(name)
    if name.startswith("programs."):
        assert reader.read(measured(None, None)) is None
    else:
        old = _Engine({"steps": 7, "occupancy_mean": 48.0})
        snap = reader.snapshot(old)
        assert reader.read(measured(snap, snap)) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_accepts_the_entry(name):
    m = manifest.load(ROOT)
    assert manifest.check(m, ROOT) == []
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    layer, moves, n_cells = NEW[name]
    assert (entry["layer"], entry["moves"]) == (layer, moves)
    assert entry["source"] == "program_counter"
    assert len(entry["workloads"]) == n_cells
    for cell in entry["workloads"]:
        reports = {e["name"] for e in
                   manifest.metrics_of(m, cell, "end_to_end")}
        assert moves in reports
