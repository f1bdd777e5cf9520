"""The two ``engine_loop`` readers of a traced run's capture:
``step_gap_share`` on the trace's own modules, and
``idle_in_admission_share``, which asks the program for its report of the
capture (``obs.capture.loop_report``): the reader on a report, the bridge
that keeps the capture for it, silence on a program without
``obs.capture``, on a capture it cannot read and at the window's opening,
and the manifest's entries."""

import json
import math
import os
import sys
import time
from pathlib import Path

import pytest

from benchmark import manifest, trace_reduce
from benchmark.run import Measured, load_reader
from benchmark.trace_reduce import Device, Event, Trace

ROOT = Path(__file__).resolve().parents[2]
TINY = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"

STEP_GAP = "engine_loop.step_gap_share"
IN_ADMISSION = "engine_loop.idle_in_admission_share"

#: a report as ``obs.capture.loop_report`` gives it: the form, not a
#: measurement
REPORT = {
    "window_s": 2.0, "device_busy_s": 1.9, "device_idle_s": 0.1,
    "idle_by_phase_s": {"admit": 0.02, "key_sync": 0.05, "prefill": 0.01,
                        "dispatch": 0.015, "between_turns": 0.005},
    "idle_in_admitting_turns_s": 0.09, "admitting_turns": 9,
    "regions": {"admit": 60, "dispatch": 60, "key_sync": 9, "prefill": 9},
    "horizons": [700, 759], "steps": 60, "step_gap_s": 0.24,
    "step_gap_by_program_s": {"jit_prefill": 0.15, "idle": 0.09},
    "small_program_s": 0.004,
}


def measured(after=None, trace=None):
    return Measured(
        system=None, model={}, geometry={}, window_s=30.0, before=None,
        after=after, trace=trace, trace_host_span=(math.nan, math.nan),
        deliveries=[], peaks={},
    )


def _trace(modules, ops):
    ms = 1e-3
    return Trace(devices=[Device(
        modules=[Event(n, a * ms, (b - a) * ms) for n, a, b in modules],
        ops=[Event("op", a * ms, (b - a) * ms) for a, b in ops],
    )], host={})


# -- engine_loop.step_gap_share ----------------------------------------------


def test_step_gap_is_the_time_between_steps_over_the_window_of_the_ops():
    # a prefill and a key split between the first two steps, the third
    # follows the second at once; the ops start before the first step
    trace = _trace(
        [("jit_step(1)", 10, 40), ("jit_prefill(2)", 41, 47),
         ("jit__threefry_split(3)", 47.5, 47.6), ("jit_step(1)", 50, 80),
         ("jit_step(1)", 80, 110)],
        [(0, 40), (41, 47), (47.5, 47.6), (50, 110)],
    )
    got = load_reader(STEP_GAP).read(measured(trace=trace))
    assert got == pytest.approx(100.0 * 10 / 110)


@pytest.mark.parametrize("modules", [[], [("jit_step(1)", 0, 10)]])
def test_step_gap_is_silent_without_two_steps(modules):
    reader = load_reader(STEP_GAP)
    assert reader.read(measured(trace=_trace(modules, [(0, 10)]))) is None
    assert reader.read(measured(trace=Trace(devices=[], host={}))) is None


def test_step_gap_on_the_recorded_capture_is_the_programs_report_of_it():
    from deeplearning4j_tpu.obs import capture

    report = capture.loop_report(TINY)
    got = load_reader(STEP_GAP).read(measured(trace=trace_reduce.load(TINY)))
    assert got == pytest.approx(
        100.0 * report["step_gap_s"] / report["window_s"])


# -- engine_loop.idle_in_admission_share -------------------------------------


@pytest.fixture
def reader(tmp_path, monkeypatch):
    """The reader with its bridge pointed at an empty ``.bench_cache``."""
    mod = load_reader(IN_ADMISSION)
    monkeypatch.setattr(mod, "_CACHE", tmp_path)
    return mod


def _capture(cache, name="cell", age_s=0.0, content=None):
    """A capture where ``run.py`` has the profiler write one."""
    path = cache / "trace" / name / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(TINY.read_bytes() if content is None else content)
    stamp = time.time() - age_s
    os.utime(path, (stamp, stamp))
    return path


def test_reader_divides_the_admitting_turns_idle_by_the_window(reader):
    assert reader.share(REPORT) == pytest.approx(100 * 0.09 / 2.0)
    assert reader.share(None) is None
    assert reader.share(dict(REPORT, window_s=0.0)) is None
    assert reader.share(dict(REPORT, regions={})) is None  # no regions held


def test_snapshot_is_none_at_the_windows_opening(reader, tmp_path):
    assert reader.snapshot(None) is None  # no capture at all
    _capture(tmp_path, age_s=3600.0)  # a crashed run's, older than this process
    assert reader.snapshot(None) is None
    assert reader.read(measured(None)) is None


def test_closing_snapshot_keeps_the_newest_capture_and_read_reduces_it(
        reader, tmp_path, capsys):
    _capture(tmp_path, "other-cell", age_s=3600.0)
    path = _capture(tmp_path)
    kept = reader.snapshot(None)
    assert os.path.samefile(kept, path)
    path.unlink()  # run.py deletes the trace before any read(m)
    value = reader.read(measured(kept))
    assert not os.path.exists(kept)  # tens of MB a run: not left behind
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("benchmark: loop report ")]
    said = json.loads(line.split("loop report ", 1)[1])
    assert {"idle_by_phase_s", "idle_in_admitting_turns_s",
            "step_gap_by_program_s", "regions", "reduced_in_s"} <= set(said)
    # the recorded trace is PR 24's, from before the regions: the line is
    # printed, the reader has nothing to say
    assert said["regions"] == {} and value is None
    # the program's totals are the harness's (device.idle_share.* divides
    # by the same window)
    busy_s, window_s = trace_reduce.busy_and_window(trace_reduce.load(TINY))
    assert said["device_busy_s"] == pytest.approx(busy_s)
    assert said["window_s"] == pytest.approx(window_s)
    assert said["steps"] == len(
        trace_reduce.module_durations(trace_reduce.load(TINY), "jit_step"))


def test_capture_is_copied_where_the_file_system_has_no_hard_links(
        reader, tmp_path, monkeypatch):
    path = _capture(tmp_path)

    def no_links(src, dst):
        raise OSError("hard links not supported")

    monkeypatch.setattr(os, "link", no_links)
    kept = reader.snapshot(None)
    assert Path(kept).read_bytes() == path.read_bytes()
    assert not os.path.samefile(kept, path)


def test_read_is_none_on_a_program_without_obs_capture(
        reader, tmp_path, capsys, monkeypatch):
    path = _capture(tmp_path)
    kept = reader.snapshot(None)
    monkeypatch.setitem(sys.modules, "deeplearning4j_tpu.obs.capture", None)
    import deeplearning4j_tpu.obs as obs

    monkeypatch.delattr(obs, "capture", raising=False)
    assert reader.read(measured(kept)) is None
    assert not os.path.exists(kept) and path.exists()
    assert "loop report" not in capsys.readouterr().out


def test_read_is_none_on_a_capture_the_program_cannot_read(
        reader, tmp_path, capsys):
    _capture(tmp_path, content=b"not a profile")
    kept = reader.snapshot(None)
    assert reader.read(measured(kept)) is None  # and does not raise
    assert not os.path.exists(kept)
    assert "benchmark: no loop report" in capsys.readouterr().out


def test_manifest_entries():
    m = manifest.load(ROOT)
    by_name = {e["name"]: e for e in m["per_layer"]}
    for name, source in ((STEP_GAP, "device_trace"),
                         (IN_ADMISSION, "program_span")):
        entry = by_name[name]
        assert (entry["layer"], entry["source"], entry["moves"],
                entry["unit"], entry["better"]) == (
            "engine_loop", source, "tpot_p95_ms", "%", "lower")
        assert entry["workloads"] == ["gpt2-large.decode-flood",
                                      "gpt2-large.chat-steady"]
    assert not manifest.check(m, ROOT)
