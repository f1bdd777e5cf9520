"""``obs.capture``: one profiler capture reduced by the program itself:
idle seconds by the loop phase they lie under, seconds between decode
steps by the program that ran in them; ``ProfileTrigger``'s capture;
``GET /profile/report``."""

import json
import os
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu.obs import ProfileTrigger, capture
from deeplearning4j_tpu.serving.metrics import LOOP_PHASES
from deeplearning4j_tpu.serving.server import ServingServer

from test_obs_engine_loop import _engine, _request, _serve

MS = 1e-3


def _ops(*spans):
    return [("op", a * MS, (b - a) * MS) for a, b in spans]


def _regions(*spans):
    return [(f"engine.{name}", a * MS, (b - a) * MS) for name, a, b in spans]


def _idle_ms(report):
    return {k: round(v / MS, 6) for k, v in report["idle_by_phase_s"].items()}


# -- idle seconds by phase ---------------------------------------------------


def test_gap_that_spans_two_phases_is_split_by_overlap():
    # the device is idle from 10 to 20; the loop admits until 14, then
    # dispatches: a gap named by its middle would say "dispatch" for all
    report = capture.reduce_events(
        _ops((0, 10), (20, 30)), None,
        _regions(("admit", 2, 14), ("dispatch", 14, 26)),
    )
    assert _idle_ms(report) == {"admit": 4.0, "dispatch": 6.0}
    assert report["window_s"] == pytest.approx(30 * MS)
    assert report["device_busy_s"] == pytest.approx(20 * MS)


def test_nested_regions_give_the_innermost():
    # key_sync and prefill lie inside admit, sync inside process
    report = capture.reduce_events(
        _ops((0, 10), (30, 40), (60, 70)), None,
        _regions(("admit", 5, 35), ("prefill", 12, 18),
                 ("key_sync", 20, 28), ("process", 40, 65),
                 ("sync", 45, 62)),
    )
    assert _idle_ms(report) == {
        "admit": 2.0 + 2.0 + 2.0, "prefill": 6.0, "key_sync": 8.0,
        "sync": 15.0, "process": 5.0,
    }
    assert report["regions"] == {"admit": 1, "prefill": 1, "key_sync": 1,
                                 "process": 1, "sync": 1}


def test_gap_under_no_region_is_between_turns():
    report = capture.reduce_events(
        _ops((0, 10), (20, 30)), None,
        _regions(("dispatch", 0, 12), ("sweep", 18, 19)),
    )
    assert _idle_ms(report) == {
        "dispatch": 2.0, "sweep": 1.0, capture.BETWEEN_TURNS: 7.0,
    }


def _phase_at(regions, t):
    """The reference: the innermost region open at ``t``, by a scan."""
    open_ = [r for r in regions if r[1] <= t < r[1] + r[2]]
    return max(open_, key=lambda r: r[1])[0] if open_ else None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_parts_sum_to_the_idle_total_and_match_a_scan(seed):
    rng = np.random.default_rng(seed)
    edges = np.cumsum(rng.integers(1, 40, 400))  # whole microseconds
    ops = [("op", a * 1e-6, (b - a) * 1e-6)
           for a, b in zip(edges[0::2], edges[1::2])]
    regions, t = [], 0
    for _ in range(60):  # turns of a loop: outer regions, some nested
        t += int(rng.integers(0, 30))
        dur = int(rng.integers(20, 200))
        regions.append(("engine.admit", t * 1e-6, dur * 1e-6))
        if dur > 60:
            a = t + int(rng.integers(1, 20))
            regions.append(
                ("engine.key_sync", a * 1e-6, int(rng.integers(5, 30)) * 1e-6))
        t += dur
    report = capture.reduce_events(ops, None, regions)
    idle = report["idle_by_phase_s"]
    assert sum(idle.values()) == report["device_idle_s"]
    assert report["device_idle_s"] + report["device_busy_s"] == pytest.approx(
        report["window_s"])
    # every whole microsecond of every gap, looked up one by one
    want = {}
    for (_, s0, d0), (_, s1, _) in zip(ops, ops[1:]):
        for us in range(round((s0 + d0) * 1e6), round(s1 * 1e6)):
            name = _phase_at(regions, (us + 0.5) * 1e-6)
            name = name[len("engine."):] if name else capture.BETWEEN_TURNS
            want[name] = want.get(name, 0) + 1
    assert {k: round(v * 1e6) for k, v in idle.items()} == want


def test_capture_without_regions_gives_empty_regions_and_no_raise():
    report = capture.reduce_events(_ops((0, 10), (15, 30)), None, [])
    assert report["regions"] == {} and report["horizons"] is None
    assert _idle_ms(report) == {capture.BETWEEN_TURNS: 5.0}
    empty = capture.reduce_events([], None, [])
    assert empty["window_s"] == 0.0 and empty["idle_by_phase_s"] == {}


# -- seconds between decode steps --------------------------------------------


def _modules(*spans):
    return [(name, a * MS, (b - a) * MS) for name, a, b in spans]


def test_prefill_between_two_steps_is_the_gap_and_its_program():
    report = capture.reduce_events(
        _ops((0, 100)), _modules(
            ("jit_step(11)", 0, 28), ("jit_prefill(7)", 29, 33),
            ("jit_step(11)", 35, 63),
        ), [],
    )
    assert report["steps"] == 2
    assert report["step_gap_s"] == pytest.approx(7 * MS)
    by = {k: round(v / MS, 6)
          for k, v in report["step_gap_by_program_s"].items()}
    assert by == {"jit_prefill": 4.0, "idle": 3.0}
    assert report["small_program_s"] == 0.0


def test_step_that_follows_at_once_has_no_gap():
    report = capture.reduce_events(
        _ops((0, 100)), _modules(
            ("jit_step(11)", 0, 28), ("jit_step(11)", 28, 56),
        ), [],
    )
    assert report["steps"] == 2 and report["step_gap_s"] == 0.0
    assert report["step_gap_by_program_s"] == {}


def test_tiny_program_is_a_small_program_under_its_own_name():
    report = capture.reduce_events(
        _ops((0, 100)), _modules(
            ("jit_step(11)", 0, 28), ("jit_convert_element_type(3)", 28.5, 28.6),
            ("jit_chunk(5)", 29, 31), ("jit__threefry_split(9)", 31.2, 31.5),
            ("jit_step(11)", 32, 60), ("jit_convert_element_type(3)", 61, 61.1),
        ), [],
    )
    by = {k: round(v / MS, 6)
          for k, v in report["step_gap_by_program_s"].items()}
    assert by == {"jit_convert_element_type": 0.1, "jit_chunk": 2.0,
                  "jit__threefry_split": 0.3, "idle": 1.6}
    assert sum(by.values()) == pytest.approx(report["step_gap_s"] / MS)
    # every small program counts, also the one after the last step
    assert report["small_program_s"] == pytest.approx(0.5 * MS)


def test_capture_without_modules_has_no_step_gap_parts():
    report = capture.reduce_events(_ops((0, 10)), None, [])
    assert (report["steps"], report["step_gap_s"],
            report["step_gap_by_program_s"], report["small_program_s"]) == (
        None, None, None, None)


# -- idle seconds in the turns that admit ------------------------------------

#: two turns: the first admits (its admit holds a key_sync), the second
#: has nothing to admit; the device idles 18-21 (the key's readback and
#: the dispatch behind it) and 43-44 (the second turn's dispatch)
_TWO_TURNS = (
    ("sweep", 9, 10), ("admit", 10, 19), ("prefill", 11, 13),
    ("key_sync", 13, 18.5), ("dispatch", 19.5, 22), ("process", 22, 30),
    ("sweep", 39, 40), ("admit", 40, 41), ("dispatch", 41, 45),
    ("process", 45, 50),
)
_TWO_TURNS_OPS = ((0, 18), (21, 43), (44, 60))


def test_idle_of_a_turn_that_admits_runs_to_the_end_of_its_dispatch():
    report = capture.reduce_events(
        _ops(*_TWO_TURNS_OPS), None, _regions(*_TWO_TURNS))
    assert report["admitting_turns"] == 1
    assert report["idle_in_admitting_turns_s"] == pytest.approx(3 * MS)
    assert report["device_idle_s"] == pytest.approx(4 * MS)
    # what the phases alone say of the same three milliseconds
    assert _idle_ms(report) == {"key_sync": 0.5, "admit": 0.5,
                                "between_turns": 0.5, "dispatch": 1.5 + 1.0}


@pytest.mark.parametrize("late_ms", [-0.4, 0.0, 1.0])
def test_idle_in_admitting_turns_holds_when_the_host_clock_reads_late(
        late_ms):
    # a capture's host events can read a millisecond late against the
    # device's: the split by phase moves, the turn's total does not
    shifted = [(n, a + late_ms, b + late_ms) for n, a, b in _TWO_TURNS]
    report = capture.reduce_events(
        _ops(*_TWO_TURNS_OPS), None, _regions(*shifted))
    assert report["idle_in_admitting_turns_s"] == pytest.approx(3 * MS)
    assert (_idle_ms(report).get("dispatch", 0.0) == 2.5) == (late_ms == 0.0)


@pytest.mark.parametrize("regions, idle_ms", [
    # the capture ends inside the turn: no dispatch to close it
    ((("admit", 8, 16), ("key_sync", 9, 15)), 4.0),
    # ... and inside its admit, which is recorded only when it ends: the
    # prefill that ended in time stands for it
    ((("sweep", 7, 8), ("prefill", 8.5, 9.5)), 4.0),
    # the capture begins inside the turn, after its sweep and its
    # admit's start
    ((("key_sync", 1, 11), ("dispatch", 12, 16), ("sweep", 18, 19)), 4.0),
    # a piggybacked chunk: the prefill opens inside dispatch
    ((("sweep", 7, 8), ("admit", 8, 8.5), ("dispatch", 9, 13),
      ("prefill", 9.5, 10.5), ("process", 13, 15)), 3.0),
    # a turn with nothing to admit
    ((("sweep", 7, 8), ("admit", 8, 8.5), ("dispatch", 9, 13)), 0.0),
])
def test_turn_admits_when_a_key_sync_or_prefill_opens_in_it(regions, idle_ms):
    report = capture.reduce_events(
        _ops((0, 10), (14, 20)), None, _regions(*regions))
    assert report["admitting_turns"] == (1 if idle_ms else 0)
    assert report["idle_in_admitting_turns_s"] == pytest.approx(idle_ms * MS)


# -- a real capture ----------------------------------------------------------


@pytest.fixture(scope="module")
def cpu_capture(tmp_path_factory):
    """One capture of a toy engine through ``ProfileTrigger``: two
    admissions, then idle turns that spend what is left of the budget."""
    profile = ProfileTrigger(log_dir=tmp_path_factory.mktemp("capture"))
    engine = _engine(profile=profile)
    _serve(engine, [_request(5, 4)])  # compile outside the capture
    profile.arm(6)
    _serve(engine, [_request(5, 6, seed=1), _request(7, 6, seed=2)])
    for _ in range(6):
        engine.step()
    return capture.find_xplane(profile.finished_capture())


def test_real_capture_yields_every_phase_and_consecutive_horizons(
        cpu_capture):
    report = capture.loop_report(cpu_capture)
    assert set(report["regions"]) == set(LOOP_PHASES)
    first, last = report["horizons"]
    # six turns: each dispatch carries the horizon it would launch
    assert 0 < last - first <= report["regions"]["dispatch"] == 6
    idle = report["idle_by_phase_s"]
    assert set(idle) <= set(LOOP_PHASES) | {capture.BETWEEN_TURNS}
    assert sum(idle.values()) == report["device_idle_s"] > 0
    # two requests were admitted inside the capture
    assert 1 <= report["admitting_turns"] <= 2
    assert 0 < report["idle_in_admitting_turns_s"] < report["device_idle_s"]
    assert report["device_busy_s"] + report["device_idle_s"] == pytest.approx(
        report["window_s"])
    # the CPU client's threads stand in for the ops; there are no modules
    assert report["steps"] is None and report["step_gap_s"] is None
    json.dumps(report)  # what GET /profile/report sends


def test_loop_report_is_memoised_by_path_and_mtime(cpu_capture):
    first = capture.loop_report(cpu_capture)
    assert capture.loop_report(str(cpu_capture)) is first
    stamp = cpu_capture.stat().st_mtime_ns + 1_000_000
    os.utime(cpu_capture, ns=(stamp, stamp))
    again = capture.loop_report(cpu_capture)
    assert again is not first and again == first


def test_profile_trigger_capture_keeps_python_frames_beside_the_regions(
        cpu_capture):
    # the regions name the loop for the report; an operator who opens the
    # capture still finds the Python stacks under them
    from jax.profiler import ProfileData

    (host,) = [p for p in ProfileData.from_file(str(cpu_capture)).planes
               if p.name == "/host:CPU"]
    names = [e.name for line in host.lines for e in line.events]
    assert any(n.startswith("engine.") for n in names)
    assert any(n.startswith("$") and "engine.py" in n for n in names)


def test_tpu_capture_reads_device_zero_and_its_modules():
    """A recorded capture of a v5e (the benchmark's test data: five
    ``jit_step`` of a toy program, from before the regions existed)."""
    tiny = (Path(__file__).resolve().parents[1] / "benchmark" / "tests"
            / "data" / "tiny.xplane.pb")
    report = capture.loop_report(tiny)
    assert report["steps"] == 5 and report["regions"] == {}
    assert report["horizons"] is None
    # 10 us of work every 3.3 ms: nearly all of the window is step gap,
    # none of it another program's, and all of it between turns
    assert 0.99 < report["step_gap_s"] / report["window_s"] < 1.0
    assert set(report["step_gap_by_program_s"]) == {"idle"}
    assert report["small_program_s"] == 0.0
    assert set(report["idle_by_phase_s"]) == {capture.BETWEEN_TURNS}
    assert report["device_busy_s"] == pytest.approx(5 * 10.2e-6, rel=0.05)


def test_finished_capture_is_none_then_refuses_while_armed(tmp_path):
    trigger = ProfileTrigger(log_dir=tmp_path)
    assert trigger.finished_capture() is None
    trigger.arm(1)
    with pytest.raises(RuntimeError, match="armed or running"):
        trigger.finished_capture()


# -- GET /profile/report -----------------------------------------------------


def test_profile_report_endpoint_404_409_200(tmp_path):
    engine = _engine(profile=ProfileTrigger(log_dir=tmp_path))
    srv = ServingServer(engine, port=0).start()
    base = "http://%s:%d" % srv.address

    def call(path, data=None):
        try:
            with urllib.request.urlopen(
                urllib.request.Request(base + path, data=data), timeout=30,
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        code, body = call("/profile/report")
        assert code == 404 and "POST /profile" in body["error"]
        # an idle loop turns every few milliseconds: hold the capture's
        # end until the report has been asked for while it runs
        trigger = engine.profile
        trigger.step_end = lambda: None
        try:
            code, body = call("/profile?s=3", data=b"")
            assert code == 200
            code, body = call("/profile/report")
            assert code == 409
        finally:
            del trigger.step_end
        deadline = time.monotonic() + 30
        while trigger.armed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not trigger.armed
        code, body = call("/profile/report")
        assert code == 200
        assert body["dir"] == str(trigger.last_capture_dir)
        # three counted turns, and those that ran while the end was held
        assert body["regions"]["dispatch"] >= 3
        assert sum(body["idle_by_phase_s"].values()) == pytest.approx(
            body["device_idle_s"])
    finally:
        srv.stop()

    # an engine without a trigger has no capture to report
    srv2 = ServingServer(_engine(), port=0).start()
    base = "http://%s:%d" % srv2.address
    try:
        assert call("/profile/report")[0] == 404
    finally:
        srv2.stop()
