"""The reduction from a trace to numbers: on hand-made events, and on a
small trace recorded on a TPU v5e (``data/tiny.xplane.pb``, written by
``benchmark/tools/record_fixture.py``; ``data/tiny.dump.txt`` lists its
device events, which is where the expected numbers below were read)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Device, Event, Trace

DATA = Path(__file__).resolve().parent / "data"


def test_busy_is_the_union_not_the_sum():
    ops = [Event("a", 0.0, 1.0), Event("b", 0.5, 1.0), Event("c", 3.0, 1.0),
           Event("inner", 3.2, 0.1)]
    assert tr.busy_intervals(ops) == [(0.0, 1.5), (3.0, 4.0)]
    busy, window = tr.busy_and_window(Trace([Device([], ops)], {}))
    assert (busy, window) == (2.5, 4.0)


def test_busy_averages_over_the_chips_that_ran():
    one = Device([], [Event("a", 0.0, 1.0), Event("b", 3.0, 1.0)])
    two = Device([], [Event("a", 0.0, 4.0)])
    assert tr.busy_and_window(Trace([one, two, Device([], [])], {})) == (3.0, 4.0)


def test_modules_by_exact_name_and_ops_inside_them():
    dev = Device(
        modules=[Event("jit_step(123)", 0.0, 1.0), Event("jit_step(123)", 2.0, 3.0),
                 Event("jit_step_other(9)", 6.0, 1.0), Event("jit_prefill(7)", 8.0, 0.5)],
        ops=[Event('%k = bf16[2]{0} custom-call(), custom_call_target="tpu_custom_call"', 0.1, 0.2),
             Event('%k = bf16[2]{0} custom-call(), custom_call_target="tpu_custom_call"', 8.1, 0.3),
             Event("%f = bf16[2]{0} fusion()", 2.5, 0.5)],
    )
    trace = Trace([dev], {})
    assert tr.module_durations(trace, "jit_step") == [1.0, 3.0]
    assert tr.module_durations(trace, "jit_prefill", "jit_step_other") == [1.0, 0.5]
    assert tr.op_seconds(trace, tr.is_mosaic_call) == (0.5, 2)
    assert tr.op_seconds(trace, tr.is_mosaic_call, "jit_step") == (0.2, 1)
    assert tr.op_seconds(trace, tr.is_mosaic_call, "jit_prefill") == (0.3, 1)


@pytest.mark.parametrize("text, label", [
    ("%sort.4 = (f32[48,50257]{1,0:T(8,128)S(1)}, s32[48,50257]{1,0:T(8,128)}) "
     "sort(f32[48,50257]{1,0} %copy-done.1), dimensions={1}",
     "sort (f32[48,50257], s32[48,50257])"),
    ("%fusion.118 = bf16[1,128,1280]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[1,128,1280]{2,1,0} %x)",
     "fusion bf16[1,128,1280]"),
    ('%step.146 = bf16[48,1,1280]{2,1,0:T(2,128)(2,1)S(1)} custom-call(bf16[48,1,1280]{2,1,0} %q), '
     'custom_call_target="tpu_custom_call"', "mosaic custom-call bf16[48,1,1280]"),
    ("%while.4 = (s32[]{:T(128)}, bf16[1,32,1280]{2,1,0}, /*index=5*/bf16[36,1280]{1,0}) while(%t)",
     "while (s32[], bf16[1,32,1280], bf16[36,1280])"),
    ("dot_general.1", "dot_general.1"),
])
def test_op_labels(text, label):
    assert tr.op_label(text) == label


def test_a_gap_is_blamed_on_the_working_thread_by_its_own_source():
    dev = Device([], [Event("a", 0.0, 1.0), Event("b", 2.0, 1.0), Event("c", 3.5, 1.0)])
    host = {
        "engine": [Event("$engine.py:10 step", 0.9, 2.6),
                   Event("$engine.py:20 _admit", 1.0, 0.9),
                   Event("$numpy asarray", 1.4, 0.2),
                   Event("$threading.py:323 wait", 3.1, 0.3)],
        "load": [Event("$serve.py:5 _closed", 0.0, 5.0),
                 Event("$queue.py:1 get", 0.1, 4.0)],
    }
    gaps = tr.idle_gaps(Trace([dev], host), frozenset({"engine.py", "serve.py"}))
    # first gap: the engine thread works (asarray under _admit); second:
    # every thread waits, the latest wait is the engine's, named by its
    # innermost call in the program's own files
    assert gaps == [["$engine.py:20 _admit", 1.0], ["$engine.py:10 step", 0.5]]


def test_find_xplane_wants_exactly_one(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(tmp_path)


# -- the recorded TPU trace: five runs of a jitted `step` (two matmuls around
# the flash kernel) with a pause between runs, v5e, 2026-09-28 ---------------


@pytest.fixture(scope="module")
def tiny():
    return tr.load(DATA / "tiny.xplane.pb")


def test_recorded_trace_has_one_chip_and_the_host(tiny):
    assert len(tiny.devices) == 1
    assert len(tiny.devices[0].modules) == 5
    assert tiny.host and all(
        e.name.startswith("$") for calls in tiny.host.values() for e in calls)


def test_recorded_module_median(tiny):
    # tiny.dump.txt: 10.144, 10.275, 10.175, 10.412, 10.144 us
    durations = tr.module_durations(tiny, "jit_step")
    assert sorted(round(d * 1e9) for d in durations) == [
        10144, 10144, 10175, 10275, 10412]
    assert tr.module_durations(tiny, "jit_prefill") == []


def test_recorded_custom_call_sum(tiny):
    # the five flash forward calls: 8.140 + 8.142 + 8.139 + 8.143 + 8.139 us
    seconds, calls = tr.op_seconds(tiny, tr.is_mosaic_call, "jit_step")
    assert calls == 5 and seconds == pytest.approx(40.703e-6, abs=1e-9)
    assert tr.op_seconds(tiny, tr.is_mosaic_call) == (seconds, calls)
    assert tr.op_seconds(tiny, tr.is_mosaic_call, "jit_other") == (0.0, 0)


def test_recorded_busy_union_and_window(tiny):
    # ops fill each 10 us program back to back; the pauses between the
    # five runs are idle: first op start 0.045334697 s, last op end
    # 0.058484415 s
    busy, window = tr.busy_and_window(tiny)
    assert busy == pytest.approx(51.054e-6, abs=2e-9)
    assert window == pytest.approx(0.013149718, abs=2e-9)
    assert len(tr.busy_intervals(tiny.devices[0].ops)) >= 5


def test_recorded_top_op_and_gaps(tiny):
    top = tr.top_ops(tiny, 3)
    assert top[0][0] == "mosaic custom-call (bf16[4,256,64], f32[4,256,1])"
    assert top[0][1] == pytest.approx(40.703e-6, abs=1e-9)
    gaps = tr.idle_gaps(tiny, frozenset({"record_fixture.py"}))
    assert gaps and sum(s for _, s in gaps) == pytest.approx(
        0.013149718 - 51.054e-6, rel=0.01)
    # the pauses are the host's `time.sleep`, but in this trace the
    # device's clock runs about 1 ms ahead of the host's, so a gap's middle
    # falls just before its sleep began: names of gaps this short are not
    # to be trusted (PERF.md, Open questions)
    assert all(isinstance(name, str) for name, _ in gaps)
