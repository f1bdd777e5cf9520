"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the trace
holds one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules``
has one event per executed program (``jit_step(<fingerprint>)``) and whose
line ``XLA Ops`` has one event per executed HLO operation, named by the
operation's HLO text (``%fusion.7 = bf16[48,1280]{...} fusion(...)``). The
host's threads are lines of the plane ``/host:CPU``; with the Python tracer
on, a Python call is an event named ``$file.py:123 function``. Device and
host events share one clock.

A CPU run (a rehearsal) has no device plane: there the XLA CPU client's
thread lines stand in, so the same code runs, and proves nothing about a
chip.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
_HOST_PLANE = "/host:CPU"
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/")
_HLO = re.compile(r"^%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")
#: host calls that only wait: a gap is blamed on them last
_WAITS = ("acquire", "wait", "poll", "sleep", "select", "get", "join")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_s: float
    dur_s: float

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


@dataclasses.dataclass
class Device:
    modules: list[Event]
    ops: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: dict[str, list[Event]]  # Python calls, by host thread, by start


def find_xplane(trace_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}"
        )
    return found[0]


def _events(line) -> list[Event]:
    return [
        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
        for e in line.events
    ]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(path))
    devices: list[Device] = []
    host: dict[str, list[Event]] = {}
    cpu_ops: list[Event] = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = Device(modules=[], ops=[])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = _events(line)
                elif line.name == "XLA Ops":
                    dev.ops = _events(line)
            devices.append(dev)
        elif plane.name == _HOST_PLANE:
            for k, line in enumerate(plane.lines):
                events = _events(line)
                if line.name.startswith(_CPU_CLIENT_LINE):
                    cpu_ops += [e for e in events if e.dur_s > 0]
                calls = [e for e in events if e.name.startswith("$")]
                if calls:
                    host[f"{k}:{line.name}"] = sorted(
                        calls, key=lambda e: e.start_s
                    )
    if not devices and cpu_ops:
        # a rehearsal on the CPU: executed thunks stand in for device ops
        devices = [Device(modules=[], ops=cpu_ops)]
    return Trace(devices=devices, host=host)


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals, as disjoint sorted spans."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_s):
        if out and e.start_s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_s)
        else:
            out.append([e.start_s, e.end_s])
    return [(a, b) for a, b in out]


def window(dev: Device) -> tuple[float, float]:
    """The traced window of one device: first start to last end of what
    ran on it."""
    events = dev.ops or dev.modules
    return (min(e.start_s for e in events), max(e.end_s for e in events))


def busy_and_window(trace: Trace) -> tuple[float, float]:
    """(busy seconds, window seconds), each averaged over the devices
    that ran anything: busy is the union of the intervals in which an
    operation ran."""
    busy, span = [], []
    for dev in trace.devices:
        if not (dev.ops or dev.modules):
            continue
        t0, t1 = window(dev)
        busy.append(sum(b - a for a, b in busy_intervals(dev.ops or dev.modules)))
        span.append(t1 - t0)
    if not busy:
        return 0.0, 0.0
    return sum(busy) / len(busy), sum(span) / len(span)


def module_name(event_name: str) -> str:
    """``jit_step(123456)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def module_durations(trace: Trace, *names: str) -> list[float]:
    """Device seconds of every executed program called one of ``names``
    (``jit_step``), on every device."""
    return [
        e.dur_s for dev in trace.devices for e in dev.modules
        if module_name(e.name) in names
    ]


def op_seconds(trace: Trace, wanted, *inside: str) -> tuple[float, int]:
    """(summed device seconds, count) of the operations ``wanted(name)``
    accepts, over every device; with ``inside``, only those that ran
    within an executed program called one of those names."""
    import bisect

    total, count = 0.0, 0
    for dev in trace.devices:
        spans = sorted(
            (e.start_s, e.end_s) for e in dev.modules
            if module_name(e.name) in inside
        )
        starts = [a for a, _ in spans]
        for e in dev.ops:
            if not wanted(e.name):
                continue
            if inside:
                i = bisect.bisect_right(starts, e.start_s) - 1
                if i < 0 or e.start_s > spans[i][1]:
                    continue
            total += e.dur_s
            count += 1
    return total, count


def is_mosaic_call(op_name: str) -> bool:
    """A Pallas (Mosaic) kernel in an op's HLO text."""
    return " custom-call(" in op_name and "tpu_custom_call" in op_name


def op_label(op_name: str) -> str:
    """A short, stable label of an HLO operation: opcode and result
    shape, layouts and numbering dropped; Mosaic kernels say so."""
    text = _COMMENT.sub("", _LAYOUT.sub("", op_name))
    m = _HLO.match(text)
    if not m:
        return op_name[:100]
    shape, opcode = m.groups()
    if opcode == "custom-call" and "tpu_custom_call" in op_name:
        opcode = "mosaic custom-call"
    return f"{opcode} {shape}"[:100]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[label, summed device seconds], ...]: the operations that took
    most device time. An operation inside a ``while`` body is listed
    beside its loop: the list ranks, it does not add up."""
    total: dict[str, float] = {}
    for dev in trace.devices:
        for e in dev.ops:
            label = op_label(e.name)
            total[label] = total.get(label, 0.0) + e.dur_s
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label, secs] for label, secs in ranked]


def _is_wait(host_name: str) -> bool:
    return host_name.rsplit(" ", 1)[-1].lstrip("_") in _WAITS


def _file_of(host_name: str) -> str | None:
    """``$engine.py:4384 _execute_plans`` -> ``engine.py``; None for a
    call without a Python file (a builtin, a C function)."""
    head = host_name[1:].split(" ", 1)[0]
    return head.rsplit(":", 1)[0] if ".py:" in head else None


def _stacks_at(events: list[Event], times: list[float]) -> list[list[Event]]:
    """The calls of one thread in progress at each of ``times`` (sorted),
    outermost first: one sweep over the thread's events."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i].start_s <= t:
            while stack and stack[-1].end_s < events[i].start_s:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1].end_s < t:
            stack.pop()
        out.append(list(stack))
    return out


def idle_gaps(trace: Trace, own_files=frozenset(), n: int = 10) -> list[list]:
    """[[what the host was doing, summed idle seconds], ...] over the
    gaps between busy spans of the first device. A gap is blamed on the
    thread that was working at its middle (its innermost call is not a
    wait; the latest to start if several), and named by that thread's
    innermost call in one of ``own_files`` (the program's and the
    benchmark's own sources, by file name), so that gaps carry the
    program's phase names and not the library call underneath."""
    if not trace.devices or not trace.devices[0].ops:
        return []
    spans = busy_intervals(trace.devices[0].ops)
    gaps = [(a1, b0) for (_, a1), (b0, _) in zip(spans, spans[1:]) if b0 > a1]
    # the longest gaps hold nearly all the idle time
    gaps = sorted(sorted(gaps, key=lambda g: g[0] - g[1])[:2000])
    mids = [0.5 * (a + b) for a, b in gaps]
    per_thread = [_stacks_at(events, mids) for events in trace.host.values()]
    total: dict[str, float] = {}
    for k, (a, b) in enumerate(gaps):
        stacks = [st[k] for st in per_thread if st[k]]
        working = [st for st in stacks if not _is_wait(st[-1].name)]
        pick = max(working or stacks, key=lambda st: st[-1].start_s,
                   default=None)
        if pick is None:
            label = "(no Python call in progress)"
        else:
            own = [e for e in pick if _file_of(e.name) in own_files]
            named = [e for e in pick if _file_of(e.name)]
            label = (own or named or pick)[-1].name
        total[label] = total.get(label, 0.0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label[:100], secs] for label, secs in ranked]
