"""One profiler capture, reduced: the loop's phase regions joined with
the device's events.

A capture of a running engine (``POST /profile?s=N``, a benchmark's
``jax.profiler.start_trace``) holds, on one clock, the device's executed
operations (plane ``/device:TPU:0``, lines ``XLA Ops`` and ``XLA
Modules``) and, on the host plane, the ``engine.<phase>`` regions that
the engine's :class:`~deeplearning4j_tpu.obs.trace.PhaseRegions` opens
around every phase of the loop. :func:`loop_report` is the one place the
two are joined: every second the device sat idle is put down to the
phase the loop was in, and every second between two decode steps to the
program that ran in it.

Read with ``jax.profiler.ProfileData`` and nothing else; the arithmetic
(:func:`reduce_events` and what it calls) works on plain lists of
``(name, start_s, dur_s)``.

Definitions:

- *window*: first start to last end of device 0's ``XLA Ops``; *idle*:
  the complement of the union of those ops inside it.
- *phase at an instant*: on the host thread that holds the
  ``engine.dispatch`` regions, the innermost open region (``key_sync``
  and ``prefill`` lie inside ``admit`` or ``dispatch``, ``sync`` inside
  ``process``); with no region open the loop is ``between_turns``.
- ``idle_by_phase_s``: each idle gap's seconds, split by overlap with
  those intervals, over all gaps; the values sum to ``device_idle_s``.
- ``idle_in_admitting_turns_s``: the idle seconds of the turns (one
  ``sweep`` to the next) in which a ``key_sync`` or a ``prefill`` opens,
  each from the start of its ``admit`` to the end of its ``dispatch``;
  ``admitting_turns`` counts them. An admission's idle gap opens under
  ``key_sync`` and closes a little into ``dispatch``, and the capture's
  host events can read a millisecond late against the device's: the
  whole turn holds the gap either way, where the boundary between two
  phases does not.
- ``step_gap_s``: device-clock seconds between the end of each
  ``jit_step`` of ``XLA Modules`` and the start of the next;
  ``step_gap_by_program_s``: those seconds by the program that ran in
  them, the rest under ``idle``; ``small_program_s``: device seconds of
  every program that is neither the step, a prefill nor a chunk.

A CPU capture has no device plane: the XLA CPU client's thread lines
stand in for the ops, there are no modules, and the step-gap parts of
the report are ``None``. It shows that the join works, never a speed.
"""

from __future__ import annotations

import functools
import os
import re
from pathlib import Path

from deeplearning4j_tpu.obs.trace import ENGINE_REGIONS

#: the loop is in none of its phases: between two turns of ``step()``
BETWEEN_TURNS = "between_turns"
#: the decode horizon's program, and the two an admission runs
STEP_PROGRAM = "jit_step"
ADMISSION_PROGRAMS = ("jit_prefill", "jit_chunk")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"

Event = tuple[str, float, float]  # name, start_s, dur_s


# -- the arithmetic: plain lists in, plain numbers out -----------------------


def union(events: list[Event]) -> list[tuple[float, float]]:
    """The union of the events' intervals as disjoint sorted spans."""
    out: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return [(a, b) for a, b in out]


def phase_intervals(regions: list[Event]) -> list[tuple[float, float, str]]:
    """``(start, end, name)``, disjoint and sorted: at each instant the
    innermost open region of one thread's properly nested regions."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name), outermost first
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, start, dur in sorted(regions, key=lambda r: (r[1], -r[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start) if stack else start
        stack.append((start + dur, name))
    close_until(float("inf"))
    return out


def split_by_overlap(gaps: list[tuple[float, float]],
                     labelled: list[tuple[float, float, str]],
                     rest: str) -> dict[str, float]:
    """Seconds of ``gaps`` by the label of the interval they overlap,
    what no interval covers under ``rest``. Both lists are disjoint and
    sorted; one pass over each."""
    total: dict[str, float] = {}
    i = 0
    for a, b in gaps:
        covered = 0.0
        while i < len(labelled) and labelled[i][1] <= a:
            i += 1
        j = i
        while j < len(labelled) and labelled[j][0] < b:
            lo, hi, label = labelled[j]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                total[label] = total.get(label, 0.0) + part
                covered += part
            j += 1
        if b - a > covered:
            total[rest] = total.get(rest, 0.0) + (b - a) - covered
    return total


def program_name(event_name: str) -> str:
    """``jit_step(123456)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def step_gaps(modules: list[Event]) -> dict:
    """What one device's ``XLA Modules`` line says about the time
    between decode steps: see the module docstring."""
    programs = sorted(
        ((program_name(n), s, s + d) for n, s, d in modules),
        key=lambda p: p[1],
    )
    steps = [p for p in programs if p[0] == STEP_PROGRAM]
    others = [(s, e, n) for n, s, e in programs if n != STEP_PROGRAM]
    gaps = [(a[2], b[1]) for a, b in zip(steps, steps[1:]) if b[1] > a[2]]
    return {
        "steps": len(steps),
        "step_gap_s": sum((b - a for a, b in gaps), 0.0),
        "step_gap_by_program_s": split_by_overlap(gaps, others, "idle"),
        "small_program_s": sum(
            (e - s for s, e, n in others if n not in ADMISSION_PROGRAMS), 0.0
        ),
    }


def admitting_turns(regions: list[Event]) -> list[tuple[float, float]]:
    """``(start, end)`` of each turn that admits. A turn runs from one
    ``sweep`` to the next and admits when a ``key_sync`` or a ``prefill``
    opens in it (inside its ``admit`` or, piggybacked, its ``dispatch``);
    its span runs from the start of its ``admit`` (of the first of these
    regions the capture holds: it can begin in the middle of a turn) to
    the end of its ``dispatch`` (the capture's end where it holds none)."""
    out: list[tuple[float, float]] = []
    begun = end = None
    admits = False
    turn_ends = [("sweep", float("inf"), 0.0)]
    for name, start, dur in sorted(regions, key=lambda r: r[1]) + turn_ends:
        if name == "sweep":
            if admits:
                out.append((begun, float("inf") if end is None else end))
            begun = end = None
            admits = False
        elif name in ("admit", "key_sync", "prefill"):
            begun = start if begun is None else begun
            admits = admits or name != "admit"
        elif name == "dispatch":
            end = start + dur
    return out


def reduce_events(ops: list[Event], modules: list[Event] | None,
                  regions: list[Event],
                  horizons: list[int] | None = None) -> dict:
    """The report from one device's ops, its modules (``None`` where the
    capture has none) and one host thread's ``engine.<phase>`` regions."""
    busy = union(ops)
    gaps = [(a1, b0) for (_, a1), (b0, _) in zip(busy, busy[1:])]
    strip = len(ENGINE_REGIONS) + 1
    named = [(n[strip:], s, d) for n, s, d in regions]
    idle = split_by_overlap(gaps, phase_intervals(named), BETWEEN_TURNS)
    turns = admitting_turns(named)
    in_turns = split_by_overlap(gaps, [(a, b, "in") for a, b in turns], "out")
    count: dict[str, int] = {}
    for name, _, _ in named:
        count[name] = count.get(name, 0) + 1
    report = {
        "window_s": busy[-1][1] - busy[0][0] if busy else 0.0,
        "device_busy_s": sum(b - a for a, b in busy),
        "device_idle_s": sum(idle.values()),
        "idle_by_phase_s": idle,
        "idle_in_admitting_turns_s": in_turns.get("in", 0.0),
        "admitting_turns": len(turns),
        "regions": count,
        "horizons": horizons,
        "steps": None, "step_gap_s": None, "step_gap_by_program_s": None,
        "small_program_s": None,
    }
    if modules is not None:
        report.update(step_gaps(modules))
    return report


# -- reading a capture -------------------------------------------------------


def find_xplane(capture_dir: str | Path) -> Path | None:
    """The newest ``.xplane.pb`` the profiler wrote under a directory
    handed to ``start_trace``; ``None`` where there is none."""
    found = list(Path(capture_dir).glob("plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime_ns) if found else None


def _events(line) -> list[Event]:
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def _read(path: str):
    """(ops, modules or None, the loop thread's regions, its horizons)."""
    from jax.profiler import ProfileData

    device: tuple[int, object] | None = None
    host = None
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and (device is None or int(m.group(1)) < device[0]):
            device = (int(m.group(1)), plane)
        elif plane.name == _HOST_PLANE:
            host = plane
    ops: list[Event] = []
    modules: list[Event] | None = None
    if device is not None:
        modules = []
        for line in device[1].lines:
            if line.name == "XLA Ops":
                ops = _events(line)
            elif line.name == "XLA Modules":
                modules = _events(line)
    dot = ENGINE_REGIONS + "."
    dispatch = dot + "dispatch"
    loop: tuple[list[Event], list[tuple]] = ([], [])
    for line in host.lines if host is not None else ():
        regions: list[Event] = []
        turns: list[tuple] = []  # (start, n) of each dispatch region
        stands_in = device is None and line.name.startswith(_CPU_CLIENT_LINE)
        for e in line.events:
            name = e.name
            if name.startswith(dot):
                regions.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
                if name == dispatch:
                    turns.append((e.start_ns, dict(e.stats).get("n")))
            elif stands_in and e.duration_ns > 0:
                ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
        if len(turns) > len(loop[1]):
            loop = (regions, sorted(turns))
    regions, turns = loop
    ns = [int(n) for _, n in turns if n is not None]
    return ops, modules, regions, [ns[0], ns[-1]] if ns else None


@functools.lru_cache(maxsize=8)
def _report(path: str, mtime_ns: int) -> dict:
    return reduce_events(*_read(path))


def loop_report(xplane_path: str | Path) -> dict:
    """The report of one capture (an ``.xplane.pb``); memoised by the
    file's path and modification time, so treat the result as read-only."""
    path = os.fspath(xplane_path)
    return _report(path, os.stat(path).st_mtime_ns)
