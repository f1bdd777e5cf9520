"""Share of the traced window in which the device sat idle in a turn of
the engine loop that admitted, in percent: the idle seconds (complement
of the union of ``XLA Ops``) from the start of each ``engine.admit``
region that holds an ``engine.key_sync`` or an ``engine.prefill`` to the
end of the ``engine.dispatch`` that follows it, over first start to last
end of ``XLA Ops``. It is the part of ``device.idle_share.serve`` that
admissions cost (the new slot's key read back, the step dispatched
behind it); the rest is what the loop leaves idle with nothing to admit.
The whole turn is taken because the gap opens under ``key_sync`` and
closes a little into ``dispatch``, and a capture's host events can read a
millisecond late against the device's.

Source: the program's ``obs.capture.loop_report`` of the run's own
capture (``idle_in_admitting_turns_s``), printed whole as a ``benchmark:
loop report`` line. **The bridge:** ``run.py`` reduces the capture to
``m.trace``, which has lost the host plane's ``engine.*`` regions, and
deletes the ``.xplane.pb`` before any ``read(m)`` runs. The closing
``snapshot`` (after ``stop_trace`` has returned) is the one moment at
which a reader sees the file: it keeps a hard link to it, and ``read(m)``
has the program reduce the kept file once the engine has stopped, so the
reduction never shares the interpreter with requests that still drain.
A program without ``obs.capture``, a capture that holds none of the
regions or one the program cannot read reports nothing. When
``trace_reduce.load`` keeps the ``engine.*`` events (ROADMAP S0b, a
``benchmark`` PR) this reader moves to ``m.trace`` and the bridge goes."""

import json
import os
import shutil
import time
from pathlib import Path

_CACHE = Path(__file__).resolve().parents[2] / ".bench_cache"
_IMPORTED = time.time()


def snapshot(engine):
    """A hard link to the newest capture under ``.bench_cache/trace``
    written since this module was imported; ``None`` where there is none
    (the window's opening)."""
    found = [
        (p.stat().st_mtime, p)
        for p in _CACHE.glob("trace/*/plugins/profile/*/*.xplane.pb")
    ]
    found = [f for f in found if f[0] >= _IMPORTED]
    if not found:
        return None
    kept = _CACHE / "loop_report.xplane.pb"
    kept.unlink(missing_ok=True)
    try:
        os.link(max(found)[1], kept)
    except OSError:  # a file system without hard links
        shutil.copyfile(max(found)[1], kept)
    return str(kept)


def report(kept):
    """The program's report of the kept capture, printed as a
    ``benchmark: loop report`` line; ``None`` where nothing was kept or
    the program cannot give one."""
    if kept is None:
        return None
    t0 = time.perf_counter()
    try:
        from deeplearning4j_tpu.obs import capture

        found = capture.loop_report(kept)
    except ImportError:  # the parent of the PR that brought obs.capture
        return None
    except Exception as e:  # a capture the program cannot read
        print(f"benchmark: no loop report: {e!r}", flush=True)
        return None
    finally:
        os.unlink(kept)
    found = dict(found, reduced_in_s=time.perf_counter() - t0)
    print(f"benchmark: loop report {json.dumps(found)}", flush=True)
    return found


def share(report):
    if report is None or not report["regions"] or not report["window_s"]:
        return None
    return 100.0 * report["idle_in_admitting_turns_s"] / report["window_s"]


def read(m):
    return share(report(m.after))
