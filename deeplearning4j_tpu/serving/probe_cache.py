"""On-disk cache of parity-probe verdicts.

The engine gates every fast path — chunked crash replay, prefix-cache
reuse, batched admission, tensor-parallel decode — behind a one-time
bitwise parity probe. Verdicts are pure functions of (probe, model
config, backend, program geometry): nothing about a particular process
run enters the comparison, so a verdict computed once is valid for
every later engine instance on the same machine. This module persists
them, keyed by a digest of exactly those inputs, so repeated engine
construction (replica fleets, restarts, tests) skips the cold-start
probe dispatches.

The file is a flat JSON object ``{digest: bool}``. Writes go through a
same-directory temp file + ``os.replace`` so concurrent engines never
read a torn file; a corrupt or unreadable file degrades to an empty
cache (the probe just runs again). Losing a race between two writers
drops at most the other writer's fresh verdicts for this process — the
next engine recomputes and re-persists them.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile


def probe_key(probe: str, cfg_json: str, **geometry) -> str:
    """Stable digest for one probe verdict: the probe name, the full
    model config JSON, the backend platform and device kind (a verdict
    from one TPU generation cannot vouch for another), and any
    program-geometry knobs the probe's compiled programs
    depend on (bucket sizes, slot counts, TP width...). The JAX version
    participates too: a verdict reflects the compiler that produced it,
    and an upgrade may change fusion/reduction order, so stale verdicts
    must miss rather than vouch for programs they never saw."""
    import jax

    dev = jax.devices()[0]
    payload = {
        "probe": probe,
        "cfg": cfg_json,
        "jax": jax.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        **{k: geometry[k] for k in sorted(geometry)},
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


class ProbeCache:
    """Read-through/write-through verdict store over one JSON file."""

    def __init__(self, path: str):
        self.path = os.fspath(path)
        self._verdicts: dict[str, bool] = self._load()

    def _load(self) -> dict[str, bool]:
        try:
            with open(self.path) as f:
                data = json.load(f)
            return {
                k: bool(v) for k, v in data.items()
                if isinstance(k, str) and isinstance(v, bool)
            }
        except (OSError, ValueError):
            return {}

    def get(self, key: str) -> bool | None:
        """The persisted verdict, or None if never computed."""
        return self._verdicts.get(key)

    def put(self, key: str, verdict: bool) -> None:
        """Persist one verdict (atomic re-write of the whole file,
        merged over whatever is on disk right now)."""
        merged = self._load()
        merged.update(self._verdicts)
        merged[key] = bool(verdict)
        self._verdicts = merged
        d = os.path.dirname(self.path) or "."
        try:
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(merged, f, sort_keys=True)
                os.replace(tmp, self.path)
            except BaseException:
                os.unlink(tmp)
                raise
        except OSError:
            # persistence is best-effort: an unwritable path costs a
            # re-probe next process, never a serving failure
            pass
