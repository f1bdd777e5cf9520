"""``BENCHMARK.json``: look-ups for the runner, and a self-check.

The self-check enforces what the driver's contract states about the file
(keys, names, units, layers, bounds, which cell reports what, files
present), so that a manifest the driver would refuse is refused here,
before a chip call. ``python3 benchmark/manifest.py`` prints the problems
and exits 1 if there are any.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
#: widths may never be reduced
_WIDTH = re.compile(
    r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head_size|"
    r"expansion|experts_per_tok|n_embd|n_inner|d_model|d_ff"
)


def load(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for c in manifest["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(
        f"no cell {name!r}; cells: {[c['name'] for c in manifest['workloads']]}"
    )


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r}")


def metrics_of(manifest: dict, cell_name: str, section: str) -> list[dict]:
    """The ``section`` metrics the cell reports: those without a
    ``workloads`` key, and those that list the cell."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def _line(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def check(manifest: dict, root: Path) -> list[str]:
    """Every way the manifest breaks the contract; empty when it holds."""
    bad: list[str] = []
    root = Path(root)
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return bad
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("file over 64 KiB")
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16) or not all(
        isinstance(p, str) and PATH.match(p) and not p.startswith("/")
        and ".." not in p.split("/") for p in paths
    ):
        bad.append(f"paths {paths!r}")
    command = manifest["command"]
    if not (1 <= len(command) <= 32) or not all(_line(w) for w in command):
        bad.append("command: 1 to 32 words of 1 to 200 characters")
    for word in command:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        if (root / word).exists() and not any(
            word == p or word.startswith(p + "/") for p in paths
        ):
            bad.append(f"command names {word!r}, a file outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        bad.append(f"run_seconds {rs!r}: a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p + "/") for p in paths)

    configs, cells = manifest["configs"], manifest["workloads"]
    if not 1 <= len(configs) <= 24:
        bad.append("1 to 24 configs")
    if not 1 <= len(cells) <= 24:
        bad.append("1 to 24 workloads")
    files = set()
    for c in configs:
        where = f"config {c.get('name')!r}"
        if set(c) != CONFIG_KEYS:
            bad.append(f"{where}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]):
            bad.append(f"{where}: name")
        if not _line(c["source"]) or not _line(c["why"]):
            bad.append(f"{where}: source and why are one line of 1 to 200")
        if not (PATH.match(c["file"]) and under_paths(c["file"])):
            bad.append(f"{where}: file {c['file']!r} not under paths")
        elif not (root / c["file"]).is_file():
            bad.append(f"{where}: file {c['file']!r} is missing")
        if c["file"] in files:
            bad.append(f"{where}: file shared with another configuration")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            bad.append(f"{where}: more than 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key):
                bad.append(f"{where}: reduced key {key!r}")
            elif _WIDTH.search(key):
                bad.append(f"{where}: reduced names a width, {key!r}")
        if c["name"] not in {w.get("config") for w in cells}:
            bad.append(f"{where}: used by no cell")
    pairs = set()
    four = 0
    for w in cells:
        where = f"cell {w.get('name')!r}"
        if set(w) != CELL_KEYS:
            bad.append(f"{where}: keys {sorted(w)}")
            continue
        for key in ("name", "config", "traffic"):
            if not NAME.match(w[key]):
                bad.append(f"{where}: {key} {w[key]!r}")
        if not _line(w["why"]):
            bad.append(f"{where}: why is one line of 1 to 200 characters")
        if w["chips"] not in (1, 4):
            bad.append(f"{where}: chips {w['chips']!r}")
        four += w["chips"] == 4
        if w["config"] not in {c.get("name") for c in configs}:
            bad.append(f"{where}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"{where}: config and traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        mix = BENCH_DIR / "traffic" / f"{w['traffic']}.json"
        if not mix.is_file():
            bad.append(f"{where}: traffic file {mix} is missing")
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}")
    for kind, names in (("config", [c.get("name") for c in configs]),
                        ("cell", [w.get("name") for w in cells])):
        if len(set(names)) != len(names):
            bad.append(f"two {kind}s share a name")

    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        bad.append("1 to 16 end_to_end metrics")
    if not 1 <= len(layer) <= 128:
        bad.append("1 to 128 per_layer metrics")
    cell_names = [w.get("name") for w in cells]
    names = [m.get("name") for m in e2e + layer]
    if len(set(names)) != len(names):
        bad.append("two metrics share a name")
    for m in e2e + layer:
        where = f"metric {m.get('name')!r}"
        keys = set(m) - {"workloads"}
        if keys != (E2E_KEYS if m in e2e else LAYER_KEYS):
            bad.append(f"{where}: keys {sorted(m)}")
            continue
        if not NAME.match(m["name"]):
            bad.append(f"{where}: name")
        if not UNIT.match(m["unit"]):
            bad.append(f"{where}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{where}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{where}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                bad.append(f"{where}: lists unknown cell {w!r}")
        if "workloads" in m and not m["workloads"]:
            bad.append(f"{where}: empty workloads")
    for m in e2e:
        if set(m) - {"workloads"} != E2E_KEYS:
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']!r}: end-to-end source {m['source']!r}")
        if not (isinstance(m["bound"], (int, float)) and 0.01 <= m["bound"] <= 0.1):
            bad.append(f"metric {m['name']!r}: bound {m['bound']!r} not in 0.01..0.1")
    if "setup_s" not in [m.get("name") for m in e2e]:
        bad.append("no setup_s among the end-to-end metrics")
    for m in layer:
        if set(m) - {"workloads"} != LAYER_KEYS:
            continue
        # the PR 22 refusal: a layer is an identifier, not a phrase
        if not NAME.match(m["layer"]):
            bad.append(f"metric {m['name']!r}: layer {m['layer']!r} is not a name")
        if m["moves"] not in [e.get("name") for e in e2e]:
            bad.append(f"metric {m['name']!r}: moves unknown {m['moves']!r}")
        reader = BENCH_DIR / "layer_metrics" / f"{m['name']}.py"
        if not reader.is_file():
            bad.append(f"metric {m['name']!r}: reader {reader} is missing")
    for w in cell_names:
        reported_e2e = {m["name"] for m in metrics_of(manifest, w, "end_to_end")}
        reported_layer = metrics_of(manifest, w, "per_layer")
        if "setup_s" not in reported_e2e:
            bad.append(f"cell {w!r} does not report setup_s")
        if len(reported_e2e) < 2:
            bad.append(f"cell {w!r} reports no end-to-end metric but setup_s")
        if not reported_layer:
            bad.append(f"cell {w!r} reports no per-layer metric")
        for m in reported_layer:
            if m.get("moves") not in reported_e2e:
                bad.append(
                    f"cell {w!r} reports {m['name']!r} but not the metric "
                    f"it moves, {m.get('moves')!r}"
                )
    return bad


def main() -> int:
    root = Path(__file__).resolve().parents[1]
    problems = check(load(root), root)
    for p in problems:
        print(p)
    print(f"BENCHMARK.json: {len(problems)} problems")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
