"""BENCHMARK.json against the driver's contract, and that the self-check
catches what refused PR 22."""

import copy
from pathlib import Path

import pytest

from benchmark import manifest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def good():
    return manifest.load(ROOT)


def test_the_committed_manifest_holds(good):
    assert manifest.check(good, ROOT) == []


def test_layers_are_the_five_identifiers(good):
    assert {m["layer"] for m in good["per_layer"]} <= {
        "scheduler", "engine_loop", "programs", "kernels", "device"}


def _broken(good, edit):
    bad = copy.deepcopy(good)
    edit(bad)
    return manifest.check(bad, ROOT)


@pytest.mark.parametrize("edit, says", [
    (lambda m: m["per_layer"][0].update(layer="engine loop"), "layer"),
    (lambda m: m["per_layer"][0].update(name="sched occupancy"), "name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["per_layer"][0].update(moves="ttft_p95_ms"), "moves"),
    (lambda m: m["per_layer"][0].update(why="because"), "keys"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "traffic file"),
    (lambda m: m["configs"][0].update(reduced=["n_embd"]), "width"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: [w.update(chips=4) for w in m["workloads"][:2]], "four-chip"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["command"].append("bench.py"), "outside paths"),
])
def test_the_self_check_catches(good, edit, says):
    problems = _broken(good, edit)
    assert any(says in p for p in problems), problems


def test_every_cell_reports_what_its_layer_metrics_move(good):
    for cell in good["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_of(good, cell["name"], "end_to_end")}
        for m in manifest.metrics_of(good, cell["name"], "per_layer"):
            assert m["moves"] in e2e
