"""One generator for every traffic mix: a mix is a data file.

``benchmark/traffic/<name>.json`` holds ``kind`` and its parameters:

- ``closed_loop``: ``outstanding`` requests always in flight, each
  completion sends the next; ``requests`` is the size of the population
  the run cycles through.
- ``open_loop``: arrivals at ``rate_per_s`` whatever the system does;
  ``ramp_s`` of the same traffic precede the measured window and
  ``drain_s`` follow it.
- ``train_steps``: token batches of the trainer's own shape.

Lengths are ``{"dist": ...}`` objects: ``fixed`` (value), ``uniform``,
``log_uniform`` (low, high) or ``log_normal`` (median, sigma, low, high);
every draw is rounded and clipped to [low, high], and the output is cut
so that prompt + output fits ``max_total``.

The population of a mix (its lengths and, in an open loop, its
inter-arrival gaps) is drawn from the file's ``population_seed``, so every
``--seed`` runs the same multiset of work. ``--seed`` decides the order of
the population and the token ids. Gaps are exponential (a Poisson process)
scaled to fill the ramp plus the window exactly, so the number of requests
is fixed too.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") not in ("closed_loop", "open_loop", "train_steps"):
        raise ValueError(f"{path}: unknown traffic kind {spec.get('kind')!r}")
    return spec


def draw_lengths(spec: dict, n: int, rng: np.random.Generator,
                 divisor: int = 1) -> np.ndarray:
    """``n`` whole lengths from one ``{"dist": ...}`` object. ``divisor``
    shrinks the whole distribution (a rehearsal's toy geometry)."""
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
        low = high = float(spec["value"])
    else:
        low, high = float(spec["low"]), float(spec["high"])
        if dist == "uniform":
            x = rng.uniform(low, high, n)
        elif dist == "log_uniform":
            x = np.exp(rng.uniform(np.log(low), np.log(high), n))
        elif dist == "log_normal":
            x = float(spec["median"]) * np.exp(
                float(spec["sigma"]) * rng.standard_normal(n)
            )
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
    x = np.clip(np.rint(x), low, high)
    return np.maximum(1, np.rint(x / divisor)).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    prompt: np.ndarray  # int32 token ids
    max_new: int
    due_s: float | None  # open loop: seconds after the ramp begins


@dataclasses.dataclass(frozen=True)
class ServeTrace:
    kind: str  # closed_loop | open_loop
    requests: list[ServeRequest]
    outstanding: int  # closed loop: requests kept in flight
    ramp_s: float
    drain_s: float


def serve_trace(spec: dict, seed: int, seconds: float, vocab: int,
                max_total: int, divisor: int = 1) -> ServeTrace:
    """The requests of one run: the file's population in the order
    ``seed`` gives it."""
    kind = spec["kind"]
    ramp_s = float(spec.get("ramp_s", 0.0))
    if kind == "open_loop":
        n = int(round(float(spec["rate_per_s"]) * (ramp_s + seconds)))
    else:
        n = int(spec["requests"])
    pop = np.random.default_rng(int(spec["population_seed"]))
    prompts = draw_lengths(spec["prompt_len"], n, pop, divisor)
    outputs = draw_lengths(spec["output_len"], n, pop, divisor)
    outputs = np.minimum(outputs, max_total - 1 - prompts)
    if outputs.min() < 1:
        raise ValueError("a prompt leaves no room for one output token")
    gaps = pop.exponential(1.0, n) if kind == "open_loop" else None

    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    due = None
    if gaps is not None:
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) * ((ramp_s + seconds) / gaps.sum())
    tokens = rng.integers(0, vocab, int(prompts.sum()), dtype=np.int32)
    cuts = np.cumsum(prompts[order])[:-1]
    requests = [
        ServeRequest(
            prompt=p, max_new=int(outputs[i]),
            due_s=None if due is None else float(due[k]),
        )
        for k, (i, p) in enumerate(zip(order, np.split(tokens, cuts)))
    ]
    return ServeTrace(
        kind=kind, requests=requests,
        outstanding=int(spec.get("outstanding", 0)),
        ramp_s=ramp_s, drain_s=float(spec.get("drain_s", 0.0)),
    )


def train_batch(step: int, seed: int, batch: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """The token batch (batch, seq_len + 1) of one training step: uniform
    random ids, a pure function of (seed, step)."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
