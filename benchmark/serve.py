"""The ``serve`` kind: a ``ServingEngine`` under generated traffic.

The benchmark builds ``TransformerConfig`` -> ``ServingEngine`` ->
``ServingServer`` from the configuration file and starts the server's own
supervised engine thread, the loop ``serve`` runs in production. Requests
enter in process: one generator thread calls ``engine.submit`` on the
traffic's schedule, and every token is stamped where the engine delivers
it, in ``Request.stream.put``. The HTTP front door is a later cell.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import queue
import threading
import time
import urllib.request

import numpy as np

from benchmark import stats, traffic

#: seconds of the window the traced run profiles, from its middle
TRACE_SECONDS = 2.0
#: a warm-up request decodes through two horizons: first token, then end
_WARM_HORIZONS = 2


class StampedStream(queue.Queue):
    """A ``Request.stream`` that keeps no token and needs no consumer:
    ``put`` stamps the delivery time, and the end-of-stream sentinel
    reports the request ended."""

    def __init__(self, on_end):
        super().__init__()
        self.times: list[float] = []
        self._on_end = on_end

    def put(self, item, block=True, timeout=None):
        if item is None:
            self._on_end(self)
        else:
            self.times.append(time.perf_counter())


@dataclasses.dataclass
class Sent:
    """One request as the benchmark saw it."""

    spec: traffic.ServeRequest
    due: float  # perf_counter time it was due (closed loop: sent)
    request: object = None  # the program's Request; None if refused
    stream: StampedStream | None = None
    ended: float | None = None
    refused: str | None = None

    def ok(self) -> bool:
        """Ended FINISHED with exactly the tokens it asked for."""
        return (
            self.request is not None and self.ended is not None
            and self.request.status.value == "finished"
            and len(self.stream.times) == self.spec.max_new
        )

    def ttft_s(self) -> float:
        return self.stream.times[0] - self.due

    def tpot_s(self) -> float | None:
        t = self.stream.times
        return (t[-1] - t[0]) / (len(t) - 1) if len(t) > 1 else None


class Load:
    """Sends a list of requests to an engine from one thread, closed or
    open loop, and keeps what happened to each."""

    def __init__(self, engine):
        from deeplearning4j_tpu.serving.scheduler import Request

        self.engine = engine
        self._request_cls = Request
        self.sent: list[Sent] = []
        self.late_s: list[float] = []
        self._ended: queue.SimpleQueue = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def _submit(self, spec: traffic.ServeRequest, due: float) -> Sent:
        rec = Sent(spec=spec, due=due)

        def on_end(stream, rec=rec):
            rec.ended = time.perf_counter()
            self._ended.put(rec)

        rec.stream = StampedStream(on_end)
        req = self._request_cls(
            prompt=spec.prompt, max_new=spec.max_new, stream=rec.stream
        )
        try:
            self.engine.submit(req)
            rec.request = req
        except Exception as e:  # Backpressure, AdmissionError: a refusal
            rec.refused = f"{type(e).__name__}: {e}"
            rec.ended = time.perf_counter()
        self.sent.append(rec)
        return rec

    def _closed(self, specs, outstanding: int, cycle: bool) -> None:
        """``outstanding`` in flight; each end sends the next. With
        ``cycle`` the list repeats until stopped, else it is sent once
        and waited for."""
        it = iter(specs)
        in_flight = 0
        while not self._stop.is_set():
            while in_flight < outstanding:
                spec = next(it, None)
                if spec is None and cycle:
                    it = iter(specs)
                    spec = next(it)
                if spec is None:
                    break
                if self._submit(spec, time.perf_counter()).request is not None:
                    in_flight += 1
            if in_flight == 0:
                return
            try:
                rec = self._ended.get(timeout=0.05)
            except queue.Empty:
                continue
            in_flight -= 1
            self.late_s.append(time.perf_counter() - rec.ended)

    def _open(self, specs, t0: float) -> None:
        for spec in specs:
            due = t0 + spec.due_s
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self._stop.wait(min(wait, 0.05)):
                    break
            if self._stop.is_set():
                return
            self.late_s.append(time.perf_counter() - due)
            self._submit(spec, due)

    def _guard(self, fn, *args) -> None:
        try:
            fn(*args)
        except BaseException as e:  # surfaced by the run, never swallowed
            self.error = e

    def _start(self, fn, *args) -> None:
        self._thread = threading.Thread(
            target=self._guard, args=(fn, *args), name="bench-load",
            daemon=True,
        )
        self._thread.start()

    def start_closed(self, specs, outstanding: int, cycle: bool) -> None:
        self._start(self._closed, specs, outstanding, cycle)

    def start_open(self, specs, t0: float) -> None:
        self._start(self._open, specs, t0)

    def join(self, timeout: float, still_well=lambda: None) -> None:
        """Wait until a list sent once has ended; ``still_well`` is asked
        every second and raises when waiting has become pointless."""
        deadline = time.perf_counter() + timeout
        while self._thread.is_alive():
            self._thread.join(1.0)
            try:
                still_well()
                if time.perf_counter() > deadline:
                    raise RuntimeError(
                        f"requests still in flight after {timeout} s")
            except BaseException:
                self._stop.set()
                raise
        if self.error is not None:
            raise self.error

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(10.0)
        if self._thread.is_alive():
            raise RuntimeError("the load generator did not stop")
        if self.error is not None:
            raise self.error


def transformer_config(model: dict):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    kwargs = dict(model)
    kwargs["compute_dtype"] = jnp.dtype(kwargs["compute_dtype"])
    return TransformerConfig(**kwargs)


def make_params(cfg, seed: int):
    """The served weights, made on the device from the seed in one
    jitted program, in the type they are served in."""
    import jax

    from deeplearning4j_tpu.models.transformer import (
        _decode_builder,
        init_transformer,
    )

    cast = _decode_builder(cfg)[3]
    return jax.jit(lambda key: cast(init_transformer(key, cfg)))(
        jax.random.key(seed % (2**31))
    )


def load_reference(config: dict):
    """The plain reference the configuration names:
    ``benchmark/reference/<name>.py``."""
    return importlib.import_module(f"benchmark.reference.{config['reference']}")


def check_logits(reference, cfg, params, check: dict, seed: int,
                 max_total: int):
    """Prefill, then decode through the cache, against the reference's
    full forward, on logits. The model's own ``prefill`` and
    ``forward_one`` (the functions the engine's programs are built from)
    run on a cache of the engine's layout and row count, on rows of
    different lengths in one bucket; the reference sees each whole
    sequence at once. Returns (ok, text)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deeplearning4j_tpu.models.transformer import _decode_builder

    fwd1, init_caches, prefill, _ = _decode_builder(cfg)
    lens = np.asarray(check["prompt_lens"], np.int32)
    steps = int(check["decode_steps"])
    bucket = int(check["bucket"])
    rng = np.random.default_rng([seed, 1])
    rows = len(lens)
    seqs = rng.integers(0, cfg.vocab_size, (rows, bucket + steps), np.int32)
    prompts = seqs[:, :bucket].copy()
    forced = np.stack([seqs[r, n:n + steps] for r, n in enumerate(lens)])
    for r, n in enumerate(lens):  # what the reference reads: prompt, forced
        seqs[r, n:n + steps] = forced[r]

    @jax.jit
    def through_cache(params, prompts, lens, forced):
        caches, lg0 = prefill(
            params, init_caches(rows, max_total), prompts, lens - 1
        )

        def one(carry, tok_j):
            caches, j = carry
            lg, caches = fwd1(params, caches, tok_j, lens + j)
            return (caches, j + 1), lg

        _, lgs = lax.scan(one, (caches, jnp.int32(0)), forced.T)
        return jnp.concatenate([lg0[None], lgs], axis=0)  # (steps+1, rows, V)

    got = np.asarray(
        through_cache(params, jnp.asarray(prompts), jnp.asarray(lens),
                      jnp.asarray(forced)), np.float32,
    ).transpose(1, 0, 2)
    full = np.asarray(reference.forward(params, jnp.asarray(seqs)))
    want = np.stack([
        full[r, n - 1:n + steps] for r, n in enumerate(lens)
    ])
    scale = float(np.max(np.abs(want)))
    max_err = float(np.max(np.abs(got - want))) / scale
    rms_err = float(np.sqrt(np.mean((got - want) ** 2))) / float(
        np.sqrt(np.mean(want**2))
    )
    ok = (bool(np.isfinite(got).all())
          and max_err <= check["max_err_of_scale"]
          and rms_err <= check["rms_err_of_rms"])
    text = (
        f"logits vs reference on {rows} sequences x {steps + 1} positions "
        f"(prefill + {steps} decode steps through the cache): max error "
        f"{max_err:.3e} of the largest logit (tol "
        f"{check['max_err_of_scale']}), rms error {rms_err:.3e} of the rms "
        f"logit (tol {check['rms_err_of_rms']})"
    )
    return ok, text


def warm_specs(traces, horizon: int):
    """One short request for every distinct prompt length of the run's
    traffic: whatever prefill and chunk programs those lengths select, and
    no others, compile here. Longest first, so a program's first use is
    not behind a queue of short ones."""
    by_len = {len(r.prompt): r for t in traces for r in t.requests}
    return [
        traffic.ServeRequest(
            prompt=by_len[n].prompt,
            max_new=min(_WARM_HORIZONS * horizon, by_len[n].max_new),
            due_s=None,
        )
        for n in sorted(by_len, reverse=True)
    ]


@dataclasses.dataclass
class Stack:
    """The system under test, built and warm."""

    engine: object
    server: object
    model: dict  # the model sizes as run
    geometry: dict  # the engine's arguments as run
    correct: bool  # logits agree, and the engine kept what it was given
    compiles_warm: int  # compile requests up to the end of warm-up

    def health(self) -> dict:
        """``GET /healthz`` of the server: the supervisor's own view."""
        url = "http://%s:%d/healthz" % self.server.address
        with urllib.request.urlopen(url, timeout=30.0) as resp:
            return json.loads(resp.read())

    def check_well(self) -> None:
        """Raise if the supervised engine loop crashed, restarted or
        died: nothing measured after that stands for the system."""
        h = self.health()
        if not h["ok"] or h["restarts"] or h["last_error"]:
            raise RuntimeError(
                f"the engine is not well: ok={h['ok']} restarts="
                f"{h['restarts']} last_error={h['last_error']}"
            )

    def stop(self) -> None:
        self.server.stop(drain_s=0.0)
        if not self.engine.idle:
            raise RuntimeError("the engine did not stop idle")


def sizes(ctx):
    """(model, engine arguments, correctness settings, length divisor) of
    this run: the configuration's, or its toy ones in a rehearsal."""
    config = ctx.config
    model, geometry = dict(config["model"]), dict(config["engine"])
    check, divisor = dict(config["correct"]), 1
    if ctx.rehearse:
        toy = config["rehearse"]
        model.update(toy["model"])
        geometry.update(toy["engine"])
        check.update(toy["correct"])
        divisor = int(toy["length_divisor"])
    return model, geometry, check, divisor


def set_up(ctx, make_traces) -> tuple[Stack, list]:
    """Weights, the logits check, engine, server, warm-up.
    ``make_traces(vocab, max_total, divisor)`` gives the traffic the
    system will see, which decides what is warmed."""
    import jax

    from deeplearning4j_tpu.serving import ServingEngine
    from deeplearning4j_tpu.serving.server import ServingServer

    log = ctx.compile_log
    model, geometry, check, divisor = sizes(ctx)
    cfg = transformer_config(model)
    max_total = int(geometry["max_total"])
    horizon = int(geometry["decode_horizon"])
    traces = make_traces(cfg.vocab_size, max_total, divisor)

    params = make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    ctx.note(f"weights on the device at {ctx.since_start():.1f} s")
    correct, text = check_logits(
        load_reference(ctx.config), cfg, params, check, ctx.seed, max_total
    )
    ctx.note(f"correct: {text}")

    # no probe verdict from outside the checkout: what runs is decided by
    # the configuration file alone
    os.environ.pop("DL4J_TPU_PROBE_CACHE", None)
    engine = ServingEngine(
        cfg, params, rng_seed=ctx.seed % (2**31), **geometry
    )
    del params
    kept = (
        engine.cfg.decode_kernel == cfg.decode_kernel
        and engine.cfg.use_flash == cfg.use_flash and engine.tp == 1
        and engine.decode_horizon == horizon
        and engine.n_slots == geometry["n_slots"]
        and engine.max_total == max_total
    )
    ctx.note(f"engine kept its settings: {kept}")
    server = ServingServer(engine, request_timeout_s=900.0,
                           hang_threshold_s=900.0)
    server.start()
    stack = Stack(engine=engine, server=server, model=model,
                  geometry=geometry,
                  correct=bool(correct and kept), compiles_warm=0)
    try:
        warm = Load(engine)
        warm.start_closed(warm_specs(traces, horizon),
                          outstanding=2 * int(geometry["n_slots"]),
                          cycle=False)
        warm.join(300.0, stack.check_well)
        bad = [s for s in warm.sent if not s.ok()]
        if bad:
            raise RuntimeError(
                f"warm-up: {len(bad)} of {len(warm.sent)} requests did not "
                f"finish ({bad[0].refused or bad[0].request.status})"
            )
        wait_idle(engine)
    except BaseException:
        stack.stop()
        raise
    stack.compiles_warm, seconds_warm, hits, misses = log.snapshot()
    ctx.note(
        f"warm at {ctx.since_start():.1f} s: {len(warm.sent)} warm-up "
        f"requests, {stack.compiles_warm} programs, {seconds_warm:.1f} s "
        f"tracing and compiling, compile cache {hits} hits {misses} misses; "
        f"probes run {engine.probes_run} from cache {engine.probes_from_cache}"
    )
    return stack, traces


def wait_idle(engine, timeout: float = 60.0) -> None:
    deadline = time.perf_counter() + timeout
    while not engine.idle:
        if time.perf_counter() > deadline:
            raise RuntimeError("the engine did not become idle")
        time.sleep(0.01)


def offer(stack: Stack, trace: traffic.ServeTrace, seconds: float,
          in_window=lambda win0, win1: None):
    """Offer one trace to the warm system: the ramp, ``seconds`` of
    window (``in_window`` runs inside it, on this thread), the drain.
    Returns (the load with its records, window start, window end)."""
    load = Load(stack.engine)
    t0 = time.perf_counter()
    try:
        if trace.kind == "closed_loop":
            load.start_closed(trace.requests, trace.outstanding, cycle=True)
        else:
            load.start_open(trace.requests, t0)
        win0 = t0 + trace.ramp_s
        win1 = win0 + seconds
        in_window(win0, win1)
        _sleep_until(win1 + trace.drain_s)
    finally:
        load.stop()
    return load, win0, win1


def _sleep_until(t: float) -> None:
    wait = t - time.perf_counter()
    if wait > 0:
        time.sleep(wait)


def window_sample(trace, load, win0, win1):
    """(sample, failed, good): the requests the window's percentiles are
    taken over. Closed loop: those that ended inside it. Open loop: those
    due inside it, failed unless they ended well before the drain was
    over."""
    if trace.kind == "closed_loop":
        sample = [s for s in load.sent
                  if s.ended is not None and win0 <= s.ended < win1]
    else:
        sample = [s for s in load.sent if win0 <= s.due < win1]
    good = [s for s in sample if s.ok()]
    failed = [s for s in sample if not s.ok()]
    return sample, failed, good


def tokens_between(load, t0: float, t1: float) -> int:
    stamps = np.concatenate(
        [np.asarray(s.stream.times) for s in load.sent if s.stream.times]
        or [np.zeros(0)]
    )
    return int(np.count_nonzero((stamps >= t0) & (stamps < t1)))


def run(ctx) -> dict:
    """One run of a serve cell; returns the result object's fields."""
    log = ctx.compile_log
    stack, (trace,) = set_up(
        ctx, lambda vocab, max_total, divisor: [traffic.serve_trace(
            ctx.traffic, ctx.seed, ctx.seconds, vocab, max_total, divisor)],
    )
    marks = {}

    def in_window(win0, win1):
        _sleep_until(win0)
        ctx.window_opens(win0)
        marks["begun"] = ctx.layer_snapshots(stack.engine)
        if ctx.trace:
            _sleep_until(win0 + 0.5 * (ctx.seconds - TRACE_SECONDS))
            ctx.start_trace()
            time.sleep(min(TRACE_SECONDS, ctx.seconds))
            ctx.stop_trace()
        _sleep_until(win1)
        marks["ended"] = ctx.layer_snapshots(stack.engine)
        marks["compiles"] = log.snapshot()[0] - stack.compiles_warm
        stack.check_well()

    try:
        load, win0, win1 = offer(stack, trace, ctx.seconds, in_window)
    finally:
        stack.stop()

    sample, failed, good = window_sample(trace, load, win0, win1)
    tokens = tokens_between(load, win0, win1)
    tpots = [s.tpot_s() for s in good if s.tpot_s() is not None]
    values = {
        "serve_tokens_per_s": tokens / ctx.seconds,
        "ttft_p95_ms": 1e3 * stats.percentile(
            [s.ttft_s() for s in good], 95, misses=len(failed)),
        "tpot_p95_ms": 1e3 * stats.percentile(tpots, 95, misses=len(failed)),
    }
    late = 1e3 * stats.percentile(load.late_s, 95) if load.late_s else math.nan
    ctx.note(
        f"window: {len(sample)} requests in the sample "
        f"({'ended' if trace.kind == 'closed_loop' else 'due'} in the "
        f"window), {len(failed)} failed "
        f"{sorted({s.refused or s.request.status.value for s in failed})}, "
        f"{len(load.sent)} sent in all, {tokens} tokens delivered in the "
        f"window, {marks['compiles']} compiles in the window "
        f"{log.names_since(stack.compiles_warm)}; the generator ran late by "
        f"{late:.2f} ms at the 95th percentile; ttft_p50_ms "
        f"{1e3 * stats.percentile([s.ttft_s() for s in good], 50):.1f} "
        f"tpot_p50_ms {1e3 * stats.percentile(tpots, 50):.2f}"
    )
    # the cache rows a request holds when its k-th token is produced: the
    # first comes from the prefill's logits and needs no decode read
    deliveries = [
        (t, len(s.spec.prompt) + k if k else 0)
        for s in load.sent for k, t in enumerate(s.stream.times)
    ] if ctx.trace else []
    return {
        "correct": bool(stack.correct and marks["compiles"] == 0),
        "attempted": len(sample),
        "failed": len(failed),
        "values": values,
        "layer_inputs": {
            "system": stack.engine,
            "begun": marks["begun"], "ended": marks["ended"],
            "deliveries": deliveries, "model": stack.model,
            "geometry": stack.geometry,
        },
    }
