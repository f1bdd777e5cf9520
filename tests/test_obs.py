"""Observability subsystem: tracer export validity, Prometheus text
format, bounded reservoirs, profiling trigger, structured logs.

The contracts under test are the ones the serving hot path leans on:

- a DISABLED tracer records exactly zero events (the engine ships with
  tracing off; the guard pins that "off" means off, not "cheap"),
- an ENABLED tracer produces structurally valid Chrome-trace JSON —
  per-track spans properly nested, metadata tracks present — that
  Perfetto/chrome://tracing will load,
- ``GET /metrics`` output parses as Prometheus text exposition 0.0.4
  and carries every family the serving dashboards scrape,
- latency series stay bounded (Algorithm R reservoir) while their
  n/total/min/max aggregates stay exact.
"""

import json
import logging
import math
import re
import time
from io import StringIO

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.obs import (
    MetricsRegistry,
    ProfileTrigger,
    Reservoir,
    Tracer,
    configure_json_logging,
)
from deeplearning4j_tpu.obs.trace import ENGINE_TRACK, SCHEDULER_TRACK
from deeplearning4j_tpu.serving import (
    Request,
    ServingEngine,
    ServingServer,
    run_request_trace,
)
from deeplearning4j_tpu.serving.metrics import PHASES, ServingMetrics

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)


def _params(seed=0):
    return init_transformer(jax.random.key(seed), CFG)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        tp = int(rng.integers(3, 10))
        out.append(Request(
            prompt=rng.integers(0, CFG.vocab_size, (tp,)).astype(np.int32),
            max_new=int(rng.integers(4, 12)),
        ))
    return out


@pytest.fixture(scope="module")
def traced_run():
    """One traced serving run shared by the export/structure tests:
    8 staggered requests through 3 slots, fused horizon 2, tracing ON."""
    tracer = Tracer(enabled=True, capacity=1 << 14)
    engine = ServingEngine(
        CFG, _params(), n_slots=3, temperature=0.0, decode_horizon=2,
        tracer=tracer,
    )
    results = run_request_trace(
        engine, [(0.002 * i, r) for i, r in enumerate(_requests(8, seed=11))]
    )
    assert len(results) == 8
    return engine, tracer


# -- reservoir / registry units ------------------------------------------


def test_reservoir_bounded_with_exact_aggregates():
    r = Reservoir(cap=64, seed=3)
    vals = np.random.default_rng(0).exponential(1.0, 10_000)
    for v in vals:
        r.add(v)
    assert len(r.values) == 64          # sample stays at cap
    assert r.n == 10_000                # aggregates stay exact
    assert r.total == pytest.approx(vals.sum())
    assert r.min == pytest.approx(vals.min())
    assert r.max == pytest.approx(vals.max())
    assert r.mean == pytest.approx(vals.mean())
    # the sample is drawn from the series, not fabricated
    pool = set(np.round(vals, 12))
    assert all(round(v, 12) in pool for v in r.values)
    with pytest.raises(ValueError):
        Reservoir(cap=0)


def test_registry_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_name", labelnames=("bad-label",))
    c = reg.counter("requests_total", "help", labelnames=("outcome",))
    with pytest.raises(ValueError):
        reg.gauge("requests_total")     # kind mismatch on existing name
    assert reg.counter("requests_total") is c  # get-or-create
    with pytest.raises(ValueError):
        c.inc(outcome="x", extra="y")   # undeclared label
    with pytest.raises(ValueError):
        c.inc(-1, outcome="x")          # counters only go up
    with pytest.raises(ValueError):
        reg.gauge("g", labelnames=("a",)).set_function(lambda: 1)


def test_histogram_render_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "help", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render()
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 3' in text
    assert 'lat_seconds_bucket{le="1"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text
    assert h.count() == 5
    m = re.search(r"lat_seconds_sum (\S+)", text)
    assert float(m.group(1)) == pytest.approx(5.605)


# -- tracer --------------------------------------------------------------


def test_disabled_tracer_records_nothing(traced_run):
    """The default engine tracer is disabled and must buffer ZERO
    events across a full serving run — the overhead guard."""
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
    assert not engine.tracer.enabled
    run_request_trace(
        engine, [(0.0, r) for r in _requests(3, seed=5)]
    )
    assert engine.tracer.n_events == 0
    assert engine.tracer.dropped == 0
    # region() must not take timestamps either
    with engine.tracer.region("t", "x"):
        pass
    assert engine.tracer.n_events == 0


def test_tracer_ring_buffer_bounds_memory():
    t = Tracer(enabled=True, capacity=8)
    for i in range(100):
        t.span("trk", "s", float(i), 0.5)
    assert t.n_events == 8
    assert t.dropped == 92
    # oldest events were the ones overwritten
    spans = [e for e in t.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 8


def test_chrome_trace_export_is_valid(traced_run, tmp_path):
    """Structural validation of the exported Chrome-trace JSON: it
    json-round-trips, declares its tracks via metadata events, spans
    carry non-negative µs ts/dur, and per-track spans NEST (no partial
    overlap) on the engine and slot tracks. The scheduler track is
    exempt from the nesting check: concurrent requests legitimately
    overlap their ``queued`` spans."""
    engine, tracer = traced_run
    path = tracer.export(tmp_path / "trace.json")
    doc = json.loads(path.read_text())

    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert ENGINE_TRACK in names and SCHEDULER_TRACK in names
    assert any(n.startswith("slot-") for n in names)
    sort_idx = {e["tid"] for e in meta if e["name"] == "thread_sort_index"}
    named = {e["tid"] for e in meta if e["name"] == "thread_name"}
    assert sort_idx == named  # every track is both named and ordered
    tid_name = {
        e["tid"]: e["args"]["name"] for e in meta
        if e["name"] == "thread_name"
    }

    spans = [e for e in evs if e["ph"] == "X"]
    assert spans, "a traced serving run must produce spans"
    span_names = {e["name"] for e in spans}
    for expected in ("queued", "prefill", "decode", "dispatch", "sync",
                     "step"):
        assert expected in span_names, f"missing lifecycle span {expected}"
    for e in spans:
        assert e["pid"] == 1
        assert e["ts"] >= 0 and e["dur"] >= 0
    for e in evs:
        if e["ph"] == "i":
            assert e["s"] == "t"
    # request ids correlate spans with logs/metrics
    assert any(
        "req_id" in (e.get("args") or {}) for e in spans
    )

    # nesting check (stack of end-times) per engine/slot track
    eps = 0.5  # µs slack for the 3-decimal rounding in the exporter
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    checked = 0
    for tid, track_spans in by_tid.items():
        name = tid_name[tid]
        if not (name == ENGINE_TRACK or name.startswith("slot-")):
            continue
        checked += 1
        stack = []  # end timestamps of open spans
        for e in sorted(track_spans, key=lambda e: (e["ts"], -e["dur"])):
            start, end = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1] <= start + eps:
                stack.pop()
            if stack:
                assert end <= stack[-1] + eps, (
                    f"span {e['name']!r} on {name} overlaps its "
                    f"enclosing span partially"
                )
            stack.append(end)
    assert checked >= 2  # engine + at least one slot track


def test_trace_counters_and_clear(traced_run):
    engine, tracer = traced_run
    evs = tracer.chrome_trace()["traceEvents"]
    counters = [e for e in evs if e["ph"] == "C"]
    assert {"queue_depth", "kv_slots_active"} <= {e["name"] for e in counters}
    for e in counters:
        (k, v), = e["args"].items()
        assert isinstance(v, float)
    t = Tracer(enabled=True, capacity=4)
    t.instant("x", "y")
    t.clear()
    assert t.n_events == 0 and t.dropped == 0


# -- serving metrics: prometheus + phase breakdown -----------------------

_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})? (?:[-+0-9.eE]+|\+Inf|NaN))$"
)

#: metric families the serving dashboards scrape
_REQUIRED_FAMILIES = (
    "serve_requests_total",
    "serve_tokens_generated_total",
    "serve_engine_steps_total",
    "serve_retries_total",
    "serve_restarts_total",
    "serve_backpressure_total",
    "serve_queue_depth",
    "serve_kv_slots",
    "serve_kv_slots_active",
    "serve_kv_occupancy",
    "serve_kv_cache_bytes",
    "serve_ttft_seconds",
    "serve_tpot_seconds",
    "serve_phase_seconds",
)


def test_prometheus_text_parses_and_is_complete(traced_run):
    engine, _ = traced_run
    text = engine.metrics.render_prometheus()
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"unparseable exposition line {line!r}"
    for fam in _REQUIRED_FAMILIES:
        assert f"# TYPE {fam} " in text, f"missing family {fam}"
    assert 'serve_requests_total{outcome="finished"} 8' in text
    assert "# TYPE serve_ttft_seconds histogram" in text
    assert 'serve_phase_seconds_bucket{phase="decode",le="+Inf"}' in text
    # histogram invariants: cumulative buckets are monotone, +Inf==count
    for fam in ("serve_ttft_seconds", "serve_tpot_seconds"):
        cum = [
            int(m.group(1)) for m in re.finditer(
                rf'{fam}_bucket{{le="[^"]+"}} (\d+)', text
            )
        ]
        assert cum == sorted(cum) and cum
        count = int(re.search(rf"{fam}_count (\d+)", text).group(1))
        assert cum[-1] == count


def test_phase_breakdown_in_summary(traced_run):
    engine, _ = traced_run
    s = engine.metrics.summary()
    assert set(s["phase_seconds"]) == set(PHASES)
    assert set(s["phase_frac"]) == set(PHASES)
    assert s["phase_seconds"]["decode"] > 0
    assert s["phase_seconds"]["prefill"] > 0
    for v in s["phase_frac"].values():
        assert 0.0 <= v <= 1.0
    # fractions are shares of ATTRIBUTED time; they sum to ~1
    assert sum(s["phase_frac"].values()) == pytest.approx(1.0, abs=0.01)
    assert s["decode_horizon"] == 2
    # the share of a horizon's decode interval the host did not wait out
    assert s["dispatch_overlap_frac"] == pytest.approx(
        1.0 - s["phase_seconds"]["sync"] / s["phase_seconds"]["decode"],
        abs=1e-4)
    assert 0.0 <= s["dispatch_overlap_frac"] <= 1.0


def test_metrics_reservoirs_are_bounded():
    m = ServingMetrics(reservoir_cap=16)
    for i in range(1000):
        m.record_step(n_active=i % 3, n_slots=2, queue_depth=i % 7)
    assert len(m.occupancy.values) == 16
    assert m.occupancy.n == 1000
    # exact extremes outlive the sample (the queue's depth has no
    # reservoir: its live value is the serve_queue_depth gauge)
    assert m.occupancy.max == 2.0
    assert not math.isinf(m.occupancy.min)


# -- profiling trigger ---------------------------------------------------


def test_profile_trigger_step_scoped_capture(tmp_path):
    trig = ProfileTrigger(log_dir=tmp_path)
    assert not trig.armed
    d = trig.arm(2)
    assert trig.armed
    with pytest.raises(RuntimeError):  # one capture at a time
        trig.arm(1)
    for _ in range(3):
        trig.step_start()
        jax.block_until_ready(jax.numpy.ones(8) * 2)
        trig.step_end()
    assert not trig.armed
    assert trig.n_captures == 1
    assert d.exists() and any(d.rglob("*")), "no XLA capture written"
    # disarmed hooks are no-ops
    trig.step_start()
    trig.step_end()
    assert trig.n_captures == 1
    with pytest.raises(ValueError):
        trig.arm(0)


# -- structured logs -----------------------------------------------------


def test_json_logs_correlate_by_req_id():
    buf = StringIO()
    pkg = logging.getLogger("deeplearning4j_tpu")
    old_level = pkg.level
    handler = configure_json_logging(level=logging.DEBUG, stream=buf)
    try:
        engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
        for r in _requests(3, seed=9):
            engine.submit(r)
        engine.run()
    finally:
        pkg.removeHandler(handler)
        pkg.setLevel(old_level)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert lines, "a logged serving run must emit records"
    recs = [json.loads(ln) for ln in lines]  # every line is one JSON obj
    for r in recs:
        assert {"ts", "level", "logger", "event"} <= set(r)
    by_req = {}
    for r in recs:
        if "req_id" in r:
            by_req.setdefault(r["req_id"], set()).add(r["event"])
    assert len(by_req) == 3
    for events in by_req.values():  # submit->admit->retire, one req_id
        assert {"request_admitted", "request_retired"} <= events


# -- training spans ------------------------------------------------------


def test_training_orchestrator_spans():
    from deeplearning4j_tpu.datasets import ListDataSetIterator, fetchers
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.models.multilayer import TRAIN_TRACK
    from deeplearning4j_tpu.nn import conf as C

    base = C.LayerConfig(
        activation="tanh", lr=0.1, num_iterations=2,
        optimization_algo=C.OptimizationAlgorithm.GRADIENT_DESCENT,
    )
    mc = C.list_builder(base, sizes=[6], n_in=4, n_out=3,
                        hidden_layer_type="dense")
    mc.pretrain = False
    mc.backward = True
    tracer = Tracer(enabled=True)
    net = MultiLayerNetwork(mc, seed=1, tracer=tracer)
    net.init()
    ds = fetchers.iris().normalize_zero_mean_unit_variance()
    net.fit(ListDataSetIterator(ds, 150))

    evs = tracer.chrome_trace()["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert all(e["cat"] == TRAIN_TRACK for e in spans)
    names = {e["name"] for e in spans}
    assert {"fit", "finetune", "finetune_batch"} <= names
    # fit encloses everything else on the track
    fit = next(e for e in spans if e["name"] == "fit")
    for e in spans:
        assert e["ts"] >= fit["ts"] - 0.5
        assert e["ts"] + e["dur"] <= fit["ts"] + fit["dur"] + 0.5

    # default-constructed network: tracing off, zero events
    net2 = MultiLayerNetwork(mc, seed=1)
    assert not net2.tracer.enabled


# -- server endpoints ----------------------------------------------------


def test_server_metrics_sidecar_and_profile_endpoint(tmp_path):
    import urllib.error
    import urllib.request

    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        profile=ProfileTrigger(log_dir=tmp_path),
    )
    srv = ServingServer(engine, port=0, metrics_port=0).start()

    def get(base, path):
        with urllib.request.urlopen(f"{base}{path}", timeout=10) as r:
            return r.status, r.headers.get("Content-Type"), r.read().decode()

    def post(base, path):
        try:
            with urllib.request.urlopen(
                urllib.request.Request(f"{base}{path}", data=b""),
                timeout=10,
            ) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        host, port = srv.address
        mhost, mport = srv.metrics_address
        assert mport != port
        main = f"http://{host}:{port}"
        side = f"http://{mhost}:{mport}"

        # the sidecar serves the same scrape surface as the main port
        for base in (main, side):
            code, ctype, text = get(base, "/metrics")
            assert code == 200 and "version=0.0.4" in ctype
            assert "# TYPE serve_queue_depth gauge" in text
            assert "serve_engine_alive 1" in text
            assert "serve_draining 0" in text
        code, _, text = get(side, "/healthz")
        assert code == 200

        # an idle loop steps every few milliseconds, so a two-step
        # capture can be over before a second POST arrives: hold its
        # end until "already armed" has been seen
        trigger = engine.profile
        trigger.step_end = lambda: None
        try:
            code, body = post(main, "/profile?s=2")
            assert code == 200 and body["armed"] == 2
            code, body = post(main, "/profile?s=1")
            assert code == 409  # already armed
        finally:
            del trigger.step_end
        code, body = post(main, "/profile?s=0")
        assert code == 400
        # and let the capture end before the loop does: the XLA
        # profiler is one a process
        deadline = time.monotonic() + 30
        while trigger.armed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not trigger.armed
    finally:
        srv.stop()

    # a server whose engine has no trigger refuses politely
    engine2 = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
    srv2 = ServingServer(engine2, port=0).start()
    try:
        host, port = srv2.address
        code, body = post(f"http://{host}:{port}", "/profile?s=1")
        assert code == 503
    finally:
        srv2.stop()
