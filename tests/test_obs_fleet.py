"""Fleet observability: distributed tracing, per-family dispatch
counts, and the crash flight recorder.

The tentpole contract pinned here: one request through the router to a
replica produces, after ``trace-merge``, a single Perfetto document in
which the router's dispatch span is the PARENT of the replica's
admission span — verified structurally (the replica span's
``parent_span_id`` resolves to the router span's ``span_id`` on a
different process track, and a flow arrow links the two). Plus the
satellite contracts: traffic dispatches are counted per program family
and replays are not, flight-recorder dumps never contain
prompt text, and the disabled paths cost nothing.
"""

import json
import threading

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.obs import (
    FlightRecorder,
    Tracer,
    format_traceparent,
    merge_traces,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    redact,
)
from deeplearning4j_tpu.serving import (
    FaultInjector,
    Request,
    ServingEngine,
    ServingServer,
)
from deeplearning4j_tpu.serving.router import ReplicaRouter

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)
_PARAMS = {}


def _params(seed=0):
    if seed not in _PARAMS:
        _PARAMS[seed] = init_transformer(jax.random.key(seed), CFG)
    return _PARAMS[seed]


# -- trace context --------------------------------------------------------


def test_traceparent_roundtrip():
    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    header = format_traceparent(tid, sid)
    assert parse_traceparent(header) == (tid, sid)
    # case-insensitive per spec, surrounding whitespace tolerated
    assert parse_traceparent(" " + header.upper() + " ") == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, "", "junk", "00-" + "g" * 32 + "-" + "1" * 16 + "-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
    "00-" + "a" * 31 + "-" + "1" * 16 + "-01",  # short trace id
])
def test_traceparent_rejects_invalid(bad):
    assert parse_traceparent(bad) is None


# -- cross-process merge --------------------------------------------------


def _spans(doc):
    return [e for e in doc["traceEvents"] if e.get("ph") == "X"]


def test_merge_traces_synthetic_structure():
    """Three synthetic per-process exports merge into one document:
    one pid per input, process_name metadata preserved, timestamps
    rebased onto a shared origin, and flow arrows synthesized for
    exactly the cross-process parent links."""
    trace_id = new_trace_id()
    router = Tracer(process_name="router")
    d1, d2 = new_span_id(), new_span_id()
    router.span("router", "dispatch", router.now(), 0.001,
                trace_id=trace_id, span_id=d1)
    router.span("router", "dispatch", router.now(), 0.001,
                trace_id=trace_id, span_id=d2)
    reps = []
    for i, parent in enumerate((d1, d2)):
        t = Tracer(process_name=f"serve-{i}")
        child = new_span_id()
        t.span("slot-0", "prefill", t.now(), 0.002, trace_id=trace_id,
               span_id=child, parent_span_id=parent)
        # in-process child: nesting shows it, no arrow synthesized
        t.span("slot-0", "decode", t.now(), 0.001, trace_id=trace_id,
               span_id=new_span_id(), parent_span_id=child)
        reps.append(t)

    merged = merge_traces(
        [router.chrome_trace()] + [t.chrome_trace() for t in reps])
    evs = merged["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert len(pids) == 3
    names = {e["args"]["name"] for e in evs
             if e.get("name") == "process_name"}
    assert names == {"router", "serve-0", "serve-1"}
    assert all(e["ts"] >= 0 for e in evs if e.get("ph") == "X")

    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    # two cross-process links (one per replica), NOT the in-process one
    assert len(starts) == len(finishes) == 2
    router_pid = next(e["pid"] for e in evs
                      if e.get("name") == "process_name"
                      and e["args"]["name"] == "router")
    for s in starts:
        assert s["pid"] == router_pid
        f = next(f for f in finishes if f["id"] == s["id"])
        assert f["pid"] != router_pid
        assert f["bp"] == "e"
    # the merged doc is valid JSON end to end
    json.dumps(merged)


def _post(addr, body, headers=None, timeout=60):
    import http.client

    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        h = {"Content-Type": "application/json"}
        h.update(headers or {})
        conn.request("POST", "/v1/generate",
                     body=json.dumps(body).encode(), headers=h)
        r = conn.getresponse()
        return r.status, json.loads(r.read()), r.getheader("X-Served-By")
    finally:
        conn.close()


def _get_json(addr, path, timeout=10):
    import http.client

    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def test_fleet_merged_trace_router_parents_admission():
    """The tentpole, live: router + 2 traced replicas over real HTTP.
    The merged trace has >= 3 process tracks, and every replica
    admission span's parent resolves to a router dispatch span on the
    router's track (cross-process), with a flow arrow between them."""
    servers, tracers = [], []
    for i in range(2):
        tr = Tracer(process_name=f"serve-{i}")
        eng = ServingEngine(
            CFG, _params(), n_slots=2, temperature=0.0,
            decode_horizon=2, tracer=tr,
            retry_backoff_s=0.001, max_backoff_s=0.004,
        )
        tracers.append(tr)
        servers.append(ServingServer(eng, port=0).start())
    rtr_tracer = Tracer(process_name="router")
    router = ReplicaRouter(
        [s.address for s in servers], health_interval_s=0.1,
        tracer=rtr_tracer,
    ).start()
    caller_trace = new_trace_id()
    try:
        rng = np.random.default_rng(3)
        for i in range(4):
            prompt = [int(t) for t in rng.integers(1, 60, 5 + i)]
            headers = None
            if i == 0:  # one request arrives with upstream context
                headers = {"traceparent": format_traceparent(
                    caller_trace, new_span_id())}
            status, body, served_by = _post(
                router.address, {"prompt": prompt, "max_new": 3},
                headers=headers)
            assert status == 200, body
            assert served_by is not None
    finally:
        router.stop()
        for s in servers:
            s.stop()

    docs = [rtr_tracer.chrome_trace()] + [
        t.chrome_trace() for t in tracers]
    merged = merge_traces(docs)
    evs = merged["traceEvents"]
    assert len({e["pid"] for e in evs}) >= 3

    dispatches = {
        e["args"]["span_id"]: e for e in evs
        if e.get("ph") == "X" and e["name"] == "dispatch"
        and "span_id" in e.get("args", {})
    }
    admissions = [
        e for e in evs
        if e.get("ph") == "X" and e["name"] == "prefill"
        and e.get("args", {}).get("parent_span_id")
    ]
    assert len(dispatches) == 4
    assert len(admissions) == 4
    for adm in admissions:
        parent = dispatches[adm["args"]["parent_span_id"]]
        assert parent["pid"] != adm["pid"]  # cross-process link
        assert parent["args"]["trace_id"] == adm["args"]["trace_id"]
    # the upstream traceparent was adopted end to end
    assert any(a["args"]["trace_id"] == caller_trace
               for a in admissions)
    # every resolved link got its flow arrow
    assert sum(1 for e in evs if e.get("ph") == "s") == 4
    assert sum(1 for e in evs if e.get("ph") == "f") == 4


# -- per-family dispatch counts ---------------------------------------------


def _drive(engine, n=3, seed=11, max_new=5):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        r = Request(
            prompt=rng.integers(1, CFG.vocab_size,
                                (int(rng.integers(4, 10)),))
            .astype(np.int32),
            max_new=max_new, done=threading.Event(),
        )
        engine.submit(r)
        reqs.append(r)
    for _ in range(500):
        if not engine.step() and all(r.done.is_set() for r in reqs):
            break
    return reqs


def test_program_dispatches_counted_per_family():
    """Every traffic dispatch is counted under its compiled family, at
    the dispatch: one ``step`` per horizon the metrics counted, one
    prefill program per admission, and the same figures on /metrics."""
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0,
                           decode_horizon=2)
    _drive(engine)
    md = engine.metrics.program_dispatches
    assert md["step"] == engine.metrics.summary()["steps"] >= 1
    assert md.get("prefill", 0) + md.get("batch_prefill", 0) >= 1
    assert engine.metrics.summary()["program_dispatches"] == dict(
        sorted(md.items()))
    text = engine.metrics.render_prometheus()
    for fam, n in md.items():
        assert (f'serve_program_dispatches_total{{family="{fam}"}} {n}'
                in text)


def test_construction_counts_no_program():
    """Construction dispatches no traffic: a fresh engine has counted
    no program, and counts from its first request on."""
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0,
                           decode_horizon=2)
    assert engine._uncounted == 0
    assert engine.metrics.program_dispatches == {}
    assert "program_dispatches" not in engine.metrics.summary()
    _drive(engine, n=1)
    assert engine.metrics.program_dispatches["step"] >= 1


def test_recovery_replay_not_counted():
    """Crash-recovery replay re-dispatches prefills and steps that
    already ran; counting them again would double-count traffic, so
    recover() suspends the count for its whole replay."""
    inj = FaultInjector().plan("step", at=2, kind="crash")
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0, decode_horizon=2,
        faults=inj, retry_backoff_s=0.001, max_backoff_s=0.004,
    )
    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(2):
        r = Request(
            prompt=rng.integers(1, 60, (6,)).astype(np.int32),
            max_new=6, done=threading.Event(),
        )
        engine.submit(r)
        reqs.append(r)
    engine.run()
    assert engine.metrics.n_restarts == 1
    # the count survived the crash (re-armed after recovery) and the
    # books balance: one step per horizon dispatched as traffic, the
    # replay's none
    assert engine._uncounted == 0
    assert (engine.metrics.program_dispatches["step"]
            == engine.metrics.summary()["steps"] >= 1)


# -- flight recorder ------------------------------------------------------


def test_flight_recorder_ring_and_redaction():
    fr = FlightRecorder(capacity=4)
    for i in range(6):
        fr.record("dispatch", k=i, prompt=[1, 2, 3],
                  text="secret prompt")
    assert fr.n_events == 4  # ring bounded
    assert fr.dropped == 2
    bundle = fr.dump("test")
    raw = json.dumps(bundle)
    assert "secret prompt" not in raw
    assert "[redacted] len=3" in raw  # sized placeholder for the list
    assert bundle["n_events"] == 4 and bundle["n_dropped"] == 2


def test_redact_nested_structures():
    obj = {"a": {"tokens": (1, 2), "deep": [{"prompt": "xyz"}]},
           "keep": 7}
    out = redact(obj)
    assert out["keep"] == 7
    assert out["a"]["tokens"] == "[redacted] len=2"
    assert out["a"]["deep"][0]["prompt"] == "[redacted] len=3"


@pytest.mark.chaos
def test_flight_dump_on_chaos_crash_has_no_prompt_text(tmp_path):
    """A chaos-marker crash inside a supervised server produces a
    flight bundle on disk whose events cover the crash — with every
    prompt field redacted."""
    inj = FaultInjector().plan("step", at=1, kind="crash")
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0, decode_horizon=2,
        faults=inj, retry_backoff_s=0.001, max_backoff_s=0.004,
    )
    server = ServingServer(engine, port=0,
                           flight_dir=str(tmp_path)).start()
    try:
        marker = [7, 13, 42, 19, 23, 29]
        status, body, _ = _post(
            server.address, {"prompt": marker, "max_new": 4})
        assert status == 200, body
    finally:
        server.stop()
    bundles = list(tmp_path.glob("flight-*engine_crash*.json"))
    assert bundles, list(tmp_path.iterdir())
    doc = json.loads(bundles[0].read_text())
    assert doc["reason"] == "engine_crash"
    kinds = {e["kind"] for e in doc["events"]}
    assert {"admit", "dispatch", "fault"} <= kinds
    raw = json.dumps(doc)
    assert "[7, 13, 42" not in raw  # prompt tokens never leave
    assert all("prompt" not in e or str(e["prompt"]).startswith(
        "[redacted]") for e in doc["events"])


def test_debug_dump_endpoints_server_and_router():
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0,
                           decode_horizon=2)
    server = ServingServer(engine, port=0).start()
    router = ReplicaRouter([server.address],
                           health_interval_s=0.1).start()
    try:
        status, body, _ = _post(
            router.address, {"prompt": [3, 5, 7, 11], "max_new": 2})
        assert status == 200, body
        code, dump = _get_json(server.address, "/debug/dump")
        assert code == 200
        assert dump["reason"] == "debug_dump"
        assert {"admit", "dispatch"} <= {e["kind"] for e in dump["events"]}
        assert dump["metrics"]["n_finished"] >= 1
        code, rdump = _get_json(router.address, "/debug/dump")
        assert code == 200
        assert any(e["kind"] == "dispatch" for e in rdump["events"])
        assert rdump["replicas"]  # routing state rides along
    finally:
        router.stop()
        server.stop()


# -- disabled paths cost nothing ------------------------------------------


def test_disabled_flight_recorder_records_nothing():
    fr = FlightRecorder(enabled=False)
    for _ in range(10):
        fr.record("dispatch", k=1)
    assert fr.n_events == 0 and fr.dropped == 0
    # a dump still works (empty postmortem, never throws)
    assert fr.dump("test")["events"] == []


def test_disabled_tracer_and_flight_zero_overhead():
    """The acceptance guard: with tracing off and the flight recorder
    off, serving records no observability events at all — and the
    token streams are byte-identical to a fully instrumented
    engine's."""
    flight_off = FlightRecorder(enabled=False)
    eng_off = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0, decode_horizon=2,
        tracer=Tracer(enabled=False), flight=flight_off,
    )
    reqs_off = _drive(eng_off)
    assert eng_off.tracer.n_events == 0
    assert flight_off.n_events == 0

    eng_on = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0, decode_horizon=2,
        tracer=Tracer(enabled=True),
    )
    reqs_on = _drive(eng_on)
    assert eng_on.tracer.n_events > 0
    assert eng_on.flight.n_events > 0
    for a, b in zip(reqs_off, reqs_on):
        np.testing.assert_array_equal(
            eng_off.pop_result(a.id), eng_on.pop_result(b.id))
