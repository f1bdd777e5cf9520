"""Chaos suite for the serving engine's fault-tolerance layer.

The load-bearing property mirrors ``test_serving.py``'s: byte-identical
greedy streams — but now UNDER INJECTED FAULTS. Because greedy decode
is deterministic and everything the device holds is a pure function of
host state (prompt + tokens decoded so far), a transient fault retried
at a boundary, and even a full engine crash recovered by replay
(re-prefill + teacher-forced token replay), must reproduce exactly the
streams an unfaulted engine produces. Every fault here is scripted
through :class:`FaultInjector` at pinned boundary indices, so the suite
is deterministic — no sleeps-and-hope.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.serving import (
    EngineCrash,
    FaultInjector,
    Request,
    RequestScheduler,
    RequestStatus,
    ServingEngine,
    ServingServer,
    run_request_trace,
)

pytestmark = pytest.mark.chaos

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)
_PARAMS = {}


def _params(seed=0):
    if seed not in _PARAMS:
        _PARAMS[seed] = init_transformer(jax.random.key(seed), CFG)
    return _PARAMS[seed]


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


@pytest.fixture(autouse=True)
def _lock_sanitized():
    """The whole chaos suite runs under the LockSanitizer: every
    engine/server/scheduler lock built inside a test is order- and
    lockset-tracked across the fault-injection/recovery paths, and any
    inversion or unlocked cross-thread write fails the test that
    provoked it."""
    from deeplearning4j_tpu.analysis.sanitizers import LockSanitizer

    with LockSanitizer() as san:
        yield san
    san.assert_clean()


def _clone(reqs):
    """Same prompts/budgets, fresh ids/state — for a faulted re-run."""
    return [Request(prompt=r.prompt.copy(), max_new=r.max_new) for r in reqs]


def _run_clean(reqs, n_slots=2):
    engine = ServingEngine(CFG, _params(), n_slots=n_slots, temperature=0.0)
    for r in reqs:
        engine.submit(r)
    return engine.run()


def _fast_engine(faults, n_slots=2, **kw):
    return ServingEngine(
        CFG, _params(), n_slots=n_slots, temperature=0.0, faults=faults,
        retry_backoff_s=0.001, max_backoff_s=0.004, **kw,
    )


def _assert_parity(clean_reqs, clean, faulted_reqs, faulted):
    for a, b in zip(clean_reqs, faulted_reqs):
        np.testing.assert_array_equal(clean[a.id], faulted[b.id])


# -- supervised retries + replay recovery --------------------------------


def test_transient_faults_byte_identical_parity():
    """Transient faults at step AND prefill boundaries mid-stream:
    retried with backoff, token streams byte-identical to an unfaulted
    engine, and the retries are visible in the metrics."""
    reqs = _requests(6, seed=7)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = (FaultInjector()
           .plan("step", at=2, kind="transient")
           .plan("step", at=6, kind="transient")
           .plan("prefill", at=1, kind="transient"))
    engine = _fast_engine(inj)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    _assert_parity(reqs, clean, reqs2, faulted)
    assert engine.metrics.n_retries == 3
    assert engine.metrics.n_restarts == 0
    assert all(r.status is RequestStatus.FINISHED for r in reqs2)


def test_engine_crash_recovers_via_replay_zero_dropped():
    """An engine-loop crash with slots mid-decode at mixed depths and
    requests still queued: recover() rebuilds device state by replay
    and every stream finishes byte-identical — zero dropped requests."""
    reqs = _requests(8, seed=3)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = (FaultInjector()
           .plan("step", at=5, kind="crash")
           .plan("step", at=11, kind="crash"))  # crash twice for spite
    engine = _fast_engine(inj)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    assert len(faulted) == len(clean) == len(reqs)
    _assert_parity(reqs, clean, reqs2, faulted)
    assert engine.metrics.n_restarts == 2


def test_persistent_transient_escalates_to_replay():
    """A transient fault that outlives the retry budget (no implicated
    request) escalates to EngineCrash; supervision recovers by replay
    and parity still holds."""
    reqs = _requests(4, seed=5)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan("step", at=1, kind="transient", times=4)
    engine = _fast_engine(inj, max_retries=2)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    _assert_parity(reqs, clean, reqs2, faulted)
    # retry budget burned (3 raises) + the 4th raise post-recovery is
    # retried afresh
    assert engine.metrics.n_retries == 4
    assert engine.metrics.n_restarts == 1


def test_unsupervised_crash_propagates():
    """run(max_restarts=0) surfaces the crash instead of looping."""
    engine = _fast_engine(FaultInjector().plan("step", at=0, kind="crash"))
    engine.submit(_requests(1, seed=9)[0])
    with pytest.raises(EngineCrash):
        engine.run(max_restarts=0)


# -- quarantine: only the poisoned request fails -------------------------


def test_permanent_prefill_fault_fails_only_poisoned_request():
    """A permanent fault during one request's admission prefill fails
    exactly that request (FAILED, done set, no slot leaked); everyone
    else decodes to byte-identical streams."""
    reqs = _requests(5, seed=11)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    reqs2[2].done = threading.Event()
    inj = FaultInjector().plan("prefill", at=2, kind="permanent")
    engine = _fast_engine(inj)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    poisoned = reqs2[2]  # admissions are FIFO: 3rd prefill = 3rd submit
    assert poisoned.status is RequestStatus.FAILED
    assert poisoned.done.is_set()
    assert "permanent" in poisoned.error
    assert poisoned.id not in faulted
    for a, b in zip(reqs, reqs2):
        if b is not poisoned:
            np.testing.assert_array_equal(clean[a.id], faulted[b.id])
    assert engine.metrics.n_failed == 1
    assert engine.pool.n_active == 0 and engine.pool.n_free == 2


def test_step_fault_naming_request_quarantines_it():
    """A persistent transient step fault carrying a req_id quarantines
    that request instead of crashing the engine; the rest finish."""
    reqs = _requests(3, seed=13)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan(
        "step", at=1, kind="transient", times=3, req_id=reqs2[0].id
    )
    engine = _fast_engine(inj, max_retries=2)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    assert reqs2[0].status is RequestStatus.FAILED
    assert engine.metrics.n_failed == 1
    assert engine.metrics.n_restarts == 0
    for a, b in zip(reqs[1:], reqs2[1:]):
        np.testing.assert_array_equal(clean[a.id], faulted[b.id])


# -- chaos at multi-step horizons ----------------------------------------


@pytest.mark.parametrize("horizon", [2, 4])
def test_chaos_parity_at_multi_step_horizon(horizon):
    """Transient faults AND a full crash with a fused K-substep decode
    program: the fault boundary is the horizon dispatch, recovery
    replays the recorded tokens, and streams stay byte-identical to an
    unfaulted engine — the pipelined hot path keeps the fault-tolerance
    contract."""
    reqs = _requests(6, seed=23)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = (FaultInjector()
           .plan("step", at=1, kind="transient")
           .plan("step", at=3, kind="crash"))
    engine = _fast_engine(inj, decode_horizon=horizon)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    _assert_parity(reqs, clean, reqs2, faulted)
    assert engine.metrics.n_retries == 1
    assert engine.metrics.n_restarts == 1
    assert all(r.status is RequestStatus.FINISHED for r in reqs2)


def test_sampled_crash_recovery_key_continuity():
    """Crash mid-decode at temperature > 0: each slot's sampling key is
    split at admission and persisted host-side, and token i is drawn
    with fold_in(slot_key, position) — so after replay (teacher-forced
    recorded tokens, keys re-seated) the resumed SAMPLED stream is
    byte-identical to an uninterrupted sampled run. This closes the
    key-stream-continuity gap stepwise replay alone could not (a shared
    per-dispatch key would have advanced differently)."""
    def build(faults=None):
        return ServingEngine(
            CFG, _params(), n_slots=3, temperature=0.8, top_k=8,
            rng_seed=21, faults=faults, retry_backoff_s=0.001,
            max_backoff_s=0.004,
        )

    reqs = _requests(6, seed=17)
    clean_eng = build()
    for r in reqs:
        clean_eng.submit(r)
    clean = clean_eng.run()

    for horizon_crash_at in (1, 3):
        reqs2 = _clone(reqs)
        inj = FaultInjector().plan("step", at=horizon_crash_at,
                                   kind="crash")
        engine = build(inj)
        for r in reqs2:
            engine.submit(r)
        faulted = engine.run()
        assert engine.metrics.n_restarts == 1
        assert all(r.status is RequestStatus.FINISHED for r in reqs2)
        _assert_parity(reqs, clean, reqs2, faulted)


def test_crash_with_unsynced_horizon_drops_no_tokens():
    """Crash while a dispatched horizon is still awaiting readback: its
    tokens were never recorded, so replay regenerates them — no
    duplicates, no gaps. The crash at dispatch #2 lands with dispatch
    #1's token block still in flight."""
    reqs = _requests(4, seed=29)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan("step", at=1, kind="crash")
    engine = _fast_engine(inj, decode_horizon=4)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()
    _assert_parity(reqs, clean, reqs2, faulted)
    assert engine.metrics.n_restarts == 1


def test_chunked_replay_recovery():
    """Forced chunked replay: recovery re-prefills prompt+tokens in one
    bucketed pass (O(len/bucket) device calls) instead of stepwise
    teacher-forcing. On this backend/model the prefill-path caches
    reproduce the decode trajectory's argmax choices, so the streams
    still match the clean run (the general guarantee is completion;
    ``tests/test_serving_schedules.py`` holds the two replays' logits
    to a tolerance)."""
    reqs = _requests(4, seed=31)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan("step", at=2, kind="crash")
    engine = _fast_engine(inj, chunked_replay=True)
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    assert engine.last_recover_mode == "chunked"
    assert engine.metrics.n_restarts == 1
    assert all(r.status is RequestStatus.FINISHED for r in reqs2)
    _assert_parity(reqs, clean, reqs2, faulted)


def test_default_replay_is_stepwise_and_preserves_parity():
    """Unless asked for chunked replay, recovery teacher-forces the
    recorded tokens step by step: the exact one, so the recovered
    streams are byte-identical to a clean run by construction."""
    reqs = _requests(5, seed=37)
    clean = _run_clean(reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan("step", at=3, kind="crash")
    engine = _fast_engine(inj)  # chunked_replay defaults to False
    for r in reqs2:
        engine.submit(r)
    faulted = engine.run()

    assert engine.last_recover_mode == "stepwise"
    _assert_parity(reqs, clean, reqs2, faulted)


# -- lifecycle: cancel and deadlines -------------------------------------


def test_cancel_frees_slot_within_one_step():
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=20,
                done=threading.Event())
    engine = ServingEngine(CFG, _params(), n_slots=1, temperature=0.0)
    engine.submit(r)
    engine.step()
    assert engine.pool.n_active == 1 and r.status is RequestStatus.RUNNING
    r.cancel()
    engine.step()  # the one step the contract allows
    assert engine.pool.n_active == 0
    assert r.status is RequestStatus.CANCELLED and r.done.is_set()
    assert len(engine.results[r.id]) >= len(r.prompt)  # partial stream
    assert engine.metrics.n_cancelled == 1


def test_cancel_queued_request_never_admitted():
    engine = ServingEngine(CFG, _params(), n_slots=1, temperature=0.0)
    blocker = Request(prompt=np.arange(4, dtype=np.int32), max_new=8)
    queued = Request(prompt=np.arange(5, dtype=np.int32), max_new=8,
                     done=threading.Event())
    engine.submit(blocker)
    engine.submit(queued)
    engine.step()  # blocker holds the only slot
    assert engine.cancel(queued.id)
    engine.run()
    assert queued.status is RequestStatus.CANCELLED
    assert queued.done.is_set()
    assert queued.id not in engine.results  # never admitted, no stream
    assert blocker.status is RequestStatus.FINISHED
    assert not engine.cancel("no-such-id")


def test_deadline_expiry_frees_slot_and_admits_next():
    """A running request whose deadline elapses is retired EXPIRED
    within one step and its slot is immediately reused."""
    r1 = Request(prompt=np.arange(4, dtype=np.int32), max_new=20,
                 deadline_s=30.0, done=threading.Event())
    r2 = Request(prompt=np.arange(6, dtype=np.int32), max_new=4)
    engine = ServingEngine(CFG, _params(), n_slots=1, temperature=0.0)
    engine.submit(r1)
    engine.submit(r2)
    engine.step()
    assert engine._slots[0].req is r1
    r1.arrival_time -= 100.0  # deterministically force the deadline past
    engine.step()  # sweep retires r1, admission reuses slot 0 for r2
    assert r1.status is RequestStatus.EXPIRED and r1.done.is_set()
    assert engine._slots[0] is not None and engine._slots[0].req is r2
    engine.run()
    assert r2.status is RequestStatus.FINISHED
    assert engine.metrics.n_expired == 1


def test_deadline_checked_at_admission():
    engine = ServingEngine(CFG, _params(), n_slots=1, temperature=0.0)
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=8,
                deadline_s=0.5, done=threading.Event())
    engine.submit(r)
    r.arrival_time -= 100.0
    engine.step()
    assert r.status is RequestStatus.EXPIRED and r.done.is_set()
    assert engine.pool.n_active == 0 and r.id not in engine.results


# -- satellite fixes ------------------------------------------------------


def test_run_request_trace_survives_backpressure():
    """A flooded trace against a depth-2 queue used to die on the
    Backpressure raise; now the submit retries as steps free space and
    every request completes."""
    engine = ServingEngine(
        CFG, _params(), n_slots=1, temperature=0.0,
        scheduler=RequestScheduler(max_queue_depth=2),
    )
    reqs = _requests(6, seed=17)
    trace = [(0.0, r) for r in reqs]
    results = run_request_trace(engine, trace, time_scale=0.0)
    assert set(results) == {r.id for r in reqs}
    assert all(r.status is RequestStatus.FINISHED for r in reqs)


def test_results_dict_is_bounded():
    """Sustained traffic must not grow host memory: the results dict
    evicts oldest past results_cap, and pop_result consumes."""
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0, results_cap=3,
    )
    reqs = _requests(8, seed=19)
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert len(engine.results) == 3
    assert engine.metrics.n_finished == 8  # all served, only dict bounded
    last = reqs[-1]
    assert engine.pop_result(last.id) is not None
    assert last.id not in engine.results
    assert engine.pop_result(last.id) is None


# -- server: drain, health model, timeout-cancel -------------------------


def _post(base, payload, timeout=60):
    req = urllib.request.Request(
        f"{base}/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(base, path, timeout=10):
    try:
        with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _warm_engine(**kw):
    """Engine with the step + a len-3 prefill program pre-compiled, so
    server-path tests aren't at the mercy of first-call compile time."""
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0, **kw)
    warm = Request(prompt=np.asarray([1, 5, 9], np.int32), max_new=2)
    engine.submit(warm)
    engine.run()
    engine.pop_result(warm.id)
    return engine


def test_server_drain_finishes_inflight_and_503s_new():
    engine = _warm_engine(
        faults=FaultInjector(delay_s=0.01)  # ~10ms/step: drain overlaps
    )
    srv = ServingServer(engine, port=0).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        out = {}

        def worker():
            out["resp"] = _post(base, {"prompt": [1, 5, 9], "max_new": 12})

        t = threading.Thread(target=worker)
        t.start()
        deadline = time.time() + 10
        while engine.pool.n_active == 0 and time.time() < deadline:
            time.sleep(0.005)  # wait for admission
        assert engine.pool.n_active == 1

        status, body = _get(base, "/readyz")
        assert status == 200 and body["ready"] is True

        stopper = threading.Thread(target=lambda: srv.stop(drain_s=30))
        stopper.start()
        deadline = time.time() + 10
        while not srv._draining.is_set() and time.time() < deadline:
            time.sleep(0.002)
        status, body = _post(base, {"prompt": [2, 3], "max_new": 2})
        assert status == 503 and body["error"] == "draining"
        status, body = _get(base, "/readyz")
        assert status == 503 and body["ready"] is False

        t.join(timeout=30)
        stopper.join(timeout=30)
        status, body = out["resp"]
        assert status == 200 and len(body["tokens"]) == 15  # drained, whole
    finally:
        srv.stop()


def test_server_timeout_cancels_request_and_frees_slot():
    """504 must not leave the slot decoding for a gone client: the
    handler cancels the request; the engine frees the slot within one
    step (the fault injector's delay makes the timeout deterministic)."""
    engine = _warm_engine(faults=FaultInjector(delay_s=0.05))
    srv = ServingServer(engine, port=0, request_timeout_s=0.3).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        status, body = _post(base, {"prompt": [1, 5, 9], "max_new": 25})
        assert status == 504
        deadline = time.time() + 10
        while engine.pool.n_active and time.time() < deadline:
            time.sleep(0.01)
        assert engine.pool.n_active == 0
        assert engine.metrics.n_cancelled == 1
        status, m = _get(base, "/metrics.json")
        assert m["n_cancelled"] == 1 and m["slots_active"] == 0
    finally:
        srv.stop()


def test_server_deadline_maps_to_408():
    engine = _warm_engine(faults=FaultInjector(delay_s=0.05))
    srv = ServingServer(engine, port=0).start()
    host, port = srv.address
    try:
        status, body = _post(
            f"http://{host}:{port}",
            {"prompt": [1, 5, 9], "max_new": 25, "deadline_s": 0.2},
        )
        assert status == 408 and body["status"] == "expired"
    finally:
        srv.stop()


def test_drain_deadline_preempts_stragglers():
    """stop(drain_s) with a request that cannot finish inside the
    window: at the deadline the server preempts (cancels) it instead of
    waiting it out — the handler answers 499/cancelled with the partial
    stream dropped, and shutdown converges promptly."""
    engine = _warm_engine(
        faults=FaultInjector(delay_s=0.05)  # ~50ms/step: 25 tokens >> drain
    )
    srv = ServingServer(engine, port=0).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    out = {}
    try:
        def worker():
            out["resp"] = _post(base, {"prompt": [1, 5, 9], "max_new": 25})

        t = threading.Thread(target=worker)
        t.start()
        deadline = time.time() + 10
        while engine.pool.n_active == 0 and time.time() < deadline:
            time.sleep(0.005)
        assert engine.pool.n_active == 1

        t0 = time.time()
        srv.stop(drain_s=0.3)
        # bounded shutdown: drain window + preemption grace, not the
        # ~1.5s the straggler would have needed
        assert time.time() - t0 < 5.0
        t.join(timeout=30)
        status, body = out["resp"]
        assert status == 499 and body["status"] == "cancelled"
        assert engine.metrics.n_cancelled >= 1
    finally:
        srv.stop()


def test_watchdog_flags_hung_engine():
    """An engine wedged inside a step (here: a scripted 0.5s stall per
    boundary) stops heartbeating while its thread stays alive; once the
    beat age passes hang_threshold_s with work pending, /healthz
    reports hung and flips 503 — and recovers to 200 when the engine
    comes back."""
    engine = _warm_engine(faults=FaultInjector(delay_s=0.5))
    srv = ServingServer(engine, port=0, hang_threshold_s=0.1).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        status, body = _get(base, "/healthz")
        assert status == 200 and body["hung"] is False

        out = {}

        def worker():
            out["resp"] = _post(base, {"prompt": [1, 5, 9], "max_new": 4})

        t = threading.Thread(target=worker)
        t.start()
        saw_hung = False
        deadline = time.time() + 15
        while time.time() < deadline:
            status, body = _get(base, "/healthz")
            if status == 503 and body["hung"]:
                saw_hung = True
                assert body["ok"] is False
                assert body["beat_age_s"] > srv.hang_threshold_s
                break
            time.sleep(0.01)
        assert saw_hung, "watchdog never flagged the stalled engine"
        t.join(timeout=30)
        assert out["resp"][0] == 200  # the stall was latency, not death

        deadline = time.time() + 10
        while time.time() < deadline:
            status, body = _get(base, "/healthz")
            if status == 200:
                break
            time.sleep(0.05)
        assert status == 200 and body["hung"] is False  # beat resumed
    finally:
        srv.stop()


def test_healthz_flips_on_unrecovered_engine_death():
    """Crash every step forever with a tiny restart budget: the
    supervisor gives up, fails all in-flight work (no handler blocks
    forever), and /healthz flips to 503 on the next poll."""
    inj = FaultInjector().plan("step", at=0, kind="crash", times=10**9)
    engine = _warm_engine()
    engine.faults = inj  # armed only after warmup
    srv = ServingServer(engine, port=0, max_restarts=1).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        status, body = _get(base, "/healthz")
        assert status == 200 and body["ok"] is True

        out = {}

        def worker():  # the victim that makes the engine step (and die)
            out["resp"] = _post(base, {"prompt": [1, 5, 9], "max_new": 8})

        t = threading.Thread(target=worker)
        t.start()
        deadline = time.time() + 10
        while time.time() < deadline:
            status, body = _get(base, "/healthz")
            if status == 503:
                break
            time.sleep(0.01)
        assert status == 503 and body["ok"] is False
        assert body["engine_alive"] is False
        assert "crash" in body["last_error"]
        assert body["restarts"] >= 1

        t.join(timeout=30)
        status, body = out["resp"]  # failed fast, not a 300s hang
        assert status == 500 and body["status"] == "failed"
        status, body = _post(base, {"prompt": [2], "max_new": 2})
        assert status == 503 and body["error"] == "engine dead"
    finally:
        srv.stop()
