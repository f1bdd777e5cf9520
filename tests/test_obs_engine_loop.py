"""The engine loop measured inside the program: phase regions (profiler
annotation + exact seconds + ring span from one helper), live cache rows
against streamed rows, the time-to-first-token split, and the compile
log with its warm latch."""

import dataclasses
import logging
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.obs import ProfileTrigger, Tracer, compile_log
from deeplearning4j_tpu.ops.pallas_kernels import decode_block_rows
from deeplearning4j_tpu.obs.trace import ENGINE_TRACK, PhaseRegions
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.serving.metrics import LOOP_PHASES, TTFT_SEGMENTS

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64
)
_PARAMS = {}


def _params():
    if "p" not in _PARAMS:
        _PARAMS["p"] = init_transformer(jax.random.key(0), CFG)
    return _PARAMS["p"]


def _engine(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("decode_horizon", 2)
    return ServingEngine(CFG, _params(), temperature=0.0,
                         batch_admission=False, **kw)


def _request(n_prompt, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return Request(
        prompt=rng.integers(1, CFG.vocab_size, (n_prompt,)).astype(np.int32),
        max_new=max_new, done=threading.Event(),
    )


def _serve(engine, reqs):
    for r in reqs:
        engine.submit(r)
    for _ in range(1000):
        if not engine.step() and all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("the engine did not finish its requests")


# -- the region helper -------------------------------------------------------


def test_region_adds_self_seconds_and_no_ring_event_when_tracer_off():
    tracer = Tracer(enabled=False)
    totals = {}
    regions = PhaseRegions("loop", totals, tracer, ENGINE_TRACK)
    t0 = time.perf_counter()
    with regions("outer", n=1):
        time.sleep(0.02)
        with regions("inner", req_id="r-1"):
            time.sleep(0.03)
    regions.flush(True)
    wall = time.perf_counter() - t0
    assert tracer.n_events == 0  # disabled stays free of ring work
    assert totals["inner"] >= 0.03
    # self time: the outer phase does not hold the inner one's seconds
    assert 0.02 <= totals["outer"] <= wall - totals["inner"]


def test_region_ring_spans_wait_for_flush():
    tracer = Tracer(enabled=True)
    regions = PhaseRegions("loop", {}, tracer, ENGINE_TRACK)
    with regions("dispatch", n=3):
        pass
    regions.flush(False)  # an idle turn: its spans are dropped
    assert tracer.n_events == 0
    with regions("dispatch", n=4):
        pass
    regions.flush(True)
    (ev,) = [e for e in tracer.chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert ev["name"] == "dispatch" and ev["cat"] == ENGINE_TRACK
    assert ev["args"] == {"n": 4}


def test_region_tells_the_outermost_phase_and_survives_a_raise():
    seen = []
    regions = PhaseRegions("loop", {}, Tracer(enabled=False), ENGINE_TRACK,
                           on_phase=seen.append)
    with pytest.raises(RuntimeError):
        with regions("admit"):
            with regions("prefill"):
                raise RuntimeError("boom")
    with regions("dispatch"):
        pass
    # the nested region never renames the phase; every exit clears it
    assert seen == ["admit", None, "dispatch", None]


# -- the engine's phases -----------------------------------------------------


def test_engine_loop_seconds_fill_every_phase_within_wall_time():
    engine = _engine(tracer=Tracer(enabled=False))
    t0 = time.perf_counter()
    _serve(engine, [_request(5, 6, seed=s) for s in range(3)])
    wall = time.perf_counter() - t0
    loop = engine.metrics.loop_seconds
    assert set(loop) == set(LOOP_PHASES)
    assert all(loop[p] > 0 for p in LOOP_PHASES), loop
    assert sum(loop.values()) <= wall
    assert engine.tracer.n_events == 0
    assert engine.metrics.summary()["loop_seconds"].keys() == loop.keys()


def test_profiler_capture_holds_engine_phase_annotations(tmp_path):
    """Any profiler capture of a running engine shows the loop's phases
    on the host plane of the same .xplane.pb as the executed ops."""
    from jax.profiler import ProfileData

    profile = ProfileTrigger(log_dir=tmp_path)
    engine = _engine(profile=profile)
    _serve(engine, [_request(5, 4)])  # compile outside the capture
    capture = profile.arm(6)
    _serve(engine, [_request(5, 6, seed=1), _request(7, 6, seed=2)])
    for _ in range(6):  # idle turns spend what is left of the budget
        engine.step()
    assert profile.n_captures == 1
    (path,) = capture.glob("plugins/profile/*/*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    assert host, "no host plane in the capture"
    events = {}
    for line in host[0].lines:
        for e in line.events:
            if e.name.startswith("engine."):
                events.setdefault(e.name, []).append(dict(e.stats))
    assert {"engine.sweep", "engine.admit", "engine.prefill",
            "engine.key_sync", "engine.dispatch", "engine.sync",
            "engine.process"} <= set(events)
    # the horizon number and the request id ride as arguments
    synced = {st["n"] for st in events["engine.sync"]}
    assert synced and synced <= {st["n"] for st in events["engine.dispatch"]}
    assert all("req_id" in st for st in events["engine.prefill"])


def test_horizon_number_links_decode_span_to_its_dispatch():
    tracer = Tracer(enabled=True)
    engine = _engine(tracer=tracer)
    _serve(engine, [_request(5, 6, seed=s) for s in range(3)])
    evs = tracer.chrome_trace()["traceEvents"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    dispatched = {e["args"]["n"]: e for e in by_name["dispatch"]}
    assert set(dispatched) >= {e["args"]["n"] for e in by_name["sync"]}
    assert ({e["args"]["n"] for e in by_name["sync"]}
            == {e["args"]["n"] for e in by_name["process"]})
    decodes = [e for e in by_name["decode"] if e["cat"].startswith("slot-")]
    assert decodes
    for e in decodes:
        cause = dispatched[e["args"]["n"]]
        # the decode span starts when its horizon's dispatch returned
        assert cause["ts"] <= e["ts"] <= cause["ts"] + cause["dur"] + 1.0
    firsts = [e for e in by_name["first_token"]]
    assert len(firsts) == 3
    assert all(e["args"]["n"] in dispatched for e in firsts)


def test_slot_key_readback_is_a_wait_of_its_own_inside_admit():
    """The readback of a new slot's sampling key waits for the device
    (the split queues behind the horizon in flight): one ``key_sync``
    region per admission, inside ``admit``, whose self seconds it does
    not count towards."""
    tracer = Tracer(enabled=True)
    engine = _engine(tracer=tracer)
    reqs = [_request(5, 6, seed=s) for s in range(3)]
    wait = 0.02
    read_key = engine._split_slot_key

    def slow_key(req_id):  # the wait, made long enough to tell apart
        with engine._regions("key_sync", req_id=req_id):
            time.sleep(wait)
        return read_key(req_id)

    engine._split_slot_key = slow_key
    _serve(engine, reqs)
    loop = engine.metrics.loop_seconds
    assert loop["key_sync"] >= wait * len(reqs)
    assert loop["admit"] < wait  # the wait is not admit's own time
    evs = [e for e in tracer.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    keys = [e for e in evs if e["name"] == "key_sync"]
    admits = [e for e in evs if e["name"] == "admit"]
    assert ({e["args"]["req_id"] for e in keys} == {r.id for r in reqs})
    for e in keys:
        assert any(a["ts"] <= e["ts"] and
                   e["ts"] + e["dur"] <= a["ts"] + a["dur"] + 1.0
                   for a in admits)


# -- live rows against streamed rows ----------------------------------------


def _rows_read(cfg, tpad, held):
    """The hand count: a live substep reads its slot's rows rounded up
    to the kernel's block."""
    block = decode_block_rows(
        tpad, cfg.kv_heads * cfg.head_dim,
        jnp.dtype(cfg.compute_dtype).itemsize,
    )
    return -(-held // block) * block


def test_kv_rows_equal_a_hand_count_over_three_horizons():
    """Four slots, K=2, three requests decoding 6 tokens each (three
    horizons): per substep a slot holds its prompt, what was decoded
    before and the row the substep writes; the decode kernel reads
    those rows rounded up to its block, and nothing for the free fourth
    slot."""
    engine = _engine(n_slots=4, decode_horizon=2)
    prompts = (5, 9, 12)
    _serve(engine, [_request(n, 6, seed=n) for n in prompts])
    m = engine.metrics
    # all three are admitted at the first boundary and decode together:
    # 6 tokens at K=2 are 3 horizons of 2 substeps, and a fourth was in
    # flight when the third's readback showed them finished. Its slots
    # are frozen on the device: nobody holds a row and the kernel reads
    # none.
    assert m.summary()["steps"] == 4
    held = [n + j + 1 for n in prompts for j in range(6)]
    assert m.kv_rows_live == sum(held)
    assert m.kv_rows_streamed == sum(
        _rows_read(CFG, engine.pool.tpad, h) for h in held
    )
    assert m.summary()["kv_rows_live"] == m.kv_rows_live
    text = m.render_prometheus()
    assert f"serve_kv_rows_live_total {m.kv_rows_live}" in text
    assert f"serve_kv_rows_streamed_total {m.kv_rows_streamed}" in text


def test_kv_rows_streamed_round_up_to_the_block_where_a_slab_has_several():
    """A geometry whose slab the block rule cuts in several blocks (512
    rows x 512 wide, float32): a request that crosses a block edge while
    it decodes reads one block more after it than before, the short one
    beside it reads one block throughout, and the free third slot reads
    nothing."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=512, n_heads=4, n_layers=1, d_ff=64,
        max_len=512,
    )
    engine = ServingEngine(
        cfg, init_transformer(jax.random.key(1), cfg), temperature=0.0,
        batch_admission=False, n_slots=3, decode_horizon=2,
    )
    tpad = engine.pool.tpad
    block = decode_block_rows(tpad, 512, 4)
    assert 16 < block <= tpad // 2
    # 4 of its 6 substeps end at or before row 2 * block, 2 after it
    long_prompt = 2 * block - 4
    rng = np.random.default_rng(7)
    reqs = [
        Request(prompt=rng.integers(1, 64, (n,)).astype(np.int32),
                max_new=6, done=threading.Event())
        for n in (long_prompt, 9)
    ]
    _serve(engine, reqs)
    m = engine.metrics
    held = [n + j + 1 for n in (long_prompt, 9) for j in range(6)]
    assert m.kv_rows_live == sum(held)
    assert m.kv_rows_streamed == (4 * 2 + 2 * 3 + 6 * 1) * block
    assert m.kv_rows_streamed == sum(_rows_read(cfg, tpad, h) for h in held)


def test_kv_rows_streamed_is_every_row_without_the_decode_kernel():
    """The dense path contracts over the whole cache: every substep
    reads every row of every slot, free and frozen ones too."""
    engine = ServingEngine(
        dataclasses.replace(CFG, decode_kernel=False), _params(),
        temperature=0.0, batch_admission=False, n_slots=4,
        decode_horizon=2,
    )
    prompts = (5, 9, 12)
    _serve(engine, [_request(n, 6, seed=n) for n in prompts])
    m = engine.metrics
    assert m.kv_rows_live == sum(
        n + j + 1 for n in prompts for j in range(6)
    )
    substeps = 2 * m.summary()["steps"]
    assert m.kv_rows_streamed == substeps * 4 * engine.pool.tpad


def test_kv_rows_stop_at_the_budget_inside_a_horizon():
    """A slot whose budget ends inside a horizon is frozen on the device
    for the rest of it, and for the horizon already in flight."""
    engine = _engine(n_slots=2, decode_horizon=4)
    _serve(engine, [_request(6, 5)])  # 5 tokens: one horizon and a quarter
    assert engine.metrics.kv_rows_live == sum(6 + j + 1 for j in range(5))


# -- the time-to-first-token split -------------------------------------------


def test_ttft_segments_add_up_to_the_time_to_first_token():
    engine = _engine()
    reqs = [_request(5, 4, seed=s) for s in range(4)]
    _serve(engine, reqs)
    m = engine.metrics
    assert m.n_ttft_segments == len(reqs)
    seg = m.ttft_segment_seconds
    assert set(seg) == set(TTFT_SEGMENTS)
    assert all(v >= 0 for v in seg.values())
    # the first three segments end at the readback, where TTFT is stamped
    to_readback = sum(seg[s] for s in TTFT_SEGMENTS[:3])
    assert to_readback == pytest.approx(m.ttft.total, rel=1e-6)
    assert m.summary()["ttft_segments"]["n"] == len(reqs)


# -- the compile log and the warm latch --------------------------------------


def test_compile_log_counts_a_fresh_jit_by_name():
    log = compile_log.install()
    assert compile_log.install() is log  # process-wide, idempotent

    def never_compiled_before(x):
        return x * 3 + 1

    x = jnp.ones(7)  # made before the snapshot: its fill compiles too
    before = log.totals()
    n0 = log.snapshot()[0]
    jax.jit(never_compiled_before)(x).block_until_ready()
    after = log.totals()
    assert after["requests"] == before["requests"] + 1
    assert log.names_since(n0) == ["never_compiled_before"]
    per = after["by_fun"]["never_compiled_before"]
    assert per["requests"] == 1
    assert 0 < per["seconds"] <= after["seconds"] - before["seconds"] + 1e-9
    assert all(v > 0 for v in after["stage_seconds"].values())
    assert after["seconds"] == pytest.approx(
        sum(after["stage_seconds"].values()))
    assert after["seconds"] > before["seconds"]


def test_compile_log_counts_nested_stages_once():
    """A jitted function that calls jitted functions traces them inside
    its own tracing: the log's seconds are the outermost stage's."""
    log = compile_log.install()

    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) @ x

    def outer_fn(x):
        for _ in range(8):
            x = inner_fn(x)
        return x

    x = jnp.eye(16)
    t0 = time.perf_counter()
    before = log.totals()["seconds"]
    jax.jit(outer_fn)(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = log.totals()
    assert after["seconds"] - before <= wall
    assert "inner_fn" not in after["by_fun"]
    assert after["by_fun"]["outer_fn"]["requests"] == 1


def test_recompiles_rise_only_after_the_warm_latch(caplog):
    engine = _engine()
    _serve(engine, [_request(5, 4)])  # bucket 8 and the step compile
    assert engine.metrics.recompiles == {}
    assert "serve_recompiles_total{" not in engine.metrics.render_prometheus()
    engine.mark_warm()
    _serve(engine, [_request(6, 4, seed=1)])  # bucket 8 again: warm
    assert engine.metrics.recompiles == {}
    with caplog.at_level(logging.WARNING,
                         logger="deeplearning4j_tpu.serving.engine"):
        _serve(engine, [_request(13, 4, seed=2)])  # bucket 16: a compile
    assert engine.metrics.recompiles.get("prefill", 0) >= 1
    lines = [r for r in caplog.records if r.getMessage() == "recompile"]
    assert any(r.fun == "prefill" for r in lines)
    text = engine.metrics.render_prometheus()
    assert 'serve_recompiles_total{fun="prefill"}' in text
    assert engine.metrics.summary()["compile"]["recompiles"]["prefill"] >= 1


def test_compile_log_keeps_a_bounded_tail_of_names():
    log = compile_log.CompileLog()  # not installed: fed by hand
    backend = "/jax/core/compile/backend_compile_duration"
    n = compile_log.NAMES_KEPT + 44
    for i in range(n):
        log._begun(backend, 0)
        log._duration(backend, 0.001, fun_name=f"jit(f{i})")
    assert log.requests == n and len(log.names) == compile_log.NAMES_KEPT
    assert log.names_since(n - 3) == [f"f{n - 3}", f"f{n - 2}", f"f{n - 1}"]
    assert log.names_since(n) == []
    assert len(log.names_since(0)) == compile_log.NAMES_KEPT


def test_metrics_carry_the_loop_and_compile_series_and_no_utilization():
    engine = _engine()
    _serve(engine, [_request(5, 4)])
    text = engine.metrics.render_prometheus()
    for phase in LOOP_PHASES:
        assert f'serve_loop_seconds_total{{phase="{phase}"}}' in text
    assert 'serve_compile_requests_total{fun="step"}' in text
    assert 'serve_compile_seconds_total{stage="backend"}' in text
    assert 'serve_compile_cache_total{result="hit"}' in text
    # no utilization gauge, no host-clock seconds per program family
    assert not re.search(r"serve_m[fb]u|program_seconds", text)
    s = engine.metrics.summary()
    assert s["compile"]["requests"] >= 2
    assert set(s["compile"]["stage_seconds"]) == {"trace", "lower", "backend"}
    assert "program_seconds" not in s


def _lora_bank(cfg):
    from deeplearning4j_tpu.models.transformer import init_lora_bank

    return init_lora_bank(jax.random.key(1), cfg, n_adapters=2, rank=2)


@pytest.mark.parametrize("how,cfg,kw", [
    ("kernel", CFG, {}),
    ("kernel", CFG, {"paged": True, "block_size": 8}),
    ("xla", dataclasses.replace(CFG, decode_int8=True), {}),
    ("xla", dataclasses.replace(CFG, decode_kernel=False), {}),
    ("xla", CFG, {"lora_bank": _lora_bank}),
], ids=["walk", "walk-paged", "int8", "dense", "lora-bank"])
def test_engine_reports_who_places_the_new_cache_rows(how, cfg, kw):
    """A fact fixed when the step programs are traced: the walk kernel
    writes the row it reads; the int8 slab, the dense path and a LoRA
    bank (which forces the dense path) leave the write to XLA."""
    kw = {k: v(cfg) if callable(v) else v for k, v in kw.items()}
    engine = ServingEngine(cfg, _params(), n_slots=2, temperature=0.0,
                           batch_admission=False, **kw)
    assert engine.metrics.summary()["kv_row_write"] == how
    text = engine.metrics.render_prometheus()
    assert f'serve_kv_row_write{{how="{how}"}} 1' in text
    other = "xla" if how == "kernel" else "kernel"
    assert f'serve_kv_row_write{{how="{other}"}}' not in text
