"""Multi-tenant serving: DRR fairness, quotas, batched LoRA, streaming.

The load-bearing claims, in order of appearance:

- **Fairness** — with a ``TenantRegistry`` attached, one tenant
  flooding the queue cannot starve its classmates: deficit-round-robin
  inside the priority class interleaves the victims' requests into the
  flood, measurably earlier than FIFO would, while the token streams
  stay byte-identical (the scheduler only reorders).
- **Quota** — a tenant's token bucket rejects at submit with
  ``QuotaExceeded`` (the 429 path), refills on the injected clock, and
  never affects other tenants' admission.
- **Batched LoRA** — the tentpole parity bar: a mixed-adapter batch is
  not an approximation. Every slot's stream is byte-identical to a
  dedicated single-adapter engine serving that adapter alone — greedy,
  sampled (the slot-key design makes the key stream invariant to batch
  composition), and through crash-recovery replay — and adapter 0 is
  bitwise the base model.
- **Streaming / embeddings** — per-token streams concatenate to exactly
  the non-streamed result and survive mid-stream cancel; embedding
  requests ride the same scheduler/metrics lifecycle without a KV slot.
"""

import queue
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_lora_bank,
    init_transformer,
)
from deeplearning4j_tpu.serving import (
    EmbeddingRequest,
    FaultInjector,
    QuotaExceeded,
    Request,
    RequestScheduler,
    RequestStatus,
    ServingEngine,
    TenantConfig,
    TenantRegistry,
)

pytestmark = pytest.mark.tenancy

needs_2_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for TP/sharding"
)

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)
# the Pallas decode kernel cannot GSPMD-partition (see
# test_serving_tp.py) — the TP LoRA parity run compares dense-vs-dense
TP_CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_len=32, decode_kernel=False,
)
_PARAMS = {}
_BANKS = {}


def _params(cfg=CFG, seed=0):
    key = (id(cfg), seed)
    if key not in _PARAMS:
        _PARAMS[key] = init_transformer(jax.random.key(seed), cfg)
    return _PARAMS[key]


def _bank(cfg=CFG, n_adapters=4, rank=2, seed=1):
    key = (id(cfg), n_adapters, rank, seed)
    if key not in _BANKS:
        _BANKS[key] = init_lora_bank(
            jax.random.key(seed), cfg, n_adapters=n_adapters, rank=rank
        )
    return _BANKS[key]


def _requests(n, seed=0, tenant_id="", adapter=0, max_new=6, prompt=None):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        p = (prompt if prompt is not None
             else rng.integers(0, CFG.vocab_size,
                               (int(rng.integers(3, 10)),)).astype(np.int32))
        out.append(Request(
            prompt=np.array(p), max_new=max_new, tenant_id=tenant_id,
            adapter=adapter, done=threading.Event(),
        ))
    return out


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return {r.id: engine.pop_result(r.id) for r in reqs}


def _run_ordered(engine, reqs):
    """Drive step-by-step, recording each request's completion rank
    (ties within one step share a rank — what matters for fairness is
    which scheduling WAVE a request lands in, not intra-step order)."""
    for r in reqs:
        engine.submit(r)
    rank, ranks = 0, {}
    while not engine.idle:
        engine.step()
        newly = [r for r in reqs if r.done.is_set() and r.id not in ranks]
        if newly:
            for r in newly:
                ranks[r.id] = rank
            rank += 1
    return ranks


# -- deficit-round-robin fairness ----------------------------------------


def _flood_and_victims(tagged=True):
    """``tagged=False`` blanks the tenant ids: the DRR tier keys by
    ``tenant_id`` whether or not a registry is attached, so the honest
    FIFO baseline is untagged traffic (one implicit tenant) — exactly
    what the pre-tenancy engine saw."""
    flood = _requests(12, seed=1, tenant_id="flood" if tagged else "")
    victims = [r for v in range(3)
               for r in _requests(
                   2, seed=10 + v,
                   tenant_id=f"victim{v}" if tagged else "")]
    return flood, victims


def _fair_registry():
    return TenantRegistry(
        [TenantConfig("flood", api_key="f")]
        + [TenantConfig(f"victim{v}", api_key=f"v{v}") for v in range(3)]
    )


def test_drr_flood_does_not_starve_victims():
    """12-request flood submitted ahead of 6 victim requests, 2 slots:
    under DRR the victims' completion ranks sit measurably ahead of
    FIFO's (where they drain strictly last), streams stay identical,
    and nobody is dropped."""
    def build(fair):
        tenancy = _fair_registry() if fair else None
        return ServingEngine(
            CFG, _params(), n_slots=2, temperature=0.0,
            scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy),
            tenancy=tenancy,
        )

    flood_a, victims_a = _flood_and_victims(tagged=False)
    fifo_ranks = _run_ordered(build(fair=False), flood_a + victims_a)
    flood_b, victims_b = _flood_and_victims()
    drr_ranks = _run_ordered(build(fair=True), flood_b + victims_b)

    def mean_victim_rank(ranks, victims, total):
        return np.mean([ranks[r.id] for r in victims]) / max(ranks.values())

    fifo_pos = mean_victim_rank(fifo_ranks, victims_a, len(fifo_ranks))
    drr_pos = mean_victim_rank(drr_ranks, victims_b, len(drr_ranks))
    # FIFO: victims queue behind the whole flood (normalized rank near
    # 1); DRR: each round-robin visit serves a victim, so they land in
    # the front half of the completion order
    assert fifo_pos > 0.7, fifo_pos
    assert drr_pos < fifo_pos - 0.2, (drr_pos, fifo_pos)
    for r in flood_b + victims_b:
        assert r.status is RequestStatus.FINISHED
    # greedy decode is order-invariant: reordering must not touch bytes
    eng = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
    flood_c, victims_c = _flood_and_victims()
    clean = _run(eng, flood_c + victims_c)
    drr_eng = build(fair=True)
    flood_d, victims_d = _flood_and_victims()
    drr_out = _run(drr_eng, flood_d + victims_d)
    for a, b in zip(flood_c + victims_c, flood_d + victims_d):
        np.testing.assert_array_equal(clean[a.id], drr_out[b.id])


def test_drr_weight_biases_share():
    """weight=3 vs weight=1 under symmetric floods: the heavy tenant's
    requests complete earlier on average (DRR credit is quantum *
    weight per visit). The quantum is shrunk below one request's token
    cost and the LIGHT tenant submits first (owning the rotation
    front), so only the weight can explain heavy finishing earlier."""
    tenancy = TenantRegistry([
        TenantConfig("heavy", api_key="h", weight=3.0),
        TenantConfig("light", api_key="l", weight=1.0),
    ])
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy,
                                   drr_quantum=8),
        tenancy=tenancy,
    )
    heavy = _requests(6, seed=2, tenant_id="heavy")
    light = _requests(6, seed=3, tenant_id="light")
    mixed = [r for pair in zip(light, heavy) for r in pair]
    ranks = _run_ordered(engine, mixed)
    assert (np.mean([ranks[r.id] for r in heavy])
            < np.mean([ranks[r.id] for r in light]))


# -- token-rate quotas ---------------------------------------------------


def test_quota_429_and_refill():
    """Token bucket: burst admits, then QuotaExceeded; the injected
    clock refills at ``rate``; an unmetered tenant is untouched
    throughout; rejections land in the per-tenant metrics."""
    now = [0.0]
    tenancy = TenantRegistry(
        [
            # each request below costs 8 prompt + 8 max_new = 16 tokens
            TenantConfig("metered", api_key="m", rate=16.0, burst=32.0),
            TenantConfig("open", api_key="o"),
        ],
        clock=lambda: now[0],
    )
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy),
        tenancy=tenancy,
    )
    prompt = np.arange(8, dtype=np.int32) % CFG.vocab_size

    def req(tid):
        return Request(prompt=prompt.copy(), max_new=8, tenant_id=tid)

    ok = [engine.submit(req("metered")) for _ in range(2)]  # 32 = burst
    assert len(ok) == 2
    with pytest.raises(QuotaExceeded):
        engine.submit(req("metered"))
    # the flooder's dry bucket must not gate anyone else
    engine.submit(req("open"))
    assert tenancy.bucket_level("metered") == pytest.approx(0.0)

    now[0] += 1.0  # +16 tokens: exactly one more request
    engine.submit(req("metered"))
    with pytest.raises(QuotaExceeded):
        engine.submit(req("metered"))

    engine.run()
    s = engine.metrics.summary()
    assert s["rejections"] == {"quota": 2}
    assert s["tenants"]["metered"]["n_rejected"] == 2
    assert s["tenants"]["metered"]["n_finished"] == 3
    assert s["tenants"]["open"]["n_finished"] == 1


def test_slo_burn_gauge_from_per_tenant_p99():
    """A tenant with a p99-TPOT SLO gets a derived
    ``serve_tenant_slo_burn{tenant}`` gauge at every /metrics render
    (observed p99 / objective); tenants without an SLO, or with no
    TPOT samples yet, publish nothing."""
    tenancy = TenantRegistry([
        TenantConfig("gold", api_key="g", slo_p99_tpot_s=0.001),
        TenantConfig("free", api_key="f"),
    ])
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy),
        tenancy=tenancy,
    )
    # SLO declared but no traffic yet -> no gauge line (a 0 would read
    # as a perfect SLO with zero samples)
    assert "serve_tenant_slo_burn{" not in engine.metrics.render_prometheus()

    prompt = np.arange(8, dtype=np.int32) % CFG.vocab_size
    for tid in ("gold", "free", "gold"):
        engine.submit(Request(prompt=prompt.copy(), max_new=8,
                              tenant_id=tid))
    engine.run()

    text = engine.metrics.render_prometheus()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("serve_tenant_slo_burn{")]
    assert len(lines) == 1 and 'tenant="gold"' in lines[0]
    burn = float(lines[0].split()[-1])
    s = engine.metrics.summary()
    assert burn == pytest.approx(
        s["tenants"]["gold"]["tpot_p99_s"] / 0.001
    )
    assert s["tenants"]["gold"]["slo_burn"] == pytest.approx(burn)
    assert "slo_burn" not in s["tenants"]["free"]
    # config plumbing: from_json carries the SLO; validation rejects 0
    reg = TenantRegistry.from_json(
        [{"id": "t", "slo_p99_tpot_s": 0.25}]
    )
    assert reg.get("t").slo_p99_tpot_s == 0.25
    with pytest.raises(ValueError, match="slo_p99_tpot_s"):
        TenantConfig("bad", slo_p99_tpot_s=0.0)


def test_max_slots_caps_concurrency():
    """A max_slots=1 tenant never holds two KV slots at once even with
    the pool free, and still finishes everything."""
    tenancy = TenantRegistry([
        TenantConfig("capped", api_key="c", max_slots=1),
        TenantConfig("roomy", api_key="r"),
    ])
    engine = ServingEngine(
        CFG, _params(), n_slots=3, temperature=0.0,
        scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy),
        tenancy=tenancy,
    )
    capped = _requests(3, seed=4, tenant_id="capped")
    roomy = _requests(3, seed=5, tenant_id="roomy")
    for r in capped + roomy:
        engine.submit(r)
    peak = 0
    while not engine.idle:
        engine.step()
        held = sum(
            1 for st in engine._slots
            if st is not None and st.req.tenant_id == "capped"
        )
        peak = max(peak, held)
    assert peak == 1
    for r in capped + roomy:
        assert r.status is RequestStatus.FINISHED


# -- batched LoRA parity -------------------------------------------------


def _mixed_reqs(adapters=(1, 2, 3, 0), seed=6, max_new=6):
    """One request per adapter, all on the SAME prompt so divergent
    streams can only come from the adapter rows."""
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, CFG.vocab_size, (7,)).astype(np.int32)
    return [
        Request(prompt=prompt.copy(), max_new=max_new, adapter=a)
        for a in adapters
    ]


def _lora_engine(cfg=CFG, bank=None, tp=None, **kw):
    kw.setdefault("temperature", 0.0)
    extra = {} if tp is None else {"tp": tp}
    return ServingEngine(
        cfg, _params(cfg), n_slots=4,
        lora_bank=_bank(cfg) if bank is None else bank,
        retry_backoff_s=0.001, max_backoff_s=0.004,
        **extra, **kw,
    )


def test_lora_mixed_batch_matches_single_adapter_engines_greedy():
    """THE parity bar: each slot of a mixed-adapter greedy batch is
    byte-identical to a dedicated engine serving that adapter alone —
    and the adapters do diverge (same prompt, distinct streams)."""
    reqs = _mixed_reqs()
    mixed = _run(_lora_engine(), reqs)
    streams = [tuple(mixed[r.id]) for r in reqs]
    assert len(set(streams)) == len(streams), "adapters failed to diverge"
    for r in reqs:
        solo_req = Request(prompt=r.prompt.copy(), max_new=r.max_new,
                           adapter=r.adapter)
        solo = _run(_lora_engine(), [solo_req])
        np.testing.assert_array_equal(mixed[r.id], solo[solo_req.id])


def test_lora_mixed_batch_matches_single_adapter_engines_sampled():
    """Sampled parity: slot keys are split in admission order and the
    per-token key is fold_in(slot_key, position) — invariant to batch
    composition. A dedicated adapter-i engine fed the SAME submission
    sequence (every request pinned to adapter i, so the key schedule
    matches) reproduces the mixed batch's adapter-i stream exactly."""
    reqs = _mixed_reqs(max_new=8)
    mixed = _run(_lora_engine(temperature=1.0, top_k=8), reqs)
    for idx, r in enumerate(reqs):
        pinned = [
            Request(prompt=q.prompt.copy(), max_new=q.max_new,
                    adapter=r.adapter)
            for q in reqs
        ]
        solo = _run(_lora_engine(temperature=1.0, top_k=8), pinned)
        np.testing.assert_array_equal(mixed[r.id], solo[pinned[idx].id])


def test_lora_adapter0_is_bitwise_base_model():
    """Adapter row 0 is the zero adapter: with the bank ATTACHED, every
    adapter-0 stream is bitwise the no-bank engine's, end to end
    (``tests/test_serving_schedules.py`` compares the logits)."""
    eng = _lora_engine()
    assert eng.n_adapters == 4
    reqs = _requests(5, seed=7, adapter=0, max_new=6)
    with_bank = _run(eng, reqs)
    clones = [Request(prompt=r.prompt.copy(), max_new=r.max_new)
              for r in reqs]
    base = _run(
        ServingEngine(CFG, _params(), n_slots=4, temperature=0.0), clones
    )
    for r, c in zip(reqs, clones):
        np.testing.assert_array_equal(with_bank[r.id], base[c.id])


def test_lora_crash_recovery_parity_sampled():
    """Mixed adapters through an engine crash (sampled, the harder
    case): replay recovery re-seats slot keys AND adapter indices, so
    the recovered streams are byte-identical to an unfaulted run."""
    reqs = _mixed_reqs(max_new=8)
    clean = _run(_lora_engine(temperature=1.0, top_k=8), reqs)
    reqs2 = [Request(prompt=r.prompt.copy(), max_new=r.max_new,
                     adapter=r.adapter) for r in reqs]
    inj = FaultInjector().plan("step", at=2, kind="crash")
    engine = _lora_engine(temperature=1.0, top_k=8, faults=inj)
    faulted = _run(engine, reqs2)
    assert engine.metrics.n_restarts == 1
    for a, b in zip(reqs, reqs2):
        np.testing.assert_array_equal(clean[a.id], faulted[b.id])
        assert b.status is RequestStatus.FINISHED


@needs_2_devices
def test_lora_tp2_parity():
    """Sharding the adapter bank with the TP column layout is invisible
    in the bytes: TP=2 mixed-adapter streams == TP=1's."""
    bank = _bank(TP_CFG)
    reqs = _mixed_reqs()
    base = _run(_lora_engine(TP_CFG, bank=bank, tp=1), reqs)
    reqs2 = [Request(prompt=r.prompt.copy(), max_new=r.max_new,
                     adapter=r.adapter) for r in reqs]
    eng = _lora_engine(TP_CFG, bank=bank, tp=2)
    sharded = _run(eng, reqs2)
    assert eng.n_adapters == 4
    for a, b in zip(reqs, reqs2):
        np.testing.assert_array_equal(base[a.id], sharded[b.id])


# -- SSE token streaming -------------------------------------------------


def _drain(q, timeout=30.0):
    toks, deadline = [], time.monotonic() + timeout
    while True:
        tok = q.get(timeout=max(deadline - time.monotonic(), 0.01))
        if tok is None:
            return toks
        toks.append(tok)


def test_streaming_tokens_concatenate_to_result():
    """A streamed request's per-token queue, concatenated, is exactly
    the generated tail of the non-streamed result — and the terminal
    status is visible BEFORE the sentinel arrives."""
    engine = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
    reqs = _requests(3, seed=8, max_new=6)
    streamed = Request(prompt=reqs[0].prompt.copy(), max_new=6,
                       stream=queue.Queue())
    out = _run(engine, reqs)

    engine2 = ServingEngine(CFG, _params(), n_slots=2, temperature=0.0)
    engine2.submit(streamed)
    t = threading.Thread(target=engine2.run)
    t.start()
    toks = _drain(streamed.stream)
    assert streamed.status is RequestStatus.FINISHED  # set pre-sentinel
    t.join(timeout=30)
    np.testing.assert_array_equal(
        np.asarray(toks, np.int32), out[reqs[0].id][len(reqs[0].prompt):]
    )


def test_streaming_mid_cancel_drains_cleanly():
    """Cancel after two streamed tokens: the sentinel still arrives
    (bounded wait, no hang), status is CANCELLED, and an unrelated
    request in the same batch finishes untouched."""
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        faults=FaultInjector(delay_s=0.01),  # ~10ms/step: cancel lands
    )
    victim = Request(prompt=np.arange(5, dtype=np.int32), max_new=20,
                     stream=queue.Queue())
    bystander = _requests(1, seed=9, max_new=5)[0]
    engine.submit(victim)
    engine.submit(bystander)
    t = threading.Thread(target=engine.run)
    t.start()
    got = [victim.stream.get(timeout=30) for _ in range(2)]
    assert all(g is not None for g in got)
    assert engine.cancel(victim.id)
    rest = _drain(victim.stream)
    assert victim.status is RequestStatus.CANCELLED
    assert len(got) + len(rest) < 20
    t.join(timeout=30)
    assert bystander.status is RequestStatus.FINISHED


# -- embeddings through the serving lifecycle ----------------------------


class _StubEmbedder:
    """Minimal zoo-shaped model: the engine only needs
    ``get_word_vector(word) -> np.ndarray | None``."""

    def __init__(self, dim=4):
        self.dim = dim

    def get_word_vector(self, word):
        if word.startswith("oov"):
            return None
        rng = np.random.default_rng(abs(hash(word)) % 2**32)
        return rng.standard_normal(self.dim).astype(np.float32)


def test_embeddings_ride_the_scheduler():
    """Embedding requests share admission/metrics/lifecycle with
    generate traffic but never take a KV slot: they are served even
    when every slot is occupied; OOV words map to None; an unknown
    model FAILS that request alone."""
    engine = ServingEngine(
        CFG, _params(), n_slots=2, temperature=0.0,
        embedders={"stub": _StubEmbedder()},
    )
    gen = _requests(4, seed=10, max_new=6)  # 4 requests > 2 slots
    emb = EmbeddingRequest(words=("alpha", "oov_x", "beta"), model="stub",
                           done=threading.Event())
    bad = EmbeddingRequest(words=("alpha",), model="nope",
                           done=threading.Event())
    for r in gen:
        engine.submit(r)
    engine.submit(emb)
    engine.submit(bad)
    engine.run()

    assert emb.status is RequestStatus.FINISHED
    assert set(emb.result) == {"alpha", "oov_x", "beta"}
    assert emb.result["oov_x"] is None
    assert emb.result["alpha"].shape == (4,)
    assert bad.status is RequestStatus.FAILED
    assert "nope" in bad.error
    for r in gen:
        assert r.status is RequestStatus.FINISHED
    s = engine.metrics.summary()
    assert s["n_embeddings"] == 1
    assert "embedding_p50_s" in s


# -- chaos with tenancy --------------------------------------------------


def test_chaos_flood_with_tenancy_and_lora():
    """The whole subsystem at once: tenanted flood + victims, mixed
    adapters, an engine crash mid-flood — everything finishes, streams
    match a clean identically-tenanted run, and the per-tenant metrics
    block tells the story."""
    def build(faults=None):
        tenancy = _fair_registry()
        return ServingEngine(
            CFG, _params(), n_slots=2, temperature=0.0,
            scheduler=RequestScheduler(max_queue_depth=64, tenancy=tenancy),
            tenancy=tenancy, lora_bank=_bank(),
            faults=faults, retry_backoff_s=0.001, max_backoff_s=0.004,
        )

    def traffic():
        flood = _requests(8, seed=11, tenant_id="flood")
        for i, r in enumerate(flood):
            r.adapter = i % 4
        victims = [r for v in range(3)
                   for r in _requests(1, seed=20 + v,
                                      tenant_id=f"victim{v}")]
        return flood + victims

    reqs = traffic()
    clean = _run(build(), reqs)
    reqs2 = traffic()
    inj = (FaultInjector()
           .plan("step", at=3, kind="crash")
           .plan("step", at=9, kind="transient"))
    engine = build(faults=inj)
    faulted = _run(engine, reqs2)

    assert engine.metrics.n_restarts == 1
    for a, b in zip(reqs, reqs2):
        np.testing.assert_array_equal(clean[a.id], faulted[b.id])
        assert b.status is RequestStatus.FINISHED
    tenants = engine.metrics.summary()["tenants"]
    assert tenants["flood"]["n_finished"] == 8
    for v in range(3):
        assert tenants[f"victim{v}"]["n_finished"] == 1
