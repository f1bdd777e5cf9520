"""Chunked-prefill piggyback suite (PR 18).

The load-bearing property is the house parity bar, one more axis: an
engine that splits long prompts into pow2 chunks and rides them along
with decode dispatches (``piggyback=True`` — the last budgeted chunk
FUSED into the decode step program itself) streams BYTE-IDENTICAL
tokens to the blocking-admission engine — greedy AND sampled, through
the adaptive horizon, prefix-cache partial hits (only the uncached
suffix is piggybacked), paged block tables, fault-injected crash
recovery mid-prefill, and TP=2. That holds by construction (the fused
``piggyback_step`` program is the decode substep envelope followed by
the exact chunk-prefill leg the blocking path runs, and the admission
key chain is pre-split in blocking order);
``tests/test_serving_schedules.py`` compares the fused program with
step + chunk run separately, every state leaf, bitwise.

The second contract is accounting: piggybacked chunk tokens are
charged to the owning tenant's DRR deficit at execution time (the
pop-time charge is credited back at deferral), so a tenant cannot
smuggle free prefill past the fair scheduler by sending long prompts.
"""


import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.serving import (
    FaultInjector,
    Request,
    RequestScheduler,
    ServingEngine,
)

pytestmark = pytest.mark.piggyback

needs_2_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for TP/sharding"
)

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
    d_ff=128, max_len=64, rope=True, decode_kernel=False,
)
_PARAMS = {}


def _params(cfg=CFG, seed=0):
    key = (id(cfg), seed)
    if key not in _PARAMS:
        _PARAMS[key] = init_transformer(jax.random.key(seed), cfg)
    return _PARAMS[key]


def _engine(piggyback=False, n_slots=4, cfg=CFG, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("max_total", 64)
    kw.setdefault("decode_horizon", 2)
    kw.setdefault("adaptive_horizon", True)
    # small bucket cap so mid-size prompts decompose into several
    # chunks (and thus actually exercise deferral + the fused leg)
    kw.setdefault("prefill_max_bucket", 8)
    return ServingEngine(
        cfg, _params(cfg), n_slots=n_slots,
        piggyback=piggyback,
        retry_backoff_s=0.001, max_backoff_s=0.004, **kw,
    )


def _piggy(**kw):
    eng = _engine(piggyback=True, **kw)
    assert eng._piggyback, "piggyback engine silently fell back"
    return eng


def _requests(n=8, seed=1, shared_frac=0.5):
    """Mixed trace: short prompts (blocking path) + long prompts that
    exceed the 8-token bucket cap (piggyback path), half sharing a
    24-token prefix so partial hits leave an uncached suffix."""
    rng = np.random.default_rng(seed)
    shared = ((1 + np.arange(24)) % 127).astype(np.int32)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(3, 40)) if i % 3 else 36
        if i % 2 and i < int(2 * shared_frac * n):
            p = np.concatenate(
                [shared, ((7 + np.arange(ln)) % 127).astype(np.int32)]
            )[:58]
        else:
            p = ((1 + np.arange(ln)) % 127).astype(np.int32)
        reqs.append(Request(id=f"r{i}", prompt=p, max_new=6))
    return reqs


def _clone(reqs):
    return [Request(id=r.id, prompt=np.asarray(r.prompt).copy(),
                    max_new=r.max_new, tenant_id=r.tenant_id)
            for r in reqs]


def _run(engine, reqs, **run_kw):
    for r in reqs:
        engine.submit(r)
    engine.run(**run_kw)
    return {r.id: np.asarray(engine.results[r.id]) for r in reqs}


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


# -- tentpole: piggyback on/off byte parity ------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_piggyback_on_off_byte_parity(temperature):
    """Adaptive-horizon trace, greedy and sampled: byte-identical
    streams, and the piggyback engine actually executed chunks
    (non-vacuity)."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _piggy(temperature=temperature)
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng.metrics.n_prefill_chunks > 0, "no chunk ever piggybacked"
    assert eng.metrics.prefill_chunk_tokens > 0


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_piggyback_prefix_partial_hit_parity(temperature):
    """Prefix-cache partial hits: only the uncached suffix is
    piggybacked, and the streams still match the blocking engine with
    the same cache."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature, prefix_cache=True),
               _clone(reqs))
    eng = _piggy(temperature=temperature, prefix_cache=True)
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng.metrics.n_prefix_hits_partial > 0, "no partial hit fired"
    assert eng.metrics.n_prefill_chunks > 0


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_piggyback_paged_parity(temperature):
    """Paged pool underneath: pending slots hold only private blocks
    until completion (aliasing deferred), and bytes still match."""
    kw = dict(temperature=temperature, paged=True, block_size=8,
              prefix_cache=True)
    reqs = _requests()
    ref = _run(_engine(**kw), _clone(reqs))
    eng = _piggy(**kw)
    assert eng._paged, "paged engine silently fell back to slab"
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng.metrics.n_prefill_chunks > 0


@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("crash_at", [1, 3, 5])
def test_piggyback_crash_mid_prefill_parity(temperature, crash_at):
    """Seeded crash while prefills are pending: recovery requeues the
    pending records (releasing their slots and pinned segments) and the
    replay still streams the blocking engine's bytes."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    faults = FaultInjector().plan("step", crash_at, "crash")
    eng = _piggy(temperature=temperature, faults=faults)
    got = _run(eng, _clone(reqs), max_restarts=5)
    _assert_same(ref, got)
    assert eng.metrics.n_restarts >= 1, "crash never fired"
    assert eng.metrics.n_prefill_chunks > 0


@needs_2_devices
@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_piggyback_tp2_parity(temperature):
    """TP=2 piggyback vs single-chip blocking: same bytes (the fused
    piggyback program shards like step + chunk — its spec declares
    K + 1 substeps)."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _piggy(temperature=temperature, tp=2)
    assert eng.tp == 2
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng.metrics.n_prefill_chunks > 0


# -- compile surface -----------------------------------------------------


def test_piggyback_compile_surface_bounded():
    """The piggyback family is bounded to the pow2 chunk grid x the
    engine's horizon set {1, K}: every compiled (bucket, K) key lies on
    that grid, and the live engine surface is a subset of the audited
    expected surface."""
    from deeplearning4j_tpu.analysis.programs import (
        ServingGeometry,
        expected_surface,
        live_engine_families,
    )

    eng = _piggy()
    _run(eng, _requests())
    keys = set(eng._piggyback_fns)
    assert keys, "no piggyback program ever compiled"
    buckets = {b for b, _ in keys}
    horizons = {k for _, k in keys}
    assert all(b & (b - 1) == 0 for b in buckets), buckets
    assert all(b <= eng._max_bucket for b in buckets), buckets
    assert horizons <= {1, eng.decode_horizon}, horizons

    geom = ServingGeometry(
        n_slots=eng.n_slots, max_total=eng.max_total,
        temperature=eng.temperature, top_k=eng.top_k,
        approx_top_k=eng.approx_top_k,
        decode_horizon=eng.decode_horizon, adaptive_horizon=True,
        prefill_max_bucket=eng._max_bucket,
    )
    exp = expected_surface(CFG, geom)
    live = live_engine_families(eng)
    assert live["piggyback_step"] <= exp["piggyback_step"]
    assert live["paged_piggyback_step"] == set()


# -- DRR accounting (satellite bugfix) -----------------------------------


def test_scheduler_adjust_deficit_and_carry():
    """adjust_deficit credits a present tenant's deficit directly and
    banks adjustments for absent tenants in the carry dict, applied on
    re-entry — the mechanism that moves the prefill charge from pop
    time to execution time."""
    sched = RequestScheduler()
    r1 = Request(id="a", prompt=np.arange(4, dtype=np.int32), max_new=2,
                 tenant_id="t1")
    sched.submit(r1)
    drr = sched._drr[r1.priority]
    assert "t1" in drr["deficit"]
    before = drr["deficit"]["t1"]
    sched.adjust_deficit(r1, 5.0)
    assert drr["deficit"]["t1"] == before + 5.0
    # absent tenant: adjustment banks in carry, lands on re-entry
    r2 = Request(id="b", prompt=np.arange(4, dtype=np.int32), max_new=2,
                 tenant_id="t2")
    sched.adjust_deficit(r2, -3.0)
    assert drr["carry"]["t2"] == -3.0
    sched.submit(r2)
    assert drr["deficit"]["t2"] == -3.0
    assert "t2" not in drr["carry"]


def test_piggyback_charges_owner_tenant():
    """Piggybacked chunk tokens land on the owning tenant's deficit:
    after a full run the net DRR charge for a long-prompt tenant equals
    the blocking engine's (pop-time) charge — deferral credit and
    per-chunk debits cancel exactly."""
    charges = {}
    for pb in (False, True):
        eng = _engine(piggyback=pb)
        reqs = [Request(id=f"x{i}", prompt=np.arange(1, 37, dtype=np.int32),
                        max_new=4, tenant_id="long") for i in range(2)]
        _run(eng, reqs)
        if pb:
            assert eng.metrics.n_prefill_chunks > 0
        drr = eng.scheduler._drr[reqs[0].priority]
        charges[pb] = drr["deficit"].get("long", 0.0) + \
            drr["carry"].get("long", 0.0)
    assert charges[True] == pytest.approx(charges[False])
