"""Block-paged KV serving suite (PR 11).

The load-bearing property is the house parity bar, one more axis: an
engine whose KV lives in a shared pool of refcounted fixed-size blocks
(``paged=True``) streams BYTE-IDENTICAL tokens to the slab engine —
greedy AND sampled, through prefix-cache hits, refcounted eviction
under block pressure, fault-injected crash-recovery replay, and TP=2.
That holds by construction (the paged step gathers a slot's blocks
into the exact slab view the fused program already computes on, and
scatters the result back); ``tests/test_serving_schedules.py`` compares
the paged and the slab step's logits bitwise over an aliased, shuffled
block table.

The second contract is allocation hygiene: block ids come off a heap
(deterministic tables), a cached prefix is byte-shared by aliasing
and refcount bump (a full hit admits with ZERO prefill dispatches),
and dropping every reference returns the pool to empty — no leaks, no
stale bytes surviving block reuse.
"""


import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
    transformer_generate,
)
from deeplearning4j_tpu.serving import (
    FaultInjector,
    PagedKVPool,
    Request,
    ServingEngine,
)

pytestmark = pytest.mark.paged

needs_2_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for TP/sharding"
)

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)
_PARAMS = {}


def _params(cfg=CFG, seed=0):
    key = (id(cfg), seed)
    if key not in _PARAMS:
        _PARAMS[key] = init_transformer(jax.random.key(seed), cfg)
    return _PARAMS[key]


def _engine(n_slots=3, cfg=CFG, **kw):
    kw.setdefault("temperature", 0.0)
    return ServingEngine(
        cfg, _params(cfg), n_slots=n_slots,
        retry_backoff_s=0.001, max_backoff_s=0.004, **kw,
    )


def _paged(n_slots=3, cfg=CFG, **kw):
    kw.setdefault("block_size", 8)
    eng = _engine(n_slots=n_slots, cfg=cfg, paged=True, **kw)
    assert isinstance(eng.pool, PagedKVPool)
    return eng


def _requests(n, seed=0, max_new=(4, 10)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(0, 64, (int(rng.integers(3, 14)),))
            .astype(np.int32),
            max_new=int(rng.integers(*max_new)),
            id=f"r{seed}-{i}",
        )
        for i in range(n)
    ]


def _clone(reqs):
    return [
        Request(prompt=np.array(r.prompt), max_new=r.max_new, id=r.id)
        for r in reqs
    ]


def _shared_prefix_requests():
    a = np.arange(1, 9, dtype=np.int32)
    b = np.arange(40, 56, dtype=np.int32)
    prompts = [
        a,
        np.concatenate([a, [60, 61]]),
        b,
        a.copy(),
        np.concatenate([b, [3, 4, 5]]),
        np.arange(20, 27, dtype=np.int32),
        np.concatenate([a, [62]]),
        b.copy(),
    ]
    return [Request(prompt=p.copy(), max_new=5 + (i % 3), id=f"p{i}")
            for i, p in enumerate(prompts)]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return {r.id: np.asarray(engine.results[r.id]) for r in reqs}


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


# -- tentpole: paged on/off byte parity ----------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_paged_on_off_byte_parity(temperature):
    """Slab vs paged engines over staggered requests at slot
    contention: byte-identical streams, greedy and sampled. Sampled
    parity follows from bitwise logits + the position-folded key
    stream, so it is as strong a check as the greedy one."""
    reqs = _requests(8, seed=1)
    slab = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _paged(temperature=temperature)
    paged = _run(eng, _clone(reqs))
    _assert_same(slab, paged)
    assert isinstance(eng.pool, PagedKVPool)
    assert eng.pool.n_blocks_in_use == 0  # every block returned


@pytest.mark.slow
def test_paged_greedy_matches_per_request_generate():
    """Paged streams equal each request decoded alone through the
    plain generate path — parity anchored to the reference, not just
    to the slab engine."""
    gen = jax.jit(
        transformer_generate(CFG),
        static_argnames=("max_new", "temperature", "top_k"),
    )
    reqs = _requests(5, seed=3)
    got = _run(_paged(), reqs)
    for r in reqs:
        ref = np.asarray(gen(
            _params(), np.asarray(r.prompt[None]), jax.random.key(0),
            max_new=r.max_new, temperature=0.0,
        ))[0]
        np.testing.assert_array_equal(got[r.id], ref)


def test_paged_no_stale_kv_after_block_reuse():
    """A slot's freed blocks go back to the heap and get reused by the
    next admission; the reused request's stream must equal a fresh
    engine's (the prefill scatter overwrites every allocated block,
    so no bytes from the previous owner leak)."""
    eng = _paged(n_slots=1)
    r1 = Request(prompt=np.arange(1, 20, dtype=np.int32), max_new=8)
    r2 = Request(prompt=np.arange(30, 37, dtype=np.int32), max_new=8)
    eng.submit(r1)
    eng.run()
    used = eng.pool.n_blocks_in_use
    assert used == 0
    eng.submit(r2)
    eng.run()
    fresh = _paged(n_slots=1)
    r2b = Request(prompt=np.array(r2.prompt), max_new=r2.max_new)
    fresh.submit(r2b)
    fresh.run()
    np.testing.assert_array_equal(eng.results[r2.id],
                                  fresh.results[r2b.id])


# -- prefix sharing: aliasing + refcounts --------------------------------


def test_paged_full_hit_aliases_blocks_zero_prefill():
    """A fully-cached admission aliases the segment's blocks into the
    slot table (refcount bump, zero bytes copied for the aligned span)
    and dispatches NO prefill program."""
    eng = _paged(n_slots=1, prefix_cache=True)
    p = np.arange(1, 9, dtype=np.int32)  # 8 = block size: pure aliasing
    r1 = Request(prompt=p.copy(), max_new=6)
    eng.submit(r1)
    eng.run()
    segs = list(eng.prefix_cache._segments)
    assert len(segs) == 1 and segs[0].block_ids
    before = eng.prefill_dispatches
    r2 = Request(prompt=p.copy(), max_new=6)
    eng.submit(r2)
    eng.run()
    assert eng.prefill_dispatches == before
    assert eng.metrics.n_prefix_hits_full == 1
    np.testing.assert_array_equal(eng.results[r1.id], eng.results[r2.id])
    # retired: the cache's refs are the only ones left on those blocks
    assert all(eng.pool.refcount(b) == 1 for b in segs[0].block_ids)


@pytest.mark.slow
def test_paged_prefix_on_off_parity_with_hits():
    """Prefix cache ON vs OFF in paged mode: byte-identical streams,
    and the cache really fired (full + partial hits, tokens saved)."""
    off = _run(_paged(prefix_cache=False), _shared_prefix_requests())
    eng = _paged(prefix_cache=True, prefix_cache_tokens=8 * CFG.max_len)
    on = _run(eng, _shared_prefix_requests())
    _assert_same(off, on)
    assert eng.metrics.n_prefix_hits_full > 0
    assert eng.metrics.n_prefix_hits_partial > 0
    assert eng.metrics.prefix_tokens_saved > 0


@pytest.mark.slow
def test_paged_refcounted_eviction_under_pressure():
    """A block-capacity-bounded prefix cache under many distinct
    prompts: eviction fires, streams stay correct, and after dropping
    every segment the pool is empty — refcounts balanced, no leaked
    blocks."""
    eng = _paged(n_slots=2, prefix_cache=True,
                 prefix_cache_tokens=2 * CFG.max_len)  # 8 blocks
    reqs = _requests(10, seed=5, max_new=(4, 6))
    got = _run(eng, reqs)
    cache = eng.prefix_cache
    assert cache.n_evictions > 0
    # parity against the uncached paged engine under the same trace
    ref = _run(_paged(n_slots=2, prefix_cache=False), _clone(reqs))
    _assert_same(ref, got)
    # cached segments hold exactly their blocks; dropping them all
    # must return the pool to empty
    for seg in list(cache._segments):
        cache.drop(seg)
    assert eng.pool.n_blocks_in_use == 0


# -- chaos: crash recovery on the paged path -----------------------------


@pytest.mark.chaos
def test_paged_crash_recovery_parity():
    """Transient faults + a hard crash mid-decode: the supervised run
    loop replays from the journal through the paged replay program and
    the streams still match a fault-free slab engine byte-for-byte."""
    reqs = _requests(6, seed=7)
    clean = _run(_engine(), _clone(reqs))
    inj = (FaultInjector()
           .plan("step", at=2, kind="transient")
           .plan("step", at=5, kind="crash")
           .plan("prefill", at=1, kind="transient"))
    eng = _paged(faults=inj)
    faulted = _run(eng, _clone(reqs))
    _assert_same(clean, faulted)
    assert eng.pool.n_blocks_in_use == 0


@pytest.mark.chaos
def test_paged_recovery_with_prefix_hits():
    """Crash recovery while cache-hit requests are in flight: replay
    rebuilds aliased tables from scratch (pool.reinit first, then
    PrefixCache.reinit — no double decref) and parity holds."""
    reqs = _shared_prefix_requests()
    clean = _run(_engine(n_slots=2, prefix_cache=True,
                         prefix_cache_tokens=8 * CFG.max_len),
                 _clone(reqs))
    inj = FaultInjector().plan("step", at=4, kind="crash")
    eng = _paged(n_slots=2, prefix_cache=True,
                 prefix_cache_tokens=8 * CFG.max_len, faults=inj)
    faulted = _run(eng, _clone(reqs))
    _assert_same(clean, faulted)


# -- TP: paged parity across the mesh ------------------------------------


@needs_2_devices
def test_paged_tp2_parity():
    """TP=2 paged vs single-chip slab: same bytes. (TP forces the
    dense decode path — same constraint as the slab TP suite.)"""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=32, decode_kernel=False,
    )
    reqs = _requests(6, seed=9)
    ref = _run(_engine(cfg=cfg), _clone(reqs))
    eng = _paged(cfg=cfg, tp=2)
    assert eng.tp == 2
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)


# -- construction says what is missing -----------------------------------


def test_paged_bad_block_size_raises():
    """A block size that does not divide Tpad is an error at
    construction, not a slab pool in silence."""
    assert isinstance(_engine(paged=True, block_size=32).pool,
                      PagedKVPool)  # Tpad=32
    with pytest.raises(ValueError, match="block_size=64"):
        _engine(paged=True, block_size=64)
