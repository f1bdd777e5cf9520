"""Prefix-cache suite: radix-tree KV reuse across serving requests.

The load-bearing property mirrors ``test_serving.py``'s: byte-identical
token streams — now with the prefix cache ON vs OFF, greedy AND
sampled, including crash-recovery replay mid-generation on a cache-hit
request. A FULL hit replays the exact ``(1, V)`` logits captured at
insert time; a PARTIAL hit copies the cached rows and chunk-computes
the suffix, another order of the full prefill's arithmetic, which
``tests/test_serving_schedules.py`` holds to a tolerance on KV rows
and logits while the streams here stay equal. The second contract is the refcount boundary: eviction never
drops a segment a live admission read (pinned until retirement), no
matter the region pressure.
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
    KVSlotPool,
    PrefixCache,
    Request,
    RequestScheduler,
    ServingEngine,
)

pytestmark = pytest.mark.prefix

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=32
)
_PARAMS = {}


def _params(seed=0):
    if seed not in _PARAMS:
        _PARAMS[seed] = init_transformer(jax.random.key(seed), CFG)
    return _PARAMS[seed]


def _engine(n_slots=2, **kw):
    kw.setdefault("temperature", 0.0)
    return ServingEngine(
        CFG, _params(), n_slots=n_slots,
        retry_backoff_s=0.001, max_backoff_s=0.004, **kw,
    )


def _shared_prefix_requests():
    """Requests dominated by two shared prefixes (system-prompt
    traffic) plus unrelated fillers, prompts varied enough that the
    radix tree sees splits, extensions, and misses."""
    a = np.arange(1, 9, dtype=np.int32)          # 8 = bucket grain
    b = np.arange(40, 56, dtype=np.int32)        # 16 tokens
    prompts = [
        a,                                        # seeds segment A
        np.concatenate([a, [60, 61]]),            # partial hit on A
        b,                                        # seeds segment B
        a.copy(),                                 # full hit on A
        np.concatenate([b, [3, 4, 5]]),           # partial hit on B
        np.arange(20, 27, dtype=np.int32),        # miss (7 tokens)
        np.concatenate([a, [62]]),                # partial hit on A
        b.copy(),                                 # full hit on B
    ]
    return [Request(prompt=p.copy(), max_new=5 + (i % 3))
            for i, p in enumerate(prompts)]


def _drive(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return [engine.results[r.id] for r in reqs]


def _assert_streams_equal(sa, sb):
    for x, y in zip(sa, sb):
        np.testing.assert_array_equal(x, y)


# -- byte parity ---------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_prefix_cache_on_off_byte_parity(temperature):
    """Cache on vs off: byte-identical streams under slot contention
    (n_slots=2 over 8 requests forces multi-round admission, so later
    rounds actually hit segments earlier rounds inserted) — and the
    cache must have REALLY been exercised: full and partial hits > 0,
    saved prefill tokens > 0."""
    off = _drive(_engine(temperature=temperature, prefix_cache=False),
                 _shared_prefix_requests())
    # Region sized to the working set (8 slots): the default (one slot
    # per decode slot = 2 here) LRU-churns under 7 inserts, which is
    # legal but leaves nothing for the repeats to hit.
    eng = _engine(temperature=temperature, prefix_cache=True,
                  prefix_cache_tokens=8 * CFG.max_len)
    on = _drive(eng, _shared_prefix_requests())
    _assert_streams_equal(off, on)
    m = eng.metrics
    assert m.n_prefix_hits_full > 0
    assert m.n_prefix_hits_partial > 0
    assert m.prefix_tokens_saved > 0
    s = m.summary()
    assert s["prefix_hit_rate"] > 0
    assert s["prefix_tokens_saved"] == m.prefix_tokens_saved


def test_greedy_matches_per_request_generate():
    """Cache-on streams equal each request decoded alone through the
    plain generate path — the same reference contract the serving
    suite pins, now through hit-path admissions."""
    gen = jax.jit(
        transformer_generate(CFG),
        static_argnames=("max_new", "temperature", "top_k"),
    )
    reqs = _shared_prefix_requests()
    streams = _drive(_engine(prefix_cache=True), reqs)
    for r, got in zip(reqs, streams):
        ref = np.asarray(gen(
            _params(), np.asarray(r.prompt[None]), jax.random.key(0),
            max_new=r.max_new, temperature=0.0,
        ))[0]
        np.testing.assert_array_equal(got, ref)


# -- hit mechanics -------------------------------------------------------


def test_full_hit_dispatches_zero_prefill_programs():
    """A fully-cached admission is ONE pure-copy program: segment slab
    + stored logits. The prefill-dispatch counter (programs that
    compute prompt rows) must not move at all."""
    eng = _engine(n_slots=1, prefix_cache=True)
    p = np.arange(1, 9, dtype=np.int32)
    r1 = Request(prompt=p.copy(), max_new=6)
    eng.submit(r1)
    eng.run()
    assert eng.prefill_dispatches > 0  # the miss admission computed
    before = eng.prefill_dispatches
    r2 = Request(prompt=p.copy(), max_new=6)
    eng.submit(r2)
    eng.run()
    assert eng.prefill_dispatches == before
    assert eng.metrics.n_prefix_hits_full == 1
    np.testing.assert_array_equal(eng.results[r1.id], eng.results[r2.id])


def test_partial_hit_reuses_prefix_and_saves_tokens():
    """A prompt extending a cached one chunk-computes only the suffix:
    matched tokens counted as saved, one suffix dispatch, stream still
    byte-equal to the uncached engine."""
    a = np.arange(1, 17, dtype=np.int32)               # 16 tokens
    b = np.concatenate([a, [60, 61, 62, 63]])          # extends a
    def run(cache):
        eng = _engine(n_slots=1, prefix_cache=cache)
        ra = Request(prompt=a.copy(), max_new=4)
        rb = Request(prompt=b.copy(), max_new=4)
        out = _drive(eng, [ra, rb])
        return eng, out
    e_off, off = run(False)
    e_on, on = run(True)
    _assert_streams_equal(off, on)
    assert e_on.metrics.n_prefix_hits_partial == 1
    assert e_on.metrics.prefix_tokens_saved == 16
    # the hit admission dispatched exactly one program (the suffix
    # window) — same count as the miss here, but over 8 rows not 32
    assert e_on.prefill_dispatches == e_off.prefill_dispatches


def test_branch_point_segment_enables_shared_prefix_hits():
    """System-prompt traffic: prompts share a 16-token prefix but all
    END differently, so no full prompt is a prefix of another and leaf
    segments alone can never match. The segment minted at the radix
    BRANCH POINT (when the second insert splits the first's edge) is
    what makes the third request hit — and, carrying no stored logits,
    it must serve partial hits only, byte-identically."""
    shared = np.arange(1, 17, dtype=np.int32)
    prompts = [np.concatenate([shared, [50 + i, 60 + i]]).astype(np.int32)
               for i in range(4)]
    def run(cache):
        eng = _engine(n_slots=1, prefix_cache=cache,
                      prefix_cache_tokens=8 * CFG.max_len)
        return eng, _drive(eng, [Request(prompt=p.copy(), max_new=3)
                                 for p in prompts])
    e_off, off = run(False)
    e_on, on = run(True)
    _assert_streams_equal(off, on)
    m = e_on.metrics
    # req 0 misses; req 1 misses but its insert mints the branch
    # segment at the shared prefix; reqs 2 and 3 partial-hit it
    assert m.n_prefix_hits_partial == 2
    assert m.n_prefix_hits_full == 0
    assert m.prefix_tokens_saved == 32
    # an exact-length query against the logits-less branch segment
    # must degrade to a partial hit, never a bogus full hit
    r = Request(prompt=shared.copy(), max_new=3)
    e_on.submit(r)
    e_on.run()
    assert m.n_prefix_hits_full == 0 and m.n_prefix_hits_partial == 3


def test_metrics_appear_in_prometheus_render():
    eng = _engine(n_slots=1, prefix_cache=True, adaptive_horizon=True)
    p = np.arange(1, 9, dtype=np.int32)
    _drive(eng, [Request(prompt=p.copy(), max_new=4),
                 Request(prompt=p.copy(), max_new=4)])
    text = eng.metrics.render_prometheus()
    assert 'serve_prefix_lookups_total{result="hit_full"} 1' in text
    assert 'serve_prefix_lookups_total{result="miss"} 1' in text
    assert "serve_prefix_tokens_saved_total 8" in text
    assert "serve_prefix_inserts_total 1" in text
    assert "serve_prefix_segments 1" in text
    assert "serve_prefix_capacity_tokens" in text
    assert "serve_decode_horizon_current" in text


# -- crash recovery ------------------------------------------------------


@pytest.mark.chaos
def test_recovery_mid_generation_on_cache_hit_request():
    """Engine crash while a cache-hit request is mid-generation
    (sampled): replay recovery reinits the region (corrupt after a
    crash) and replays through the same lookup path — every lookup
    misses against the empty tree, i.e. the cold branch — so the
    recovered streams stay byte-identical to an unfaulted cache-on
    run AND to the cache-off engine."""
    p = np.arange(1, 9, dtype=np.int32)
    def drive(eng):
        reqs = [Request(prompt=p.copy(), max_new=8) for _ in range(2)]
        return _drive(eng, reqs), eng
    r_off, _ = drive(_engine(n_slots=1, temperature=0.7))
    r_on, e_on = drive(_engine(n_slots=1, temperature=0.7,
                               prefix_cache=True))
    assert e_on.metrics.n_prefix_hits_full == 1  # hit request exists
    # crash strikes after the second (full-hit) admission dispatched
    inj = FaultInjector().plan("step", at=10, kind="crash")
    r_cr, e_cr = drive(_engine(n_slots=1, temperature=0.7,
                               prefix_cache=True, faults=inj))
    assert e_cr.metrics.n_restarts == 1
    assert e_cr.metrics.n_prefix_hits_full == 1
    _assert_streams_equal(r_off, r_on)
    _assert_streams_equal(r_on, r_cr)
    # the rebuilt cache is coherent: the first post-recovery admission
    # misses (reinit dropped every segment) and re-seeds the tree, the
    # next one full-hits with zero prefill dispatches again
    x1 = Request(prompt=p.copy(), max_new=4)
    e_cr.submit(x1)
    e_cr.run()
    before = e_cr.prefill_dispatches
    x2 = Request(prompt=p.copy(), max_new=4)
    e_cr.submit(x2)
    e_cr.run()
    assert e_cr.prefill_dispatches == before  # full hit, pure copy
    assert e_cr.metrics.n_prefix_hits_full == 2


# -- eviction / refcounts ------------------------------------------------


@pytest.mark.chaos
def test_eviction_never_drops_pinned_segment():
    """Region sized to ONE segment, two concurrent admissions: the
    second insert must DECLINE (the only slot is pinned by the live
    first request), never evict it. After retirement unpins, the next
    insert evicts normally."""
    eng = _engine(n_slots=2, prefix_cache=True,
                  prefix_cache_tokens=1)  # rounds up to 1 region slot
    cache = eng.prefix_cache
    assert cache.n_region_slots == 1
    a = np.arange(1, 9, dtype=np.int32)
    b = np.arange(30, 40, dtype=np.int32)
    ra = Request(prompt=a.copy(), max_new=6)
    rb = Request(prompt=b.copy(), max_new=6)
    eng.submit(ra)
    eng.submit(rb)
    eng.step()  # admits both; first insert claims the slot, pinned
    assert cache.n_segments == 1
    assert cache.n_pinned == 1
    (seg,) = cache._segments
    assert seg.alive and seg.refs > 0
    assert cache.n_insert_declined >= 1  # second insert backed off
    eng.run()
    assert cache.n_pinned == 0  # retirement unpinned
    # now an insert may evict: a third, different prompt takes the slot
    rc = Request(prompt=np.arange(50, 60, dtype=np.int32), max_new=4)
    eng.submit(rc)
    eng.run()
    assert cache.n_evictions == 1
    assert not seg.alive
    assert eng.metrics.n_prefix_evictions == 1


def test_lru_eviction_prefers_least_recently_used():
    pool = KVSlotPool(CFG, 1, CFG.max_len)
    cache = PrefixCache(pool, 2 * pool.tpad)
    assert cache.n_region_slots == 2
    (s1,) = cache.insert(range(1, 9))
    (s2,) = cache.insert(range(11, 19))
    cache.unpin(s1)
    cache.unpin(s2)
    cache.lookup(range(1, 9))  # refresh s1's LRU tick
    (s3,) = cache.insert(range(21, 29))
    assert s3 is not None
    assert not s2.alive and s1.alive  # s2 was least recent
    assert cache.n_evictions == 1
    # all pinned -> insert declines instead of evicting
    cache.unpin(s3)
    cache.pin(s1)
    cache.pin(s3)
    assert cache.insert(range(31, 39)) == []
    assert cache.n_insert_declined == 1


# -- radix tree ----------------------------------------------------------


def test_radix_tree_split_lookup_prune():
    pool = KVSlotPool(CFG, 1, CFG.max_len)
    cache = PrefixCache(pool, 4 * pool.tpad)
    (long,) = cache.insert([1, 2, 3, 4, 5, 6])
    cache.unpin(long)
    # inserting a strict prefix splits the edge; both remain cached
    (short,) = cache.insert([1, 2, 3])
    cache.unpin(short)
    assert cache.n_segments == 2
    # deepest live segment wins; matched_len == segment.length
    seg, m = cache.lookup([1, 2, 3, 4, 5, 6, 7, 8])
    assert seg is long and m == 6
    seg, m = cache.lookup([1, 2, 3, 4])
    assert seg is short and m == 3
    seg, m = cache.lookup([1, 2])
    assert seg is None and m == 0  # segments only at node boundaries
    assert cache.lookup([9, 9])[0] is None
    # duplicate insert declines quietly (already cached)
    assert cache.insert([1, 2, 3]) == []
    # evicting the deep segment falls back to the shorter prefix
    cache.pin(short)
    (s3,) = cache.insert([7, 7, 7])
    (s4,) = cache.insert([8, 8, 8])
    (s5,) = cache.insert([9, 9, 9])  # evicts `long` (only unpinned)
    assert s3 and s4 and s5 and not long.alive
    seg, m = cache.lookup([1, 2, 3, 4, 5, 6])
    assert seg is short and m == 3
    # reinit drops everything (crash recovery)
    cache.reinit()
    assert cache.n_segments == 0 and cache.n_pinned == 0
    assert cache.lookup([1, 2, 3])[0] is None


# -- batched admission ---------------------------------------------------


def test_batched_admission_parity_and_fewer_dispatches():
    """Four same-bucket misses admitted in one horizon: batched
    admission coalesces them into ONE dispatched prefill program,
    byte-identical to serial admission."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, (6 + i % 3,)).astype(np.int32)
               for i in range(4)]
    def run(batch):
        eng = _engine(n_slots=4, batch_admission=batch)
        reqs = [Request(prompt=p.copy(), max_new=5) for p in prompts]
        return eng, _drive(eng, reqs)
    e_ser, ser = run(False)
    e_bat, bat = run(True)
    _assert_streams_equal(ser, bat)
    assert e_bat.metrics.n_batched_admissions == 4
    assert e_ser.metrics.n_batched_admissions == 0
    assert e_bat.prefill_dispatches == 1   # one group program
    assert e_ser.prefill_dispatches == 4   # one per request


def test_batched_partial_hits_share_one_dispatch():
    """Several prompts extending the SAME cached prefix, admitted in
    one horizon: the batched hit program computes every suffix in one
    dispatch (the many-requests-behind-one-system-prompt case)."""
    a = np.arange(1, 9, dtype=np.int32)
    exts = [np.concatenate([a, [50 + i, 60 - i]]) for i in range(3)]
    def run(cache):
        eng = _engine(n_slots=3, prefix_cache=cache)
        seed = Request(prompt=a.copy(), max_new=4)
        _drive(eng, [seed])
        before = eng.prefill_dispatches
        reqs = [Request(prompt=p.copy(), max_new=4) for p in exts]
        return eng, _drive(eng, reqs), eng.prefill_dispatches - before
    e_off, off, _ = run(False)
    e_on, on, delta = run(True)
    _assert_streams_equal(off, on)
    assert e_on.metrics.n_prefix_hits_partial == 3
    assert e_on.metrics.prefix_tokens_saved == 24
    assert delta == 1  # one batched suffix program for all three
    assert e_on.metrics.n_batched_admissions == 3


# -- adaptive horizon ----------------------------------------------------


def test_adaptive_horizon_shrinks_then_restores():
    """With requests queued, the dispatched horizon drops to 1 (the
    next admission boundary is one substep away); once the queue
    drains the configured K is restored. Streams are unchanged —
    the device stopping rule is per-substep."""
    p = np.arange(1, 9, dtype=np.int32)
    def reqs():
        return [Request(prompt=p.copy(), max_new=6) for _ in range(2)]
    fixed = _drive(_engine(n_slots=1, decode_horizon=4), reqs())
    eng = _engine(n_slots=1, decode_horizon=4, adaptive_horizon=True)
    rs = reqs()
    for r in rs:
        eng.submit(r)
    seen = set()
    while not eng.idle:
        eng.step()
        seen.add(eng.decode_horizon_current)
    adaptive = [eng.results[r.id] for r in rs]
    _assert_streams_equal(fixed, adaptive)
    assert seen == {1, 4}  # shrank while queued, restored after drain
    assert eng.decode_horizon_current == 4
    assert "serve_decode_horizon_current" in eng.metrics.render_prometheus()


# -- scheduler prefix affinity -------------------------------------------


def test_scheduler_prefix_affinity_promotes_matches():
    sched = RequestScheduler(prefix_affinity_tokens=4)
    pre = np.arange(1, 9, dtype=np.int32)
    r1 = Request(prompt=pre.copy(), max_new=2)
    r2 = Request(prompt=np.arange(40, 48, dtype=np.int32), max_new=2)
    r3 = Request(prompt=np.concatenate([pre, [9]]), max_new=2)
    for r in (r1, r2, r3):
        sched.submit(r)
    assert sched.pop() is r1
    assert sched.pop(affinity_hint=r1.prompt) is r3  # promoted over r2
    assert sched.pop(affinity_hint=r3.prompt) is r2  # plain FIFO now
    # affinity never crosses a priority boundary
    hi = Request(prompt=np.arange(50, 58, dtype=np.int32), max_new=2,
                 priority=0)
    lo = Request(prompt=pre.copy(), max_new=2, priority=1)
    sched.submit(lo)
    sched.submit(hi)
    assert sched.pop(affinity_hint=pre) is hi


# -- slot pool determinism (satellite) -----------------------------------


def test_slot_pool_free_list_lowest_index_first():
    pool = KVSlotPool(CFG, 4, CFG.max_len)
    assert [pool.acquire() for _ in range(4)] == [0, 1, 2, 3]
    pool.release(2)
    pool.release(0)
    assert pool.acquire() == 0  # lowest free index, not LIFO
    assert pool.acquire() == 2
    with pytest.raises(RuntimeError):
        pool.acquire()
    with pytest.raises(ValueError):
        pool.release(7)


def test_slot_pool_generation_counter_detects_reuse():
    """The generation counter is what lets pipelined readback discard
    a token block that raced a slot's retire/re-acquire."""
    pool = KVSlotPool(CFG, 2, CFG.max_len)
    s = pool.acquire()
    g1 = pool.generation(s)
    pool.release(s)
    assert pool.acquire() == s  # deterministically the same slot
    g2 = pool.generation(s)
    assert g2 == g1 + 1  # a stale block's gen no longer matches
    other = pool.acquire()
    assert pool.generation(other) == 1


# -- paged pool block determinism (satellite) ----------------------------


def _paged_pool(n_slots=2, block_size=8):
    from deeplearning4j_tpu.serving import PagedKVPool
    return PagedKVPool(CFG, n_slots, CFG.max_len, block_size=block_size)


def test_paged_pool_block_alloc_lowest_id_first():
    """Block ids come off a heap lowest-first (the block analogue of
    the slot free-list test): allocation order is a pure function of
    the request sequence, so identical runs produce identical tables."""
    pool = _paged_pool()
    s = pool.acquire()
    pool.alloc_slot_blocks(s, 17)  # ceil(17/8) = 3 blocks
    assert pool.slot_blocks(s) == [1, 2, 3]  # 0 is the zero sentinel
    pool.release(s)
    assert pool.n_blocks_in_use == 0
    s2 = pool.acquire()
    pool.alloc_slot_blocks(s2, 9)
    assert pool.slot_blocks(s2) == [1, 2]  # freed ids reused, lowest first
    extra = pool.alloc_blocks(2)
    assert extra == [3, 4]
    with pytest.raises(RuntimeError):
        pool.alloc_blocks(pool.n_free_blocks + 1)


def test_paged_pool_generation_counter_spans_block_reuse():
    """Slot reuse bumps the generation even though the slot's KV now
    lives in reallocated blocks — a stale pipelined readback keyed on
    (slot, gen) is still discarded after the block-table rewrite."""
    pool = _paged_pool(n_slots=1)
    s = pool.acquire()
    pool.alloc_slot_blocks(s, 16)
    g1 = pool.generation(s)
    old_blocks = pool.slot_blocks(s)
    pool.release(s)
    assert pool.table(s).tolist() == [0] * pool.blocks_per_slot
    s2 = pool.acquire()
    assert s2 == s
    pool.alloc_slot_blocks(s2, 16)
    assert pool.generation(s2) == g1 + 1
    assert pool.slot_blocks(s2) == old_blocks  # same bytes, new gen


def test_paged_pool_snapshot_identity_at_block_granularity():
    """Two pools driven through the same acquire/alloc/alias/release
    sequence end with byte-identical block tables and refcounts — the
    block-granular snapshot-identity contract recovery replay and the
    prefix cache's aliasing both lean on."""
    def drive(pool):
        a = pool.acquire()
        b = pool.acquire()
        pool.alloc_slot_blocks(a, 20)
        pool.alloc_slot_blocks(b, 8)
        shared = pool.slot_blocks(a)[:2]
        pool.release(b)
        b2 = pool.acquire()
        pool.alias_into_slot(b2, shared)
        pool.alloc_slot_blocks(b2, 24, start=2)
        return pool

    p1 = drive(_paged_pool())
    p2 = drive(_paged_pool())
    np.testing.assert_array_equal(p1.tables(), p2.tables())
    assert [p1.refcount(i) for i in range(p1.n_blocks)] == \
           [p2.refcount(i) for i in range(p2.n_blocks)]
    # the aliased blocks really are shared (refcount 2), and releasing
    # one owner keeps them alive for the other
    shared = p1.slot_blocks(0)[:2]
    assert all(p1.refcount(b) == 2 for b in shared)
    p1.release(0)
    assert all(p1.refcount(b) == 1 for b in shared)
    assert p1.slot_blocks(1)[:2] == shared


def test_paged_pool_reinit_restores_full_capacity():
    """reinit() after a crash returns every block to the free heap and
    zeroes every table — the pool-side half of the recovery contract
    (PrefixCache.reinit drops its segment block refs WITHOUT decref,
    relying on exactly this)."""
    pool = _paged_pool()
    a = pool.acquire()
    pool.alloc_slot_blocks(a, 32)
    assert pool.n_blocks_in_use > 0
    pool.reinit()
    assert pool.n_blocks_in_use == 0
    assert pool.n_free_blocks == pool.n_blocks - 1  # all but sentinel
    assert pool.tables().sum() == 0
    assert pool.refcount(0) == 1  # sentinel stays pinned
