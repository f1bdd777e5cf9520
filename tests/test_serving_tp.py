"""Tensor-parallel serving + replica-router suite (PR 6).

The load-bearing property extends the house parity bar one more axis:
sharding the fused decode program and the KV slot pool over a device
mesh must be invisible in the bytes. A TP=2 engine's token streams —
greedy AND sampled, through batched admission, fused horizons, and
crash-recovery replay — are asserted identical to the single-chip
engine's. That holds by construction of the exact-TP layout (column
projections shard; row projections stay replicated behind a forced
all-gather, so every floating-point reduction keeps single-chip flop
order); the sharded reductions' logits and cache rows are also held
to a tolerance in ``tests/test_serving_schedules.py``. ``tp=N`` shards
over N devices or raises at construction.

The router suite pins the fleet-level contracts: prefix-affinity
dispatch (shared-prefix prompts pin to one replica's cache),
least-loaded spread otherwise, and per-replica fault isolation — one
replica crash-recovering (or dying outright) never fails requests on
the other.

Multi-device cases skip cleanly when the host exposes a single device
(conftest forces 8 virtual CPU devices, so CI always runs them).
"""

import http.client
import json
import threading

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.serving import (
    FaultInjector,
    KVSlotPool,
    PrefixCache,
    Request,
    ServingEngine,
    ServingServer,
)
from deeplearning4j_tpu.serving.router import PrefixShadow, ReplicaRouter

pytestmark = pytest.mark.tp_serve

needs_2_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for TP/sharding"
)

# the Pallas decode kernel cannot GSPMD-partition, so TP forces the
# dense decode path; parity runs compare dense-vs-dense at BOTH widths
# (kernel-vs-dense equality is a different claim)
CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_len=32, decode_kernel=False,
)
_PARAMS = {}


def _params(seed=0):
    if seed not in _PARAMS:
        _PARAMS[seed] = init_transformer(jax.random.key(seed), CFG)
    return _PARAMS[seed]


def _engine(tp=1, n_slots=4, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("decode_horizon", 2)
    return ServingEngine(
        CFG, _params(), n_slots=n_slots,
        retry_backoff_s=0.001, max_backoff_s=0.004, tp=tp, **kw,
    )


def _requests(n, seed=0, max_new=(4, 10)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(0, 64, (int(rng.integers(3, 12)),))
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


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return {r.id: engine.pop_result(r.id) for r in reqs}


# -- tentpole (a): sharded decode byte parity ----------------------------


@needs_2_devices
@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_tp2_streams_byte_identical_to_tp1(temperature):
    """The headline bar: TP=2 decode (sharded params, sharded KV pool,
    fused horizons, batched admission) produces byte-identical streams
    to the single-chip engine — greedy and sampled."""
    reqs = _requests(6, seed=1)
    base = _run(_engine(tp=1, temperature=temperature), reqs)

    reqs2 = _clone(reqs)
    eng = _engine(tp=2, temperature=temperature)
    assert eng.tp == 2
    assert eng.tp_mesh is not None
    got = _run(eng, reqs2)
    for r in reqs:
        assert np.array_equal(base[r.id], got[r.id]), r.id


@needs_2_devices
def test_tp_prefill_bucketing_parity_across_prompt_lengths():
    """Prompt lengths straddling several pow2 prefill buckets, so the
    sharded bucketed-prefill programs (not just decode) are compared."""
    rng = np.random.default_rng(7)
    reqs = [
        Request(prompt=rng.integers(0, 64, (ln,)).astype(np.int32),
                max_new=4, id=f"p{ln}")
        for ln in (1, 2, 3, 7, 8, 9, 15, 20)
    ]
    base = _run(_engine(tp=1, n_slots=8), reqs)
    reqs2 = _clone(reqs)
    eng = _engine(tp=2, n_slots=8)
    assert eng.tp == 2
    got = _run(eng, reqs2)
    for r in reqs:
        assert np.array_equal(base[r.id], got[r.id]), r.id


@needs_2_devices
def test_tp_crash_recovery_replay_parity():
    """Crash mid-horizon under TP=2: the supervised replay rebuilds the
    SHARDED caches and the recovered streams still match an unfaulted
    single-chip run byte-for-byte."""
    reqs = _requests(4, seed=3)
    clean = _run(_engine(tp=1), reqs)

    reqs2 = _clone(reqs)
    inj = FaultInjector().plan("step", at=1, kind="crash")
    eng = _engine(tp=2, faults=inj)
    assert eng.tp == 2
    got = _run(eng, reqs2)
    assert eng.metrics.n_restarts == 1
    for r in reqs:
        assert np.array_equal(clean[r.id], got[r.id]), r.id


def test_tp_requires_dividing_heads():
    """tp=3 cannot shard 4 heads: construction says so."""
    with pytest.raises(ValueError, match="dividing n_heads"):
        _engine(tp=3)


def test_tp1_is_the_unsharded_engine():
    eng = _engine(tp=1)
    assert eng.tp == 1 and eng.tp_mesh is None


# -- construction says what is missing ------------------------------------


def test_tp_requires_enough_devices():
    """A process with fewer devices than ``tp`` cannot shard: the
    constructor raises instead of serving on one chip."""
    with pytest.raises(ValueError, match="need 16 devices"):
        _engine(tp=16)


# -- satellite: hit-weighted prefix eviction -----------------------------


def test_hot_segment_outlives_colder_newer_ones():
    """Hit-count-weighted eviction: a pinned-then-unpinned segment that
    served many lookups survives region pressure that evicts colder
    segments inserted AFTER it (pure LRU would evict the hot one
    first)."""
    pool = KVSlotPool(CFG, 1, CFG.max_len)
    cache = PrefixCache(pool, 3 * pool.tpad)  # 3 region slots
    assert cache.hit_weight > 0

    hot = cache.insert(tuple(range(8)))[0]
    cache.unpin(hot)
    for _ in range(4):  # hot: refreshed by lookups
        seg, n = cache.lookup(tuple(range(8)) + (60, 61))
        assert seg is hot and n == 8
    # two colder segments, inserted later (higher last_use)
    c1 = cache.insert((50, 51, 52))[0]
    cache.unpin(c1)
    c2 = cache.insert((40, 41, 42))[0]
    cache.unpin(c2)

    # region full: the next insert must evict — and the victim must be
    # a cold segment despite the hot one having the OLDEST last_use
    cache.insert((30, 31, 32, 33))
    assert hot.alive, "hit-weighted eviction evicted the hot segment"
    assert not (c1.alive and c2.alive)
    assert cache.stats()["hits_recorded"] >= 4


def test_hit_weight_zero_restores_pure_lru():
    pool = KVSlotPool(CFG, 1, CFG.max_len)
    cache = PrefixCache(pool, 2 * pool.tpad, hit_weight=0.0)
    old = cache.insert(tuple(range(6)))[0]
    cache.unpin(old)
    for _ in range(10):
        cache.lookup(tuple(range(6)))
    newer = cache.insert((50, 51, 52))[0]
    cache.unpin(newer)
    cache.insert((40, 41, 42))
    assert not old.alive, "hit_weight=0 must fall back to pure LRU"
    assert newer.alive


# -- satellite: metrics scrape stays off-device --------------------------


def test_metrics_scrape_reads_host_metadata_only():
    """serve_kv_* and prefix_cache gauges must be scrape-safe: after a
    request has run, poison the live device arrays — a scrape that
    touched them (nbytes, shapes, stats) would raise / sync. Pins the
    zero-extra-dispatches-per-scrape contract."""
    eng = _engine(prefix_cache=True)
    _run(eng, _requests(2, seed=9))
    before = eng.metrics.render_prometheus()
    assert "serve_kv_cache_bytes" in before
    kv_bytes = eng.pool.nbytes()
    region_bytes = eng.prefix_cache.nbytes()
    assert kv_bytes > 0 and region_bytes > 0

    # poison: any device-array access during a scrape now explodes
    eng.pool.caches = None
    eng.prefix_cache.region = None

    text = eng.metrics.render_prometheus()
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("serve_kv_cache_bytes ")
    )
    assert float(line.split()[1]) == float(kv_bytes)
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("serve_prefix_region_bytes ")
    )
    assert float(line.split()[1]) == float(region_bytes)
    stats = eng.prefix_cache.stats()
    assert eng.pool.nbytes() == kv_bytes
    assert eng.prefix_cache.nbytes() == region_bytes
    assert stats["capacity_tokens"] == eng.prefix_cache.capacity_tokens


# -- tentpole (b): replica router ----------------------------------------


def _post(addr, body, timeout=60):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/generate", body=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read()), r.getheader("X-Served-By")
    finally:
        conn.close()


def _get(addr, path, timeout=10):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _fleet(n=2, faults=None):
    """n in-process replicas (full ServingServer each) + a router."""
    servers = []
    for i in range(n):
        eng = ServingEngine(
            CFG, _params(), n_slots=4, temperature=0.0,
            decode_horizon=2, prefix_cache=True,
            retry_backoff_s=0.001, max_backoff_s=0.004,
            faults=(faults[i] if faults else None),
        )
        servers.append(ServingServer(eng, port=0).start())
    router = ReplicaRouter(
        [s.address for s in servers],
        affinity_min_match=6, health_interval_s=0.1,
    ).start()
    return router, servers


def test_prefix_shadow_trie():
    t = PrefixShadow()
    t.insert([1, 2, 3, 4])
    t.insert([1, 2, 9])
    assert t.longest_match([1, 2, 3, 4, 5]) == 4
    assert t.longest_match([1, 2, 9, 9]) == 3
    assert t.longest_match([7, 7]) == 0
    assert len(t) == 5  # 1-2-3-4 chain + the 9 branch node


def test_prefix_shadow_reset_at_cap():
    t = PrefixShadow(max_nodes=4)
    t.insert([1, 2, 3, 4])
    t.insert([5, 6])  # over cap: wholesale reset, then re-learn
    assert t.resets == 1
    assert t.longest_match([1, 2, 3, 4]) == 0
    assert t.longest_match([5, 6]) == 2


def test_router_least_loaded_spreads_and_affinity_pins():
    rng = np.random.default_rng(11)
    router, servers = _fleet(2)
    try:
        # distinct prompts spread over both replicas
        seen = set()
        for _ in range(4):
            p = rng.integers(0, 64, (8,)).tolist()
            st, out, served = _post(
                router.address, {"prompt": p, "max_new": 3})
            assert st == 200, out
            seen.add(served)
        assert len(seen) == 2, "least-loaded dispatch never spread"

        # shared-prefix prompts pin to ONE replica (affinity override)
        shared = rng.integers(0, 64, (10,)).tolist()
        pinned = set()
        for _ in range(5):
            p = shared + rng.integers(0, 64, (3,)).tolist()
            st, out, served = _post(
                router.address, {"prompt": p, "max_new": 3})
            assert st == 200, out
            pinned.add(served)
        assert len(pinned) == 1, f"affinity split the prefix: {pinned}"

        # the pinned replica's prefix cache actually got the reuse
        name = pinned.pop()
        hit_engines = [
            s.engine for s in servers
            if f"{s.address[0]}:{s.address[1]}" == name
        ]
        assert len(hit_engines) == 1
        m = hit_engines[0].metrics
        assert (m.n_prefix_hits_full + m.n_prefix_hits_partial) > 0

        st, raw = _get(router.address, "/metrics")
        assert st == 200 and b"router_affinity_total" in raw
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_router_serves_through_single_replica_crash_recovery():
    """Per-replica chaos: replica 1's engine crashes mid-decode and its
    own supervisor replays it; the router keeps serving BOTH replicas'
    traffic with zero failed requests (the crashed replica's in-flight
    set recovers via replay, byte-identical by the chaos suite's
    bar)."""
    rng = np.random.default_rng(13)
    faults = [None, FaultInjector().plan("step", at=2, kind="crash")]
    router, servers = _fleet(2, faults=faults)
    try:
        results = []
        for _ in range(8):
            p = rng.integers(0, 64, (7,)).tolist()
            st, out, served = _post(
                router.address, {"prompt": p, "max_new": 5})
            results.append((st, served))
        assert all(st == 200 for st, _ in results), results
        assert {s for _, s in results} == {
            f"{s.address[0]}:{s.address[1]}" for s in servers
        }, "both replicas must have served through the crash"
        crashed = servers[1].engine.metrics.n_restarts
        assert crashed == 1, "the planned crash never exercised replay"
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_router_retries_onto_survivor_when_replica_dies():
    """Hard replica death: the router marks it unhealthy on the first
    failed forward and every subsequent request lands on the survivor;
    /healthz stays 200 (degraded, not down)."""
    rng = np.random.default_rng(17)
    router, servers = _fleet(2)
    try:
        for _ in range(2):  # prime both shadows
            p = rng.integers(0, 64, (6,)).tolist()
            assert _post(router.address,
                         {"prompt": p, "max_new": 3})[0] == 200
        servers[0].stop()
        survivor = f"{servers[1].address[0]}:{servers[1].address[1]}"
        for _ in range(4):
            p = rng.integers(0, 64, (6,)).tolist()
            st, out, served = _post(
                router.address, {"prompt": p, "max_new": 3})
            assert st == 200, out
            assert served == survivor
        router.poll_health()
        st, raw = _get(router.address, "/healthz")
        assert st == 200
        payload = json.loads(raw)
        assert payload["ok"] and payload["healthy"] == [survivor]
        st, raw = _get(router.address, "/replicas")
        assert st == 200
        states = json.loads(raw)
        assert states[survivor]["healthy"]
    finally:
        router.stop()
        for s in servers[1:]:
            s.stop()


def test_router_503_when_no_replica_left():
    router, servers = _fleet(1)
    try:
        servers[0].stop()
        router.poll_health()
        st, out, served = _post(
            router.address, {"prompt": [1, 2, 3], "max_new": 2})
        assert st == 503 and served is None
        st, _ = _get(router.address, "/healthz")
        assert st == 503
    finally:
        router.stop()


def test_router_rejects_malformed_and_unknown():
    router, servers = _fleet(1)
    try:
        conn = http.client.HTTPConnection(*router.address, timeout=10)
        conn.request("POST", "/v1/generate", body=b"{oops",
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
        conn.close()
        st, _ = _get(router.address, "/nope")
        assert st == 404
    finally:
        router.stop()
        for s in servers:
            s.stop()


@needs_2_devices
def test_router_over_tp_replicas():
    """The full PR-6 stack: two replicas EACH serving with TP=2 behind
    the affinity router; streams match the single-chip engine
    byte-for-byte through the whole fleet path."""
    reqs = _requests(4, seed=19, max_new=(3, 6))
    base = _run(_engine(tp=1), _clone(reqs))

    servers = []
    for _ in range(2):
        eng = ServingEngine(
            CFG, _params(), n_slots=4, temperature=0.0,
            decode_horizon=2, tp=2,
            retry_backoff_s=0.001, max_backoff_s=0.004,
        )
        assert eng.tp == 2
        servers.append(ServingServer(eng, port=0).start())
    router = ReplicaRouter(
        [s.address for s in servers], affinity_min_match=6,
    ).start()
    try:
        for r in reqs:
            st, out, _ = _post(router.address, {
                "prompt": [int(t) for t in r.prompt],
                "max_new": r.max_new,
            })
            assert st == 200, out
            assert out["tokens"] == [int(t) for t in base[r.id]], r.id
    finally:
        router.stop()
        for s in servers:
            s.stop()
