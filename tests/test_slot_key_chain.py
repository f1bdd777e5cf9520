"""The engine's sampling-key chain runs on the host: each admission
splits the master key with the bits ``jax.random.split`` gives, queues no
device program and reads nothing back, and every sampled token is the
one the device chain would have drawn."""

import functools

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    init_transformer,
)
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.serving.engine import threefry_split_host

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64
)
_PARAMS = {}


def _params():
    if "p" not in _PARAMS:
        _PARAMS["p"] = init_transformer(jax.random.key(0), CFG)
    return _PARAMS["p"]


@functools.partial(jax.jit, static_argnums=(1,))
def _device_chain(key_data, n):
    """n chained ``jax.random.split``s: the carried key and the split-off
    key of each, as raw data."""

    def split(key, _):
        key, sub = jax.random.split(key)
        return key, (jax.random.key_data(key), jax.random.key_data(sub))

    _, out = jax.lax.scan(split, jax.random.wrap_key_data(key_data), None,
                          length=n)
    return out


@pytest.mark.parametrize("partitionable", [True, False],
                         ids=["partitionable", "original"])
@pytest.mark.parametrize("seed", [0, 3, 21, 2**31 - 1])
def test_host_split_chain_equals_jax_split_bit_for_bit(seed, partitionable):
    n = 1000
    with jax.threefry_partitionable(partitionable):
        key = np.asarray(jax.random.key_data(jax.random.key(seed)))
        carried, slots = (np.asarray(a) for a in _device_chain(key, n))
        got_carried, got_slots = [], []
        for _ in range(n):
            key, sub = threefry_split_host(key)
            got_carried.append(key)
            got_slots.append(sub)
    assert np.stack(got_carried).dtype == np.uint32
    np.testing.assert_array_equal(np.stack(got_carried), carried)
    np.testing.assert_array_equal(np.stack(got_slots), slots)


def _engine(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("decode_horizon", 2)
    return ServingEngine(CFG, _params(), temperature=1.0, rng_seed=7,
                         batch_admission=False, **kw)


def _requests(lengths=(5, 9, 30, 12), max_new=6):
    rng = np.random.default_rng(4)
    return [
        Request(id=f"r{i}",
                prompt=rng.integers(1, CFG.vocab_size, (n,)).astype(np.int32),
                max_new=max_new)
        for i, n in enumerate(lengths)
    ]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return {r.id: np.asarray(engine.results[r.id]).tolist() for r in reqs}


def test_admission_runs_no_device_program_for_the_key(monkeypatch):
    engine = _engine()

    def refused(*a, **k):
        raise AssertionError("admission touched the device key API")

    monkeypatch.setattr(jax.random, "split", refused)
    monkeypatch.setattr(jax.random, "key_data", refused)
    reqs = _requests(lengths=(5, 9, 12))
    out = _run(engine, reqs)
    assert all(len(out[r.id]) == len(r.prompt) + r.max_new for r in reqs)
    assert engine.metrics.loop_seconds["key_sync"] > 0


@pytest.mark.parametrize("kw", [
    {},
    {"piggyback": True, "prefill_max_bucket": 8, "max_total": 64},
], ids=["seat", "piggyback-presplit"])
def test_sampled_tokens_equal_those_of_the_device_chain(kw):
    """Four sampled requests through two slots, so admissions interleave
    with horizons in flight: the host chain and a chain drawn from
    ``jax.random.split`` itself give the same streams token for token."""
    host = _engine(**kw)
    want_host = _run(host, _requests())

    device = _engine(**kw)
    chain = [jax.random.key(7)]

    def device_split(req_id):
        chain[0], sub = jax.random.split(chain[0])
        return np.asarray(jax.random.key_data(sub))

    device._split_slot_key = device_split
    assert _run(device, _requests()) == want_host
    if kw.get("piggyback"):
        assert host.metrics.n_prefill_chunks > 0, "no prompt was deferred"


def test_engine_refuses_a_key_impl_it_cannot_split_on_the_host():
    with jax.default_prng_impl("rbg"):
        with pytest.raises(ValueError, match="threefry2x32"):
            _engine()
