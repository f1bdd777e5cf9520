"""The traffic generator: same seed, same bits; another seed, the same
multiset of work in another order."""

import numpy as np
import pytest

from benchmark import traffic

MIXES = ["decode-flood", "chat-steady"]
BIG = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def _trace(name, seed, seconds=30.0):
    return traffic.serve_trace(traffic.load(name), seed, seconds, 50257, 1024)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bits(name):
    a, b = _trace(name, BIG), _trace(name, BIG)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_order(name):
    a, b = _trace(name, 1), _trace(name, BIG)
    shape = lambda t: sorted((len(r.prompt), r.max_new) for r in t.requests)
    assert shape(a) == shape(b)
    assert [len(r.prompt) for r in a.requests] != [len(r.prompt) for r in b.requests]
    assert not np.array_equal(a.requests[0].prompt[:8], b.requests[0].prompt[:8])


def test_open_loop_fills_ramp_and_window_exactly():
    spec = traffic.load("chat-steady")
    t = _trace("chat-steady", 7, seconds=20.0)
    due = [r.due_s for r in t.requests]
    assert len(due) == round(spec["rate_per_s"] * (spec["ramp_s"] + 20.0))
    assert due == sorted(due) and due[0] > 0
    assert due[-1] == pytest.approx(spec["ramp_s"] + 20.0)
    gaps = lambda t: sorted(np.diff([0.0] + [r.due_s for r in t.requests]).round(9))
    assert gaps(t) == gaps(_trace("chat-steady", 8, seconds=20.0))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_the_file_and_the_model(name):
    spec = traffic.load(name)
    for r in _trace(name, 3).requests:
        assert spec["prompt_len"]["low"] <= len(r.prompt) <= spec["prompt_len"]["high"]
        assert 1 <= r.max_new <= spec["output_len"]["high"]
        assert len(r.prompt) + r.max_new <= 1023
        assert r.prompt.dtype == np.int32 and 0 <= r.prompt.min() and r.prompt.max() < 50257


def test_train_batches_are_a_function_of_seed_and_step():
    a = traffic.train_batch(3, BIG, 2, 16, 100)
    assert np.array_equal(a, traffic.train_batch(3, BIG, 2, 16, 100))
    assert not np.array_equal(a, traffic.train_batch(4, BIG, 2, 16, 100))
    assert not np.array_equal(a, traffic.train_batch(3, BIG + 1, 2, 16, 100))
    assert a.shape == (2, 17) and a.dtype == np.int32
