"""Grammar-constrained decoding + sampling-surface suite (PR 20).

Two load-bearing contracts. (1) The house parity bar, one more axis:
an engine armed with the sampling surface (``sampling_surface=True``)
routes EVERY decode dispatch through the masked step family — DFA mask
gather, logit-bias scatter, per-slot temperature/top_k/top_p, logprob
gather — yet unconstrained traffic streams BYTE-IDENTICAL tokens to
the plain engine, greedy AND sampled, across K∈{1,4}, paged block
tables, chunked-prefill piggyback, fault-injected crash recovery, and
TP=2. That holds because every surface feature folds out to the exact
plain computation at its neutral value (state 0, bias-free rows,
engine-default temp/top_k, top_p=1); ``tests/test_serving_schedules.py``
compares the masked and the plain step program's whole state bitwise.

(2) Validity: a request with a JSON-schema/regex ``response_format``
only ever emits DFA-permitted tokens — the mask lands BEFORE the draw
and the FSM advances in-program across all K substeps — so constrained
outputs parse and validate by construction, greedy and sampled,
including byte-identical replay through crash recovery (FSM state is
re-derived from ``gstate0`` + the emitted prefix at re-seat).
"""

import json

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
    ServingEngine,
)
from deeplearning4j_tpu.serving.grammar import (
    GrammarBudgetError,
    GrammarCache,
    GrammarTable,
    StopMatcher,
    compile_json_schema,
    compile_regex,
    default_token_bytes,
    schema_to_regex,
    validate_json_value,
)
from deeplearning4j_tpu.serving.scheduler import AdmissionError

pytestmark = pytest.mark.grammar

needs_2_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for TP/sharding"
)

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=2,
    d_ff=128, max_len=64, rope=True, decode_kernel=False,
)
EOS = 127
TOKEN_BYTES = default_token_bytes(CFG.vocab_size)
_PARAMS = {}


def _params(cfg=CFG, seed=0):
    key = (id(cfg), seed)
    if key not in _PARAMS:
        _PARAMS[key] = init_transformer(jax.random.key(seed), cfg)
    return _PARAMS[key]


def _engine(surface=False, n_slots=4, cfg=CFG, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("max_total", 64)
    kw.setdefault("decode_horizon", 2)
    kw.setdefault("adaptive_horizon", True)
    kw.setdefault("prefill_max_bucket", 8)
    return ServingEngine(
        cfg, _params(cfg), n_slots=n_slots,
        sampling_surface=surface,
        retry_backoff_s=0.001, max_backoff_s=0.004, **kw,
    )


def _surface(**kw):
    eng = _engine(surface=True, **kw)
    assert eng._surface, "sampling surface silently fell back"
    return eng


def _requests(n=8, seed=1, max_new=6):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        ln = int(rng.integers(3, 40)) if i % 3 else 36
        p = ((1 + np.arange(ln)) % 127).astype(np.int32)
        reqs.append(Request(id=f"r{i}", prompt=p, max_new=max_new))
    return reqs


def _clone(reqs):
    return [Request(id=r.id, prompt=np.asarray(r.prompt).copy(),
                    max_new=r.max_new) for r in reqs]


def _run(engine, reqs, **run_kw):
    for r in reqs:
        engine.submit(r)
    engine.run(**run_kw)
    return {r.id: np.asarray(engine.results[r.id]) for r in reqs}


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def _generated(res, req):
    """Generated span of a full-sequence result (prompt and trailing
    EOS stripped)."""
    toks = [int(t) for t in np.asarray(res)[len(req.prompt):]]
    if toks and toks[-1] == req.eos_token:
        toks = toks[:-1]
    return toks


def _decode(toks):
    return bytes(t for t in toks if t < 256).decode("latin-1")


# -- grammar units -------------------------------------------------------


def test_regex_dfa_token_permissions():
    """The compiled DFA permits exactly the byte alternatives at each
    state, EOS only in accepting states."""
    cg = compile_regex("(yes|no)", TOKEN_BYTES, EOS)
    start = cg.start
    permitted = {t for t in range(128) if cg.trans[start, t] >= 0}
    assert permitted == {ord("y"), ord("n")}
    s = start
    for b in b"no":
        assert cg.trans[s, b] >= 0
        s = int(cg.trans[s, b])
    assert cg.accepting[s]
    assert cg.trans[s, EOS] == s, "EOS must self-loop at accepting"
    assert cg.trans[start, EOS] < 0, "EOS permitted before accepting"


def test_schema_to_regex_and_validator():
    schema = {
        "type": "object",
        "properties": {
            "ok": {"type": "boolean"},
            "tag": {"enum": ["a", "b"]},
        },
        "required": ["ok", "tag"],
    }
    pat = schema_to_regex(schema)
    cg = compile_json_schema(schema, TOKEN_BYTES, EOS)
    assert cg.n_states > 1
    assert pat.startswith("\\{")
    assert validate_json_value({"ok": True, "tag": "a"}, schema)
    assert not validate_json_value({"ok": 1, "tag": "a"}, schema)
    assert not validate_json_value({"ok": True, "tag": "z"}, schema)


def test_grammar_cache_memory_and_disk(tmp_path):
    """Fresh compile is a miss; the second lookup hits memory; a new
    cache instance over the same directory hits disk."""
    path = str(tmp_path / "grammars")
    c1 = GrammarCache(path)
    cg1, how1 = c1.get_or_compile("regex", "(a|b)c*", TOKEN_BYTES, EOS)
    assert how1 == "miss"
    cg2, how2 = c1.get_or_compile("regex", "(a|b)c*", TOKEN_BYTES, EOS)
    assert how2 == "hit" and cg2 is cg1
    c2 = GrammarCache(path)
    cg3, how3 = c2.get_or_compile("regex", "(a|b)c*", TOKEN_BYTES, EOS)
    assert how3 == "hit", "on-disk entry not found by a fresh cache"
    np.testing.assert_array_equal(cg3.trans, cg1.trans)
    np.testing.assert_array_equal(cg3.mask_words, cg1.mask_words)


def test_grammar_table_seat_release_evict():
    """Absolute-state seating: refcounted re-seat, LRU eviction of
    refcount-0 grammars under pressure, budget error when every row is
    pinned, and the all-permitted sentinel in row 0."""
    a = compile_regex("aaaa", TOKEN_BYTES, EOS)
    b = compile_regex("bbbb", TOKEN_BYTES, EOS)
    big = compile_regex("cccc", TOKEN_BYTES, EOS)
    assert big.n_states == a.n_states  # same shape, different bytes
    # capacity sized so a + b fill every non-sentinel row
    gt = GrammarTable(1 + a.n_states + b.n_states, CFG.vocab_size)
    assert gt.allows(0, 5) and gt.advance(0, 5) == 0  # sentinel
    sa = gt.seat(a)
    assert sa >= 1
    assert gt.seat(a) == sa, "re-seat must return the same start"
    v0 = gt.version
    gt.release(a.key)
    gt.release(a.key)
    # refcount 0 but still seated: rows stay until pressure evicts
    assert gt.base_of(a.key) is not None
    gt.seat(b)
    gt.seat(big)  # must evict a (refcount 0) to fit
    assert gt.base_of(a.key) is None
    assert gt.version > v0
    # everything pinned now: one more grammar cannot fit
    with pytest.raises(GrammarBudgetError):
        gt.seat(compile_regex("dddd", TOKEN_BYTES, EOS))
    # a DFA larger than capacity - 1 is over budget outright
    with pytest.raises(GrammarBudgetError):
        gt.seat(compile_regex("e" * (gt.capacity + 4),
                              TOKEN_BYTES, EOS))


def test_stop_matcher_holdback_and_flush():
    """Tokens that could begin a stop match are held back; a match
    drops the held tokens and reports the stripped length; flush
    releases the hold-back on other terminations."""
    m = StopMatcher([[5, 6]])
    assert m.push(1) == ([1], 0)
    assert m.push(5) == ([], 0), "possible stop prefix must be held"
    assert m.push(6) == ([], 2), "match strips the stop sequence"
    m2 = StopMatcher([[5, 6]])
    m2.push(5)
    assert m2.push(7) == ([5, 7], 0), "failed prefix is released"
    m3 = StopMatcher([[5, 6]])
    m3.push(5)
    assert m3.flush() == [5]


def test_request_field_validation():
    p = np.arange(4, dtype=np.int32)
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, temperature=-0.5)
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, top_k=0)
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, top_p=0.0)
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, logit_bias={i: 1.0 for i in range(9)})
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, stop=[[1]] * 5)
    with pytest.raises(AdmissionError):
        Request(prompt=p, max_new=2, response_format={"type": "nope"})
    r = Request(prompt=p, max_new=2, top_logprobs=3)
    assert r.logprobs, "top_logprobs must imply logprobs"
    assert r.uses_sampling_surface
    assert not Request(prompt=p, max_new=2).uses_sampling_surface


# -- admission gates -----------------------------------------------------


def test_plain_engine_rejects_surface_requests():
    eng = _engine()
    with pytest.raises(AdmissionError):
        eng.submit(Request(prompt=np.arange(4, dtype=np.int32),
                           max_new=2, top_p=0.5))


def test_constrained_requires_eos_token():
    eng = _surface()
    with pytest.raises(AdmissionError):
        eng.submit(Request(
            prompt=np.arange(4, dtype=np.int32), max_new=4,
            response_format={"type": "regex", "regex": "(yes|no)"},
        ))


def test_approx_top_k_with_surface_raises():
    """lax.approx_max_k reorders ties and has no traced-k variant, so
    asking for both is an error at construction, not a surface that is
    silently off."""
    with pytest.raises(ValueError, match="approx_top_k"):
        _engine(surface=True, temperature=0.9, top_k=8,
                approx_top_k=True)


def test_compile_budget_overflow_rejected():
    """A grammar whose DFA exceeds the table budget 400s at submit and
    is counted as a compile error — the engine stays healthy."""
    eng = _surface(grammar_states=8)
    with pytest.raises(AdmissionError):
        eng.submit(Request(
            prompt=np.arange(4, dtype=np.int32), max_new=8,
            eos_token=EOS,
            response_format={"type": "regex", "regex": "a" * 64},
        ))
    assert eng.metrics._c_grammar_compiles.value(result="error") == 1
    # the engine still serves after the rejection
    got = _run(eng, _requests(n=2))
    assert len(got) == 2


def test_compile_cache_hit_miss_metrics():
    eng = _surface()
    rf = {"type": "regex", "regex": "(yes|no)"}
    reqs = [Request(id=f"c{i}", prompt=np.arange(4, dtype=np.int32),
                    max_new=8, eos_token=EOS, response_format=rf)
            for i in range(3)]
    _run(eng, reqs)
    m = eng.metrics._c_grammar_compiles
    assert m.value(result="miss") == 1
    assert m.value(result="hit") == 2


# -- tentpole: unconstrained byte parity through the masked family -------


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_unconstrained_byte_parity(temperature):
    """Plain traffic through a surface engine is byte-identical to the
    plain engine — every fold-out (state 0, no bias, default sampler)
    is exact, greedy and sampled."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _surface(temperature=temperature)
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng._masked_step_fns, "masked family never dispatched"


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_unconstrained_piggyback_parity(temperature):
    """Surface + chunked-prefill piggyback: the masked piggyback
    program keeps both parity bars at once."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _surface(temperature=temperature, piggyback=True)
    assert eng._piggyback
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    assert eng.metrics.n_prefill_chunks > 0
    assert eng._masked_piggyback_fns, "masked piggyback never compiled"


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("horizon", [1, 4])
def test_unconstrained_parity_grid(temperature, paged, horizon):
    """The heavy grid: K∈{1,4} x paged on/off x greedy/sampled."""
    kw = dict(temperature=temperature, decode_horizon=horizon)
    if paged:
        kw.update(paged=True, block_size=8)
    reqs = _requests()
    ref = _run(_engine(**kw), _clone(reqs))
    eng = _surface(**kw)
    if paged:
        assert eng._paged
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)


@needs_2_devices
@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_tp2_parity_and_constrained(temperature):
    """TP=2 surface engine vs single-chip plain engine: same bytes for
    plain traffic, and constrained requests stay valid under TP."""
    reqs = _requests()
    ref = _run(_engine(temperature=temperature), _clone(reqs))
    eng = _surface(temperature=temperature, tp=2)
    assert eng.tp == 2
    got = _run(eng, _clone(reqs))
    _assert_same(ref, got)
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=12,
                eos_token=EOS,
                response_format={"type": "regex", "regex": "(yes|no)"})
    res = _run(eng, [r])
    assert _decode(_generated(res[r.id], r)) in ("yes", "no")


# -- constrained decoding ------------------------------------------------


@pytest.mark.parametrize("temperature", [None, 0.9])
def test_constrained_tokens_all_dfa_permitted(temperature):
    """Every emitted token of a constrained stream is permitted by the
    DFA at its state, and the stream ends in an accepting state —
    greedy and sampled."""
    eng = _surface(temperature=0.0)
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=20,
                eos_token=EOS, temperature=temperature,
                response_format={"type": "regex",
                                 "regex": "(yes|no|maybe)!?"})
    res = _run(eng, [r])
    toks = _generated(res[r.id], r)
    assert toks, "constrained stream emitted nothing"
    cg = r._grammar
    s = cg.start
    for t in toks:
        assert cg.trans[s, t] >= 0, f"token {t} not permitted at {s}"
        s = int(cg.trans[s, t])
    assert cg.accepting[s]
    assert _decode(toks) in ("yes", "no", "maybe",
                             "yes!", "no!", "maybe!")


@pytest.mark.parametrize("temperature", [None, 0.9])
def test_constrained_json_schema_parses_and_validates(temperature):
    schema = {
        "type": "object",
        "properties": {
            "ok": {"type": "boolean"},
            "tag": {"enum": ["a", "bb"]},
        },
        "required": ["ok", "tag"],
    }
    eng = _surface(temperature=0.0)
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=30,
                eos_token=EOS, temperature=temperature,
                response_format={"type": "json_schema",
                                 "schema": schema})
    res = _run(eng, [r])
    value = json.loads(_decode(_generated(res[r.id], r)))
    assert validate_json_value(value, schema)


@pytest.mark.slow
@pytest.mark.parametrize("temperature", [None, 0.9])
def test_twenty_seeded_schemas_validate(temperature):
    """20 seeded schemas from the supported subset, decoded greedy AND
    sampled — every output parses as JSON and validates."""
    rng = np.random.default_rng(7)

    def rand_leaf():
        kind = rng.integers(0, 4)
        if kind == 0:
            return {"type": "boolean"}
        if kind == 1:
            n = int(rng.integers(2, 4))
            return {"enum": [
                "".join(chr(97 + int(c))
                        for c in rng.integers(0, 26, rng.integers(1, 4)))
                for _ in range(n)
            ]}
        if kind == 2:
            return {"const": int(rng.integers(0, 100))}
        return {"type": "null"}

    def rand_schema():
        props = {}
        for j in range(int(rng.integers(1, 3))):
            name = "".join(chr(97 + int(c))
                           for c in rng.integers(0, 26, 2)) + str(j)
            if rng.integers(0, 4) == 0:
                props[name] = {"type": "array", "items": rand_leaf(),
                               "minItems": 1, "maxItems": 2}
            else:
                props[name] = rand_leaf()
        return {"type": "object", "properties": props,
                "required": list(props)}

    schemas = [rand_schema() for _ in range(20)]
    eng = _surface(temperature=0.0, max_total=64)
    reqs = [
        Request(id=f"s{i}", prompt=np.arange(3, dtype=np.int32),
                max_new=52, eos_token=EOS, temperature=temperature,
                response_format={"type": "json_schema", "schema": sc})
        for i, sc in enumerate(schemas)
    ]
    res = _run(eng, reqs)
    for r, sc in zip(reqs, schemas):
        value = json.loads(_decode(_generated(res[r.id], r)))
        assert validate_json_value(value, sc), (sc, value)


# -- sampling controls ---------------------------------------------------


def test_stop_sequence_truncates_exactly():
    """A stop sequence taken from the greedy reference stream truncates
    the output right before the match and counts a stop hit."""
    eng = _engine()
    base = Request(id="b", prompt=np.arange(8, dtype=np.int32),
                   max_new=8)
    ref = _generated_plain(_run(eng, [base])["b"], base)
    assert len(ref) == 8
    stop = ref[3:5]
    # truncation point = FIRST occurrence of the pair in the stream
    # (greedy streams may repeat)
    cut = next(i for i in range(len(ref) - 1)
               if ref[i:i + 2] == stop)
    eng2 = _surface()
    r = Request(id="s", prompt=np.arange(8, dtype=np.int32),
                max_new=8, stop=[stop])
    got = _generated_plain(_run(eng2, [r])["s"], r)
    assert got == ref[:cut], "stream must end right before the match"
    assert eng2.metrics._c_stop_hits.value() == 1


def _generated_plain(res, req):
    return [int(t) for t in np.asarray(res)[len(req.prompt):]]


def test_logit_bias_forces_token():
    eng = _surface()
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=5,
                logit_bias={7: 1000.0})
    got = _generated_plain(_run(eng, [r])[r.id], r)
    assert got == [7] * 5


def test_logprobs_records():
    """Per-token logprobs ride the packed aux tensor: one record per
    generated token, chosen-token logprob equals the top alternative
    under greedy, alternatives sorted descending."""
    eng = _surface()
    r = Request(prompt=np.arange(4, dtype=np.int32), max_new=6,
                logprobs=True, top_logprobs=3)
    got = _generated_plain(_run(eng, [r])[r.id], r)
    recs = r.logprobs_out
    assert recs is not None and len(recs) == len(got) == 6
    for tok, rec in zip(got, recs):
        assert rec["token"] == tok
        assert rec["logprob"] <= 0.0
        tops = rec["top_logprobs"]
        assert len(tops) == 3
        lps = [t["logprob"] for t in tops]
        assert lps == sorted(lps, reverse=True)
        # greedy: the chosen token IS the argmax
        assert tops[0]["token"] == tok
        assert tops[0]["logprob"] == pytest.approx(rec["logprob"])


def test_per_request_temperature_and_topk_override():
    """temperature=0 / top_k=1 overrides on a sampled engine reproduce
    the greedy engine's bytes — the traced per-slot vectors really
    steer the draw."""
    ref_eng = _engine(temperature=0.0)
    reqs = _requests(n=4)
    ref = _run(ref_eng, _clone(reqs))
    eng = _surface(temperature=0.9)
    greedy = [Request(id=r.id, prompt=np.asarray(r.prompt).copy(),
                      max_new=r.max_new, temperature=0.0)
              for r in reqs]
    _assert_same(ref, _run(eng, greedy))
    eng2 = _surface(temperature=0.9)
    topk1 = [Request(id=r.id, prompt=np.asarray(r.prompt).copy(),
                     max_new=r.max_new, top_k=1)
             for r in reqs]
    _assert_same(ref, _run(eng2, topk1))


def test_top_p_nucleus_collapses_to_greedy():
    """A vanishingly small top_p keeps only the argmax in the nucleus,
    so a sampled request reproduces greedy bytes."""
    ref = _run(_engine(temperature=0.0), _requests(n=4))
    eng = _surface(temperature=0.9)
    reqs = [Request(id=f"r{i}", prompt=r.prompt, max_new=r.max_new,
                    top_p=1e-9)
            for i, r in enumerate(_requests(n=4))]
    _assert_same(ref, _run(eng, reqs))


# -- crash recovery ------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("crash_at", [2, 4])
def test_crash_recovery_constrained_byte_parity(temperature, crash_at):
    """Seeded crash mid-generation with constrained + stop + bias +
    logprobs traffic in flight: recovery re-seats FSM states (replayed
    from gstate0 over the emitted prefix), stop buffers, and bias rows,
    and the streams are byte-identical to the no-fault run."""
    schema = {"type": "object",
              "properties": {"k": {"enum": ["x", "yy", "zzz"]}},
              "required": ["k"]}

    def make_reqs():
        reqs = _requests(n=4, max_new=8)
        reqs.append(Request(
            id="cons", prompt=np.arange(4, dtype=np.int32), max_new=20,
            eos_token=EOS, temperature=temperature or None,
            response_format={"type": "json_schema", "schema": schema},
        ))
        reqs.append(Request(
            id="bias", prompt=np.arange(6, dtype=np.int32), max_new=6,
            logit_bias={9: 5.0}, logprobs=True,
        ))
        return reqs

    ref = _run(_surface(temperature=temperature), make_reqs())
    faults = FaultInjector().plan("step", crash_at, "crash")
    eng = _surface(temperature=temperature, faults=faults)
    got = _run(eng, make_reqs(), max_restarts=5)
    _assert_same(ref, got)
    assert eng.metrics.n_restarts >= 1, "crash never fired"
    value = json.loads(_decode(_generated(
        got["cons"],
        Request(id="x", prompt=np.arange(4, dtype=np.int32),
                max_new=20, eos_token=EOS),
    )))
    assert validate_json_value(value, schema)


# -- compile surface ------------------------------------------------------


def test_masked_compile_surface_bounded():
    """The live masked families stay inside the audited expected
    surface for the same geometry."""
    from deeplearning4j_tpu.analysis.programs import (
        ServingGeometry,
        expected_surface,
        live_engine_families,
    )

    eng = _surface(piggyback=True)
    _run(eng, _requests())
    geom = ServingGeometry(
        n_slots=eng.n_slots, max_total=eng.max_total,
        temperature=eng.temperature, top_k=eng.top_k,
        approx_top_k=eng.approx_top_k,
        decode_horizon=eng.decode_horizon, adaptive_horizon=True,
        prefill_max_bucket=eng._max_bucket,
        sampling_surface=True,
    )
    exp = expected_surface(CFG, geom)
    live = live_engine_families(eng)
    assert live["masked_step"], "no masked program ever compiled"
    assert live["masked_step"] <= exp["masked_step"]
    assert live["masked_piggyback_step"] <= exp["masked_piggyback_step"]
    assert live["paged_masked_step"] == set()
    assert "gstate_set" in exp["singletons"]
