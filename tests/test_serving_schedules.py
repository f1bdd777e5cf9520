"""What a schedule may change, decided here and not by the running server.

Until PR 29 ``ServingEngine`` ran nine bitwise probes at construction
and at run time, and switched a feature off in silence when one said
no. The engine now does what its arguments say; what each probe
compared is compared here, on the production programs over scratch
state, and a difference fails a test.

1. **Only the scheduling differs** — the fused piggyback program
   against step + chunk, the masked step on neutral surface state
   against the plain step, the paged step against the slab step,
   adapter 0 of a LoRA bank against no bank, a KV segment through the
   wire against a local prefill. Same arithmetic, so bitwise.
2. **The arithmetic's order differs** — a prefix hit's chunk-computed
   suffix against one full prefill, a batched prefill against serial
   ones, chunked against stepwise crash replay, sharded against
   single-chip reductions. Float32 toy models on XLA:CPU: logits and
   cache rows within ``TOL`` of the leaf's largest entry, the feature
   on against off (the off side is what ``tests/test_serving.py`` pins
   byte for byte to ``transformer_generate``). The token streams of the
   same pairs are compared, unloosened, in ``test_serving_prefix.py``,
   ``test_serving_faults.py`` and ``test_serving_tp.py``.
3. **What is missing is an error at construction**, by name.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    full_cache_leaf,
    init_lora_bank,
    init_transformer,
    make_paged_fwd1,
    paged_slot_scatter,
)
from deeplearning4j_tpu.serving import Request, ServingEngine
from deeplearning4j_tpu.serving.disagg import (
    decode_segment,
    encode_segment,
    slab_to_blocks,
)
from deeplearning4j_tpu.serving.engine import _NO_EOS
from deeplearning4j_tpu.serving.grammar import MAX_LOGIT_BIAS

#: largest |on - off| over a leaf, as a share of the leaf's largest
#: entry. Measured on XLA:CPU (jax 0.9.0, float32, the toy model below):
#: prefix hit 3.2e-7, batched admission 1.9e-7, chunked replay 5.2e-7,
#: tp=2 2.5e-7: two to four float32 roundings of the largest entry.
#: tests/test_gated_stack.py starts from 2e-4; this is six times the
#: largest measured.
TOL = 3e-6

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_len=32, decode_kernel=False,
)
_PARAMS = []


def _params():
    if not _PARAMS:
        _PARAMS.append(init_transformer(jax.random.key(0), CFG))
    return _PARAMS[0]


def _engine(**kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("decode_horizon", 2)
    return ServingEngine(
        CFG, _params(), retry_backoff_s=0.001, max_backoff_s=0.004, **kw
    )


def _seq(n, start=1):
    return ((start + np.arange(n)) % CFG.vocab_size).astype(np.int32)


def _scratch_state(eng):
    """A pool-shaped device state over freshly zeroed buffers: the
    production programs run on it, the live pool is not touched."""
    n = eng.n_slots
    return (
        eng._init_caches(n, eng.max_total),
        jnp.zeros((n, CFG.vocab_size), jnp.float32),
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), bool),
        jnp.zeros((n,), jnp.int32),
        jnp.full((n,), _NO_EOS, jnp.int32),
    )


def _decode_state(eng):
    """Every slot live at a small position over patterned logits (each
    side of a comparison gets fresh buffers: the programs donate)."""
    n, vs = eng.n_slots, CFG.vocab_size
    if eng._paged:
        # sentinel-only tables: every row scatters to block 0
        # identically on both sides
        caches = {
            "blocks": jax.tree.map(jnp.zeros_like, eng.pool.caches),
            "tables": jnp.zeros((n, eng.pool.blocks_per_slot), jnp.int32),
        }
    else:
        caches = eng._init_caches(n, eng.max_total)
    return (
        caches,
        jnp.arange(n * vs, dtype=jnp.float32).reshape(n, vs) % 7.0,
        jnp.arange(n, dtype=jnp.int32) % 3,
        jnp.ones((n,), bool),
        jnp.full((n,), 5, jnp.int32),
        jnp.full((n,), _NO_EOS, jnp.int32),
    )


def _slot_keys(eng):
    return jnp.asarray(np.arange(
        eng._slot_keys.size, dtype=eng._slot_keys.dtype
    ).reshape(eng._slot_keys.shape))


def _slot_rows(caches, slot, n):
    return [np.asarray(leaf[:, :, slot, :n])
            for leaf in jax.tree.leaves(caches)]


def _assert_bitwise(x, y):
    xs, ys = jax.tree.leaves(x), jax.tree.leaves(y)
    assert len(xs) == len(ys)
    for a, b in zip(xs, ys):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_close(x, y):
    """Float leaves within TOL of the reference leaf's largest entry;
    positions, masks, budgets and tokens equal."""
    xs, ys = jax.tree.leaves(x), jax.tree.leaves(y)
    assert len(xs) == len(ys)
    for a, b in zip(xs, ys):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == bool or np.issubdtype(a.dtype, np.integer):
            np.testing.assert_array_equal(a, b)
        else:
            b = b.astype(np.float32)
            np.testing.assert_allclose(
                a.astype(np.float32), b, rtol=0,
                atol=TOL * float(np.max(np.abs(b))),
            )


# -- 1. only the scheduling differs: bitwise ------------------------------


def _piggyback_against_step_and_chunk(paged):
    """The fused chunk+decode program against the production step and
    chunk programs run separately over identical inputs: every
    decode-state leaf, the token block, the scratch slab, the chunk's
    logits row."""
    eng = _engine(piggyback=True, prefill_max_bucket=8, paged=paged)
    b, k = eng._max_bucket, eng.decode_horizon
    ad = jnp.zeros((eng.n_slots,), jnp.int32)
    ctoks = jnp.asarray(_seq(b)[None, :])
    cad = jnp.zeros((1,), jnp.int32)
    out_a = eng._step_fn_for(k)(
        eng.params, *_decode_state(eng), _slot_keys(eng), ad
    )
    tmp_a, lg_a = eng._chunk_fn(b)(
        eng.params, eng._init_caches(1, eng.max_total), ctoks,
        jnp.int32(0), jnp.int32(b - 1), cad,
    )
    out_b = eng._piggyback_fn(b, k)(
        eng.params, *_decode_state(eng), _slot_keys(eng), ad,
        eng._init_caches(1, eng.max_total), ctoks,
        jnp.int32(0), jnp.int32(b - 1), cad,
    )
    _assert_bitwise(out_a, out_b[:6])
    _assert_bitwise(tmp_a, out_b[6])
    _assert_bitwise(lg_a, out_b[7])


def _masked_against_plain_step():
    """The masked step (grammar mask, logit bias, per-slot temperature /
    top-k / top-p, logprob gathers, all at their neutral values) against
    the plain step, and the masked piggyback program against the plain
    one: every decode-state leaf and the tokens equal, the FSM state
    held at the unconstrained sentinel."""
    eng = _engine(sampling_surface=True, piggyback=True,
                  prefill_max_bucket=8, temperature=0.9, top_k=8)
    n, k, b = eng.n_slots, eng.decode_horizon, eng._max_bucket
    ad = jnp.zeros((n,), jnp.int32)
    # what _seat_surface writes for a request that sets nothing
    neutral = (
        jnp.full((n,), eng.temperature, jnp.float32),
        jnp.full((n,), int(eng.top_k or 0), jnp.int32),
        jnp.ones((n,), jnp.float32),
        jnp.full((n, MAX_LOGIT_BIAS), -1, jnp.int32),
        jnp.zeros((n, MAX_LOGIT_BIAS), jnp.float32),
    )
    tabs = eng._grammar_device_tables()

    def gstate():
        return jnp.zeros((n,), jnp.int32)

    out_a = eng._step_fn_for(k)(
        eng.params, *_decode_state(eng), _slot_keys(eng), ad
    )
    out_b = eng._masked_step_fn_for(k)(
        eng.params, *_decode_state(eng), gstate(), _slot_keys(eng), ad,
        *neutral, *tabs,
    )
    _assert_bitwise(out_a[:5], out_b[:5])
    _assert_bitwise(out_a[5], out_b[6][:, :, 0])
    _assert_bitwise(out_b[5], np.zeros((n,), np.int32))

    ctoks = jnp.asarray(_seq(b)[None, :])
    cad = jnp.zeros((1,), jnp.int32)
    chunk = (jnp.int32(0), jnp.int32(b - 1), cad)
    out_c = eng._piggyback_fn(b, k)(
        eng.params, *_decode_state(eng), _slot_keys(eng), ad,
        eng._init_caches(1, eng.max_total), ctoks, *chunk,
    )
    out_d = eng._masked_piggyback_fn(b, k)(
        eng.params, *_decode_state(eng), gstate(), _slot_keys(eng), ad,
        *neutral, *tabs,
        eng._init_caches(1, eng.max_total), ctoks, *chunk,
    )
    _assert_bitwise(out_c[:5], out_d[:5])
    _assert_bitwise(out_c[5], out_d[6][:, :, 0])
    _assert_bitwise(out_c[6], out_d[7])
    _assert_bitwise(out_c[7], out_d[8])


def _paged_against_slab_step():
    """The paged step (block gather, the slab compute, block scatter)
    against the slab step, batch 2 over the same prefilled rows, the
    tables SHUFFLED (blocks scattered through the pool, as after churn)
    and one block ALIASED between the rows (the shared-prefix shape):
    the logits of three greedy steps."""
    eng = _engine(paged=True, block_size=8)
    bs, total, n = eng._block_size, eng.max_total, 8
    shapes = jax.eval_shape(lambda: eng._init_caches(1, total))
    bps = full_cache_leaf(shapes).shape[3] // bs
    tmp, lg = jax.jit(eng._do_prefill)(
        eng.params, eng._init_caches(1, total), jnp.asarray(_seq(n)[None])
    )
    slab = eng._init_caches(2, total)
    for s in (0, 1):
        slab = jax.tree.map(
            lambda c, t: c.at[:, :, s:s + 1].set(t), slab, tmp
        )
    tables = (np.random.default_rng(0).permutation(2 * bps) + 1).reshape(
        2, bps
    ).astype(np.int32)
    tables[1, 0] = tables[0, 0]
    blocks = jax.tree.map(
        lambda sh: jnp.zeros(
            (sh.shape[0], sh.shape[1], 2 * bps + 1, bs, sh.shape[4]),
            sh.dtype,
        ),
        shapes,
    )
    dtab = jnp.asarray(tables)
    for s in (0, 1):
        blocks = paged_slot_scatter(blocks, dtab[s], tmp)
    pcaches = {"blocks": blocks, "tables": dtab}

    def greedy(fwd1):
        return jax.jit(lambda c, l, p: fwd1(
            eng.params, c, jnp.argmax(l, axis=-1).astype(jnp.int32), p
        ))

    sstep, pstep = greedy(eng._fwd1), greedy(make_paged_fwd1(eng._fwd1))
    slg = plg = jnp.concatenate([lg, lg], axis=0)
    pos = jnp.full((2,), n, jnp.int32)
    for _ in range(3):
        slg, slab = sstep(slab, slg, pos)
        plg, pcaches = pstep(pcaches, plg, pos)
        pos = pos + 1
        _assert_bitwise(slg, plg)


def _lora_adapter0_against_no_bank():
    """With the bank riding in params, adapter index 0 against the
    bank-free base model through prefill and three greedy steps: the
    forward SELECTS the base activations for adapter-0 rows
    (``jnp.where``, never ``+ 0.0``), so the logits are the same bits."""
    bank = init_lora_bank(jax.random.key(1), CFG, n_adapters=3, rank=4)
    eng = _engine(lora_bank=bank)
    total, n = eng.max_total, 8
    base = {k: v for k, v in eng.params.items() if k != "lora"}
    ad = jnp.zeros((1,), jnp.int32)

    def stream(p):
        caches, logits = jax.jit(eng._do_prefill)(
            p, eng._init_caches(1, total), jnp.asarray(_seq(n)[None]),
            adapter=ad,
        )
        out = [logits]
        pos = jnp.full((1,), n, jnp.int32)
        step = jax.jit(lambda pp, c, lg, po: eng._fwd1(
            pp, c, jnp.argmax(lg, axis=-1).astype(jnp.int32), po,
            adapter=ad,
        ))
        for _ in range(3):
            logits, caches = step(p, caches, logits, pos)
            pos = pos + 1
            out.append(logits)
        return out

    _assert_bitwise(stream(base), stream(eng.params))


def _wire_against_local_prefill(paged):
    """A segment moved prefill -> seg_store -> a real
    ``encode_segment`` / ``decode_segment`` byte round trip -> device
    import -> zero-prefill hit insert, against the direct prefill: KV
    rows and logits. A paged engine also pushes the slab through the
    block scatter / gather pair its ingest uses."""
    eng = _engine(prefix_cache=True, paged=paged)
    n = min(eng._min_bucket + 3, eng.max_total - 1, eng.pool.tpad)
    seq = _seq(n)
    sa = eng._prefill_into_state(_scratch_state(eng), seq, 0, 1, _NO_EOS)
    rows_a, lg_a = _slot_rows(sa[0], 0, n), np.asarray(sa[1][0])
    region = eng._seg_store()(
        eng.pool.alloc_region(1), sa[0], jnp.int32(0), jnp.int32(0)
    )
    leaves = [np.asarray(leaf) for leaf in jax.tree.leaves(region)]
    lg = np.asarray(eng._logit_row()(sa[1], jnp.int32(0)))
    frame = encode_segment(
        config_hash=eng.config_hash, tokens=seq,
        leaves=(slab_to_blocks(leaves, eng._block_size) if paged
                else leaves),
        logits=lg, layout="paged" if paged else "slab",
        block_size=eng._block_size if paged else 0,
    )
    dec = decode_segment(frame, expect_hash=eng.config_hash)
    slab = eng._wire_slab(dec)
    if paged:
        bs = eng._block_size
        bps = eng.pool.tpad // bs
        blocks = jax.tree.map(
            lambda sh: jnp.zeros(
                (sh.shape[0], sh.shape[1], bps + 1, bs, sh.shape[4]),
                sh.dtype,
            ),
            jax.eval_shape(lambda: eng._init_caches(1, eng.max_total)),
        )
        row = jnp.asarray(np.arange(1, bps + 1, dtype=np.int32))
        blocks = eng._paged_seg_import()(blocks, row, slab)
        slab = eng._paged_seg_fetch()(blocks, row)
    region2 = eng._seg_import()(
        eng.pool.alloc_region(1), slab, jnp.int32(0)
    )
    sc = eng._hit_insert()(
        *_scratch_state(eng), region2, jnp.asarray(dec["logits"]),
        jnp.int32(0), jnp.int32(0), jnp.int32(n), jnp.int32(1),
        jnp.int32(_NO_EOS),
    )
    _assert_bitwise(rows_a, _slot_rows(sc[0], 0, n))
    _assert_bitwise(lg_a, sc[1][0])


@pytest.mark.parametrize("compare", [
    pytest.param(lambda: _piggyback_against_step_and_chunk(False),
                 id="piggyback"),
    pytest.param(lambda: _piggyback_against_step_and_chunk(True),
                 id="piggyback-paged"),
    pytest.param(_masked_against_plain_step, id="masked-step"),
    pytest.param(_paged_against_slab_step, id="paged-step"),
    pytest.param(_lora_adapter0_against_no_bank, id="lora-adapter0"),
    pytest.param(lambda: _wire_against_local_prefill(False),
                 id="wire"),
    pytest.param(lambda: _wire_against_local_prefill(True),
                 id="wire-paged"),
])
def test_rescheduling_alone_changes_no_bit(compare):
    compare()


# -- 2. the arithmetic's order differs: a tolerance -----------------------


def _prefix_hit_against_full_prefill():
    """Copy-cached-prefix-rows + chunk-computed suffix against the full
    bucketed prefill: KV rows and logits."""
    eng = _engine(prefix_cache=True)
    L = eng._min_bucket
    n = min(L + 3, eng.max_total, eng.pool.tpad)
    assert n > L
    seq = _seq(n)
    # miss path: the full bucketed prefill
    sa = eng._prefill_into_state(_scratch_state(eng), seq, 0, 1, _NO_EOS)
    # the segment, built as insert-on-completion builds it
    sb = eng._prefill_into_state(
        _scratch_state(eng), seq[:L], 0, 1, _NO_EOS
    )
    region = eng._seg_store()(
        eng.pool.alloc_region(1), sb[0], jnp.int32(0), jnp.int32(0)
    )
    # hit path: fetch + suffix chunks + insert
    tmp = eng._seg_fetch()(region, jnp.int32(0))
    for t0, ln, b in eng._chunk_schedule(n, start=L):
        pad = np.zeros((1, b), np.int32)
        pad[0, :ln] = seq[t0:t0 + ln]
        tmp, lg = eng._chunk_fn(b)(
            eng.params, tmp, jnp.asarray(pad), jnp.int32(t0),
            jnp.int32(ln - 1), jnp.zeros((1,), jnp.int32),
        )
    sc = eng._insert()(
        *_scratch_state(eng), tmp, lg, jnp.int32(0), jnp.int32(n),
        jnp.int32(1), jnp.int32(_NO_EOS),
    )
    _assert_close(_slot_rows(sc[0], 0, n), _slot_rows(sa[0], 0, n))
    _assert_close(sc[1][0], sa[1][0])


def _batched_against_serial_admission():
    """The batched same-bucket prefill program (vector last_idx) and the
    batched partial-hit program against the serial per-request paths:
    the full device state."""
    eng = _engine(prefix_cache=True)
    n0 = eng._min_bucket
    n1 = n0 - 1
    b = eng._bucket_for(n0)
    seq0, seq1 = _seq(n0), _seq(n1, start=2)
    no_eos = jnp.asarray([_NO_EOS, _NO_EOS], np.int32)
    sa = eng._prefill_into_state(_scratch_state(eng), seq0, 0, 3, _NO_EOS)
    sa = eng._prefill_into_state(sa, seq1, 1, 2, _NO_EOS)
    prompts = np.zeros((2, b), np.int32)
    prompts[0, :n0] = seq0
    prompts[1, :n1] = seq1
    sb = eng._batch_prefill_fn(b, 2)(
        *_scratch_state(eng), eng.params, jnp.asarray(prompts),
        jnp.asarray([n0 - 1, n1 - 1], np.int32),
        jnp.asarray([0, 1], np.int32), jnp.asarray([n0, n1], np.int32),
        jnp.asarray([3, 2], np.int32), no_eos, jnp.zeros((2,), jnp.int32),
    )
    _assert_close(sb, sa)

    # two suffixes behind one cached prefix: serial fetch + chunk +
    # insert against one batched program
    L, lns = eng._min_bucket, (2, 1)
    bs = eng._bucket_for(max(lns))
    sfx = [_seq(ln, start=5 + r) for r, ln in enumerate(lns)]
    sp = eng._prefill_into_state(
        _scratch_state(eng), _seq(L, start=3), 0, 1, _NO_EOS
    )
    region = eng._seg_store()(
        eng.pool.alloc_region(1), sp[0], jnp.int32(0), jnp.int32(0)
    )
    sh = _scratch_state(eng)
    toks = np.zeros((2, bs), np.int32)
    for r, ln in enumerate(lns):
        toks[r, :ln] = sfx[r]
        tmp, lg = eng._chunk_fn(bs)(
            eng.params, eng._seg_fetch()(region, jnp.int32(0)),
            jnp.asarray(toks[r:r + 1]), jnp.int32(L), jnp.int32(ln - 1),
            jnp.zeros((1,), jnp.int32),
        )
        sh = eng._insert()(
            *sh, tmp, lg, jnp.int32(r), jnp.int32(L + ln), jnp.int32(2),
            jnp.int32(_NO_EOS),
        )
    sbh = eng._batch_hit_fn(bs, 2)(
        *_scratch_state(eng), eng.params, region,
        jnp.asarray([0, 0], np.int32), jnp.asarray(toks), jnp.int32(L),
        jnp.asarray([ln - 1 for ln in lns], np.int32),
        jnp.asarray([0, 1], np.int32),
        jnp.asarray([L + ln for ln in lns], np.int32),
        jnp.asarray([2, 2], np.int32), no_eos, jnp.zeros((2,), jnp.int32),
    )
    _assert_close(sbh, sh)


def _chunked_against_stepwise_replay():
    """Two engines crash at the same horizon with the same tokens
    recorded; one rebuilds each live slot by one bucketed prefill over
    prompt + tokens, the other by prefill + teacher-forced steps: the
    pending logits and the cache rows each slot holds after
    ``recover()``."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, (ln,)).astype(np.int32)
               for ln in (5, 9, 12)]

    def crashed(chunked):
        eng = _engine(chunked_replay=chunked)
        for i, p in enumerate(prompts):
            eng.submit(Request(prompt=p.copy(), max_new=10, id=f"r{i}"))
        for _ in range(4):
            eng.step()
        assert eng.recover() == len(prompts)
        assert eng.last_recover_mode == (
            "chunked" if chunked else "stepwise"
        )
        return eng

    on, off = crashed(True), crashed(False)
    for slot, (st_on, st_off) in enumerate(zip(on._slots, off._slots)):
        assert st_on.req.id == st_off.req.id
        assert st_on.tokens == st_off.tokens and st_on.tokens
        rows = len(st_on.req.prompt) + len(st_on.tokens)
        _assert_close(_slot_rows(on.pool.caches, slot, rows),
                      _slot_rows(off.pool.caches, slot, rows))
    _assert_close((on._logits, on._dpos, on._dactive, on._dbudget),
                  (off._logits, off._dpos, off._dactive, off._dbudget))


def _tp2_against_single_chip():
    """The sharded prefill and step programs against the single-chip
    ones on scratch state: caches, logits, positions and the greedy
    tokens of two fused substeps."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for tp=2")
    seq = _seq(8)

    def run(tp):
        eng = _engine(tp=tp)
        assert eng.tp == tp
        st = eng._prefill_into_state(
            _scratch_state(eng), seq, 0, 6, _NO_EOS
        )
        after_prefill = jax.tree.map(np.asarray, st)
        out = eng._step_fn_for(eng.decode_horizon)(
            eng.params, *st, _slot_keys(eng),
            jnp.zeros((eng.n_slots,), jnp.int32),
        )
        return after_prefill, out

    (pre2, out2), (pre1, out1) = run(2), run(1)
    _assert_close(pre2, pre1)
    _assert_close(out2, out1)


@pytest.mark.parametrize("compare", [
    pytest.param(_prefix_hit_against_full_prefill, id="prefix-hit"),
    pytest.param(_batched_against_serial_admission,
                 id="batched-admission"),
    pytest.param(_chunked_against_stepwise_replay, id="chunked-replay"),
    pytest.param(_tp2_against_single_chip, id="tp2"),
])
def test_reordered_arithmetic_stays_within_tolerance(compare):
    compare()


# -- 3. what is missing is an error at construction -----------------------


@pytest.mark.parametrize("keyword", ["chunked_replay", "batch_admission"])
def test_auto_is_refused(keyword):
    """Nothing is decided by a probe at run time any more: the two
    switches that took "auto" take True or False."""
    with pytest.raises(ValueError, match=keyword):
        _engine(**{keyword: "auto"})


def test_prefix_cache_serves_hits():
    """``prefix_cache=True`` serves a full and a partial hit: no verdict
    stands between the argument and the lookup."""
    eng = _engine(prefix_cache=True)
    a = _seq(8)
    for prompt in (a, a.copy(), np.concatenate([a, [50, 51]])):
        eng.submit(Request(prompt=prompt, max_new=3))
        eng.run()
    assert eng.metrics.n_prefix_hits_full == 1
    assert eng.metrics.n_prefix_hits_partial == 1
