"""Continuous-batching decode engine — pipelined, multi-step hot path.

Iteration-level scheduling (Orca, OSDI '22) composed with multi-step
scheduling (vLLM): instead of batching whole requests, the engine
batches DECODE STEPS — and instead of paying one dispatch + one host
sync per step, it fuses ``decode_horizon`` (K) steps into ONE jitted
program and overlaps the host side of horizon n with the device side
of horizon n+1. It owns a fixed-shape batch of ``n_slots`` KV-cache
slots (one pooled ``init_caches`` allocation, see :mod:`cache_pool`);
every ``step()``:

1. sweeps occupied slots for cancelled/deadline-expired requests and
   retires them (slot freed within one horizon boundary);
2. admits queued requests into freed slots: a per-BUCKET jitted
   prefill runs at batch 1 (the prompt right-padded to a power-of-two
   length bucket) and its cache rows are inserted into the pooled
   buffers at the slot index; prompts longer than the largest bucket
   are chunked through ``forward_chunk`` at the same bucket sizes, so
   ``_prefill_fns`` holds O(log max_len) programs no matter how many
   distinct prompt lengths traffic brings;
3. DISPATCHES one fused K-substep decode program for all slots and
   only then
4. SYNCS the PREVIOUS horizon's (slots, K) token block, doing finish
   detection / retirement / metrics while the device is already
   computing the next horizon (async double-buffered readback — the
   ``np.asarray`` sync is the one blocking host sync per horizon).

Everything the per-substep decode logic needs lives ON DEVICE and is
threaded through the programs — positions, active mask, remaining
token budget, per-slot EOS id, pending logits — so EOS/max-len
deactivation happens in-program via the active mask: a slot that
finishes mid-horizon stops advancing (its position freezes, its
sampled tokens are masked to 0) without any host round trip. The host
replays the same stopping rule when the block arrives, so host
bookkeeping and the device mask can never disagree. Host <-> device
state only meets at admission (prefill writes the slot's state) and at
crash recovery (state is rebuilt from host records).

Slot-reuse slack: because horizon n's block is synced AFTER horizon
n+1 is dispatched, a slot retired at sync time may already appear in
the in-flight horizon. Each dispatch snapshots (slot, occupant,
pool generation); a sync discards blocks whose slot has since been
retired or re-acquired (the dummy tokens a finished slot decodes are
dead by construction — the next admission's prefill insert rewrites
the whole Tpad slab).

jit stability: exactly one compiled step program per engine, one
prefill program per power-of-two bucket, one chunk program per bucket
on the long-prompt path, plus two tiny state-edit programs.

Greedy determinism: at ``temperature=0`` the engine samples via the
same ``_top_k_filter`` + argmax the plain ``transformer_generate``
path uses; the decode math is row-/padding-invariant (masked cache
rows contribute exact zeros), and a right-padded bucket prefill is
bitwise identical to an exact-length prefill at the true last row
(causal masking — pinned empirically by the parity tests), so token
streams are byte-identical to running each request alone for every
horizon K — ``tests/test_serving.py`` asserts K in {1, 2, 4, 8}.

Sampled determinism: at ``temperature > 0`` each slot gets its own
sampling key at admission (split from the engine master key in
admission order) and token ``i`` is drawn with ``fold_in(slot_key,
position_i)`` — the key stream is a pure function of (slot key,
position), independent of batch composition, horizon K, and crashes.
Persisting the key data per slot makes crash-recovery replay exact for
sampled requests too: replay teacher-forces the recorded tokens, then
sampling resumes at the next position with the next key the
uninterrupted run would have used (``tests/test_serving_faults.py``
pins byte-parity for a sampled run crashed mid-decode).

Fault tolerance (the DL4J lineage: the reference runtime supervised
its workers via Akka and rebuilt them from ZooKeeper state; here the
unit of supervision is the horizon dispatch and the durable state is
host-side). The engine consults an optional
:class:`~.faults.FaultInjector` at its two host boundaries — "step"
before each horizon dispatch, "prefill" before each admission — and
supervises itself:

- a ``TransientFault`` at a boundary retries with capped exponential
  backoff (``max_retries``/``retry_backoff_s``/``max_backoff_s``);
- a fault that PERSISTS past the retry budget, or a ``PermanentFault``,
  quarantines only the implicated request — slot freed, ``done`` set,
  status ``FAILED`` — and the batch keeps decoding;
- an ``EngineCrash`` (or any fault with no implicated request)
  abandons the device state entirely (including any un-synced
  horizon: its tokens were never recorded, so replay simply
  regenerates them); :meth:`recover` rebuilds state by DETERMINISTIC
  REPLAY. Two replay modes:

  * **stepwise** (the conservative default): re-prefill every live
    slot's original prompt through the same bucketed program as its
    admission, then TEACHER-FORCE the recorded tokens one fused step
    at a time — exactly re-tracing the crashed run's op sequence, so
    at ``temperature=0`` the resumed stream is byte-identical to an
    uninterrupted one (chaos parity tests pin this);
  * **chunked** (O(prompt/bucket + tokens/bucket) device calls per
    slot instead of O(tokens)): re-prefill ``prompt + tokens_so_far``
    in one pass through the bucketed/chunked prefill path. The
    prefill-path logits can differ from the decode-path logits in the
    last float bit (different XLA schedules), so it is what recovery
    does only when asked: ``chunked_replay=True``
    (``tests/test_serving_faults.py`` covers both modes;
    ``tests/test_serving_schedules.py`` holds the two to a tolerance).

Request lifecycle: ``Request.deadline_s`` and ``Request.cancel()`` are
checked at every horizon boundary; a timed-out or cancelled request is
retired (status EXPIRED/CANCELLED, partial stream in ``results``, KV
slot freed) instead of decoding to ``max_new``. :meth:`preempt_all`
cancels every live and queued request — the drain-deadline hook
``ServingServer.stop`` uses to converge instead of waiting out
stragglers. ``last_dispatch_t`` is a monotonic heartbeat for the
server's hung-engine watchdog.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    _top_k_filter,
    decode_rows_live,
    decode_rows_streamed,
    full_cache_leaf,
    kv_cache_rows,
    kv_row_write,
    make_paged_fwd1,
    paged_block_copy,
    paged_slot_gather,
    paged_slot_scatter,
    place_serving_tp_params,
    serving_tp_cache_sharding,
    topk_select,
)
from deeplearning4j_tpu.parallel.mesh import model_parallel_mesh
from deeplearning4j_tpu.obs import compile_log
from deeplearning4j_tpu.obs.flight import FlightRecorder
from deeplearning4j_tpu.obs.logs import log_event
from deeplearning4j_tpu.obs.profiler import ProfileTrigger
from deeplearning4j_tpu.obs.trace import (
    ENGINE_REGIONS,
    ENGINE_TRACK,
    SCHEDULER_TRACK,
    PhaseRegions,
    Tracer,
    new_span_id,
    slot_track,
)
from deeplearning4j_tpu.serving.cache_pool import KVSlotPool, PagedKVPool
from deeplearning4j_tpu.serving.disagg import (
    WireError,
    model_config_hash,
    slab_to_blocks,
)
from deeplearning4j_tpu.serving.faults import (
    EngineCrash,
    FaultInjector,
    PermanentFault,
    TransientFault,
)
from deeplearning4j_tpu.analysis.sanitizers import note_access, wrap_lock
from deeplearning4j_tpu.serving.grammar import (
    MAX_LOGIT_BIAS,
    MAX_TOP_LOGPROBS,
    GrammarCache,
    GrammarError,
    GrammarTable,
    StopMatcher,
    default_token_bytes,
    parse_response_format,
)
from deeplearning4j_tpu.serving.metrics import ServingMetrics
from deeplearning4j_tpu.serving.prefix_cache import PrefixCache, Segment
from deeplearning4j_tpu.serving.scheduler import (
    AdmissionError,
    Backpressure,
    Request,
    RequestScheduler,
    RequestStatus,
)
from deeplearning4j_tpu.serving.tenancy import QuotaExceeded

#: device EOS id for requests without one (never equals a sampled token)
_NO_EOS = -1

_log = logging.getLogger(__name__)


#: Declared donation intent per program family: the argnums each
#: family donates, on every backend (XLA:CPU honours donation too, so
#: the CPU suite runs the programs the chip runs and a handle kept
#: across a dispatch fails there first). This table IS
#: the contract the static donation audit (analysis/audit.py) checks
#: against each family's traced avals: every donated argument must be
#: consumable by an output of matching shape/dtype, or the donation is
#: dead weight ("donation not used") and the cache stops updating in
#: place.
PROGRAM_DONATION: dict[str, tuple[int, ...]] = {
    # step/replay thread the pooled caches + per-slot device state
    "step": (1, 2, 3, 4, 5),          # caches, logits, pos, active, budget
    "replay": (1, 2),                 # caches, logits
    "deactivate": (0,),               # active mask
    # admission programs donate the pool state sextuple
    "prefill": (0, 1, 2, 3, 4, 5),
    "insert": (0, 1, 2, 3, 4, 5),
    "hit_insert": (0, 1, 2, 3, 4, 5),
    "batch_prefill": (0, 1, 2, 3, 4, 5),
    "batch_hit": (0, 1, 2, 3, 4, 5),
    # segment store replaces the region functionally
    "seg_store": (0,),
    # wire-segment import lands a host-uploaded slab the same way
    "seg_import": (0,),
    # pure reads
    "chunk": (),
    "seg_fetch": (),
    "logit_row": (),
    # paged families: the caches argument is the {"blocks", "tables"}
    # dict; donating it donates both leaves — the tables leaf is
    # consumed by the identity pass-through output, blocks by the
    # scattered blocks output
    "paged_step": (1, 2, 3, 4, 5),
    "paged_replay": (1, 2),
    "paged_prefill": (0, 1, 2, 3, 4, 5),
    "paged_insert": (0, 1, 2, 3, 4, 5),
    "block_copy": (0,),
    "paged_seg_fetch": (),
    "paged_seg_import": (0,),
    # piggyback: decode state donated exactly as "step" (argnums
    # 1..5), plus the admitting slot's chunk scratch slab (argnum 9),
    # consumed by the fused program's updated-scratch output
    "piggyback_step": (1, 2, 3, 4, 5, 9),
    "paged_piggyback_step": (1, 2, 3, 4, 5, 9),
    # masked step (grammar-constrained decoding + per-request sampling
    # surface): decode state donated as "step" (argnums 1..5) plus the
    # per-slot grammar FSM state vector (argnum 7), consumed by the
    # program's advanced-state output. The mask/transition tables are
    # NOT donated — they are reused across dispatches and shared with
    # the host mirror.
    "masked_step": (1, 2, 3, 4, 5, 7),
    "paged_masked_step": (1, 2, 3, 4, 5, 7),
    # masked piggyback adds the admitting slot's chunk scratch slab
    # (argnum 17), as "piggyback_step" donates its argnum 9
    "masked_piggyback_step": (1, 2, 3, 4, 5, 7, 17),
    "paged_masked_piggyback_step": (1, 2, 3, 4, 5, 7, 17),
    # single-slot grammar-state seat (admission), like "deactivate"
    "gstate_set": (0,),
}


# -- program-family factories ----------------------------------------------
#
# Every compiled program the engine can emit is built by one of these
# module-level factories. The engine's jit caches call them with its
# own closures; the program-surface registry (analysis/programs.py)
# calls the SAME factories with abstract avals — so the audited
# programs are the live programs by construction, not by transcription.


#: rows a counting ``fwd1`` adds under a horizon's token block
MOE_COUNT_ROWS = 3


def tallied(fwd1):
    """``(fwd1, ride)`` for ONE trace of a step family. A ``fwd1`` that
    counts its expert layers (``fwd1.counts_moe``: the gated stack's)
    is called with a list that collects each substep's int32 (3,)
    counters, and ``ride(block)`` appends them as ``MOE_COUNT_ROWS``
    rows under the horizon's (slots, K[, width]) token block, so they
    come back with the one readback a horizon has. Any other ``fwd1``
    comes back as it is with an identity ``ride``: the program is the
    one it was."""
    if not getattr(fwd1, "counts_moe", False):
        return fwd1, lambda block: block
    counts = []

    def counting(*args, **kwargs):
        return fwd1(*args, stats=counts, **kwargs)

    def ride(block):
        rows = jnp.stack(counts, axis=1).astype(block.dtype)  # (3, K)
        if block.ndim == 3:  # the masked families' aux block: column 0
            rows = jnp.zeros(
                rows.shape + block.shape[2:], block.dtype
            ).at[:, :, 0].set(rows)
        return jnp.concatenate([block, rows], axis=0)

    return counting, ride


def build_step_program(fwd1, horizon: int, temperature: float,
                       top_k: int | None, approx_top_k: bool):
    """K fused decode substeps in one program. The carry — caches,
    pending logits, positions, active mask, remaining budget — lives
    entirely on device; ``eos`` is per-slot data. The chain is unrolled
    (not ``lax.scan``) so XLA keeps in-place cache updates; the layer
    loop inside ``fwd1`` is already unrolled for the same reason."""

    def step(params, caches, logits, pos, active, budget, eos,
             slot_keys_raw, adapters):
        # per-slot keys (raw uint32 rows, host-persisted): token i
        # of slot s is sampled with fold_in(key_s, position) — a
        # pure function of the slot's admission key and its stream
        # position, so the key stream is invariant to batch
        # composition, horizon K, and crash-recovery replay
        keys = (
            jax.random.wrap_key_data(slot_keys_raw)
            if temperature != 0 else None
        )
        toks_all = []
        fwd, ride = tallied(fwd1)
        for k in range(horizon):
            filt = _top_k_filter(logits, top_k, approx_top_k)
            if temperature == 0:
                toks = jnp.argmax(filt, axis=-1).astype(jnp.int32)
            else:
                tok_keys = jax.vmap(jax.random.fold_in)(keys, pos)
                toks = jax.vmap(
                    lambda kk, lg: jax.random.categorical(kk, lg)
                )(tok_keys, filt / temperature).astype(jnp.int32)
            # inactive slots decode token 0 at their frozen
            # position — shape stability. Where the decode kernel
            # places the rows (kv_row_write "kernel") they write
            # nothing; where XLA does (int8 cache, dense path) the
            # garbage row they write stays inside their own slab and
            # is wiped by the next admission's prefill insert
            toks = jnp.where(active, toks, 0)
            new_logits, caches = fwd(
                params, caches, toks, pos, adapter=adapters,
                active=active,
            )
            # advance only live slots, then deactivate in-program:
            # a slot that just emitted EOS or spent its budget
            # stops mutating for the rest of the horizon
            pos = jnp.where(active, pos + 1, pos)
            budget = jnp.where(active, budget - 1, budget)
            active = active & (toks != eos) & (budget > 0)
            logits = new_logits
            toks_all.append(toks)
        return (caches, logits, pos, active, budget,
                ride(jnp.stack(toks_all, axis=1)))

    return step


def build_replay_program(fwd1):
    """Teacher-forced decode step for stepwise crash recovery: feed
    RECORDED tokens (no sampling) and freeze the pending-logits rows of
    slots whose recording is already exhausted — those rows must stay
    exactly what the slot's last real step produced."""

    def rstep(params, caches, logits, toks, pos, replaying, adapters):
        new_logits, caches = fwd1(
            params, caches, toks, pos, adapter=adapters
        )
        logits = jnp.where(replaying[:, None], new_logits, logits)
        return caches, logits

    return rstep


def build_deact_program():
    """Single-slot deactivation: flip one row of the device-resident
    active mask (retirement between horizons)."""
    return lambda active, slot: active.at[slot].set(False)


def build_prefill_program(do_prefill, init_caches, max_total: int):
    """Fused admission program for one prompt bucket: prefill-at-
    batch-1 over the padded prompt, slab insert at the slot index, and
    the slot's device state (pos/active/budget/eos + pending logits)
    set in the same dispatch."""

    def prefill(caches, logits, pos, active, budget, eos, params,
                prompt, last_idx, slot, pos0, max_new, eos_tok,
                adapter):
        # batch-1 prefill into a scratch single-slot cache of the
        # SAME Tpad as the pool, then insert the slab at the slot
        # index. The slab copy includes the zero rows beyond the
        # prompt — that wipes the previous occupant's rows, so no
        # stale state survives reuse. ``last_idx`` points at the true
        # last prompt row; the padded rows are causally invisible to
        # it, so the logits are bitwise those of an exact-length
        # prefill.
        tmp, lg = do_prefill(
            params, init_caches(1, max_total), prompt,
            last_idx=last_idx, adapter=adapter,
        )
        caches = jax.tree.map(
            lambda c, t: lax.dynamic_update_slice(
                c, t, (0, 0, slot, 0, 0)
            ),
            caches, tmp,
        )
        logits = lax.dynamic_update_slice(logits, lg, (slot, 0))
        pos = pos.at[slot].set(pos0)
        active = active.at[slot].set(True)
        budget = budget.at[slot].set(max_new)
        eos = eos.at[slot].set(eos_tok)
        return caches, logits, pos, active, budget, eos

    return prefill


def build_chunk_program(fwd_chunk):
    """Chunk-at-offset program for the long-prompt path: one
    ``forward_chunk`` pass over the bucket's rows of a batch-1 scratch
    cache, returning the (1, V) logits at ``last_idx``."""

    def chunk(params, tmp, toks, pos0, last_idx, adapter):
        lg, tmp = fwd_chunk(
            params, tmp, toks, pos0, last_idx=last_idx,
            adapter=adapter,
        )
        return tmp, lg

    return chunk


def build_piggyback_program(fwd1, fwd_chunk, horizon: int,
                            temperature: float, top_k: int | None,
                            approx_top_k: bool):
    """Chunked-prefill piggyback (Sarathi-style): K fused decode
    substeps for the active slots AND one bounded prefill chunk for an
    admitting slot, in a single dispatch. The decode leg is the
    ``build_step_program`` body verbatim; the chunk leg is the
    ``build_chunk_program`` body verbatim, over the admitting slot's
    OWN batch-1 scratch cache — the two legs share no buffers, so
    fusing them cannot perturb either side's numerics
    (``tests/test_serving_schedules.py`` compares the two bitwise)."""

    def pstep(params, caches, logits, pos, active, budget, eos,
              slot_keys_raw, adapters, tmp, ctoks, cpos0, clast,
              cadapter):
        keys = (
            jax.random.wrap_key_data(slot_keys_raw)
            if temperature != 0 else None
        )
        toks_all = []
        fwd, ride = tallied(fwd1)
        for k in range(horizon):
            filt = _top_k_filter(logits, top_k, approx_top_k)
            if temperature == 0:
                toks = jnp.argmax(filt, axis=-1).astype(jnp.int32)
            else:
                tok_keys = jax.vmap(jax.random.fold_in)(keys, pos)
                toks = jax.vmap(
                    lambda kk, lg: jax.random.categorical(kk, lg)
                )(tok_keys, filt / temperature).astype(jnp.int32)
            toks = jnp.where(active, toks, 0)
            new_logits, caches = fwd(
                params, caches, toks, pos, adapter=adapters,
                active=active,
            )
            pos = jnp.where(active, pos + 1, pos)
            budget = jnp.where(active, budget - 1, budget)
            active = active & (toks != eos) & (budget > 0)
            logits = new_logits
            toks_all.append(toks)
        clg, tmp = fwd_chunk(
            params, tmp, ctoks, cpos0, last_idx=clast,
            adapter=cadapter,
        )
        return (caches, logits, pos, active, budget,
                ride(jnp.stack(toks_all, axis=1)), tmp, clg)

    return pstep


def _dyn_top_k_filter(logits, top_ks):
    """Per-slot top-k filter with a TRACED k vector. ``_top_k_filter``
    thresholds at ``lax.top_k(logits, k)[0][..., -1]`` — the kth order
    statistic — and an ascending full sort gathered at ``V - k`` yields
    the same float value, so the subsequent ``where(logits < kth)``
    keeps bitwise-identical rows. ``k == 0`` is the no-filter sentinel
    (engine-wide ``top_k=None``), folded out so those slots keep the
    raw logits object untouched."""
    vs = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)
    idx = jnp.clip(vs - top_ks, 0, vs - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(srt, idx[:, None], axis=-1)
    filt = jnp.where(logits < kth, -jnp.inf, logits)
    return jnp.where((top_ks > 0)[:, None], filt, logits)


def _top_p_filter(scaled, top_ps):
    """Per-slot nucleus filter on the temperature-scaled logits: keep
    the smallest descending-probability prefix whose mass reaches
    top_p (the token crossing the threshold is kept, standard nucleus
    semantics). ``top_p == 1`` is the no-filter sentinel, folded out
    so unfiltered slots keep ``scaled`` bitwise."""
    srt = -jnp.sort(-scaled, axis=-1)
    probs = jax.nn.softmax(srt, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = (csum - probs) < top_ps[:, None]
    cut = jnp.min(
        jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True
    )
    out = jnp.where(scaled < cut, -jnp.inf, scaled)
    return jnp.where((top_ps < 1.0)[:, None], out, scaled)


def _masked_draw(logits, pos, active, gstate, keys, temps, top_ks,
                 top_ps, bias_idx, bias_val, mask_words, n_logprobs):
    """One masked substep's draw: grammar mask → logit bias → logprob
    rows → per-slot top-k → temperature → top-p → greedy/sampled
    select. Every per-request control sits behind a ``jnp.where`` at
    its neutral value (state 0, no bias rows, k=0, p=1, engine
    temperature) so a slot using none of them reproduces the base
    step program's token stream bitwise
    (``tests/test_serving_schedules.py`` compares the two programs).

    Returns ``(toks, aux)`` where ``aux`` is the packed int32 per-slot
    row ``[tok, bitcast(chosen logprob), top ids..., bitcast(top
    logprobs)...]`` — logprobs ride the one existing readback instead
    of syncing the (slots, V) logits."""
    vs = logits.shape[-1]
    # grammar mask: gather each slot's packed row for its current FSM
    # state and unpack 32 bits/word in-program. Row 0 is the
    # all-permitted unconstrained sentinel, and the gstate>0 fold
    # keeps unconstrained rows as the untouched logits object.
    rows = mask_words[gstate]
    bits = (
        rows[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
    ) & jnp.uint32(1)
    allowed = bits.reshape(rows.shape[0], -1)[:, :vs] != 0
    constrained = (gstate > 0)[:, None]
    base = jnp.where(constrained & ~allowed, -jnp.inf, logits)
    # sparse per-slot logit bias: idx<0 rows are padding. The has_bias
    # fold is load-bearing for parity — ``base + 0.0`` flips -0.0
    # logits to +0.0.
    has_bias = jnp.any(bias_idx >= 0, axis=-1)[:, None]
    idx = jnp.clip(bias_idx, 0, vs - 1)
    val = jnp.where(bias_idx >= 0, bias_val, 0.0)
    delta = jax.vmap(
        lambda i, v: jnp.zeros((vs,), logits.dtype).at[i].add(v)
    )(idx, val)
    base = jnp.where(has_bias, base + delta, base)
    # logprob source: the masked+biased distribution BEFORE
    # top-k/temperature/top-p shaping — API logprobs describe the
    # model's constrained distribution, not the sampler's
    lp = jax.nn.log_softmax(base, axis=-1)
    filt = _dyn_top_k_filter(base, top_ks)
    greedy = jnp.argmax(filt, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    scaled = filt / safe_t[:, None]
    final = _top_p_filter(scaled, top_ps)
    tok_keys = jax.vmap(jax.random.fold_in)(keys, pos)
    sampled = jax.vmap(
        lambda kk, lg: jax.random.categorical(kk, lg)
    )(tok_keys, final).astype(jnp.int32)
    toks = jnp.where(temps > 0, sampled, greedy)
    toks = jnp.where(active, toks, 0)
    lp_cho = jnp.take_along_axis(lp, toks[:, None], axis=-1)[:, 0]
    tv, ti = lax.top_k(lp, n_logprobs)
    aux = jnp.concatenate(
        [
            toks[:, None],
            lax.bitcast_convert_type(lp_cho, jnp.int32)[:, None],
            ti.astype(jnp.int32),
            lax.bitcast_convert_type(tv, jnp.int32),
        ],
        axis=1,
    )
    return toks, aux


def build_masked_step_program(fwd1, horizon: int, n_logprobs: int):
    """Grammar-constrained + per-request-sampling variant of
    ``build_step_program``: the same unrolled K-substep chain, with a
    per-slot FSM state vector threaded through it. Each substep masks
    disallowed tokens BEFORE the draw and advances the state
    in-program off the chosen token, so K>1 horizons stay constrained
    without a host round-trip. The token output is replaced by the
    packed aux tensor (slots, K, 2+2*n_logprobs) whose [:, :, 0] slice
    is the token stream."""

    def mstep(params, caches, logits, pos, active, budget, eos,
              gstate, slot_keys_raw, adapters, temps, top_ks, top_ps,
              bias_idx, bias_val, mask_words, trans_tab):
        keys = jax.random.wrap_key_data(slot_keys_raw)
        aux_all = []
        fwd, ride = tallied(fwd1)
        for k in range(horizon):
            toks, aux = _masked_draw(  # lint: prng-ok _masked_draw folds pos into the key; pos advances every substep
                logits, pos, active, gstate, keys, temps, top_ks,
                top_ps, bias_idx, bias_val, mask_words, n_logprobs,
            )
            # advance the FSM off the chosen token; disallowed
            # transitions are stored as 0 in the table so the gather
            # never indexes negatively. Inactive and unconstrained
            # slots hold their state.
            nxt = trans_tab[gstate, toks]
            gstate = jnp.where(active & (gstate > 0), nxt, gstate)
            new_logits, caches = fwd(
                params, caches, toks, pos, adapter=adapters,
                active=active,
            )
            pos = jnp.where(active, pos + 1, pos)
            budget = jnp.where(active, budget - 1, budget)
            active = active & (toks != eos) & (budget > 0)
            logits = new_logits
            aux_all.append(aux)
        return (caches, logits, pos, active, budget, gstate,
                ride(jnp.stack(aux_all, axis=1)))

    return mstep


def build_masked_piggyback_program(fwd1, fwd_chunk, horizon: int,
                                   n_logprobs: int):
    """Masked decode leg + one bounded prefill chunk in a single
    dispatch — ``build_masked_step_program`` body verbatim plus the
    ``build_chunk_program`` leg, mirroring how ``piggyback_step``
    extends ``step``."""

    def mpstep(params, caches, logits, pos, active, budget, eos,
               gstate, slot_keys_raw, adapters, temps, top_ks,
               top_ps, bias_idx, bias_val, mask_words, trans_tab,
               tmp, ctoks, cpos0, clast, cadapter):
        keys = jax.random.wrap_key_data(slot_keys_raw)
        aux_all = []
        fwd, ride = tallied(fwd1)
        for k in range(horizon):
            toks, aux = _masked_draw(  # lint: prng-ok _masked_draw folds pos into the key; pos advances every substep
                logits, pos, active, gstate, keys, temps, top_ks,
                top_ps, bias_idx, bias_val, mask_words, n_logprobs,
            )
            nxt = trans_tab[gstate, toks]
            gstate = jnp.where(active & (gstate > 0), nxt, gstate)
            new_logits, caches = fwd(
                params, caches, toks, pos, adapter=adapters,
                active=active,
            )
            pos = jnp.where(active, pos + 1, pos)
            budget = jnp.where(active, budget - 1, budget)
            active = active & (toks != eos) & (budget > 0)
            logits = new_logits
            aux_all.append(aux)
        clg, tmp = fwd_chunk(
            params, tmp, ctoks, cpos0, last_idx=clast,
            adapter=cadapter,
        )
        return (caches, logits, pos, active, budget, gstate,
                ride(jnp.stack(aux_all, axis=1)), tmp, clg)

    return mpstep


def build_gstate_set_program():
    """Single-slot grammar-state seat: write one row of the
    device-resident FSM state vector at admission (and zero it at
    retirement), like ``build_deact_program``."""
    return lambda gstate, slot, val: gstate.at[slot].set(val)


def build_insert_program():
    """Slab insert + state set (no prefill): lands a scratch cache
    built by the chunked path — or zeros, for an empty prompt — into
    the pool at the slot index."""

    def insert(caches, logits, pos, active, budget, eos, tmp, lg,
               slot, pos0, max_new, eos_tok):
        caches = jax.tree.map(
            lambda c, t: lax.dynamic_update_slice(
                c, t, (0, 0, slot, 0, 0)
            ),
            caches, tmp,
        )
        logits = lax.dynamic_update_slice(logits, lg, (slot, 0))
        pos = pos.at[slot].set(pos0)
        active = active.at[slot].set(True)
        budget = budget.at[slot].set(max_new)
        eos = eos.at[slot].set(eos_tok)
        return caches, logits, pos, active, budget, eos

    return insert


def build_hit_insert_program():
    """FULL-hit admission: one gather/dynamic-update program that
    copies a segment's whole slab from the region into the pool at the
    slot index, lands the segment's stored last-row logits, and sets
    the slot's device state — zero prompt rows computed, zero prefill
    dispatches."""

    def hit(caches, logits, pos, active, budget, eos, region, seg_lg,
            seg, slot, pos0, max_new, eos_tok):
        slab = jax.tree.map(
            lambda r: lax.dynamic_slice(
                r, (0, 0, seg, 0, 0),
                (r.shape[0], r.shape[1], 1, r.shape[3], r.shape[4]),
            ),
            region,
        )
        caches = jax.tree.map(
            lambda c, t: lax.dynamic_update_slice(
                c, t, (0, 0, slot, 0, 0)
            ),
            caches, slab,
        )
        logits = lax.dynamic_update_slice(logits, seg_lg, (slot, 0))
        pos = pos.at[slot].set(pos0)
        active = active.at[slot].set(True)
        budget = budget.at[slot].set(max_new)
        eos = eos.at[slot].set(eos_tok)
        return caches, logits, pos, active, budget, eos

    return hit


def build_seg_fetch_program():
    """Segment fetch: one region slot's slab as a batch-1 scratch
    cache (the partial-hit path chunk-computes the suffix on top)."""

    def fetch(region, seg):
        return jax.tree.map(
            lambda r: lax.dynamic_slice(
                r, (0, 0, seg, 0, 0),
                (r.shape[0], r.shape[1], 1, r.shape[3], r.shape[4]),
            ),
            region,
        )

    return fetch


def build_seg_store_program():
    """Segment store: copy a pool slot's slab into the region at the
    segment index (insert-on-completion). Pool caches are read, not
    donated; the region is replaced functionally."""

    def store(region, caches, seg, slot):
        slab = jax.tree.map(
            lambda c: lax.dynamic_slice(
                c, (0, 0, slot, 0, 0),
                (c.shape[0], c.shape[1], 1, c.shape[3], c.shape[4]),
            ),
            caches,
        )
        return jax.tree.map(
            lambda r, t: lax.dynamic_update_slice(
                r, t, (0, 0, seg, 0, 0)
            ),
            region, slab,
        )

    return store


def build_seg_import_program():
    """Wire-segment import: land a batch-1 slab (a remote replica's
    ``_seg_fetch``-layout segment, uploaded from host bytes) into the
    region at the segment index — the disaggregated-ingest mirror of
    the segment store, with the pool slot slice replaced by the slab
    that arrived over the wire."""

    def imp(region, slab, seg):
        return jax.tree.map(
            lambda r, t: lax.dynamic_update_slice(
                r, t, (0, 0, seg, 0, 0)
            ),
            region, slab,
        )

    return imp


def build_logit_row_program():
    """(1, V) row slice of the pending logits — captured at insert
    time so a later FULL hit replays the exact prefill logits without
    recomputing anything."""
    return lambda lg, slot: lax.dynamic_slice(
        lg, (slot, 0), (1, lg.shape[1])
    )


def build_batch_prefill_program(do_prefill, init_caches,
                                max_total: int, nb: int):
    """BATCHED admission prefill: ``nb`` same-bucket prompts prefilled
    in one dispatched program (vector per-row last_idx), each row's
    slab + logits + device state landed at its slot. Group sizes are
    padded to powers of two (pad rows repeat row 0, re-writing
    identical values), so the program count stays
    O(buckets x log n_slots)."""

    def bprefill(caches, logits, pos, active, budget, eos, params,
                 prompts, last_idx, slots, pos0, max_new, eos_toks,
                 adapters):
        tmp, lg = do_prefill(
            params, init_caches(nb, max_total), prompts,
            last_idx=last_idx, adapter=adapters,
        )
        for r in range(nb):
            slab = jax.tree.map(
                lambda t, r=r: t[:, :, r:r + 1], tmp
            )
            caches = jax.tree.map(
                lambda c, t, r=r: lax.dynamic_update_slice(
                    c, t, (0, 0, slots[r], 0, 0)
                ),
                caches, slab,
            )
            logits = lax.dynamic_update_slice(
                logits, lg[r:r + 1], (slots[r], 0)
            )
            pos = pos.at[slots[r]].set(pos0[r])
            active = active.at[slots[r]].set(True)
            budget = budget.at[slots[r]].set(max_new[r])
            eos = eos.at[slots[r]].set(eos_toks[r])
        return caches, logits, pos, active, budget, eos

    return bprefill


def build_batch_hit_program(fwd_chunk, nb: int):
    """BATCHED partial-hit admission for ``nb`` requests sharing the
    same cached-prefix length L and suffix bucket: one gather pulls
    each row's segment slab from the region, one ``forward_chunk`` at
    scalar pos0=L (vector per-row last_idx) computes all the uncached
    suffixes, and each row lands at its slot. The common case — many
    requests behind one system prompt — gathers the SAME segment nb
    times."""

    def bhit(caches, logits, pos, active, budget, eos, params, region,
             seg_idx, toks, p0, last_idx, slots, posf, max_new,
             eos_toks, adapters):
        tmp = jax.tree.map(
            lambda r_: jnp.take(r_, seg_idx, axis=2), region
        )
        lg, tmp = fwd_chunk(
            params, tmp, toks, p0, last_idx=last_idx,
            adapter=adapters,
        )
        for r in range(nb):
            slab = jax.tree.map(
                lambda t, r=r: t[:, :, r:r + 1], tmp
            )
            caches = jax.tree.map(
                lambda c, t, r=r: lax.dynamic_update_slice(
                    c, t, (0, 0, slots[r], 0, 0)
                ),
                caches, slab,
            )
            logits = lax.dynamic_update_slice(
                logits, lg[r:r + 1], (slots[r], 0)
            )
            pos = pos.at[slots[r]].set(posf[r])
            active = active.at[slots[r]].set(True)
            budget = budget.at[slots[r]].set(max_new[r])
            eos = eos.at[slots[r]].set(eos_toks[r])
        return caches, logits, pos, active, budget, eos

    return bhit


# -- paged program factories -----------------------------------------------
#
# Paged-mode analogues over the {"blocks", "tables"} caches dict. The
# compute is IDENTICAL to the slab programs — same do_prefill, same
# fwd1 via make_paged_fwd1's gather/compute/scatter wrapper — only the
# landing changes: instead of a dynamic-update at the slot's slab, rows
# scatter into the pool blocks the slot's table row names. Rows past
# the row's allocated coverage scatter into the zero sentinel (block 0,
# re-zeroed in-program), so a slot only ever writes blocks it owns.


def build_paged_prefill_program(do_prefill, init_caches, max_total: int):
    """Paged admission prefill: batch-1 prefill into a scratch slab
    (same as the slab program), then scatter the slab's rows into the
    slot's table-row blocks. Fresh private blocks get the scratch
    cache's zero rows beyond the prompt, so no stale bytes from a
    previous block owner survive reuse."""

    def prefill(caches, logits, pos, active, budget, eos, params,
                prompt, last_idx, slot, pos0, max_new, eos_tok,
                adapter):
        tmp, lg = do_prefill(
            params, init_caches(1, max_total), prompt,
            last_idx=last_idx, adapter=adapter,
        )
        row = caches["tables"][slot]
        caches = {
            "blocks": paged_slot_scatter(caches["blocks"], row, tmp),
            "tables": caches["tables"],
        }
        logits = lax.dynamic_update_slice(logits, lg, (slot, 0))
        pos = pos.at[slot].set(pos0)
        active = active.at[slot].set(True)
        budget = budget.at[slot].set(max_new)
        eos = eos.at[slot].set(eos_tok)
        return caches, logits, pos, active, budget, eos

    return prefill


def build_paged_insert_program():
    """Paged insert + state set (no prefill): scatter a batch-1 scratch
    slab — built by the chunked path or a segment gather — into the
    slot's table-row blocks and land the pending logits row."""

    def insert(caches, logits, pos, active, budget, eos, tmp, lg,
               slot, pos0, max_new, eos_tok):
        row = caches["tables"][slot]
        caches = {
            "blocks": paged_slot_scatter(caches["blocks"], row, tmp),
            "tables": caches["tables"],
        }
        logits = lax.dynamic_update_slice(logits, lg, (slot, 0))
        pos = pos.at[slot].set(pos0)
        active = active.at[slot].set(True)
        budget = budget.at[slot].set(max_new)
        eos = eos.at[slot].set(eos_tok)
        return caches, logits, pos, active, budget, eos

    return insert


def build_paged_seg_fetch_program():
    """Paged segment fetch: gather a segment's block list (sentinel-
    padded to full table width, so uncovered rows come back zero) into
    a batch-1 scratch slab the chunk programs accept unchanged."""

    def fetch(blocks, seg_row):
        return paged_slot_gather(blocks, seg_row)

    return fetch


def build_paged_seg_import_program():
    """Paged wire-segment import: scatter a host-uploaded batch-1 slab
    into the segment's freshly allocated blocks through a sentinel-
    padded table row (rows past the segment's block span land in the
    sentinel block and vanish, as everywhere else in the paged
    layout)."""

    def imp(blocks, seg_row, slab):
        return paged_slot_scatter(blocks, seg_row, slab)

    return imp


def build_block_copy_program():
    """Copy one block's rows to another block across every layer/leaf —
    the paged segment store's tail privatization (a donor slot keeps
    writing its tail block past the cached length, so the cache copies
    that one block instead of aliasing it)."""

    def copy(blocks, src, dst):
        return paged_block_copy(blocks, src, dst)

    return copy


class _SlotState:
    """Host-side record for one occupied slot."""

    __slots__ = ("req", "tokens", "t_first_token", "gen", "key_data",
                 "adapter", "segs", "gkey", "gstate0", "stop_matcher",
                 "lp_out", "n_stripped", "n_substeps", "t_boundary",
                 "t_seated")

    def __init__(self, req: Request, gen: int, key_data,
                 adapter: int = 0):
        self.req = req
        self.tokens: list[int] = []
        self.t_first_token: float | None = None
        self.gen = gen  # pool generation at admission (reuse detection)
        # raw uint32 data of the slot's sampling key (host-persisted so
        # crash-recovery replay resumes the exact key stream)
        self.key_data = key_data
        # LoRA bank row (host-persisted so recovery replays through the
        # same adapter weights)
        self.adapter = adapter
        # prefix-cache segments this request pins (the one its
        # admission read + the one its prompt inserted); unpinned at
        # retirement so LRU eviction can reclaim them
        self.segs: list[Segment] = []
        # sampling-surface state (engines with sampling_surface=True):
        # gkey/gstate0 pin the seated grammar's table rows + start
        # state so crash recovery can re-walk the transition table
        # over st.tokens; the stop matcher holds back a rolling
        # suffix; lp_out collects per-token logprob records
        self.gkey = None
        self.gstate0 = 0
        self.stop_matcher: StopMatcher | None = None
        self.lp_out: list | None = None
        self.n_stripped = 0
        # decode substeps dispatched for this slot: with the prompt's
        # length, the cache rows it holds on the device (the host's
        # ``tokens`` lag the device by the horizon in flight)
        self.n_substeps = 0
        # the step boundary that popped the request and the moment its
        # slot went live, for the time-to-first-token split; None for a
        # state rebuilt by recovery or seated by migration
        self.t_boundary: float | None = None
        self.t_seated: float | None = None


class _AdmitPlan:
    """One admission being planned: the popped request, its acquired
    slot, and the prefix-cache classification (``kind`` in
    miss/partial/full, ``seg`` the pinned source segment, ``matched``
    the usable grain-aligned cached-token count)."""

    __slots__ = ("req", "slot", "kind", "seg", "matched", "admitted",
                 "prefill_s", "t_pf", "t_boundary")

    def __init__(self, req: Request, slot: int, t_boundary: float):
        self.req = req
        self.slot = slot
        self.t_boundary = t_boundary  # the step boundary that popped it
        self.kind = "miss"
        self.seg: Segment | None = None
        self.matched = 0
        self.admitted = False  # slot state seated (crash requeue guard)
        self.prefill_s = 0.0
        self.t_pf = 0.0


class _PendingPrefill:
    """One deferred admission (chunked-prefill piggyback): the plan
    holds the acquired slot + pinned prefix segment; ``chunks`` is the
    remaining pow2 chunk schedule over the uncached suffix; ``tmp`` /
    ``lg`` carry the batch-1 scratch cache and last chunk's (1, V)
    logits across horizons until the completion insert seats the
    slot."""

    __slots__ = ("plan", "chunks", "tmp", "lg", "t_start")

    def __init__(self, plan: _AdmitPlan, chunks, tmp, t_start: float):
        self.plan = plan
        self.chunks = chunks
        self.tmp = tmp
        self.lg = None
        self.t_start = t_start


# Process-level compiled-program sharing.  The callable a family jits
# is fully determined by (cfg, tp, paged geometry, max_total, the
# family's own statics): two engines with the same key — replica
# fleets, supervised restarts, parity-test pairs — reuse ONE jitted
# callable instead of recompiling identical programs.  Safe because
# every program is pure (all state rides in the arguments) and
# jax.jit retraces per input aval, so shape differences (n_slots,
# prompt buckets) never alias.  The executables themselves live in
# jax's own caches, so jax.clear_caches() still frees them; this dict
# only pins the small wrapper objects.
_SHARED_PROGRAMS: dict = {}


def _shared_program(key, thunk):
    fn = _SHARED_PROGRAMS.get(key)
    if fn is None:
        fn = _SHARED_PROGRAMS[key] = thunk()
    return fn


class _Inflight:
    """One dispatched-but-unsynced horizon: the device future holding
    the (slots, K) token block plus a snapshot of who occupied each
    slot at dispatch time."""

    __slots__ = ("toks", "snaps", "t_dispatch", "n")

    def __init__(self, toks, snaps, t_dispatch, n):
        self.toks = toks
        self.snaps = snaps  # [(slot, _SlotState)] occupied at dispatch
        self.t_dispatch = t_dispatch
        self.n = n  # horizon number: links dispatch, sync, decode spans


class ServingEngine:
    """Fixed-shape pipelined continuous-batching decode loop.

    ``params`` may be float or ``quantize_decode_params`` output (pair
    with ``cfg.decode_int8=True`` for the int8 KV cache). Sampling
    settings are engine-wide (they are baked into the compiled step);
    ``temperature=0`` decodes greedily.

    ``decode_horizon`` (K) is the number of decode steps fused into one
    dispatched program; lifecycle checks, admission and fault injection
    happen at horizon boundaries, so K trades up-to-K-steps extra
    admission/TTFT latency for amortized dispatch + host-sync overhead.
    K=1 reproduces the unpipelined per-step cadence except that token
    readback still lags dispatch by one step (the double buffer).

    ``max_queue_depth`` sizes the engine's own scheduler (default: the
    scheduler's 128; a closed loop that keeps more than slots + 128
    requests outstanding needs more, or the surplus is refused). It is
    ``RequestScheduler(max_queue_depth=)`` for a caller that hands the
    engine its arguments as data and builds no scheduler.
    ``prefill_max_bucket`` caps the power-of-two prompt padding bucket;
    longer prompts are chunked through the same buckets.
    ``chunked_replay`` picks the crash-replay mode (see module doc).

    The engine does what its arguments say or raises at construction,
    naming what is missing: no feature is switched off at run time.
    Whether a schedule is correct is decided by a test (tier-1 on
    XLA:CPU, ``chip_smoke.py --legs features`` on the chip).

    Supervision knobs: ``faults`` (an optional
    :class:`~.faults.FaultInjector`), ``max_retries`` transient retries
    per boundary with exponential backoff starting at
    ``retry_backoff_s`` capped at ``max_backoff_s``. ``results_cap``
    bounds the finished-stream dict (oldest evicted first) so sustained
    traffic cannot leak host memory; front ends should prefer
    :meth:`pop_result`, which removes the entry on read.

    Observability: ``tracer`` (an :class:`~deeplearning4j_tpu.obs
    .trace.Tracer`) records the request lifecycle as spans — queued on
    the scheduler track, prefill/decode/first-token/terminal per slot
    track, the loop's phases (sweep/admit/prefill/dispatch/sync/
    process) and the whole step on the engine track — defaulting to a
    DISABLED tracer (every record call is one attribute check). The
    phases are also ``engine.<phase>`` annotations in any profiler
    capture and exact totals in ``metrics.loop_seconds``, tracer or not;
    ``profile`` (an :class:`~deeplearning4j_tpu.obs.profiler
    .ProfileTrigger`) brackets engine steps so an armed XLA capture
    starts and stops on step boundaries.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params,
        *,
        n_slots: int = 8,
        max_total: int | None = None,
        temperature: float = 0.0,
        top_k: int | None = None,
        approx_top_k: bool = False,
        decode_horizon: int = 1,
        adaptive_horizon: bool = False,
        prefill_max_bucket: int = 128,
        chunked_replay: bool = False,
        batch_admission: bool = True,
        prefix_cache: bool = False,
        prefix_cache_tokens: int | None = None,
        prefix_affinity_tokens: int = 0,
        scheduler: RequestScheduler | None = None,
        max_queue_depth: int | None = None,
        metrics: ServingMetrics | None = None,
        rng_seed: int = 0,
        faults: FaultInjector | None = None,
        max_retries: int = 3,
        retry_backoff_s: float = 0.01,
        max_backoff_s: float = 0.25,
        results_cap: int = 1024,
        tracer: Tracer | None = None,
        flight: FlightRecorder | None = None,
        profile: ProfileTrigger | None = None,
        tp: int = 1,
        lora_bank=None,
        tenancy=None,
        embedders=None,
        paged: bool = False,
        block_size: int | None = None,
        piggyback: bool = False,
        prefill_budget: int | None = None,
        sampling_surface: bool = False,
        grammar_states: int = 256,
        grammar_cache: str | GrammarCache | None = None,
    ):
        self.n_slots = n_slots
        self.max_total = int(min(max_total or cfg.max_len, cfg.max_len))
        if cfg.gated:
            # what a stack whose cache is grouped by layer kind (a slab
            # and a ring, or one plane of latent rows) cannot do yet
            # raises here, by name: none of it falls back in silence
            for asked, what, lacks in (
                (paged, "the paged pool (paged=True)",
                 "PagedKVPool carves one slab of K and V planes into "
                 "blocks and has no block table for a ring leaf or a "
                 "one-plane latent leaf"),
                (prefix_cache, "the prefix cache (prefix_cache=True)",
                 "a cached segment holds K and V rows 0..n of every "
                 "layer: a ring has already dropped all but the last "
                 "window, and a latent leaf has one plane"),
                (int(tp) > 1, "tensor-parallel serving (tp > 1)",
                 "serving_tp_shardings has no layout for per-layer head "
                 "counts, held experts, a ring leaf or latent projections"),
                (lora_bank is not None, "a LoRA bank (lora_bank=...)",
                 "init_lora_bank stacks q and MLP factors of one shape a "
                 "layer, and _gated_block has no delta attach point (a "
                 "latent layer's query is two low-rank products)"),
            ):
                if asked:
                    raise NotImplementedError(
                        f"{what} is not built for a stack of gated layers "
                        f"(layer_types set): {lacks}"
                    )
        for name, asked in (("chunked_replay", chunked_replay),
                            ("batch_admission", batch_admission)):
            if not isinstance(asked, bool):
                raise ValueError(f"{name} is True or False, got {asked!r}")
        if sampling_surface and approx_top_k:
            raise ValueError(
                "sampling_surface=True cannot be served with "
                "approx_top_k=True: approx_max_k has no variant with a "
                "traced k a slot and the exact filter's tie semantics"
            )
        # programs dispatched while this is non-zero are not traffic
        # and stay out of metrics.program_dispatches: recovery replay
        self._uncounted = 0
        # the process's compile log, installed before anything below
        # compiles; _compiles_seen is its request count at mark_warm()
        self._compile_log = compile_log.install()
        self._compiles_seen: int | None = None
        # crash flight recorder: enabled by default (one deque.append
        # per horizon/admission — postmortems must exist BEFORE the
        # incident, so this is not opt-in like the tracer)
        self.flight = flight if flight is not None else FlightRecorder()
        # always empty: benchmark/serve.py's warm-up note still prints
        # the two, and that file is a `benchmark` PR's to edit
        self.probes_run: list[str] = []
        self.probes_from_cache: list[str] = []
        # batched LoRA: the adapter bank (init_lora_bank pytree) rides
        # inside params under the "lora" key; each slot carries an
        # adapter INDEX as traced data, so one compiled step serves
        # every adapter mix (no per-adapter program families). Row 0 is
        # the zero adapter — the forward SELECTS the untouched base
        # activations for it (jnp.where, not +0.0), so adapter-0 output
        # is the base model's (tests/test_serving_schedules.py: bitwise
        # on XLA:CPU; a rounding apart on the v5e, PERF.md PR 29).
        self.lora_bank = lora_bank
        self.n_adapters = 0
        if lora_bank is not None:
            self.n_adapters = int(
                jax.tree.leaves(lora_bank)[0].shape[1]
            )
            if cfg.decode_kernel:
                # the Pallas decode kernel has no adapter-gather path;
                # the dense fallback is the same numerics (see
                # block_decode)
                cfg = dataclasses.replace(cfg, decode_kernel=False)
        # multi-tenant serving config (see serving.tenancy): resolves
        # per-tenant slot caps at admission; quota charging happens in
        # the scheduler's submit
        self.tenancy = tenancy
        # host-side embedding tables (name -> object with
        # embedding(word)) served at admission boundaries without a KV
        # slot — the scheduler/metrics/drain machinery is model-agnostic
        self.embedders = dict(embedders or {})
        # tensor parallelism: resolve the mesh BEFORE anything compiles.
        # tp > 1 shards the whole hot path — params per
        # serving_tp_shardings (exact head/column layout), the KV pool
        # and prefix region per serving_tp_cache_sharding. A process
        # with fewer than tp devices, or heads tp does not divide, is a
        # ValueError here.
        self.tp = max(1, int(tp))
        self.tp_mesh = None
        if self.tp > 1:
            if cfg.decode_kernel:
                # the Pallas decode kernel is a custom call GSPMD
                # cannot partition; the dense fallback is the same
                # numerics (see block_decode)
                cfg = dataclasses.replace(cfg, decode_kernel=False)
            self.tp_mesh = model_parallel_mesh(self.tp)
        self.cfg = cfg
        self.temperature = temperature
        self.top_k = top_k
        self.approx_top_k = approx_top_k
        self.decode_horizon = max(1, int(decode_horizon))
        # adaptive horizon: shrink K to 1 while requests wait in the
        # queue (admissions happen at horizon boundaries, so a hot
        # queue wants short horizons), restore the configured K when it
        # drains. The device stopping rule is per-substep, so horizon
        # partitioning never changes token streams (K-parity tests).
        self.adaptive_horizon = bool(adaptive_horizon)
        self.decode_horizon_current = self.decode_horizon
        self.chunked_replay = chunked_replay
        self.batch_admission = batch_admission
        self.faults = faults
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.max_backoff_s = max_backoff_s
        self.results_cap = results_cap
        # disabled-by-default tracer: every record call is one attribute
        # check, so leaving it wired costs nothing (see obs.trace)
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.profile = profile

        fwd1, init_caches, do_prefill, cast_params = _decode_builder(
            cfg, tp_mesh=self.tp_mesh
        )
        self._fwd1 = fwd1
        self._init_caches = init_caches
        self._do_prefill = do_prefill
        self._fwd_chunk = _chunk_builder(cfg, tp_mesh=self.tp_mesh)
        if self.lora_bank is not None:
            # the bank travels inside params: place_serving_tp_params
            # shards it with the column layout (A replicated, B sharded
            # on the output dim) and cast_params passes it through —
            # _lora_delta casts at use, so the bank stays f32 at rest
            params = dict(params)
            params["lora"] = self.lora_bank
        if self.tp_mesh is not None:
            # shard the weights over the mesh (exact head/column
            # layout) before the cast — the cast is elementwise, so it
            # preserves placement and runs shard-local
            params = place_serving_tp_params(self.tp_mesh, params, cfg)
        # one-time weight cast (generate does this inside its jitted
        # program; hoisting it out of the per-step program keeps every
        # step from re-casting — same values, cast is deterministic)
        self._cfg_key = cfg.to_json()
        # the model-config identity KV segments are keyed by on the
        # wire and in the prefix cache: a segment computed under a
        # different config hash must never be seated here
        self.config_hash = model_config_hash(cfg)
        as_served = jax.eval_shape(cast_params, params)
        if jax.tree.all(jax.tree.map(
                lambda a, b: a.dtype == b.dtype, params, as_served)):
            # already in the types they are served in: a jitted cast
            # would only copy them, and a second copy of the weights is
            # what does not fit beside the first (11 GB of experts)
            self.params = params
        else:
            self.params = _shared_program(
                (self._cfg_key, self.tp, "cast_params"),
                lambda: jax.jit(cast_params),
            )(params)

        # block-paged KV: the pool becomes a shared store of fixed-size
        # blocks with per-slot int32 block tables (vLLM-style), so
        # long-prompt traffic allocates ceil((prompt+max_new)/bs)
        # blocks instead of a full Tpad slab and cached prefixes are
        # byte-SHARED by table aliasing. The paged step gathers a slab
        # view, runs the slab compute and scatters back, so logits
        # are the slab pool's (tests/test_serving_schedules.py).
        self._paged = bool(paged)
        self._block_size = int(block_size or 8)
        if self._paged:
            tpad = full_cache_leaf(jax.eval_shape(
                lambda: self._init_caches(1, self.max_total)
            )).shape[3]
            if tpad % self._block_size:
                raise ValueError(
                    f"block_size={self._block_size} does not divide the "
                    f"{tpad} cache rows a slot of max_total="
                    f"{self.max_total} pads to"
                )

        pool_sharding = (serving_tp_cache_sharding(self.tp_mesh, cfg)
                         if self.tp_mesh is not None else None)
        if self._paged:
            self.pool = PagedKVPool(
                cfg, n_slots, self.max_total, sharding=pool_sharding,
                block_size=self._block_size,
            )
        else:
            self.pool = KVSlotPool(
                cfg, n_slots, self.max_total, sharding=pool_sharding,
            )
        # NOT `scheduler or ...`: RequestScheduler defines __len__, so
        # a caller's (normally empty) scheduler would be falsy and
        # silently swapped for a default one, dropping its knobs
        if scheduler is not None and max_queue_depth is not None:
            raise ValueError(
                "max_queue_depth sizes the engine's own scheduler: give it "
                "to the RequestScheduler that is passed in instead"
            )
        self.scheduler = scheduler if scheduler is not None else (
            RequestScheduler(
                **({} if max_queue_depth is None
                   else {"max_queue_depth": int(max_queue_depth)}),
                max_total_tokens=self.max_total,
                prefix_affinity_tokens=prefix_affinity_tokens,
                tenancy=tenancy,
            )
        )
        if self.scheduler.max_total_tokens is None:
            self.scheduler.max_total_tokens = self.max_total
        self.metrics = metrics or ServingMetrics()
        self.metrics.decode_horizon = self.decode_horizon
        self.metrics.topk_select = topk_select(
            cfg.vocab_size, self.top_k, self.approx_top_k
        )
        self.metrics.kv_row_write = kv_row_write(cfg)
        self.metrics.kv_cache_rows = kv_cache_rows(cfg)
        self.metrics.compile_log = self._compile_log
        # the one place a phase of step() is named: profiler
        # annotation, metrics.loop_seconds, ring span, sanitizer phase
        self._regions = PhaseRegions(
            ENGINE_REGIONS, self.metrics.loop_seconds, self.tracer,
            ENGINE_TRACK, on_phase=self._set_phase,
        )
        # power-of-two prompt buckets: the largest must respect the
        # positional table (prefill embeds rows 0..bucket-1) and the
        # pooled slab row count (the insert window must fit Tpad)
        limit = min(int(prefill_max_bucket), cfg.max_len, self.pool.tpad)
        mb = 1
        while mb * 2 <= limit:
            mb *= 2
        self._max_bucket = mb
        self._min_bucket = min(8, mb)
        # partial-hit rounding grain: block-aligned in paged mode so
        # every partial hit is pure block aliasing (no sub-block copy),
        # the bucket grain otherwise
        self._hit_grain = (
            max(self._min_bucket, self._block_size) if self._paged
            else self._min_bucket
        )

        # chunked-prefill piggyback (Sarathi-style): long-prompt
        # admissions defer their uncached suffix to a FIFO of pending
        # records that the dispatch loop drains under a per-horizon
        # token budget, fusing the last budgeted chunk into the decode
        # dispatch itself. Default budget 2x the largest bucket: one
        # standalone chunk + one fused chunk per horizon, so a
        # deferred prompt always makes >= _max_bucket progress while
        # decode keeps stepping. The fused program's two legs share
        # no buffers, so it is step + chunk run separately, bit for
        # bit (tests/test_serving_schedules.py).
        self._piggyback = bool(piggyback)
        self.prefill_budget = max(1, int(
            prefill_budget if prefill_budget is not None
            else 2 * self._max_bucket
        ))
        self._pending_prefills: deque[_PendingPrefill] = deque()
        self._presplit_keys: dict[str, np.ndarray] = {}
        self._pb_did_work = False

        # prefix cache: radix tree over a bounded segment region with
        # the pool's slab layout (see serving.prefix_cache). Partial
        # hits are rounded DOWN to the bucket grain (_min_bucket) so
        # every suffix chunk window starts sublane-aligned and provably
        # fits Tpad. A hit's suffix is chunk-computed where a miss is
        # one prefill: another order of the same arithmetic, held to a
        # tolerance in tests/test_serving_schedules.py.
        self.prefix_cache: PrefixCache | None = None
        if prefix_cache:
            self.prefix_cache = PrefixCache(
                self.pool,
                (prefix_cache_tokens if prefix_cache_tokens is not None
                 else n_slots * self.pool.tpad),
                on_evict=self._on_prefix_evict,
                # branch-point segments shorter than the hit grain
                # can never serve a hit (partial matches round down;
                # block-aligned under paging)
                min_seg_len=self._hit_grain,
                config_hash=self.config_hash,
            )
        self._register_gauges()

        # per-slot decode state, DEVICE-resident (threaded through the
        # fused step so pipelined dispatch never reads stale host state)
        self._logits = jnp.zeros((n_slots, cfg.vocab_size), jnp.float32)
        self._dpos = jnp.zeros((n_slots,), jnp.int32)
        self._dactive = jnp.zeros((n_slots,), bool)
        self._dbudget = jnp.zeros((n_slots,), jnp.int32)
        self._deos = jnp.full((n_slots,), _NO_EOS, jnp.int32)

        self._slots: list[_SlotState | None] = [None] * n_slots
        self._inflight: _Inflight | None = None
        # terminal streams are written by the engine thread and read by
        # HTTP handler threads (GET /v1/result pops them), so every
        # access goes through the lock
        self._results_lock = wrap_lock(threading.Lock(), "engine.results")
        self._results: dict[str, np.ndarray] = {}  # guarded-by: _results_lock
        # attached opt-in SyncSanitizer (None in production: the hot
        # path pays one attribute-is-None check per phase)
        self._san = None
        self._key = jax.random.key(rng_seed)
        # per-slot sampling keys, split from the master key at
        # admission (deterministic by admission order). The step
        # program derives each sampled token's key as
        # fold_in(slot_key, position) — a pure function of slot key and
        # position, independent of batch composition or horizon K, so
        # crash-recovery replay (teacher-force recorded tokens, re-seat
        # positions and keys) resumes the EXACT key stream an
        # uninterrupted run would have used. _slot_keys is the raw
        # uint32 key data, host-side; each _SlotState keeps its row.
        _kd0 = np.asarray(jax.random.key_data(self._key))
        self._slot_keys = np.zeros(
            (n_slots,) + _kd0.shape, _kd0.dtype
        )
        # per-slot LoRA adapter indices, host-side mirror of
        # _slot_keys: written at admission, snapshotted (copied) per
        # dispatch, re-seated from _SlotState records at recovery.
        # Always threaded into the compiled programs — with no bank the
        # traced vector is unused and folds out of the graph, so the
        # program count and numerics are unchanged.
        self._slot_adapters = np.zeros((n_slots,), np.int32)
        self._steps = 0
        self._admitting = 0  # requests between scheduler pop and slot
        self.last_dispatch_t: float | None = None  # watchdog heartbeat
        self.last_recover_mode: str | None = None
        # programs that COMPUTE prompt rows (bucketed prefill, chunk
        # windows, batched prefill groups) — a pure-copy admission
        # (full prefix hit: segment slab + stored logits) dispatches
        # none, which tests assert on.
        self.prefill_dispatches = 0

        # donating the cache + per-slot state lets XLA update them in
        # place (the cache is the dominant allocation). The donated
        # argnums per family are DECLARED in PROGRAM_DONATION — the
        # static donation audit checks that table against the traced
        # programs, so drift between intent and program shape fails CI.
        self._state_donate = PROGRAM_DONATION[
            "paged_step" if self._paged else "step"
        ]
        # every program below is shared process-wide through
        # _shared_program keyed on this tuple + the family's own
        # statics (the donated argnums are a function of the family
        # name and need not be keyed)
        self._prog_key = (
            self._cfg_key, self.tp, self._paged, self._block_size,
            self.max_total,
        )
        # one compiled step program per horizon ACTUALLY used: just
        # {K} static, {1, K} with the adaptive horizon
        self._step_fns: dict[int, object] = {}
        self._replay_fn = _shared_program(
            self._prog_key + ("replay",),
            lambda: jax.jit(
                build_replay_program(
                    make_paged_fwd1(self._fwd1) if self._paged
                    else self._fwd1
                ),
                donate_argnums=PROGRAM_DONATION[
                    "paged_replay" if self._paged else "replay"
                ],
            ),
        )
        self._deact_fn = _shared_program(
            self._prog_key + ("deactivate",),
            lambda: jax.jit(
                build_deact_program(),
                donate_argnums=PROGRAM_DONATION["deactivate"],
            ),
        )
        self._prefill_fns: dict[int, object] = {}
        self._chunk_fns: dict[int, object] = {}
        self._batch_prefill_fns: dict[tuple[int, int], object] = {}
        self._batch_hit_fns: dict[tuple[int, int], object] = {}
        self._insert_fn = None
        self._hit_insert_fn = None
        self._seg_store_fn = None
        self._seg_fetch_fn = None
        self._seg_import_fn = None
        self._logit_row_fn = None
        self._admit_donate = PROGRAM_DONATION["prefill"]
        # paged program caches. The SLAB prefill/insert/chunk caches
        # above stay live in paged mode too: the chunked partial-hit
        # path computes suffix windows on batch-1 slab scratch in both
        # modes.
        self._paged_prefill_fns: dict[int, object] = {}
        self._paged_insert_fn = None
        self._paged_seg_fetch_fn = None
        self._paged_seg_import_fn = None
        self._block_copy_fn = None
        self._paged_admit_donate = PROGRAM_DONATION["paged_prefill"]
        # chunked-prefill piggyback: one fused program per (bucket, K)
        # actually used
        self._piggyback_fns: dict[tuple[int, int], object] = {}

        # grammar-constrained decoding + per-request sampling surface:
        # per-slot FSM state / temperature / top-k / top-p / logit-bias
        # vectors threaded through masked step variants as traced data
        # (the adapter-id idiom, one compiled family for every mix).
        # On neutral surface state the masked step is the base step,
        # bit for bit (tests/test_serving_schedules.py).
        self._surface = bool(sampling_surface)
        self._gtable: GrammarTable | None = None
        self.grammar_cache: GrammarCache | None = None
        self._masked_step_fns: dict[int, object] = {}
        self._masked_piggyback_fns: dict[tuple[int, int], object] = {}
        self._gstate_set_fn = None
        self._n_logprobs = min(MAX_TOP_LOGPROBS, cfg.vocab_size)
        # device copies of the grammar table, refreshed when the host
        # table's version moves (seat/evict between horizons only)
        self._gtab_version = -1
        self._dmask_tab = None
        self._dtrans_tab = None
        # host mirrors of the per-slot surface vectors: written at
        # admission, snapshotted per dispatch, re-seated at recovery
        # (the _slot_adapters contract). _slot_gstate holds each
        # slot's ABSOLUTE seat state for recovery re-walks — the live
        # value is the DEVICE-resident _dgstate carry.
        self._slot_gstate = np.zeros((n_slots,), np.int32)
        self._slot_temps = np.full(
            (n_slots,), self.temperature, np.float32
        )
        self._slot_topks = np.full(
            (n_slots,), int(self.top_k or 0), np.int32
        )
        self._slot_topps = np.ones((n_slots,), np.float32)
        self._slot_bias_idx = np.full(
            (n_slots, MAX_LOGIT_BIAS), -1, np.int32
        )
        self._slot_bias_val = np.zeros(
            (n_slots, MAX_LOGIT_BIAS), np.float32
        )
        self._dgstate = jnp.zeros((n_slots,), jnp.int32)
        if self._surface:
            self._gtable = GrammarTable(
                max(2, int(grammar_states)), cfg.vocab_size
            )
            self.grammar_cache = (
                grammar_cache
                if isinstance(grammar_cache, GrammarCache)
                else GrammarCache(grammar_cache)
            )
            self.metrics.registry.gauge(
                "serve_grammar_table_rows",
                "Grammar DFA table rows in use (incl. the "
                "unconstrained sentinel row).",
            ).set_function(lambda: self._gtable.rows_used)

    def _count_program(self, family: str) -> None:
        """One traffic dispatch of a compiled program family."""
        if not self._uncounted:
            self.metrics.record_program(family)

    def mark_warm(self) -> None:
        """Whoever warmed the engine calls this once, when every program
        the traffic will need has compiled (``chip_smoke.py`` does after
        its warm-up request; a benchmark that warms with its own traffic
        may). A compile request after it, by any thread of the process,
        is a recompile: ``serve_recompiles_total{fun}`` rises and one
        ``recompile`` log line names the function, at the next step
        boundary."""
        self._compiles_seen = self._compile_log.requests

    def _note_recompiles(self) -> None:
        seen, self._compiles_seen = (
            self._compiles_seen, self._compile_log.requests
        )
        for fun in self._compile_log.names_since(seen):
            self.metrics.record_recompile(fun)
            log_event(_log, "recompile", level=logging.WARNING, fun=fun,
                      horizon=self._steps)

    def _register_gauges(self) -> None:
        """Live-state gauges on the metrics registry: scrapes read
        engine state through callbacks, so the hot path never updates
        them."""
        reg = self.metrics.registry
        reg.gauge(
            "serve_kv_slots", "KV slot pool size (decode batch width).",
        ).set_function(lambda: self.n_slots)
        reg.gauge(
            "serve_kv_slots_active", "KV slots currently occupied.",
        ).set_function(lambda: self.pool.n_active)
        reg.gauge(
            "serve_kv_occupancy", "Occupied fraction of the slot pool.",
        ).set_function(lambda: self.pool.occupancy)
        reg.gauge(
            "serve_kv_slot_generations",
            "Total slot acquire count (slot-reuse churn).",
        ).set_function(
            lambda: sum(
                self.pool.generation(s) for s in range(self.n_slots)
            )
        )
        reg.gauge(
            "serve_kv_cache_bytes",
            "Device bytes of the pooled KV cache (global logical bytes "
            "under TP; precomputed host metadata, no device sync).",
        ).set_function(lambda: self.pool.nbytes())
        if self.pool.is_paged:
            reg.gauge(
                "serve_kv_blocks",
                "Allocatable KV blocks in the paged pool (sentinel "
                "excluded).",
            ).set_function(lambda: self.pool.n_blocks - 1)
            reg.gauge(
                "serve_kv_blocks_free",
                "KV blocks on the paged pool's free heap.",
            ).set_function(lambda: self.pool.n_free_blocks)
            reg.gauge(
                "serve_kv_blocks_in_use",
                "KV blocks held by slot tables or cached segments.",
            ).set_function(lambda: self.pool.n_blocks_in_use)
            reg.gauge(
                "serve_kv_block_size",
                "Rows per KV block (paged layout granule).",
            ).set_function(lambda: self.pool.block_size)
        reg.gauge(
            "serve_tp_degree",
            "Tensor-parallel width the engine is serving at (1 = "
            "single chip).",
        ).set_function(lambda: self.tp)
        reg.gauge(
            "serve_queue_depth", "Requests queued, not yet admitted.",
        ).set_function(lambda: len(self.scheduler))
        reg.gauge(
            "serve_lora_adapters",
            "Rows in the batched-LoRA adapter bank (0 = base only; "
            "row 0 is always the zero adapter).",
        ).set_function(lambda: self.n_adapters)
        if self.tenancy is not None:
            reg.gauge(
                "serve_tenants", "Configured tenants in the registry.",
            ).set_function(lambda: len(self.tenancy))
            # declare per-tenant SLOs so every /metrics render derives
            # serve_tenant_slo_burn{tenant} from the observed p99s
            for tid in self.tenancy.tenant_ids():
                t = self.tenancy.get(tid)
                if t.slo_p99_tpot_s is not None:
                    self.metrics.set_tenant_slo(tid, t.slo_p99_tpot_s)
        reg.gauge(
            "serve_topk_select",
            "How the step programs' top-k filter finds its threshold, "
            "decided when they are traced: chunked (selection by "
            "chunks), sort (lax.top_k over the vocabulary), approx or "
            "none.",
            labelnames=("how",),
        ).set(1, how=self.metrics.topk_select)
        reg.gauge(
            "serve_kv_row_write",
            "Who places a decode substep's new K and V rows in the "
            "cache, decided when the step programs are traced: kernel "
            "(the decode kernel writes the row it reads) or xla (a "
            "scatter before it: int8 cache, dense path).",
            labelnames=("how",),
        ).set(1, how=self.metrics.kv_row_write)
        reg.gauge(
            "serve_kv_cache_rows",
            "What a position's cache row is, decided when the step "
            "programs are traced: kv (a K and a V plane), kv+ring (the "
            "same in a slab and a ring leaf) or latent (one plane that "
            "is key and value of every head).",
            labelnames=("how",),
        ).set(1, how=self.metrics.kv_cache_rows)
        reg.gauge(
            "serve_decode_horizon_current",
            "Decode substeps fused into the next horizon dispatch "
            "(shrinks to 1 under adaptive_horizon while the queue is "
            "non-empty).",
        ).set_function(lambda: self.decode_horizon_current)
        if self._piggyback:
            reg.gauge(
                "serve_prefill_budget_tokens",
                "Chunk tokens the piggyback scheduler may spend per "
                "decode horizon (--prefill-budget).",
            ).set_function(lambda: self.prefill_budget)
            reg.gauge(
                "serve_prefill_pending",
                "Admissions whose prefill is deferred across horizons "
                "(piggyback records holding a slot, not yet seated).",
            ).set_function(lambda: len(self._pending_prefills))
        if self.prefix_cache is not None:
            reg.gauge(
                "serve_prefix_segments", "Cached prefix segments.",
            ).set_function(lambda: self.prefix_cache.n_segments)
            reg.gauge(
                "serve_prefix_segments_pinned",
                "Segments pinned by in-flight requests (not evictable).",
            ).set_function(lambda: self.prefix_cache.n_pinned)
            reg.gauge(
                "serve_prefix_tokens_cached",
                "Prompt tokens held in cached segments.",
            ).set_function(lambda: self.prefix_cache.tokens_cached)
            reg.gauge(
                "serve_prefix_capacity_tokens",
                "Prefix-cache capacity in tokens (whole region slots).",
            ).set_function(lambda: self.prefix_cache.capacity_tokens)
            reg.gauge(
                "serve_prefix_region_bytes",
                "Device bytes of the prefix-cache segment region.",
            ).set_function(lambda: self.prefix_cache.nbytes())

    def _on_prefix_evict(self, seg) -> None:
        self.metrics.record_prefix_eviction()
        self.tracer.instant(
            ENGINE_TRACK, "prefix_evict", length=seg.length,
        )

    # -- compiled programs -------------------------------------------------
    #
    # Program BODIES live in the module-level build_*_program factories
    # so the static auditor traces the exact functions the engine jits;
    # these methods only cache the jitted callables per family key.

    def _step_fn_for(self, horizon: int):
        """The compiled fused-step program for ``horizon`` substeps
        (cached per K — the adaptive horizon alternates between the
        configured K and 1)."""
        fn = self._step_fns.get(horizon)
        if fn is None:
            fn = _shared_program(
                self._prog_key + ("step", horizon, self.temperature,
                                  self.top_k, self.approx_top_k),
                lambda: jax.jit(
                    build_step_program(
                        make_paged_fwd1(self._fwd1) if self._paged
                        else self._fwd1,
                        horizon, self.temperature, self.top_k,
                        self.approx_top_k,
                    ),
                    donate_argnums=self._state_donate,
                ),
            )
            self._step_fns[horizon] = fn
        return fn

    def _prefill_fn(self, bucket: int):
        """Jitted fused admission program for one prompt bucket (see
        :func:`build_prefill_program`)."""
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            # bucket only changes input shapes, so every bucket shares
            # ONE callable (jit traces per aval under the hood; the
            # per-bucket dict keys still express the compile surface)
            fn = _shared_program(
                self._prog_key + ("prefill",),
                lambda: jax.jit(
                    build_prefill_program(
                        self._do_prefill, self._init_caches,
                        self.max_total,
                    ),
                    donate_argnums=self._admit_donate,
                ),
            )
            self._prefill_fns[bucket] = fn
        return fn

    def _chunk_fn(self, bucket: int):
        """Jitted chunk-at-offset program for the long-prompt path
        (see :func:`build_chunk_program`)."""
        fn = self._chunk_fns.get(bucket)
        if fn is None:
            fn = _shared_program(
                self._prog_key + ("chunk",),
                lambda: jax.jit(build_chunk_program(self._fwd_chunk)),
            )
            self._chunk_fns[bucket] = fn
        return fn

    def _piggyback_fn(self, bucket: int, horizon: int):
        """Jitted fused chunk+decode piggyback program (see
        :func:`build_piggyback_program`). Like ``_chunk_fn``, one
        callable serves every bucket (jit retraces per chunk aval);
        the per-(bucket, K) dict keys express the compile surface the
        audit fences."""
        fn = self._piggyback_fns.get((bucket, horizon))
        if fn is None:
            fn = _shared_program(
                self._prog_key + (
                    "piggyback_step", horizon, self.temperature,
                    self.top_k, self.approx_top_k,
                ),
                lambda: jax.jit(
                    build_piggyback_program(
                        make_paged_fwd1(self._fwd1) if self._paged
                        else self._fwd1,
                        self._fwd_chunk, horizon, self.temperature,
                        self.top_k, self.approx_top_k,
                    ),
                    donate_argnums=PROGRAM_DONATION[
                        "paged_piggyback_step" if self._paged
                        else "piggyback_step"
                    ],
                ),
            )
            self._piggyback_fns[(bucket, horizon)] = fn
        return fn

    def _masked_step_fn_for(self, horizon: int):
        """The compiled masked (grammar + sampling surface) step for
        ``horizon`` substeps. Engine-wide temperature/top_k are NOT in
        the shared-program key: they ride as per-slot traced vectors,
        so one compiled family serves every sampling mix."""
        fn = self._masked_step_fns.get(horizon)
        if fn is None:
            fn = _shared_program(
                self._prog_key + (
                    "masked_step", horizon, self._n_logprobs,
                ),
                lambda: jax.jit(
                    build_masked_step_program(
                        make_paged_fwd1(self._fwd1) if self._paged
                        else self._fwd1,
                        horizon, self._n_logprobs,
                    ),
                    donate_argnums=PROGRAM_DONATION[
                        "paged_masked_step" if self._paged
                        else "masked_step"
                    ],
                ),
            )
            self._masked_step_fns[horizon] = fn
        return fn

    def _masked_piggyback_fn(self, bucket: int, horizon: int):
        """Jitted masked chunk+decode piggyback program (see
        :func:`build_masked_piggyback_program`); per-(bucket, K) dict
        keys express the compile surface the audit fences."""
        fn = self._masked_piggyback_fns.get((bucket, horizon))
        if fn is None:
            fn = _shared_program(
                self._prog_key + (
                    "masked_piggyback_step", horizon, self._n_logprobs,
                ),
                lambda: jax.jit(
                    build_masked_piggyback_program(
                        make_paged_fwd1(self._fwd1) if self._paged
                        else self._fwd1,
                        self._fwd_chunk, horizon, self._n_logprobs,
                    ),
                    donate_argnums=PROGRAM_DONATION[
                        "paged_masked_piggyback_step" if self._paged
                        else "masked_piggyback_step"
                    ],
                ),
            )
            self._masked_piggyback_fns[(bucket, horizon)] = fn
        return fn

    def _gstate_set(self):
        """Jitted single-slot grammar-state write (see
        :func:`build_gstate_set_program`)."""
        if self._gstate_set_fn is None:
            self._gstate_set_fn = _shared_program(
                self._prog_key + ("gstate_set",),
                lambda: jax.jit(
                    build_gstate_set_program(),
                    donate_argnums=PROGRAM_DONATION["gstate_set"],
                ),
            )
        return self._gstate_set_fn

    def _grammar_device_tables(self):
        """Device copies of the combined grammar mask/transition
        tables, refreshed exactly when the host table's version moved
        (seats and evictions happen between horizons, admission-side,
        so a dispatch never races this)."""
        gt = self._gtable
        if self._gtab_version != gt.version:
            self._dmask_tab = jnp.asarray(gt.mask_words)
            self._dtrans_tab = jnp.asarray(gt.trans)
            self._gtab_version = gt.version
        return self._dmask_tab, self._dtrans_tab

    def _insert(self):
        """Jitted slab insert + state set (see
        :func:`build_insert_program`)."""
        if self._insert_fn is None:
            self._insert_fn = _shared_program(
                self._prog_key + ("insert",),
                lambda: jax.jit(
                    build_insert_program(),
                    donate_argnums=PROGRAM_DONATION["insert"],
                ),
            )
        return self._insert_fn

    def _hit_insert(self):
        """Jitted FULL-hit admission (see
        :func:`build_hit_insert_program`)."""
        if self._hit_insert_fn is None:
            # donates the pool state only — the region must survive
            self._hit_insert_fn = _shared_program(
                self._prog_key + ("hit_insert",),
                lambda: jax.jit(
                    build_hit_insert_program(),
                    donate_argnums=PROGRAM_DONATION["hit_insert"],
                ),
            )
        return self._hit_insert_fn

    def _seg_fetch(self):
        """Jitted segment fetch (see
        :func:`build_seg_fetch_program`)."""
        if self._seg_fetch_fn is None:
            self._seg_fetch_fn = _shared_program(
                self._prog_key + ("seg_fetch",),
                lambda: jax.jit(build_seg_fetch_program()),
            )
        return self._seg_fetch_fn

    def _seg_store(self):
        """Jitted segment store (see
        :func:`build_seg_store_program`)."""
        if self._seg_store_fn is None:
            self._seg_store_fn = _shared_program(
                self._prog_key + ("seg_store",),
                lambda: jax.jit(
                    build_seg_store_program(),
                    donate_argnums=PROGRAM_DONATION["seg_store"],
                ),
            )
        return self._seg_store_fn

    def _seg_import(self):
        """Jitted wire-segment import (see
        :func:`build_seg_import_program`)."""
        if self._seg_import_fn is None:
            self._seg_import_fn = _shared_program(
                self._prog_key + ("seg_import",),
                lambda: jax.jit(
                    build_seg_import_program(),
                    donate_argnums=PROGRAM_DONATION["seg_import"],
                ),
            )
        return self._seg_import_fn

    def _logit_row(self):
        """Jitted (1, V) pending-logits row slice (see
        :func:`build_logit_row_program`)."""
        if self._logit_row_fn is None:
            self._logit_row_fn = _shared_program(
                self._prog_key + ("logit_row",),
                lambda: jax.jit(build_logit_row_program()),
            )
        return self._logit_row_fn

    def _paged_prefill_fn(self, bucket: int):
        """Jitted paged admission program for one prompt bucket (see
        :func:`build_paged_prefill_program`)."""
        fn = self._paged_prefill_fns.get(bucket)
        if fn is None:
            fn = _shared_program(
                self._prog_key + ("paged_prefill",),
                lambda: jax.jit(
                    build_paged_prefill_program(
                        self._do_prefill, self._init_caches,
                        self.max_total,
                    ),
                    donate_argnums=self._paged_admit_donate,
                ),
            )
            self._paged_prefill_fns[bucket] = fn
        return fn

    def _paged_insert(self):
        """Jitted paged insert + state set (see
        :func:`build_paged_insert_program`)."""
        if self._paged_insert_fn is None:
            self._paged_insert_fn = _shared_program(
                self._prog_key + ("paged_insert",),
                lambda: jax.jit(
                    build_paged_insert_program(),
                    donate_argnums=PROGRAM_DONATION["paged_insert"],
                ),
            )
        return self._paged_insert_fn

    def _paged_seg_fetch(self):
        """Jitted paged segment fetch (see
        :func:`build_paged_seg_fetch_program`)."""
        if self._paged_seg_fetch_fn is None:
            self._paged_seg_fetch_fn = _shared_program(
                self._prog_key + ("paged_seg_fetch",),
                lambda: jax.jit(build_paged_seg_fetch_program()),
            )
        return self._paged_seg_fetch_fn

    def _paged_seg_import(self):
        """Jitted paged wire-segment import (see
        :func:`build_paged_seg_import_program`)."""
        if self._paged_seg_import_fn is None:
            self._paged_seg_import_fn = _shared_program(
                self._prog_key + ("paged_seg_import",),
                lambda: jax.jit(
                    build_paged_seg_import_program(),
                    donate_argnums=PROGRAM_DONATION["paged_seg_import"],
                ),
            )
        return self._paged_seg_import_fn

    def _block_copy(self):
        """Jitted single-block copy (see
        :func:`build_block_copy_program`)."""
        if self._block_copy_fn is None:
            self._block_copy_fn = _shared_program(
                self._prog_key + ("block_copy",),
                lambda: jax.jit(
                    build_block_copy_program(),
                    donate_argnums=PROGRAM_DONATION["block_copy"],
                ),
            )
        return self._block_copy_fn

    def _batch_prefill_fn(self, bucket: int, nb: int):
        """Jitted BATCHED admission prefill (see
        :func:`build_batch_prefill_program`)."""
        fn = self._batch_prefill_fns.get((bucket, nb))
        if fn is None:
            fn = _shared_program(
                self._prog_key + ("batch_prefill", nb),
                lambda: jax.jit(
                    build_batch_prefill_program(
                        self._do_prefill, self._init_caches,
                        self.max_total, nb,
                    ),
                    donate_argnums=self._admit_donate,
                ),
            )
            self._batch_prefill_fns[(bucket, nb)] = fn
        return fn

    def _batch_hit_fn(self, bucket: int, nb: int):
        """Jitted BATCHED partial-hit admission (see
        :func:`build_batch_hit_program`)."""
        fn = self._batch_hit_fns.get((bucket, nb))
        if fn is None:
            fn = _shared_program(
                self._prog_key + ("batch_hit", nb),
                lambda: jax.jit(
                    build_batch_hit_program(self._fwd_chunk, nb),
                    donate_argnums=self._admit_donate,
                ),
            )
            self._batch_hit_fns[(bucket, nb)] = fn
        return fn

    # -- bucketing ---------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        """Smallest power-of-two bucket >= n (caller ensures
        ``n <= self._max_bucket``)."""
        b = self._min_bucket
        while b < n:
            b *= 2
        return b

    def _chunk_schedule(self, n: int, start: int = 0
                        ) -> list[tuple[int, int, int]]:
        """(offset, real_len, bucket) chunks covering a prompt's rows
        start..n-1 through the power-of-two bucket programs. Every
        write window [offset, offset+bucket) must fit the pooled Tpad
        (a clamped ``dynamic_update_slice`` would SHIFT over real
        rows); when the padded tail would spill, the remainder is
        decomposed into exact power-of-two pieces plus one minimal
        padded tail, which always fits (pieces are sublane multiples,
        Tpad is a sublane multiple). ``start`` > 0 is the partial-hit
        suffix path — the first ``start`` rows came from a cached
        segment; the caller grain-aligns it (start % _min_bucket == 0)
        so the window-fit invariant carries over unchanged."""
        if start % self._min_bucket:
            raise AssertionError(
                f"chunk start {start} not {self._min_bucket}-aligned"
            )
        tpad = self.pool.tpad
        sched, t0, rem = [], start, n - start
        while rem > self._max_bucket:
            sched.append((t0, self._max_bucket, self._max_bucket))
            t0 += self._max_bucket
            rem -= self._max_bucket
        if rem:
            b = self._bucket_for(rem)
            if t0 + b <= tpad:
                sched.append((t0, rem, b))
            else:
                while rem:
                    if rem >= b:
                        sched.append((t0, b, b))
                        t0 += b
                        rem -= b
                    elif b > self._min_bucket:
                        b //= 2
                    else:
                        sched.append((t0, rem, b))
                        rem = 0
        for t0, _, b in sched:  # invariant: no clamped insert, ever
            if t0 + b > tpad:
                raise AssertionError(
                    f"chunk window [{t0}, {t0 + b}) spills Tpad {tpad}"
                )
        return sched

    # -- host-side loop ----------------------------------------------------

    def submit(self, req: Request) -> str:
        """Queue a request (see ``RequestScheduler.submit`` for the
        backpressure/admission contract). Rejections are labelled per
        tenant and per reason (quota vs queue depth) in the metrics."""
        if req.adapter >= max(1, self.n_adapters):
            raise AdmissionError(
                f"request {req.id}: adapter {req.adapter} outside the "
                f"loaded bank ({self.n_adapters} adapters)"
            )
        if self.cfg.gated and req.kind.startswith("kv_"):
            raise NotImplementedError(
                f"request {req.id} ({req.kind}): disaggregated KVSG "
                "frames are not built for a stack of gated layers "
                "(layer_types set): a frame carries K and V rows 0..n of "
                "one slab; a ring leaf holds the last window only, and a "
                "latent leaf has one plane"
            )
        if getattr(req, "uses_sampling_surface", False):
            if not self._surface:
                raise AdmissionError(
                    f"request {req.id}: sampling-surface fields "
                    "(temperature/top_k/top_p/stop/logit_bias/"
                    "logprobs/response_format) need an engine built "
                    "with sampling_surface=True"
                )
            if req.response_format is not None:
                if req.eos_token is None:
                    raise AdmissionError(
                        f"request {req.id}: response_format requires "
                        "eos_token (grammars terminate by permitting "
                        "EOS in accepting states)"
                    )
                kind, spec = parse_response_format(req.response_format)
                try:
                    cg, how = self.grammar_cache.get_or_compile(
                        kind, spec,
                        default_token_bytes(self.cfg.vocab_size),
                        req.eos_token,
                        max_states=self._gtable.capacity - 1,
                    )
                except GrammarError as e:
                    self.metrics.record_grammar_compile("error")
                    raise AdmissionError(
                        f"request {req.id}: {e}"
                    ) from None
                self.metrics.record_grammar_compile(how)
                req._grammar = cg
        try:
            rid = self.scheduler.submit(req)
        except Backpressure as e:
            reason = ("quota" if isinstance(e, QuotaExceeded)
                      else "backpressure")
            self.metrics.record_backpressure()
            self.metrics.record_rejection(reason, tenant=req.tenant_id)
            self.tracer.instant(
                SCHEDULER_TRACK, "backpressure", req_id=req.id
            )
            log_event(_log, "request_rejected", level=logging.DEBUG,
                      req_id=req.id, reason=reason,
                      tenant=req.tenant_id or None)
            raise
        self.tracer.instant(SCHEDULER_TRACK, "submit", req_id=rid)
        log_event(_log, "request_submitted", level=logging.DEBUG,
                  req_id=rid, prompt_len=len(req.prompt),
                  max_new=req.max_new, tenant=req.tenant_id or None,
                  trace_id=req.trace_id or None)
        return rid

    @property
    def results(self) -> dict[str, np.ndarray]:
        """Terminal streams by request id: prompt + generated tokens
        (partial for CANCELLED/EXPIRED/FAILED-while-running). Bounded
        to ``results_cap`` entries, oldest evicted; ``pop_result``
        consumes an entry. Returns a snapshot — the live dict is shared
        with the engine thread."""
        with self._results_lock:
            return dict(self._results)

    def pop_result(self, req_id: str, default=None):
        """Remove and return a terminal stream (front-end consumption:
        read-once keeps the results dict from growing with traffic)."""
        with self._results_lock:
            return self._results.pop(req_id, default)

    @property
    def idle(self) -> bool:
        """True when no request is queued, mid-admission, decoding, or
        awaiting readback. ``pool.n_active`` (not the device mask) is
        what covers the admission window — the slot is acquired before
        the prefill runs, and a concurrent drain must not mistake that
        window for idleness; ``_admitting`` covers the few instructions
        between the scheduler pop and the acquire; ``_inflight`` covers
        the pipelined horizon whose tokens are still on device."""
        return (self.pool.n_active == 0 and self._admitting == 0
                and len(self.scheduler) == 0 and self._inflight is None)

    def cancel(self, req_id: str) -> bool:
        """Cancel by id: flags the request whether it is queued or
        decoding; the engine honors the flag within one horizon.
        Returns False when the id is unknown (already retired or never
        seen)."""
        for st in self._slots:
            if st is not None and st.req.id == req_id:
                st.req.cancel()
                return True
        return self.scheduler.cancel(req_id)

    def preempt_all(self) -> int:
        """Cancel every live and queued request (drain-deadline
        preemption: ``ServingServer.stop`` calls this when ``drain_s``
        elapses, so shutdown converges within one horizon instead of
        waiting out stragglers). Returns the number newly cancelled."""
        n = 0
        for st in self._slots:
            if st is not None and not st.req.cancelled:
                st.req.cancel()
                n += 1
        for rec in self._pending_prefills:
            if not rec.plan.req.cancelled:
                rec.plan.req.cancel()
                n += 1
        return n + self.scheduler.cancel_all()

    # -- live session migration --------------------------------------------
    #
    # Instead of preempting in-flight requests at the drain deadline,
    # the server can EXPORT each active slot as a KVSG frame extended
    # with generation state (tokens so far, remaining budget, the
    # slot's sampling-key words) and re-seat it on another replica
    # mid-generation. Byte parity holds by construction: the exported
    # slab covers rows [0, prompt+generated) — exactly the state a
    # crash-recovery replay of prompt+tokens rebuilds — the pending
    # logits row is the next token's sampling input, and fold_in(key,
    # position) sampling only needs the key words and the position to
    # continue the identical stream, greedy and sampled alike. The
    # receiving engine even recovers migrated sessions through its own
    # crashes: replay uses req.prompt + st.tokens + st.key_data, all
    # of which the seat installs.

    def export_sessions(self) -> list[dict]:
        """Snapshot every live slot for migration and free it WITHOUT
        a terminal status — each request stays RUNNING ("parked"), its
        waiting handler blocked until :meth:`complete_migrated` /
        :meth:`fail_migrated` settles it with the destination's
        outcome. ENGINE-LOOP THREAD ONLY (touches device state and
        slot bookkeeping); the server services it between steps. A
        slot whose snapshot fails is skipped and left live — it falls
        back to the ordinary preempt/recovery path."""
        if self.cfg.gated:
            raise NotImplementedError(
                "session export (KVSG frames) is not built for a stack "
                "of gated layers (layer_types set): a frame carries K and V "
                "rows 0..n of one slab; a ring leaf holds the last window "
                "only, and a latent leaf has one plane"
            )
        if self._inflight is not None:
            # sync the pipelined horizon first so tokens-so-far and the
            # device logits row agree on the export position
            inflight, self._inflight = self._inflight, None
            self._process(inflight)
        now = time.perf_counter()
        out: list[dict] = []
        for slot, st in enumerate(self._slots):
            if st is None or st.req.kind not in ("generate", "kv_session"):
                continue
            if st.req.cancelled or st.req.expired(now):
                continue  # the lifecycle sweep owns these
            if getattr(st.req, "uses_sampling_surface", False):
                # sampling-surface state (grammar FSM position, stop
                # hold-back, bias rows) does not travel on the KVSG
                # wire; these slots drain locally via preempt/recovery
                continue
            t0 = time.perf_counter()
            req = st.req
            try:
                seq = np.concatenate(
                    [req.prompt, np.asarray(st.tokens, np.int32)]
                )
                if self._paged:
                    slab = self._paged_seg_fetch()(
                        self.pool.caches,
                        jnp.asarray(self.pool.table(slot)),
                    )
                else:
                    slab = self._seg_store()(
                        self.pool.alloc_region(1), self.pool.caches,
                        jnp.int32(0), jnp.int32(slot),
                    )
                leaves = [
                    np.asarray(leaf)  # lint: sync-ok migration export copies the live segment to host by design
                    for leaf in jax.tree.leaves(slab)
                ]
                lg = np.asarray(  # lint: sync-ok pending logits row rides the migration frame
                    self._logit_row()(self._logits, jnp.int32(slot))
                )
            except Exception as e:  # noqa: BLE001 — skip slot, keep exporting
                self.flight.record(
                    "migrate_export_failed", req_id=req.id, slot=slot,
                    error=str(e),
                )
                continue
            kd = np.asarray(st.key_data).reshape(-1)
            out.append({
                "req": req,
                "n_streamed": len(st.tokens),
                "config_hash": self.config_hash,
                "tokens": seq,
                "leaves": (slab_to_blocks(leaves, self._block_size)
                           if self._paged else leaves),
                "logits": lg,
                "layout": "paged" if self._paged else "slab",
                "block_size": self._block_size if self._paged else 0,
                "gen": {
                    "n_prompt": int(len(req.prompt)),
                    "tokens": [int(t) for t in st.tokens],
                    "max_new": int(req.max_new),
                    "eos_token": (None if req.eos_token is None
                                  else int(req.eos_token)),
                    "adapter": int(st.adapter),
                    "key_data": [int(x) for x in kd.tolist()],
                    "req_id": req.id,
                },
            })
            # park the request: free the slot with NO terminal status —
            # the destination's decode finishes it, complete_migrated
            # stores the result and wakes the handler
            self.pool.release(slot)
            if self.prefix_cache is not None:
                for seg in st.segs:
                    self.prefix_cache.unpin(seg)
            st.segs = []
            self._slots[slot] = None
            self._dactive = self._deact_fn(self._dactive, jnp.int32(slot))
            self.metrics.record_migration_out(
                len(st.tokens), time.perf_counter() - t0,
                tenant=req.tenant_id,
            )
            self.tracer.instant(
                slot_track(slot), "migrate_out", req_id=req.id,
                n_tokens=len(st.tokens),
            )
            self.flight.record(
                "migrate_out", req_id=req.id, slot=slot,
                n_generated=len(st.tokens),
                tenant=req.tenant_id or None,
            )
            log_event(_log, "session_exported", req_id=req.id, slot=slot,
                      n_generated=len(st.tokens),
                      tenant=req.tenant_id or None)
        return out

    def complete_migrated(self, req: Request, tokens,
                          n_streamed: int = 0) -> None:
        """Settle a parked (exported) request with the DESTINATION
        replica's finished token stream (full sequence: prompt +
        every generated token). Any HTTP/stop thread may call this —
        the slot is long freed, so only results/metrics/stream state
        is touched, all of it lock-guarded or thread-safe."""
        toks = np.asarray(tokens, np.int32).reshape(-1)
        new = [int(t) for t in toks[len(req.prompt):]]
        req.status = RequestStatus.FINISHED
        req.error = None
        self._store_result(req, new)
        self.metrics.record_migration_settled(ok=True,
                                              tenant=req.tenant_id)
        self.flight.record(
            "migrate_settled", req_id=req.id, ok=True,
            n_generated=len(new),
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=req.status.value, n_tokens=len(new),
                  error=None, tenant=req.tenant_id or None,
                  kind="migrated")
        if req.stream is not None:
            for t in new[int(n_streamed):]:
                req.stream.put(t)
            req.stream.put(None)  # end-of-stream sentinel
        if req.done is not None:
            req.done.set()

    def fail_migrated(self, req: Request, error: str,
                      partial=None) -> None:
        """Settle a parked request whose migration did NOT land: the
        soft fallback to the pre-migration drain behavior (preempted →
        CANCELLED), with whatever tokens were generated before export
        preserved as the partial result."""
        req.status = RequestStatus.CANCELLED
        req.error = error
        self._store_result(
            req, [int(t) for t in (partial if partial is not None else ())]
        )
        self.metrics.record_migration_settled(ok=False,
                                              tenant=req.tenant_id)
        self.flight.record(
            "migrate_settled", req_id=req.id, ok=False, error=error,
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=req.status.value, n_tokens=0, error=error,
                  tenant=req.tenant_id or None, kind="migrated")
        if req.stream is not None:
            req.stream.put(None)  # end-of-stream sentinel
        if req.done is not None:
            req.done.set()

    # -- retirement --------------------------------------------------------

    def _store_result(self, req: Request, tokens: list[int]) -> None:
        stream = np.concatenate([req.prompt, np.asarray(tokens, np.int32)])  # lint: sync-ok host token list, no device buffer involved
        with self._results_lock:
            note_access("engine.results", write=True)
            self._results[req.id] = stream
            while len(self._results) > self.results_cap:
                self._results.pop(next(iter(self._results)))

    def _retire(self, slot: int, status: RequestStatus, now: float,
                error: str | None = None, *,
                deactivate: bool = False) -> None:
        """Free a slot and move its request to a terminal status.
        ``deactivate`` also clears the slot's DEVICE active bit — needed
        when the device mask may still be live (cancel/expiry/
        quarantine); a FINISHED slot already deactivated in-program."""
        st = self._slots[slot]
        req = st.req
        req.status = status
        req.error = error
        self._store_result(req, st.tokens)
        if status is RequestStatus.FINISHED:
            decode_s = now - (st.t_first_token or now)
            self.metrics.record_finished(
                req.id, len(st.tokens), decode_s, tenant=req.tenant_id,
            )
            if (req.kind == "generate" and st.t_first_token is not None
                    and req.arrival_time is not None):
                # engine-measured request timing, surfaced in the HTTP
                # response: ttft_s is engine-local (scheduler arrival to
                # first token — excludes any upstream prefill/transfer
                # leg), decode_s is the wall time after the first token,
                # which lets a client recover true end-to-end TTFT as
                # (request wall - decode_s) without streaming
                req.timing = {
                    "ttft_s": st.t_first_token - req.arrival_time,
                    "decode_s": decode_s,
                }
        else:
            self.metrics.record_outcome(status, tenant=req.tenant_id)
        self.pool.release(slot)
        if self.prefix_cache is not None:
            for seg in st.segs:
                self.prefix_cache.unpin(seg)
        st.segs = []
        if self._surface:
            if st.stop_matcher is not None and req.stream is not None:
                # release the hold-back before the end-of-stream
                # sentinel (empty when a stop match consumed it)
                for t in st.stop_matcher.flush():
                    req.stream.put(t)
            self._clear_surface(slot, st)
        self._slots[slot] = None
        if deactivate:
            self._dactive = self._deact_fn(self._dactive, jnp.int32(slot))
        self.tracer.instant(
            slot_track(slot), status.value, ts=now, req_id=req.id,
            n_tokens=len(st.tokens),
        )
        log_event(_log, "request_retired", req_id=req.id, slot=slot,
                  status=status.value, n_tokens=len(st.tokens),
                  error=error, tenant=req.tenant_id or None,
                  trace_id=req.trace_id or None)
        if req.stream is not None:
            req.stream.put(None)  # end-of-stream sentinel
        if req.done is not None:
            req.done.set()

    def _retire_unadmitted(self, req: Request, status: RequestStatus,
                           error: str | None = None) -> None:
        """Terminal status for a request that never got a slot."""
        self._presplit_keys.pop(req.id, None)
        req.status = status
        req.error = error
        self.metrics.record_outcome(status, tenant=req.tenant_id)
        self.tracer.instant(
            SCHEDULER_TRACK, status.value, req_id=req.id
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=status.value, n_tokens=0, error=error,
                  tenant=req.tenant_id or None,
                  trace_id=req.trace_id or None)
        if req.stream is not None:
            req.stream.put(None)  # end-of-stream sentinel
        if req.done is not None:
            req.done.set()

    def _finish(self, slot: int, now: float) -> None:
        self._retire(slot, RequestStatus.FINISHED, now)

    def _serve_embedding(self, req, now: float) -> None:
        """Serve an :class:`EmbeddingRequest` host-side at the
        admission boundary: no KV slot, no device dispatch — a zoo
        embedding model's table lookup — but the full request
        lifecycle (scheduler pop, per-tenant metrics, logs, ``done``),
        proving the serving machinery is model-agnostic."""
        t0 = time.perf_counter()
        emb = self.embedders.get(req.model)
        if emb is None:
            req.status = RequestStatus.FAILED
            req.error = (
                f"unknown embedding model {req.model!r} "
                f"(loaded: {sorted(self.embedders) or 'none'})"
            )
            self.metrics.record_outcome(RequestStatus.FAILED)
        else:
            vectors = {}
            for w in req.words:
                v = emb.get_word_vector(w)
                vectors[w] = None if v is None else np.asarray(v)  # lint: sync-ok host embedding table row, no device buffer
            req.result = vectors
            req.status = RequestStatus.FINISHED
            self.metrics.record_embedding(
                req.model, len(req.words),
                time.perf_counter() - t0, tenant=req.tenant_id,
            )
        self.tracer.instant(
            SCHEDULER_TRACK, "embedding", req_id=req.id,
            model=req.model, n_words=len(req.words),
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=req.status.value, n_tokens=0,
                  error=req.error, tenant=req.tenant_id or None,
                  kind="embedding")
        if req.done is not None:
            req.done.set()

    # -- disaggregated prefill/decode --------------------------------------
    #
    # A PREFILL replica serves KVExportRequests: prefill the prompt
    # into a transiently held pool slot through the SAME bucketed
    # admission programs a monolithic admission dispatches — which is
    # what makes the transfer byte-exact by construction — snapshot the
    # segment slab plus the pending logits row to host, and release the
    # slot without decoding. A DECODE replica serves KVIngestRequests:
    # validate the wire-decoded slab against its own cache geometry,
    # land it in the prefix cache (region import in slab mode, private
    # block scatter in paged mode), and let the follow-up generate
    # full-hit — zero prefill dispatched for the covered prompt. The
    # wire moves bytes and computes nothing
    # (tests/test_serving_disagg.py). An ingest that validation
    # declines is SOFT: the sender falls back to local prefill.

    def _serve_kv_export(self, req, now: float) -> None:
        """Serve a :class:`KVExportRequest` at the admission boundary.
        ``req.result`` gets the raw segment material (host arrays +
        layout metadata) ready for
        :func:`~deeplearning4j_tpu.serving.disagg.encode_segment` —
        framing happens on the HTTP thread, off the engine loop."""
        t0 = time.perf_counter()
        seq = np.asarray(req.prompt, np.int32)
        n = int(len(seq))
        if n + 1 > self.max_total or n > self.pool.tpad:
            self._retire_unadmitted(
                req, RequestStatus.FAILED,
                f"prompt of {n} tokens cannot be exported "
                f"(max_total={self.max_total}, tpad={self.pool.tpad})",
            )
            return
        slot = self.pool.acquire()
        try:
            self._prefill_seq_into_slot(seq, slot, 1, _NO_EOS,
                                        adapter=req.adapter)
            if self._paged:
                slab = self._paged_seg_fetch()(
                    self.pool.caches,
                    jnp.asarray(self.pool.table(slot)),
                )
            else:
                # a 1-slot region IS the batch-1 slab every seat path
                # consumes; seg_store copies the pool slot into it
                slab = self._seg_store()(
                    self.pool.alloc_region(1), self.pool.caches,
                    jnp.int32(0), jnp.int32(slot),
                )
            leaves = [
                np.asarray(leaf)  # lint: sync-ok wire export copies the segment to host by design
                for leaf in jax.tree.leaves(slab)
            ]
            lg = np.asarray(  # lint: sync-ok pending logits row rides the wire frame
                self._logit_row()(self._logits, jnp.int32(slot))
            )
        except BaseException:
            # EngineCrash (or anything unexpected): the popped request
            # must not be dropped — requeue it before the supervisor
            # rebuilds state, exactly like an unseated admission plan.
            self.pool.release(slot)
            self.scheduler.requeue(req)
            raise
        # the slot was only a prefill staging area: clear its device
        # active bit (prefill armed it with budget 1) and free it
        self._dactive = self._deact_fn(self._dactive, jnp.int32(slot))
        self.pool.release(slot)
        req.result = {
            "config_hash": self.config_hash,
            "tokens": seq,
            "leaves": (slab_to_blocks(leaves, self._block_size)
                       if self._paged else leaves),
            "logits": lg,
            "layout": "paged" if self._paged else "slab",
            "block_size": self._block_size if self._paged else 0,
        }
        req.status = RequestStatus.FINISHED
        nbytes = sum(a.nbytes for a in leaves) + lg.nbytes
        self.metrics.record_kv_export(
            n, nbytes, time.perf_counter() - t0, tenant=req.tenant_id,
        )
        # a real admission span (named "prefill", prefix="export") so
        # the merged fleet trace chains controller dispatch -> export
        # prefill -> transfer -> decode ingest; the span id rides the
        # result so the HTTP layer parents its transfer span on it
        tctx = {}
        if self.tracer.enabled and req.trace_id:
            tctx = {"trace_id": req.trace_id, "span_id": new_span_id()}
            if req.parent_span_id:
                tctx["parent_span_id"] = req.parent_span_id
            req.result["span_id"] = tctx["span_id"]
        self.tracer.span(
            SCHEDULER_TRACK, "prefill", t0, time.perf_counter() - t0,
            req_id=req.id, prompt_len=n, prefix="export",
            nbytes=nbytes, **tctx,
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=req.status.value, n_tokens=n, error=None,
                  tenant=req.tenant_id or None, kind="kv_export")
        if req.done is not None:
            req.done.set()

    def _serve_kv_ingest(self, req, now: float) -> None:
        """Seat a wire-delivered KV segment (req.segment: a
        :func:`~deeplearning4j_tpu.serving.disagg.decode_segment`
        dict) in the prefix cache so the follow-up generate request
        full-hits. Slotless and SOFT-failing: every decline reports
        ``{"stored": False, "reason": ...}`` and the sender falls back
        to local prefill, so a decline costs latency, never
        correctness."""
        t0 = time.perf_counter()
        seg_data = req.segment
        tokens = np.asarray(seg_data["tokens"], np.int32)
        n = int(len(tokens))
        cache = self.prefix_cache
        reason = None
        if cache is None:
            reason = "no prefix cache on this replica"
        elif seg_data.get("config_hash") != self.config_hash:
            reason = "model config hash mismatch"
        elif n < self._hit_grain or n > self.pool.tpad:
            reason = (f"segment of {n} tokens not seatable "
                      f"(grain={self._hit_grain}, tpad={self.pool.tpad})")
        stored = False
        if reason is None:
            try:
                slab = self._wire_slab(seg_data)
            except WireError as e:
                reason = str(e)
            else:
                stored, reason = self._seat_wire_segment(
                    tokens, slab, seg_data["logits"]
                )
        req.result = {"stored": stored, "reason": reason, "n_tokens": n}
        req.status = RequestStatus.FINISHED
        self.metrics.record_kv_ingest(
            n, int(seg_data.get("nbytes", 0)),
            time.perf_counter() - t0, stored=stored,
            tenant=req.tenant_id,
        )
        tctx = {}
        if self.tracer.enabled and req.trace_id:
            tctx = {"trace_id": req.trace_id, "span_id": new_span_id()}
            if req.parent_span_id:
                tctx["parent_span_id"] = req.parent_span_id
        self.tracer.span(
            SCHEDULER_TRACK, "kv_ingest", t0,
            time.perf_counter() - t0, req_id=req.id,
            n_tokens=n, stored=stored, **tctx,
        )
        log_event(_log, "request_retired", req_id=req.id, slot=None,
                  status=req.status.value, n_tokens=n,
                  error=None if stored else reason,
                  tenant=req.tenant_id or None, kind="kv_ingest")
        if req.done is not None:
            req.done.set()

    def _wire_slab(self, seg_data: dict):
        """Validate a decoded frame's slab leaves against this
        engine's cache geometry and upload them as the batch-1 device
        pytree every seat path consumes. Raises :class:`WireError`
        (status 400) on any disagreement — geometry is derived from
        the config, so after the hash check a mismatch here means a
        corrupt or hand-rolled frame, not version skew."""
        shapes = jax.eval_shape(
            lambda: self._init_caches(1, self.max_total)
        )
        specs = jax.tree.leaves(shapes)
        leaves = seg_data["leaves"]
        if len(leaves) != len(specs):
            raise WireError(
                f"frame has {len(leaves)} cache leaves, engine "
                f"expects {len(specs)}"
            )
        up = []
        for i, (arr, spec) in enumerate(zip(leaves, specs)):
            if (tuple(arr.shape) != tuple(spec.shape)
                    or arr.dtype != spec.dtype):
                raise WireError(
                    f"leaf {i} is {arr.dtype.name}{tuple(arr.shape)}, "
                    f"engine expects "
                    f"{np.dtype(spec.dtype).name}{tuple(spec.shape)}"
                )
            up.append(jnp.asarray(arr))
        lg = seg_data["logits"]
        if (tuple(lg.shape) != (1, self.cfg.vocab_size)
                or lg.dtype != np.float32):
            raise WireError(
                f"logits are {lg.dtype.name}{tuple(lg.shape)}, engine "
                f"expects float32(1, {self.cfg.vocab_size})"
            )
        return jax.tree.unflatten(jax.tree.structure(shapes), up)

    def _seat_wire_segment(self, tokens: np.ndarray, slab,
                           logits_row) -> tuple[bool, str | None]:
        """Insert ``tokens`` in the prefix cache and back every new
        segment with the wire slab's rows. Returns ``(stored,
        reason)`` where ``stored`` means the follow-up generate will
        FULL-hit (full-length segment seated with its logits row)."""
        cache = self.prefix_cache
        n = int(len(tokens))
        seg, matched = cache.lookup(tokens)
        if seg is not None and matched == n and seg.logits is not None:
            return True, "already cached"
        segs = cache.insert(tokens)
        if not segs:
            return False, "cache declined (all segments pinned)"
        stored = False
        for seg in segs:
            if self._paged:
                if not self._back_paged_wire_segment(seg, slab):
                    # block allocation lost to admission pressure:
                    # un-cache rather than leave an unbacked segment
                    cache.drop(seg)
                    continue
            else:
                cache.region = self._seg_import()(
                    cache.region, slab, jnp.int32(seg.slot)
                )
            if seg.length == n:
                seg.logits = jnp.asarray(logits_row)
                stored = True
            self.metrics.record_prefix_insert()
            self.tracer.instant(
                ENGINE_TRACK, "prefix_insert", source="wire",
                length=seg.length,
            )
            cache.unpin(seg)
        return stored, None if stored else "segment backing failed"

    def _back_paged_wire_segment(self, seg, slab) -> bool:
        """Back one paged wire segment with freshly allocated private
        blocks holding the slab's rows — there is no donor slot to
        alias; the prefill happened on another replica. Rows past the
        segment's block span scatter to the sentinel block and vanish.
        False when the allocation loses to admission pressure."""
        need = self.pool.blocks_needed(seg.length)
        try:
            ids = self.pool.alloc_blocks(need)
        except RuntimeError:
            return False
        row = np.zeros((self.pool.blocks_per_slot,), np.int32)
        row[:need] = ids
        self.pool.caches = self._paged_seg_import()(
            self.pool.caches, jnp.asarray(row), slab
        )
        seg.block_ids = ids
        return True

    def _serve_kv_session(self, req, now: float) -> None:
        """Seat a LIVE migrated session (:class:`KVSessionRequest`) in
        a fresh slot mid-generation. The wire slab covers rows
        [0, prompt+generated); seating it with pos0 = that length and
        budget = remaining is EXACTLY the full-hit insert of a
        seq-so-far segment — an existing program family — after which
        the ordinary decode loop continues the stream.
        The migrated sampling-key words are installed verbatim so
        fold_in(key, position) draws the same randomness the source
        would have: byte-identical continuation, greedy AND sampled.
        Every decline is SOFT (``result["seated"] is False`` → the
        sender keeps its existing fail path for that session)."""
        t0 = time.perf_counter()
        seg_data = req.segment
        n0 = int(len(req.prompt))
        g = len(req.gen_tokens)
        m = n0 + g
        budget = int(req.max_new) - g
        kd = np.asarray(
            () if req.key_data is None else req.key_data,
            self._slot_keys.dtype,
        ).reshape(-1)
        reason = None
        if seg_data.get("config_hash") != self.config_hash:
            reason = "model config hash mismatch"
        elif int(len(seg_data["tokens"])) != m:
            reason = (f"frame covers {len(seg_data['tokens'])} tokens, "
                      f"session claims prompt {n0} + generated {g}")
        elif n0 + int(req.max_new) > self.max_total or m > self.pool.tpad:
            reason = (f"session of {m} tokens / budget {req.max_new} "
                      f"does not fit (max_total={self.max_total}, "
                      f"tpad={self.pool.tpad})")
        elif budget < 1:
            reason = "session has no remaining budget"
        elif kd.shape != self._slot_keys.shape[1:]:
            reason = (f"sampling key has {kd.shape} words, engine "
                      f"uses {self._slot_keys.shape[1:]}")
        if reason is None:
            try:
                slab = self._wire_slab(seg_data)
            except WireError as e:
                reason = str(e)
        if reason is not None:
            req.result = {"seated": False, "reason": reason}
            self.metrics.record_migration_in(
                g, time.perf_counter() - t0, seated=False,
                tenant=req.tenant_id,
            )
            self.flight.record(
                "migrate_declined", req_id=req.id, reason=reason,
            )
            self._retire_unadmitted(req, RequestStatus.FAILED, reason)
            return
        eos_tok = _NO_EOS if req.eos_token is None else int(req.eos_token)
        slot = self.pool.acquire()
        try:
            if self._paged:
                self._paged_ensure_blocks(slot, m + budget)
            insert = self._paged_insert() if self._paged else self._insert()
            self._set_state(insert(
                *self._state(), slab, jnp.asarray(seg_data["logits"]),
                jnp.int32(slot), jnp.int32(m), jnp.int32(budget),
                jnp.int32(eos_tok),
            ))
        except BaseException:
            # EngineCrash (or anything unexpected): the popped request
            # must not be dropped — requeue it before the supervisor
            # rebuilds state, exactly like an unseated admission plan.
            self.pool.release(slot)
            self.scheduler.requeue(req)
            raise
        # NO key split here: the slot continues the SOURCE's stream, so
        # the migrated key words are installed verbatim and this
        # engine's own key chain is untouched (its replay determinism
        # for locally admitted requests is unaffected).
        self._slot_keys[slot] = kd
        self._slot_adapters[slot] = req.adapter
        st = _SlotState(req, self.pool.generation(slot), kd, req.adapter)
        st.tokens = list(req.gen_tokens)
        st.n_substeps = len(st.tokens)
        st.t_first_token = now if g else None
        self._slots[slot] = st
        req.status = RequestStatus.RUNNING
        req.result = {"seated": True, "n_tokens": m}
        self.metrics.record_migration_in(
            g, time.perf_counter() - t0, seated=True,
            tenant=req.tenant_id,
        )
        tctx = {}
        if self.tracer.enabled and req.trace_id:
            tctx = {"trace_id": req.trace_id, "span_id": new_span_id()}
            if req.parent_span_id:
                tctx["parent_span_id"] = req.parent_span_id
        self.tracer.span(
            slot_track(slot), "migrate_in", t0,
            time.perf_counter() - t0, req_id=req.id,
            n_tokens=m, **tctx,
        )
        self.flight.record(
            "migrate_seated", req_id=req.id, slot=slot,
            n_generated=g, budget=budget,
            tenant=req.tenant_id or None,
        )
        log_event(_log, "session_seated", req_id=req.id, slot=slot,
                  prompt_len=n0, n_generated=g, budget=budget,
                  tenant=req.tenant_id or None)

    def _slot_of(self, req_id: str | None) -> int | None:
        if req_id is None:
            return None
        for slot, st in enumerate(self._slots):
            if st is not None and st.req.id == req_id:
                return slot
        return None

    # lint: hot-path
    def _sweep_lifecycle(self, now: float) -> None:
        """Retire cancelled / deadline-expired occupied slots (this is
        what bounds slot occupation to one horizon past cancel/expiry).
        Tokens still in flight for a swept slot are discarded at sync
        by the snapshot identity check."""
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            if st.req.cancelled:
                self._retire(slot, RequestStatus.CANCELLED, now,
                             deactivate=True)
            elif st.req.expired(now):
                self._retire(slot, RequestStatus.EXPIRED, now,
                             deactivate=True)
        # piggyback records hold a slot before seating — sweep them on
        # the same cadence so a cancelled/expired deferred admission
        # frees its slot (and pinned segment) within one horizon too
        if self._pending_prefills:
            kept: deque[_PendingPrefill] = deque()
            while self._pending_prefills:
                rec = self._pending_prefills.popleft()
                req = rec.plan.req
                if req.cancelled or req.expired(now):
                    self._drop_pending(
                        rec,
                        RequestStatus.CANCELLED if req.cancelled
                        else RequestStatus.EXPIRED,
                    )
                else:
                    kept.append(rec)
            self._pending_prefills = kept

    def _drop_pending(self, rec: _PendingPrefill,
                      status: RequestStatus,
                      error: str | None = None) -> None:
        """Release a deferred admission's slot + pinned segment and
        retire its request without seating. Executed chunks stay
        charged to the tenant's DRR deficit (the device time was
        spent); the un-executed remainder was already credited back at
        defer time."""
        pl = rec.plan
        if pl.seg is not None and self.prefix_cache is not None:
            self.prefix_cache.unpin(pl.seg)
            pl.seg = None
        self.pool.release(pl.slot)
        self._retire_unadmitted(pl.req, status, error)

    # -- admission ---------------------------------------------------------

    def _prefill_into_state(self, state, seq: np.ndarray, slot: int,
                            budget: int, eos_tok: int,
                            adapter: int = 0, paged: bool = False):
        """Land ``seq`` in ``slot`` of a pool-shaped ``state`` tuple
        through the bucketed prefill path and return the new state
        (pure w.r.t. engine attributes — the schedule tests run it on
        scratch state). Dispatches O(1) programs for bucket-sized
        sequences and O(len/bucket) on the chunked long-prompt path.
        ``adapter`` selects the LoRA bank row (traced data, so every
        adapter shares the bucket's one compiled program). With
        ``paged`` the state's caches are the {"blocks", "tables"} dict
        and the two landing dispatches switch to the paged programs —
        everything else (bucketing, chunk windows, the batch-1 scratch
        compute) is byte-for-byte the slab path."""
        n = int(len(seq))
        ad = jnp.asarray([adapter], jnp.int32)
        insert = self._paged_insert() if paged else self._insert()
        if n == 0:
            # empty prompt: decode starts from uniform logits over a
            # zeroed slab, as the unbucketed prefill did
            tmp = self._init_caches(1, self.max_total)
            lg = jnp.zeros((1, self.cfg.vocab_size), jnp.float32)
            return insert(
                *state, tmp, lg, jnp.int32(slot), jnp.int32(0),
                jnp.int32(budget), jnp.int32(eos_tok),
            )
        if n <= self._max_bucket:
            b = self._bucket_for(n)
            pad = np.zeros((1, b), np.int32)
            pad[0, :n] = seq
            self.prefill_dispatches += 1
            self._count_program("paged_prefill" if paged else "prefill")
            pf = self._paged_prefill_fn(b) if paged else self._prefill_fn(b)
            return pf(
                *state, self.params, jnp.asarray(pad), jnp.int32(n - 1),
                jnp.int32(slot), jnp.int32(n), jnp.int32(budget),
                jnp.int32(eos_tok), ad,
            )
        # chunked: walk the prompt through forward_chunk at bucket
        # sizes over a batch-1 scratch cache, then one slab insert —
        # a long admission compiles nothing new and never stalls
        # the decode loop on a monster program
        tmp = self._init_caches(1, self.max_total)
        lg = None
        for t0, ln, b in self._chunk_schedule(n):
            pad = np.zeros((1, b), np.int32)
            pad[0, :ln] = seq[t0:t0 + ln]
            self._count_program("chunk")
            tmp, lg = self._chunk_fn(b)(
                self.params, tmp, jnp.asarray(pad), jnp.int32(t0),
                jnp.int32(ln - 1), ad,
            )
            self.prefill_dispatches += 1
        return insert(
            *state, tmp, lg, jnp.int32(slot), jnp.int32(n),
            jnp.int32(budget), jnp.int32(eos_tok),
        )

    def _caches_in(self):
        """The caches operand for the next dispatch. Paged mode
        rebuilds the {"blocks", "tables"} dict with a FRESH device
        mirror of the host block tables EVERY call — a stale mirror
        from before a release/re-admit would scatter a dead slot's
        decode rows into blocks the pool has since handed to someone
        else, so never cache this across pool mutations."""
        if self._paged:
            return {
                "blocks": self.pool.caches,
                "tables": jnp.asarray(self.pool.tables()),
            }
        return self.pool.caches

    def _caches_out(self, caches) -> None:
        """Re-own the caches a dispatch returned (the table mirror is
        discarded — the host tables are the source of truth)."""
        self.pool.caches = caches["blocks"] if self._paged else caches

    def _state(self):
        return (self._caches_in(), self._logits, self._dpos,
                self._dactive, self._dbudget, self._deos)

    def _set_state(self, out) -> None:
        (caches, self._logits, self._dpos, self._dactive,
         self._dbudget, self._deos) = out
        self._caches_out(caches)

    def _paged_ensure_blocks(self, slot: int, n_tokens: int) -> None:
        """Grow ``slot``'s block coverage to ``n_tokens`` rows with
        fresh private blocks (no-op when already covered — the aliased
        prefix-hit entries stay untouched). Clamped to the slab row
        bound: rows past Tpad cannot exist in either layout."""
        n_tokens = min(int(n_tokens), self.pool.tpad)
        need = self.pool.blocks_needed(n_tokens)
        have = int(np.count_nonzero(self.pool.table(slot)))
        if need > have:
            self.pool.alloc_slot_blocks(slot, n_tokens, start=have)

    def _prefill_seq_into_slot(self, seq: np.ndarray, slot: int,
                               budget: int, eos_tok: int,
                               adapter: int = 0) -> None:
        """Land ``seq`` (prompt, or prompt+replayed tokens) in ``slot``
        through the bucketed prefill path and set the slot's device
        state: position len(seq), active, ``budget`` tokens
        remaining."""
        if self._paged:
            # cover every row the slot can ever write BEFORE building
            # the state tuple, so the fresh table mirror includes the
            # allocation (rows past coverage scatter to the sentinel
            # and vanish)
            self._paged_ensure_blocks(slot, len(seq) + budget)
        self._set_state(self._prefill_into_state(
            self._state(), seq, slot, budget, eos_tok, adapter,
            paged=self._paged,
        ))

    def _check_prefill_faults(self, req: Request) -> bool:
        """The admission fault boundary under transient-retry
        supervision — one check per ADMISSION (not per chunk or per
        batch), so scripted chaos plans stay request-aligned. Returns
        False when the request is poisoned (caller fails it);
        ``EngineCrash`` propagates to the supervisor."""
        if self.faults is None:
            return True
        attempt, backoff = 0, self.retry_backoff_s
        while True:
            try:
                self.faults.check("prefill", req_id=req.id)
                return True
            except TransientFault as e:
                self.metrics.record_retry()
                attempt += 1
                if attempt > self.max_retries:
                    req.error = (
                        f"transient prefill fault persisted past "
                        f"{self.max_retries} retries: {e}"
                    )
                    return False
                time.sleep(backoff)
                backoff = min(backoff * 2, self.max_backoff_s)
            except PermanentFault as e:
                req.error = str(e)
                return False

    def _classify_plan(self, pl: _AdmitPlan) -> None:
        """Prefix-cache lookup for one planned admission. A FULL hit
        (whole prompt cached, stored logits present) admits by pure
        copy; a PARTIAL hit reuses the longest cached prefix rounded
        DOWN to the bucket grain (suffix chunk windows must start
        sublane-aligned to provably fit Tpad) and chunk-computes only
        the suffix. The source segment is pinned here and unpinned at
        retirement, so eviction can never drop a segment an active
        slot's admission read."""
        cache = self.prefix_cache
        n = len(pl.req.prompt)
        # adapter != 0 prompts are NOT cacheable or reusable: the MLP
        # delta makes every later layer's KV rows adapter-dependent, so
        # segments are base-model-only and nonzero adapters always take
        # the full prefill path
        if cache is None or n == 0 or pl.req.adapter != 0:
            return
        seg, m = cache.lookup(pl.req.prompt)
        if seg is None:
            self.metrics.record_prefix_lookup("miss", 0)
            return
        if m == n and seg.logits is not None:
            pl.kind, pl.seg, pl.matched = "full", seg, n
        else:
            L = min(m, n - 1)
            L -= L % self._hit_grain
            if L <= 0:
                self.metrics.record_prefix_lookup("miss", 0)
                return
            pl.kind, pl.seg, pl.matched = "partial", seg, L
        cache.pin(seg)
        self.metrics.record_prefix_lookup(
            "hit_full" if pl.kind == "full" else "hit_partial",
            pl.matched,
        )
        self.tracer.instant(
            slot_track(pl.slot), "prefix_hit", req_id=pl.req.id,
            kind=pl.kind, cached_tokens=pl.matched, prompt_len=n,
        )

    def _paged_seg_tmp(self, seg):
        """Gather a cached segment's blocks into a batch-1 scratch slab
        (sentinel-padded table row, so rows past the segment's block
        span come back zero). The chunked suffix programs and the paged
        insert consume it exactly like a slab-mode segment fetch."""
        row = np.zeros((self.pool.blocks_per_slot,), np.int32)
        row[:len(seg.block_ids)] = seg.block_ids
        return self._paged_seg_fetch()(
            self.pool.caches, jnp.asarray(row)
        )

    def _alias_hit_blocks(self, pl: _AdmitPlan, covered: int) -> None:
        """Land a prefix hit's cached rows by table aliasing: share the
        segment's FULL blocks over rows [0, covered) into the slot
        (refcount bump, zero device work), then cover the rest of the
        slot's writable range with fresh private blocks. The segment's
        copied tail block (when its length is not block-aligned) is
        never aliased — rows the slot itself writes, hit-suffix or
        decode, must land in private blocks."""
        full = covered // self.pool.block_size
        if full:
            self.pool.alias_into_slot(pl.slot, pl.seg.block_ids[:full])
        self._paged_ensure_blocks(
            pl.slot, len(pl.req.prompt) + pl.req.max_new
        )

    def _admit_full_hit(self, pl: _AdmitPlan) -> None:
        """Admission by pure device copy: segment slab + stored logits.
        Dispatches ZERO prefill programs for the cached portion — which
        is all of it. Paged mode goes further: the segment's full
        blocks are byte-SHARED into the slot's table (aliasing, no
        copy); one gather + one insert land the tail rows and re-zero
        the fresh private blocks."""
        req = pl.req
        n = len(req.prompt)
        eos_tok = _NO_EOS if req.eos_token is None else int(req.eos_token)
        if self._paged:
            self._alias_hit_blocks(pl, n)
            tmp = self._paged_seg_tmp(pl.seg)
            self._set_state(self._paged_insert()(
                *self._state(), tmp, pl.seg.logits, jnp.int32(pl.slot),
                jnp.int32(n), jnp.int32(req.max_new),
                jnp.int32(eos_tok),
            ))
            return
        self._set_state(self._hit_insert()(
            *self._state(), self.prefix_cache.region, pl.seg.logits,
            jnp.int32(pl.seg.slot), jnp.int32(pl.slot),
            jnp.int32(n), jnp.int32(req.max_new),
            jnp.int32(eos_tok),
        ))

    def _admit_partial_hit(self, pl: _AdmitPlan) -> None:
        """Serial partial-hit assembly: fetch the segment slab as the
        scratch cache, chunk-compute rows [matched, n) through the same
        bucket programs the long-prompt path uses, then one slab
        insert. Only the uncached suffix costs prefill dispatches. In
        paged mode the matched rows additionally land in the slot by
        block ALIASING (the hit grain is block-aligned, so the matched
        range is whole shared blocks) and the insert scatters through
        the slot's table."""
        req = pl.req
        seq, n, L = req.prompt, len(req.prompt), pl.matched
        eos_tok = _NO_EOS if req.eos_token is None else int(req.eos_token)
        if self._paged:
            self._alias_hit_blocks(pl, L)
            tmp = self._paged_seg_tmp(pl.seg)
        else:
            tmp = self._seg_fetch()(
                self.prefix_cache.region, jnp.int32(pl.seg.slot)
            )
        lg = None
        for t0, ln, b in self._chunk_schedule(n, start=L):
            pad = np.zeros((1, b), np.int32)
            pad[0, :ln] = seq[t0:t0 + ln]
            self._count_program("chunk")
            tmp, lg = self._chunk_fn(b)(
                self.params, tmp, jnp.asarray(pad), jnp.int32(t0),
                jnp.int32(ln - 1),
                jnp.asarray([req.adapter], jnp.int32),
            )
            self.prefill_dispatches += 1
        insert = self._paged_insert() if self._paged else self._insert()
        self._set_state(insert(
            *self._state(), tmp, lg, jnp.int32(pl.slot), jnp.int32(n),
            jnp.int32(req.max_new), jnp.int32(eos_tok),
        ))

    @staticmethod
    def _pad_group(group: list, nb: int) -> list:
        """Pad a batched-admission group to ``nb`` rows by repeating
        the first plan — the duplicate rows recompute identical values
        and re-write them to the same slot, so the result is unchanged
        while the compiled-program count stays at powers of two."""
        return group + [group[0]] * (nb - len(group))

    def _batch_prefill_group(self, bucket: int,
                             group: list[_AdmitPlan]) -> None:
        """One dispatched program admits every plan in ``group`` (all
        misses padding to the same bucket)."""
        nb = 1
        while nb < len(group):
            nb *= 2
        rows = self._pad_group(group, nb)
        prompts = np.zeros((nb, bucket), np.int32)
        last_idx = np.zeros((nb,), np.int32)
        slots = np.zeros((nb,), np.int32)
        pos0 = np.zeros((nb,), np.int32)
        max_new = np.zeros((nb,), np.int32)
        eos_toks = np.full((nb,), _NO_EOS, np.int32)
        adapters = np.zeros((nb,), np.int32)
        for r, pl in enumerate(rows):
            n = len(pl.req.prompt)
            prompts[r, :n] = pl.req.prompt
            last_idx[r] = n - 1
            slots[r] = pl.slot
            pos0[r] = n
            max_new[r] = pl.req.max_new
            if pl.req.eos_token is not None:
                eos_toks[r] = int(pl.req.eos_token)
            adapters[r] = pl.req.adapter
        self.prefill_dispatches += 1
        self._count_program("batch_prefill")
        self._set_state(self._batch_prefill_fn(bucket, nb)(
            *self._state(), self.params, jnp.asarray(prompts),
            jnp.asarray(last_idx), jnp.asarray(slots),
            jnp.asarray(pos0), jnp.asarray(max_new),
            jnp.asarray(eos_toks), jnp.asarray(adapters),
        ))
        self.metrics.record_batched_admissions(len(group))

    def _batch_hit_group(self, bucket: int, L: int,
                         group: list[_AdmitPlan]) -> None:
        """One dispatched program admits every plan in ``group`` (all
        partial hits with cached length L and a single suffix window of
        the same bucket)."""
        nb = 1
        while nb < len(group):
            nb *= 2
        rows = self._pad_group(group, nb)
        seg_idx = np.zeros((nb,), np.int32)
        toks = np.zeros((nb, bucket), np.int32)
        last_idx = np.zeros((nb,), np.int32)
        slots = np.zeros((nb,), np.int32)
        posf = np.zeros((nb,), np.int32)
        max_new = np.zeros((nb,), np.int32)
        eos_toks = np.full((nb,), _NO_EOS, np.int32)
        adapters = np.zeros((nb,), np.int32)
        for r, pl in enumerate(rows):
            n = len(pl.req.prompt)
            ln = n - L
            seg_idx[r] = pl.seg.slot
            toks[r, :ln] = pl.req.prompt[L:]
            last_idx[r] = ln - 1
            slots[r] = pl.slot
            posf[r] = n
            max_new[r] = pl.req.max_new
            if pl.req.eos_token is not None:
                eos_toks[r] = int(pl.req.eos_token)
            adapters[r] = pl.req.adapter
        self.prefill_dispatches += 1
        self._count_program("batch_hit")
        self._set_state(self._batch_hit_fn(bucket, nb)(
            *self._state(), self.params, self.prefix_cache.region,
            jnp.asarray(seg_idx), jnp.asarray(toks), jnp.int32(L),
            jnp.asarray(last_idx), jnp.asarray(slots),
            jnp.asarray(posf), jnp.asarray(max_new),
            jnp.asarray(eos_toks), jnp.asarray(adapters),
        ))
        self.metrics.record_batched_admissions(len(group))

    # lint: hot-path
    def _split_slot_key(self, req_id: int) -> np.ndarray:
        """Advance the master key chain by one admission and read the
        new slot's key back. The split is a tiny device program queued
        behind the horizon in flight, so the readback waits for up to a
        whole step: the loop waiting on the device, booked as
        ``key_sync`` and not as the phase it interrupts."""
        self._key, sub = jax.random.split(self._key)
        with self._regions("key_sync", req_id=req_id):
            return np.asarray(jax.random.key_data(sub))  # lint: sync-ok per-admission key snapshot (tiny, off the decode critical section)

    # lint: hot-path
    def _seat_plan(self, pl: _AdmitPlan, now: float) -> None:
        """Host bookkeeping that makes an executed plan a live slot:
        sampling key split (in admission order — the order replay
        reproduces), slot state, metrics, spans."""
        req, slot = pl.req, pl.slot
        # piggyback engines pre-split at plan execution (same order)
        # so a prefill deferred across horizons cannot reorder the
        # master key chain; everyone else splits here, at seating
        kd = self._presplit_keys.pop(req.id, None)
        if kd is None:
            kd = self._split_slot_key(req.id)
        self._slot_keys[slot] = kd
        self._slot_adapters[slot] = req.adapter
        st = _SlotState(req, self.pool.generation(slot), kd,
                        req.adapter)
        if pl.seg is not None:
            st.segs.append(pl.seg)
        if self._surface:
            self._seat_surface(slot, st, req)
        self._slots[slot] = st
        pl.admitted = True
        req.status = RequestStatus.RUNNING
        self.metrics.record_prefill(req.id, pl.prefill_s)
        st.t_boundary = pl.t_boundary
        st.t_seated = time.perf_counter()
        delay = (st.t_seated - req.arrival_time
                 if req.arrival_time is not None else None)
        if delay is not None:
            self.metrics.record_admitted(req.id, delay,
                                         tenant=req.tenant_id)
            self.tracer.span(
                SCHEDULER_TRACK, "queued", req.arrival_time,
                delay, req_id=req.id,
            )
        # the ADMISSION span: when the request carries distributed-
        # trace context (router/server resolved a traceparent), the
        # span joins the fleet trace — parent_span_id is the upstream
        # dispatch span, so trace-merge draws the cross-process arrow
        # into this span
        tctx = {}
        if self.tracer.enabled and req.trace_id:
            tctx = {"trace_id": req.trace_id, "span_id": new_span_id()}
            if req.parent_span_id:
                tctx["parent_span_id"] = req.parent_span_id
        self.tracer.span(
            slot_track(slot), "prefill", pl.t_pf, pl.prefill_s,
            req_id=req.id, prompt_len=len(req.prompt),
            prefix=pl.kind, cached_tokens=pl.matched, **tctx,
        )
        self.flight.record(
            "admit", req_id=req.id, slot=slot,
            prompt_len=len(req.prompt), prefix=pl.kind,
            tenant=req.tenant_id or None,
            trace_id=req.trace_id or None,
        )
        log_event(_log, "request_admitted", req_id=req.id,
                  slot=slot, prompt_len=len(req.prompt),
                  queue_delay_s=delay,
                  prefill_s=round(pl.prefill_s, 6),
                  prefix=pl.kind, cached_tokens=pl.matched,
                  tenant=req.tenant_id or None,
                  adapter=req.adapter or None,
                  trace_id=req.trace_id or None)

    def _seat_surface(self, slot: int, st: _SlotState,
                      req: Request) -> None:
        """Seat the slot's sampling-surface rows: host mirror vectors
        (snapshotted per dispatch, re-seated at recovery — the
        _slot_adapters contract), the compiled grammar in the combined
        table, and the DEVICE-resident FSM state row. Defaults
        reproduce the engine-wide sampler bitwise (temperature/top_k
        engine values, p=1, no bias, state 0)."""
        t = (req.temperature if req.temperature is not None
             else self.temperature)
        k = req.top_k if req.top_k is not None else (self.top_k or 0)
        p = req.top_p if req.top_p is not None else 1.0
        self._slot_temps[slot] = np.float32(t)
        self._slot_topks[slot] = np.int32(k)
        self._slot_topps[slot] = np.float32(p)
        self._slot_bias_idx[slot] = -1
        self._slot_bias_val[slot] = 0.0
        if req.logit_bias:
            for j, (ti, tv) in enumerate(sorted(req.logit_bias.items())):
                self._slot_bias_idx[slot, j] = ti
                self._slot_bias_val[slot, j] = tv
        start = 0
        if req._grammar is not None:
            try:
                start = self._gtable.seat(req._grammar)
                st.gkey = req._grammar.key
            except GrammarError as e:
                # seat-time pressure (table rows pinned by live
                # requests): submit's budget check passed, so this is
                # a transient-capacity edge. Never decode this slot
                # unconstrained — cancel before its first step.
                req.error = str(e)
                req.cancel()
                log_event(_log, "grammar_seat_failed", req_id=req.id,
                          error=str(e))
        st.gstate0 = int(start)
        self._slot_gstate[slot] = start
        self._dgstate = self._gstate_set()(
            self._dgstate, jnp.int32(slot), jnp.int32(start)
        )
        st.stop_matcher = StopMatcher(req.stop) if req.stop else None
        st.lp_out = [] if req.logprobs else None

    def _clear_surface(self, slot: int, st: _SlotState) -> None:
        """Retire-side inverse of ``_seat_surface``: drop the grammar
        refcount and reset the host mirror rows to engine defaults.
        The device FSM row is NOT rewritten — a stale state on an
        inactive slot is inert (draws forced to 0, advance gated on
        active) and the next occupant's seat overwrites it."""
        if st.gkey is not None:
            self._gtable.release(st.gkey)
            st.gkey = None
        self._slot_gstate[slot] = 0
        self._slot_temps[slot] = self.temperature
        self._slot_topks[slot] = int(self.top_k or 0)
        self._slot_topps[slot] = 1.0
        self._slot_bias_idx[slot] = -1
        self._slot_bias_val[slot] = 0.0
        if st.lp_out is not None:
            st.req.logprobs_out = st.lp_out

    def _maybe_insert_prefix(self, pl: _AdmitPlan) -> None:
        """Insert-on-completion (of the prefill): cache the admitted
        prompt's full KV as a new segment — one slab copy into the
        region plus the (1, V) logits row, both captured before any
        decode step touches the slot. ``insert`` may return a second
        segment at a newly observed branch point (two prompts seen
        diverging there — the system-prompt sharing signal); it gets
        the same slab copy but NO logits row (no request ended at that
        length, so it only ever serves partial hits). The creating
        request pins every segment until retirement.

        Paged storage inverts the copy direction of the slab region:
        instead of copying the slot's slab OUT, the segment takes
        cache-owned REFERENCES on the slot's own full blocks (incref —
        the slot never rewrites rows below its prompt length) plus one
        privately copied tail block when the length is not
        block-aligned (the slot keeps writing that block's remaining
        rows). One block copy at most, usually zero device work."""
        cache = self.prefix_cache
        n = len(pl.req.prompt)
        if (cache is None or pl.kind == "full" or pl.req.adapter != 0
                or n < self._min_bucket):
            return
        for seg in cache.insert(pl.req.prompt):
            if self._paged:
                if not self._paged_store_segment(seg, pl.slot):
                    # tail-block allocation lost to admission pressure:
                    # un-cache rather than leave an unbacked segment
                    cache.drop(seg)
                    continue
            else:
                cache.region = self._seg_store()(
                    cache.region, self.pool.caches, jnp.int32(seg.slot),
                    jnp.int32(pl.slot),
                )
            if seg.length == n:
                seg.logits = self._logit_row()(
                    self._logits, jnp.int32(pl.slot))
            self._slots[pl.slot].segs.append(seg)
            self.metrics.record_prefix_insert()
            self.tracer.instant(
                ENGINE_TRACK, "prefix_insert", req_id=pl.req.id,
                length=seg.length,
            )

    def _paged_store_segment(self, seg, slot: int) -> bool:
        """Back a new segment with block references off donor ``slot``:
        incref the donor's full blocks (aliased, zero device work —
        the donor only ever writes rows >= seg.length, which live in
        later blocks) and COPY the partial tail block, if any, into a
        cache-private block (the donor keeps writing that block's
        remaining rows). Returns False — no references taken — when
        the tail block cannot be allocated."""
        bs = self.pool.block_size
        row = self.pool.table(slot)
        full = seg.length // bs
        tail = seg.length % bs
        try:
            tail_ids = self.pool.alloc_blocks(1) if tail else []
        except RuntimeError:
            return False
        ids = [int(b) for b in row[:full]]
        self.pool.incref(ids)
        if tail:
            self.pool.caches = self._block_copy()(
                self.pool.caches, jnp.int32(int(row[full])),
                jnp.int32(tail_ids[0]),
            )
        seg.block_ids = ids + tail_ids
        return True

    # lint: hot-path
    def _admit(self, now: float) -> None:
        """Admission at a horizon boundary: pop every admissible
        request (one per free slot), classify each against the prefix
        cache, then execute — misses that pad to the same bucket
        coalesce into ONE dispatched prefill program, partial hits
        sharing (bucket, cached length) coalesce the same way, full
        hits admit by pure copy — and finally seat slot states in
        admission order. A crash mid-batch requeues every plan that was
        not yet seated (front of its class, original order) and
        releases its slot/segment pins before the supervisor rebuilds
        state."""
        if not len(self.scheduler):
            return
        if not (self.pool.n_free or self.scheduler.has_kind("embedding")
                or self.scheduler.has_kind("kv_ingest")):
            return
        self._admitting += 1
        plans: list[_AdmitPlan] = []
        # per-tenant slot caps: live occupancy plus this batch's plans
        # (so one admission round cannot overshoot a cap)
        used: dict[str, int] = {}
        if self.tenancy is not None:
            for st in self._slots:
                if st is not None:
                    tid = st.req.tenant_id
                    used[tid] = used.get(tid, 0) + 1

        # paged: blocks this admission round has already promised to
        # plans not yet executed — two plans must not both pass the
        # free-heap check against the same blocks. Conservative (a
        # prefix hit will alias part of its need), so execution-time
        # allocation can never fail.
        reserved = [0]

        def admissible(r):
            if r.kind in ("embedding", "kv_ingest"):
                return True  # served host-side at admission, slotless
            # generate AND kv_export take the slot checks below
            # (an export transiently holds a pool slot for its prefill)
            if self.pool.n_free == 0:
                return False
            if self._paged:
                need = self.pool.blocks_needed(
                    len(r.prompt) + r.max_new
                )
                while need + reserved[0] > self.pool.n_free_blocks:
                    # hand cached blocks back to the free heap before
                    # declining — live traffic outranks cached prefixes
                    if (self.prefix_cache is None
                            or not self.prefix_cache.reclaim()):
                        return False
            if self.tenancy is not None:
                t = self.tenancy.get(r.tenant_id)
                if (t is not None and t.max_slots is not None
                        and used.get(r.tenant_id, 0) >= t.max_slots):
                    return False
            return True

        try:
            hint = None
            while len(self.scheduler):
                req = self.scheduler.pop(
                    affinity_hint=hint, admissible=admissible
                )
                if req is None:
                    break
                if req.cancelled:
                    self._retire_unadmitted(req, RequestStatus.CANCELLED)
                    continue
                if req.expired(now):
                    self._retire_unadmitted(req, RequestStatus.EXPIRED)
                    continue
                if req.kind == "embedding":
                    self._serve_embedding(req, now)
                    continue
                if req.kind == "kv_ingest":
                    self._serve_kv_ingest(req, now)  # lint: sync-ok wire seat must land before decode admits
                    continue
                if req.kind == "kv_export":
                    self._serve_kv_export(req, now)  # lint: sync-ok export materializes the wire frame bytes
                    continue
                if req.kind == "kv_session":
                    # seats synchronously (pool/block state updates
                    # before the next admissible() check); count the
                    # held slot against its tenant's cap like a plan
                    self._serve_kv_session(req, now)  # lint: sync-ok migrated session must seat before decode admits
                    if req.status is RequestStatus.RUNNING:
                        used[req.tenant_id] = used.get(req.tenant_id, 0) + 1
                    continue
                plans.append(_AdmitPlan(req, self.pool.acquire(), now))
                used[req.tenant_id] = used.get(req.tenant_id, 0) + 1
                if self._paged:
                    reserved[0] += self.pool.blocks_needed(
                        len(req.prompt) + req.max_new
                    )
                # prefix affinity only helps adapter-0 traffic (nonzero
                # adapters never reuse cached segments)
                hint = req.prompt if req.adapter == 0 else None
            if not plans:
                return
            for pl in plans:
                self._classify_plan(pl)
            self._execute_plans(plans, now)
        except BaseException:
            # EngineCrash (or anything unexpected) mid-batch: no popped
            # request may be dropped — requeue every unseated plan at
            # the front of its class (reversed, so original order is
            # restored) before the supervisor rebuilds state.
            for pl in reversed(plans):
                if not pl.admitted:
                    if pl.seg is not None:
                        self.prefix_cache.unpin(pl.seg)
                    self.pool.release(pl.slot)
                    self.scheduler.requeue(pl.req)
            raise
        finally:
            self._admitting -= 1

    # lint: hot-path
    def _execute_plans(self, plans: list[_AdmitPlan],
                       now: float) -> None:
        # fault boundary first, in admission order, so scripted chaos
        # fires at the same per-request check counts as serial
        # admission did
        live: list[_AdmitPlan] = []
        for pl in plans:
            if self._check_prefill_faults(pl.req):
                live.append(pl)
            else:
                if pl.seg is not None:
                    self.prefix_cache.unpin(pl.seg)
                    pl.seg = None
                self.pool.release(pl.slot)
                pl.admitted = True  # handled: excluded from requeue
                self._retire_unadmitted(
                    pl.req, RequestStatus.FAILED, pl.req.error
                )
        deferred: set[int] = set()
        if self._piggyback:
            # pre-split sampling keys for EVERY surviving plan now, in
            # admission order — the exact split sequence non-piggyback
            # seating produces — so deferring a prefill across
            # horizons cannot reorder the master key chain (sampled
            # byte parity). A crash before seating keeps the stash;
            # re-admission reuses it without advancing the chain,
            # matching the blocking path (which never split either).
            for pl in live:
                if pl.req.id not in self._presplit_keys:
                    self._presplit_keys[pl.req.id] = (
                        self._split_slot_key(pl.req.id)
                    )
            # defer only prompts whose uncached suffix exceeds one
            # bucket — everything the blocking path serves in a single
            # prefill dispatch stays on the blocking path, bitwise
            for pl in live:
                n = len(pl.req.prompt)
                cached = pl.matched if pl.kind == "partial" else 0
                if pl.kind != "full" and n - cached > self._max_bucket:
                    self._enqueue_piggyback(pl, now)
                    deferred.add(id(pl))
        occupied = any(st is not None for st in self._slots)
        t_exec = time.perf_counter()
        # group what can share a dispatch
        # the batched admission programs are slab-landing (whole
        # groups dynamic-update into pool slabs); paged admissions go
        # serial through the paged prefill/insert programs
        batch_ok = (len(live) > 1 and self.batch_admission
                    and not self._paged)
        miss_groups: dict[int, list[_AdmitPlan]] = {}
        hit_groups: dict[tuple[int, int], list[_AdmitPlan]] = {}
        if batch_ok:
            for pl in live:
                n = len(pl.req.prompt)
                if pl.kind == "miss" and 0 < n <= self._max_bucket:
                    miss_groups.setdefault(
                        self._bucket_for(n), []
                    ).append(pl)
                elif pl.kind == "partial":
                    sfx = n - pl.matched
                    if sfx <= self._max_bucket:
                        b = self._bucket_for(sfx)
                        if pl.matched + b <= self.pool.tpad:
                            hit_groups.setdefault(
                                (b, pl.matched), []
                            ).append(pl)
        batched: set[int] = set()
        region = self._regions
        for bucket, group in sorted(miss_groups.items()):
            if len(group) >= 2:
                t0 = time.perf_counter()
                with region("prefill", bucket=bucket, rows=len(group)):
                    self._batch_prefill_group(bucket, group)
                dt = (time.perf_counter() - t0) / len(group)
                for pl in group:
                    pl.t_pf, pl.prefill_s = t0, dt
                    batched.add(id(pl))
        for (bucket, length), group in sorted(hit_groups.items()):
            if len(group) >= 2:
                t0 = time.perf_counter()
                with region("prefill", bucket=bucket, rows=len(group),
                            cached=length):
                    self._batch_hit_group(bucket, length, group)
                dt = (time.perf_counter() - t0) / len(group)
                for pl in group:
                    pl.t_pf, pl.prefill_s = t0, dt
                    batched.add(id(pl))
        # serial remainder, in admission order
        for pl in live:
            if id(pl) in batched or id(pl) in deferred:
                continue
            t0 = time.perf_counter()
            n = len(pl.req.prompt)
            with region("prefill", req_id=pl.req.id, prompt_len=n,
                        bucket=self._bucket_for(min(n, self._max_bucket)),
                        prefix=pl.kind):
                if pl.kind == "full":
                    self._admit_full_hit(pl)
                elif pl.kind == "partial":
                    self._admit_partial_hit(pl)
                else:
                    eos_tok = (_NO_EOS if pl.req.eos_token is None
                               else int(pl.req.eos_token))
                    self._prefill_seq_into_slot(
                        pl.req.prompt, pl.slot, pl.req.max_new, eos_tok,
                        adapter=pl.req.adapter,
                    )
            pl.t_pf, pl.prefill_s = t0, time.perf_counter() - t0
        # decode-stall accounting: admission prefill executed while
        # decode slots sat occupied is exactly the stall piggyback
        # exists to bound — measured identically on and off so the
        # bench comparison is honest
        if occupied:
            self.metrics.record_decode_stall(
                time.perf_counter() - t_exec
            )
        # seat states in admission order (sampling-key split order is
        # part of the determinism contract), then cache new prefixes
        for pl in live:
            if id(pl) not in deferred:
                self._seat_plan(pl, now)
        for pl in live:
            if id(pl) not in deferred:
                self._maybe_insert_prefix(pl)

    # -- chunked-prefill piggyback -----------------------------------------
    #
    # A deferred admission keeps its acquired slot and pinned prefix
    # segment but is NOT seated: its uncached suffix sits as a pow2
    # chunk schedule in a _PendingPrefill record, and every dispatch
    # horizon spends up to `prefill_budget` chunk tokens advancing the
    # FIFO — middles standalone, the last budgeted chunk FUSED into
    # the decode dispatch itself (the piggyback_step program). The
    # final chunk always runs standalone so the completion insert
    # consumes a well-defined logits row, then the record completes —
    # insert + seat — in the same horizon a blocking admission would
    # have joined. Byte parity with the blocking path holds because
    # the chunk programs, schedule, and scratch slab are IDENTICAL;
    # only the horizon at which each dispatch happens moves.

    def _enqueue_piggyback(self, pl: _AdmitPlan, now: float) -> None:
        """Turn an executed-plan candidate into a pending record: set
        up its scratch slab (segment fetch for partial hits — only the
        uncached suffix is piggybacked), its chunk schedule, and, in
        paged mode, its private block coverage."""
        req = pl.req
        L = pl.matched if pl.kind == "partial" else 0
        if self._paged:
            # private blocks for every row the slot will write; rows
            # [0, L) stay sentinel-mapped until completion (decode
            # steps run while this record is pending, and an inactive
            # slot's frozen-position garbage write must never land in
            # a SHARED prefix block — aliasing is deferred to
            # _complete_pending, a refcount bump that cannot fail)
            full = L // self.pool.block_size
            self.pool.alloc_slot_blocks(
                pl.slot, min(len(req.prompt) + req.max_new,
                             self.pool.tpad),
                start=full,
            )
            tmp = (self._paged_seg_tmp(pl.seg) if pl.kind == "partial"
                   else self._init_caches(1, self.max_total))
        elif pl.kind == "partial":
            tmp = self._seg_fetch()(
                self.prefix_cache.region, jnp.int32(pl.seg.slot)
            )
        else:
            tmp = self._init_caches(1, self.max_total)
        rec = _PendingPrefill(
            pl, deque(self._chunk_schedule(len(req.prompt), start=L)),
            tmp, now,
        )
        self._pending_prefills.append(rec)
        # the scheduler pop charged the whole prompt to the tenant's
        # DRR deficit up front; credit the deferred suffix back here
        # and re-charge it chunk by chunk as the work executes, so
        # fairness meters the device time when it is actually spent
        self.scheduler.adjust_deficit(req, float(len(req.prompt) - L))
        self.flight.record(
            "piggyback", phase="defer", req_id=req.id, slot=pl.slot,
            suffix_tokens=len(req.prompt) - L,
            n_chunks=len(rec.chunks),
        )
        self.tracer.instant(
            slot_track(pl.slot), "piggyback_defer", req_id=req.id,
            suffix_tokens=len(req.prompt) - L,
        )
        log_event(_log, "piggyback_defer", req_id=req.id, slot=pl.slot,
                  prompt_len=len(req.prompt), cached_tokens=L,
                  n_chunks=len(rec.chunks),
                  tenant=req.tenant_id or None)

    def _account_chunk(self, rec: _PendingPrefill, ln: int,
                       fused: bool) -> None:
        """Bookkeeping for one executed piggyback chunk (standalone or
        fused): pop it from the schedule, charge the tenant, count."""
        rec.chunks.popleft()
        pl = rec.plan
        self.prefill_dispatches += 1
        self.metrics.record_prefill_chunk(ln)
        self.scheduler.adjust_deficit(pl.req, -float(ln))
        self.flight.record(
            "piggyback", phase="chunk", req_id=pl.req.id, slot=pl.slot,
            chunk_tokens=ln, fused=fused, remaining=len(rec.chunks),
        )

    def _run_pending_chunk(self, rec: _PendingPrefill) -> int:
        """Run the head chunk of ``rec`` standalone — the non-fused
        path: budget middles, final chunks, and horizons with no
        active decode slot to piggyback on. Returns real tokens."""
        pl = rec.plan
        t0, ln, b = rec.chunks[0]
        pad = np.zeros((1, b), np.int32)
        pad[0, :ln] = pl.req.prompt[t0:t0 + ln]
        self._count_program("chunk")
        with self._regions("prefill", req_id=pl.req.id, chunk=ln,
                           bucket=b):
            rec.tmp, rec.lg = self._chunk_fn(b)(
                self.params, rec.tmp, jnp.asarray(pad), jnp.int32(t0),
                jnp.int32(ln - 1),
                jnp.asarray([pl.req.adapter], jnp.int32),
            )
        self._account_chunk(rec, ln, fused=False)
        return ln

    def _complete_pending(self, rec: _PendingPrefill) -> None:
        """All chunks executed: land the scratch slab with the SAME
        insert program the blocking path uses, seat the slot, and
        cache the new prefix — the deferred admission is now
        indistinguishable from a blocking one."""
        pl = rec.plan
        req = pl.req
        now = time.perf_counter()
        n = len(req.prompt)
        eos_tok = _NO_EOS if req.eos_token is None else int(req.eos_token)
        if self._paged and pl.kind == "partial":
            # alias the cached prefix blocks in now (refcount bump,
            # no allocation — the private coverage was reserved at
            # defer time); the insert scatter then rewrites the
            # aliased rows with the identical bytes the segment holds,
            # exactly like the blocking partial-hit path
            full = pl.matched // self.pool.block_size
            if full:
                self.pool.alias_into_slot(
                    pl.slot, pl.seg.block_ids[:full]
                )
        insert = self._paged_insert() if self._paged else self._insert()
        self._set_state(insert(
            *self._state(), rec.tmp, rec.lg, jnp.int32(pl.slot),
            jnp.int32(n), jnp.int32(req.max_new), jnp.int32(eos_tok),
        ))
        pl.t_pf = rec.t_start
        pl.prefill_s = now - rec.t_start
        self._seat_plan(pl, now)
        self._maybe_insert_prefix(pl)
        self.flight.record(
            "piggyback", phase="seated", req_id=req.id, slot=pl.slot,
            prefill_s=round(pl.prefill_s, 6),
        )

    def _advance_piggyback(self, can_fuse: bool
                           ) -> _PendingPrefill | None:
        """Spend up to ``prefill_budget`` chunk tokens advancing the
        pending FIFO (oldest first — Sarathi-style per-iteration token
        budget). Returns the record whose head chunk should be FUSED
        into this horizon's decode dispatch (never a record's final
        chunk), or None. The first chunk always runs even over budget,
        so every pending admission makes progress each horizon."""
        budget = self.prefill_budget
        spent = 0
        fused = None
        t_wall = time.perf_counter()
        while self._pending_prefills and spent < budget:
            rec = self._pending_prefills[0]
            ln = rec.chunks[0][1]
            final = len(rec.chunks) == 1
            if not final and can_fuse and spent + ln >= budget:
                fused = rec
                self._pb_did_work = True
                break
            spent += self._run_pending_chunk(rec)
            self._pb_did_work = True
            if final:
                self._complete_pending(rec)
                self._pending_prefills.popleft()
        if can_fuse and spent:
            # standalone chunks executed ahead of an occupied-slot
            # dispatch are residual decode stall (the fused chunk is
            # the part that isn't)
            self.metrics.record_decode_stall(
                time.perf_counter() - t_wall
            )
        return fused

    # -- supervised dispatch + pipelined readback --------------------------

    # lint: hot-path
    def _dispatch(self) -> _Inflight | None:
        """Dispatch one fused K-substep horizon for every occupied slot
        under transient-retry supervision; returns the in-flight record
        WITHOUT syncing its tokens. Persistent faults quarantine the
        implicated request when one is named, otherwise escalate to
        ``EngineCrash`` (replay recovery). Returns None when there is
        nothing to dispatch (or quarantining emptied the batch)."""
        self._pb_did_work = False
        fused = None
        if self._pending_prefills:
            # advance deferred prefills under the token budget FIRST:
            # completions seat their slot pre-dispatch (joining this
            # horizon exactly as a blocking admission would), and the
            # returned record's head chunk rides the decode dispatch
            # below. With no occupied slot there is nothing to fuse
            # with — chunks run standalone and this horizon may
            # dispatch no step at all.
            fused = self._advance_piggyback(  # lint: sync-ok host-int chunk accounting, no device readback
                can_fuse=any(st is not None for st in self._slots)
            )
        if not any(st is not None for st in self._slots):
            return None
        # adaptive horizon: when requests are waiting for a slot, drop
        # to K=1 so the next admission boundary arrives one substep
        # away; restore the configured K once the queue drains. Byte-
        # safe — the device stopping rule is applied per-substep, so
        # the emitted stream is invariant to K. Piggyback pendings are
        # NOT queue pressure (their slot is already taken): K stays
        # configured, the budget bounds their prefill instead.
        k = (1 if (self.adaptive_horizon and len(self.scheduler) > 0)
             else self.decode_horizon)
        self.decode_horizon_current = k
        surface = self._surface
        step_fn = (self._masked_step_fn_for(k) if surface
                   else self._step_fn_for(k))
        if fused is not None:
            fp = fused.plan
            ct0, cln, cb = fused.chunks[0]
            cpad = np.zeros((1, cb), np.int32)
            cpad[0, :cln] = fp.req.prompt[ct0:ct0 + cln]
            pb_fn = (self._masked_piggyback_fn(cb, k) if surface
                     else self._piggyback_fn(cb, k))
        attempt, backoff = 0, self.retry_backoff_s
        # .copy(): jnp.asarray can zero-copy alias the mutable host key
        # buffer on CPU, and dispatch is async — a later admission
        # writing a slot key must not race the in-flight step. The
        # snapshot is what gets dispatched, and (under the sanitizer)
        # what gets integrity-tracked until the readback.
        keys_host = self._slot_keys.copy()
        ad_host = self._slot_adapters.copy()
        if surface:
            # per-slot sampling-surface vectors, snapshotted for the
            # same async-alias reason as the keys above
            temps_host = self._slot_temps.copy()
            topks_host = self._slot_topks.copy()
            topps_host = self._slot_topps.copy()
            bidx_host = self._slot_bias_idx.copy()
            bval_host = self._slot_bias_val.copy()
            mask_tab, trans_tab = self._grammar_device_tables()
        while True:
            try:
                if self.faults is not None:
                    self.faults.check("step")
                # _caches_in INSIDE the retry loop: a quarantining
                # retire below releases the slot and rewrites its table
                # row, so the paged table mirror must be rebuilt before
                # every (re)dispatch
                if fused is None and not surface:
                    (caches, self._logits, self._dpos,
                     self._dactive, self._dbudget, toks) = step_fn(
                        self.params, self._caches_in(), self._logits,
                        self._dpos, self._dactive, self._dbudget,
                        self._deos, jnp.asarray(keys_host),
                        jnp.asarray(ad_host),
                    )
                elif fused is None:
                    # masked step: grammar FSM state threaded through
                    # the substeps; ``toks`` is the packed aux block
                    # (slots, K, 2+2*n_logprobs), token ids in [:,:,0]
                    (caches, self._logits, self._dpos,
                     self._dactive, self._dbudget, self._dgstate,
                     toks) = step_fn(
                        self.params, self._caches_in(), self._logits,
                        self._dpos, self._dactive, self._dbudget,
                        self._deos, self._dgstate,
                        jnp.asarray(keys_host), jnp.asarray(ad_host),
                        jnp.asarray(temps_host),
                        jnp.asarray(topks_host),
                        jnp.asarray(topps_host),
                        jnp.asarray(bidx_host),
                        jnp.asarray(bval_host),
                        mask_tab, trans_tab,
                    )
                elif not surface:
                    # piggyback: K decode substeps + one bounded
                    # prefill chunk for the admitting slot, fused
                    (caches, self._logits, self._dpos,
                     self._dactive, self._dbudget, toks,
                     fused.tmp, fused.lg) = pb_fn(
                        self.params, self._caches_in(), self._logits,
                        self._dpos, self._dactive, self._dbudget,
                        self._deos, jnp.asarray(keys_host),
                        jnp.asarray(ad_host), fused.tmp,
                        jnp.asarray(cpad), jnp.int32(ct0),
                        jnp.int32(cln - 1),
                        jnp.asarray([fp.req.adapter], jnp.int32),
                    )
                else:
                    (caches, self._logits, self._dpos,
                     self._dactive, self._dbudget, self._dgstate,
                     toks, fused.tmp, fused.lg) = pb_fn(
                        self.params, self._caches_in(), self._logits,
                        self._dpos, self._dactive, self._dbudget,
                        self._deos, self._dgstate,
                        jnp.asarray(keys_host), jnp.asarray(ad_host),
                        jnp.asarray(temps_host),
                        jnp.asarray(topks_host),
                        jnp.asarray(topps_host),
                        jnp.asarray(bidx_host),
                        jnp.asarray(bval_host),
                        mask_tab, trans_tab, fused.tmp,
                        jnp.asarray(cpad), jnp.int32(ct0),
                        jnp.int32(cln - 1),
                        jnp.asarray([fp.req.adapter], jnp.int32),
                    )
                self._caches_out(caches)
                if fused is not None:
                    self._account_chunk(fused, cln, fused=True)  # lint: sync-ok host-int chunk accounting
                break
            except TransientFault as e:
                self.metrics.record_retry()
                self.tracer.instant(
                    ENGINE_TRACK, "retry", site="step", error=str(e)
                )
                self.flight.record("fault", fault="transient",
                                   site="step", error=str(e),
                                   attempt=attempt + 1)
                attempt += 1
                if attempt <= self.max_retries:
                    time.sleep(backoff)
                    backoff = min(backoff * 2, self.max_backoff_s)
                    continue
                slot = self._slot_of(e.req_id)
                if slot is None:
                    raise EngineCrash(
                        f"transient step fault persisted past "
                        f"{self.max_retries} retries: {e}"
                    ) from e
                self._retire(slot, RequestStatus.FAILED,
                             time.perf_counter(), error=str(e),
                             deactivate=True)
                if not any(st is not None for st in self._slots):
                    return None
                attempt, backoff = 0, self.retry_backoff_s
            except PermanentFault as e:
                self.flight.record("fault", fault="permanent",
                                   site="step", error=str(e))
                slot = self._slot_of(e.req_id)
                if slot is None:
                    raise EngineCrash(
                        f"permanent step fault names no live request: {e}"
                    ) from e
                self._retire(slot, RequestStatus.FAILED,
                             time.perf_counter(), error=str(e),
                             deactivate=True)
                if not any(st is not None for st in self._slots):
                    return None
            except EngineCrash as e:
                # injected whole-engine crash: the last flight event
                # before the supervisor's postmortem dump names it
                self.flight.record("fault", fault="crash", site="step",
                                   error=str(e))
                raise
        now = time.perf_counter()
        self.last_dispatch_t = now
        if self._san is not None:
            self._san.track("dispatch.slot_keys", keys_host)
        snaps = [(s, st) for s, st in enumerate(self._slots)
                 if st is not None]
        self.metrics.record_step(
            len(snaps), self.n_slots, len(self.scheduler)
        )
        # the cache rows each occupied slot holds when substep j reads
        # them: its prompt, the substeps dispatched before, and the row
        # substep j writes. A slot past its budget is frozen on the
        # device and holds nothing the kernel needs (an EOS the host
        # has not read back yet is counted until it has). What the step
        # program reads for them is decode_rows_streamed's to say.
        held = [[] for _ in range(k)]
        for _, st in snaps:
            rows = len(st.req.prompt) + st.n_substeps
            for j in range(min(k, st.req.max_new - st.n_substeps)):
                held[j].append(rows + j + 1)
            st.n_substeps += k
        self.metrics.record_kv_rows(
            sum(decode_rows_live(self.cfg, h) for h in held),
            sum(
                decode_rows_streamed(
                    self.cfg, self.n_slots, self.pool.tpad, h,
                    paged=self._paged,
                )
                for h in held
            ),
        )
        fam = "step" if fused is None else "piggyback_step"
        if surface:
            fam = "masked_" + fam
        self._count_program(("paged_" + fam) if self._paged else fam)
        if self.flight.enabled:
            self.flight.record(
                "dispatch", k=k, n_active=len(snaps),
                queue_depth=len(self.scheduler),
                **({"piggyback_chunk": cln} if fused is not None
                   else {}),
                **({"blocks_in_use": self.pool.n_blocks_in_use,
                    "blocks_free": self.pool.n_free_blocks}
                   if self._paged else {}),
            )
        return _Inflight(toks, snaps, now, self._steps + 1)

    # lint: hot-path
    def _process(self, horizon: _Inflight) -> None:
        """Sync a horizon's (slots, K) token block and do the host-side
        bookkeeping: append tokens (replaying the same EOS/budget
        stopping rule the device mask applied in-program), stamp first
        tokens, retire finished slots. Blocks whose slot was retired or
        re-acquired since dispatch are discarded."""
        t_sync = time.perf_counter()
        with self._regions("sync", n=horizon.n):
            toks_host = np.asarray(horizon.toks)  # lint: sync-ok THE designated readback, 1/horizon
        aux_host = None
        if toks_host.ndim == 3:
            # masked-step horizons read back the packed aux block:
            # [:, :, 0] is the token stream, the rest carries bitcast
            # logprob rows — still ONE readback per horizon
            aux_host = toks_host
            toks_host = aux_host[:, :, 0]
        if toks_host.shape[0] > self.n_slots:
            # a counting fwd1's rows under the block (``tallied``)
            self.metrics.record_moe(
                *toks_host[self.n_slots:].sum(axis=1).tolist()
            )
        if self._san is not None:
            # the program that read the dispatch-tracked buffers has
            # completed: verify nothing mutated them while in flight
            self._san.check("dispatch.slot_keys")
        now = time.perf_counter()
        self.metrics.record_readback(
            sync_wait_s=now - t_sync,
            overlap_s=max(0.0, t_sync - horizon.t_dispatch),
        )
        # per-slot decode span for this horizon: dispatch → block
        # arrival, clipped at the NEXT horizon's dispatch (which already
        # happened — pipelining) so consecutive decode spans on one slot
        # track stay disjoint in the trace viewer
        t_span_end = now
        if (self._inflight is not None
                and self._inflight.t_dispatch > horizon.t_dispatch):
            t_span_end = min(now, self._inflight.t_dispatch)
        for slot, st in horizon.snaps:
            if (self._slots[slot] is not st
                    or st.gen != self.pool.generation(slot)):
                continue  # retired/reused since dispatch: tokens dead
            req = st.req
            self.tracer.span(
                slot_track(slot), "decode", horizon.t_dispatch,
                t_span_end - horizon.t_dispatch, req_id=req.id,
                k=int(toks_host.shape[1]), n=horizon.n,
            )
            finished = False
            first = st.t_first_token is None
            for k in range(toks_host.shape[1]):
                tok = int(toks_host[slot, k])
                if st.t_first_token is None:
                    st.t_first_token = now
                    self.tracer.instant(
                        slot_track(slot), "first_token", ts=now,
                        req_id=req.id, n=horizon.n,
                    )
                    if req.arrival_time is not None:
                        self.metrics.record_first_token(
                            req.id, now - req.arrival_time
                        )
                st.tokens.append(tok)
                if st.lp_out is not None and aux_host is not None:
                    row = aux_host[slot, k]
                    nl = self._n_logprobs
                    rec = {
                        "token": tok,
                        # contiguous row slice: bitcast back to f32
                        "logprob": float(row[1:2].view(np.float32)[0]),  # lint: sync-ok row is a host numpy slice of aux_host, no device buffer
                    }
                    if req.top_logprobs:
                        ids = row[2:2 + nl][:req.top_logprobs]
                        vals = row[2 + nl:2 + 2 * nl].view(
                            np.float32
                        )[:req.top_logprobs]
                        rec["top_logprobs"] = [
                            {"token": int(i), "logprob": float(v)}  # lint: sync-ok host numpy scalars from aux_host
                            for i, v in zip(ids, vals)
                        ]
                    st.lp_out.append(rec)
                stopped = False
                if st.stop_matcher is not None:
                    emitted, stripped = st.stop_matcher.push(tok)
                    if req.stream is not None:
                        for et in emitted:
                            req.stream.put(et)
                    if stripped:
                        # the matched stop sequence is NOT part of the
                        # output: truncate the record (the held tokens
                        # were never streamed)
                        del st.tokens[-stripped:]
                        if st.lp_out is not None:
                            del st.lp_out[-stripped:]
                        self.metrics.record_stop_hit()
                        stopped = True
                elif req.stream is not None:
                    # host-side fan-out for SSE: tokens already arrived
                    # with this horizon's one readback, so streaming
                    # costs zero extra device syncs
                    req.stream.put(tok)
                if stopped:
                    finished = True
                    # the device mask did NOT freeze this slot (stops
                    # are host-side): retire with deactivate below
                    break
                if (tok == req.eos_token
                        or len(st.tokens) >= req.max_new):
                    finished = True
                    break  # device mask froze this slot here too
            if (first and st.t_seated is not None
                    and req.arrival_time is not None):
                # this horizon's tokens are on the request's stream:
                # its time to first token, cut where the engine sees it
                self.metrics.record_ttft_segments(
                    st.t_boundary - req.arrival_time,
                    st.t_seated - st.t_boundary,
                    now - st.t_seated,
                    time.perf_counter() - now,
                )
            if finished:
                if stopped:
                    self._retire(slot, RequestStatus.FINISHED, now,
                                 deactivate=True)
                else:
                    self._finish(slot, now)

    def attach_sanitizer(self, san) -> None:
        """Attach an opt-in :class:`SyncSanitizer`: the engine stamps
        its phase (sweep/admit/dispatch/process) onto the sanitizer's
        thread-local so blocking syncs are attributed and budgeted, and
        registers each dispatch's host key snapshot for in-flight
        mutation checks. Detach with ``attach_sanitizer(None)``."""
        self._san = san

    def _set_phase(self, phase: str | None) -> None:
        """The outermost open region of ``_regions``, for the
        sanitizer's per-phase sync budgets."""
        san = self._san
        if san is not None:
            san.set_phase(phase)

    # lint: hot-path
    def step(self) -> bool:
        """One horizon boundary: sweep lifecycle, admit waiting
        requests, dispatch the next K-substep horizon, then sync and
        process the PREVIOUS horizon's tokens (so host bookkeeping
        overlaps device compute). Returns False when there was nothing
        to do. Raises ``EngineCrash`` when the dispatch loop cannot
        make progress (callers recover via :meth:`recover`)."""
        prof = self.profile
        if prof is not None:
            prof.step_start()
        now = time.perf_counter()
        region = self._regions
        progressed = True  # a step that raises is worth its spans
        try:
            with region("sweep"):
                self._sweep_lifecycle(now)
            with region("admit"):
                self._admit(now)
            with region("dispatch", n=self._steps + 1):
                prev, self._inflight = self._inflight, self._dispatch()
            if self._inflight is not None:
                self._steps += 1
            if prev is not None:
                with region("process", n=prev.n):
                    self._process(prev)
            progressed = (prev is not None or self._inflight is not None
                          or self._pb_did_work)
        finally:
            region.flush(progressed)
            if prof is not None:
                prof.step_end()
        if (self._compiles_seen is not None
                and self._compile_log.requests != self._compiles_seen):
            self._note_recompiles()
        if self.tracer.enabled and progressed:
            t_end = time.perf_counter()
            self.tracer.span(
                ENGINE_TRACK, "step", now, t_end - now, n=self._steps
            )
            self.tracer.counter(
                SCHEDULER_TRACK, "queue_depth", len(self.scheduler),
                ts=t_end,
            )
            self.tracer.counter(
                ENGINE_TRACK, "kv_slots_active", self.pool.n_active,
                ts=t_end,
            )
        return progressed

    # -- crash recovery ----------------------------------------------------

    def _reset_device_state(self) -> None:
        self._logits = jnp.zeros(
            (self.n_slots, self.cfg.vocab_size), jnp.float32
        )
        self._dpos = jnp.zeros((self.n_slots,), jnp.int32)
        self._dactive = jnp.zeros((self.n_slots,), bool)
        self._dbudget = jnp.zeros((self.n_slots,), jnp.int32)
        self._deos = jnp.full((self.n_slots,), _NO_EOS, jnp.int32)
        self._dgstate = jnp.zeros((self.n_slots,), jnp.int32)

    def recover(self) -> int:
        """Rebuild engine/device state by deterministic replay after an
        engine-loop crash. The device buffers are abandoned (assumed
        corrupt — with donation they may already be invalidated
        mid-dispatch) and re-created zeroed; any un-synced horizon is
        dropped (its tokens were never recorded, so the replayed run
        regenerates them). Each live slot is then rebuilt either by
        CHUNKED replay — one bucketed prefill pass over
        ``prompt + tokens_so_far``, O(len/bucket) device calls — or by
        STEPWISE replay — re-prefill the original prompt, then
        teacher-force the recorded tokens one fused step at a time —
        per ``chunked_replay`` (see module docstring). Queued requests
        are untouched. Returns the number of live requests replayed."""
        t_rec = time.perf_counter()
        self.metrics.record_restart()
        self.tracer.instant(ENGINE_TRACK, "crash", ts=t_rec)
        self.flight.record(
            "restart", n_live=sum(
                1 for st in self._slots if st is not None
            ), queue_depth=len(self.scheduler),
            restarts=self.metrics.n_restarts,
        )
        # replay dispatches are not traffic
        self._uncounted += 1
        try:
            return self._recover_inner(t_rec)
        finally:
            self._uncounted -= 1

    def _recover_inner(self, t_rec: float) -> int:
        self._inflight = None
        # deferred admissions lose their device-side chunk progress
        # with the abandoned buffers: hand them back to the scheduler
        # (reversed, so front-requeue restores admission order) before
        # the pool reinit and replay only seated slots. Their
        # pre-split sampling keys stay stashed — re-admission reuses
        # them without advancing the master chain, exactly the key an
        # uninterrupted blocking run would have assigned.
        if self._pending_prefills:
            for rec in reversed(self._pending_prefills):
                pl = rec.plan
                if pl.seg is not None and self.prefix_cache is not None:
                    self.prefix_cache.unpin(pl.seg)
                    pl.seg = None
                self.pool.release(pl.slot)
                self.scheduler.requeue(pl.req)
            self._pending_prefills.clear()
        live = [(s, st) for s, st in enumerate(self._slots)
                if st is not None]
        chunked = bool(live) and self.chunked_replay
        self.pool.reinit()
        self._reset_device_state()
        if self.prefix_cache is not None:
            # the region shares the crash's blast radius (donated
            # programs may have invalidated it mid-flight): drop every
            # segment and re-create it zeroed. Replay then misses on
            # every lookup — i.e. it replays through the same lookup
            # path and takes the cold branch, byte-identical to a
            # cold-start replay.
            self.prefix_cache.reinit()
            for st in self._slots:
                if st is not None:
                    st.segs = []
        # re-seat each live slot's sampling key from its host record —
        # with position-indexed fold_in sampling this is all it takes
        # for a temperature>0 stream to resume exactly where it left off
        self._slot_keys[:] = 0
        self._slot_adapters[:] = 0
        if self._surface:
            # sampling-surface mirrors share the keys' re-seat
            # contract; the device grammar-table copies share the
            # crash's blast radius, so force a refresh from the host
            # table (which survived — it is plain numpy)
            self._slot_gstate[:] = 0
            self._slot_temps[:] = self.temperature
            self._slot_topks[:] = int(self.top_k or 0)
            self._slot_topps[:] = 1.0
            self._slot_bias_idx[:] = -1
            self._slot_bias_val[:] = 0.0
            self._gtab_version = -1
        for slot, st in live:
            self._slot_keys[slot] = st.key_data
            self._slot_adapters[slot] = st.adapter
            # the rebuilt device state holds what the host recorded
            st.n_substeps = len(st.tokens)
            if self._surface:
                self._reseat_surface(slot, st)
        if self._surface and live:
            self._dgstate = jnp.asarray(self._slot_gstate.copy())
        self.last_recover_mode = (
            None if not live else ("chunked" if chunked else "stepwise")
        )
        if not live:
            log_event(_log, "engine_recovered", mode=None, n_replayed=0,
                      restarts=self.metrics.n_restarts)
            return 0
        if chunked:
            for slot, st in live:
                req = st.req
                seq = np.concatenate(
                    [req.prompt, np.asarray(st.tokens, np.int32)]
                )
                eos_tok = (_NO_EOS if req.eos_token is None
                           else int(req.eos_token))
                self._prefill_seq_into_slot(
                    seq, slot, req.max_new - len(st.tokens), eos_tok,
                    adapter=st.adapter,
                )
            self._log_recovered(t_rec, len(live))
            return len(live)
        pos = np.zeros((self.n_slots,), np.int32)
        for slot, st in live:
            req = st.req
            eos_tok = (_NO_EOS if req.eos_token is None
                       else int(req.eos_token))
            self._prefill_seq_into_slot(
                req.prompt, slot, req.max_new, eos_tok,
                adapter=st.adapter,
            )
            pos[slot] = len(req.prompt)
        for j in range(max((len(st.tokens) for _, st in live), default=0)):
            toks = np.zeros((self.n_slots,), np.int32)
            replaying = np.zeros((self.n_slots,), bool)
            for slot, st in live:
                if j < len(st.tokens):
                    toks[slot] = st.tokens[j]
                    replaying[slot] = True
            # pos must be snapshotted: jnp.asarray can zero-copy alias
            # a numpy buffer on CPU and dispatch is async, so mutating
            # pos below would race the in-flight replay step
            caches, self._logits = self._replay_fn(
                self.params, self._caches_in(), self._logits,
                jnp.asarray(toks), jnp.asarray(pos.copy()),
                jnp.asarray(replaying),
                jnp.asarray(self._slot_adapters.copy()),
            )
            self._caches_out(caches)
            for slot, st in live:
                if j < len(st.tokens):
                    pos[slot] += 1
        # stepwise replay drove positions through host arrays; re-seat
        # the device state to match the rebuilt trajectory
        active = np.zeros((self.n_slots,), bool)
        budget = np.zeros((self.n_slots,), np.int32)
        eos = np.full((self.n_slots,), _NO_EOS, np.int32)
        for slot, st in live:
            active[slot] = True
            budget[slot] = st.req.max_new - len(st.tokens)
            if st.req.eos_token is not None:
                eos[slot] = int(st.req.eos_token)
        self._dpos = jnp.asarray(pos)
        self._dactive = jnp.asarray(active)
        self._dbudget = jnp.asarray(budget)
        self._deos = jnp.asarray(eos)
        self._log_recovered(t_rec, len(live))
        return len(live)

    def _reseat_surface(self, slot: int, st: _SlotState) -> None:
        """Crash-recovery re-seat of one live slot's sampling-surface
        state (mirrors the adapter/key re-seat): per-slot sampler
        vectors from the request, the grammar FSM state re-walked over
        the recorded tokens from the seat state, and the stop-sequence
        hold-back rebuilt by re-pushing the stream (a live slot's
        record cannot contain a completed stop match, so the rebuild
        emits nothing we'd have to suppress — emissions are simply
        discarded, they already streamed before the crash)."""
        req = st.req
        self._slot_temps[slot] = np.float32(
            req.temperature if req.temperature is not None
            else self.temperature
        )
        self._slot_topks[slot] = np.int32(
            req.top_k if req.top_k is not None else (self.top_k or 0)
        )
        self._slot_topps[slot] = np.float32(
            req.top_p if req.top_p is not None else 1.0
        )
        self._slot_bias_idx[slot] = -1
        self._slot_bias_val[slot] = 0.0
        if req.logit_bias:
            for j, (ti, tv) in enumerate(sorted(req.logit_bias.items())):
                self._slot_bias_idx[slot, j] = ti
                self._slot_bias_val[slot, j] = tv
        g = int(st.gstate0)
        for t in st.tokens:
            g = self._gtable.advance(g, int(t))
        self._slot_gstate[slot] = g
        if st.stop_matcher is not None:
            st.stop_matcher = StopMatcher(req.stop)
            for t in st.tokens:
                st.stop_matcher.push(int(t))

    def _log_recovered(self, t_rec: float, n_replayed: int) -> None:
        now = time.perf_counter()
        self.tracer.span(
            ENGINE_TRACK, "recover", t_rec, now - t_rec,
            mode=self.last_recover_mode, n_replayed=n_replayed,
        )
        log_event(_log, "engine_recovered", mode=self.last_recover_mode,
                  n_replayed=n_replayed,
                  restarts=self.metrics.n_restarts,
                  recover_s=round(now - t_rec, 6))

    def fail_all(self, error: str) -> None:
        """Terminal supervision failure: fail every live and queued
        request (slot freed, ``done`` set) so no caller blocks on an
        engine that will never step again. Device state is left as-is
        (possibly corrupt — nothing will dispatch to it again)."""
        now = time.perf_counter()
        self._inflight = None
        while self._pending_prefills:
            self._drop_pending(
                self._pending_prefills.popleft(),
                RequestStatus.FAILED, error,
            )
        for slot, st in enumerate(self._slots):
            if st is not None:
                self._retire(slot, RequestStatus.FAILED, now, error=error)
        while True:
            req = self.scheduler.pop()
            if req is None:
                break
            self._retire_unadmitted(req, RequestStatus.FAILED, error)

    def run(self, max_steps: int | None = None, *,
            max_restarts: int = 5) -> dict[str, np.ndarray]:
        """Step until every queued/active request reaches a terminal
        status, supervising crashes: up to ``max_restarts`` replay
        recoveries before the crash propagates."""
        steps = 0
        restarts = 0
        while not self.idle:
            try:
                self.step()
            except EngineCrash:
                if restarts >= max_restarts:
                    raise
                restarts += 1
                self.recover()
                continue
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.results


def run_request_trace(
    engine: ServingEngine,
    trace: list[tuple[float, Request]],
    *,
    time_scale: float = 1.0,
    max_restarts: int = 5,
) -> dict[str, np.ndarray]:
    """Replay an arrival trace against a live engine.

    ``trace``: (arrival_offset_seconds, request) pairs; offsets are
    relative to the replay start and scaled by ``time_scale`` (0 floods
    every request instantly — useful for deterministic tests). The
    engine keeps stepping while waiting, exactly as a serving loop
    would, so admissions interleave with in-flight decodes. A submit
    rejected with ``Backpressure`` is retried on the next loop
    iteration (a decode step frees queue space) instead of killing the
    replay, and engine crashes recover by replay up to
    ``max_restarts`` times.
    """
    from collections import deque

    order = sorted(range(len(trace)), key=lambda j: trace[j][0])
    t0 = time.perf_counter()
    i = 0
    pending: deque[Request] = deque()
    restarts = 0
    while i < len(order) or pending or not engine.idle:
        now = time.perf_counter() - t0
        while i < len(order) and trace[order[i]][0] * time_scale <= now:
            pending.append(trace[order[i]][1])
            i += 1
        while pending:
            try:
                engine.submit(pending[0])
            except Backpressure:
                break  # queue full — a step below frees space, retry then
            pending.popleft()
        try:
            progressed = engine.step()
        except EngineCrash:
            if restarts >= max_restarts:
                raise
            restarts += 1
            engine.recover()
            continue
        if not progressed and not pending and i < len(order):
            # idle engine, next arrival still in the future
            time.sleep(
                min(0.001, max(0.0, trace[order[i]][0] * time_scale - now))
            )
    return engine.results
