"""Serving latency/utilization metrics.

Three sinks behind one recording API, so the engine instruments each
event exactly once:

- a :class:`~deeplearning4j_tpu.obs.registry.MetricsRegistry` of
  Prometheus counters/histograms (``serve_*`` families) — what the
  serving server renders at ``GET /metrics`` for a fleet scraper;
- bounded in-memory :class:`~deeplearning4j_tpu.obs.registry.Reservoir`
  series for the ``summary()`` percentile view (exact counts/totals,
  sampled percentiles — a week of traffic costs the same memory as a
  minute);
- optionally the JSONL :class:`MetricsWriter` (same format the
  trainer's listener emits, so the same grep/plot tooling reads both).

The series:

- ``serve/ttft_seconds`` — time-to-first-token per request, measured
  from scheduler arrival (so queue wait counts — that is the number a
  user sees);
- ``serve/tpot_seconds`` — time-per-output-token per request over its
  decode phase (steps after the first token);
- ``serve/occupancy`` — ACTIVE SLOT COUNT per engine step (the
  effective decode batch; > 1 means batching actually interleaved
  requests), with the fraction as ``serve/occupancy_frac``;
- ``serve/queue_depth`` — queued (not yet admitted) requests, sampled
  per engine step (JSONL only: the live value is the ``serve_queue_depth``
  gauge, the trace's the ``queue_depth`` counter);
- ``serve/queue_delay_seconds`` — submit-to-admission wait per request
  (the scheduling component of TTFT, separated out so horizon-induced
  admission latency is visible on its own);
- ``serve/sync_wait_seconds`` / ``serve/overlap_seconds`` — per
  readback, how long the host blocked on the device token sync vs how
  long it spent doing useful work (bookkeeping + next dispatch) while
  the horizon computed (JSONL only; their exact sums are
  ``phase_seconds["sync"]`` and ``phase_seconds["decode"]`` less it).
  ``dispatch_overlap_frac`` in ``summary()`` is
  overlap / (overlap + sync wait): ~0 means the host serializes with
  the device (the pre-pipelining behavior), near 1 means readback is
  fully hidden.

Per-phase accounting: every recorded second is also attributed to one
of four request phases — ``queue`` (submit → admission), ``prefill``
(admission prefill wall time), ``decode`` (horizon dispatch → token
block arrival), ``sync`` (the blocking slice of decode: the host-side
``np.asarray`` wait) — accumulated exactly in ``phase_seconds`` and
exported both as a labelled Prometheus histogram
(``serve_phase_seconds{phase=...}``) and as ``phase_frac`` in
``summary()``. This is the breakdown that justifies (or kills) tuning
work: an adaptive decode horizon only pays if ``queue`` dominates, a
batched same-bucket admission only if ``prefill`` does. Note ``sync``
is a sub-interval of ``decode`` (fractions tell where time GOES, not a
partition of wall time).

The engine LOOP has its own exact books, next to the requests':
``loop_seconds`` holds the self time of each phase of
``ServingEngine.step`` (``sweep``, ``admit``, ``prefill``, ``key_sync``,
``dispatch``, ``sync``, ``process`` — see :data:`LOOP_PHASES`; in
``sync`` the thread waits for the device), fed by the same
regions that name the phases in a profiler capture;
``kv_rows_live`` / ``kv_rows_streamed`` count, per decode substep, the
cache rows the occupied slots hold against the rows the step program
reads for them: with the decode kernel, each live slot's rows rounded
up to the kernel's T block and nothing for a free or frozen slot;
on the dense path and under the paged wrapper, every row of every slot
(``models.transformer.decode_rows_streamed``). Their ratio is the share
of the step's cache stream any request needed (a stack whose layers keep
caches of two lengths counts both as layer-weighted means, so the ratio
still is that share); ``moe_assignments_local`` / ``moe_assignments_total``
/ ``moe_experts_hit`` are an expert stack's books, counted inside the step
program and read back with its tokens: token-expert pairs computed by the
experts held here, pairs routed in all, and held experts that saw a token
(each streams its weights once); ``state_slot_updates`` is a stack
with linear_attention or mamba layers' book, counted the same way: the
(live slot, state layer) pairs whose recurrent state the step programs
advanced,
each reading and writing one state block (``state_block_bytes``) and
placing one convolution input in the ring (``conv_row_write`` says who
places it, so no counter of its own is needed) — the
state leaf holds no rows, so ``kv_rows_live`` / ``kv_rows_streamed``
count the full layers' slab alone there; ``ttft_segment_seconds`` splits every request's time
to first token at the three points the engine can see
(:data:`TTFT_SEGMENTS`). ``compile_log`` is the process's
:class:`~deeplearning4j_tpu.obs.compile_log.CompileLog`, and
``recompiles`` the compile requests seen after whoever warmed the
engine said so (``ServingEngine.mark_warm``), by function: the one
thing a warm server must never do.

With a multi-step decode horizon (``decode_horizon`` > 1) a "step" in
the series above is one K-substep horizon dispatch; TTFT is still
measured to the host-visible first token, so it honestly includes the
up-to-K-substeps readback lag the pipeline introduces.

Multi-tenant serving adds a ``tenant`` dimension: terminal outcomes,
generated tokens, and rejections get tenant-labelled Prometheus
families (``serve_tenant_requests_total``/``serve_tenant_tokens_total``
/``serve_rejections_total``), and per-tenant TPOT/queue-delay
reservoirs feed a ``tenants`` block in ``summary()``. Single-tenant
deployments pay nothing: the tenant state is created lazily on the
first event that carries a non-empty tenant id, and all the unlabelled
families above are recorded exactly as before.

p50/p99 come from ``summary()``; with fewer than ~100 samples the p99
is just the max-ish tail order statistic — fine for a bench row.
"""

from __future__ import annotations

import threading

import numpy as np

from deeplearning4j_tpu.analysis.sanitizers import note_access, wrap_lock
from deeplearning4j_tpu.obs.registry import MetricsRegistry, Reservoir
from deeplearning4j_tpu.utils.metrics import MetricsWriter

#: the four request phases the per-phase breakdown attributes time to
PHASES = ("queue", "prefill", "decode", "sync")

#: reservoir size for the latency series (uniform sample; exact
#: n/total/min/max are kept alongside)
RESERVOIR_CAP = 4096

#: the phases of one turn of the engine loop, in the order they run
#: (``prefill`` and ``key_sync`` inside ``admit`` or ``dispatch``,
#: ``sync`` inside ``process``); each holds its self time, so they add
#: up. In ``sync`` (the designated readback) the loop's thread waits
#: for the device; the rest is the host's own work, ``key_sync`` (a new
#: slot's sampling key, split on the host) included.
LOOP_PHASES = ("sweep", "admit", "prefill", "key_sync", "dispatch", "sync",
               "process")

#: a request's time to first token, cut where the engine can see it:
#: arrival -> the step boundary that popped it from the queue ->
#: seated in its slot (prefill done) -> the readback that brought its
#: first token -> that token put on its stream
TTFT_SEGMENTS = ("arrival_to_boundary", "boundary_to_seated",
                 "seated_to_readback", "readback_to_stream")


def _pct(res: Reservoir, p: float) -> float:
    return float(np.percentile(np.asarray(res.values, np.float64), p))


class ServingMetrics:
    def __init__(self, writer: MetricsWriter | None = None,
                 prefix: str = "serve",
                 registry: MetricsRegistry | None = None,
                 reservoir_cap: int = RESERVOIR_CAP):
        self.writer = writer
        self.prefix = prefix
        self.registry = registry if registry is not None else MetricsRegistry()
        self.ttft = Reservoir(reservoir_cap)
        self.tpot = Reservoir(reservoir_cap)
        self.occupancy = Reservoir(reservoir_cap)
        self.queue_delay = Reservoir(reservoir_cap)
        # exact per-phase wall-second totals (see module docstring)
        self.phase_seconds = {p: 0.0 for p in PHASES}
        # exact engine-loop totals, written by the engine thread only
        # (like phase_seconds, so no lock) and copied into the
        # Prometheus counters when they are rendered
        self.loop_seconds = {p: 0.0 for p in LOOP_PHASES}
        self.kv_rows_live = 0
        self.kv_rows_streamed = 0
        # expert layers' books (a stack with routed experts only):
        # counted on the device, read back with each horizon's tokens
        self.moe_assignments_local = 0
        self.moe_assignments_total = 0
        self.moe_experts_hit = 0
        # a stack with linear_attention or mamba layers: state blocks
        # advanced,
        # and the bytes of one (0: no state leaf)
        self.state_slot_updates = 0
        self.state_block_bytes = 0
        self.ttft_segment_seconds = {s: 0.0 for s in TTFT_SEGMENTS}
        self.n_ttft_segments = 0
        self.program_dispatches: dict[str, int] = {}
        # the process-wide compile log, set by the engine; recompiles
        # are the requests it saw after ServingEngine.mark_warm()
        self.compile_log = None
        self.recompiles: dict[str, int] = {}
        # stamped by the engine at construction; reported in summary()
        # so a bench row records which horizon produced its numbers
        self.decode_horizon = 1
        # how the step programs' top-k filter finds its threshold at
        # the engine's vocabulary and k (transformer.topk_select)
        self.topk_select = "none"
        # who places a substep's new K and V rows in the cache at the
        # engine's configuration (transformer.kv_row_write)
        self.kv_row_write = "xla"
        # what a position's cache row is (transformer.kv_cache_rows)
        self.kv_cache_rows = "kv"
        # who places a substep's convolution input in a state stack's
        # ring (transformer.conv_row_write; None: no state layers)
        self.conv_row_write = None
        # what computes the held experts' SwiGLU in the step programs
        # (transformer.moe_grouped; "none": no routed layers)
        self.moe_grouped = "none"
        self.n_finished = 0
        self.n_generated = 0
        # fault-tolerance counters (see serving.faults / engine docs):
        # retries = transient-fault boundary retries; restarts = engine
        # rebuilds by replay; failed/cancelled/expired = non-FINISHED
        # terminal request outcomes
        self.n_retries = 0
        self.n_restarts = 0
        self.n_failed = 0
        self.n_cancelled = 0
        self.n_expired = 0
        self.n_backpressure = 0
        # prefix-cache counters (see serving.prefix_cache): lookups by
        # outcome, prompt tokens whose prefill was skipped because their
        # KV came from a cached segment, segments inserted/evicted
        self.n_prefix_hits_full = 0
        self.n_prefix_hits_partial = 0
        self.n_prefix_misses = 0
        self.prefix_tokens_saved = 0
        self.n_prefix_inserts = 0
        self.n_prefix_evictions = 0
        # admissions coalesced into shared same-bucket prefill dispatches
        self.n_batched_admissions = 0
        # chunked-prefill piggyback (see engine): bounded prefill
        # chunks executed for deferred admissions (fused into a decode
        # dispatch or standalone), their token total, and wall seconds
        # occupied decode slots sat behind admission prefill work
        self.n_prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        self.decode_stall_seconds = 0.0
        # embedding requests served host-side (no KV slot)
        self.n_embeddings = 0
        self.embed_latency = Reservoir(reservoir_cap)
        # disaggregated prefill/decode counters (see serving.disagg):
        # exports = segments prefilled here for another replica,
        # ingests = wire segments offered to the local prefix cache
        # (stored or declined), transfers = push attempts to a decode
        # replica's /v1/kv_segment, recorded by the HTTP layer
        self.n_kv_exports = 0
        self.kv_export_bytes = 0
        self.n_kv_ingests_stored = 0
        self.n_kv_ingests_declined = 0
        self.kv_ingest_bytes = 0
        self.n_transfers = 0
        self.n_transfer_failures = 0
        self.transfer_bytes = 0
        self.transfer_seconds = 0.0
        self.kv_export_latency = Reservoir(reservoir_cap)
        self.kv_ingest_latency = Reservoir(reservoir_cap)
        self.transfer_latency = Reservoir(reservoir_cap)
        # live session migration (drain-time export/seat/settle):
        # exports = slots parked here and shipped out, seats = migrated
        # sessions offered to this engine (seated or declined),
        # settlements = parked requests resolved by the destination's
        # outcome (ok) or by the fail fallback (failed)
        self.n_migrations_out = 0
        self.n_migrations_seated = 0
        self.n_migrations_declined = 0
        self.n_migrations_settled_ok = 0
        self.n_migrations_settled_failed = 0
        self.migration_seat_latency = Reservoir(reservoir_cap)
        self._reservoir_cap = reservoir_cap
        # per-tenant state, created lazily on the first event carrying a
        # non-empty tenant id. HTTP handler threads record rejections
        # while the engine thread records finishes, so creation and the
        # exact counters move under a lock (the Prometheus counters have
        # their own).
        self._tlock = wrap_lock(threading.Lock(), "metrics._tlock")
        self._tenants: dict[str, dict] = {}  # guarded-by: _tlock
        self.n_rejections: dict[str, int] = {}  # guarded-by: _tlock
        # tenant_id -> p99 TPOT objective in seconds; the burn gauge is
        # derived from the per-tenant reservoir at render time
        self._tenant_slos: dict[str, float] = {}  # guarded-by: _tlock
        self._step = 0

        # Prometheus instruments (get-or-create: a shared registry can
        # back several metrics objects without double registration)
        reg = self.registry
        self._c_requests = reg.counter(
            "serve_requests_total",
            "Terminal request outcomes by status.", ("outcome",),
        )
        self._c_tokens = reg.counter(
            "serve_tokens_generated_total", "Tokens generated (all requests).",
        )
        self._c_steps = reg.counter(
            "serve_engine_steps_total",
            "Decode horizons dispatched (K substeps each).",
        )
        self._c_retries = reg.counter(
            "serve_retries_total", "Transient-fault boundary retries.",
        )
        self._c_restarts = reg.counter(
            "serve_restarts_total", "Engine rebuilds by deterministic replay.",
        )
        self._c_backpressure = reg.counter(
            "serve_backpressure_total",
            "Submits rejected at max queue depth (HTTP 429).",
        )
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds",
            "Time to first token, from scheduler arrival.",
        )
        self._h_tpot = reg.histogram(
            "serve_tpot_seconds", "Time per output token after the first.",
        )
        self._h_phase = reg.histogram(
            "serve_phase_seconds",
            "Per-event wall seconds by request phase "
            "(queue|prefill|decode|sync).", ("phase",),
        )
        self._c_prefix_lookups = reg.counter(
            "serve_prefix_lookups_total",
            "Prefix-cache lookups by outcome "
            "(hit_full|hit_partial|miss).", ("result",),
        )
        self._c_prefix_saved = reg.counter(
            "serve_prefix_tokens_saved_total",
            "Prompt tokens served from cached KV instead of prefill.",
        )
        self._c_prefix_inserts = reg.counter(
            "serve_prefix_inserts_total", "Prefix segments cached.",
        )
        self._c_prefix_evictions = reg.counter(
            "serve_prefix_evictions_total",
            "Prefix segments evicted (LRU, never pinned ones).",
        )
        self._c_batched = reg.counter(
            "serve_prefill_batched_total",
            "Admissions coalesced into shared same-bucket prefill "
            "dispatches.",
        )
        self._c_prefill_chunks = reg.counter(
            "serve_prefill_chunks_total",
            "Bounded prefill chunks executed for deferred piggyback "
            "admissions (fused or standalone).",
        )
        self._c_decode_stall = reg.counter(
            "serve_decode_stall_seconds_total",
            "Wall seconds occupied decode slots sat stalled behind "
            "admission prefill work.",
        )
        self._c_rejections = reg.counter(
            "serve_rejections_total",
            "Submits shed before queueing, by reason "
            "(backpressure|quota) and tenant.", ("reason", "tenant"),
        )
        self._c_tenant_requests = reg.counter(
            "serve_tenant_requests_total",
            "Terminal request outcomes by tenant.", ("tenant", "outcome"),
        )
        self._c_tenant_tokens = reg.counter(
            "serve_tenant_tokens_total",
            "Tokens generated per tenant.", ("tenant",),
        )
        self._g_slo_burn = reg.gauge(
            "serve_tenant_slo_burn",
            "Observed p99 TPOT / tenant SLO objective (> 1 = violating).",
            ("tenant",),
        )
        self._c_embeddings = reg.counter(
            "serve_embeddings_total",
            "Embedding requests served, by model.", ("model",),
        )
        self._h_embed = reg.histogram(
            "serve_embedding_seconds",
            "Embedding request service time (host-side lookup).",
        )
        self._c_kv_exports = reg.counter(
            "serve_kv_exports_total",
            "KV segments prefilled here and exported for a decode "
            "replica (disaggregated serving).",
        )
        self._c_kv_export_bytes = reg.counter(
            "serve_kv_export_bytes_total",
            "Raw segment bytes exported over the KV wire.",
        )
        self._h_kv_export = reg.histogram(
            "serve_kv_export_seconds",
            "Export service time: prefill + host snapshot.",
        )
        self._c_kv_ingests = reg.counter(
            "serve_kv_ingests_total",
            "Wire KV segments offered to the local prefix cache, by "
            "result (stored|declined).", ("result",),
        )
        self._c_kv_ingest_bytes = reg.counter(
            "serve_kv_ingest_bytes_total",
            "Raw segment bytes received over the KV wire.",
        )
        self._h_kv_ingest = reg.histogram(
            "serve_kv_ingest_seconds",
            "Ingest service time: validate + device seat.",
        )
        self._c_transfers = reg.counter(
            "serve_transfers_total",
            "KV segment pushes to a decode replica, by result "
            "(ok|failed).", ("result",),
        )
        self._c_transfer_bytes = reg.counter(
            "serve_transfer_bytes_total",
            "Frame bytes pushed to decode replicas over the KV wire.",
        )
        self._h_transfer = reg.histogram(
            "serve_transfer_seconds",
            "One KV segment push: POST /v1/kv_segment round trip.",
        )
        self._c_migrations_out = reg.counter(
            "serve_migrations_out_total",
            "Live sessions exported (parked) at drain for re-seating "
            "on another replica.",
        )
        self._c_migrations_in = reg.counter(
            "serve_migrations_in_total",
            "Migrated live sessions offered to this engine, by result "
            "(seated|declined).", ("result",),
        )
        self._c_migrations_settled = reg.counter(
            "serve_migrations_settled_total",
            "Parked requests resolved, by result (ok = destination "
            "finished the stream, failed = fallback preemption).",
            ("result",),
        )
        self._h_migration_seat = reg.histogram(
            "serve_migration_seat_seconds",
            "One migrated session seat: validate + device insert.",
        )
        self._c_grammar_compiles = reg.counter(
            "serve_grammar_compiles_total",
            "Grammar constraint resolutions at submit, by result "
            "(hit = LRU/disk cache, miss = fresh DFA compile, "
            "error = rejected 400).", ("result",),
        )
        self._c_stop_hits = reg.counter(
            "serve_stop_hits_total",
            "Requests finished by a stop-sequence match (host-side "
            "suffix match at readback).",
        )
        self._c_prog_dispatches = reg.counter(
            "serve_program_dispatches_total",
            "Program dispatches by compiled family.", ("family",),
        )
        self._c_loop_seconds = reg.counter(
            "serve_loop_seconds_total",
            "Self seconds of each phase of the engine loop "
            "(sweep|admit|prefill|key_sync|dispatch|sync|process). In "
            "sync (the horizon's readback) the loop waits for the "
            "device; the others, key_sync (a new slot's sampling key, "
            "split on the host) included, are host work.",
            ("phase",),
        )
        self._c_kv_rows_live = reg.counter(
            "serve_kv_rows_live_total",
            "Cache rows the occupied slots held, summed over decode "
            "substeps dispatched.",
        )
        self._c_kv_rows_streamed = reg.counter(
            "serve_kv_rows_streamed_total",
            "Cache rows the dispatched step programs read, summed over "
            "decode substeps (live / streamed = share of the decode "
            "stream a request needed).",
        )
        self._c_moe = {
            "moe_assignments_local": reg.counter(
                "serve_moe_assignments_local_total",
                "Token-expert pairs the experts held here computed, "
                "summed over expert layers and decode substeps.",
            ),
            "moe_assignments_total": reg.counter(
                "serve_moe_assignments_total",
                "Token-expert pairs the router made (experts per token "
                "x live tokens x expert layers), held here or not.",
            ),
            "moe_experts_hit": reg.counter(
                "serve_moe_experts_hit_total",
                "Held experts with at least one token, summed over "
                "expert layers and decode substeps: each streams its "
                "weights once.",
            ),
        }
        self._c_state_bytes = reg.counter(
            "serve_state_bytes_total",
            "Bytes of recurrent state the step programs read and wrote "
            "(linear_attention and mamba layers: state_slot_updates x 2 "
            "x one slot's state block of a layer).",
        )
        self._c_compile_requests = reg.counter(
            "serve_compile_requests_total",
            "Backend compile requests of this process (a persistent-"
            "cache hit is a request), by jitted function.", ("fun",),
        )
        self._c_compile_seconds = reg.counter(
            "serve_compile_seconds_total",
            "Seconds this process spent compiling, by stage "
            "(trace|lower|backend; backend is the XLA compile or the "
            "cache load).", ("stage",),
        )
        self._c_compile_cache = reg.counter(
            "serve_compile_cache_total",
            "Persistent compile cache look-ups that ended in a load "
            "(hit) or a new entry (miss).", ("result",),
        )
        self._c_recompiles = reg.counter(
            "serve_recompiles_total",
            "Compile requests after the engine was declared warm, by "
            "jitted function: alert on any increase.", ("fun",),
        )

    def _emit(self, tag: str, value: float, step: int | None = None) -> None:
        if self.writer is not None:
            self.writer.scalar(f"{self.prefix}/{tag}", value, step)

    def _tenant(self, tenant_id: str) -> dict:  # lint: holds _tlock
        """Per-tenant exact counters + reservoirs. Call holding
        ``_tlock``."""
        st = self._tenants.get(tenant_id)
        if st is None:
            note_access("metrics.tenants", write=True)
            st = self._tenants[tenant_id] = {
                "tpot": Reservoir(self._reservoir_cap),
                "queue_delay": Reservoir(self._reservoir_cap),
                "n_finished": 0,
                "n_generated": 0,
                "n_rejected": 0,
                "n_other": 0,
            }
        return st

    def record_phase(self, phase: str, seconds: float) -> None:
        """Attribute ``seconds`` of wall time to a request phase."""
        self.phase_seconds[phase] += seconds
        self._h_phase.observe(seconds, phase=phase)

    def record_program(self, family: str) -> None:
        """Count one dispatch of a compiled program family. What a
        family costs on the device is read from a profiler capture
        (``POST /profile``), not guessed from the host's clock."""
        self.program_dispatches[family] = (
            self.program_dispatches.get(family, 0) + 1
        )
        self._c_prog_dispatches.inc(family=family)

    def record_kv_rows(self, live: int, streamed: int) -> None:
        """One decode dispatch: over its substeps the occupied slots
        held ``live`` cache rows and the step program read
        ``streamed``."""
        self.kv_rows_live += live
        self.kv_rows_streamed += streamed

    def record_moe(self, local: int, total: int, experts_hit: int) -> None:
        """One horizon's expert-layer counters, summed over its
        substeps (``models.transformer``'s gated ``forward_one``)."""
        self.moe_assignments_local += local
        self.moe_assignments_total += total
        self.moe_experts_hit += experts_hit

    def record_counts(self, counts: dict) -> None:
        """One horizon's counter rows (``engine.count_rows``), summed
        over its substeps, by name."""
        if "moe_experts_hit" in counts:
            self.record_moe(counts["moe_assignments_local"],
                            counts["moe_assignments_total"],
                            counts["moe_experts_hit"])
        self.state_slot_updates += counts.get("state_slot_updates", 0)

    def record_ttft_segments(self, *seconds: float) -> None:
        """One request's time to first token, cut into
        :data:`TTFT_SEGMENTS`."""
        for name, s in zip(TTFT_SEGMENTS, seconds, strict=True):
            self.ttft_segment_seconds[name] += s
        self.n_ttft_segments += 1

    def record_recompile(self, fun: str) -> None:
        """One compile request after the engine was declared warm."""
        self.recompiles[fun] = self.recompiles.get(fun, 0) + 1
        self._c_recompiles.inc(fun=fun)

    def record_step(self, n_active: int, n_slots: int,
                    queue_depth: int) -> None:
        """Per-engine-step utilization sample (``n_active`` slots
        decoding this step, of ``n_slots``)."""
        self.occupancy.add(float(n_active))
        self._c_steps.inc()
        self._emit("occupancy", n_active, self._step)
        self._emit("occupancy_frac", n_active / n_slots, self._step)
        self._emit("queue_depth", queue_depth, self._step)
        self._step += 1

    def record_admitted(self, req_id: str, delay_s: float,
                        tenant: str = "") -> None:
        """Request left the queue for a KV slot after ``delay_s``
        seconds of waiting (admission happens at horizon boundaries, so
        this is where decode_horizon > 1 shows up first)."""
        self.queue_delay.add(float(delay_s))
        self.record_phase("queue", float(delay_s))
        self._emit("queue_delay_seconds", delay_s)
        if tenant:
            with self._tlock:
                self._tenant(tenant)["queue_delay"].add(float(delay_s))

    def record_prefill(self, req_id: str, seconds: float) -> None:
        """One admission prefill (all bucket/chunk dispatches)."""
        self.record_phase("prefill", float(seconds))

    def record_readback(self, sync_wait_s: float,
                        overlap_s: float) -> None:
        """One horizon readback: host blocked ``sync_wait_s`` on the
        token sync after ``overlap_s`` of overlapped host work. The
        horizon's decode interval (dispatch → block arrival) is their
        sum."""
        self.record_phase("decode", float(sync_wait_s) + float(overlap_s))
        self.record_phase("sync", float(sync_wait_s))
        self._emit("sync_wait_seconds", sync_wait_s)
        self._emit("overlap_seconds", overlap_s)

    def record_first_token(self, req_id: str, ttft_s: float) -> None:
        self.ttft.add(float(ttft_s))
        self._h_ttft.observe(ttft_s)
        self._emit("ttft_seconds", ttft_s)

    def record_finished(self, req_id: str, n_tokens: int,
                        decode_s: float, tenant: str = "") -> None:
        """Request retired: ``n_tokens`` generated, ``decode_s`` wall
        seconds spent after the first token."""
        self.n_finished += 1
        self.n_generated += n_tokens
        self._c_requests.inc(outcome="finished")
        self._c_tokens.inc(n_tokens)
        tpot = None
        if n_tokens > 1:
            tpot = decode_s / (n_tokens - 1)
            self.tpot.add(tpot)
            self._h_tpot.observe(tpot)
            self._emit("tpot_seconds", tpot)
        if tenant:
            self._c_tenant_requests.inc(tenant=tenant, outcome="finished")
            self._c_tenant_tokens.inc(n_tokens, tenant=tenant)
            with self._tlock:
                st = self._tenant(tenant)
                st["n_finished"] += 1
                st["n_generated"] += n_tokens
                if tpot is not None:
                    st["tpot"].add(tpot)

    def record_retry(self) -> None:
        """One transient-fault retry at an engine boundary."""
        self.n_retries += 1
        self._c_retries.inc()
        self._emit("retries_total", self.n_retries)

    def record_restart(self) -> None:
        """One engine-state rebuild by deterministic replay."""
        self.n_restarts += 1
        self._c_restarts.inc()
        self._emit("restarts_total", self.n_restarts)

    def record_backpressure(self) -> None:
        """One submit shed at max queue depth."""
        self.n_backpressure += 1
        self._c_backpressure.inc()

    def record_grammar_compile(self, result: str) -> None:
        """One grammar constraint resolution (hit|miss|error)."""
        self._c_grammar_compiles.inc(result=result)

    def record_stop_hit(self) -> None:
        """One request finished by a stop-sequence match."""
        self._c_stop_hits.inc()

    def record_rejection(self, reason: str, tenant: str = "") -> None:
        """One submit shed before queueing, with its reason
        (``backpressure`` = queue depth, ``quota`` = tenant token
        bucket dry). Recorded ALONGSIDE :meth:`record_backpressure`
        — that unlabelled counter keeps its pre-tenancy meaning while
        this family adds the reason/tenant breakdown."""
        self._c_rejections.inc(reason=reason, tenant=tenant)
        with self._tlock:
            self.n_rejections[reason] = self.n_rejections.get(reason, 0) + 1
            if tenant:
                self._tenant(tenant)["n_rejected"] += 1

    def record_embedding(self, model: str, n_words: int,
                         seconds: float, tenant: str = "") -> None:
        """One embedding request served host-side (``n_words`` lookups
        against the ``model`` embedder, no KV slot involved)."""
        self.n_embeddings += 1
        self.embed_latency.add(float(seconds))
        self._c_embeddings.inc(model=model)
        self._h_embed.observe(seconds)
        self._emit("embedding_seconds", seconds)
        if tenant:
            self._c_tenant_requests.inc(tenant=tenant, outcome="embedding")
            with self._tlock:
                self._tenant(tenant)["n_finished"] += 1

    def record_kv_export(self, n_tokens: int, nbytes: int,
                         seconds: float, tenant: str = "") -> None:
        """One KV segment prefilled here for a decode replica
        (``n_tokens`` of prompt, ``nbytes`` of raw segment bytes)."""
        self.n_kv_exports += 1
        self.kv_export_bytes += int(nbytes)
        self.kv_export_latency.add(float(seconds))
        self._c_kv_exports.inc()
        self._c_kv_export_bytes.inc(int(nbytes))
        self._h_kv_export.observe(seconds)
        self._emit("kv_export_seconds", seconds)
        if tenant:
            self._c_tenant_requests.inc(tenant=tenant, outcome="kv_export")
            with self._tlock:
                self._tenant(tenant)["n_finished"] += 1

    def record_kv_ingest(self, n_tokens: int, nbytes: int,
                         seconds: float, *, stored: bool,
                         tenant: str = "") -> None:
        """One wire segment offered to the local prefix cache.
        ``stored`` means the follow-up generate will full-hit; a
        decline is soft (the sender falls back to local prefill)."""
        if stored:
            self.n_kv_ingests_stored += 1
        else:
            self.n_kv_ingests_declined += 1
        self.kv_ingest_bytes += int(nbytes)
        self.kv_ingest_latency.add(float(seconds))
        self._c_kv_ingests.inc(result="stored" if stored else "declined")
        self._c_kv_ingest_bytes.inc(int(nbytes))
        self._h_kv_ingest.observe(seconds)
        self._emit("kv_ingest_seconds", seconds)
        if tenant:
            self._c_tenant_requests.inc(tenant=tenant, outcome="kv_ingest")
            with self._tlock:
                self._tenant(tenant)["n_finished"] += 1

    def record_transfer(self, nbytes: int, seconds: float, *,
                        ok: bool = True) -> None:
        """One KV segment push to a decode replica (HTTP layer).
        Failed pushes record their wall time but no bytes — the
        segment never landed."""
        self.n_transfers += 1
        self.transfer_latency.add(float(seconds))
        self.transfer_seconds += float(seconds)
        self._c_transfers.inc(result="ok" if ok else "failed")
        self._h_transfer.observe(seconds)
        self._emit("transfer_seconds", seconds)
        if ok:
            self.transfer_bytes += int(nbytes)
            self._c_transfer_bytes.inc(int(nbytes))
        else:
            self.n_transfer_failures += 1

    def record_migration_out(self, n_generated: int, seconds: float,
                             tenant: str = "") -> None:
        """One live slot exported (parked) for migration at drain."""
        self.n_migrations_out += 1
        self._c_migrations_out.inc()
        self._emit("migration_export_seconds", seconds)

    def record_migration_in(self, n_generated: int, seconds: float, *,
                            seated: bool, tenant: str = "") -> None:
        """One migrated session offered to this engine. A decline is
        soft — the source keeps its existing fail path."""
        if seated:
            self.n_migrations_seated += 1
        else:
            self.n_migrations_declined += 1
        self.migration_seat_latency.add(float(seconds))
        self._c_migrations_in.inc(
            result="seated" if seated else "declined"
        )
        self._h_migration_seat.observe(seconds)
        self._emit("migration_seat_seconds", seconds)
        if tenant and seated:
            self._c_tenant_requests.inc(tenant=tenant,
                                        outcome="migrated_in")

    def record_migration_settled(self, *, ok: bool,
                                 tenant: str = "") -> None:
        """One parked request resolved: the destination finished its
        stream (ok) or migration failed and the request fell back to
        the preemption path."""
        if ok:
            self.n_migrations_settled_ok += 1
        else:
            self.n_migrations_settled_failed += 1
        self._c_migrations_settled.inc(result="ok" if ok else "failed")

    def record_prefix_lookup(self, result: str, saved_tokens: int) -> None:
        """One admission-time prefix-cache lookup. ``result`` is
        ``hit_full``/``hit_partial``/``miss``; ``saved_tokens`` is how
        many prompt tokens the hit served from cached KV (the usable,
        grain-aligned match — 0 on a miss)."""
        self._c_prefix_lookups.inc(result=result)
        if result == "hit_full":
            self.n_prefix_hits_full += 1
        elif result == "hit_partial":
            self.n_prefix_hits_partial += 1
        else:
            self.n_prefix_misses += 1
        if saved_tokens:
            self.prefix_tokens_saved += int(saved_tokens)
            self._c_prefix_saved.inc(int(saved_tokens))
            self._emit("prefix_tokens_saved_total",
                       self.prefix_tokens_saved)

    def record_prefix_insert(self) -> None:
        """One new segment cached."""
        self.n_prefix_inserts += 1
        self._c_prefix_inserts.inc()

    def record_prefix_eviction(self) -> None:
        """One unpinned segment dropped by LRU pressure."""
        self.n_prefix_evictions += 1
        self._c_prefix_evictions.inc()

    def record_batched_admissions(self, n: int) -> None:
        """``n`` admissions served by ONE shared prefill dispatch
        (recorded once per coalesced group, n >= 2)."""
        self.n_batched_admissions += int(n)
        self._c_batched.inc(int(n))

    def record_prefill_chunk(self, tokens: int) -> None:
        """One bounded prefill chunk executed for a deferred
        (piggyback) admission — fused into a decode dispatch or run
        standalone under the per-horizon token budget."""
        self.n_prefill_chunks += 1
        self.prefill_chunk_tokens += int(tokens)
        self._c_prefill_chunks.inc()

    def record_decode_stall(self, seconds: float) -> None:
        """Wall time occupied decode slots waited on admission
        prefill work (measured piggyback-on AND -off, so the bench
        comparison prices the stall reduction honestly)."""
        self.decode_stall_seconds += float(seconds)
        self._c_decode_stall.inc(float(seconds))

    def record_outcome(self, status, tenant: str = "") -> None:
        """Non-FINISHED terminal outcome (status is a
        ``RequestStatus`` or its string value)."""
        s = getattr(status, "value", status)
        self._c_requests.inc(outcome=s)
        if tenant:
            self._c_tenant_requests.inc(tenant=tenant, outcome=s)
            with self._tlock:
                self._tenant(tenant)["n_other"] += 1
        if s == "failed":
            self.n_failed += 1
            self._emit("failed_total", self.n_failed)
        elif s == "cancelled":
            self.n_cancelled += 1
            self._emit("cancelled_total", self.n_cancelled)
        elif s == "expired":
            self.n_expired += 1
            self._emit("expired_total", self.n_expired)

    def set_tenant_slo(self, tenant_id: str, p99_tpot_s: float) -> None:
        """Declare a tenant's p99 TPOT objective (seconds). From then
        on every render publishes ``serve_tenant_slo_burn{tenant}`` =
        observed p99 / objective, once the tenant has TPOT samples."""
        if p99_tpot_s <= 0:
            raise ValueError("p99_tpot_s must be > 0")
        with self._tlock:
            self._tenant_slos[tenant_id] = float(p99_tpot_s)

    def _update_slo_burn(self) -> None:
        """Refresh the burn-rate gauges from the per-tenant TPOT
        reservoirs. Tenants with an SLO but no samples yet publish
        nothing (a 0 would read as a perfect SLO with zero traffic)."""
        with self._tlock:
            for tid, target in self._tenant_slos.items():
                st = self._tenants.get(tid)
                if st is not None and st["tpot"]:
                    burn = _pct(st["tpot"], 99) / target
                    self._g_slo_burn.set(burn, tenant=tid)

    def _update_exact_totals(self) -> None:
        """Bring the Prometheus counters up to the exact totals the
        engine thread keeps as plain attributes (and the compile log
        keeps for the process), so the hot path pays no lock for
        them."""
        def raise_to(counter, value, **labels):
            counter.inc(max(0.0, value - counter.value(**labels)), **labels)

        for phase, secs in self.loop_seconds.items():
            raise_to(self._c_loop_seconds, secs, phase=phase)
        raise_to(self._c_kv_rows_live, self.kv_rows_live)
        raise_to(self._c_kv_rows_streamed, self.kv_rows_streamed)
        for name, counter in self._c_moe.items():
            raise_to(counter, getattr(self, name))
        raise_to(self._c_state_bytes,
                 2 * self.state_block_bytes * self.state_slot_updates)
        if self.compile_log is None:
            return
        totals = self.compile_log.totals()
        for fun, per in totals["by_fun"].items():
            raise_to(self._c_compile_requests, per["requests"], fun=fun)
        for stage, secs in totals["stage_seconds"].items():
            raise_to(self._c_compile_seconds, secs, stage=stage)
        raise_to(self._c_compile_cache, totals["cache_hits"], result="hit")
        raise_to(self._c_compile_cache, totals["cache_misses"],
                 result="miss")

    def render_prometheus(self) -> str:
        """The backing registry in Prometheus text format (what the
        serving server returns at ``GET /metrics``)."""
        self._update_slo_burn()
        self._update_exact_totals()
        return self.registry.render()

    def summary(self) -> dict:
        """Aggregate view: p50/p99 latencies + mean utilization +
        per-phase breakdown."""
        out = {
            "n_finished": self.n_finished,
            "n_generated": self.n_generated,
            "n_retries": self.n_retries,
            "n_restarts": self.n_restarts,
            "n_failed": self.n_failed,
            "n_cancelled": self.n_cancelled,
            "n_expired": self.n_expired,
            "steps": self._step,
            "decode_horizon": self.decode_horizon,
            "topk_select": self.topk_select,
            "kv_row_write": self.kv_row_write,
            "kv_cache_rows": self.kv_cache_rows,
            "moe_grouped": self.moe_grouped,
        }
        lookups = (self.n_prefix_hits_full + self.n_prefix_hits_partial
                   + self.n_prefix_misses)
        if lookups:
            out["prefix_lookups"] = lookups
            out["prefix_hit_rate"] = (
                (self.n_prefix_hits_full + self.n_prefix_hits_partial)
                / lookups
            )
            out["prefix_tokens_saved"] = self.prefix_tokens_saved
            out["prefix_inserts"] = self.n_prefix_inserts
            out["prefix_evictions"] = self.n_prefix_evictions
        if self.n_batched_admissions:
            out["batched_admissions"] = self.n_batched_admissions
        if self.n_prefill_chunks:
            out["prefill_chunks"] = self.n_prefill_chunks
            out["prefill_chunk_tokens"] = self.prefill_chunk_tokens
        if self.decode_stall_seconds > 0:
            out["decode_stall_s"] = round(self.decode_stall_seconds, 6)
        if self.n_embeddings:
            out["n_embeddings"] = self.n_embeddings
            out["embedding_p50_s"] = _pct(self.embed_latency, 50)
        if (self.n_kv_exports or self.n_transfers
                or self.n_kv_ingests_stored or self.n_kv_ingests_declined):
            d = {
                "kv_exports": self.n_kv_exports,
                "kv_export_bytes": self.kv_export_bytes,
                "kv_ingests_stored": self.n_kv_ingests_stored,
                "kv_ingests_declined": self.n_kv_ingests_declined,
                "kv_ingest_bytes": self.kv_ingest_bytes,
                "transfers": self.n_transfers,
                "transfer_failures": self.n_transfer_failures,
                "transfer_bytes": self.transfer_bytes,
            }
            if self.kv_export_latency:
                d["kv_export_p50_s"] = _pct(self.kv_export_latency, 50)
            if self.transfer_latency:
                d["transfer_p50_s"] = _pct(self.transfer_latency, 50)
                if self.transfer_seconds > 0:
                    d["transfer_bytes_per_s"] = (
                        self.transfer_bytes / self.transfer_seconds
                    )
            out["disagg"] = d
        if (self.n_migrations_out or self.n_migrations_seated
                or self.n_migrations_declined):
            d = {
                "migrations_out": self.n_migrations_out,
                "migrations_seated": self.n_migrations_seated,
                "migrations_declined": self.n_migrations_declined,
                "migrations_settled_ok": self.n_migrations_settled_ok,
                "migrations_settled_failed":
                    self.n_migrations_settled_failed,
            }
            if self.migration_seat_latency:
                d["seat_p50_s"] = _pct(self.migration_seat_latency, 50)
                d["seat_p99_s"] = _pct(self.migration_seat_latency, 99)
            out["migration"] = d
        with self._tlock:
            if self.n_rejections:
                out["rejections"] = dict(self.n_rejections)
            if self._tenants:
                tenants = {}
                for tid in sorted(self._tenants):
                    st = self._tenants[tid]
                    t = {
                        "n_finished": st["n_finished"],
                        "n_generated": st["n_generated"],
                    }
                    if st["n_rejected"]:
                        t["n_rejected"] = st["n_rejected"]
                    if st["n_other"]:
                        t["n_other_outcomes"] = st["n_other"]
                    if st["tpot"]:
                        t["tpot_p50_s"] = _pct(st["tpot"], 50)
                        t["tpot_p99_s"] = _pct(st["tpot"], 99)
                        slo = self._tenant_slos.get(tid)
                        if slo is not None:
                            t["slo_burn"] = t["tpot_p99_s"] / slo
                    if st["queue_delay"]:
                        t["queue_delay_p50_s"] = _pct(st["queue_delay"], 50)
                        t["queue_delay_p99_s"] = _pct(st["queue_delay"], 99)
                    tenants[tid] = t
                out["tenants"] = tenants
        for name, xs in [("ttft", self.ttft), ("tpot", self.tpot),
                         ("queue_delay", self.queue_delay)]:
            if xs:
                out[f"{name}_p50_s"] = _pct(xs, 50)
                out[f"{name}_p99_s"] = _pct(xs, 99)
        if self.phase_seconds["decode"] > 0:
            # a horizon's decode interval is overlapped host work plus
            # the blocking sync (record_readback)
            out["dispatch_overlap_frac"] = 1.0 - (
                self.phase_seconds["sync"] / self.phase_seconds["decode"]
            )
        if self.occupancy:
            # mean slots actually decoding per step — the "effective
            # batch" a continuous batcher is supposed to keep > 1
            out["occupancy_mean"] = self.occupancy.mean
        if self.program_dispatches:
            out["program_dispatches"] = dict(
                sorted(self.program_dispatches.items())
            )
        out["loop_seconds"] = {
            p: round(v, 6) for p, v in self.loop_seconds.items()
        }
        out["kv_rows_live"] = self.kv_rows_live
        out["kv_rows_streamed"] = self.kv_rows_streamed
        if self.moe_assignments_total:
            for name in self._c_moe:
                out[name] = getattr(self, name)
        if self.state_block_bytes:
            out["state_slot_updates"] = self.state_slot_updates
        if self.conv_row_write is not None:
            out["conv_row_write"] = self.conv_row_write
        if self.n_ttft_segments:
            out["ttft_segments"] = {
                "n": self.n_ttft_segments,
                "seconds": {s: round(v, 6) for s, v in
                            self.ttft_segment_seconds.items()},
            }
        if self.compile_log is not None:
            totals = self.compile_log.totals()
            del totals["by_fun"]  # per function: /metrics, or the log itself
            totals["recompiles"] = dict(sorted(self.recompiles.items()))
            out["compile"] = totals
        attributed = sum(self.phase_seconds.values())
        if attributed > 0:
            out["phase_seconds"] = {
                p: round(v, 6) for p, v in self.phase_seconds.items()
            }
            out["phase_frac"] = {
                p: round(v / attributed, 4)
                for p, v in self.phase_seconds.items()
            }
        return out
