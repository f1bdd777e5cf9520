"""Program-surface registry: every compiled family the serving engine
can emit, as abstract avals — no devices, no weights, no execution.

The engine's jit caches call the module-level ``build_*_program``
factories in ``serving.engine`` with its own closures; this module
calls the SAME factories with closures built from a
:class:`TransformerConfig` plus a :class:`ServingGeometry`, and derives
every argument as a :class:`jax.ShapeDtypeStruct` via ``eval_shape``.
A registry entry is therefore the live program by construction — the
static auditor (``analysis.audit``) traces these specs and checks
dtype promotion, donation, collective signatures, callback smuggling,
and the compile-surface bounds without ever running the engine.

Family keys mirror the engine's jit-cache keys exactly (step programs
per horizon, prefill/chunk per pow2 bucket, batched admission per
(bucket, pow2 group)), so a test can diff the registry against a live
engine's ``CompileCountGuard`` families.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _chunk_builder,
    _decode_builder,
    init_lora_bank,
    init_transformer,
    make_paged_fwd1,
    tp_collective_contract,
)
from deeplearning4j_tpu.parallel.mesh import model_parallel_mesh
from deeplearning4j_tpu.serving.engine import (
    PROGRAM_DONATION,
    build_batch_hit_program,
    build_batch_prefill_program,
    build_block_copy_program,
    build_chunk_program,
    build_deact_program,
    build_hit_insert_program,
    build_gstate_set_program,
    build_insert_program,
    build_logit_row_program,
    build_masked_piggyback_program,
    build_masked_step_program,
    build_paged_insert_program,
    build_paged_prefill_program,
    build_paged_seg_fetch_program,
    build_paged_seg_import_program,
    build_piggyback_program,
    build_prefill_program,
    build_replay_program,
    build_seg_fetch_program,
    build_seg_import_program,
    build_seg_store_program,
    build_step_program,
)


def _sds(tree):
    """Aval tree -> ShapeDtypeStruct tree (jittable-argument form)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _pow2_up_to(limit: int) -> list[int]:
    out, b = [], 1
    while b <= limit:
        out.append(b)
        b *= 2
    return out


@dataclasses.dataclass(frozen=True)
class ServingGeometry:
    """The serving-side knobs that determine the compiled surface —
    the registry analogue of ``ServingEngine.__init__``'s geometry
    arguments. Defaults give a small surface that traces in seconds
    on CPU (the CI audit geometry)."""

    n_slots: int = 4
    max_total: int = 64
    temperature: float = 0.0
    top_k: int | None = None
    approx_top_k: bool = False
    decode_horizon: int = 2
    adaptive_horizon: bool = True
    prefill_max_bucket: int = 32
    tp: int = 1
    n_adapters: int = 0
    lora_rank: int = 4
    prefix_segments: int = 2
    # block-paged KV surface (``ServingEngine(paged=True)``): the paged
    # families ride ALONGSIDE the slab ones — a paged engine still
    # compiles the chunk/scratch-slab programs (suffix path)
    paged: bool = False
    block_size: int = 8
    # production sampling surface (``ServingEngine(sampling_surface=
    # True)``): masked step/piggyback variants replace the plain ones
    # at dispatch time, plus the single-row grammar-state seat program
    sampling_surface: bool = False
    grammar_states: int = 64
    n_bias: int = 8
    n_logprobs: int = 8

    def blocks_per_slot(self, cfg: TransformerConfig) -> int:
        """Table width — mirrors ``PagedKVPool``'s Tpad/block split."""
        return self.tpad(cfg) // self.block_size

    def n_blocks(self, cfg: TransformerConfig) -> int:
        """Default pool capacity: slab-equivalent + the zero sentinel."""
        return self.n_slots * self.blocks_per_slot(cfg) + 1

    def tpad(self, cfg: TransformerConfig) -> int:
        """Pooled slab row count — mirrors ``init_caches``."""
        total = min(self.max_total, cfg.max_len)
        if total <= 1024:
            return -(-total // 8) * 8
        return -(-total // 512) * 512

    def buckets(self, cfg: TransformerConfig) -> list[int]:
        """The pow2 prompt-bucket grid — mirrors the engine's
        ``_min_bucket``/``_max_bucket`` derivation."""
        limit = min(
            self.prefill_max_bucket, cfg.max_len, self.tpad(cfg)
        )
        mb = 1
        while mb * 2 <= limit:
            mb *= 2
        lo = min(8, mb)
        return [b for b in _pow2_up_to(mb) if b >= lo]

    def horizons(self) -> list[int]:
        """Fused-step horizons the engine can key programs on:
        {K}, or {1, K} under the adaptive horizon."""
        k = max(1, self.decode_horizon)
        return sorted({1, k}) if self.adaptive_horizon else [k]

    def group_sizes(self) -> list[int]:
        """Batched-admission group sizes (pow2, padded up)."""
        return _pow2_up_to(self.n_slots)

    def to_json_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramSpec:
    """One enumerable compiled program: a build() thunk returning the
    (python callable, abstract argument tuple) pair the auditor
    traces, plus the family's DECLARED contracts — donation argnums
    (from ``PROGRAM_DONATION``) and the collective signature ({} for
    single-chip families: any collective is drift)."""

    name: str
    family: str
    donate: tuple[int, ...]
    tp: bool
    collectives: dict[str, int]
    build: object  # () -> (fn, args)

    def trace(self):
        fn, args = self.build()
        return jax.jit(fn).trace(*args)


class _FamilyAvals:
    """Shared abstract avals for one (cfg, geometry, tp_mesh) tuple —
    params after the serving weight cast, pooled caches, scratch
    caches, prefix region, and the per-slot device state."""

    def __init__(self, cfg: TransformerConfig, geom: ServingGeometry,
                 tp_mesh=None, lora: bool = False):
        self.cfg, self.geom = cfg, geom
        fwd1, ic, do_prefill, cast = _decode_builder(
            cfg, tp_mesh=tp_mesh
        )
        self.fwd1 = fwd1
        self.init_caches = ic
        self.do_prefill = do_prefill
        self.fwd_chunk = _chunk_builder(cfg, tp_mesh=tp_mesh)

        def abstract_params():
            p = init_transformer(jax.random.key(0), cfg)
            if lora:
                p = dict(p)
                p["lora"] = init_lora_bank(
                    jax.random.key(1), cfg,
                    n_adapters=max(2, geom.n_adapters),
                    rank=geom.lora_rank,
                )
            return cast(p)

        self.params = _sds(jax.eval_shape(abstract_params))
        self.caches = _sds(
            jax.eval_shape(lambda: ic(geom.n_slots, geom.max_total))
        )
        self.scratch = _sds(
            jax.eval_shape(lambda: ic(1, geom.max_total))
        )
        self.region = _sds(
            jax.eval_shape(
                lambda: ic(geom.prefix_segments, geom.max_total)
            )
        )
        n, v = geom.n_slots, cfg.vocab_size
        self.logits = jax.ShapeDtypeStruct((n, v), jnp.float32)
        self.row_logits = jax.ShapeDtypeStruct((1, v), jnp.float32)
        self.pos = _i32(n)
        self.active = jax.ShapeDtypeStruct((n,), jnp.bool_)
        self.budget = _i32(n)
        self.eos = _i32(n)
        key_shape = jax.eval_shape(
            lambda: jax.random.key_data(jax.random.key(0))
        ).shape
        self.slot_keys = jax.ShapeDtypeStruct(
            (n,) + key_shape, jnp.uint32
        )
        self.adapters = _i32(n)
        # sampling-surface avals: per-slot traced sampling vectors plus
        # the shared device DFA tables (mask bitmask words + absolute
        # transition rows) — mirrors the engine's mirrors/_gtable
        self.gstate = _i32(n)
        self.temps = jax.ShapeDtypeStruct((n,), jnp.float32)
        self.topks = _i32(n)
        self.topps = jax.ShapeDtypeStruct((n,), jnp.float32)
        self.bias_idx = _i32(n, geom.n_bias)
        self.bias_val = jax.ShapeDtypeStruct(
            (n, geom.n_bias), jnp.float32
        )
        self.mask_tab = jax.ShapeDtypeStruct(
            (geom.grammar_states, -(-v // 32)), jnp.uint32
        )
        self.trans_tab = _i32(geom.grammar_states, v)
        if geom.paged:
            # blocks leaves mirror PagedKVPool._alloc_caches: the slab
            # leaf's (slot, Tpad) plane becomes (n_blocks, block_size)
            nb = geom.n_blocks(cfg)
            bps = geom.blocks_per_slot(cfg)
            self.blocks = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (s.shape[0], s.shape[1], nb, geom.block_size,
                     s.shape[4]),
                    s.dtype,
                ),
                self.scratch,
            )
            self.tables = _i32(geom.n_slots, bps)
            self.paged_caches = {
                "blocks": self.blocks, "tables": self.tables
            }
            self.seg_row = _i32(bps)

    def state(self):
        return (self.caches, self.logits, self.pos, self.active,
                self.budget, self.eos)

    def paged_state(self):
        return (self.paged_caches, self.logits, self.pos, self.active,
                self.budget, self.eos)

    def surface_tail(self):
        """The masked programs' trailing arguments, in ``mstep``
        signature order (after ``params`` + the slot state)."""
        return (self.gstate, self.slot_keys, self.adapters,
                self.temps, self.topks, self.topps, self.bias_idx,
                self.bias_val, self.mask_tab, self.trans_tab)


def _specs_for(av: _FamilyAvals, geom: ServingGeometry, *,
               tp: bool = False, suffix: str = "",
               families: set[str] | None = None) -> list[ProgramSpec]:
    """ProgramSpecs for every family under one aval set. ``families``
    restricts the emitted set (TP/LoRA variants re-enumerate only the
    forward-pass families — the copy/slice programs contain no model
    code, so their sharded variants add tracing time, not coverage)."""
    cfg = av.cfg
    out: list[ProgramSpec] = []

    def want(f):
        return families is None or f in families

    def add(name, family, build, n_substeps=0, scanned=False):
        contract = (
            tp_collective_contract(cfg, n_substeps, scanned=scanned)
            if tp and n_substeps else {}
        )
        out.append(ProgramSpec(
            name=name + suffix, family=family,
            donate=PROGRAM_DONATION[family], tp=tp,
            collectives=contract, build=build,
        ))

    if want("step"):
        for k in geom.horizons():
            add(
                f"step[K={k}]", "step",
                lambda k=k: (
                    build_step_program(
                        av.fwd1, k, geom.temperature, geom.top_k,
                        geom.approx_top_k,
                    ),
                    (av.params, *av.state(), av.slot_keys,
                     av.adapters),
                ),
                n_substeps=k,
            )
    if want("replay"):
        add(
            "replay", "replay",
            lambda: (
                build_replay_program(av.fwd1),
                (av.params, av.caches, av.logits, _i32(geom.n_slots),
                 av.pos,
                 jax.ShapeDtypeStruct((geom.n_slots,), jnp.bool_),
                 av.adapters),
            ),
            n_substeps=1,
        )
    if want("deactivate"):
        add(
            "deactivate", "deactivate",
            lambda: (build_deact_program(), (av.active, _i32())),
        )
    if want("prefill"):
        for b in geom.buckets(cfg):
            add(
                f"prefill[b={b}]", "prefill",
                lambda b=b: (
                    build_prefill_program(
                        av.do_prefill, av.init_caches, geom.max_total
                    ),
                    (*av.state(), av.params, _i32(1, b), _i32(),
                     _i32(), _i32(), _i32(), _i32(), _i32(1)),
                ),
                n_substeps=1, scanned=cfg.scan_layers,
            )
    if want("chunk"):
        for b in geom.buckets(cfg):
            add(
                f"chunk[b={b}]", "chunk",
                lambda b=b: (
                    build_chunk_program(av.fwd_chunk),
                    (av.params, av.scratch, _i32(1, b), _i32(),
                     _i32(), _i32(1)),
                ),
                n_substeps=1,
            )
    if want("piggyback_step"):
        # fused chunk+decode piggyback: the pow2 chunk grid crossed
        # with the step horizons — ascending, so the last entry per
        # family is the (max bucket, max K) budget envelope. One
        # chunk leg (unscanned forward_chunk pass) costs the same
        # collective count as one decode substep, hence K+1.
        for b in geom.buckets(cfg):
            for k in geom.horizons():
                add(
                    f"piggyback_step[b={b},K={k}]", "piggyback_step",
                    lambda b=b, k=k: (
                        build_piggyback_program(
                            av.fwd1, av.fwd_chunk, k,
                            geom.temperature, geom.top_k,
                            geom.approx_top_k,
                        ),
                        (av.params, *av.state(), av.slot_keys,
                         av.adapters, av.scratch, _i32(1, b),
                         _i32(), _i32(), _i32(1)),
                    ),
                    n_substeps=k + 1,
                )
    nl = min(geom.n_logprobs, cfg.vocab_size)
    if geom.sampling_surface and want("masked_step"):
        # masked variants: same unrolled chain + the traced sampling
        # vectors and DFA tables, so the per-substep collective count
        # matches the plain family exactly
        for k in geom.horizons():
            add(
                f"masked_step[K={k}]", "masked_step",
                lambda k=k: (
                    build_masked_step_program(av.fwd1, k, nl),
                    (av.params, *av.state(), *av.surface_tail()),
                ),
                n_substeps=k,
            )
    if geom.sampling_surface and want("masked_piggyback_step"):
        for b in geom.buckets(cfg):
            for k in geom.horizons():
                add(
                    f"masked_piggyback_step[b={b},K={k}]",
                    "masked_piggyback_step",
                    lambda b=b, k=k: (
                        build_masked_piggyback_program(
                            av.fwd1, av.fwd_chunk, k, nl
                        ),
                        (av.params, *av.state(), *av.surface_tail(),
                         av.scratch, _i32(1, b), _i32(), _i32(),
                         _i32(1)),
                    ),
                    n_substeps=k + 1,
                )
    if geom.sampling_surface and want("gstate_set"):
        add(
            "gstate_set", "gstate_set",
            lambda: (
                build_gstate_set_program(),
                (av.gstate, _i32(), _i32()),
            ),
        )
    if want("insert"):
        add(
            "insert", "insert",
            lambda: (
                build_insert_program(),
                (*av.state(), av.scratch, av.row_logits, _i32(),
                 _i32(), _i32(), _i32()),
            ),
        )
    if want("hit_insert"):
        add(
            "hit_insert", "hit_insert",
            lambda: (
                build_hit_insert_program(),
                (*av.state(), av.region, av.row_logits, _i32(),
                 _i32(), _i32(), _i32(), _i32()),
            ),
        )
    if want("seg_fetch"):
        add(
            "seg_fetch", "seg_fetch",
            lambda: (build_seg_fetch_program(), (av.region, _i32())),
        )
    if want("seg_store"):
        add(
            "seg_store", "seg_store",
            lambda: (
                build_seg_store_program(),
                (av.region, av.caches, _i32(), _i32()),
            ),
        )
    if want("seg_import"):
        add(
            "seg_import", "seg_import",
            lambda: (
                build_seg_import_program(),
                (av.region, av.scratch, _i32()),
            ),
        )
    if want("logit_row"):
        add(
            "logit_row", "logit_row",
            lambda: (build_logit_row_program(), (av.logits, _i32())),
        )
    if geom.paged and want("paged_step"):
        for k in geom.horizons():
            add(
                f"paged_step[K={k}]", "paged_step",
                lambda k=k: (
                    build_step_program(
                        make_paged_fwd1(av.fwd1), k, geom.temperature,
                        geom.top_k, geom.approx_top_k,
                    ),
                    (av.params, *av.paged_state(), av.slot_keys,
                     av.adapters),
                ),
                n_substeps=k,
            )
    if geom.paged and want("paged_piggyback_step"):
        for b in geom.buckets(cfg):
            for k in geom.horizons():
                add(
                    f"paged_piggyback_step[b={b},K={k}]",
                    "paged_piggyback_step",
                    lambda b=b, k=k: (
                        build_piggyback_program(
                            make_paged_fwd1(av.fwd1), av.fwd_chunk,
                            k, geom.temperature, geom.top_k,
                            geom.approx_top_k,
                        ),
                        (av.params, *av.paged_state(), av.slot_keys,
                         av.adapters, av.scratch, _i32(1, b),
                         _i32(), _i32(), _i32(1)),
                    ),
                    n_substeps=k + 1,
                )
    if geom.paged and geom.sampling_surface and want("paged_masked_step"):
        for k in geom.horizons():
            add(
                f"paged_masked_step[K={k}]", "paged_masked_step",
                lambda k=k: (
                    build_masked_step_program(
                        make_paged_fwd1(av.fwd1), k, nl
                    ),
                    (av.params, *av.paged_state(),
                     *av.surface_tail()),
                ),
                n_substeps=k,
            )
    if (geom.paged and geom.sampling_surface
            and want("paged_masked_piggyback_step")):
        for b in geom.buckets(cfg):
            for k in geom.horizons():
                add(
                    f"paged_masked_piggyback_step[b={b},K={k}]",
                    "paged_masked_piggyback_step",
                    lambda b=b, k=k: (
                        build_masked_piggyback_program(
                            make_paged_fwd1(av.fwd1), av.fwd_chunk,
                            k, nl,
                        ),
                        (av.params, *av.paged_state(),
                         *av.surface_tail(), av.scratch, _i32(1, b),
                         _i32(), _i32(), _i32(1)),
                    ),
                    n_substeps=k + 1,
                )
    if geom.paged and want("paged_replay"):
        add(
            "paged_replay", "paged_replay",
            lambda: (
                build_replay_program(make_paged_fwd1(av.fwd1)),
                (av.params, av.paged_caches, av.logits,
                 _i32(geom.n_slots), av.pos,
                 jax.ShapeDtypeStruct((geom.n_slots,), jnp.bool_),
                 av.adapters),
            ),
            n_substeps=1,
        )
    if geom.paged and want("paged_prefill"):
        for b in geom.buckets(cfg):
            add(
                f"paged_prefill[b={b}]", "paged_prefill",
                lambda b=b: (
                    build_paged_prefill_program(
                        av.do_prefill, av.init_caches, geom.max_total
                    ),
                    (*av.paged_state(), av.params, _i32(1, b),
                     _i32(), _i32(), _i32(), _i32(), _i32(),
                     _i32(1)),
                ),
                n_substeps=1, scanned=cfg.scan_layers,
            )
    if geom.paged and want("paged_insert"):
        add(
            "paged_insert", "paged_insert",
            lambda: (
                build_paged_insert_program(),
                (*av.paged_state(), av.scratch, av.row_logits,
                 _i32(), _i32(), _i32(), _i32()),
            ),
        )
    if geom.paged and want("paged_seg_fetch"):
        add(
            "paged_seg_fetch", "paged_seg_fetch",
            lambda: (
                build_paged_seg_fetch_program(),
                (av.blocks, av.seg_row),
            ),
        )
    if geom.paged and want("paged_seg_import"):
        add(
            "paged_seg_import", "paged_seg_import",
            lambda: (
                build_paged_seg_import_program(),
                (av.blocks, av.seg_row, av.scratch),
            ),
        )
    if geom.paged and want("block_copy"):
        add(
            "block_copy", "block_copy",
            lambda: (
                build_block_copy_program(),
                (av.blocks, _i32(), _i32()),
            ),
        )
    if want("batch_prefill"):
        for b in geom.buckets(cfg):
            for nb in geom.group_sizes():
                add(
                    f"batch_prefill[b={b},n={nb}]", "batch_prefill",
                    lambda b=b, nb=nb: (
                        build_batch_prefill_program(
                            av.do_prefill, av.init_caches,
                            geom.max_total, nb,
                        ),
                        (*av.state(), av.params, _i32(nb, b),
                         _i32(nb), _i32(nb), _i32(nb), _i32(nb),
                         _i32(nb), _i32(nb)),
                    ),
                    n_substeps=1,
                )
    if want("batch_hit"):
        for b in geom.buckets(cfg):
            for nb in geom.group_sizes():
                add(
                    f"batch_hit[b={b},n={nb}]", "batch_hit",
                    lambda b=b, nb=nb: (
                        build_batch_hit_program(av.fwd_chunk, nb),
                        (*av.state(), av.params, av.region, _i32(nb),
                         _i32(nb, b), _i32(), _i32(nb), _i32(nb),
                         _i32(nb), _i32(nb), _i32(nb), _i32(nb)),
                    ),
                    n_substeps=1,
                )
    return out


#: forward-pass families — the ones whose TP variants carry the
#: collective contract (the copy/slice programs contain no model code)
_FORWARD_FAMILIES = {"step", "replay", "prefill", "chunk",
                     "piggyback_step", "masked_step",
                     "masked_piggyback_step"}


def enumerate_programs(
    cfg: TransformerConfig, geom: ServingGeometry
) -> list[ProgramSpec]:
    """Every program family the engine can emit under ``(cfg, geom)``:
    the full single-chip surface, plus TP-sharded variants of the
    forward families when ``geom.tp > 1`` (requires ``tp`` visible
    devices — the engine has the same requirement), plus the
    LoRA-bank fused-step variant when ``geom.n_adapters > 0``."""
    specs = _specs_for(_FamilyAvals(cfg, geom), geom)
    if geom.tp > 1:
        if jax.device_count() < geom.tp:
            raise ValueError(
                f"tp={geom.tp} needs >= {geom.tp} devices "
                f"(have {jax.device_count()})"
            )
        # mirrors the engine: the Pallas decode kernel cannot be
        # GSPMD-partitioned, so TP serving always runs the dense path
        cfg_tp = dataclasses.replace(cfg, decode_kernel=False)
        mesh = model_parallel_mesh(geom.tp)
        fams = set(_FORWARD_FAMILIES)
        if geom.paged:
            # TP paged serving exists (paged-parity TP tests), so its
            # forward variants carry the same collective contract
            fams |= {"paged_step", "paged_replay", "paged_prefill",
                     "paged_piggyback_step", "paged_masked_step",
                     "paged_masked_piggyback_step"}
        specs += _specs_for(
            _FamilyAvals(cfg_tp, geom, tp_mesh=mesh), geom,
            tp=True, suffix=f"[tp={geom.tp}]",
            families=fams,
        )
    if geom.n_adapters > 0:
        # the bank rides inside params; the adapter-index vector is
        # already a traced argument of every step program, so the only
        # new family is the bank-carrying step itself
        cfg_lora = dataclasses.replace(cfg, decode_kernel=False)
        specs += _specs_for(
            _FamilyAvals(cfg_lora, geom, lora=True), geom,
            suffix="[lora]", families={"step"},
        )
    return specs


def expected_surface(
    cfg: TransformerConfig, geom: ServingGeometry
) -> dict[str, object]:
    """The compile-surface contract, in ``CompileCountGuard``'s
    vocabulary: allowed jit-cache keys per keyed family and the
    O(log max_len) count bound. The audit's static surface check
    asserts the registry's enumeration equals this; the live-engine
    test asserts an engine's observed keys are a subset of it."""
    buckets = set(geom.buckets(cfg))
    groups = set(geom.group_sizes())
    mb = max(buckets)
    import math

    singletons = {
        "replay", "deactivate", "insert", "hit_insert",
        "seg_fetch", "seg_store", "seg_import", "logit_row",
    }
    if geom.paged:
        singletons |= {
            "paged_replay", "paged_insert", "paged_seg_fetch",
            "paged_seg_import", "block_copy",
        }
    if geom.sampling_surface:
        singletons |= {"gstate_set"}
    pb_grid = {(b, k) for b in buckets for k in geom.horizons()}
    return {
        "step": set(geom.horizons()),
        "prefill": buckets,
        "chunk": buckets,
        # paged families: empty when the geometry is slab-only, so the
        # surface diff below stays key-stable across modes
        "paged_step": set(geom.horizons()) if geom.paged else set(),
        "paged_prefill": buckets if geom.paged else set(),
        "batch_prefill": {(b, n) for b in buckets for n in groups},
        "batch_hit": {(b, n) for b in buckets for n in groups},
        # piggyback: the pow2 chunk grid crossed with the step
        # horizons — the fused-program surface is bounded by
        # O(log max_bucket) x |{1, K}|
        "piggyback_step": set(pb_grid),
        "paged_piggyback_step": (
            set(pb_grid) if geom.paged else set()
        ),
        # masked (sampling-surface) variants share the plain families'
        # key grids — a surface engine compiles masked programs
        # INSTEAD of the plain ones per dispatch, so the total live
        # surface stays within the same O(log) envelope
        "masked_step": (
            set(geom.horizons()) if geom.sampling_surface else set()
        ),
        "paged_masked_step": (
            set(geom.horizons())
            if geom.sampling_surface and geom.paged else set()
        ),
        "masked_piggyback_step": (
            set(pb_grid) if geom.sampling_surface else set()
        ),
        "paged_masked_piggyback_step": (
            set(pb_grid)
            if geom.sampling_surface and geom.paged else set()
        ),
        "singletons": singletons,
        "log_bound": int(math.log2(mb)) + 1,
    }


def live_engine_families(engine) -> dict[str, set]:
    """A live engine's OBSERVED jit-cache keys, in
    :func:`expected_surface` vocabulary — the bridge the registry-vs-
    engine test diffs: every observed key must be inside the surface
    the registry enumerates for the same geometry."""
    paged = bool(getattr(engine, "_paged", False))
    singles = set()
    for name, fn in (
        ("paged_replay" if paged else "replay", engine._replay_fn),
        ("deactivate", engine._deact_fn),
        ("insert", engine._insert_fn),
        ("hit_insert", engine._hit_insert_fn),
        ("seg_fetch", engine._seg_fetch_fn),
        ("seg_store", engine._seg_store_fn),
        ("seg_import", engine._seg_import_fn),
        ("logit_row", engine._logit_row_fn),
        ("paged_insert", getattr(engine, "_paged_insert_fn", None)),
        ("paged_seg_fetch",
         getattr(engine, "_paged_seg_fetch_fn", None)),
        ("paged_seg_import",
         getattr(engine, "_paged_seg_import_fn", None)),
        ("block_copy", getattr(engine, "_block_copy_fn", None)),
        ("gstate_set", getattr(engine, "_gstate_set_fn", None)),
    ):
        if fn is not None:
            singles.add(name)
    # a paged engine's step-fn cache holds paged_step programs (same
    # horizon keys, paged fwd1) — report it under the paged family;
    # same for the fused piggyback cache, keyed (bucket, K)
    steps = set(engine._step_fns)
    pb = set(getattr(engine, "_piggyback_fns", {}))
    msteps = set(getattr(engine, "_masked_step_fns", {}) or {})
    mpb = set(getattr(engine, "_masked_piggyback_fns", {}) or {})
    return {
        "step": set() if paged else steps,
        "paged_step": steps if paged else set(),
        "prefill": set(engine._prefill_fns),
        "paged_prefill": set(getattr(engine, "_paged_prefill_fns", {})),
        "chunk": set(engine._chunk_fns),
        "batch_prefill": set(engine._batch_prefill_fns),
        "batch_hit": set(engine._batch_hit_fns),
        "piggyback_step": set() if paged else pb,
        "paged_piggyback_step": pb if paged else set(),
        "masked_step": set() if paged else msteps,
        "paged_masked_step": msteps if paged else set(),
        "masked_piggyback_step": set() if paged else mpb,
        "paged_masked_piggyback_step": mpb if paged else set(),
        "singletons": singles,
    }


def default_audit_config() -> TransformerConfig:
    """The committed audit geometry's model config: small enough that
    the full surface traces + compiles in seconds on CPU, bf16 compute
    so the dtype-promotion lint has teeth, GQA + RoPE so the audited
    forward is the feature-bearing one. ``decode_kernel=False``: the
    auditor budget-COMPILES on CPU. The Pallas TPU kernel can be lowered
    there (``lowering_platforms=("tpu",)``, as
    tests/test_tpu_lowering.py does) but not compiled."""
    return TransformerConfig(
        vocab_size=128,
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        n_layers=2,
        d_ff=128,
        max_len=64,
        rope=True,
        compute_dtype=jnp.bfloat16,
        decode_kernel=False,
    )


def default_audit_geometry() -> ServingGeometry:
    """The committed audit geometry (see ``.graftaudit.json``): every
    family class is populated — adaptive horizon (two step programs),
    three buckets, batched groups to 4, TP=2 forward variants, one
    LoRA step variant, and the block-paged families (paged engines are
    first-class, so their surface is budget-fenced too)."""
    return ServingGeometry(
        n_slots=4,
        max_total=64,
        decode_horizon=2,
        adaptive_horizon=True,
        prefill_max_bucket=32,
        tp=2,
        n_adapters=2,
        lora_rank=4,
        prefix_segments=2,
        paged=True,
        block_size=8,
        sampling_surface=True,
        grammar_states=64,
    )
