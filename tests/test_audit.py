"""graftaudit suite: the program-surface registry + jaxpr auditor.

Mirrors ``test_analysis.py``'s structure: each audit check is fed a
seeded violation of the exact bug class it exists for (an injected
bf16->f32 upcast, a donation with no consuming output, a tampered
collective contract, a smuggled host callback, a blown flop/memory
budget, a hole in the compile surface) and must flag it while staying
quiet on the blessed shape next to it. Plus the two load-bearing
meta-tests: the shipped registry audits clean against the committed
``.graftaudit.json``, and a live engine's observed jit-cache keys all
fall inside the surface the registry enumerates for the same geometry.
The interprocedural host-sync lint (call-graph propagation) is covered
here too, next to the auditor it upgraded alongside.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis.audit import (
    AuditFinding,
    check_budgets,
    check_callbacks,
    check_collectives,
    check_donation,
    check_dtype,
    check_surface,
    budget_representatives,
    default_baseline_path,
    load_baseline,
    main as audit_main,
    measure_spec,
    run_audit,
)
from deeplearning4j_tpu.analysis.core import ModuleInfo
from deeplearning4j_tpu.analysis.programs import (
    ProgramSpec,
    ServingGeometry,
    default_audit_config,
    default_audit_geometry,
    enumerate_programs,
    expected_surface,
    live_engine_families,
)
from deeplearning4j_tpu.analysis.rules import run_rules
from deeplearning4j_tpu.models.transformer import TransformerConfig


def _spec(name, fn, args, donate=(), tp=False, collectives=None):
    """A minimal hand-rolled ProgramSpec for single-check tests."""
    return ProgramSpec(
        name=name, family="synthetic", donate=tuple(donate), tp=tp,
        collectives=dict(collectives or {}), build=lambda: (fn, args),
    )


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# -- check: dtype promotion -----------------------------------------------


def test_dtype_counts_injected_f32_upcast():
    def leaky(p):
        # the seeded bug: a bf16 intermediate silently promoted to f32
        return (p.astype(jnp.float32) * 2.0).astype(jnp.bfloat16)

    spec = _spec("leaky", leaky, (_bf16(8),))
    rec = measure_spec(spec)
    assert rec["f32_upcasts"] == 1
    # drift vs the reviewed baseline is the finding...
    fs = check_dtype(spec, rec, {"f32_upcasts": 0})
    assert [f.check for f in fs] == ["dtype"]
    assert "drifted" in fs[0].message
    # ...a matching baseline (the reviewed upcast) is clean
    assert check_dtype(spec, rec, {"f32_upcasts": 1}) == []


def test_dtype_flags_f64_unconditionally():
    spec = _spec("wide", lambda p: p, (_f32(4),))
    rec = dict(measure_spec(spec), f64_casts=1)
    fs = check_dtype(spec, rec, None)  # no baseline needed
    assert [f.check for f in fs] == ["dtype"]
    assert "float64" in fs[0].message


def test_dtype_pure_bf16_program_is_clean():
    spec = _spec("pure", lambda p: p * jnp.bfloat16(2), (_bf16(8),))
    rec = measure_spec(spec)
    assert rec["f32_upcasts"] == 0
    assert check_dtype(spec, rec, {"f32_upcasts": 0}) == []


# -- check: donation ------------------------------------------------------


def test_donation_gap_when_output_cannot_consume_arg():
    # the seeded bug: a cache arg declared donated, but the program
    # stopped returning the updated cache — aliasing silently dies
    spec = _spec("drop", lambda c: c.sum(), (_f32(4, 4),), donate=(0,))
    rec = measure_spec(spec)
    assert rec["donation_unused"]
    fs = check_donation(spec, rec)
    assert [f.check for f in fs] == ["donation"]
    assert "donation not used" in fs[0].message


def test_donation_matching_output_is_clean():
    spec = _spec("ok", lambda c: c + 1, (_f32(4, 4),), donate=(0,))
    rec = measure_spec(spec)
    assert rec["donation_unused"] == []
    assert check_donation(spec, rec) == []


def test_donation_matches_pytree_leaves_by_shape_and_dtype():
    caches = {"k": _f32(2, 8), "v": _f32(2, 8)}

    def update(c, x):
        return {"k": c["k"] + x, "v": c["v"] * x}, x.sum()

    spec = _spec("tree", update, (caches, _f32(2, 8)), donate=(0,))
    rec = measure_spec(spec)
    assert rec["donation_unused"] == []


# -- check: collective signature ------------------------------------------


@pytest.fixture(scope="module")
def tp_replay_record():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8 on CPU)")
    geom = dataclasses.replace(
        default_audit_geometry(), tp=2, n_adapters=0
    )
    specs = enumerate_programs(default_audit_config(), geom)
    (spec,) = [s for s in specs if s.name == "replay[tp=2]"]
    return spec, measure_spec(spec)


def test_collectives_match_declared_contract(tp_replay_record):
    spec, rec = tp_replay_record
    assert rec["collectives"]  # the TP program really has collectives
    assert check_collectives(spec, rec) == []


def test_collectives_flag_contract_drift(tp_replay_record):
    spec, rec = tp_replay_record
    tampered = dataclasses.replace(
        spec, collectives={"sharding_constraint": 1}
    )
    fs = check_collectives(tampered, rec)
    assert [f.check for f in fs] == ["collectives"]
    assert "TP parity" in fs[0].message


def test_collectives_flag_stray_collective_in_single_chip(
        tp_replay_record):
    # the seeded bug: a collective leaking into a single-chip family
    spec, rec = tp_replay_record
    stray = dataclasses.replace(spec, tp=False, collectives={})
    fs = check_collectives(stray, rec)
    assert [f.check for f in fs] == ["collectives"]
    assert "single-chip" in fs[0].message


def test_collectives_single_chip_clean_program():
    spec = _spec("plain", lambda p: p + 1, (_f32(4),))
    rec = measure_spec(spec)
    assert rec["collectives"] == {}
    assert check_collectives(spec, rec) == []


# -- check: host callbacks ------------------------------------------------


def test_callbacks_flag_smuggled_debug_print():
    def chatty(p):
        jax.debug.print("p0={}", p[0])  # the seeded bug
        return p + 1

    spec = _spec("chatty", chatty, (_f32(4),))
    rec = measure_spec(spec)
    assert "debug_print" in rec["callbacks"]  # jax 0.9's primitive
    fs = check_callbacks(spec, rec)
    assert [f.check for f in fs] == ["callbacks"]


def test_callbacks_flag_smuggled_pure_callback():
    def smuggler(p):
        host = jax.pure_callback(
            lambda a: np.sin(a), jax.ShapeDtypeStruct((4,), np.float32),
            p,
        )
        return p + host

    spec = _spec("smuggler", smuggler, (_f32(4),))
    rec = measure_spec(spec)
    assert "pure_callback" in rec["callbacks"]
    assert check_callbacks(spec, rec)


def test_callbacks_clean_program():
    spec = _spec("quiet", lambda p: p + 1, (_f32(4),))
    assert check_callbacks(spec, measure_spec(spec)) == []


# -- check: memory/flop budgets -------------------------------------------


def test_budget_measurement_populates_flops_and_temp():
    spec = _spec("mm", lambda a, b: a @ b, (_f32(16, 16), _f32(16, 16)))
    rec = measure_spec(spec, budgets=True)
    assert rec["flops"] and rec["flops"] > 0
    assert rec["temp_bytes"] is not None
    assert rec["arg_bytes"] == 2 * 16 * 16 * 4
    assert rec["out_bytes"] == 16 * 16 * 4


def test_budget_flags_blown_flop_and_temp_budget():
    spec = _spec("hog", lambda p: p, (_f32(4),))
    rec = {"arg_bytes": 100, "out_bytes": 50, "flops": 1000.0,
           "temp_bytes": 4096}
    base = {"arg_bytes": 100, "out_bytes": 50, "flops": 500.0,
            "temp_bytes": 2048}
    fs = check_budgets(spec, rec, base)
    assert sorted(f.check for f in fs) == ["budget", "budget"]
    assert any("flops regression" in f.message for f in fs)
    assert any("temp_bytes regression" in f.message for f in fs)


def test_budget_within_tolerance_is_clean():
    spec = _spec("ok", lambda p: p, (_f32(4),))
    rec = {"arg_bytes": 100, "out_bytes": 50, "flops": 1040.0,
           "temp_bytes": 2048}
    base = {"arg_bytes": 100, "out_bytes": 50, "flops": 1000.0,
            "temp_bytes": 2048}
    assert check_budgets(spec, rec, base) == []


def test_budget_flags_aval_surface_move():
    spec = _spec("grew", lambda p: p, (_f32(4),))
    rec = {"arg_bytes": 128, "out_bytes": 50, "flops": None,
           "temp_bytes": None}
    base = {"arg_bytes": 100, "out_bytes": 50}
    fs = check_budgets(spec, rec, base)
    assert [f.check for f in fs] == ["budget"]
    assert "arg_bytes changed" in fs[0].message


def test_budget_representatives_pick_family_envelopes():
    geom = dataclasses.replace(
        default_audit_geometry(), tp=1, n_adapters=0
    )
    specs = enumerate_programs(default_audit_config(), geom)
    reps = budget_representatives(specs)
    # one per family; the keyed families contribute their LARGEST member
    assert "step[K=2]" in reps and "step[K=1]" not in reps
    assert "prefill[b=32]" in reps and "prefill[b=8]" not in reps
    assert "batch_prefill[b=32,n=4]" in reps
    assert "replay" in reps  # singletons are their own envelope


# -- check: compile surface -----------------------------------------------


def test_surface_clean_on_full_enumeration():
    cfg = default_audit_config()
    geom = ServingGeometry()
    specs = enumerate_programs(cfg, geom)
    assert check_surface(cfg, geom, specs) == []


def test_surface_flags_missing_bucket_and_singleton():
    cfg = default_audit_config()
    geom = ServingGeometry()
    specs = enumerate_programs(cfg, geom)
    holey = [s for s in specs
             if s.name not in ("prefill[b=16]", "seg_store")]
    fs = check_surface(cfg, geom, holey)
    assert any(f.program == "prefill" and "buckets" in f.message
               for f in fs)
    assert any("seg_store" in f.message for f in fs)


def test_surface_flags_duplicate_and_off_grid_programs():
    cfg = default_audit_config()
    geom = ServingGeometry()
    specs = enumerate_programs(cfg, geom)
    fs = check_surface(cfg, geom, specs + [specs[0]])
    assert any("duplicate" in f.message for f in fs)
    # a request-shaped key off the pow2 grid (the retrace bug class
    # CompileCountGuard catches at runtime, caught statically here)
    rogue = dataclasses.replace(specs[0], name="prefill[b=13]")
    fs = check_surface(cfg, geom, specs + [rogue])
    assert any(f.program == "prefill" for f in fs)


# -- the committed baseline + repo meta-test ------------------------------


def test_repo_audits_clean_against_committed_baseline():
    """Load-bearing: the shipped registry, audited against the
    committed ``.graftaudit.json``, has zero findings (CI runs the
    same check via ``python -m deeplearning4j_tpu audit --strict``).
    Trace-only here: the budget compiles have their own test and CI
    leg."""
    cfg = default_audit_config()
    geom = default_audit_geometry()
    tp_skipped = False
    if jax.device_count() < geom.tp:  # pragma: no cover - env guard
        geom = dataclasses.replace(geom, tp=1)
        tp_skipped = True
    baseline = load_baseline(default_baseline_path())
    assert baseline is not None, "commit .graftaudit.json"
    records, findings, stale, errors = run_audit(
        cfg, geom, baseline=baseline, budgets="none"
    )
    if tp_skipped:  # pragma: no cover - env guard
        stale = [n for n in stale if "[tp=" not in n]
    assert errors == []
    assert [f.render() for f in findings] == []
    assert stale == []
    assert len(records) == len(baseline["programs"]) or tp_skipped


def test_registry_surface_matches_committed_geometry():
    """The committed baseline's cfg/geometry blocks reproduce the
    committed program list exactly — renaming a family or moving the
    grid without --write-baseline must show up as a diff here."""
    baseline = load_baseline(default_baseline_path())
    cfg = TransformerConfig.from_json(json.dumps(baseline["cfg"]))
    geom = ServingGeometry(**baseline["geometry"])
    if jax.device_count() < geom.tp:  # pragma: no cover - env guard
        pytest.skip("needs the TP surface (conftest forces 8 devices)")
    names = {s.name for s in enumerate_programs(cfg, geom)}
    assert names == set(baseline["programs"])


# -- registry vs live engine ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_serving():
    from deeplearning4j_tpu.models.transformer import init_transformer

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_len=32,
    )
    return cfg, init_transformer(jax.random.key(0), cfg)


def test_live_engine_families_inside_registry_surface(tiny_serving):
    """The acceptance diff: every jit-cache key a LIVE engine compiles
    is enumerated by the registry for the same geometry — the auditor
    really audits the programs the engine runs, not a lookalike."""
    from deeplearning4j_tpu.analysis.sanitizers import CompileCountGuard
    from deeplearning4j_tpu.serving import Request, ServingEngine

    cfg, params = tiny_serving
    eng = ServingEngine(
        cfg, params, n_slots=2, temperature=0.0, decode_horizon=2,
        adaptive_horizon=True, prefill_max_bucket=16,
    )
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.scheduler.submit(Request(
            id=f"r{i}",
            prompt=rng.integers(1, 60, (3 + 3 * i,)).astype(np.int32),
            max_new=4,
        ))
    results = eng.run()
    assert len(results) == 4
    CompileCountGuard(eng).assert_ok()

    geom = ServingGeometry(
        n_slots=2, max_total=cfg.max_len, decode_horizon=2,
        adaptive_horizon=True, prefill_max_bucket=16,
    )
    exp = expected_surface(cfg, geom)
    got = live_engine_families(eng)
    assert got["step"] <= exp["step"]
    assert got["prefill"] <= exp["prefill"]
    assert got["chunk"] <= exp["chunk"]
    assert got["batch_prefill"] <= exp["batch_prefill"]
    assert got["batch_hit"] <= exp["batch_hit"]
    assert got["singletons"] <= exp["singletons"]
    # and the registry enumerates a spec for every observed key
    names = {s.name for s in enumerate_programs(cfg, geom)}
    for k in got["step"]:
        assert f"step[K={k}]" in names
    for b in got["prefill"]:
        assert f"prefill[b={b}]" in names
    for b, n in got["batch_prefill"]:
        assert f"batch_prefill[b={b},n={n}]" in names
    assert got["singletons"] <= {
        s.name for s in enumerate_programs(cfg, geom)
    }


# -- audit CLI exit codes -------------------------------------------------


def _tiny_audit_surface(monkeypatch):
    """Shrink the CLI's default surface to a 13-program grid that
    traces in well under a second, and skip the budget compiles (the
    budget machinery has its own tests above)."""
    from deeplearning4j_tpu.analysis import audit as audit_mod
    from deeplearning4j_tpu.analysis import programs as programs_mod

    monkeypatch.setattr(
        programs_mod, "default_audit_config",
        lambda: TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2,
            n_layers=1, d_ff=64, max_len=16,
            compute_dtype=jnp.bfloat16, decode_kernel=False,
        ),
    )
    monkeypatch.setattr(
        programs_mod, "default_audit_geometry",
        lambda: ServingGeometry(
            n_slots=2, max_total=16, decode_horizon=1,
            adaptive_horizon=False, prefill_max_bucket=8, tp=1,
            n_adapters=0, prefix_segments=1,
        ),
    )
    monkeypatch.setattr(
        audit_mod, "budget_representatives", lambda specs: set()
    )


def test_audit_cli_exit_codes(tmp_path, monkeypatch):
    _tiny_audit_surface(monkeypatch)
    bl = tmp_path / ".graftaudit.json"
    report = tmp_path / "report.json"
    assert audit_main(["--baseline", str(bl), "--write-baseline"]) == 0
    assert audit_main(["--baseline", str(bl), "--strict",
                       "--json-out", str(report)]) == 0
    out = json.loads(report.read_text())
    assert out["findings"] == [] and out["programs"]

    data = json.loads(bl.read_text())
    assert data["version"] == 1
    # a program missing from the baseline is a finding outright
    dropped = dict(data, programs=dict(data["programs"]))
    del dropped["programs"]["logit_row"]
    bl.write_text(json.dumps(dropped))
    assert audit_main(["--baseline", str(bl)]) == 1
    # a stale entry only fails under --strict (mirrors graftlint)
    ghost = dict(data, programs=dict(data["programs"]))
    ghost["programs"]["ghost[b=99]"] = {"collectives": {}}
    bl.write_text(json.dumps(ghost))
    assert audit_main(["--baseline", str(bl)]) == 0
    assert audit_main(["--baseline", str(bl), "--strict"]) == 1
    assert audit_main(["--no-baseline"]) == 0


def test_audit_cli_rejects_unknown_baseline_version(tmp_path,
                                                    monkeypatch):
    _tiny_audit_surface(monkeypatch)
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 99, "programs": {}}))
    with pytest.raises(ValueError, match="unsupported baseline"):
        audit_main(["--baseline", str(bl)])


# -- interprocedural host-sync lint ---------------------------------------


def _findings(src, rules=None):
    return run_rules(ModuleInfo("synthetic.py", src, "synthetic.py"),
                     rules=rules)


def test_host_sync_chain_through_helper():
    src = '''
import numpy as np

def helper(x):
    return np.asarray(x)

# lint: hot-path
def dispatch(x):
    return helper(x)
'''
    fs = _findings(src, ["host-sync"])
    assert [f.qualname for f in fs] == ["dispatch"]
    assert "'helper'" in fs[0].message and "syncs" in fs[0].message


def test_host_sync_transitive_chain_names_the_path():
    src = '''
import numpy as np

def deep(x):
    return np.asarray(x)

def middle(x):
    return deep(x)

# lint: hot-path
def hot(x):
    return middle(x)
'''
    fs = _findings(src, ["host-sync"])
    assert [f.qualname for f in fs] == ["hot"]
    assert "'middle'" in fs[0].message and "deep" in fs[0].message


def test_host_sync_sync_ok_at_source_kills_the_chain():
    src = '''
import numpy as np

def helper(x):
    return np.asarray(x)  # lint: sync-ok the designated readback

# lint: hot-path
def dispatch(x):
    return helper(x)
'''
    assert _findings(src, ["host-sync"]) == []


def test_host_sync_sync_ok_at_call_site_suppresses():
    src = '''
import numpy as np

def helper(x):
    return np.asarray(x)

# lint: hot-path
def dispatch(x):
    return helper(x)  # lint: sync-ok drained at horizon boundary
'''
    assert _findings(src, ["host-sync"]) == []


def test_host_sync_hot_callee_not_reflagged_through_caller():
    # the callee's own sync site is the one finding; its hot-path
    # callers are not re-flagged (annotating the source must not
    # require annotating every transitive caller)
    src = '''
import numpy as np

# lint: hot-path
def inner(x):
    return np.asarray(x)

# lint: hot-path
def outer(x):
    return inner(x)
'''
    fs = _findings(src, ["host-sync"])
    assert [f.qualname for f in fs] == ["inner"]


def test_host_sync_resolves_self_method_calls():
    src = '''
import numpy as np

class Engine:
    def _readback(self, x):
        return np.asarray(x)

    # lint: hot-path
    def dispatch(self, x):
        return self._readback(x)
'''
    fs = _findings(src, ["host-sync"])
    assert [f.qualname for f in fs] == ["Engine.dispatch"]
    assert "Engine._readback" in fs[0].message


def test_host_sync_cold_caller_of_syncing_helper_is_clean():
    src = '''
import numpy as np

def helper(x):
    return np.asarray(x)

def cold(x):
    return helper(x)
'''
    assert _findings(src, ["host-sync"]) == []


# -- run_audit seeded end-to-end ------------------------------------------


def test_run_audit_reports_new_program_against_baseline():
    """A family added without --write-baseline is itself a finding:
    the compile surface cannot grow silently."""
    cfg = default_audit_config()
    geom = ServingGeometry()
    specs = enumerate_programs(cfg, geom)
    baseline = {"version": 1, "programs": {}}
    records, findings, stale, errors = run_audit(
        cfg, geom, baseline=baseline, budgets="none"
    )
    assert errors == []
    assert len(records) == len(specs)
    missing = [f for f in findings if f.check == "baseline"]
    assert len(missing) == len(specs)
    assert all("not in baseline" in f.message for f in missing)


def test_finding_render_shape():
    f = AuditFinding("dtype", "step[K=2]", "boom")
    assert f.render() == "step[K=2]: [dtype] boom"
