"""graftaudit: jaxpr-level static auditor over the program-surface
registry (``analysis.programs``).

Where graftlint stops at the Python AST, this auditor traces every
compiled family the serving engine can emit — as abstract avals, no
devices, no weights, nothing executed — and checks properties of the
*programs themselves*:

1.  **dtype promotion** — the count of bf16→f32 ``convert_element_type``
    upcasts per program is recorded in the reviewed baseline; drift
    (an accidental upcast, a weak-typed Python scalar promoting a bf16
    intermediate) is a finding. Casts to f64 are always findings.
2.  **donation** — every argnum a family declares in
    ``PROGRAM_DONATION`` must be consumable by an output of matching
    shape/dtype ("donation not used" means the cache stopped updating
    in place on TPU).
3.  **collective signature** — the count and kind of collectives in
    each TP program must equal the declared contract
    (``tp_collective_contract``); non-TP programs must be
    collective-free. Drift silently breaks the byte-exact TP parity
    layout.
4.  **host callbacks** — ``pure_callback`` / ``debug_callback`` /
    ``io_callback`` inside a jitted family (a smuggled
    ``jax.debug.print`` syncs the decode loop) is a finding.
5.  **compile surface** — the enumerated registry must equal
    ``expected_surface`` (CompileCountGuard's bounds), statically.
6.  **memory/flop budgets** — per-family envelope programs are
    lowered and compiled on CPU; ``cost_analysis`` flops and
    ``memory_analysis`` temp bytes are baselined in
    ``.graftaudit.json`` and a >10% regression fails; argument/output
    byte totals are pure aval math and must match exactly.

Exit codes mirror ``analysis.lint``: 0 clean, 1 findings (or stale
baseline entries under ``--strict``), 2 trace/compile errors.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import time

import numpy as np

#: collectives + the sharding constraints that pin the TP layout — the
#: vocabulary of the collective-signature contract
COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_reduce", "reduce_scatter", "ppermute",
    "all_to_all", "pmin", "pmax", "sharding_constraint",
})

#: host-callback primitives — any of these inside a jitted serving
#: family stalls the device on the Python runtime
CALLBACK_PRIMS = frozenset({
    "pure_callback", "debug_callback", "io_callback",
    "debug_print",  # what jax.debug.print emits since jax 0.9
})

#: budget tolerance: flops / temp bytes may grow this factor over the
#: reviewed baseline before the audit fails
BUDGET_TOLERANCE = 1.10


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One audit violation: which check, on which program, and why."""

    check: str
    program: str
    message: str

    def render(self) -> str:
        return f"{self.program}: [{self.check}] {self.message}"


# ------------------------------------------------------------------ #
# jaxpr walking                                                      #
# ------------------------------------------------------------------ #


def iter_eqns(jaxpr):
    """All equations of ``jaxpr``, recursing into sub-jaxprs (pjit
    bodies, scan/cond branches, closed_call …)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from iter_eqns(sub)


def count_primitives(jaxpr) -> dict[str, int]:
    out: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        out[name] = out.get(name, 0) + 1
    return out


def convert_dtype_pairs(jaxpr) -> list[tuple[str, str]]:
    """(src, dst) dtype names of every ``convert_element_type``."""
    pairs = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = np.dtype(eqn.invars[0].aval.dtype).name
        dst = np.dtype(eqn.outvars[0].aval.dtype).name
        pairs.append((src, dst))
    return pairs


def _nbytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(
        aval.dtype
    ).itemsize


def _tree_bytes(tree) -> int:
    import jax

    return sum(_nbytes(a) for a in jax.tree.leaves(tree))


# ------------------------------------------------------------------ #
# per-program measurement                                            #
# ------------------------------------------------------------------ #


def measure_spec(spec, *, budgets: bool = False) -> dict:
    """Trace one :class:`~.programs.ProgramSpec` and collect every
    statically derivable property the checks consume. With
    ``budgets=True`` the program is also lowered + compiled (CPU) for
    ``cost_analysis`` flops and ``memory_analysis`` temp bytes."""
    import jax

    fn, args = spec.build()
    traced = jax.jit(fn).trace(*args)
    closed = traced.jaxpr
    prims = count_primitives(closed.jaxpr)
    pairs = convert_dtype_pairs(closed.jaxpr)
    record = {
        "family": spec.family,
        "tp": spec.tp,
        "collectives": {
            k: prims[k] for k in sorted(COLLECTIVE_PRIMS)
            if prims.get(k)
        },
        "callbacks": sorted(k for k in CALLBACK_PRIMS if prims.get(k)),
        "f32_upcasts": sum(
            1 for s, d in pairs
            if d == "float32" and s in ("bfloat16", "float16")
        ),
        "f64_casts": sum(1 for _, d in pairs if d == "float64"),
        "arg_bytes": _tree_bytes(args),
        "out_bytes": sum(_nbytes(a) for a in closed.out_avals),
        "donation_unused": _donation_gaps(spec, args, closed),
        "flops": None,
        "temp_bytes": None,
    }
    if budgets:
        compiled = traced.lower().compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = (ca or {}).get("flops")
        if flops is not None:
            record["flops"] = float(flops)
        ma = compiled.memory_analysis()
        temp = getattr(ma, "temp_size_in_bytes", None)
        if temp is not None:
            record["temp_bytes"] = int(temp)
    return record


def _donation_gaps(spec, args, closed) -> list[str]:
    """Donated-argnum leaves with no matching output aval. Donation is
    pure aval math: XLA can only alias a donated input buffer into an
    output of identical shape+dtype, so an unmatched leaf is exactly
    the "donation is not useful" warning, caught statically."""
    import jax

    budget: dict[tuple, int] = {}
    for a in closed.out_avals:
        k = (tuple(a.shape), np.dtype(a.dtype).name)
        budget[k] = budget.get(k, 0) + 1
    gaps = []
    for i in spec.donate:
        for leaf in jax.tree.leaves(args[i]):
            k = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
            if budget.get(k, 0) > 0:
                budget[k] -= 1
            else:
                gaps.append(
                    f"arg {i} leaf {k[1]}{list(k[0])} has no "
                    f"matching output"
                )
    return gaps


# ------------------------------------------------------------------ #
# checks                                                             #
# ------------------------------------------------------------------ #


def check_dtype(spec, record, base_entry) -> list[AuditFinding]:
    f = []
    if record["f64_casts"]:
        f.append(AuditFinding(
            "dtype", spec.name,
            f"{record['f64_casts']} cast(s) to float64",
        ))
    if base_entry is not None:
        want = base_entry.get("f32_upcasts")
        if want is not None and record["f32_upcasts"] != want:
            f.append(AuditFinding(
                "dtype", spec.name,
                f"f32 upcast count drifted: {record['f32_upcasts']} "
                f"vs baseline {want} (accidental upcast or weak-typed "
                f"scalar leak; re-review and --write-baseline if "
                f"intended)",
            ))
    return f


def check_donation(spec, record) -> list[AuditFinding]:
    return [
        AuditFinding("donation", spec.name, f"donation not used: {g}")
        for g in record["donation_unused"]
    ]


def check_collectives(spec, record) -> list[AuditFinding]:
    got = record["collectives"]
    want = spec.collectives
    if got == want:
        return []
    if not spec.tp:
        return [AuditFinding(
            "collectives", spec.name,
            f"single-chip program contains collectives {got}",
        )]
    return [AuditFinding(
        "collectives", spec.name,
        f"signature {got} != declared contract {want} — drift here "
        f"breaks the byte-exact TP parity layout",
    )]


def check_callbacks(spec, record) -> list[AuditFinding]:
    if not record["callbacks"]:
        return []
    return [AuditFinding(
        "callbacks", spec.name,
        f"host callback(s) inside jitted program: "
        f"{', '.join(record['callbacks'])}",
    )]


def check_budgets(spec, record, base_entry) -> list[AuditFinding]:
    f = []
    if base_entry is None:
        return f
    for key in ("arg_bytes", "out_bytes"):
        want = base_entry.get(key)
        if want is not None and record[key] != want:
            f.append(AuditFinding(
                "budget", spec.name,
                f"{key} changed: {record[key]} vs baseline {want} "
                f"(aval surface moved; re-review and --write-baseline "
                f"if intended)",
            ))
    for key in ("flops", "temp_bytes"):
        want, got = base_entry.get(key), record.get(key)
        if want and got and got > want * BUDGET_TOLERANCE:
            f.append(AuditFinding(
                "budget", spec.name,
                f"{key} regression: {got:.0f} > baseline {want:.0f} "
                f"(+{100 * (got / want - 1):.0f}%, tolerance "
                f"{100 * (BUDGET_TOLERANCE - 1):.0f}%)",
            ))
    return f


def check_surface(cfg, geom, specs) -> list[AuditFinding]:
    """Registry enumeration must equal the compile-surface contract
    (``expected_surface`` — CompileCountGuard's bounds), statically."""
    from deeplearning4j_tpu.analysis.programs import expected_surface

    exp = expected_surface(cfg, geom)
    f = []
    names = [s.name for s in specs]
    if len(set(names)) != len(names):
        dupes = sorted({n for n in names if names.count(n) > 1})
        f.append(AuditFinding(
            "surface", "<registry>", f"duplicate program names {dupes}"
        ))
    base = [
        s for s in specs
        if "[tp=" not in s.name and "[lora]" not in s.name
    ]

    def keyed(pattern):
        out = set()
        for s in base:
            m = re.fullmatch(pattern, s.name)
            if m:
                out.add(tuple(int(g) for g in m.groups()))
        return out

    for fam in ("step", "paged_step", "masked_step",
                "paged_masked_step"):
        got_step = {k for (k,) in keyed(fam + r"\[K=(\d+)\]")}
        if got_step != exp[fam]:
            f.append(AuditFinding(
                "surface", fam,
                f"horizons {sorted(got_step)} != expected "
                f"{sorted(exp[fam])}",
            ))
    for fam in ("prefill", "chunk", "paged_prefill"):
        got = {b for (b,) in keyed(fam + r"\[b=(\d+)\]")}
        if got != exp[fam]:
            f.append(AuditFinding(
                "surface", fam,
                f"buckets {sorted(got)} != expected "
                f"{sorted(exp[fam])}",
            ))
        if len(got) > exp["log_bound"]:
            f.append(AuditFinding(
                "surface", fam,
                f"{len(got)} programs exceed the O(log max_len) "
                f"bound {exp['log_bound']}",
            ))
    for fam in ("batch_prefill", "batch_hit"):
        got = keyed(fam + r"\[b=(\d+),n=(\d+)\]")
        if got != exp[fam]:
            f.append(AuditFinding(
                "surface", fam,
                f"(bucket, group) grid {sorted(got)} != expected "
                f"{sorted(exp[fam])}",
            ))
    for fam in ("piggyback_step", "paged_piggyback_step",
                "masked_piggyback_step",
                "paged_masked_piggyback_step"):
        got = keyed(fam + r"\[b=(\d+),K=(\d+)\]")
        if got != exp[fam]:
            f.append(AuditFinding(
                "surface", fam,
                f"(bucket, K) grid {sorted(got)} != expected "
                f"{sorted(exp[fam])}",
            ))
    singles = {s.name for s in base if s.name in exp["singletons"]}
    missing = exp["singletons"] - singles
    if missing:
        f.append(AuditFinding(
            "surface", "<registry>",
            f"missing singleton families {sorted(missing)}",
        ))
    return f


# ------------------------------------------------------------------ #
# baseline (.graftaudit.json — same reviewed-file machinery as        #
# graftlint's .graftlint.json)                                        #
# ------------------------------------------------------------------ #

#: record keys persisted per program in the baseline
_BASELINE_KEYS = (
    "f32_upcasts", "collectives", "arg_bytes", "out_bytes", "flops",
    "temp_bytes",
)


def default_baseline_path() -> str:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".graftaudit.json")


def load_baseline(path: str) -> dict | None:
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        data = json.load(fh)
    if data.get("version") != 1:
        raise ValueError(
            f"{path}: unsupported baseline version {data.get('version')!r}"
        )
    return data


def baseline_payload(cfg, geom, records: dict[str, dict]) -> dict:
    progs = {}
    for name in sorted(records):
        rec = records[name]
        entry = {
            k: rec[k] for k in _BASELINE_KEYS if rec.get(k) is not None
        }
        # empty collective signatures are still contractual
        entry["collectives"] = rec["collectives"]
        progs[name] = entry
    return {
        "version": 1,
        "cfg": json.loads(cfg.to_json()),
        "geometry": geom.to_json_dict(),
        "programs": progs,
    }


# ------------------------------------------------------------------ #
# driver                                                             #
# ------------------------------------------------------------------ #


def budget_representatives(specs) -> set[str]:
    """One envelope program per (family, variant): enumeration order
    is ascending in K / bucket / group size, so the last member of
    each group is the largest — the family's budget envelope. A flop
    or memory regression in shared forward code moves the envelope;
    compiling every grid point would only re-measure the same code at
    smaller shapes (~50s instead of ~15s on CPU)."""
    last: dict[tuple, str] = {}
    for s in specs:
        variant = (
            "tp" if "[tp=" in s.name
            else "lora" if "[lora]" in s.name else ""
        )
        last[(s.family, variant)] = s.name
    return set(last.values())


def run_audit(cfg, geom, *, baseline: dict | None = None,
              budgets: str = "representative"):
    """Audit the full surface of ``(cfg, geom)``.

    Returns ``(records, findings, stale, errors)`` — per-program
    measurement records, verified findings, baseline entries no
    program claims any more, and trace/compile failures. ``budgets``
    is ``"representative"`` (compile each family's envelope program),
    ``"full"`` (compile everything), or ``"none"`` (trace-only)."""
    from deeplearning4j_tpu.analysis.programs import enumerate_programs

    specs = enumerate_programs(cfg, geom)
    reps = (
        budget_representatives(specs) if budgets == "representative"
        else {s.name for s in specs} if budgets == "full"
        else set()
    )
    base_progs = (baseline or {}).get("programs", {})
    records: dict[str, dict] = {}
    findings: list[AuditFinding] = []
    errors: list[str] = []
    for spec in specs:
        try:
            rec = measure_spec(spec, budgets=spec.name in reps)
        except Exception as e:  # pragma: no cover - defensive
            errors.append(f"{spec.name}: {type(e).__name__}: {e}")
            continue
        records[spec.name] = rec
        entry = base_progs.get(spec.name) if baseline else None
        findings += check_dtype(spec, rec, entry)
        findings += check_donation(spec, rec)
        findings += check_collectives(spec, rec)
        findings += check_callbacks(spec, rec)
        findings += check_budgets(spec, rec, entry)
        if baseline is not None and entry is None:
            findings.append(AuditFinding(
                "baseline", spec.name,
                "program not in baseline (accept with "
                "--write-baseline)",
            ))
    findings += check_surface(cfg, geom, specs)
    stale = sorted(set(base_progs) - set(records)) if baseline else []
    return records, findings, stale, errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftaudit",
        description=(
            "statically audit every compiled program family the "
            "serving engine can emit (no devices, nothing executed)"
        ),
    )
    ap.add_argument(
        "--baseline", default=None,
        help="baseline path (default: <repo>/.graftaudit.json)",
    )
    ap.add_argument(
        "--no-baseline", action="store_true",
        help="skip baseline comparison entirely",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="(re)write the baseline from this run and exit 0",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="also fail on stale baseline entries",
    )
    ap.add_argument(
        "--full-budgets", action="store_true",
        help="compile EVERY program for budgets, not just each "
             "family's envelope",
    )
    ap.add_argument(
        "--json-out", default=None,
        help="write the full report (records + findings) as JSON",
    )
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.analysis.programs import (
        default_audit_config,
        default_audit_geometry,
    )

    cfg = default_audit_config()
    geom = default_audit_geometry()
    tp_skipped = False
    if geom.tp > 1 and jax.device_count() < geom.tp:
        print(
            f"graftaudit: note: tp={geom.tp} surface skipped "
            f"({jax.device_count()} device(s) visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 to audit it)"
        )
        geom = dataclasses.replace(geom, tp=1)
        tp_skipped = True

    bl_path = args.baseline or default_baseline_path()
    baseline = None if args.no_baseline else load_baseline(bl_path)
    if baseline is None and not (args.no_baseline
                                 or args.write_baseline):
        print(f"graftaudit: no baseline at {bl_path} "
              f"(--write-baseline to create it)")

    t0 = time.perf_counter()
    records, findings, stale, errors = run_audit(
        cfg, geom, baseline=baseline,
        budgets="full" if args.full_budgets else "representative",
    )
    wall = time.perf_counter() - t0
    if tp_skipped:
        # baseline TP entries are not stale — this run couldn't see them
        stale = [n for n in stale if "[tp=" not in n]

    if args.write_baseline:
        payload = baseline_payload(cfg, geom, records)
        with open(bl_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(
            f"graftaudit: wrote {len(records)} program budget(s) to "
            f"{bl_path} — review and commit it"
        )
        return 0

    for f in findings:
        print(f.render())
    for name in stale:
        print(f"{name}: [baseline] stale entry (no such program; "
              f"--write-baseline to drop)")
    if args.json_out:
        report = {
            "version": 1,
            "geometry": geom.to_json_dict(),
            "wall_s": round(wall, 2),
            "programs": records,
            "findings": [dataclasses.asdict(f) for f in findings],
            "stale": stale,
            "errors": errors,
        }
        with open(args.json_out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
    status = (
        2 if errors
        else 1 if findings or (args.strict and stale)
        else 0
    )
    print(
        f"graftaudit: {len(records)} programs audited in {wall:.1f}s — "
        f"{len(findings)} finding(s), {len(stale)} stale, "
        f"{len(errors)} error(s)"
    )
    for e in errors:
        print(f"error: {e}")
    return status


if __name__ == "__main__":
    raise SystemExit(main())
