"""What PR 31 adds to the benchmark: the cost functions, the two readers on
a hand-made trace, the configuration file against the catalog row it was
copied from, the traffic mix, the manifest's new entries, and a rehearsal
of the new cell. By hand (``python3 -m pytest benchmark/tests``): not
tier-1. Nothing here pins where in their lists the new entries stand."""

import json
import math
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import costs_pangu, manifest, traffic
from benchmark.run import Measured, load_reader
from benchmark.trace_reduce import Device, Event, Trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "openpangu-ultra-moe-718b.reasoning-decode"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/openpangu-ultra-moe-718b.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
MODEL = CONFIG["model"]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_cost_functions_by_hand():
    # one token at context 1,000 and one at 3: 5 layers x rows x 576 x 2 B
    assert costs_pangu.latent_decode_needed_bytes(
        [1000, 3, 0], 5, 512, 64, 2) == 5 * 1003 * 576 * 2
    # 128 heads x (2 x 576 + 2 x 512) = 278,528 operations a row and layer
    assert 128 * (2 * 576 + 2 * 512) == 278_528
    assert costs_pangu.latent_decode_needed_flops(
        [1000, 3, 0], 5, 128, 512, 64) == 5 * 1003 * 278_528
    # 242 operations a byte against a v5e's ridge of 240.5: compute bounds it
    assert 278_528 / 1152 == pytest.approx(241.78, abs=0.01)
    floor = costs_pangu.latent_decode_floor_seconds(
        [1000, 3, 0], 5, 128, 512, 64, 2, PEAKS)
    assert floor == pytest.approx(5 * 1003 * 278_528 / 197e12)
    assert floor > 5 * 1003 * 1152 / 819e9
    # few heads: the bytes bound it
    assert costs_pangu.latent_decode_floor_seconds(
        [1000], 5, 16, 512, 64, 2, PEAKS) == pytest.approx(
            5 * 1000 * 1152 / 819e9)


def _trace(kernel_name="latent_decode_attn"):
    """Two ``jit_step`` programs of 100 ms and one ``jit_prefill``: the
    kernel 25 ms a step, the query latent 3 ms, the query heads 6 ms, the
    key-value latent 1 ms, the folded queries 4 ms, the values out 3 ms,
    the output projection 9 ms (not counted), grouped products 20 ms; a
    prefill's product over 1,536 that no reader may count."""
    ops, modules = [], []
    layout = "{1,0:T(8,128)(2,1)S(1)}"

    def op(t, dur, text):
        ops.append(Event(text, t, dur))
        return t + dur

    for t0 in (1.0, 1.2):
        modules.append(Event("jit_step(123)", t0, 0.1))
        t = op(t0, 0.025, f"%{kernel_name}.7 = (bf16[224,128,512]{layout}, "
               f"bf16[5,1,224,4096,640]{layout}) custom-call(...), "
               'custom_call_target="tpu_custom_call"')
        t = op(t, 0.003, f"%fusion.2 = bf16[224,1536]{layout} fusion(...)")
        t = op(t, 0.006, f"%fusion.3 = bf16[224,128,192]{layout} fusion(...)")
        t = op(t, 0.001, f"%fusion.4 = bf16[224,576]{layout} fusion(...)")
        t = op(t, 0.004, f"%fusion.5 = bf16[224,128,640]{layout} fusion(...)")
        t = op(t, 0.003, f"%fusion.6 = bf16[224,128,128]{layout} fusion(...)")
        t = op(t, 0.009, f"%fusion.8 = bf16[224,7680]{layout} fusion(...)")
        op(t, 0.020, f"%ragged-dot-none.4 = bf16[1920,2048]{layout} "
           'custom-call(...), custom_call_target="tpu_custom_call"')
    modules.append(Event("jit_prefill(5)", 1.4, 0.02))
    op(1.4, 0.02, f"%fusion.1 = bf16[1,1024,1536]{layout} fusion(...)")
    return Trace(devices=[Device(modules=modules, ops=ops)], host={})


class _Leaf:
    class dtype:
        itemsize = 2


class _System:
    """An engine as the readers see it."""

    class pool:
        caches = {"latent": _Leaf}


class _Parent:
    """The parent's engine serving a K/V stack."""

    class pool:
        caches = {"full": _Leaf, "window": _Leaf}


def measured(deliveries=(), model=MODEL, peaks=PEAKS, system=_System,
             trace=None):
    return Measured(
        system=system, model=model, geometry=CONFIG["engine"], window_s=30.0,
        before=None, after=None, trace=trace or _trace(),
        trace_host_span=(10.0, 12.0), deliveries=list(deliveries), peaks=peaks,
    )


def test_latent_decode_roofline_share_reads_the_floor_over_the_kernel():
    reader = load_reader("kernels.latent_decode_roofline_share")
    deliveries = [(10.5, 1600), (11.0, 300), (11.5, 0), (12.5, 4000)]
    floor = 5 * 1900 * 278_528 / 197e12
    got = reader.read(measured(deliveries=deliveries))
    assert got == pytest.approx(100 * floor / 0.050)
    assert got < 100
    # a K/V model, a K/V pool, a trace without the kernel: nothing
    gpt2 = {"d_model": 1280, "n_heads": 20, "n_layers": 36}
    assert reader.read(measured(deliveries=deliveries, model=gpt2)) is None
    assert reader.read(measured(deliveries=deliveries, system=_Parent)) is None
    assert reader.read(measured(
        deliveries=deliveries, trace=_trace("decode_attn"))) is None
    assert reader.read(measured(deliveries=deliveries, peaks={})) is None


def test_latent_attn_device_share_keys_on_the_attention_s_own_shapes():
    reader = load_reader("programs.latent_attn_device_share")
    busy = 2 * 0.071 + 0.02
    own = 0.025 + 0.003 + 0.006 + 0.001 + 0.004 + 0.003
    assert reader.read(measured()) == pytest.approx(100 * 2 * own / busy)
    assert reader.read(measured(model={"d_model": 1280})) is None
    assert reader.read(measured(trace=_trace("decode_attn"))) is None


def test_traffic_mix():
    spec = traffic.load("reasoning-decode")
    trace = traffic.serve_trace(spec, 2**31 + 31, 30.0, MODEL["vocab_size"],
                                4096)
    prompts = [len(r.prompt) for r in trace.requests]
    outputs = [r.max_new for r in trace.requests]
    assert trace.kind == "closed_loop" and trace.outstanding == 448
    assert trace.ramp_s == 45 and trace.drain_s == 0
    assert len(prompts) == 896 and 256 <= min(prompts) and max(prompts) <= 1024
    assert 1536 <= min(outputs) and max(outputs) <= 3000
    assert 520 < sum(prompts) / 896 < 590 and 2200 < sum(outputs) / 896 < 2340
    assert max(p + o for p, o in zip(prompts, outputs)) < 4096
    assert max(max(r.prompt) for r in trace.requests) < MODEL["vocab_size"]
    # the engine's queue holds what the slots do not, in a rehearsal too
    engine = CONFIG["engine"]
    toy = dict(engine, **CONFIG["rehearse"]["engine"])
    for geometry in (engine, toy):
        assert (geometry["max_queue_depth"]
                >= trace.outstanding - geometry["n_slots"])


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog here")
def test_the_file_holds_every_published_number():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "openPangu-Ultra-MoE-718B")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == {"first_k_dense_replace", "n_routed_experts",
                      "vocab_size"}
    assert set(CONFIG["reduced"]) == differ | {"n_layers"}
    assert CONFIG["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600,
        "num_nextn_predict_layers": 1}
    assert "32 chips share each layer" in CONFIG["deployment"]
    assert any("num_nextn_predict_layers" in d for d in CONFIG["departures"])
    # the model group: every width as published
    src = row["config"]
    assert MODEL["d_model"] == src["hidden_size"] == 7680
    assert MODEL["n_heads"] == src["num_attention_heads"] == 128
    assert MODEL["d_ff"] == src["intermediate_size"] == 18432
    assert MODEL["q_lora_rank"] == src["q_lora_rank"] == 1536
    assert MODEL["kv_lora_rank"] == src["kv_lora_rank"] == 512
    assert MODEL["qk_nope_head_dim"] == src["qk_nope_head_dim"] == 128
    assert MODEL["qk_rope_head_dim"] == src["qk_rope_head_dim"] == 64
    assert MODEL["v_head_dim"] == src["v_head_dim"] == 128
    assert MODEL["d_expert"] == src["moe_intermediate_size"] == 2048
    assert MODEL["d_shared"] == (
        src["n_shared_experts"] * src["moe_intermediate_size"])
    assert MODEL["moe_k"] == src["num_experts_per_tok"] == 8
    assert MODEL["n_experts_total"] == 256 == CONFIG["published"][
        "n_routed_experts"]
    assert MODEL["moe_scale"] == src["routed_scaling_factor"] == 2.5
    assert MODEL["norm_eps"] == src["rms_norm_eps"] == 1e-5
    assert MODEL["rope_theta"] == src["rope_theta"] == 25600000
    assert MODEL["sandwich_norm"] is src["sandwich_norm"] is True
    assert MODEL["moe_score"] == "sigmoid"
    # the cut
    assert MODEL["n_experts"] == CONFIG["n_routed_experts"] == 8
    assert MODEL["vocab_size"] == CONFIG["vocab_size"] == 19200
    assert MODEL["vocab_size"] * 8 == src["vocab_size"]
    assert MODEL["n_layers"] == CONFIG["n_layers"] == 5
    assert MODEL["dense_layers"] == [0]
    assert CONFIG["first_k_dense_replace"] == len(MODEL["dense_layers"])
    assert MODEL["layer_types"] == ["latent_attention"] * 5
    # the guide's floors: a dense layer and four expert layers, eight
    # experts a layer, an eighth of the vocabulary
    assert MODEL["n_layers"] - len(MODEL["dense_layers"]) >= 4


def test_manifest_entries():
    m = manifest.load(ROOT)
    assert manifest.check(m, ROOT) == []
    cell = manifest.cell(m, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reasoning-decode"
    entry = manifest.config_entry(m, "openpangu-ultra-moe-718b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    e2e = {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")}
    assert e2e == {"tpot_p95_ms", "setup_s"}
    layer = {x["name"] for x in manifest.metrics_of(m, CELL, "per_layer")}
    assert layer == {
        "engine_loop.horizon_ms", "programs.step_device_ms",
        "programs.prefill_device_share", "kernels.moe_roofline_share",
        "moe.assignments_per_expert_hit",
        "kernels.latent_decode_roofline_share",
        "programs.latent_attn_device_share",
    }
    for name in ("kernels.latent_decode_roofline_share",
                 "programs.latent_attn_device_share"):
        (x,) = [x for x in m["per_layer"] if x["name"] == name]
        assert x["workloads"] == [CELL] and x["moves"] == "tpot_p95_ms"
        assert x["source"] == "device_trace" and x["unit"] == "%"


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark/reference/pangu_moe.py").read_text()
    assert "deeplearning4j_tpu" not in text.replace(
        "the program under test", "")
    assert 'default_matmul_precision("highest")' in text


def test_rehearsal_of_the_new_cell():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 31), "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) for v in line["metrics"].values())
