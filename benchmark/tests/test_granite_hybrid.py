"""What the granite-4.0-h-micro cell adds to the benchmark: the reference,
the cost functions, the two readers on a hand-made trace (and silent on
every other configuration's model), the configuration file against the
catalog row it was copied from, the traffic mix, the manifest's new
entries, and a rehearsal of the new cell. By hand (``python3 -m pytest
benchmark/tests``): not tier-1. Nothing here pins where in their lists the
new entries stand."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import costs_granite_hybrid, manifest, traffic
from benchmark.run import Measured, load_reader
from benchmark.trace_reduce import Device, Event, Trace

ROOT = Path(__file__).resolve().parents[2]
CELL = "granite-4.0-h-micro.batch-decode"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/granite-4.0-h-micro.json").read_text())
CATALOG = Path(os.environ.get("MODEL_CATALOG", "architectures.jsonl"))
MODEL = CONFIG["model"]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BLOCK = 128 * 4096 * 4  # one slot's state in one mamba layer, bytes
READERS = ("kernels.ssd_decode_roofline_share", "programs.ssd_device_share")


def test_cost_functions_by_hand():
    # 2.10 MB a block, read and written
    assert BLOCK == 2_097_152
    assert costs_granite_hybrid.ssd_decode_needed_bytes(
        10, 128, 4096) == 10 * 2 * BLOCK
    assert costs_granite_hybrid.ssd_decode_needed_flops(
        10, 128, 4096) == 10 * 5 * 128 * 4096
    floor = costs_granite_hybrid.ssd_decode_floor_seconds(
        1, 128, 4096, PEAKS)
    # 5.12 us a live slot and layer: the bytes bind
    assert floor == pytest.approx(2 * BLOCK / 819e9)
    assert 1e6 * floor == pytest.approx(5.12, abs=0.01)
    # 64 slots x 36 layers: 11.8 ms a substep, 9.66 GB
    assert 64 * 36 * 1e3 * floor == pytest.approx(11.80, abs=0.01)
    assert 64 * 36 * 2 * BLOCK == pytest.approx(9.66e9, rel=1e-3)


def _trace(kernel_name="ssd_decode"):
    """Two ``jit_step`` programs of 100 ms and one ``jit_prefill``: the
    kernel 40 ms a step, W_in's product 5 ms, the convolution 2 ms, dt and
    the decay 1 ms, x by heads 1 ms, the gated norm 3 ms, the attention
    layers' walk 10 ms, attention's q 1 ms and W_out 6 ms (none of the
    three counted); a prefill's product over 8,512 that no reader may
    count."""
    ops, modules = [], []
    layout = "{1,0:T(8,128)(2,1)S(1)}"

    def op(t, dur, text):
        ops.append(Event(text, t, dur))
        return t + dur

    for t0 in (1.0, 1.2):
        modules.append(Event("jit_step(123)", t0, 0.1))
        t = op(t0, 0.040, f"%{kernel_name}.7 = (f32[64,1,4096]{layout}, "
               f"f32[36,1,64,128,4096]{layout}) custom-call(...), "
               'custom_call_target="tpu_custom_call"')
        t = op(t, 0.005, f"%fusion.2 = bf16[64,8512]{layout} fusion(...)")
        t = op(t, 0.002, f"%fusion.3 = f32[64,4,4352]{layout} fusion(...)")
        t = op(t, 0.001, f"%fusion.4 = f32[64,64]{layout} fusion(...)")
        t = op(t, 0.001, f"%fusion.5 = f32[64,1,64,64]{layout} fusion(...)")
        t = op(t, 0.003, f"%fusion.6 = bf16[64,1,4096]{layout} fusion(...)")
        t = op(t, 0.010, f"%decode_attn.3 = (bf16[64,4,512]{layout}, "
               f"bf16[4,2,64,3072,512]{layout}) custom-call(...), "
               'custom_call_target="tpu_custom_call"')
        t = op(t, 0.001, f"%fusion.7 = bf16[64,32,64]{layout} fusion(...)")
        op(t, 0.006, f"%fusion.8 = bf16[64,2048]{layout} fusion(...)")
    modules.append(Event("jit_prefill(5)", 1.4, 0.02))
    op(1.4, 0.02, f"%fusion.1 = bf16[1,1024,8512]{layout} fusion(...)")
    return Trace(devices=[Device(modules=modules, ops=ops)], host={})


def measured(deliveries=(), model=MODEL, peaks=PEAKS, trace=None,
             geometry=None):
    return Measured(
        system=None, model=model, geometry=geometry or CONFIG["engine"],
        window_s=30.0, before=None, after=None, trace=trace or _trace(),
        trace_host_span=(10.0, 12.0), deliveries=list(deliveries), peaks=peaks,
    )


def test_ssd_decode_roofline_share_reads_the_floor_over_the_kernel():
    reader = load_reader("kernels.ssd_decode_roofline_share")
    # three decode tokens in the trace's span; a first token (0 rows) and
    # one after the span update nothing here
    deliveries = [(10.5, 600), (11.0, 300), (11.2, 1300), (11.5, 0),
                  (12.5, 900)]
    floor = 3 * 36 * 2 * BLOCK / 819e9
    got = reader.read(measured(deliveries=deliveries))
    assert got == pytest.approx(100 * floor / 0.080)
    assert got < 100
    assert reader.read(measured(
        deliveries=deliveries, trace=_trace("linear_decode"))) is None
    assert reader.read(measured(deliveries=deliveries, peaks={})) is None


def test_ssd_device_share_keys_on_the_layer_s_own_shapes():
    reader = load_reader("programs.ssd_device_share")
    busy = 2 * 0.069 + 0.02
    own = 0.040 + 0.005 + 0.002 + 0.001 + 0.001 + 0.003
    assert reader.read(measured()) == pytest.approx(100 * 2 * own / busy)
    assert reader.read(measured(trace=_trace("linear_decode"))) is None


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("config", [
    "gpt2-large", "laguna-s-2.1", "openpangu-ultra-moe-718b",
    "olmo-hybrid-7b"])
def test_the_readers_are_silent_on_the_other_models(name, config):
    """Each accepted serve configuration's model and engine, on a trace
    that even holds the kernel: no mamba layers, nothing to read."""
    other = json.loads(
        (ROOT / f"benchmark/configs/{config}.json").read_text())
    reader = load_reader(name)
    assert reader.read(measured(
        deliveries=[(10.5, 600)], model=other["model"],
        geometry=other["engine"])) is None


def test_traffic_mix():
    spec = traffic.load("batch-decode")
    trace = traffic.serve_trace(spec, 2**31 + 40, 30.0, MODEL["vocab_size"],
                                3072)
    prompts = np.asarray([len(r.prompt) for r in trace.requests])
    outputs = np.asarray([r.max_new for r in trace.requests])
    assert trace.kind == "closed_loop" and trace.outstanding == 128
    assert trace.ramp_s == 25 and trace.drain_s == 0
    assert len(prompts) == 256 and 256 <= prompts.min()
    assert prompts.max() <= 1536
    assert 384 <= outputs.min() and outputs.max() <= 1280
    assert 650 < prompts.mean() < 780
    assert 780 < outputs.mean() < 880
    # about a quarter take a prefill and a chunk
    assert 0.15 < float(np.mean(prompts > 1024)) < 0.30
    assert (prompts + outputs).max() < 3072
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
               if r["name"] == "granite-4.0-h-micro")
    assert CONFIG["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if CONFIG.get(k) != v} == set()
    assert CONFIG["reduced"] == []
    assert "whole" in CONFIG["deployment"]
    src = row["config"]
    assert MODEL["d_model"] == src["hidden_size"] == 2048
    assert MODEL["n_heads"] == src["num_attention_heads"] == 32
    assert MODEL["n_kv_heads"] == src["num_key_value_heads"] == 8
    assert MODEL["d_ff"] == src["shared_intermediate_size"] == 8192
    assert MODEL["vocab_size"] == src["vocab_size"] == 100352
    assert MODEL["norm_eps"] == src["rms_norm_eps"]
    assert MODEL["n_layers"] == src["num_hidden_layers"] == 40
    assert MODEL["layer_types"] == src["layer_types"]
    assert MODEL["dense_layers"] == list(range(40))
    assert MODEL["rope"] is False and src["position_embedding_type"] == "nope"
    for key in ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_expand",
                "mamba_conv_bias", "embedding_multiplier",
                "residual_multiplier", "attention_multiplier",
                "logits_scaling", "tie_word_embeddings"):
        assert MODEL[key] == src[key]
    assert src["mamba_proj_bias"] is False and src["num_local_experts"] == 0


def test_manifest_entries():
    m = manifest.load(ROOT)
    assert manifest.check(m, ROOT) == []
    cell = manifest.cell(m, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "batch-decode"
    entry = manifest.config_entry(m, "granite-4.0-h-micro")
    assert entry["reduced"] == CONFIG["reduced"] == []
    assert entry["source"] == CONFIG["source"]
    e2e = {x["name"] for x in manifest.metrics_of(m, CELL, "end_to_end")}
    assert e2e == {"tpot_p95_ms", "setup_s"}
    layer = {x["name"] for x in manifest.metrics_of(m, CELL, "per_layer")}
    assert layer == {
        "engine_loop.horizon_ms", "programs.step_device_ms",
        "programs.prefill_device_share", *READERS,
    }
    for name in READERS:
        (x,) = [x for x in m["per_layer"] if x["name"] == name]
        assert x["workloads"] == [CELL] and x["moves"] == "tpot_p95_ms"
        assert x["source"] == "device_trace" and x["unit"] == "%"


def test_the_reference_imports_nothing_from_the_program():
    text = (ROOT / "benchmark/reference/granite_hybrid.py").read_text()
    assert "deeplearning4j_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(step" in text  # the token recurrence


def test_the_reference_is_the_recurrence_at_one_position():
    """One token: the state is B (dt x)^T from zero, so the mixer's output
    is C . B dt x + D x before the gate: a hand computation of the first
    position against the reference's ``_mamba``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid as ref

    rng = np.random.default_rng(0)
    heads, hd, n, d = 2, 4, 8, 8
    settings = {"mamba_n_heads": heads, "mamba_d_head": hd,
                "mamba_d_state": n, "mamba_n_groups": 1,
                "mamba_conv_bias": True, "norm_eps": 1e-5}
    width = heads * hd + 2 * n
    p = {"w_in": rng.standard_normal((d, heads * hd + width + heads)),
         "conv_w": rng.standard_normal((4, width)),
         "conv_b": rng.standard_normal(width),
         "dt_bias": rng.standard_normal(heads),
         "A_log": rng.standard_normal(heads), "D": rng.standard_normal(heads),
         "ssm_norm_scale": np.ones(heads * hd),
         "wo": np.eye(heads * hd).reshape(heads, hd, heads * hd)}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    h = jnp.asarray(rng.standard_normal((1, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref._mamba(p, h, settings, lambda x: x, None))[0]
    proj = np.asarray(h)[0] @ np.asarray(p["w_in"])
    inner = heads * hd
    z, xbc, dt = proj[:inner], proj[inner:inner + width], proj[inner + width:]
    c = xbc * np.asarray(p["conv_w"])[-1] + np.asarray(p["conv_b"])
    c = c / (1 + np.exp(-c))
    x, b, cc = c[:inner].reshape(heads, hd), c[inner:inner + n], c[inner + n:]
    dt = np.log1p(np.exp(dt + np.asarray(p["dt_bias"])))
    y = (cc @ b) * dt[:, None] * x + np.asarray(p["D"])[:, None] * x
    y = y.reshape(-1) * z / (1 + np.exp(-z))
    y = y / np.sqrt(np.mean(y * y) + 1e-5)
    np.testing.assert_allclose(got, y, rtol=1e-4, atol=1e-4)


def test_rehearsal_of_the_new_cell():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 40), "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert all(math.isfinite(v["value"]) for v in line["metrics"].values())
