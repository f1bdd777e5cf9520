"""The ``train`` kind: ``transformer_train_step`` on a one-device mesh.

Steps run back to back on seeded random token batches. The next batch is
made on the host and placed while the current step runs, and a step's loss
is read only after the next step is dispatched, so the device never waits
for the host. Every loss in the window must be finite.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic
from benchmark.serve import TRACE_SECONDS, load_reference, transformer_config


def _rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_loss_and_grads(reference, cfg, mesh, params, check: dict,
                         seed: int):
    """Loss and a sample of gradient leaves of the program's loss (its
    compute type, its flash kernels) against the float32 reference, on
    one short batch at the published widths. Returns (ok, text)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import transformer_loss

    toks = jnp.asarray(traffic.train_batch(
        0, seed + 1, int(check["batch"]), int(check["seq_len"]), cfg.vocab_size
    ))
    loss, grads = jax.jit(jax.value_and_grad(transformer_loss(cfg, mesh)))(
        params, toks
    )
    ref_loss, ref_grads = reference.loss_and_grads(params, toks)
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    errs = {}
    for leaf in check["gradient_leaves"]:
        got, want = grads, ref_grads
        for key in leaf.split("/"):
            got, want = got[key], want[key]
        errs[leaf] = _rel_l2(got, want)
    worst = max(errs.values())
    ok = (np.isfinite(loss) and loss_err <= check["loss_rel_tol"]
          and worst <= check["gradient_rel_l2_tol"])
    text = (
        f"loss {loss:.6f} vs reference {ref_loss:.6f} (rel {loss_err:.2e}, "
        f"tol {check['loss_rel_tol']}); gradient leaves, relative L2 error "
        + ", ".join(f"{k} {v:.2e}" for k, v in errs.items())
        + f" (tol {check['gradient_rel_l2_tol']})"
    )
    return bool(ok), text


def run(ctx) -> dict:
    """One run of a train cell; returns the result object's fields."""
    import jax

    from deeplearning4j_tpu.models.transformer import transformer_train_step
    from deeplearning4j_tpu.parallel.mesh import dp_mp_mesh

    config, log = ctx.config, ctx.compile_log
    model = dict(config["model"])
    trainer = dict(config["trainer"])
    check = dict(config["correct"])
    if ctx.rehearse:
        toy = config["rehearse"]
        model.update(toy["model"])
        trainer.update(toy["trainer"])
        check.update(toy["correct"])
    cfg = transformer_config(model)
    batch, seq_len = int(trainer["batch"]), int(trainer["seq_len"])
    mesh = dp_mp_mesh(*trainer["mesh"])
    step, init_state, shard_tokens = transformer_train_step(mesh, cfg)

    # weights and optimizer state on the device in one jitted program
    params, opt_state = jax.jit(init_state)(jax.random.key(ctx.seed % (2**31)))
    jax.block_until_ready(params)
    ctx.note(f"state on the device at {ctx.since_start():.1f} s")
    correct, text = check_loss_and_grads(
        load_reference(config), cfg, mesh, params, check, ctx.seed
    )
    ctx.note(f"correct: {text}")

    def batch_of(k: int):
        return shard_tokens(
            traffic.train_batch(k, ctx.seed, batch, seq_len, cfg.vocab_size)
        )

    losses: list[float] = []
    n = 0  # steps dispatched
    toks = batch_of(n)
    for _ in range(2):  # the compile, and one step of the compiled program
        params, opt_state, loss = step(params, opt_state, toks)
        n += 1
        toks = batch_of(n)
        losses.append(float(loss))
    requests_warm, seconds_warm, hits, misses = log.snapshot()
    ctx.note(
        f"warm at {ctx.since_start():.1f} s: {requests_warm} programs, "
        f"{seconds_warm:.1f} s tracing and compiling, compile cache {hits} "
        f"hits {misses} misses; warm-up losses {losses}"
    )

    begun = ctx.layer_snapshots(None)
    t0 = time.perf_counter()
    ctx.window_opens(t0)
    n0 = n
    trace_at = t0 + 0.5 * (ctx.seconds - TRACE_SECONDS) if ctx.trace else None
    tracing = False
    pending = None
    while True:
        params, opt_state, loss = step(params, opt_state, toks)
        n += 1
        toks = batch_of(n)
        if pending is not None:
            losses.append(float(pending))  # waits for the step before
        pending = loss
        now = time.perf_counter()
        if trace_at is not None and not tracing and now >= trace_at:
            ctx.start_trace()
            tracing, trace_at = True, time.perf_counter()
        elif tracing and now >= trace_at + TRACE_SECONDS:
            jax.block_until_ready(loss)
            ctx.stop_trace()
            tracing, trace_at = False, None
        if now - t0 >= ctx.seconds:
            break
    losses.append(float(jax.block_until_ready(pending)))
    t1 = time.perf_counter()
    if tracing:
        ctx.stop_trace()
    ended = ctx.layer_snapshots(None)
    steps = n - n0
    window = losses[-steps:]
    in_window = log.snapshot()[0] - requests_warm
    finite = bool(np.isfinite(window).all())
    ctx.note(
        f"window: {steps} steps of {batch} x {seq_len} tokens in "
        f"{t1 - t0:.3f} s, loss {window[0]:.4f} -> {window[-1]:.4f}, every "
        f"loss finite: {finite}, {in_window} compiles in the window "
        f"{log.names_since(requests_warm)}"
    )
    return {
        "correct": bool(correct and finite and in_window == 0),
        "attempted": steps,
        "failed": int(np.count_nonzero(~np.isfinite(window))),
        "values": {"train_tokens_per_s": batch * seq_len * steps / (t1 - t0)},
        "layer_inputs": {
            "system": None, "begun": begun, "ended": ended,
            "model": model, "geometry": trainer,
        },
    }
