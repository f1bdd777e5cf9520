"""Worker process for the failure-recovery test.

Run: python tests/recovery_worker.py <ckpt_dir> <total_steps> <save_every>
       [--status-url URL] [--final PATH] [--crash-after-none]

Deterministic training loop (data and key derived from the step index
alone) with periodic checkpoints, so a killed-and-restarted run replays
the exact remaining steps: restart == uninterrupted, bit-for-bit with a
stateless optimizer. Heartbeats POST to the master's statetracker REST
when --status-url is given (≙ WorkerActor.heartbeat).
"""

import argparse
import json
import os
import sys
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def build():
    import jax.numpy as jnp
    import optax

    w_rng = np.random.default_rng(7)
    params = {
        "w1": jnp.asarray(w_rng.normal(size=(6, 12)).astype(np.float32) * 0.4),
        "b1": jnp.zeros((12,)),
        "w2": jnp.asarray(w_rng.normal(size=(12, 3)).astype(np.float32) * 0.4),
        "b2": jnp.zeros((3,)),
    }

    def loss_fn(p, xb, yb):
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        return optax.softmax_cross_entropy(h @ p["w2"] + p["b2"], yb).mean()

    return params, loss_fn


def batch_for_step(i: int):
    """Step-indexed deterministic data — replayable after restart."""
    rng = np.random.default_rng(1000 + i)
    x = rng.normal(size=(16, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    return x, y


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("ckpt_dir")
    ap.add_argument("total_steps", type=int)
    ap.add_argument("save_every", type=int)
    ap.add_argument("--status-url", default=None)
    ap.add_argument("--final", default=None)
    ap.add_argument("--step-delay", type=float, default=0.0,
                    help="sleep per step — gives the kill-test parent a "
                    "window to observe checkpoints before completion")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    # NO persistent compile cache: this worker is SIGKILLed mid-run by
    # design (the kill-restart test), and a kill during a cache write
    # must never be able to poison the shared cache

    import jax.numpy as jnp
    import optax

    from deeplearning4j_tpu.parallel.checkpoint import CheckpointManager

    params, loss_fn = build()
    opt = optax.sgd(0.2)  # stateless -> params-only checkpoints resume exactly

    mgr = CheckpointManager(args.ckpt_dir, save_every=args.save_every, keep=3)
    start = 0
    restored = mgr.restore_latest(params)
    if restored is not None:
        params, meta = restored
        start = int(meta["step"])
        print(f"RESUMED_FROM={start}", flush=True)

    @jax.jit
    def step(p, x, y):
        l, g = jax.value_and_grad(loss_fn)(p, x, y)
        return optax.apply_updates(p, opt.update(g, opt.init(p))[0]), l

    loss = None
    for i in range(start + 1, args.total_steps + 1):
        x, y = batch_for_step(i)
        params, loss = step(params, jnp.asarray(x), jnp.asarray(y))
        loss = float(loss)
        if args.status_url:
            req = urllib.request.Request(
                f"{args.status_url}/statetracker/heartbeat",
                data=json.dumps(
                    {"worker": "w0", "meta": {"step": i}}
                ).encode(),
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            urllib.request.urlopen(req, timeout=10).read()
        mgr.maybe_save(i, params, {"loss": loss})
        print(f"STEP={i}", flush=True)
        if args.step_delay:
            import time

            time.sleep(args.step_delay)

    if args.final:
        np.savez(
            args.final,
            **{k: np.asarray(v) for k, v in params.items()},
            loss=np.float64(loss),
        )
    print(f"LOSS={loss:.10f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
