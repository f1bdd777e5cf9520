"""Worker process for the 2-process jax.distributed test.

Run as: python tests/distributed_worker.py <registry_addr> <job_id> <pid> <nprocs>

Boot sequence (≙ the reference's DeepLearning4jDistributed bootstrap,
DeepLearning4jDistributed.java:48, with ZooKeeper discovery
≙ ZooKeeperConfigurationRegister.java:40):
- process 0 registers the jax.distributed coordinator address in the
  network registry; the other processes retrieve it — the ONLY shared
  state is the registry address (no shared filesystem);
- every process calls jax.distributed.initialize and registers itself as
  an (ephemeral) worker;
- all processes run the same SPMD program: a DataParallelTrainer step
  over the global (nprocs x local_devices) mesh;
- each prints its final loss as LOSS=<float> for the test to compare.

The device topology is pinned BEFORE jax import: 4 virtual CPU devices
per process, so 2 processes reproduce the 8-device mesh the
single-process suite uses.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    .replace("--xla_force_host_platform_device_count=8", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

import numpy as np  # noqa: E402


def main() -> int:
    registry_addr, job_id, pid_s, nprocs_s = sys.argv[1:5]
    pid, nprocs = int(pid_s), int(nprocs_s)

    import jax

    jax.config.update("jax_platforms", "cpu")

    # NO persistent compile cache here, deliberately: under
    # jax.distributed the cache's cross-process write coordination
    # deadlocked the 2-process bring-up (worker hung until the 420s
    # test timeout — measured). Only the pytest process itself caches
    # (conftest); every subprocess worker runs uncached.

    from deeplearning4j_tpu.parallel.registry import NetworkRegistry

    reg = NetworkRegistry(registry_addr, job_id)
    if pid == 0:
        # the coordinator picks a free port and publishes it
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        coordinator = f"127.0.0.1:{port}"
        reg.register_master({"coordinator": coordinator, "nprocs": nprocs})
    else:
        coordinator = reg.retrieve_master(timeout=60.0)["coordinator"]

    from deeplearning4j_tpu.parallel.cluster import initialize_distributed

    initialize_distributed(
        coordinator=coordinator, num_processes=nprocs, process_id=pid
    )
    reg.register_worker(str(pid), {"devices": jax.local_device_count()})

    assert jax.device_count() == 4 * nprocs, jax.device_count()
    assert jax.process_count() == nprocs

    # the same tiny MLP training run as the single-process reference in
    # the test — identical seeds, identical global batch
    import jax.numpy as jnp
    import optax

    from deeplearning4j_tpu.parallel import DataParallelTrainer
    from deeplearning4j_tpu.parallel import mesh as mesh_lib

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
    w_rng = np.random.default_rng(1)
    params = {
        "w1": jnp.asarray(w_rng.normal(size=(8, 16)).astype(np.float32) * 0.3),
        "b1": jnp.zeros((16,)),
        "w2": jnp.asarray(w_rng.normal(size=(16, 4)).astype(np.float32) * 0.3),
        "b2": jnp.zeros((4,)),
    }

    def loss_fn(p, xb, yb, key=None):
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        return optax.softmax_cross_entropy(logits, yb).mean()

    mesh = mesh_lib.data_parallel_mesh(jax.device_count())
    trainer = DataParallelTrainer(
        loss_fn, mesh=mesh, optimizer=optax.sgd(0.1)
    )
    state = trainer.init(params)
    xs, ys = trainer.shard_global_batch(x, y)
    loss = None
    for i in range(20):
        state, loss = trainer.step(state, xs, ys, jax.random.key(0))
    print(f"WORKERS={','.join(reg.list_workers())}", flush=True)
    print(f"LOSS={float(loss):.10f}", flush=True)

    if len(sys.argv) > 5:
        # multi-process orbax round-trip: every process writes only the
        # shards it owns (the npz manager cannot address a multi-process
        # mesh — the regime AsyncShardedCheckpointManager exists for)
        from deeplearning4j_tpu.parallel.checkpoint import (
            AsyncShardedCheckpointManager,
        )

        mgr = AsyncShardedCheckpointManager(sys.argv[5], save_every=1)
        mgr.maybe_save(20, state.params, {"loss": float(loss)})
        mgr.wait()
        restored, meta = mgr.restore_latest(state.params)
        ok = all(
            bool(jnp.all(a == b))
            for a, b in zip(
                jax.tree.leaves(restored), jax.tree.leaves(state.params)
            )
        ) and int(meta["step"]) == 20
        print(f"ORBAX={'ok' if ok else 'MISMATCH'}", flush=True)

    # cross-process tensor parallelism: build a (dp=4, tp=2) mesh whose
    # TP pairs SPAN the process boundary (device i paired with i+4, i.e.
    # one device from each process), so the Megatron layout's psum runs
    # over the host-to-host transport — the regime a real multi-host TPU
    # pod exercises over DCN. ≙ the reference's cross-JVM parameter
    # traffic, now an in-graph collective.
    from jax.sharding import Mesh

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, transformer_train_step,
    )
    from deeplearning4j_tpu.parallel import mesh as mesh_lib

    if nprocs != 2:
        # the cross-process pairing below is written for exactly 2
        # processes; other topologies skip the TP check cleanly
        return 0
    devs = jax.devices()
    local = jax.local_device_count()
    grid = np.array(
        [[devs[i], devs[i + local]] for i in range(local)], dtype=object
    )
    tmesh = Mesh(grid, (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    from _dist_common import (
        N_EXPERTS, TINY_TRANSFORMER, TOKENS_SHAPE, TRANSFORMER_SEED,
    )

    tcfg = TransformerConfig(**TINY_TRANSFORMER)
    tstep, tinit, tshard = transformer_train_step(tmesh, tcfg)
    tparams, topt = tinit(jax.random.key(TRANSFORMER_SEED))
    toks_np = (
        np.random.default_rng(TRANSFORMER_SEED)
        .integers(0, tcfg.vocab_size, TOKENS_SHAPE)
        .astype(np.int32)
    )
    ttoks = tshard(toks_np)
    tl = None
    for _ in range(3):
        tparams, topt, tl = tstep(tparams, topt, ttoks)
    print(f"TPLOSS={float(tl):.10f}", flush=True)

    # ZeRO-3/FSDP across the process boundary: params + optimizer state
    # shard over the data axis (whose groups span both processes), so
    # the per-step all-gathers and reduce-scatters ride the host-to-host
    # transport — the DCN regime of a multi-slice pod.
    fstep, finit, fshard = transformer_train_step(tmesh, tcfg, fsdp=True)
    fparams, fopt = finit(jax.random.key(TRANSFORMER_SEED))
    ftoks = fshard(toks_np)
    fl = None
    for _ in range(3):
        fparams, fopt, fl = fstep(fparams, fopt, ftoks)
    print(f"FSDPLOSS={float(fl):.10f}", flush=True)

    # MoE/EP across the process boundary: experts live one-per-device on
    # the model axis, whose pairs span the two processes — the token
    # all-to-all dispatch/combine crosses hosts.
    import dataclasses

    # field-for-field identical to tcfg apart from the experts — the
    # MOELOSS comparison against the single-process reference depends
    # on the two configs never drifting
    mcfg = dataclasses.replace(tcfg, n_experts=N_EXPERTS)
    mstep, minit, mshard = transformer_train_step(tmesh, mcfg)
    mparams, mopt = minit(jax.random.key(TRANSFORMER_SEED))
    mtoks = mshard(toks_np)
    ml = None
    for _ in range(3):
        mparams, mopt, ml = mstep(mparams, mopt, mtoks)
    print(f"MOELOSS={float(ml):.10f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
