"""Distributed training two ways: per-step AllReduce DP and local-SGD
parameter averaging, on an 8-virtual-device mesh.

≙ the reference's two scaleout policies (SURVEY §2): IterativeReduce
per-round gradient aggregation (IterativeReduceWorkRouter + actor
round-trip) and Spark/YARN parameter averaging after k local fits
(SparkDl4jMultiLayer.java:144-148, yarn Master.compute:47-62) — both
re-expressed as single compiled SPMD programs whose collectives ride the
mesh instead of actor messages.

Runs on CPU with 8 virtual devices so it works anywhere; on a real TPU
slice the same code runs unchanged over the physical mesh. For REAL
multi-process distribution (2+ hosts over jax.distributed, discovery via
the network registry), see tests/distributed_worker.py and
tests/test_distributed_multiprocess.py.

Run: python examples/distributed_local_sgd.py
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

os.environ["JAX_PLATFORMS"] = "cpu"  # demo: virtual devices; on a real
# TPU slice with >=8 chips, delete this line and the flags below
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.datasets import fetchers
from deeplearning4j_tpu.parallel import DataParallelTrainer, local_sgd_step
from deeplearning4j_tpu.parallel import mesh as mesh_lib


def build_model():
    w_rng = np.random.default_rng(1)
    params = {
        "w1": jnp.asarray(w_rng.normal(size=(4, 16)).astype(np.float32) * 0.4),
        "b1": jnp.zeros((16,)),
        "w2": jnp.asarray(w_rng.normal(size=(16, 3)).astype(np.float32) * 0.4),
        "b2": jnp.zeros((3,)),
    }

    def loss_fn(p, xb, yb, key=None):
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        return optax.softmax_cross_entropy(h @ p["w2"] + p["b2"], yb).mean()

    return params, loss_fn


def main():
    ds = fetchers.iris().normalize_zero_mean_unit_variance()
    n = (len(ds.features) // 8) * 8
    x = jnp.asarray(ds.features[:n])
    y = jnp.asarray(ds.labels[:n])
    mesh = mesh_lib.data_parallel_mesh(8)
    print(f"mesh: {mesh.shape} over {len(jax.devices())} devices")

    # -- mode 1: per-step gradient AllReduce ------------------------------
    params, loss_fn = build_model()
    trainer = DataParallelTrainer(loss_fn, mesh=mesh, optimizer=optax.sgd(0.1))
    state = trainer.init(params)
    xs, ys = trainer.shard_global_batch(x, y)
    state, losses = trainer.run_steps(state, xs, ys, jax.random.key(0), 200)
    print(f"DP AllReduce: loss {float(losses[0]):.4f} -> "
          f"{float(losses[-1]):.4f}")

    # -- mode 2: local SGD + parameter averaging on a CNN -----------------
    # ≙ the north-star "Spark parameter-averaging distributed CNN"
    # config: each of the 8 devices runs k local steps of LeNet on its
    # shard, then parameters are pmean'd — one shard_map program per
    # round, no actor round-trips
    from deeplearning4j_tpu.models.lenet import build_lenet, lenet_loss

    net, cnn_params = build_lenet(seed=0)
    ds2 = fetchers.mnist(n=64)
    cx = jnp.asarray(ds2.features)
    cy = jnp.asarray(ds2.labels)
    step = local_sgd_step(lenet_loss(net), mesh, local_steps=4, lr=0.05)
    loss = None
    for i in range(25):  # 25 rounds x 4 local steps
        cnn_params, loss = step(cnn_params, cx, cy, jax.random.key(i))
    print(f"local-SGD CNN (k=4 averaging rounds): final loss "
          f"{float(loss):.4f}")


if __name__ == "__main__":
    main()
