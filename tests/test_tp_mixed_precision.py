"""Tensor-parallel block parity, remat trainer, mixed-precision policy,
and the full driver dryrun entry."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.parallel import DataParallelTrainer, data_parallel_mesh
from deeplearning4j_tpu.parallel.mesh import dp_mp_mesh
from deeplearning4j_tpu.parallel.tensor_parallel import shard_dense_params, tp_mlp_block


def test_tp_mlp_block_matches_dense(devices):
    mesh = dp_mp_mesh(4, 2)
    rng = np.random.default_rng(0)
    d_in, hidden, d_out = 6, 8, 5
    w1 = jnp.asarray(rng.normal(size=(d_in, hidden)).astype(np.float32))
    b1 = jnp.asarray(rng.normal(size=(hidden,)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(hidden, d_out)).astype(np.float32))
    b2 = jnp.asarray(rng.normal(size=(d_out,)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(3, d_in)).astype(np.float32))
    block = tp_mlp_block(mesh)
    y = block(x, *shard_dense_params(mesh, w1, b1, w2, b2))
    ref = jnp.tanh(x @ w1 + b1) @ w2 + b2
    assert jnp.max(jnp.abs(y - ref)) < 1e-4


def test_remat_trainer_matches_plain(devices):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn import conf as C

    mc = C.list_builder(
        C.LayerConfig(activation="tanh"), sizes=[16], n_in=8, n_out=3,
        pretrain=False, backward=True,
    )
    net = MultiLayerNetwork(mc, seed=0)
    params = net.init()

    def loss(p, x, y, key=None):
        return net.supervised_score_fn(p, x, y)

    import optax

    mesh = data_parallel_mesh(8)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)])
    t_plain = DataParallelTrainer(loss, mesh=mesh, optimizer=optax.sgd(0.1))
    t_remat = DataParallelTrainer(loss, mesh=mesh, optimizer=optax.sgd(0.1), remat=True)
    s1, s2 = t_plain.init(params), t_remat.init(params)
    for i in range(3):
        s1, l1 = t_plain.step(s1, *t_plain.shard_batch(x, y), jax.random.key(i))
        s2, l2 = t_remat.step(s2, *t_remat.shard_batch(x, y), jax.random.key(i))
    assert abs(float(l1) - float(l2)) < 1e-5


def test_mixed_bf16_policy_forward():
    from deeplearning4j_tpu.models.lenet import build_lenet

    with dtypes.policy(dtypes.MIXED_BF16):
        net, params = build_lenet(seed=0)
        # params stay f32; compute casts to bf16
        assert params[0]["convweights"].dtype == jnp.float32
        out = net.feed_forward_fn(params, jnp.zeros((4, 784)))[-1]
    assert out.dtype in (jnp.bfloat16, jnp.float32)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_mixed_bf16_loss_runs_in_accum_dtype():
    # softmax/log/loss must run f32 under MIXED_BF16 — bf16
    # log-probabilities stall training on deeper nets (seen on AlexNet)
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn import conf as C

    with dtypes.policy(dtypes.MIXED_BF16):
        mc = C.list_builder(
            C.LayerConfig(activation="relu"), sizes=[16], n_in=8, n_out=3,
            pretrain=False, backward=True,
        )
        net = MultiLayerNetwork(mc, seed=0)
        params = net.init()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)])
        score = net.supervised_score_fn(params, x, y)
        assert score.dtype == jnp.float32
        # training still converges under the mixed policy
        trainer = DataParallelTrainer(
            lambda p, xx, yy, key=None: net.supervised_score_fn(p, xx, yy),
            mesh=data_parallel_mesh(8),
        )
        state = trainer.init(params)
        xs, ys = trainer.shard_batch(x, y)
        state, losses = trainer.run_steps(state, xs, ys, jax.random.key(0), 60)
        l = np.asarray(losses)
        assert np.isfinite(l).all() and l[-1] < l[0] * 0.5


def _graft_entry():
    import importlib.util
    from pathlib import Path

    # relative to this file, so the suite runs from any checkout
    spec = importlib.util.spec_from_file_location(
        "graft_entry_test",
        Path(__file__).resolve().parent.parent / "__graft_entry__.py",
    )
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_graft_dryrun_raises_when_devices_are_short(devices):
    """No fallback to another platform: too few devices is an error."""
    with pytest.raises(RuntimeError, match="needs 64 devices"):
        _graft_entry().dryrun_multichip(64)


@pytest.mark.slow
def test_graft_dryrun_multichip(devices):
    m = _graft_entry()
    fn, args = m.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)
    m.dryrun_multichip(8)
