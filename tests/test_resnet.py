"""ResNet + BatchNorm (beyond-parity modern CNN family)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.resnet import (
    ResNetConfig, init_resnet, resnet_apply, resnet_train_step,
)

CFG = ResNetConfig(num_classes=4, blocks_per_stage=1,
                   stage_channels=(8, 16))


def _data(n=16, hw=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    labels = rng.integers(0, 4, n)
    # make the task learnable: shift each image by its class
    x += labels[:, None, None, None] * 0.7
    y = np.eye(4, dtype=np.float32)[labels]
    return jnp.asarray(x), jnp.asarray(y)


def test_forward_shapes_and_state_update():
    params, state = init_resnet(jax.random.key(0), CFG)
    x, _ = _data()
    logits, new_state = resnet_apply(CFG, train=True)(params, state, x)
    assert logits.shape == (16, 4)
    # train mode rolls the running statistics
    assert not np.allclose(
        np.asarray(new_state["stem"]["mean"]),
        np.asarray(state["stem"]["mean"]),
    )
    # eval mode leaves them untouched and is deterministic
    l1, s1 = resnet_apply(CFG, train=False)(params, state, x)
    l2, s2 = resnet_apply(CFG, train=False)(params, state, x)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(
        np.asarray(s1["stem"]["mean"]), np.asarray(state["stem"]["mean"])
    )


def test_trains_and_eval_mode_classifies():
    step, init = resnet_train_step(CFG)
    params, state, opt_state = init(jax.random.key(1))
    x, y = _data(n=32, seed=1)
    losses = []
    for _ in range(40):
        params, state, opt_state, loss = step(params, state, opt_state, x, y)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, losses[::10]
    # eval-mode accuracy on the training batch after fitting
    logits, _ = resnet_apply(CFG, train=False)(params, state, x)
    acc = float(
        (jnp.argmax(logits, -1) == jnp.argmax(y, -1)).mean()
    )
    assert acc >= 0.75, acc


def test_sync_bn_shard_map_matches_full_batch(devices):
    """Per-replica BN with axis_name pmean == full-batch BN: the sync-BN
    contract for shard_map/pmap regimes (each replica sees only its
    batch shard; the moments are averaged over the dp axis)."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.models.resnet import _batch_norm

    mesh = Mesh(np.array(devices[:8]), ("data",))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(32, 4, 4, 6)).astype(np.float32))
    p = {"scale": jnp.asarray(rng.normal(size=(6,)).astype(np.float32)),
         "bias": jnp.asarray(rng.normal(size=(6,)).astype(np.float32))}
    s = {"mean": jnp.zeros((6,)), "var": jnp.ones((6,))}

    y_ref, s_ref = _batch_norm(x, p, s, True, 0.9, 1e-5)

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("data"), P(), P()),
        out_specs=(P("data"), P()),
        check_vma=False,
    )
    def sharded_bn(xs, p, s):
        return _batch_norm(xs, p, s, True, 0.9, 1e-5, axis_name="data")

    y, s_new = sharded_bn(x, p, s)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(s_new["mean"]), np.asarray(s_ref["mean"]),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(s_new["var"]), np.asarray(s_ref["var"]),
        rtol=1e-5, atol=1e-6,
    )


def test_pjit_batch_norm_is_sync(devices):
    """Under jit with a dp-sharded batch, the BN reductions are GLOBAL
    (XLA inserts the collectives): the whole-model train step over an
    8-device-sharded batch matches the single-device run — the property
    'sync-BN over the dp axis' reduces to under pjit."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    step, init = resnet_train_step(CFG)
    params, state, opt_state = init(jax.random.key(4))
    x, y = _data(n=32, seed=4)

    p2, s2, o2 = jax.tree.map(jnp.copy, (params, state, opt_state))
    mesh = Mesh(np.array(devices[:8]), ("data",))
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    ys = jax.device_put(y, NamedSharding(mesh, P("data")))

    _, state_1, _, loss_1 = step(params, state, opt_state, x, y)
    _, state_8, _, loss_8 = step(p2, s2, o2, xs, ys)
    np.testing.assert_allclose(
        float(loss_1), float(loss_8), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(state_8["stem"]["mean"]),
        np.asarray(state_1["stem"]["mean"]),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.slow
def test_cifar_accuracy_acceptance():
    """Accuracy acceptance with a concrete bound, like the DBN-Iris
    gate: ResNet on the structured synthetic CIFAR task (the offline
    stand-in — zero-egress env), evaluated on a HELD-OUT split in eval
    mode (running BN statistics). The task has real signal (oriented
    gratings per class) under noise; a broken residual/BN/optimizer
    path fails the bound immediately."""
    import optax

    from deeplearning4j_tpu.models.alexnet import synthetic_cifar

    ds = synthetic_cifar(n=1536, seed=7)
    x = np.asarray(ds.features, np.float32).reshape(-1, 32, 32, 3)
    y = np.asarray(ds.labels, np.float32)
    x_tr, y_tr = jnp.asarray(x[:1024]), jnp.asarray(y[:1024])
    x_te, y_te = jnp.asarray(x[1024:]), jnp.asarray(y[1024:])

    cfg = ResNetConfig(num_classes=10, blocks_per_stage=1,
                       stage_channels=(8, 16, 32))
    step, init = resnet_train_step(
        cfg, optimizer=optax.sgd(0.05, momentum=0.9)
    )
    params, state, opt_state = init(jax.random.key(5))
    rng = np.random.default_rng(5)
    # held-out accuracy saturates at 1.0 by ~step 60 on this task
    # (measured); 70 keeps margin over the 0.85 gate at half the wall
    # time of the original 120
    for _ in range(70):
        idx = rng.integers(0, len(x_tr), 256)
        params, state, opt_state, loss = step(
            params, state, opt_state, x_tr[idx], y_tr[idx]
        )
    assert np.isfinite(float(loss))
    logits, _ = resnet_apply(cfg, train=False)(params, state, x_te)
    acc = float((jnp.argmax(logits, -1) == jnp.argmax(y_te, -1)).mean())
    assert acc >= 0.85, f"held-out accuracy {acc:.3f} below the 0.85 gate"


def test_projection_skips_only_on_channel_change():
    params, _ = init_resnet(jax.random.key(2), CFG)
    # first block of stage 0: in==out channels (stem matches stage 0)
    assert "proj" not in params["stages"][0][0]
    # first block of stage 1: 8 -> 16 channels needs the 1x1 projection
    assert "proj" in params["stages"][1][0]
    assert params["stages"][1][0]["proj"].shape == (1, 1, 8, 16)
