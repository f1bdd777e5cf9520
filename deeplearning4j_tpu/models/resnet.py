"""ResNet (CIFAR-style) with BatchNorm — beyond-parity modern CNN.

The reference's CNN story ends at forward-only conv+pool
(ConvolutionDownSampleLayer.java:113-121) and predates both residual
connections and batch normalization; LeNet/AlexNet here mirror its era.
This model brings the framework's CNN family to the modern baseline:
3x3 conv / BN / relu basic blocks with identity skips, the He et al.
CIFAR layout (3 stages of n blocks at 16/32/64 channels, stride-2
transitions, global average pool).

TPU-first notes:
- NHWC activations, HWIO kernels (`lax.conv_general_dilated`), bf16
  compute under the dtypes policy with f32 BN statistics;
- BatchNorm keeps its running statistics in an explicit ``state``
  pytree threaded through the train step (the framework is pure
  functions over pytrees — no mutable layers), updated with momentum
  inside the same jitted step;
- the whole model is stacked-layer pytrees + `lax.conv` calls, so it
  shards over the data axis like every other model (works with
  `mesh.shard_batch`/`place_global`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from deeplearning4j_tpu import dtypes


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    num_classes: int = 10
    in_channels: int = 3
    #: blocks per stage (He CIFAR recipe: depth = 6n+2; n=3 -> ResNet-20)
    blocks_per_stage: int = 3
    stage_channels: tuple = (16, 32, 64)
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5


def _conv_init(key, h, w, cin, cout):
    # He normal fan-in init
    scale = np.sqrt(2.0 / (h * w * cin))
    return jax.random.normal(key, (h, w, cin, cout), jnp.float32) * scale


def _bn_params(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _bn_state(c):
    return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def init_resnet(key, cfg: ResNetConfig):
    """Returns (params, bn_state) pytrees."""
    keys = iter(jax.random.split(key, 4 + 3 * cfg.blocks_per_stage * len(cfg.stage_channels)))
    c0 = cfg.stage_channels[0]
    params: dict[str, Any] = {
        "stem": {"w": _conv_init(next(keys), 3, 3, cfg.in_channels, c0),
                 "bn": _bn_params(c0)},
        "stages": [],
        "head": {
            "w": jax.random.normal(
                next(keys), (cfg.stage_channels[-1], cfg.num_classes),
                jnp.float32,
            ) / np.sqrt(cfg.stage_channels[-1]),
            "b": jnp.zeros((cfg.num_classes,)),
        },
    }
    state: dict[str, Any] = {"stem": _bn_state(c0), "stages": []}
    cin = c0
    for cout in cfg.stage_channels:
        stage_p, stage_s = [], []
        for b in range(cfg.blocks_per_stage):
            block = {
                "conv1": _conv_init(next(keys), 3, 3, cin, cout),
                "bn1": _bn_params(cout),
                "conv2": _conv_init(next(keys), 3, 3, cout, cout),
                "bn2": _bn_params(cout),
            }
            bs = {"bn1": _bn_state(cout), "bn2": _bn_state(cout)}
            if cin != cout:
                block["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
            stage_p.append(block)
            stage_s.append(bs)
            cin = cout
        params["stages"].append(stage_p)
        state["stages"].append(stage_s)
    return params, state


def _conv(x, w):
    # all convs are stride-1 SAME by design: downsampling happens only
    # through the count-corrected average pool at stage transitions
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _batch_norm(
    x, p, s, train: bool, momentum: float, eps: float,
    axis_name: str | None = None,
):
    """Returns (y, new_state). Statistics in f32 regardless of compute
    dtype; train mode normalizes with batch stats and rolls the running
    averages, eval mode uses the running stats.

    Sync-BN: under jit/pjit with a batch-sharded input, the means below
    are GLOBAL by construction — XLA inserts the cross-replica reduction,
    so the pjit path is synchronized batch norm already (locked by
    ``test_resnet.py::test_pjit_batch_norm_is_sync``). ``axis_name`` is
    for the per-replica regimes (``shard_map``/``pmap``), where each
    replica sees only its shard: the two raw moments are ``pmean``-ed
    over the named axis (pmean of per-shard VARIANCES would be wrong —
    E[x^2] - E[x]^2 needs globally-averaged moments)."""
    x32 = x.astype(jnp.float32)
    if train:
        if axis_name is not None:
            # cross-replica: pmean the raw moments, then E[x^2]-E[x]^2.
            # (The moment form cancels catastrophically for large-mean
            # near-constant channels, so it is confined to this path
            # where per-shard variances cannot be combined directly.)
            mean = lax.pmean(jnp.mean(x32, axis=(0, 1, 2)), axis_name)
            sq = lax.pmean(
                jnp.mean(jnp.square(x32), axis=(0, 1, 2)), axis_name
            )
            var = sq - jnp.square(mean)
        else:
            mean = jnp.mean(x32, axis=(0, 1, 2))
            var = jnp.var(x32, axis=(0, 1, 2))
        new_s = {
            "mean": momentum * s["mean"] + (1 - momentum) * mean,
            "var": momentum * s["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    y = (x32 - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype), new_s


def resnet_apply(cfg: ResNetConfig, train: bool, axis_name: str | None = None):
    """apply(params, state, x NHWC) -> (logits f32, new_state).

    ``axis_name`` enables cross-replica sync-BN inside per-replica
    regimes (shard_map/pmap); the plain jit/pjit path is sync already
    (see ``_batch_norm``)."""

    def block_fn(x, bp, bs):
        h, bs1 = _batch_norm(
            _conv(x, bp["conv1"]), bp["bn1"], bs["bn1"], train,
            cfg.bn_momentum, cfg.bn_eps, axis_name,
        )
        h = jax.nn.relu(h)
        h, bs2 = _batch_norm(
            _conv(h, bp["conv2"]), bp["bn2"], bs["bn2"], train,
            cfg.bn_momentum, cfg.bn_eps, axis_name,
        )
        skip = _conv(x, bp["proj"]) if "proj" in bp else x
        return jax.nn.relu(h + skip), {"bn1": bs1, "bn2": bs2}

    def apply(params, state, x):
        policy = dtypes.get_policy()
        x = x.astype(policy.compute_dtype)
        h = _conv(x, params["stem"]["w"])
        h, stem_s = _batch_norm(
            h, params["stem"]["bn"], state["stem"], train,
            cfg.bn_momentum, cfg.bn_eps, axis_name,
        )
        h = jax.nn.relu(h)
        new_state = {"stem": stem_s, "stages": []}
        for si, (stage_p, stage_s) in enumerate(
            zip(params["stages"], state["stages"])
        ):
            if si > 0:
                # stride-2 stage transition via average pooling (the
                # parameter-free CIFAR-ResNet downsampling); divide by
                # the per-window element count, not a fixed 4 — with odd
                # spatial dims SAME pads the last window, and a fixed
                # divisor would underweight border activations
                pooled = lax.reduce_window(
                    h, 0.0, lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "SAME"
                )
                counts = lax.reduce_window(
                    jnp.ones(h.shape[1:3], h.dtype)[None, :, :, None],
                    0.0, lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "SAME",
                )
                h = pooled / counts
            ss = []
            for bp, bs in zip(stage_p, stage_s):
                h, nbs = block_fn(h, bp, bs)
                ss.append(nbs)
            new_state["stages"].append(ss)
        h = jnp.mean(h.astype(jnp.float32), axis=(1, 2))  # global avg pool
        logits = h @ params["head"]["w"] + params["head"]["b"]
        return logits, new_state

    return apply


def _supervised_loss(cfg: ResNetConfig):
    apply = resnet_apply(cfg, train=True)

    def loss_fn(params, state, x, y):
        logits, new_state = apply(params, state, x)
        return optax.softmax_cross_entropy(logits, y).mean(), new_state

    return loss_fn


def _sgd_update(optimizer, loss_fn, params, state, opt_state, x, y):
    (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, state, x, y
    )
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, new_state, opt_state, loss


def resnet_train_step(cfg: ResNetConfig, optimizer=None):
    """Jitted supervised step threading the BN state:
    ``step(params, state, opt_state, x, y) ->
    (params, state, opt_state, loss)``; labels one-hot (B, C)."""
    optimizer = optimizer or optax.sgd(0.1, momentum=0.9)
    loss_fn = _supervised_loss(cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, state, opt_state, x, y):
        return _sgd_update(
            optimizer, loss_fn, params, state, opt_state, x, y
        )

    def init(key):
        params, state = init_resnet(key, cfg)
        return params, state, optimizer.init(params)

    return step, init


def resnet_run_steps(cfg: ResNetConfig, optimizer=None):
    """One jitted program scanning n supervised steps — the bench/tight-
    loop form (per-step dispatch would be dispatch-latency-bound for a
    model this small; the carry is a few MB so the scan copy is noise).
    ``run(params, state, opt_state, x, y, n) ->
    (params, state, opt_state, losses (n,))``."""
    optimizer = optimizer or optax.sgd(0.1, momentum=0.9)
    loss_fn = _supervised_loss(cfg)

    @functools.partial(
        jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2)
    )
    def run(params, state, opt_state, x, y, n: int):
        def body(carry, _):
            p, s, o = carry
            p, s, o, loss = _sgd_update(optimizer, loss_fn, p, s, o, x, y)
            return (p, s, o), loss

        (params, state, opt_state), losses = lax.scan(
            body, (params, state, opt_state), None, length=n
        )
        return params, state, opt_state, losses

    return run
