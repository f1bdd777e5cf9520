"""Pipeline (stage) parallelism: GPipe-style microbatch schedule over ICI.

The reference has no pipeline parallelism (SURVEY §2 P5 — layer-wise
*pretraining* is sequential-by-layer, MultiLayerNetwork.java:139-181, not
pipelined execution); this module provides it as a beyond-parity
capability, built the TPU way:

- The network is split into ``n_stages`` identically-shaped stage
  functions whose params are stacked on a leading stage axis and sharded
  over the mesh's ``pipe`` axis — each device owns one stage.
- A batch is split into ``M`` microbatches.  A single ``lax.scan`` runs
  ``M + n_stages - 1`` ticks; on every tick each device applies its stage
  and hands its activation to the next device with ``lax.ppermute`` over
  the ICI ring.  The pipeline "bubble" is the standard
  ``(S-1)/(M+S-1)`` GPipe cost.
- The whole schedule is one compiled SPMD program; ``jax.grad`` through
  the ``shard_map`` gives the backward pipeline for free (ppermute
  transposes to the reverse rotation).

Stages must map (mb, D) -> (mb, D) (uniform width); put embed/readout in
the first/last stage or outside the pipelined trunk.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

PIPE_AXIS = "pipe"

StageFn = Callable[[Any, jax.Array], jax.Array]  # (stage_params, h) -> h


def pipeline_mesh(n_stages: int) -> Mesh:
    """1-D mesh of ``n_stages`` devices along the ``pipe`` axis."""
    devs = jax.devices()
    if len(devs) < n_stages:
        raise ValueError(
            f"pipeline needs {n_stages} devices, have {len(devs)}"
        )
    return Mesh(np.array(devs[:n_stages]), (PIPE_AXIS,))


def stack_stage_params(params_list: list[Any]) -> Any:
    """Stack per-stage param pytrees on a new leading stage axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def _build_apply(mesh: Mesh, stage_fn: StageFn, n_stages: int):
    """shard_map'd fn(stacked_params, x[M, mb, ...]) -> y[M, mb, ...]."""

    def per_device(params, x):
        # params arrive as this stage's block: leading axis must be 1 —
        # a longer block means the stacked stage axis didn't match the
        # mesh and stages would silently be dropped by the [0] below
        leading = {jax.tree.leaves(params)[0].shape[0]}
        assert leading == {1}, (
            f"stage-param stack does not match pipe axis ({n_stages} "
            f"devices, per-device block of {leading})"
        )
        p = jax.tree.map(lambda a: a[0], params)
        m = x.shape[0]
        me = lax.axis_index(PIPE_AXIS)
        recv = jnp.zeros(x.shape[1:], x.dtype)
        out = jnp.zeros_like(x)

        def tick(carry, t):
            recv, out = carry
            # stage 0 draws fresh microbatches; later stages consume the
            # activation rotated in on the previous tick
            inp = jnp.where(me == 0, x[jnp.clip(t, 0, m - 1)], recv)
            h = stage_fn(p, inp)
            widx = t - (n_stages - 1)
            write = (me == n_stages - 1) & (widx >= 0)
            out = jnp.where(
                write,
                lax.dynamic_update_index_in_dim(
                    out, h, jnp.clip(widx, 0, m - 1), 0
                ),
                out,
            )
            if n_stages > 1:
                h = lax.ppermute(
                    h,
                    PIPE_AXIS,
                    [(i, i + 1) for i in range(n_stages - 1)],
                )
            return (h, out), None

        (recv, out), _ = lax.scan(
            tick, (recv, out), jnp.arange(m + n_stages - 1)
        )
        # out is zeros everywhere but the last stage; psum replicates it
        return lax.psum(out, PIPE_AXIS)

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(PIPE_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )


def pipeline_apply(mesh: Mesh, stage_fn: StageFn):
    """Build jitted ``fn(stacked_params, x) -> y``.

    ``stacked_params`` leaves carry a leading stage axis (length =
    mesh pipe-axis size); ``x`` is ``(M, microbatch, ...)``.
    """
    n_stages = mesh.shape[PIPE_AXIS]
    return jax.jit(_build_apply(mesh, stage_fn, n_stages))


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    """(B, ...) -> (M, B/M, ...)."""
    assert x.shape[0] % n_micro == 0, (x.shape, n_micro)
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


def pipeline_train_step(
    mesh: Mesh,
    stage_fn: StageFn,
    loss_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    optimizer: optax.GradientTransformation | None = None,
):
    """Build a jitted full training step through the pipeline.

    ``loss_fn(head_params, h, y) -> scalar`` consumes the pipeline output
    ``h`` of shape ``(M, mb, D)`` (e.g. a readout + mean loss).  Params are
    ``(stacked_stage_params, head_params)``.  Returns
    ``step(params, opt_state, x, y) -> (params, opt_state, loss)`` plus an
    ``init(params)`` for the optimizer state.
    """
    optimizer = optimizer or optax.sgd(1e-2, momentum=0.9)
    n_stages = mesh.shape[PIPE_AXIS]
    apply = _build_apply(mesh, stage_fn, n_stages)

    def loss(params, x, y):
        stacked, head = params
        h = apply(stacked, x)
        return loss_fn(head, h, y)

    stage_shard = NamedSharding(mesh, P(PIPE_AXIS))
    repl = NamedSharding(mesh, P())

    def place(params):
        stacked, head = params
        stacked = jax.tree.map(
            lambda a: jax.device_put(a, stage_shard), stacked
        )
        head = jax.tree.map(lambda a: jax.device_put(a, repl), head)
        return stacked, head

    # params/opt_state are donated (as in DataParallelTrainer): callers
    # must treat the inputs as consumed and keep using the returned state
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        l, grads = jax.value_and_grad(loss)(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    return step, optimizer.init, place
