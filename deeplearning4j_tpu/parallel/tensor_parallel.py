"""Tensor (model) parallelism hooks.

The reference has no TP (SURVEY §2 P4 — 'provide via pjit param sharding;
design for it'); these are the standard Megatron-style building blocks
over the mesh's model axis:

- column-parallel dense: W sharded on its output dim; activations stay
  sharded, no collective.
- row-parallel dense: W sharded on its input dim; partial products are
  summed with ``psum`` over ICI.
- ``tp_mlp_block``: column -> nonlinearity -> row, the canonical pairing
  with exactly one AllReduce per block.

SERVING uses a different, byte-exact variant of this layout
(:func:`serving_tp_shardings` below, defined next to the model): the
row-parallel halves (wo, w2) stay REPLICATED and their sharded input
activations are all-gathered first, so every floating-point reduction
keeps the single-chip flop order — Megatron's psum of partial products
reassociates the sum and drifts ~1e-6, which would break the serving
engine's byte-identical parity bar. Column projections (attention
heads, d_ff, vocab) shard exactly as here; the KV cache shards on its
packed head axis (:func:`serving_tp_cache_sharding`), so per-slot
slabs, the prefix-cache region, slab copies, bucketed prefill and
chunked replay all run under one sharding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as mesh_lib


def tp_mlp_block(mesh, activation=jnp.tanh):
    """Build jitted fn(x, w1, b1, w2, b2) -> y with w1/w2 sharded on the
    model axis (w1 column-wise, w2 row-wise)."""
    axis = mesh_lib.MODEL_AXIS

    def per_device(x, w1, b1, w2, b2):
        # x replicated (B, D); w1 block (D, H/n); w2 block (H/n, D2)
        h = activation(x @ w1 + b1)  # (B, H/n) — no collective
        partial = h @ w2  # (B, D2) partial sum
        y = lax.psum(partial, axis)
        return y + b2

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(axis), P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def shard_dense_params(mesh, w1, b1, w2, b2):
    """Place the block's params with their TP shardings."""
    from jax.sharding import NamedSharding

    axis = mesh_lib.MODEL_AXIS
    return (
        jax.device_put(w1, NamedSharding(mesh, P(None, axis))),
        jax.device_put(b1, NamedSharding(mesh, P(axis))),
        jax.device_put(w2, NamedSharding(mesh, P(axis, None))),
        jax.device_put(b2, NamedSharding(mesh, P())),
    )


def serving_tp_shardings(mesh, cfg):
    """Exact-parity serving TP layout for a transformer params pytree —
    see the module docstring and the implementation (kept next to
    ``init_transformer`` so layouts cannot drift from the param tree)."""
    from deeplearning4j_tpu.models.transformer import serving_tp_shardings as f

    return f(mesh, cfg)


def serving_tp_cache_sharding(mesh, cfg):
    """Head-axis sharding for a decode-cache allocation under serving
    TP (pool slabs and the prefix-cache region share it)."""
    from deeplearning4j_tpu.models.transformer import (
        serving_tp_cache_sharding as f,
    )

    return f(mesh, cfg)
