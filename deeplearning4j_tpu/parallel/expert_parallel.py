"""Expert parallelism (MoE) — beyond-parity capability.

The reference has no expert parallelism (SURVEY §2 P7: absent). This module
provides the TPU-native version: a mixture-of-experts feed-forward block
whose experts are sharded one-per-device over the mesh's ``expert`` axis,
with GShard-style top-k token routing. Tokens are data-sharded over the
*same* axis, so dispatch and return are each exactly one
``lax.all_to_all`` over ICI — the canonical EP communication pattern.

Design notes (TPU-first):
- Static shapes everywhere: a fixed per-expert ``capacity`` buffer
  ``(E, C, D)`` absorbs routing imbalance; overflow tokens are dropped
  (their combine weight is zero), as in GShard/Switch.
- Dispatch/combine are expressed as dense einsums against a 0/1 dispatch
  mask ``(T, E, C)`` — matmuls the MXU tiles, instead of data-dependent
  gathers XLA can't vectorize.
- The router (tiny ``(D, E)`` matmul) is replicated; gradient flows
  through the normalized top-k gate weights, and a Switch-style auxiliary
  load-balancing loss is returned alongside the output.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel import mesh as mesh_lib

AXIS = mesh_lib.EXPERT_AXIS


class MoEParams(NamedTuple):
    """Router + stacked expert FFN weights.

    Expert tensors carry a leading ``(E, ...)`` axis sharded over the
    expert mesh axis; the router is replicated.
    """

    wg: jax.Array  # (D, E) router
    w1: jax.Array  # (E, D, H)
    b1: jax.Array  # (E, H)
    w2: jax.Array  # (E, H, D)
    b2: jax.Array  # (E, D)


def init_moe_params(
    key, d_model: int, d_hidden: int, num_experts: int, dtype=jnp.float32
) -> MoEParams:
    kg, k1, k2 = jax.random.split(key, 3)
    s_in = 1.0 / jnp.sqrt(d_model)
    s_hid = 1.0 / jnp.sqrt(d_hidden)
    return MoEParams(
        wg=(jax.random.normal(kg, (d_model, num_experts)) * s_in).astype(dtype),
        w1=(
            jax.random.normal(k1, (num_experts, d_model, d_hidden)) * s_in
        ).astype(dtype),
        b1=jnp.zeros((num_experts, d_hidden), dtype),
        w2=(
            jax.random.normal(k2, (num_experts, d_hidden, d_model)) * s_hid
        ).astype(dtype),
        b2=jnp.zeros((num_experts, d_model), dtype),
    )


def place_moe_params(mesh, params: MoEParams) -> MoEParams:
    """Device-put params with EP shardings (experts split, router replicated)."""
    ex = NamedSharding(mesh, P(AXIS))
    rep = NamedSharding(mesh, P())
    return MoEParams(
        wg=jax.device_put(params.wg, rep),
        w1=jax.device_put(params.w1, ex),
        b1=jax.device_put(params.b1, ex),
        w2=jax.device_put(params.w2, ex),
        b2=jax.device_put(params.b2, ex),
    )


def _top_k_dispatch(gates, k: int, capacity: int):
    """Build dispatch mask (T, E, C) and combine weights (T, E, C).

    Sequential top-k with per-expert cumulative position counting
    (GShard alg. 1): choice j's slots start after the tokens already
    placed by choices < j. Tokens whose slot index >= capacity drop.

    Slot counting runs in float32 regardless of the gate dtype: bf16
    cumsum collides past 256 tokens, which would silently merge distinct
    tokens into one capacity slot.
    """
    t, e = gates.shape
    f32 = jnp.float32
    remaining = gates.astype(f32)
    counts = jnp.zeros((e,), f32)
    dispatch = jnp.zeros((t, e, capacity), f32)
    gate_sum = jnp.zeros((t,), f32)
    combine = jnp.zeros((t, e, capacity), f32)
    route_frac = jnp.zeros((e,), f32)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=1)  # (T,)
        onehot = jax.nn.one_hot(idx, e, dtype=f32)  # (T, E)
        # pre-capacity routed fraction: the load-balancing loss must see
        # the router's true assignment, not the post-drop dispatch, or
        # gradient pressure vanishes exactly when an expert overflows
        route_frac = route_frac + jnp.mean(onehot, axis=0) / k
        gate_j = jnp.sum(gates.astype(f32) * onehot, axis=1)  # (T,)
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts  # (T, E)
        counts = counts + jnp.sum(onehot, axis=0)
        slot = jnp.sum(pos * onehot, axis=1).astype(jnp.int32)  # (T,)
        keep = (slot < capacity).astype(f32)
        slot_oh = jax.nn.one_hot(slot, capacity, dtype=f32)
        d_j = (onehot * keep[:, None])[:, :, None] * slot_oh[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + gate_j[:, None, None] * d_j
        gate_sum = gate_sum + gate_j * keep
        remaining = remaining * (1.0 - onehot)
    if k > 1:
        # normalize surviving top-k gate weights to sum to 1 per token;
        # at k=1 keep the raw gate multiplier (Switch) — g/g == 1 would
        # cancel the router's task gradient exactly
        combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]
    dt = gates.dtype
    return dispatch.astype(dt), combine.astype(dt), route_frac.astype(dt)


def _moe_core(params: MoEParams, x, *, axis, n_exp, k, capacity_factor,
              activation):
    """Per-device routed-FFN body: x (T_local, D) -> (y, local aux loss).

    Runs inside shard_map; ``axis`` names the mesh axis the experts (and
    the two all-to-alls) live on.
    """
    if params.w1.shape[0] != 1:
        raise ValueError(
            f"MoE assumes one expert per device: num_experts must equal "
            f"the mesh's {axis!r} size ({n_exp}), got a per-device "
            f"block of {params.w1.shape[0]}"
        )
    t_local, d = x.shape
    capacity = max(1, int(capacity_factor * k * t_local / n_exp))
    gates = jax.nn.softmax(x @ params.wg, axis=-1)  # (T, E)
    dispatch, combine, route_frac = _top_k_dispatch(gates, k, capacity)
    # Switch aux loss E * sum_e(f_e * P_e) on the pre-capacity routed
    # fractions (caller pmean-averages over the mesh)
    mean_prob = jnp.mean(gates, axis=0)
    aux = n_exp * jnp.sum(route_frac * mean_prob)

    # dispatch: (T, D) x (T, E, C) -> (E, C, D), then one all-to-all so
    # device e holds every source shard's bucket for expert e
    buckets = jnp.einsum("td,tec->ecd", x, dispatch)
    buckets = lax.all_to_all(
        buckets, axis, split_axis=0, concat_axis=0, tiled=True
    )  # (E_src, C, D) on the device owning this expert
    h = activation(
        jnp.einsum("scd,dh->sch", buckets, params.w1[0]) + params.b1[0]
    )
    out = jnp.einsum("sch,hd->scd", h, params.w2[0]) + params.b2[0]
    # return trip + weighted combine back to token order (combine is
    # zero on unoccupied capacity slots, so padding never leaks)
    out = lax.all_to_all(
        out, axis, split_axis=0, concat_axis=0, tiled=True
    )  # (E, C, D) indexed by expert again
    y = jnp.einsum("ecd,tec->td", out, combine)
    return y, aux


def _param_specs(axis):
    return MoEParams(P(), P(axis), P(axis), P(axis), P(axis))


def moe_apply(mesh, *, k: int = 2, capacity_factor: float = 2.0,
              activation=jax.nn.relu):
    """Build the jitted EP MoE forward: fn(params, x) -> (y, aux_loss).

    ``x`` is ``(T, D)`` tokens sharded over the expert axis (data-sharded);
    ``y`` has the same sharding. ``aux_loss`` is the Switch load-balancing
    loss ``E * sum_e(f_e * P_e)`` (floor 1.0 when perfectly balanced),
    already averaged over the mesh.
    """
    n_exp = mesh.shape[AXIS]

    def per_device(params: MoEParams, x):
        y, aux = _moe_core(
            params, x, axis=AXIS, n_exp=n_exp, k=k,
            capacity_factor=capacity_factor, activation=activation,
        )
        return y, lax.pmean(aux, AXIS)

    fn = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(_param_specs(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def moe_ffn(mesh, *, expert_axis=None, token_spec=None, k: int = 2,
            capacity_factor: float = 2.0, activation=jax.nn.gelu):
    """MoE FFN over (B, T, D) activations, for use *inside* a jitted model
    on a multi-axis mesh (e.g. the transformer's (data, model) mesh with
    experts on the model axis and the batch data-sharded).

    Tokens are *replicated* over the expert axis in this layout (the
    transformer TP stack keeps activations unsharded on the model axis),
    so unlike :func:`moe_apply` there is nothing to all-to-all: every
    device routes the full local token set, applies only its *own*
    expert to that expert's capacity bucket, and one ``psum`` over the
    expert axis sums the per-expert partial outputs. FFN FLOPs per
    device are 1/E of the total — true expert-parallel scaling.

    Returns ``fn(params, x) -> (y, aux)`` (not jitted — call it inside
    the surrounding jit).
    """
    axis = expert_axis or mesh_lib.MODEL_AXIS
    n_exp = mesh.shape[axis]
    token_spec = token_spec or P(mesh_lib.DATA_AXIS, None, None)

    def per_device(params: MoEParams, x):
        if params.w1.shape[0] != 1:
            raise ValueError(
                f"MoE assumes one expert per device: num_experts must "
                f"equal the mesh's {axis!r} size ({n_exp}), got a "
                f"per-device block of {params.w1.shape[0]}"
            )
        b, t, d = x.shape
        xt = x.reshape(b * t, d)
        capacity = max(1, int(capacity_factor * k * b * t / n_exp))
        gates = jax.nn.softmax(xt @ params.wg, axis=-1)
        dispatch, combine, route_frac = _top_k_dispatch(gates, k, capacity)
        aux = n_exp * jnp.sum(route_frac * jnp.mean(gates, axis=0))
        # this device's expert only: slice its dispatch/combine columns
        e = lax.axis_index(axis)
        d_e = lax.dynamic_index_in_dim(dispatch, e, axis=1, keepdims=False)
        c_e = lax.dynamic_index_in_dim(combine, e, axis=1, keepdims=False)
        bucket = jnp.einsum("td,tc->cd", xt, d_e)  # (C, D)
        h = activation(bucket @ params.w1[0] + params.b1[0])
        out = h @ params.w2[0] + params.b2[0]  # (C, D)
        y = jnp.einsum("cd,tc->td", out, c_e)  # this expert's share
        y = lax.psum(y, axis)
        return (
            y.reshape(b, t, d),
            lax.pmean(aux, tuple(mesh.axis_names)),
        )

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(_param_specs(axis), token_spec),
        out_specs=(token_spec, P()),
        check_vma=False,
    )


def moe_reference(params: MoEParams, x, *, k: int = 2,
                  activation=jax.nn.relu):
    """Unsharded single-device reference (no capacity limit) for testing:
    every token is processed by its true top-k experts."""
    gates = jax.nn.softmax(x @ params.wg, axis=-1)
    _, top_idx = lax.top_k(gates, k)  # (T, k)
    top_gates = jnp.take_along_axis(gates, top_idx, axis=1)
    if k > 1:  # k=1 keeps the raw gate multiplier (Switch)
        top_gates = top_gates / jnp.sum(top_gates, axis=1, keepdims=True)

    def expert_out(e, xt):
        h = activation(xt @ params.w1[e] + params.b1[e])
        return h @ params.w2[e] + params.b2[e]

    def per_token(xt, idx, g):
        outs = jnp.stack([expert_out(idx[j], xt) for j in range(k)])
        return jnp.sum(g[:, None] * outs, axis=0)

    return jax.vmap(per_token)(x, top_idx, top_gates)


# -- experts held as a chip's share ------------------------------------------
#
# The layer below is what expert parallelism asks of one chip, without the
# exchange: it is TOLD which experts it holds (``first`` .. ``first`` +
# E_held - 1 of ``n_total``), routes over all of them as the model
# publishes, and computes the part of the result its own experts give. No
# capacity, no dropped token, static shapes: the token-expert pairs that
# land on a held expert are sorted by expert and go through one grouped
# product per projection (``lax.ragged_dot``: XLA:TPU lowers it to a Mosaic
# grouped matmul that streams each hit expert's weights once). The same
# code serves a 64-row decode step and a 1,024-row prefill chunk.


def route_top_k(h, router, *, k: int, scale: float, score: str = "softmax"):
    """Score-then-top-k routing over every published expert: ``score``
    ``"softmax"`` (over all experts) or ``"sigmoid"`` (each expert
    alone). Both grow with the logit, so they select the same set and
    differ in the weights.

    ``h`` (N, D), ``router`` (D, E_total). The product, the scores and
    the weights are float32 whatever the compute dtype: a bf16 product
    moves a token's k-th and (k+1)-th expert past each other far more
    often than the rounding of the activations does. Returns ``(ids,
    weights)``, both (N, k): the k largest scores' experts and ``scale
    * s_e / sum_{e' in top k} s_e'`` (a sigmoid's sum + 1e-20)."""
    logits = jnp.dot(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if score == "softmax":
        top_s, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return top_i, scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    if score != "sigmoid":
        raise ValueError(f"score is 'softmax' or 'sigmoid', got {score!r}")
    top_s, top_i = lax.top_k(jax.nn.sigmoid(logits), k)
    return top_i, scale * top_s / (
        jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)


def swiglu(h, w_gate, w_up, w_down):
    """``(silu(h w_gate) * (h w_up)) w_down`` over (..., D) rows."""
    a = jax.nn.silu(h @ w_gate) * (h @ w_up)
    return a @ w_down


def moe_held_ffn(h, router, w_gate, w_up, w_down, *, first: int, k: int,
                 scale: float, live=None, score: str = "softmax",
                 grouped: str = "ragged"):
    """The held experts' part of a routed SwiGLU layer, routed by
    :func:`route_top_k` (``score``: softmax or sigmoid, then top k).

    ``h`` (N, D) rows; ``router`` (D, E_total); ``w_gate`` / ``w_up``
    (E_held, D, F) and ``w_down`` (E_held, F, D): experts ``first`` ..
    ``first + E_held - 1``. ``live`` (N,) bool, optional: rows nobody
    reads (a free serving slot) are routed nowhere. Returns ``(y,
    counts)``: ``y`` (N, D) = sum over the token's top-k experts THAT ARE
    HELD of ``w_e F_e(h)`` (nothing stands in for the others), and
    ``counts`` int32 (3,): token-expert pairs computed here, pairs routed
    in all (k x live rows), held experts with at least one row.

    ``grouped`` says what computes the experts' SwiGLU over the sorted
    pairs (``models.transformer.moe_grouped`` chooses): ``"ragged"``,
    three ``lax.ragged_dot`` calls; ``"streamed"``, one
    ``moe_ragged_dot`` kernel, which streams each hit expert's weights
    once and holds its few rows in VMEM (the pairs laid out with each
    expert's rows at a multiple of ``MOE_CHUNK``). The same numbers
    either way, to the rounding of the float32 sums."""
    n, d = h.shape
    e_held = w_gate.shape[0]
    ids, weights = route_top_k(h, router, k=k, scale=scale, score=score)
    here = (ids >= first) & (ids < first + e_held)
    if live is not None:
        here = here & live[:, None]
    # pairs elsewhere sort behind every group and belong to none
    local = jnp.where(here, ids - first, e_held).reshape(-1)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=e_held + 1)[:e_held].astype(jnp.int32)
    # back: the product's row of each pair in token order, for a gather
    # and then a sum over the token's k pairs in one fixed order (a
    # scatter-add's is not); rows of pairs on no held expert hold
    # whatever the product left there, so they are selected away, not
    # multiplied by zero
    if grouped == "streamed":
        out, back = _streamed(h, w_gate, w_up, w_down, local[order], order,
                              sizes, k)
    elif grouped == "ragged":
        # rows of the grouped products: the sorted pairs, padded to an
        # ODD number of 128-row tiles. XLA:TPU tiles a ragged dot's rows
        # by the largest of 512 / 256 / 128 that divides them, and a tile
        # computes all its rows for every group it touches: at 512 rows a
        # tile a 1,024-token chunk's products were bound by that waste
        # (2.7 ms a call against 1.3 ms for a decode step's 640 rows, on
        # a v5e)
        tiles = -(-n * k // 128) | 1
        rows = jnp.zeros((tiles * 128,), order.dtype).at[:n * k].set(
            order // k)
        x = h[rows]  # (tiles * 128, D); the padding rows belong to no group
        a = jax.nn.silu(lax.ragged_dot(x, w_gate, sizes)) * lax.ragged_dot(
            x, w_up, sizes
        )
        out = lax.ragged_dot(a.astype(h.dtype), w_down, sizes)[:n * k]
        back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    else:
        raise ValueError(
            f"grouped is 'ragged' or 'streamed', got {grouped!r}")
    out = jnp.where(here[..., None], out[back].reshape(n, k, d), 0)
    y = jnp.einsum(
        "nkd,nk->nd", out, weights.astype(out.dtype),
        preferred_element_type=jnp.float32,
    )
    n_live = n if live is None else jnp.sum(live)
    counts = jnp.stack([
        jnp.sum(here), k * n_live, jnp.sum(sizes > 0),
    ]).astype(jnp.int32)
    return y.astype(h.dtype), counts


@functools.partial(jax.jit, static_argnames=("k",))
def _streamed(h, w_gate, w_up, w_down, expert, order, sizes, k: int):
    """The held experts' SwiGLU of the sorted pairs by ``moe_ragged_dot``.
    ``expert`` (N*k,): the held expert of each sorted pair (``E_held``:
    none). Returns ``(out, back)``: the kernel's rows and each pair's
    row among them, in token order (0 for a pair on no held expert).
    Jitted, so that a step program traces it once for all its expert
    layers and substeps."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        moe_group_starts,
        moe_ragged_dot,
        moe_stream_rows,
    )

    pairs, e_held = expert.shape[0], sizes.shape[0]
    p = moe_stream_rows(pairs, e_held)
    held = expert < e_held
    e = jnp.minimum(expert, e_held - 1)
    # group e's sorted pairs start at row cumsum(sizes)[e] - sizes[e];
    # in the kernel's layout at moe_group_starts(sizes)[e]
    shift = moe_group_starts(sizes) - (jnp.cumsum(sizes) - sizes)
    at = jnp.where(held, jnp.arange(pairs) + shift[e], p)
    rows = jnp.zeros((p,), order.dtype).at[at].set(order // k, mode="drop")
    out = moe_ragged_dot(h[rows], w_gate, w_up, w_down, sizes)
    return out, jnp.zeros_like(order).at[order].set(jnp.where(held, at, 0))
