"""Pallas TPU kernels for hot ops.

Two kernels, each with an ``interpret=True`` path so tests run on CPU and
the lowered path engages on real TPU:

- ``flash_attention``: blocked attention forward keeping the running
  softmax state in VMEM scratch — one HBM pass over K/V per Q block.
  The online-softmax math matches ``ops.attention.blocked_attention``.
- ``fused_embedding_dot``: the Word2Vec HS inner product batch
  (gather rows -> masked sigmoid dots) fused into one VMEM-resident
  kernel — the hot read side of InMemoryLookupTable.iterateSample.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret() -> bool:
    """Compile for the TPU, interpret on the CPU (the test backend).
    Any other backend is an error: a kernel quietly interpreted on a
    device nobody chose would run, slowly, and report nothing."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default jax backend is {backend!r}"
    )


# -- flash attention ----------------------------------------------------------
#
# Streamed-grid design: the grid is (batch*heads, q_blocks, kv_blocks)
# with the kv dimension sequential ("arbitrary"), so VMEM holds only one
# (block_q, d) Q tile, one (block_k, d) K/V tile and the running softmax
# state in scratch — O(block) VMEM regardless of T. (The previous design
# handed each kernel instance full-length K/V refs, which hit the 16MB
# scoped-VMEM limit at T=8192.)

def _causal_bias(q_start, k_start, block_q: int, block_k: int):
    """0 where col <= row, -inf above the diagonal (absolute positions)."""
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(cols <= rows, 0.0, -jnp.inf).astype(jnp.float32)


def _kv_block_visible(q_start, k_start, block_q: int):
    """Causal visibility of a KV block to a Q block: it contributes iff
    its first column is <= the Q block's last row. Shared by the forward
    and fused-backward kernels so the skip bound cannot drift."""
    return k_start <= q_start + block_q - 1


def _kv_block_fully_visible(q_start, k_start, block_q: int, block_k: int):
    """True when every (row, col) pair in the tile is causally visible
    (the tile lies entirely on/below the diagonal) — such tiles skip the
    bias construction entirely. The O(T^2) softmax bookkeeping is VPU-
    bound at long T (measured ~half the kernel time at T=8192), and the
    two iota builds + compare + add of the bias are a meaningful share;
    only diagonal-crossing tiles (a 1/n_blocks fraction) pay them."""
    return k_start + block_k - 1 <= q_start


def _causal_dispatch(
    compute, causal: bool, q_start, k_start, block_q: int, block_k: int
):
    """Emit ``compute(masked)`` under the tile's causal class — fully
    visible (no bias), diagonal-crossing (bias), or invisible (skipped).
    ONE dispatch shared by the forward and fused-backward kernels so the
    masking classes cannot drift between the two."""
    if not causal:
        compute(False)
        return
    full = _kv_block_fully_visible(q_start, k_start, block_q, block_k)

    @pl.when(full)
    def _full():
        compute(False)

    @pl.when(
        jnp.logical_and(
            _kv_block_visible(q_start, k_start, block_q),
            jnp.logical_not(full),
        )
    )
    def _diag():
        compute(True)


# backward dq strategy: True = one bf16 partial plane per KV block,
# summed in f32 outside the kernel (no HBM read-modify-write); False =
# f32 rmw accumulation in the dq output block across kv revisits
_DQ_PARTIALS = True
# debugging escape hatch (ADVICE r4): store the dq partial planes in
# f32 instead of the input dtype, restoring the rmw path's backward
# precision at 2x the plane HBM. Flip when triaging suspected grad
# corruption on device — if f32 partials fix it, the bf16 ds/plane
# rounding is implicated; if not, look at the accumulation structure.
# (The routine guard is bench._verify_flash_grads, which runs the
# production bwd geometry against dense autodiff on the real TPU every
# bench round; interpret-mode CPU tests cannot observe device drift.)
_DQ_PARTIALS_F32 = False


def _dim_semantics(interpret, semantics=("parallel", "parallel", "arbitrary")):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _softmax_fold(s, m_s, l_s, at):
    """Fold one f32 score tile into the running row maximum ``m_s[at]``
    and row sum ``l_s[at]`` of the online softmax. Returns the tile's
    unnormalised probabilities, the factor that rescales what was
    accumulated under the old maximum, and the new maximum, which the
    caller stores once it has rescaled its accumulator. ONE update shared
    by the forward kernels of both layouts."""
    m_prev = m_s[at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_s[at] = corr * l_s[at] + jnp.sum(p, axis=-1)
    return p, corr, m_new


def _flash_fwd_stream_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
    *, block_q: int, block_k: int, n_k: int, scale: float, causal: bool,
):
    """One (q block, kv block) grid step of the online-softmax forward."""
    kk = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    k_start = kk * block_k

    @pl.when(kk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def compute(masked: bool):
        # scale folded into the Q tile: one multiply over (block_q, d)
        # instead of a full (block_q, block_k) pass on the f32 scores —
        # the softmax bookkeeping is VPU-bound at long T
        q = (q_ref[0] * jnp.asarray(scale, q_ref.dtype))
        s = jnp.dot(q, k_ref[0].T, preferred_element_type=jnp.float32)
        if masked:
            s = s + _causal_bias(q_start, k_start, block_q, block_k)
        p, corr, m_new = _softmax_fold(s, m_s, l_s, (slice(None), 0))
        # PV dot with p cast to the value dtype (bf16 on TPU): operands
        # must stay low-precision to hit the MXU at full rate — an f32
        # matmul runs at a fraction of peak on v5e. The accumulator is
        # f32 (preferred_element_type + f32 scratch), the standard
        # flash-bf16 recipe. (A bf16 sub/exp variant measured
        # perf-NEUTRAL on v5e while costing ~1% extra error and an
        # lse inconsistent with the backward's f32 p recompute — not
        # worth it.)
        acc_s[:] = corr[:, None] * acc_s[:] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )
        m_s[:, 0] = m_new

    _causal_dispatch(compute, causal, q_start, k_start, block_q, block_k)

    @pl.when(kk == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, 0], 1e-30)
        o_ref[0] = (acc_s[:] / l[:, None]).astype(o_ref.dtype)
        # lse carried as (bh, t, 1): a 2-D (bh, t) output would need a
        # (1, block_q) block, which Mosaic rejects (second-to-last dim
        # must be a multiple of 8 or the full array dim)
        lse_ref[0, :, 0] = (m_s[:, 0] + jnp.log(l)).astype(jnp.float32)


def _flash_fwd_call(qf, kf, vf, block_q, block_k, interpret, causal):
    bh, t, d = qf.shape
    scale = 1.0 / (d**0.5)
    n_k = t // block_k
    kernel = functools.partial(
        _flash_fwd_stream_kernel, block_q=block_q, block_k=block_k,
        n_k=n_k, scale=scale, causal=causal,
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ),
        grid=(bh, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_dim_semantics(interpret),
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)


def _flash_bwd_tile(q, k_blk, v_blk, do, lse, delta, dk_s, dv_s, scale,
                    bias=None):
    """One (q block, kv block) tile of the fused backward, shared by the
    kernels of both layouts: ``q`` arrives scaled, ``bias`` (if the tile
    crosses the diagonal) builds the causal bias. Adds the tile's share
    to the ``dk_s`` / ``dv_s`` accumulators and returns its f32 dq
    contribution."""
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
    if bias is not None:
        s = s + bias()
    p = jnp.exp(s - lse[:, None])
    dv_s[:] = dv_s[:] + jnp.dot(
        p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
    )
    dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
    # ds in the storage dtype: cast p and (dp - delta) BEFORE the
    # multiply instead of multiplying f32 and casting the product —
    # one fewer full-tile f32 pass; measured part of a -4% bench win
    # at T=8192 (r4), grad error covered by the on-device parity
    # gate (bench._verify_flash_grads). (An exp2/log2e fold was
    # also tried and measured neutral-to-negative in situ — exp
    # stays.)
    ds = p.astype(q.dtype) * (dp - delta[:, None]).astype(q.dtype)
    dk_s[:] = dk_s[:] + jnp.dot(
        ds.T, q, preferred_element_type=jnp.float32
    )
    return jnp.dot(
        ds, k_blk, preferred_element_type=jnp.float32
    ) * scale


def _store_dq(dq_ref, dq_c, kk, dq_partials: bool):
    """Place a tile's dq contribution (see ``_DQ_PARTIALS``)."""
    if dq_partials:
        # one clean write per (kv, q) cell into this kv block's
        # partial plane; the caller sums planes in f32. No HBM
        # read-modify-write at all — the non-consecutive-revisit
        # accumulation pattern (ADVICE r3 medium) is gone.
        dq_ref[0, 0] = dq_c.astype(dq_ref.dtype)
    else:
        @pl.when(kk == 0)
        def _dq_init():
            dq_ref[0] = dq_c

        @pl.when(kk != 0)
        def _dq_acc():
            dq_ref[0] = dq_ref[0] + dq_c


def _zero_hidden_dq(dq_ref, q_start, k_start, block_q: int):
    """In partials mode a tile the causal dispatch skipped still owns a
    block of its kv block's dq plane: zero it."""
    @pl.when(jnp.logical_not(_kv_block_visible(q_start, k_start, block_q)))
    def _dq_zero():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dk_s, dv_s,
    *, block_q: int, block_k: int, n_q: int, scale: float, causal: bool,
    dq_partials: bool = False,
):
    """One (kv block, q block) step of the FUSED backward pass.

    The split dQ / dK-dV kernels each recomputed s, p and dp — 7 full
    T^2 matmul passes plus a double run of the VPU-bound softmax
    bookkeeping (bias, exp, sub). Fusing computes them once: 5 matmul
    passes and one exp per tile. Grid is (bh, kv_blocks, q_blocks), Q
    innermost: dK/dV accumulate in VMEM scratch and finalize once per
    KV block; the dQ tile accumulates in its f32 HBM output block,
    revisited once per KV block (read-modify-write; kv block 0 — always
    causally visible — initializes it).
    """
    kk = pl.program_id(1)
    qq = pl.program_id(2)
    k_start = kk * block_k
    q_start = qq * block_q

    @pl.when(qq == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def compute(masked: bool):
        # operands stay in their storage dtype (bf16 on TPU) — only the
        # accumulation is f32 (preferred_element_type); f32 matmul
        # operands would fall off the MXU fast path. Scale folds into
        # the Q tile (s = (q*scale)@k^T), which also absorbs the dk
        # scale (dk = scale * ds^T @ q = ds^T @ (q*scale)); the dq
        # contribution is rescaled on its small (block_q, d) tile.
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
        do = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        dq_c = _flash_bwd_tile(
            q, k_blk, v_blk, do, lse, delta, dk_s, dv_s, scale,
            functools.partial(
                _causal_bias, q_start, k_start, block_q, block_k
            ) if masked else None,
        )
        _store_dq(dq_ref, dq_c, kk, dq_partials)

    # invisible tiles are skipped wholesale (in rmw mode their dq tile
    # is left untouched — kv block 0, always visible, initialized it;
    # in partials mode their plane block is zeroed below)
    _causal_dispatch(compute, causal, q_start, k_start, block_q, block_k)
    if dq_partials and causal:
        _zero_hidden_dq(dq_ref, q_start, k_start, block_q)

    @pl.when(qq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
) -> jax.Array:
    """(B, T, H, D) attention, pallas-blocked. T must divide by blocks."""
    b, t, h, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0
    interpret = _default_interpret() if interpret is None else interpret

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    out, _ = _flash_fwd_call(qf, kf, vf, block_q, block_k, interpret, causal)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash_bhtd(
    qf, kf, vf, block_q, block_k, interpret, causal,
    bwd_block_q=None, bwd_block_k=None,
):
    out, _ = _flash_fwd_call(qf, kf, vf, block_q, block_k, interpret, causal)
    return out


def _flash_fwd_rule(
    qf, kf, vf, block_q, block_k, interpret, causal,
    bwd_block_q=None, bwd_block_k=None,
):
    out, lse = _flash_fwd_call(qf, kf, vf, block_q, block_k, interpret, causal)
    # name the residuals so a surrounding jax.checkpoint policy can mark
    # them saveable: without this, rematerialization re-runs the whole
    # pallas forward inside the backward pass just to regenerate lse
    # (q/k/v are dot outputs the dots policy already saves) — measured
    # 16.5ms/step at GPT-2-small scale
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (qf, kf, vf, out, lse)


def _flash_bwd_rule(
    block_q, block_k, interpret, causal, bwd_block_q, bwd_block_k, res, do
):
    qf, kf, vf, out, lse = res
    bh, t, d = qf.shape
    scale = 1.0 / (d**0.5)
    # the backward's compute/DMA balance differs from the forward's (5
    # dots + an f32 rmw dq tile vs 2 dots): it gets its own block shape
    block_q = bwd_block_q or block_q
    block_k = bwd_block_k or block_k
    n_q, n_k = t // block_q, t // block_k
    # delta_i = <dO_i, O_i> — the softmax normalizer correction; kept
    # (bh, t, 1) for the same Mosaic block-shape rule as lse
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[..., None]

    # partials memory scales with n_k (one bf16 plane per KV block):
    # fine at the model's tuned blocks (n_k <= 8) but a 32x HBM blowup
    # for a caller using the public default block_k=128 at long T —
    # those fall back to the rmw accumulation path
    dq_partials = _DQ_PARTIALS and n_k <= 8
    if dq_partials:
        plane_dtype = jnp.float32 if _DQ_PARTIALS_F32 else qf.dtype
        dq_shape = jax.ShapeDtypeStruct((n_k, bh, t, d), plane_dtype)
        dq_spec = pl.BlockSpec(
            (1, 1, block_q, d), lambda i, j, qq: (j, i, qq, 0)
        )
    else:
        # dq accumulates across kv blocks in its HBM tile: f32 so
        # repeated read-modify-writes don't round at bf16 (cast once
        # below, matching the old scratch-accumulator precision)
        dq_shape = jax.ShapeDtypeStruct((bh, t, d), jnp.float32)
        dq_spec = pl.BlockSpec((1, block_q, d), lambda i, j, qq: (i, qq, 0))
    dq_raw, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            n_q=n_q, scale=scale, causal=causal,
            dq_partials=dq_partials,
        ),
        out_shape=(
            dq_shape,
            jax.ShapeDtypeStruct((bh, t, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, t, d), vf.dtype),
        ),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, qq: (i, qq, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, qq: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, qq: (i, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j, qq: (i, qq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, qq: (i, qq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, qq: (i, qq, 0)),
        ],
        out_specs=(
            dq_spec,
            pl.BlockSpec((1, block_k, d), lambda i, j, qq: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, qq: (i, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        # the kv dim must be SEQUENTIAL (not "parallel") in rmw mode:
        # dq tiles are revisited and accumulated across it — a megacore
        # split over kv (v4/v5p) would race the read-modify-writes
        compiler_params=_dim_semantics(
            interpret, ("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd",
    )(qf, kf, vf, do, lse, delta)
    if dq_partials:
        dq = jnp.sum(dq_raw.astype(jnp.float32), axis=0).astype(qf.dtype)
        return dq, dk, dv
    return dq_raw.astype(qf.dtype), dk, dv


_flash_bhtd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _blocks_within(t: int, *blocks):
    """Each block size clamped to the sequence length ``t``, which it
    has to divide; ``None`` (a backward block that inherits the
    forward's) stays ``None``."""
    clamped = tuple(b if b is None else min(b, t) for b in blocks)
    assert all(b is None or t % b == 0 for b in clamped), (t, blocks)
    return clamped


def flash_attention_trainable(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    layout: str = "bthd",
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
) -> jax.Array:
    """Differentiable flash attention: (B, T, H, D) in and out
    (``layout="bhtd"``: (B, H, T, D) in and out — a free reshape into
    the kernel's (B*H, T, D) view, no physical transpose).

    Forward saves only O and the per-row logsumexp; the backward pass is
    two more pallas kernels (dQ; dK/dV) that stream blocks and recompute
    probabilities — O(T) memory instead of the T x T attention matrix that
    plain autodiff through dense attention would save.
    """
    if layout == "bhtd":
        b, h, t, d = q.shape
    else:
        b, t, h, d = q.shape
    block_q, block_k, bwd_block_q, bwd_block_k = _blocks_within(
        t, block_q, block_k, bwd_block_q, bwd_block_k
    )
    interpret = _default_interpret() if interpret is None else interpret
    if layout == "bhtd":
        qf, kf, vf = (a.reshape(b * h, t, d) for a in (q, k, v))
    else:
        qf, kf, vf = (
            a.transpose(0, 2, 1, 3).reshape(b * h, t, d) for a in (q, k, v)
        )
    out = _flash_bhtd(
        qf, kf, vf, block_q, block_k, interpret, causal,
        bwd_block_q, bwd_block_k,
    )
    out = out.reshape(b, h, t, d)
    return out if layout == "bhtd" else out.transpose(0, 2, 1, 3)


# -- flash attention, packed layout --------------------------------------------
#
# The training block's second path: q, k, v, o and their cotangents are
# all (B, T, H*K), the layout a ``[B*T, D] x [D, H*K]`` projection writes
# and ``wo`` reads, so no transpose stands between the products and the
# kernels. A block is one 128-lane group of WHOLE heads (two at K = 64)
# of one row block: lane-dense, where a (B*H, T, 64) block half-fills
# every 128-lane tile it moves. Inside a grid step each head of the group
# is computed from the 128-lane tiles with the other heads' lanes zeroed
# in one operand of every product (a contraction of 128 with 64 exact
# zeros: the same sum, and on a 128 x 128 MXU the same passes) and its
# lanes of the result kept by a lane select. The tile math, the causal
# dispatch and the dq handling are the (B*H, T, K) kernels' own.

_PACK_LANES = 128


def _band_rows(block: int) -> int:
    """Rows of one causal band of a ``block`` x ``block`` tile that
    crosses the diagonal: a quarter of the tile in whole 128-lane tiles
    (every band's visible prefix of the key columns is then a whole
    number of lane tiles), the largest such that divides the tile, and
    128 at least: 1,024 -> 4 bands of 256, 512 -> 4 of 128, 256 -> 2 of
    128. A tile of 128 rows or fewer, or one that is no multiple of 128,
    is one band, which is the unbanded body. Measured at 8 x 16 x 1,024
    x 64 on a v5e (``scripts/flash_train_bench.py``; PERF.md, PR 35)."""
    if block % _PACK_LANES:
        return block
    rows = max(_PACK_LANES, block // 4 // _PACK_LANES * _PACK_LANES)
    while block % rows:
        rows -= _PACK_LANES
    return rows


def _diagonal_bands(block_q: int, block_k: int, masked: bool):
    """The packed kernels' walk over one tile, ``(first row, rows,
    visible key columns)`` a band. A causal tile that crosses the
    diagonal with ``block_q == block_k`` (its first row is then its first
    column) is cut into bands of ``_band_rows`` query rows, band ``r``
    seeing the key columns ``[0, (r + 1) rows)``: what lies above the
    diagonal is never multiplied, exponentiated or summed, down to a
    band. Every other tile is one band over all its columns."""
    rows = _band_rows(block_q) if masked and block_q == block_k else block_q
    if rows == block_q:
        return [(0, block_q, block_k)]
    return [(r, rows, r + rows) for r in range(0, block_q, rows)]


def flash_computed_share(
    t: int, block_q: int, block_k: int, causal: bool
) -> float:
    """The share of the ``t`` x ``t`` score square that a packed flash
    kernel multiplies at these blocks: 1.0 without ``causal``; with it,
    nothing of a tile above the diagonal, all of a tile below it, and of
    a tile that crosses it the bands of ``_diagonal_bands`` (0.5 is the
    triangle; one causal tile of 1,024 rows reads 0.625)."""
    if not causal:
        return 1.0
    area = 0
    for q_start in range(0, t, block_q):
        for k_start in range(0, t, block_k):
            if k_start > q_start + block_q - 1:
                continue
            crosses = k_start + block_k - 1 > q_start
            area += sum(
                rows * cols
                for _, rows, cols in _diagonal_bands(block_q, block_k, crosses)
            )
    return area / (t * t)


def _band_bias(bands, masked: bool, q_start, k_start, block_q: int,
               block_k: int):
    """The causal bias of each band of a tile: none unless it crosses
    the diagonal (``masked``). The bands of a cut tile share one ``rows``
    x ``rows`` triangle, which lies over the last ``rows`` of a band's
    visible columns."""
    if not masked:
        return [None] * len(bands)
    if len(bands) == 1:
        return [_causal_bias(q_start, k_start, block_q, block_k)]
    rows = bands[0][1]
    corner = _causal_bias(0, 0, rows, rows)
    return [
        corner if cols == rows else jnp.concatenate(
            [jnp.zeros((rows, cols - rows), corner.dtype), corner], axis=1)
        for _, _, cols in bands
    ]


def _head_lanes(shape, head_dim: int, a: int):
    """Bool mask of ``shape`` (.., 128): the lanes of head ``a`` of a
    128-lane group."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return lane // head_dim == a


def _flash_fwd_packed_lone_tile(
    q_ref, k_ref, v_ref, o_ref, lse_ref, bands, head_dim: int, scale: float
):
    """The forward of a causal tile that is the only one of its rows
    (``n_k == 1``) and is cut into ``bands``: there is no running state
    to fold into, so each band is finished where it is computed, and its
    score tile is held TRANSPOSED, keys on sublanes and queries on lanes.
    The row maximum and the row sum then reduce over sublanes on the VPU
    and stay lane-major, where a (queries, keys) tile pays two lane
    reductions a query row whatever the band sees, which was three
    fifths of the forward (PERF.md, PR 35). The same products at the same
    precision; the row sum adds in another order, so the output can
    differ from the (queries, keys) body's in its last bf16 bit."""
    heads = _PACK_LANES // head_dim
    q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
    k_blk = k_ref[0]
    v_t = v_ref[0].T  # (128, block): one transpose a body
    rows = bands[0][1]
    key = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    query = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    corner = jnp.where(key <= query, 0.0, -jnp.inf).astype(jnp.float32)
    # a head's lanes of o are its sublanes of o^T
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_PACK_LANES, rows), 0)
    for r0, _, cols in bands:
        band = slice(r0, r0 + rows)
        q_r, k_r = q[band], k_blk[:cols]
        bias = corner if cols == rows else jnp.concatenate(
            [jnp.zeros((cols - rows, rows), corner.dtype), corner])
        o_t = jnp.zeros((_PACK_LANES, rows), jnp.float32)
        l_t = jnp.ones((_PACK_LANES, rows), jnp.float32)
        lse_t = jnp.zeros((_PACK_LANES, rows), jnp.float32)
        for a in range(heads):
            mine = _head_lanes(q_r.shape, head_dim, a)
            s_t = jax.lax.dot_general(  # k q^T, (cols, rows)
                k_r, jnp.where(mine, q_r, jnp.zeros_like(q_r)),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + bias
            m = jnp.max(s_t, axis=0, keepdims=True)
            p_t = jnp.exp(s_t - m)
            l = jnp.maximum(jnp.sum(p_t, axis=0, keepdims=True), 1e-30)
            pv_t = jnp.dot(  # v^T p^T, (128, rows)
                v_t[:, :cols], p_t.astype(v_t.dtype),
                preferred_element_type=jnp.float32,
            )
            mine_t = sublane // head_dim == a
            o_t = jnp.where(mine_t, pv_t, o_t)
            l_t = jnp.where(mine_t, l, l_t)
            lse_t = jnp.where(sublane == a, m + jnp.log(l), lse_t)
        o_ref[0, band, :] = (o_t / l_t).T.astype(o_ref.dtype)
        lse_ref[0, 0, band, :] = lse_t.T[:, :heads]


def _flash_fwd_packed_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
    *, block_q: int, block_k: int, n_k: int, head_dim: int, scale: float,
    causal: bool,
):
    """One (batch, lane group, q block, kv block) grid step of the
    online-softmax forward over a group of ``128 // head_dim`` heads."""
    if causal and n_k == 1:
        bands = _diagonal_bands(block_q, block_k, True)
        if len(bands) > 1:  # block_q == block_k: the rows' only tile
            _flash_fwd_packed_lone_tile(
                q_ref, k_ref, v_ref, o_ref, lse_ref, bands, head_dim, scale)
            return
    kk = pl.program_id(3)
    q_start = pl.program_id(2) * block_q
    k_start = kk * block_k
    heads = _PACK_LANES // head_dim

    @pl.when(kk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def compute(masked: bool):
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        bands = _diagonal_bands(block_q, block_k, masked)
        # one bias for every head of the group
        biases = _band_bias(bands, masked, q_start, k_start, block_q, block_k)
        for (r0, rows, cols), bias in zip(bands, biases):
            band = slice(r0, r0 + rows)
            q_r, k_r, v_r = q[band], k_blk[:cols], v_blk[:cols]
            acc = acc_s[band]
            for a in range(heads):
                mine = _head_lanes(q_r.shape, head_dim, a)
                s = jnp.dot(
                    jnp.where(mine, q_r, jnp.zeros_like(q_r)), k_r.T,
                    preferred_element_type=jnp.float32,
                )
                if masked:
                    s = s + bias
                at = (band, a)
                p, corr, m_new = _softmax_fold(s, m_s, l_s, at)
                pv = jnp.dot(
                    p.astype(v_r.dtype), v_r,
                    preferred_element_type=jnp.float32,
                )
                acc = jnp.where(mine, corr[:, None] * acc + pv, acc)
                m_s[at] = m_new
            acc_s[band] = acc

    _causal_dispatch(compute, causal, q_start, k_start, block_q, block_k)

    @pl.when(kk == n_k - 1)
    def _finalize():
        l_lanes = jnp.ones_like(acc_s)
        for a in range(heads):
            l = jnp.maximum(l_s[:, a], 1e-30)
            l_lanes = jnp.where(
                _head_lanes(l_lanes.shape, head_dim, a), l[:, None], l_lanes
            )
            lse_ref[0, 0, :, a] = (m_s[:, a] + jnp.log(l)).astype(
                jnp.float32
            )
        o_ref[0] = (acc_s[:] / l_lanes).astype(o_ref.dtype)


def _flash_fwd_packed_call(q, k, v, head_dim, block_q, block_k, interpret,
                           causal):
    b, t, hk = q.shape
    heads = _PACK_LANES // head_dim
    n_k = t // block_k
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_packed_kernel, block_q=block_q, block_k=block_k,
            n_k=n_k, head_dim=head_dim, scale=1.0 / (head_dim**0.5),
            causal=causal,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t, hk), q.dtype),
            # (.., T, heads of a group): a row's heads side by side, where
            # (.., T, 1) a head pads every row to 128 lanes once a head,
            # in HBM and in the kernel's VMEM alike
            jax.ShapeDtypeStruct(
                (b, hk // _PACK_LANES, t, heads), jnp.float32),
        ),
        grid=(b, hk // _PACK_LANES, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, _PACK_LANES), lambda i, g, j, kk: (i, j, g)),
            pl.BlockSpec(
                (1, block_k, _PACK_LANES), lambda i, g, j, kk: (i, kk, g)),
            pl.BlockSpec(
                (1, block_k, _PACK_LANES), lambda i, g, j, kk: (i, kk, g)),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, block_q, _PACK_LANES), lambda i, g, j, kk: (i, j, g)),
            pl.BlockSpec(
                (1, 1, block_q, heads), lambda i, g, j, kk: (i, g, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, heads), jnp.float32),
            pltpu.VMEM((block_q, heads), jnp.float32),
            pltpu.VMEM((block_q, _PACK_LANES), jnp.float32),
        ],
        compiler_params=_dim_semantics(
            interpret, ("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_fwd_packed",
    )(q, k, v)


def _flash_bwd_packed_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
    dq_ref, dk_ref, dv_ref, dk_s, dv_s,
    *, block_q: int, block_k: int, n_q: int, head_dim: int, scale: float,
    causal: bool, dq_partials: bool,
):
    """One (batch, lane group, kv block, q block) step of the fused
    backward over a group of heads. ``delta = <dO, O>`` per head is taken
    here from the O block (f32, as the (B*H, T, K) path takes it outside
    its kernel): a pass over (block_q, 128) beside (block_q, block_k)
    tiles, where outside it is a reduction over a 64-wide minor
    dimension and a transpose."""
    kk = pl.program_id(2)
    qq = pl.program_id(3)
    k_start = kk * block_k
    q_start = qq * block_q
    heads = _PACK_LANES // head_dim

    @pl.when(qq == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def compute(masked: bool):
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)
        do = do_ref[0]
        do_o = do.astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        bands = _diagonal_bands(block_q, block_k, masked)
        # one bias for every head of the group
        biases = _band_bias(bands, masked, q_start, k_start, block_q, block_k)
        dq_bands = []
        for (r0, rows, cols), tile in zip(bands, biases):
            band = slice(r0, r0 + rows)
            q_r, do_r, do_o_r = q[band], do[band], do_o[band]
            # a band adds to the rows of dk and dv that it sees
            dk_r, dv_r = (
                acc if cols == block_k else acc.at[:cols]
                for acc in (dk_s, dv_s)
            )
            bias = (lambda tile=tile: tile) if masked else None
            dq_c = jnp.zeros(q_r.shape, jnp.float32)
            for a in range(heads):
                mine = _head_lanes(q_r.shape, head_dim, a)
                # q and dO carry head a's lanes alone, so s, dp, dk and
                # dv are head a's; k's and v's other lanes meet zeros
                dq_a = _flash_bwd_tile(
                    jnp.where(mine, q_r, jnp.zeros_like(q_r)),
                    k_blk[:cols], v_blk[:cols],
                    jnp.where(mine, do_r, jnp.zeros_like(do_r)),
                    lse_ref[0, 0, band, a],
                    jnp.sum(jnp.where(mine, do_o_r, 0.0), axis=-1),
                    dk_r, dv_r, scale, bias,
                )
                dq_c = jnp.where(mine, dq_a, dq_c)
            dq_bands.append(dq_c)
        dq_c = dq_bands[0] if len(bands) == 1 else jnp.concatenate(dq_bands)
        _store_dq(dq_ref, dq_c, kk, dq_partials)

    _causal_dispatch(compute, causal, q_start, k_start, block_q, block_k)
    if dq_partials and causal:
        _zero_hidden_dq(dq_ref, q_start, k_start, block_q)

    @pl.when(qq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_packed(
    q, k, v, head_dim, block_q, block_k, interpret, causal,
    bwd_block_q, bwd_block_k,
):
    out, _ = _flash_fwd_packed_call(
        q, k, v, head_dim, block_q, block_k, interpret, causal
    )
    return out


def _flash_packed_fwd_rule(
    q, k, v, head_dim, block_q, block_k, interpret, causal,
    bwd_block_q, bwd_block_k,
):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd_packed_call(
        q, k, v, head_dim, block_q, block_k, interpret, causal
    )
    # the names a surrounding jax.checkpoint policy saves (see
    # _flash_fwd_rule)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_packed_bwd_rule(
    head_dim, block_q, block_k, interpret, causal, bwd_block_q,
    bwd_block_k, res, do,
):
    q, k, v, out, lse = res
    b, t, hk = q.shape
    heads = _PACK_LANES // head_dim
    block_q = bwd_block_q or block_q
    block_k = bwd_block_k or block_k
    n_q, n_k = t // block_q, t // block_k
    # dq as in _flash_bwd_rule: a plane a KV block, or f32 revisits
    dq_partials = _DQ_PARTIALS and n_k <= 8
    if dq_partials:
        plane_dtype = jnp.float32 if _DQ_PARTIALS_F32 else q.dtype
        dq_shape = jax.ShapeDtypeStruct((n_k, b, t, hk), plane_dtype)
        dq_spec = pl.BlockSpec(
            (1, 1, block_q, _PACK_LANES), lambda i, g, j, qq: (j, i, qq, g)
        )
    else:
        dq_shape = jax.ShapeDtypeStruct((b, t, hk), jnp.float32)
        dq_spec = pl.BlockSpec(
            (1, block_q, _PACK_LANES), lambda i, g, j, qq: (i, qq, g)
        )
    q_spec = pl.BlockSpec(
        (1, block_q, _PACK_LANES), lambda i, g, j, qq: (i, qq, g))
    kv_spec = pl.BlockSpec(
        (1, block_k, _PACK_LANES), lambda i, g, j, qq: (i, j, g))
    dq_raw, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_packed_kernel, block_q=block_q, block_k=block_k,
            n_q=n_q, head_dim=head_dim, scale=1.0 / (head_dim**0.5),
            causal=causal, dq_partials=dq_partials,
        ),
        out_shape=(
            dq_shape,
            jax.ShapeDtypeStruct((b, t, hk), k.dtype),
            jax.ShapeDtypeStruct((b, t, hk), v.dtype),
        ),
        grid=(b, hk // _PACK_LANES, n_k, n_q),
        in_specs=[
            q_spec, kv_spec, kv_spec, q_spec, q_spec,
            pl.BlockSpec(
                (1, 1, block_q, heads), lambda i, g, j, qq: (i, g, qq, 0)),
        ],
        out_specs=(dq_spec, kv_spec, kv_spec),
        scratch_shapes=[
            pltpu.VMEM((block_k, _PACK_LANES), jnp.float32),
            pltpu.VMEM((block_k, _PACK_LANES), jnp.float32),
        ],
        # the kv dim sequential, as in _flash_bwd_rule
        compiler_params=_dim_semantics(
            interpret, ("parallel", "parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="flash_bwd_packed",
    )(q, k, v, out, do, lse)
    if not dq_partials:
        return dq_raw.astype(q.dtype), dk, dv
    if n_k == 1 and dq_raw.dtype == q.dtype:
        return dq_raw[0], dk, dv  # the one plane is dq
    return jnp.sum(dq_raw.astype(jnp.float32), axis=0).astype(q.dtype), dk, dv


_flash_packed.defvjp(_flash_packed_fwd_rule, _flash_packed_bwd_rule)


def flash_attention_packed(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    head_dim: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    causal: bool = False,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
) -> jax.Array:
    """Differentiable flash attention over (B, T, H*K) in and out: the
    layout the projections write and ``wo`` reads, heads side by side on
    the lane dimension. ``head_dim`` divides 128 and ``H*K`` is a
    multiple of 128 (a block is a 128-lane group of whole heads). The
    same mathematics as :func:`flash_attention_trainable`, at the same
    precision; the kernels show in a trace as ``flash_fwd_packed`` and
    ``flash_bwd_packed``."""
    b, t, hk = q.shape
    if _PACK_LANES % head_dim or hk % _PACK_LANES:
        raise ValueError(
            f"the packed flash kernels take whole heads in groups of "
            f"{_PACK_LANES} lanes: head_dim {head_dim} must divide "
            f"{_PACK_LANES} and H*K = {hk} be a multiple of it"
        )
    block_q, block_k, bwd_block_q, bwd_block_k = _blocks_within(
        t, block_q, block_k, bwd_block_q, bwd_block_k
    )
    interpret = _default_interpret() if interpret is None else interpret
    return _flash_packed(
        q, k, v, head_dim, block_q, block_k, interpret, causal,
        bwd_block_q, bwd_block_k,
    )


# -- flash decode attention (single-position KV-cache read) -------------------
#
# The decode hot loop reads the KV cache every step, so its HBM layout
# and how much of it is read are the perf story. A (B, T, H, K) cache
# tiles on (H, K) = (12, 64) which Mosaic/XLA pads to (16, 128) — 2.67x
# the logical bytes streamed per step (measured: the QK einsum alone was
# 601us/step at GPT-2-small B=16). These kernels read a PACKED
# (B, T, H*K) cache whose minor dim is a lane-aligned 768: padding
# ~1.01x, and the per-head split happens in registers via an iota-built
# block-diagonal expansion matrix (no lane-splitting relayout). The
# online softmax runs across sequential T blocks, exactly like the
# training flash kernel; masked positions (> pos, or cache padding)
# contribute nothing, and a block that holds no row at or before ``pos``
# is neither computed nor copied in.
#
# All ``groups`` query rows are folded into ONE pair of wide MXU
# contractions per block (r5 rewrite): the per-group Python loop of the
# original kernel ran `groups` iterations of (block_t, n_kv)-thin ops,
# which made GQA (groups=3, n_kv=2) SLOWER than MHA despite a 3x smaller
# cache stream (11.1K vs 11.5K tok/s measured in situ).
#
# - K side: s_all (block_t, G*n_kv) = KB @ M^T via one dot_general,
#   where M[(g,h), j] = q_g[j] * (head(j)==h) — the query fold into the
#   block-diagonal reducer. In int8 mode KB stays int8 and M is built
#   int8 from the in-register-quantized queries (one scale per group),
#   so the dot runs on the int8 MXU and the cache is never converted.
# - V side: PV (G*n_kv, hk) = softmax-weights^T @ VB via one dot_general
#   contracting the t axis (int8 mode: weights quantized per tile, VB
#   stays int8), then an iota-built segment mask + one tiny (G, G*n_kv)
#   dot collapse per-head rows into per-group outputs. No (block_t, hk)
#   elementwise pass touches the V block in either mode.
#
# Softmax state (m, l) lives in (1, G*n_kv) lanes (lane = g*n_kv + h);
# the accumulator is (G, hk). The four helpers below are that math, once:
# the grid kernel (int8 slab, paged pool) keeps the state in VMEM
# scratch across grid steps, the walk kernel (slab) carries it through
# its loop.


def _decode_structure(groups: int, n_kv_heads: int, head_dim: int):
    """iota-built structure matrices (no data movement):
    e_tile[r, j] = (head(j) == r % n_kv), the head-segment mask per
    (group, head) row, (gh, hk); s_g[g, r] = (r // n_kv == g), the group
    collapse, (groups, gh) — its transpose doubles as the row-repeat of
    per-group values."""
    gh = groups * n_kv_heads
    hk = n_kv_heads * head_dim
    row_h = jax.lax.broadcasted_iota(jnp.int32, (gh, hk), 0) % n_kv_heads
    col_h = jax.lax.broadcasted_iota(jnp.int32, (gh, hk), 1) // head_dim
    g_row = jax.lax.broadcasted_iota(jnp.int32, (groups, gh), 0)
    g_col = jax.lax.broadcasted_iota(jnp.int32, (groups, gh), 1) // n_kv_heads
    return (
        (row_h == col_h).astype(jnp.float32),
        (g_row == g_col).astype(jnp.float32),
    )


def _decode_fold_query(q, e_tile, s_g, cache_dtype, quantized: bool):
    """The (G, hk) query rows as M (gh, hk) in the cache's dtype: row
    (g, h) is query row g masked to head h's lane segment. int8 mode
    quantizes in-register, one scale per group, and also returns that
    scale per (g, h) lane, (1, gh); otherwise None."""
    qf = q.astype(jnp.float32)
    q_rep = jnp.dot(s_g.T, qf, preferred_element_type=jnp.float32)
    if not quantized:
        return (q_rep * e_tile).astype(cache_dtype), None
    qmax = jnp.maximum(
        jnp.max(jnp.abs(qf), axis=1, keepdims=True), 1e-8
    )  # (G, 1)
    qsc_rep = jnp.dot(
        s_g.T, qmax / 127.0, preferred_element_type=jnp.float32
    )  # (gh, 1): per-(group,head)-row q scale
    m_t = (
        jnp.clip(jnp.round(q_rep / qsc_rep), -127, 127) * e_tile
    ).astype(jnp.int8)
    return m_t, qsc_rep.reshape(1, -1)


def _decode_block(state, kb, vb, scales, m_t, qsc_lane, e_tile, s_g,
                  t_start, pos, scale: float):
    """Fold one (block_t, hk) K/V block that starts at row ``t_start``
    into the online-softmax ``state`` (m, l, acc); rows past ``pos`` are
    masked. The block must hold a row at or before ``pos``.

    Operands stay in the storage dtype (bf16 on TPU: the MXU fast path —
    f32-operand dots measured ~4x slower); softmax state and
    accumulators are f32. int8 mode (``scales`` = the (block_t, 1) f32
    K and V scale columns): both cache planes feed the MXU directly as
    int8 — converting a plane on the VPU costs more than the int8 DMA
    saves (measured 43us/layer, bf16-equal, before this design)."""
    m_prev, l_prev, acc = state
    quantized = scales is not None
    if quantized:
        ksc, vsc = scales
        s_all = jax.lax.dot_general(
            kb, m_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32) * (ksc * scale) * qsc_lane
    else:
        s_all = jax.lax.dot_general(
            kb, m_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_t, gh)
    rows = t_start + jax.lax.broadcasted_iota(
        jnp.int32, (kb.shape[0], 1), 0
    )
    s_all = jnp.where(rows > pos, -jnp.inf, s_all)
    m_new = jnp.maximum(m_prev, jnp.max(s_all, axis=0, keepdims=True))
    p = jnp.exp(s_all - m_new)  # (block_t, gh) f32
    corr = jnp.exp(m_prev - m_new)  # (1, gh)
    l_new = corr * l_prev + jnp.sum(p, axis=0, keepdims=True)
    if quantized:
        p_v = p * vsc
        psc = jnp.maximum(jnp.max(p_v), 1e-30) / 127.0
        p_low = jnp.clip(jnp.round(p_v / psc), -127, 127).astype(jnp.int8)
    else:
        p_low = p.astype(vb.dtype)
    pv = jax.lax.dot_general(
        p_low, vb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32 if quantized else jnp.float32,
    )  # (gh, hk): row (g, h) valid only on head-segment h
    pv_m = pv.astype(jnp.float32) * e_tile
    if quantized:
        pv_m = pv_m * psc
    o_blk = jnp.dot(s_g, pv_m, preferred_element_type=jnp.float32)  # (G, hk)
    # per-lane correction expanded to (G, hk): corr[g, head(j)]
    corr_exp = jnp.dot(
        s_g * corr, e_tile, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc * corr_exp + o_blk


def _decode_output(l, acc, e_tile, s_g):
    """(G, hk) attention output from the final state. A row that
    attended to nothing (l = 0, acc = 0) gives zeros."""
    l_exp = jnp.dot(
        s_g * jnp.maximum(l, 1e-30), e_tile,
        preferred_element_type=jnp.float32,
    )
    return acc / l_exp


def _flash_decode_kernel(
    q_ref, k_ref, v_ref, pos_ref, *rest,
    block_t: int, n_t: int, n_kv_heads: int, head_dim: int,
    groups: int, scale: float, quantized: bool = False,
):
    """One (batch, t-block) grid step of single-position decode
    attention: the K/V (and int8 scale) blocks arrive through BlockSpecs,
    the state lives in VMEM scratch across the row's grid steps.
    rest = ([ks_ref, vs_ref,] o_ref, m_s, l_s, acc_s)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
    tt = pl.program_id(1)
    t_start = tt * block_t
    pos = pos_ref[pl.program_id(0)]

    @pl.when(tt == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # the structure matrices are built only on the grid steps that use
    # them: a skipped block builds none
    @pl.when(t_start <= pos)
    def _compute():
        e_tile, s_g = _decode_structure(groups, n_kv_heads, head_dim)
        m_t, qsc_lane = _decode_fold_query(
            q_ref[0], e_tile, s_g, k_ref.dtype, quantized
        )
        scales = (ks_ref[0, 0, 0], vs_ref[0, 0, 0]) if quantized else None
        m_s[:], l_s[:], acc_s[:] = _decode_block(
            (m_s[:], l_s[:], acc_s[:]), k_ref[0, 0, 0], v_ref[0, 0, 0],
            scales, m_t, qsc_lane, e_tile, s_g, t_start, pos, scale,
        )

    @pl.when(tt == n_t - 1)
    def _finalize():
        e_tile, s_g = _decode_structure(groups, n_kv_heads, head_dim)
        o_ref[0] = _decode_output(
            l_s[:], acc_s[:], e_tile, s_g
        ).astype(o_ref.dtype)


# The per-row positions go to the kernel WHOLE, as a (B,) int32 vector
# in scalar memory, and each grid row reads its own entry by
# ``program_id(0)``. A batch-indexed (1, 1) block of a (B, 1) array is
# what interpret mode accepts and the TPU lowering refuses for B > 1:
# a block's last two dims must be (8, 128)-divisible or the array's own.
_POS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _decode_positions(pos, b: int) -> jax.Array:
    """(B,) int32: a scalar ``pos`` broadcasts to every row, a (B,)
    vector (serving) keeps per-slot depths."""
    return jnp.broadcast_to(
        jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,)
    )


# The block-size rule's constants, measured on a v5e (PERF.md, PR 26:
# 48 x 1,024 x 1,280 bf16 MHA and 16 x 8,704 x 256 bf16 GQA).
# A block under 128 rows half-fills the MXU pass that contracts over
# its rows: 64-row blocks cost 1.4x the time of 128-row ones a row.
_DECODE_MIN_ROWS = 128
# What a block of the walk costs beside its rows (loop and copy
# bookkeeping, the softmax state's update), written as the K and V
# bytes the kernel moves in that time: 0.04-0.08 us.
_DECODE_STEP_BYTES = 56 * 1024
# Scoped VMEM the K and V block planes may take, with headroom under
# the ~16MB limit for q, out and scratch: a single 8704-row bf16 block
# at hk=256 OOMed at 17.04M under the grid pipeline, matching its
# 4-plane estimate.
_DECODE_VMEM_BYTES = 14 * 1024 * 1024
# K/V blocks in flight or in use at once in the walk kernel: one under
# the arithmetic, two on their way. With one on its way the copy's
# latency (about 0.5 us) was exposed once a block: 165 us a call against
# 120 at the benchmark's geometry; a fourth buffer gained nothing.
_WALK_BUFFERS = 3
# Rows of the aligned tile the writing walk copies back to HBM around
# a slot's new row. Mosaic refuses a slice of 1, 2 or 4 rows of a tiled
# memref ("must be aligned to tiling (8)", bf16 and f32 alike), and 8
# divides every cache T (``decode_block_rows`` asserts it).
_WRITE_ROWS = 8


@functools.lru_cache(maxsize=None)
def decode_block_rows(t: int, hk: int, itemsize: int) -> int:
    """Rows of one T block of :func:`flash_decode_attention` for a
    ``t``-row slab of packed width ``hk`` and cache item size
    ``itemsize``: the one rule both the kernel and the engine's row
    counter (``decode_rows_streamed``) read.

    bf16 / f32 (the walk kernel). A row reads whole blocks up to its
    last needed row, so a block of ``r`` rows reads ``r / 2`` rows too
    many on average, and a row half its slab long visits
    ``t / 2r + 1/2`` blocks that each cost what ``_DECODE_STEP_BYTES``
    of cache would: the rule takes the 8-aligned divisor of ``t``, of at
    least ``_DECODE_MIN_ROWS`` rows and within the VMEM budget (K and V
    planes in three buffers), that minimises the sum. A slab with no
    such divisor is one block, the kernel of before PR 26 tile for tile.
    That gives 128 rows at (1024, 1280, bf16) and 544 at (8704, 256,
    bf16), where 512 and 1,088 were measured (PERF.md, PR 26): with
    every row read, the worst case for small blocks, 340 us a call
    against the single 1,024-row block's 344, and 217 (512) or 206
    (1,088) against the two 4,352-row blocks' 209; with rows a quarter
    to a half full, 120 against 344 and 129 (either) against 204.
    Before the walk was bounded, and with one copy in flight, fewer and
    larger blocks won (r5: +24.5% tok/s from 512 to 4,352 rows at
    T=8704): every row was read anyway and each block exposed its
    copy's latency.

    int8 (the grid kernel): as few blocks as VMEM allows, the rule of
    before PR 26. A grid step there costs about 1.4 us whatever it
    holds (four block operands two deep, the query quantised again),
    skipped steps 0.5 us, so small blocks lose more than their
    round-up saves: at (1024, 1280) 512-row blocks measured 237 us a
    call on the flood's lengths, 150 on the chat's and 247 with every
    row read, against 241 / 241 / 246 before the index maps were
    clamped; 128-row blocks 266 / 191 / 558. int8 streams 1 byte an
    element but the in-register conversion keeps per-block scratch (a
    single-block int8 OOM at 25.54M, T=8704, hk=256, works out to ~2.87
    bytes an element-plane): VMEM budgets its four planes at 3.

    An adversarial ``t`` (8 x prime) has only tiny and whole-slab
    divisors; callers size ``t`` as a multiple of 512 above 1024
    (``init_caches``)."""
    assert t % 8 == 0, f"cache T dim must be a multiple of 8, got {t}"

    def divisors(lo, hi):
        return [r for r in range(lo, min(t, hi) + 1, 8) if t % r == 0]

    if itemsize == 1:
        cap = max(8, _DECODE_VMEM_BYTES // (hk * 3 * 4))
        return max(divisors(8, cap))
    cap = max(8, _DECODE_VMEM_BYTES // (hk * itemsize * 2 * _WALK_BUFFERS))
    step_rows = _DECODE_STEP_BYTES / (2 * hk * itemsize)
    fits = divisors(_DECODE_MIN_ROWS, cap)
    if not fits:
        # nothing between the floor and the budget divides t: the whole
        # slab if it fits, else the largest divisor that does
        return max(divisors(8, cap))
    return min(fits, key=lambda r: (t / (2 * r) + 0.5) * step_rows + r / 2)


def _decode_last_rows(pos, active, b: int, t: int) -> jax.Array:
    """(B,) int32: the last cache row each batch row attends to (its
    position, capped at the slab), -1 for a row that is not active."""
    last = jnp.minimum(_decode_positions(pos, b), t - 1)
    if active is None:
        return last
    return jnp.where(jnp.asarray(active, bool), last, -1)


def _grid_walk(last, block_t: int):
    """The (B,) vectors the grid kernel's K/V index maps read beside
    ``last``. An active row walks its own blocks 0..``last // block_t``
    and then repeats the last one; a row that is not active names the
    block the active row before it ended on (the first block of the
    first active row when there is none before it). A block index that
    repeats the previous grid step's issues no copy, so only blocks
    that hold a needed row are ever read. Returns ``(src, hi)``: the
    slab row and the last block a batch row may name."""
    live = last >= 0
    rows = jnp.arange(last.shape[0], dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live)).astype(jnp.int32)
    hi = jnp.where(before >= 0, jnp.maximum(last[src], 0) // block_t, 0)
    return src, hi


def _bounded_grid_kernel(layer_ref, last_ref, src_ref, hi_ref, q_ref, k_ref,
                         v_ref, *rest, **kw):
    """int8 slab grid step: ``_flash_decode_kernel`` with the row's
    position read from the scalar-prefetched ``last``; ``layer``,
    ``src`` and ``hi`` are consumed by the index maps alone."""
    del layer_ref, src_ref, hi_ref
    _flash_decode_kernel(q_ref, k_ref, v_ref, last_ref, *rest, **kw)


def _kv_walk_math(n_kv_heads: int, head_dim: int, groups: int,
                  scale: float, block_t: int):
    """The walk's arithmetic over K and V planes: ``math(q_ref, dtype)``
    for a row's (1, G, hk) query block gives ``(state0, fold, finish)``,
    the online softmax of :func:`_decode_block` over blocks of two
    planes (``state0`` is a function: the state is made where the loop
    starts)."""
    def math(q_ref, dtype):
        e_tile, s_g = _decode_structure(groups, n_kv_heads, head_dim)
        m_t, _ = _decode_fold_query(q_ref[0], e_tile, s_g, dtype, False)
        gh = groups * n_kv_heads

        def state0():
            return (
                jnp.full((1, gh), -jnp.inf, jnp.float32),
                jnp.zeros((1, gh), jnp.float32),
                jnp.zeros(q_ref.shape[1:], jnp.float32),
            )

        def fold(state, buf, slot, j, last):
            return _decode_block(
                state, buf[slot, 0], buf[slot, 1], None, m_t, None, e_tile,
                s_g, j * block_t, last, scale,
            )

        def finish(state):
            _, l, acc = state
            return _decode_output(l, acc, e_tile, s_g)

        return state0, fold, finish

    return math


# Parts a block's rows are cut in by ``_latent_walk_math``.
_LATENT_PARTS = 2


def _latent_walk_math(value_width: int):
    """The walk's arithmetic over ONE plane of latent rows: a row's
    ``q`` (H, W) holds every head's query folded onto the latent (scale
    included), a block (block_t, W) is the key of all of them and, in
    its first ``value_width`` lanes, their value, so it is read once for
    both products. Heads lie on sublanes and cache rows on lanes, as in
    the flash forward kernel: scores (H, rows), softmax state (H, 1),
    accumulator (H, value_width), no segment masks.

    The block's rows are cut in ``_LATENT_PARTS`` parts. A part's
    weights are taken against the part's OWN maximum (with the running
    one), so its softmax waits for no other part's scores, and all the
    softmaxes are written before the first values product: one part's
    ``exp`` then runs under another part's products and the MXU is fed
    all through the block, where one softmax over the whole block stood
    between the two products with the MXU idle (PERF.md, PR 37). The
    parts meet once a block, in one rescale of the accumulator; it is
    the online softmax of a walk with blocks a part long, summed in
    another order."""
    nt = (((1,), (1,)), ((), ()))

    def math(q_ref, dtype):
        h = q_ref.shape[1]

        def state0():
            return (
                jnp.full((h, 1), -jnp.inf, jnp.float32),
                jnp.zeros((h, 1), jnp.float32),
                jnp.zeros((h, value_width), jnp.float32),
            )

        def fold(state, buf, slot, j, last):
            m_prev, l_prev, acc = state
            block_t = buf.shape[2]
            r = block_t // _LATENT_PARTS
            # block j of the row's walk, a part at a time; q is read
            # from its block every time (held across the walk it is
            # spilled and filled again)
            q = q_ref[0]
            kbs = [buf[slot, 0, k * r:(k + 1) * r, :]
                   for k in range(_LATENT_PARTS)]
            scores = [
                jax.lax.dot_general(
                    q, kb, nt, preferred_element_type=jnp.float32)
                for kb in kbs
            ]  # (H, r) each
            weights = []
            for k, s in enumerate(scores):
                cols = j * block_t + k * r + jax.lax.broadcasted_iota(
                    jnp.int32, (1, r), 1
                )
                # a select, not a bias a column: what a slot's last tenant
                # left past the position may score inf
                s = jnp.where(cols > last, -jnp.inf, s)
                m_k = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                # a part past the row's position in its first block has
                # seen nothing yet (m_k = -inf): exp(-inf - 0) = 0
                p = jnp.exp(s - jnp.where(m_k == -jnp.inf, 0.0, m_k))
                weights.append((
                    m_k, jnp.sum(p, axis=1, keepdims=True),
                    p.astype(kbs[k].dtype),
                ))
            # part 0 holds the block's first row: m_new is finite, and a
            # part that saw nothing is scaled by exp(-inf) = 0
            m_new = functools.reduce(jnp.maximum, [w[0] for w in weights])
            corr = jnp.exp(m_prev - m_new)
            l_new, acc = corr * l_prev, acc * corr
            for (m_k, l_k, p), kb in zip(weights, kbs):
                scale = jnp.exp(m_k - m_new)
                pv = jnp.dot(
                    p, kb[:, :value_width],
                    preferred_element_type=jnp.float32,
                )
                l_new, acc = l_new + l_k * scale, acc + pv * scale
            return m_new, l_new, acc

        def finish(state):
            _, l, acc = state
            return acc / jnp.maximum(l, 1e-30)

        return state0, fold, finish

    return math


def _walk_decode_kernel(
    layer_ref, last_ref, base_ref, nxt_ref, *rest,
    block_t: int, math, planes: int = 2, write_rows: int = 0,
):
    """One batch row of the bounded walk: the stacked cache stays in
    HBM and the row's blocks 0..``last // block_t`` come in by explicit
    copies into ``_WALK_BUFFERS`` buffers (all ``planes`` of a block, K
    and V, in one strided copy), so no grid step and no copy is spent on
    a block nobody reads; a row that is not active (``last`` = -1)
    copies nothing and writes zeros. ``math`` is the arithmetic over the
    blocks (:func:`_kv_walk_math`, :func:`_latent_walk_math`).

    The blocks of ALL rows form one sequence, and the copy of the block
    ``_WALK_BUFFERS - 1`` places ahead in that sequence — the next rows'
    first ones at a row's end — starts before this block's arithmetic:
    the pipeline never drains between rows. ``base[i]`` is row i's
    first index in that sequence (modulo the buffers it picks one),
    ``nxt[k]`` the first row >= k with anything to read (B when
    none).

    ``write_rows`` > 0: the kernel also PLACES the row's fresh K and V
    (``new_ref``, (1, planes, width)) at cache row ``at_ref[i]``: in the block
    that holds that row it patches the aligned ``write_rows``-row tile
    in VMEM before the arithmetic reads it, and copies the patched tile
    (both planes) back to HBM from a scratch of its own, so the walk's
    buffers are never held up. The cache is the same HBM buffer in and
    out (``input_output_aliases``). ``pend`` says a write-back is in
    flight: it is waited before the scratch is reused and before the
    last grid step ends. A row that is not active, or whose ``at`` lies
    in no block it walks, writes nothing.
    rest = ([at_ref,] q_ref, [new_ref,] kv_hbm, o_ref, [kv_out,] buf,
    sem[, wbuf, wsem, pend])."""
    if write_rows:
        (at_ref, q_ref, new_ref, _, o_ref, kv_hbm, buf, sem, wbuf, wsem,
         pend) = rest
    else:
        q_ref, kv_hbm, o_ref, buf, sem = rest
    b = last_ref.shape[0]
    i = pl.program_id(0)
    last = last_ref[i]

    def n_blocks(row):
        return (last_ref[row] + block_t) // block_t  # -1 -> 0

    def copy(row, j, slot):
        return pltpu.make_async_copy(
            kv_hbm.at[
                layer_ref[0], pl.ds(0, planes), row,
                pl.ds(pl.multiple_of(j * block_t, block_t), block_t),
            ],
            buf.at[slot], sem.at[slot],
        )

    def write_back(row, t0):
        return pltpu.make_async_copy(
            wbuf,
            kv_hbm.at[
                layer_ref[0], pl.ds(0, planes), row,
                pl.ds(pl.multiple_of(t0, write_rows), write_rows),
            ],
            wsem.at[0],
        )

    def settle():
        # the write-back in flight, if any, has landed (every one moves
        # the same bytes, so any descriptor of that shape waits for it)
        @pl.when(pend[0] == 1)
        def _():
            write_back(0, 0).wait()
            pend[0] = 0

    def after(row, j):
        # the block that follows (row, j) in the sequence; row == B: none
        r = jnp.minimum(row, b - 1)
        more = j + 1 < n_blocks(r)
        return jnp.where(more, row, nxt_ref[r + 1]), jnp.where(more, j + 1, 0)

    def start(row, j, slot):
        @pl.when(row < b)
        def _():
            copy(row, j, slot).start()

    @pl.when(i == 0)
    def _prime():
        if write_rows:
            pend[0] = 0
        row, j = nxt_ref[0], 0
        for slot in range(_WALK_BUFFERS - 1):
            start(row, j, slot)
            row, j = after(row, j)

    @pl.when(last < 0)
    def _idle():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(last >= 0)
    def _walk():
        state0, fold, finish = math(q_ref, buf.dtype)

        def place(j, slot):
            at = at_ref[i]

            @pl.when((at >= j * block_t) & (at < (j + 1) * block_t))
            def _():
                r = at - j * block_t
                r0 = pl.multiple_of(r // write_rows * write_rows, write_rows)
                tile = buf[slot, :, pl.ds(r0, write_rows), :]
                rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
                tile = jnp.where(
                    rows == r - r0, new_ref[0][:, None, :], tile
                )
                buf[slot, :, pl.ds(r0, write_rows), :] = tile
                settle()
                wbuf[...] = tile
                write_back(i, j * block_t + r0).start()
                pend[0] = 1

        def block(j, state):
            g = base_ref[i] + j
            slot = g % _WALK_BUFFERS
            copy(i, j, slot).wait()
            ahead = (i, j)
            for _ in range(_WALK_BUFFERS - 1):
                ahead = after(*ahead)
            start(*ahead, (g + _WALK_BUFFERS - 1) % _WALK_BUFFERS)
            if write_rows:
                place(j, slot)
            return fold(state, buf, slot, j, last)

        state = jax.lax.fori_loop(0, n_blocks(i), block, state0())
        o_ref[0] = finish(state).astype(o_ref.dtype)

    if write_rows:
        @pl.when(i == b - 1)
        def _drain():
            settle()


def flash_decode_attention(
    q: jax.Array,
    kvcache: jax.Array,
    pos: jax.Array,
    n_kv_heads: int,
    layer: int = 0,
    block_t: int | None = None,
    interpret: bool | None = None,
    kv_scales: jax.Array | None = None,
    active: jax.Array | None = None,
) -> jax.Array:
    """One decode step of causal attention against a packed KV cache.

    ``q``: (B, G, Hkv*K) — query heads grouped for GQA (G = H/Hkv; 1 for
    MHA), each group packed head-major. ``kvcache``: the FULL STACKED
    (n_layers, 2, B, T, Hkv*K) cache (axis 1: K then V) — ``layer``
    selects the layer inside the kernel, so no host-side slice is
    needed. (Slicing the stack outside the kernel materializes a copy
    of the whole layer cache per call — a custom call needs a dense
    operand buffer, so XLA cannot fuse the slice the way it fuses one
    feeding an einsum: 521us/step at GPT-2-small, measured.) ``pos``:
    scalar int32, the position being decoded, or an (B,) vector of
    per-row positions (continuous-batching serving, where each slot
    decodes at its own depth) — rows > pos are invisible. ``active``:
    optional (B,) bool; a row that is not active attends to nothing and
    returns zeros (default: every row active). Returns (B, G, Hkv*K)
    attention output in q's dtype. READ-ONLY: row ``pos`` must already
    be in ``kvcache`` (the caller wrote it: the int8 slab, whose scale
    planes Mosaic will not copy by rows); a bf16 / f32 cache takes
    :func:`flash_decode_attention_write`, which places the row itself.

    What is read: a row's slab is walked in T blocks of ``block_t`` rows
    (default :func:`decode_block_rows`; T must be a multiple of it,
    callers pad) and the walk, copies included, stops at the block that
    holds ``pos``: a row reads ``pos + 1`` rows rounded up to the block,
    a row that is not active reads nothing. Rows past ``pos`` inside the
    last block are masked.

    ``kv_scales`` (int8 serving mode): per-row dequant scales
    (n_layers, 2, B, T, 1) f32 for an int8 ``kvcache`` — rows convert
    to q's dtype in-register and the scales fold into the logits (K) /
    softmax weights (V), so the HBM cache stream is the int8 bytes.
    Mosaic refuses an explicit copy of a (rows, 1) slice of the scale
    planes (a minor dim of 1 is not tile-aligned), so the int8 cache
    keeps a (B, T / block_t) grid whose BlockSpec index maps stop at
    the row's last block (:func:`_grid_walk`): the same reads, at the
    price of an empty grid step for every block skipped.

    ``layer`` reaches the kernel as data and the call is one jitted
    function of the shapes: a step program that calls it once a layer
    and substep (144 times at 36 layers x K=4) traces and lowers the
    kernel once, not once a call.
    """
    return _decode_attention(
        q, kvcache, jnp.asarray(pos, jnp.int32), active,
        jnp.asarray(layer, jnp.int32), kv_scales, None, None,
        n_kv_heads=n_kv_heads,
        block_t=_decode_block_t(q, kvcache, block_t),
        interpret=_default_interpret() if interpret is None else interpret,
    )


def _decode_block_t(q, kvcache, block_t: int | None) -> int:
    t, hk = kvcache.shape[3], q.shape[2]
    if block_t is None:
        block_t = decode_block_rows(t, hk, kvcache.dtype.itemsize)
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    return block_t


def flash_decode_attention_write(
    q: jax.Array,
    kvcache: jax.Array,
    kv_new: jax.Array,
    pos: jax.Array,
    n_kv_heads: int,
    layer: int = 0,
    write_at: jax.Array | None = None,
    active: jax.Array | None = None,
    block_t: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_decode_attention` over a bf16 / f32 cache that also
    PLACES the step's fresh rows: ``kv_new`` (B, 2, Hkv*K) (K then V;
    cast to the cache's dtype) goes to cache row ``write_at`` (scalar
    or (B,); default ``pos``; a ring passes ``pos % rows``) of
    ``kvcache[layer, :, b]`` before the row's walk reads it, so the
    attention sees exactly the values a write followed by a read would.
    Returns ``(o, kvcache)``: the cache is the same buffer in and out
    (``input_output_aliases``), so a caller that donates it never
    copies it.

    What is written: in the block that holds ``write_at`` the kernel
    patches the aligned ``_WRITE_ROWS``-row tile in VMEM and copies
    both planes of it back (the other rows as they were read); one row
    alone is no copy Mosaic accepts. A row that is not ``active``
    writes nothing, nor does one whose ``write_at`` lies in no block
    its walk reads (past ``pos``'s block, or outside the slab)."""
    return _decode_attention(
        q, kvcache, jnp.asarray(pos, jnp.int32), active,
        jnp.asarray(layer, jnp.int32), None, kv_new,
        jnp.asarray(pos if write_at is None else write_at, jnp.int32),
        n_kv_heads=n_kv_heads,
        block_t=_decode_block_t(q, kvcache, block_t),
        interpret=_default_interpret() if interpret is None else interpret,
    )


def latent_block_rows(t: int, width: int, itemsize: int) -> int:
    """Rows of one T block of :func:`latent_decode_attention_write` over
    a ``t``-row slab of one plane of ``width`` values: the rule of
    :func:`decode_block_rows` for the same bytes a row (one plane of
    ``width`` moves what K and V planes of ``width / 2`` do). The kernel
    and the engine's row counter both read it."""
    return decode_block_rows(t, width // 2, itemsize)


def latent_decode_attention_write(
    q: jax.Array,
    cache: jax.Array,
    row_new: jax.Array | None,
    pos: jax.Array,
    value_width: int,
    layer: int = 0,
    write_at: jax.Array | None = None,
    active: jax.Array | None = None,
    block_t: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of latent attention in its absorbed form, over a
    cache of ONE plane, that also places the step's fresh row.

    ``q`` (B, H, W): every head's query folded onto the latent, then its
    rotated part, the softmax scale included, zero in the lane padding.
    ``cache``: the stacked (layers, 1, B, T, W) leaf; a row is the
    normed latent (``value_width`` values), the rotated shared key, and
    zeros up to W (a multiple of 128 lanes). ``row_new`` (B, 1, W) goes
    to cache row ``write_at`` (default ``pos``) of ``cache[layer, 0,
    b]`` before the walk reads it (``None``: nothing is written and the
    cache comes back as it was; row ``pos`` is already there). Scores
    are ``q . row`` over all W lanes; the value of every head is the
    row's first ``value_width`` lanes, so a block is copied in once and
    used in both products.
    Returns ``(o, cache)``: ``o`` (B, H, value_width) and the cache, the
    same buffer in and out.

    The walk is :func:`flash_decode_attention_write`'s: blocks up to the
    row's position, nothing read or written for a row that is not
    ``active``, copies in flight across rows, the new row patched into
    an aligned ``_WRITE_ROWS``-row tile."""
    b, _, width = q.shape
    t = cache.shape[3]
    assert cache.shape[1] == 1 and cache.shape[4] == width, (
        cache.shape, q.shape)
    if block_t is None:
        block_t = latent_block_rows(t, width, cache.dtype.itemsize)
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    return _latent_decode_attention(
        q, cache, row_new, jnp.asarray(pos, jnp.int32), active,
        jnp.asarray(layer, jnp.int32),
        jnp.asarray(pos if write_at is None else write_at, jnp.int32),
        value_width=value_width, block_t=block_t,
        interpret=_default_interpret() if interpret is None else interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("value_width", "block_t", "interpret")
)
def _latent_decode_attention(q, cache, row_new, pos, active, layer, write_at,
                             *, value_width: int, block_t: int,
                             interpret: bool):
    b = q.shape[0]
    last = _decode_last_rows(pos, active, b, cache.shape[3])
    out = _walk_call(
        _latent_walk_math(value_width), q, cache, row_new, write_at, last,
        jnp.reshape(layer, (1,)), out_width=value_width, block_t=block_t,
        interpret=interpret, name="latent_decode_attn",
    )
    return (out, cache) if row_new is None else out


def _walk_call(math, q, kvcache, kv_new, write_at, last, layer, *,
               out_width: int, block_t: int, interpret: bool, name: str):
    """The bounded walk (:func:`_walk_decode_kernel`) of ``q`` (B, G, W)
    over the stacked cache (layers, planes, B, T, W) with ``math`` as
    its arithmetic: (B, G, out_width), and with ``kv_new`` (B, planes,
    W) also the cache, updated in place."""
    b, g, width = q.shape
    planes = kvcache.shape[1]
    n_blocks = (last + block_t) // block_t
    rows = jnp.arange(b, dtype=jnp.int32)
    nxt = jnp.concatenate([
        jax.lax.cummin(jnp.where(n_blocks > 0, rows, b), reverse=True),
        jnp.full((1,), b, jnp.int32),
    ])
    prefetch = [layer, last, jnp.cumsum(n_blocks) - n_blocks, nxt]

    def row(i, *_):
        return (i, 0, 0)

    in_specs = [
        pl.BlockSpec((1, g, width), row),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, kvcache]
    out_shape = jax.ShapeDtypeStruct((b, g, out_width), q.dtype)
    out_specs = pl.BlockSpec((1, g, out_width), row)
    scratch = [
        pltpu.VMEM((_WALK_BUFFERS, planes, block_t, width), kvcache.dtype),
        pltpu.SemaphoreType.DMA((_WALK_BUFFERS,)),
    ]
    write_rows, aliases = 0, {}
    if kv_new is not None:
        write_rows = _WRITE_ROWS
        assert kv_new.shape == (b, planes, width), (kv_new.shape, q.shape)
        prefetch.append(_decode_positions(write_at, b))
        in_specs.insert(1, pl.BlockSpec((1, planes, width), row))
        operands.insert(1, kv_new.astype(kvcache.dtype))
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct(kvcache.shape, kvcache.dtype),
        )
        out_specs = (out_specs, pl.BlockSpec(memory_space=pl.ANY))
        scratch += [
            pltpu.VMEM((planes, write_rows, width), kvcache.dtype),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SMEM((1,), jnp.int32),
        ]
        # the cache is updated in place: the last operand is the
        # second result
        aliases = {len(prefetch) + len(operands) - 1: 1}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(
            _walk_decode_kernel, block_t=block_t, math=math, planes=planes,
            write_rows=write_rows,
        ),
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        # rows in order: each starts the next one's first copies
        compiler_params=_dim_semantics(interpret, ("arbitrary",)),
        interpret=interpret,
        name=name,
    )(*prefetch, *operands)


@functools.partial(
    jax.jit, static_argnames=("n_kv_heads", "block_t", "interpret")
)
def _decode_attention(q, kvcache, pos, active, layer, kv_scales, kv_new,
                      write_at, *, n_kv_heads: int, block_t: int,
                      interpret: bool):
    b, g, hk = q.shape
    t = kvcache.shape[3]
    head_dim = hk // n_kv_heads
    last = _decode_last_rows(pos, active, b, t)
    layer = jnp.reshape(layer, (1,))
    statics = dict(
        block_t=block_t, n_kv_heads=n_kv_heads, head_dim=head_dim,
        groups=g, scale=1.0 / (head_dim**0.5),
    )
    out_shape = jax.ShapeDtypeStruct((b, g, hk), q.dtype)

    def row(i, *_):
        return (i, 0, 0)

    if kv_scales is None:
        return _walk_call(
            _kv_walk_math(n_kv_heads, head_dim, g, statics["scale"], block_t),
            q, kvcache, kv_new, write_at, last, layer, out_width=hk,
            block_t=block_t, interpret=interpret, name="decode_attn",
        )

    assert kv_new is None, "the int8 slab's rows are written by XLA"
    assert kvcache.dtype == jnp.int8, kvcache.dtype
    assert kv_scales.shape == (kvcache.shape[0], 2, b, t, 1), kv_scales.shape

    def plane(p, width):
        # one K or V plane of the stacked buffer, walked per row: tt
        # runs over every block, the map stops at the row's last one
        def index(i, tt, layer, last, src, hi):
            blk = jnp.where(last[i] < 0, hi[i], jnp.minimum(tt, hi[i]))
            return (layer[0], p, src[i], blk, 0)
        return pl.BlockSpec((1, 1, 1, block_t, width), index)

    gh = g * n_kv_heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, t // block_t),
        # the K and V planes of the one stacked cache buffer as two
        # block views (XLA dedups the duplicated operand), then their
        # per-row scale planes (trailing singleton keeps the block
        # Mosaic-legal: second-to-last dim block_t %8, last full)
        in_specs=[
            pl.BlockSpec((1, g, hk), row),
            plane(0, hk), plane(1, hk), plane(0, 1), plane(1, 1),
        ],
        out_specs=pl.BlockSpec((1, g, hk), row),
        scratch_shapes=[
            pltpu.VMEM((1, gh), jnp.float32),  # m (lane = g*n_kv+h)
            pltpu.VMEM((1, gh), jnp.float32),  # l
            pltpu.VMEM((g, hk), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _bounded_grid_kernel, n_t=t // block_t, quantized=True, **statics
        ),
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=_dim_semantics(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(
        layer, last, *_grid_walk(last, block_t),
        q, kvcache, kvcache, kv_scales, kv_scales,
    )


def _paged_decode_kernel(tbl_ref, q_ref, k_ref, v_ref, pos_ref, *rest,
                         **kw):
    """Paged grid step: identical math to ``_flash_decode_kernel`` —
    the block table ref is consumed by the BlockSpec index maps (it
    picks WHICH pool block streams in per (batch, tile) cell), never by
    the body, so the per-tile arithmetic and the online-softmax
    accumulation order are the slab kernel's, tile for tile."""
    del tbl_ref  # scalar-prefetch operand: index-map-only
    _flash_decode_kernel(q_ref, k_ref, v_ref, pos_ref, *rest, **kw)


def flash_decode_attention_paged(
    q: jax.Array,
    blocks: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    n_kv_heads: int,
    layer: int = 0,
    interpret: bool | None = None,
    block_scales: jax.Array | None = None,
) -> jax.Array:
    """One decode step of causal attention against a BLOCK-PAGED KV
    pool (vLLM-style): K/V live as a shared pool of fixed-size blocks,
    ``blocks`` (n_layers, 2, n_blocks, block_size, Hkv*K), and each
    batch row reads the blocks its ``tables`` row names, in table
    order. The table is a SCALAR-PREFETCH operand
    (``pltpu.PrefetchScalarGridSpec``): the grid is (B, blocks_per_
    slot) and the K/V BlockSpec index maps look the pool block id up as
    ``tables[i, tt]`` — the kernel gathers block-by-block straight from
    HBM, no contiguous slab view is ever materialized. Entry semantics
    match the serving pool: entry ``j`` maps logical rows
    [j*block_size, (j+1)*block_size); id 0 is the all-zero sentinel for
    unallocated entries (masked out anyway — tiles past ``pos`` skip).

    The per-tile math is ``_flash_decode_kernel``'s, so the output is
    bitwise ``flash_decode_attention(..., block_t=block_size)`` over
    the gathered contiguous cache — same tile partitioning, same
    accumulation order. ``block_scales`` (int8 mode) carries the
    per-row dequant planes (n_layers, 2, n_blocks, block_size, 1) f32;
    dequantization stays fused in the inner loop exactly as in the
    slab kernel, so the HBM stream is the int8 bytes plus the table
    ints.
    """
    b, g, hk = q.shape
    bs = blocks.shape[3]
    bps = tables.shape[1]
    head_dim = hk // n_kv_heads
    assert tables.shape == (b, bps), (tables.shape, b)
    assert bs % 8 == 0, f"block_size must be a multiple of 8, got {bs}"
    interpret = _default_interpret() if interpret is None else interpret
    quantized = block_scales is not None
    kernel = functools.partial(
        _paged_decode_kernel, block_t=bs, n_t=bps,
        n_kv_heads=n_kv_heads, head_dim=head_dim, groups=g,
        scale=1.0 / (head_dim**0.5), quantized=quantized,
    )
    pos_arr = _decode_positions(pos, b)
    in_specs = [
        pl.BlockSpec((1, g, hk), lambda i, tt, tbl: (i, 0, 0)),
        # K and V planes of the one block pool, table-indexed on the
        # block axis (XLA dedups the duplicated operand)
        pl.BlockSpec(
            (1, 1, 1, bs, hk),
            lambda i, tt, tbl: (layer, 0, tbl[i, tt], 0, 0),
        ),
        pl.BlockSpec(
            (1, 1, 1, bs, hk),
            lambda i, tt, tbl: (layer, 1, tbl[i, tt], 0, 0),
        ),
        _POS_SPEC,
    ]
    operands = [q, blocks, blocks, pos_arr]
    if quantized:
        assert blocks.dtype == jnp.int8, blocks.dtype
        assert block_scales.shape == (
            blocks.shape[0], 2, blocks.shape[2], bs, 1
        ), block_scales.shape
        in_specs += [
            pl.BlockSpec(
                (1, 1, 1, bs, 1),
                lambda i, tt, tbl: (layer, 0, tbl[i, tt], 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, bs, 1),
                lambda i, tt, tbl: (layer, 1, tbl[i, tt], 0, 0),
            ),
        ]
        operands += [block_scales, block_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, bps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, hk), lambda i, tt, tbl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, g * n_kv_heads), jnp.float32),  # m (lane = g*n_kv+h)
            pltpu.VMEM((1, g * n_kv_heads), jnp.float32),  # l
            pltpu.VMEM((g, hk), jnp.float32),              # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, g, hk), q.dtype),
        grid_spec=grid_spec,
        compiler_params=_dim_semantics(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attn",
    )(jnp.asarray(tables, jnp.int32), *operands)


# -- fused embedding dot (word2vec HS read side) ------------------------------

def _emb_dot_kernel(h_ref, w_ref, mask_ref, out_ref):
    h = h_ref[:]  # (block_b, d)
    w = w_ref[:]  # (block_b, L, d)
    mask = mask_ref[:]  # (block_b, L)
    dots = jnp.einsum("bd,bld->bl", h, w)
    # clip for the sigmoid only — this is the READ side (f values); the
    # skip-on-saturation semantics live in the gradient computation
    # (_hs_math's in_range on g), not here: zeroing f would be
    # indistinguishable from a genuinely small sigmoid downstream
    out_ref[:] = jax.nn.sigmoid(jnp.clip(dots, -6.0, 6.0)) * mask


def fused_embedding_dot(
    h: jax.Array, w_rows: jax.Array, mask: jax.Array, block_b: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """sigmoid(<h_b, w_{b,l}>) * mask — (B, D), (B, L, D), (B, L) -> (B, L)."""
    b, d = h.shape
    L = w_rows.shape[1]
    block_b = min(block_b, b)
    assert b % block_b == 0
    interpret = _default_interpret() if interpret is None else interpret
    return pl.pallas_call(
        _emb_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((b, L), h.dtype),
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, L, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, L), lambda i: (i, 0)),
        interpret=interpret,
        name="emb_dot",
    )(h, w_rows, mask)
