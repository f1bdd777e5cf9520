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
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[:, 0] = corr * l_s[:, 0] + jnp.sum(p, axis=-1)
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
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if masked:
            s = s + _causal_bias(q_start, k_start, block_q, block_k)
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
        dq_c = jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32
        ) * scale
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

    # invisible tiles are skipped wholesale (in rmw mode their dq tile
    # is left untouched — kv block 0, always visible, initialized it;
    # in partials mode their plane block is zeroed below)
    _causal_dispatch(compute, causal, q_start, k_start, block_q, block_k)
    if dq_partials and causal:
        @pl.when(
            jnp.logical_not(_kv_block_visible(q_start, k_start, block_q))
        )
        def _dq_zero():
            dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

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
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0
    interpret = _default_interpret() if interpret is None else interpret
    if layout == "bhtd":
        qf, kf, vf = (a.reshape(b * h, t, d) for a in (q, k, v))
    else:
        qf, kf, vf = (
            a.transpose(0, 2, 1, 3).reshape(b * h, t, d) for a in (q, k, v)
        )
    if bwd_block_q is not None:
        bwd_block_q = min(bwd_block_q, t)
        assert t % bwd_block_q == 0
    if bwd_block_k is not None:
        bwd_block_k = min(bwd_block_k, t)
        assert t % bwd_block_k == 0
    out = _flash_bhtd(
        qf, kf, vf, block_q, block_k, interpret, causal,
        bwd_block_q, bwd_block_k,
    )
    out = out.reshape(b, h, t, d)
    return out if layout == "bhtd" else out.transpose(0, 2, 1, 3)


# -- flash decode attention (single-position KV-cache read) -------------------
#
# The decode hot loop reads the WHOLE KV cache every step, so its HBM
# layout is the perf story. A (B, T, H, K) cache tiles on (H, K) =
# (12, 64) which Mosaic/XLA pads to (16, 128) — 2.67x the logical bytes
# streamed per step (measured: the QK einsum alone was 601us/step at
# GPT-2-small B=16). This kernel reads a PACKED (B, T, H*K) cache whose
# minor dim is a lane-aligned 768: padding ~1.01x, and the per-head
# split happens in registers via an iota-built block-diagonal expansion
# matrix (no lane-splitting relayout). The online softmax runs in VMEM
# scratch across sequential T blocks, exactly like the training flash
# kernel; masked positions (> pos, or cache padding) contribute nothing
# and fully-invisible blocks skip compute.


def _flash_decode_kernel(
    q_ref, k_ref, v_ref, pos_ref, *rest,
    block_t: int, n_t: int, n_kv_heads: int, head_dim: int,
    groups: int, scale: float, quantized: bool = False,
):
    """One (batch, t-block) grid step of single-position decode attention.

    All ``groups`` query rows are folded into ONE pair of wide MXU
    contractions per block (r5 rewrite): the per-group Python loop of
    the original kernel ran `groups` iterations of (block_t, n_kv)-thin
    ops, which made GQA (groups=3, n_kv=2) SLOWER than MHA despite a 3x
    smaller cache stream (11.1K vs 11.5K tok/s measured in situ).

    - K side: s_all (block_t, G*n_kv) = KB @ M^T via one dot_general,
      where M[(g,h), j] = q_g[j] * (head(j)==h) — the query fold into
      the block-diagonal reducer. In int8 mode KB stays int8 and M is
      built int8 from the in-register-quantized queries (one scale per
      group), so the dot runs on the int8 MXU and the cache is never
      converted.
    - V side: PV (G*n_kv, hk) = softmax-weights^T @ VB via one
      dot_general contracting the t axis (int8 mode: weights quantized
      per tile, VB stays int8), then an iota-built segment mask + one
      tiny (G, G*n_kv) dot collapse per-head rows into per-group
      outputs. No (block_t, hk) elementwise pass touches the V block in
      either mode.

    Softmax state lives in (1, G*n_kv) lanes (lane = g*n_kv + h);
    the accumulator is (G, hk).
    rest = ([ks_ref, vs_ref,] o_ref, m_s, l_s, acc_s).
    """
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

    hk = n_kv_heads * head_dim
    gh = groups * n_kv_heads
    # iota-built structure matrices (no data movement):
    # e_tile[r, j] = (head(j) == r % n_kv): head-segment mask per
    # (group, head) row; s_g[g, r] = (r // n_kv == g): group collapse
    # (its transpose doubles as the row-repeat of per-group values).
    row_h = jax.lax.broadcasted_iota(jnp.int32, (gh, hk), 0) % n_kv_heads
    col_h = jax.lax.broadcasted_iota(jnp.int32, (gh, hk), 1) // head_dim
    e_tile = (row_h == col_h).astype(jnp.float32)  # (gh, hk)
    g_row = jax.lax.broadcasted_iota(jnp.int32, (groups, gh), 0)
    g_col = jax.lax.broadcasted_iota(jnp.int32, (groups, gh), 1) // n_kv_heads
    s_g = (g_row == g_col).astype(jnp.float32)  # (groups, gh)

    @pl.when(t_start <= pos)
    def _compute():
        # operands stay in the storage dtype (bf16 on TPU: the MXU fast
        # path — f32-operand dots measured ~4x slower); softmax state
        # and accumulators are f32. int8 mode: both cache planes feed
        # the MXU directly as int8 — converting a plane on the VPU
        # costs more than the int8 DMA saves (measured 43us/layer,
        # bf16-equal, before this design).
        qf = q_ref[0].astype(jnp.float32)  # (G, hk)
        # M^T rows (g, h): query row g replicated over its n_kv head
        # rows, masked to each head's lane segment
        q_rep = jnp.dot(s_g.T, qf, preferred_element_type=jnp.float32)
        if quantized:
            kb = k_ref[0, 0, 0]  # int8 (block_t, hk), never converted
            vb = v_ref[0, 0, 0]  # int8, never converted
            ksc = ks_ref[0, 0, 0]  # (block_t, 1) f32
            vsc = vs_ref[0, 0, 0]
            qmax = jnp.maximum(
                jnp.max(jnp.abs(qf), axis=1, keepdims=True), 1e-8
            )  # (G, 1)
            qscale = qmax / 127.0
            qsc_rep = jnp.dot(
                s_g.T, qscale, preferred_element_type=jnp.float32
            )  # (gh, 1): per-(group,head)-row q scale
            qsc_lane = qsc_rep.reshape(1, gh)
            q_rep_scaled = q_rep / qsc_rep
            m_t = (
                jnp.clip(jnp.round(q_rep_scaled), -127, 127) * e_tile
            ).astype(jnp.int8)  # (gh, hk)
            s_all = jax.lax.dot_general(
                kb, m_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.float32) * (ksc * scale) * qsc_lane
        else:
            kb = k_ref[0, 0, 0]
            vb = v_ref[0, 0, 0]
            m_t = (q_rep * e_tile).astype(kb.dtype)
            s_all = jax.lax.dot_general(
                kb, m_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (block_t, gh)
        rows = t_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, 1), 0
        )
        s_all = jnp.where(rows > pos, -jnp.inf, s_all)
        m_prev = m_s[:]  # (1, gh)
        m_new = jnp.maximum(m_prev, jnp.max(s_all, axis=0, keepdims=True))
        p = jnp.exp(s_all - m_new)  # (block_t, gh) f32
        corr = jnp.exp(m_prev - m_new)  # (1, gh)
        l_s[:] = corr * l_s[:] + jnp.sum(p, axis=0, keepdims=True)
        if quantized:
            p_v = p * vsc
            pmax = jnp.maximum(jnp.max(p_v), 1e-30)
            psc = pmax / 127.0
            p_low = jnp.clip(jnp.round(p_v / psc), -127, 127).astype(
                jnp.int8
            )
        else:
            psc = None
            p_low = p.astype(vb.dtype)
        pv = jax.lax.dot_general(
            p_low, vb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32 if quantized else jnp.float32,
        )  # (gh, hk): row (g, h) valid only on head-segment h
        pv_m = pv.astype(jnp.float32) * e_tile
        if quantized:
            pv_m = pv_m * psc
        o_blk = jnp.dot(
            s_g, pv_m, preferred_element_type=jnp.float32
        )  # (G, hk)
        # per-lane correction expanded to (G, hk): corr[g, head(j)]
        corr_exp = jnp.dot(
            s_g * corr, e_tile, preferred_element_type=jnp.float32
        )
        acc_s[:] = acc_s[:] * corr_exp + o_blk
        m_s[:] = m_new

    @pl.when(tt == n_t - 1)
    def _finalize():
        l_exp = jnp.dot(
            s_g * jnp.maximum(l_s[:], 1e-30), e_tile,
            preferred_element_type=jnp.float32,
        )  # (G, hk)
        o_ref[0] = (acc_s[:] / l_exp).astype(o_ref.dtype)


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


def flash_decode_attention(
    q: jax.Array,
    kvcache: jax.Array,
    pos: jax.Array,
    n_kv_heads: int,
    layer: int = 0,
    block_t: int | None = None,
    interpret: bool | None = None,
    kv_scales: jax.Array | None = None,
) -> jax.Array:
    """One decode step of causal attention against a packed KV cache.

    ``q``: (B, G, Hkv*K) — query heads grouped for GQA (G = H/Hkv; 1 for
    MHA), each group packed head-major. ``kvcache``: the FULL STACKED
    (n_layers, 2, B, T, Hkv*K) cache (axis 1: K then V) — ``layer`` (a
    static int) selects the layer inside the BlockSpec index map, so no
    host-side slice is needed. (Slicing the stack outside the kernel
    materializes a copy of the whole layer cache per call — a custom
    call needs a dense operand buffer, so XLA cannot fuse the slice the
    way it fuses one feeding an einsum: 521us/step at GPT-2-small,
    measured.) T must be a multiple of ``block_t`` (callers pad; rows
    beyond ``pos`` are masked so padding is free). ``pos``: scalar
    int32, the position being decoded, or an (B,) vector of per-row
    positions (continuous-batching serving, where each slot decodes at
    its own depth) — rows > pos are invisible. Returns (B, G, Hkv*K)
    attention output in q's dtype.

    ``kv_scales`` (int8 serving mode): per-row dequant scales
    (n_layers, 2, B, T, 1) f32 for an int8 ``kvcache`` — rows convert
    to q's dtype in-register and the scales fold into the logits (K) /
    softmax weights (V), so the HBM cache stream is the int8 bytes.
    """
    b, g, hk = q.shape
    t = kvcache.shape[3]
    head_dim = hk // n_kv_heads
    # the block search below requires an 8-aligned T to terminate
    assert t % 8 == 0, f"cache T dim must be a multiple of 8, got {t}"
    if block_t is None:
        # as FEW t blocks as VMEM allows: per-cell fixed costs dominate
        # at this arithmetic intensity, so bigger blocks win as long as
        # they fit — at T=8704 raising the block from 512 to 4352
        # measured +24.5% tok/s (r5 "8k-context serving"). The ceiling
        # is the ~16MB scoped VMEM budget: the K and V block planes,
        # double-buffered by the pipeline, are the dominant allocation
        # (a single 8704-row bf16 block OOMed at 17.04M, matching the
        # 4-plane estimate), so cap rows at 14MiB / (hk * eff_bytes * 4)
        # with headroom for q/out/scratch. int8 caches stream half the
        # HBM bytes but the kernel's in-register conversion keeps extra
        # per-block scratch: the measured single-block int8 OOM
        # (25.54M at T=8704, hk=256) works out to ~2.87 bytes per
        # element-plane, so int8 budgets at 3 — NOT its 1-byte stream
        # size. The 14MB budget is sized so the measured-best bf16
        # block (4352 at hk=256: 8.5M actual) and its int8 twin
        # (12.8M actual) both land under the 16MB scoped limit with
        # headroom. No floor overriding the budget: huge-hk geometries
        # get correspondingly small blocks instead of an OOM. Then the
        # smallest divisor count that keeps blocks under the cap and
        # 8-aligned; callers size T as a multiple of 512 above 1024
        # (init_caches), so the search lands on large blocks instead
        # of walking down to 8-row blocks (an adversarial 8*prime T
        # would pay ~100x per-cell).
        eff_bytes = 3 if kvcache.dtype.itemsize == 1 else kvcache.dtype.itemsize
        cap = max(8, (14 * 1024 * 1024) // (hk * eff_bytes * 4))
        n_t = -(-t // cap)
        while t % n_t or (t // n_t) % 8:
            n_t += 1
        block_t = t // n_t
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    interpret = _default_interpret() if interpret is None else interpret
    n_t = t // block_t
    quantized = kv_scales is not None
    kernel = functools.partial(
        _flash_decode_kernel, block_t=block_t, n_t=n_t,
        n_kv_heads=n_kv_heads, head_dim=head_dim, groups=g,
        scale=1.0 / (head_dim**0.5), quantized=quantized,
    )
    pos_arr = _decode_positions(pos, b)
    in_specs = [
        pl.BlockSpec((1, g, hk), lambda i, tt: (i, 0, 0)),
        # the K and V planes of the one stacked cache buffer, as two
        # block views (XLA dedups the duplicated operand)
        pl.BlockSpec(
            (1, 1, 1, block_t, hk),
            lambda i, tt: (layer, 0, i, tt, 0),
        ),
        pl.BlockSpec(
            (1, 1, 1, block_t, hk),
            lambda i, tt: (layer, 1, i, tt, 0),
        ),
        _POS_SPEC,
    ]
    operands = [q, kvcache, kvcache, pos_arr]
    if quantized:
        assert kvcache.dtype == jnp.int8, kvcache.dtype
        assert kv_scales.shape == (kvcache.shape[0], 2, b, t, 1), (
            kv_scales.shape
        )
        # per-row scale planes for K and V (trailing singleton keeps the
        # block Mosaic-legal: second-to-last dim block_t %8, last full)
        in_specs += [
            pl.BlockSpec(
                (1, 1, 1, block_t, 1),
                lambda i, tt: (layer, 0, i, tt, 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, block_t, 1),
                lambda i, tt: (layer, 1, i, tt, 0),
            ),
        ]
        operands += [kv_scales, kv_scales]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, g, hk), q.dtype),
        grid=(b, n_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, hk), lambda i, tt: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, g * n_kv_heads), jnp.float32),  # m (lane = g*n_kv+h)
            pltpu.VMEM((1, g * n_kv_heads), jnp.float32),  # l
            pltpu.VMEM((g, hk), jnp.float32),              # acc
        ],
        compiler_params=_dim_semantics(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attn",
    )(*operands)


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
