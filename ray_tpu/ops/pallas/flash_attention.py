"""Pallas TPU flash-attention (forward + backward kernels).

The hot op of the framework's model stack. Online-softmax tiling keeps the
S x S score matrix out of HBM; blocks are sized for the MXU (128 lanes) and
VMEM residency. Used by :mod:`ray_tpu.ops.attention` which wires it into a
``jax.custom_vjp``.

Design notes (measured on v5e):
- Matmul operands stay in the input dtype (bf16) with f32 MXU accumulation;
  upcasting operands to f32 would halve MXU throughput.
- ``sm_scale`` is folded into ``q`` before the kernels run, saving a full
  elementwise pass over the S x S score matrix in every kernel (the VPU, not
  the MXU, is the bottleneck of flash attention at long seq). The dq output
  is rescaled once outside (O(S*D), negligible).
- One masked code path: TPU predication (pl.when) compiles both branches
  into the kernel, so splitting interior/edge tiles doubles VMEM stack for
  no win (measured).

Sequence lengths need not divide the block size: wrappers zero-pad to block
multiples and kernels mask out-of-bounds columns (padded rows are sliced off
and padded inputs are zeros, so gradients through padding vanish).

Capability analog of what the reference delegates to vLLM/FlashAttention CUDA
kernels (reference has no TPU attention kernel; see SURVEY.md section 5.7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # m/l scratch are broadcast along the lane dim


def _pad_seq(x, block):
    """Zero-pad (bh, s, d) along s to a multiple of block."""
    s = x.shape[1]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _mask_s(s, qi, ki, block_q, block_k, kv_len, causal, offset):
    """Bounds + causal mask for a (block_q, block_k) score tile.

    ``offset = sk - sq`` aligns the causal diagonal with the END of the kv
    sequence (query i attends keys j <= i + offset), matching mha_reference —
    e.g. a decode step (sq=1) against a longer KV cache attends everything.
    """
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = cols < kv_len
    if causal:
        keep = jnp.logical_and(keep, rows + offset >= cols)
    return jnp.where(keep, s, NEG_INF), keep


def _last_k_block(qi, block_q, block_k, num_kv_blocks, offset):
    """Last kv block (inclusive) a causal q block attends to, clamped so the
    finalize step always fires even for fully-masked q blocks."""
    last = ((qi + 1) * block_q - 1 + offset) // block_k
    return jnp.clip(last, 0, num_kv_blocks - 1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                causal, block_q, block_k, num_kv_blocks, kv_len,
                offset, with_lse):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Last kv block this q block attends to (inclusive).
    if causal:
        last_k = _last_k_block(qi, block_q, block_k, num_kv_blocks, offset)
    else:
        last_k = num_kv_blocks - 1

    @pl.when(ki <= last_k)
    def _compute():
        q = q_ref[0]                                # (block_q, d), pre-scaled
        k = k_ref[0]                                # (block_k, d)
        v = v_ref[0]                                # (block_k, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s, keep = _mask_s(s, qi, ki, block_q, block_k,
                          kv_len, causal, offset)

        m_prev = m_scr[...][:, :1]                  # (block_q, 1)
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp in the input dtype: bf16 exp is measurably faster on the VPU
        # and p feeds a bf16 MXU matmul anyway; f32 inputs keep f32 exp.
        pdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
        p = jnp.where(keep, jnp.exp((s - m_new).astype(pdt)), pdt(0.0))
        alpha = jnp.exp(m_prev - m_new)             # (block_q, 1)
        l_new = alpha * l_prev + jnp.sum(p.astype(jnp.float32), axis=-1,
                                         keepdims=True)

        acc = acc_scr[...]
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == last_k)
    def _finalize():
        m = m_scr[...][:, :1]
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref[0].shape)


def flash_attention_fwd(q, k, v, *, sm_scale, causal, block_q=128, block_k=128,
                        interpret=False, with_lse=True, q_offset=None):
    """q,k,v: (BH, S, D) -> (o: (BH, S, D), lse: (BH, S, LANES) f32 | None).

    lse is the row logsumexp saved as a backward residual (lane-broadcast
    layout; logically (BH, S)). Inference callers pass with_lse=False to
    skip the extra HBM write (pallas outputs are never DCE'd).

    ``q_offset`` places the causal diagonal: query row i attends keys
    <= i + q_offset. Default (None) = sk - sq, i.e. queries are the
    LAST sq rows of the kv sequence. Chunked prefill passes the chunk's
    absolute start position instead (queries sit mid-sequence, not at
    the end); must be static — one compile per distinct offset."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    offset = (sk - sq) if q_offset is None else int(q_offset)
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)  # fold scale in
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    kernel = functools.partial(
        _fwd_kernel, causal=causal,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk, kv_len=sk,
        offset=offset, with_lse=with_lse)

    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct(qp.shape, q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, qp.shape[1], LANES), jnp.float32))

    res = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    if with_lse:
        out, lse = res
        return out[:, :sq], lse[:, :sq]
    return res[0][:, :sq], None


# ---------------------------------------------------------------------------
# Backward. lse comes from the forward kernel (saved residual — no recompute
# pass). Kernels: (1) dk/dv with grid over kv blocks, inner loop over q
# blocks; (2) dq with grid over q blocks, inner loop over kv blocks. p is
# recomputed per tile from q,k and lse; delta = rowsum(do * o).
# q arrives pre-scaled by sm_scale, so p = exp(q'k - lse) directly and
# ds needs no extra scale for dk; dq is rescaled by the wrapper.
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, block_q, block_k, num_q_blocks, kv_len,
                offset):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        # First q block whose rows attend this kv block: i + offset >= ki*bk.
        first_q = jnp.maximum(0, ki * block_k - offset) // block_q
        should_run = qi >= first_q
    else:
        should_run = qi >= 0

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                                # (bq, d), pre-scaled
        k = k_ref[0]                                # (bk, d)
        v = v_ref[0]
        do = do_ref[0]                              # (bq, d)
        lse = lse_ref[0][:, :1]                     # (bq, 1)
        delta = delta_ref[0][:, :1]                 # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s, keep = _mask_s(s, qi, ki, block_q, block_k, kv_len, causal, offset)
        pdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
        p = jnp.where(keep, jnp.exp((s - lse).astype(pdt)), pdt(0.0))  # (bq, bk)
        # dv += p^T do
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do v^T ; ds = p * (dp - delta)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dk = ds^T q'  (q' = sm_scale*q, so the scale is already included)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr,
               *, causal, block_q, block_k, num_kv_blocks, kv_len,
               offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    if causal:
        last_k = _last_k_block(qi, block_q, block_k, num_kv_blocks, offset)
    else:
        last_k = num_kv_blocks - 1

    @pl.when(ki <= last_k)
    def _compute():
        q = q_ref[0]                                # pre-scaled
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s, keep = _mask_s(s, qi, ki, block_q, block_k, kv_len, causal, offset)
        pdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
        p = jnp.where(keep, jnp.exp((s - lse).astype(pdt)), pdt(0.0))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dq' = ds k ; wrapper multiplies by sm_scale once outside.
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last_k)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, do, lse, *, sm_scale, causal,
                        block_q=128, block_k=128, interpret=False):
    """lse: (BH, S, LANES) f32 from flash_attention_fwd."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    offset = sk - sq
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)  # fold scale in
    qp = _pad_seq(q, block_q)
    kp, vp = _pad_seq(k, block_k), _pad_seq(v, block_k)
    op, dop = _pad_seq(o, block_q), _pad_seq(do, block_q)
    lse = _pad_seq(lse, block_q)
    sqp, skp = qp.shape[1], kp.shape[1]
    nq = sqp // block_q
    nk = skp // block_k

    delta = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1)                                  # (bh, sqp)
    delta = jnp.broadcast_to(delta[:, :, None], (bh, sqp, LANES))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          kv_len=sk, offset=offset),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, skp, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv_blocks=nk,
                          kv_len=sk, offset=offset),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lse, delta)

    dq = (dq[:, :sq].astype(jnp.float32) * sm_scale).astype(q.dtype)
    return dq, dk[:, :sk], dv[:, :sk]
