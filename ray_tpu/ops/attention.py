"""Attention ops: jnp reference + Pallas flash attention with custom VJP.

Public entry point is :func:`attention` which dispatches to the Pallas kernel
on TPU (or interpret mode when forced) and to the XLA reference elsewhere.
Shapes follow (batch, seq, heads, head_dim); GQA is supported by num_kv_heads
dividing num_heads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import flash_attention as _fa


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(b, s, kv_heads, d) -> (b, s, num_heads, d) for GQA."""
    b, s, kvh, d = k.shape
    if kvh == num_heads:
        return k
    if num_heads % kvh:
        raise ValueError(f"num_heads {num_heads} not divisible by kv_heads {kvh}")
    reps = num_heads // kvh
    return jnp.repeat(k, reps, axis=2)


def mha_reference(q, k, v, *, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  segment_ids: Optional[jax.Array] = None,
                  q_offset: Optional[int] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Plain XLA attention. (b, s, h, d) layout. O(S^2) memory — the
    correctness oracle and the CPU-test path. ``q_offset`` places the
    causal diagonal (query i attends keys <= i + q_offset; default
    sk - sq: queries are the last rows); ``window`` keeps the last
    ``window`` of those keys (a sliding-window layer)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    keep = jnp.ones((b, 1, sq, sk), dtype=bool)
    if causal:
        diag = (sk - sq) if q_offset is None else q_offset
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=diag)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((sq, sk), dtype=bool),
                                    k=diag - window)
        keep = keep & mask[None, None]
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        keep = keep & seg_mask[:, None]
    logits = jnp.where(keep, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows produce 0, matching the flash-kernel convention.
    probs = jnp.where(keep.any(axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --- flash attention with custom vjp (pallas fwd + pallas bwd) -------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret,
           q_offset=None, window=None):
    # Primal (inference) path: skip the lse output entirely.
    o, _ = _fa.flash_attention_fwd(q, k, v, sm_scale=sm_scale, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret, with_lse=False,
                                   q_offset=q_offset, window=window)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               q_offset=None, window=None):
    if q_offset is not None or window is not None \
            or v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "q_offset (chunked-prefill causal placement), window and "
            "values of another width than the keys are inference-only "
            "paths; the backward kernels assume causal queries that are "
            "the last rows, and one head width")
    o, lse = _fa.flash_attention_fwd(q, k, v, sm_scale=sm_scale, causal=causal,
                                     block_q=block_q, block_k=block_k,
                                     interpret=interpret)
    # Under jax.checkpoint with a save_only_these_names policy, naming the
    # kernel outputs lets the backward pass reuse them instead of re-running
    # the forward kernel (q/k/v are cheap weight-matmul recomputes; o/lse
    # are not). lse is (BH, 1, S), one value a lane, as the kernels write
    # and read it.
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, q_offset,
               window, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, o, do, lse, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    q_offset: Optional[int] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Pallas flash attention, (b, s, h, d) layout, differentiable
    (except with q_offset, the inference-only chunked-prefill causal
    placement, window, a sliding-window layer's band, or values (b, s, h,
    dv) of another width than the keys, a latent layer's heads)."""
    b, sq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    # (b, s, h, d) -> (b*h, s, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, v.shape[-1])
    of = _flash(qf, kf, vf, scale, causal, block_q, block_k, interpret,
                q_offset, window)
    return of.reshape(b, h, sq, -1).transpose(0, 2, 1, 3)


def _on_tpu() -> bool:
    """Whether this process's JAX backend is a TPU. A backend that
    fails to initialise raises here: an ``auto`` path is chosen from
    the platform, never from a caught error."""
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = True,
              sm_scale: Optional[float] = None,
              impl: str = "auto",
              block_q: int = 128, block_k: int = 128,
              q_offset: Optional[int] = None,
              window: Optional[int] = None) -> jax.Array:
    """Dispatch: 'auto' uses the Pallas kernel on TPU for seq >= 128 and the
    XLA reference otherwise. 'flash' / 'reference' force a path;
    'flash_interpret' runs the kernel in interpret mode (CPU tests)."""
    if impl == "auto":
        impl = "flash" if (_on_tpu() and q.shape[1] >= 128) else "reference"
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             q_offset=q_offset, window=window)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               q_offset=q_offset, window=window)
    if impl == "flash_interpret":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k,
                               interpret=True, q_offset=q_offset,
                               window=window)
    raise ValueError(f"unknown attention impl: {impl}")
