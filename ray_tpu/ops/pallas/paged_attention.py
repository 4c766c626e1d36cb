"""Pallas TPU paged-attention decode kernel.

Fuses the two reference designs the serving stack sits between:
vLLM's PagedAttention (block tables over a fixed KV pool) and the
flash-attention tiling already in ``flash_attention.py`` (online
softmax, VMEM-resident running max/sum). One decode step used to cost
a full ``gather_table`` — an O(slots x max_len x layers) HBM copy
materializing the contiguous ``(slots, max_len)`` attention view —
before any attention math ran. Here the Pallas grid walks each slot's
block table DIRECTLY: the kv index_map reads the scalar-prefetched
table and streams the slot's physical pool blocks into VMEM one at a
time, accumulating online-softmax attention. The gathered view never
exists; ``gather_table`` stays only on the prefix-hit prefill path and
in debug/parity tooling.

Numerics mirror ``ray_tpu.llm.model._gqa_attend_cached`` (the gather
path's attention): f32 score dot, post-dot ``/ sqrt(head_dim)`` scale,
f32 exp, f32 accumulation — online softmax is an exact refactoring of
the masked softmax for the same summation order within a block, so the
two impls agree to f32 rounding (and bitwise on integer-valued
constructions; see tests/test_zz_paged_attn.py).

The pool is HEAD-MAJOR — one layer is ``(num_blocks, kv_heads,
block_size, head_dim)`` — so the block one grid step streams,
``(block_size, head_dim)`` for one head of one physical block, is a
whole number of TPU tiles (the TPU compiler refuses a block whose
second-minor dim is a size-1 slice of a longer one, which a
token-major pool forced). ``table_view`` is the one place that turns
this layout back into the contiguous ``(slots, len, kv_heads,
head_dim)`` view the gather path and the references attend over.

Grid: ``(slots, kv_heads, table_width)`` with the table-walk dimension
sequential ("arbitrary"). Blocks past a slot's last live block are
clamped to the last live one in the index_map — reads stay inside
blocks the slot owns, and Mosaic's pipeliner elides the duplicate
consecutive fetches, so short slots don't pay for the table width.

Interpret mode (``interpret=True``) runs the same kernel logic through
the Pallas interpreter — tier-1 (JAX_PLATFORMS=cpu) exercises the real
table walk, masking, and online-softmax phases, not a shadow
implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # m/l scratch are broadcast along the lane dim


def _last_block(length, bs):
    """Index of the last live pool block for a slot with ``length``
    valid positions (length >= 1 on the decode path: empty slots carry
    position 0 => length 1, table row = trash)."""
    return jnp.maximum(length, 1) - 1


def _decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bs, hd):
    b_ = pl.program_id(0)
    j = pl.program_id(2)
    length = lengths_ref[b_]
    last = _last_block(length, bs) // bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)         # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)         # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / jnp.sqrt(
                jnp.float32(hd))                    # (g, bs)
        cols = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        keep = cols < length
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                  # (g, 1)
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == last)
    def _finalize():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    interpret=False):
    """Single-token decode attention straight through block tables.

    q: (slots, kv_heads, group, head_dim) — grouped queries, one token
    per slot; k_pool/v_pool: (num_blocks, kv_heads, block_size,
    head_dim) — ONE layer of the engine pool; tables: (slots, width)
    int32 physical block ids (trash-padded); lengths: (slots,) int32
    valid positions per slot INCLUDING the current token (>= 1).
    Returns (slots, kv_heads, group, head_dim) float32 — the same
    value ``_gqa_attend_cached`` computes from the gathered view, with
    no gathered view.
    """
    b, kvh, g, hd = q.shape
    nb, kvh_p, bs, hd_p = k_pool.shape
    if (kvh_p, hd_p) != (kvh, hd):
        raise ValueError(
            f"pool heads/dim {(kvh_p, hd_p)} != query {(kvh, hd)}")
    w = tables.shape[1]

    def _qmap(b_, h_, j, t, ln):
        return (b_, h_, 0, 0)

    def _kvmap(b_, h_, j, t, ln):
        # clamp past-the-end walks onto the slot's last live block:
        # reads never leave blocks the slot owns, and the pipeliner
        # skips re-fetching the same block on consecutive steps
        last = _last_block(ln[b_], bs) // bs
        return (t[b_, jnp.minimum(j, last)], h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, w),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), _qmap),
            pl.BlockSpec((1, 1, bs, hd), _kvmap),
            pl.BlockSpec((1, 1, bs, hd), _kvmap),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), _qmap),
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, hd=hd)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool,
      v_pool)


def table_view(pool, tables):
    """One layer of the head-major pool seen through block tables as
    the contiguous attention view: (num_blocks, kv_heads, block_size,
    head_dim) x (slots, width) -> (slots, width * block_size, kv_heads,
    head_dim). The same bytes in token order, so attention over the
    view is bitwise what the monolithic cache computes."""
    b, w = tables.shape
    _, kvh, bs, hd = pool.shape
    g = pool[tables]                        # (b, w, kvh, bs, hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(b, w * bs, kvh, hd)


def paged_attention_reference(q, k_pool, v_pool, tables, lengths):
    """Gather-then-softmax reference (the exact math
    ``_gqa_attend_cached`` runs on the gathered view) — the parity
    target the kernel is tested against, and the debug tool for
    bisecting a kernel/table discrepancy on device."""
    hd = q.shape[-1]
    vk = table_view(k_pool, tables)
    vv = table_view(v_pool, tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bkgd,blkd->bkgl", qf,
                        vk.astype(jnp.float32)) / jnp.sqrt(hd)
    mask = jnp.arange(vk.shape[1])[None] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgl,blkd->bkgd", probs,
                      vv.astype(jnp.float32))


def paged_attention_verify(q, k_pool, v_pool, tables, lengths):
    """Multi-query verify attention through block tables — the gather
    twin of ``paged_attention`` widened to w in-flight queries per
    slot for speculative decoding: query j attends the cached history
    PLUS the draft tokens written ahead of it this round, under a
    per-query causal mask.

    q: (slots, w, kv_heads, group, head_dim) — the last emitted token
    plus up to w-1 draft tokens per slot; k_pool/v_pool: one layer of
    the engine pool as in ``paged_attention``; tables: (slots, width)
    int32; lengths: (slots, w) int32 valid positions per QUERY
    including that query's own token (column j = cached + j + 1).
    Returns (slots, w, kv_heads, group, head_dim) float32.

    This is a gather-based implementation (materializes the table view
    per layer, like ``paged_attention_reference``): one verify round
    replaces w sequential decode steps, so it pays ONE gather where
    the sequential gather path paid w — the win the spec-decode bench
    measures. Extending the fused one-query-per-block-walk kernel
    above to multi-query rows is future work; exact-zero masking
    (NEG_INF then softmax) keeps pool bytes beyond each query's mask
    bitwise-irrelevant, so verify rows reproduce sequential decode's
    attention exactly."""
    hd = q.shape[-1]
    vk = table_view(k_pool, tables)
    vv = table_view(v_pool, tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bwkgd,blkd->bwkgl", qf,
                        vk.astype(jnp.float32)) / jnp.sqrt(hd)
    mask = (jnp.arange(vk.shape[1])[None, None]
            < lengths[:, :, None])                  # (b, wq, w*bs)
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bwkgl,blkd->bwkgd", probs,
                      vv.astype(jnp.float32))
