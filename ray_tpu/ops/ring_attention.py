"""Ring attention: exact causal attention over a context-parallel mesh axis.

Each device holds a sequence shard of q, k, v. K/V shards rotate around the
ring via ``lax.ppermute`` while each device folds the visiting chunk into an
online-softmax accumulator — communication rides the ICI ring and overlaps
with the chunk matmuls. Memory is O(S_local^2) per step, O(S_local) state.

The reference framework has no sequence/context parallelism at all
(SURVEY.md section 2.3 verifies the absence); this op plus the "context" mesh
axis in ray_tpu.parallel is the TPU-native capability that fills that gap.

Call inside ``jax.shard_map`` with the sequence dim sharded over
``axis_name``. Differentiable via JAX autodiff (ppermute transposes to the
reverse permutation); per-step work is rematerialized with jax.checkpoint.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30


def _chunk_attn(q, k, v, q_off, k_off, causal, scale):
    """One ring step: q local block vs one visiting kv chunk.

    q: (b, sq, h, d); k, v: (b, sk, kvh, d) — GQA heads are expanded HERE,
    after the ring transfer, so only kvh heads ride the ICI ring. Offsets are
    global sequence positions of element 0. Returns (o_unnorm f32, m, l) with
    shapes ((b, sq, h, d), (b, h, sq), (b, h, sq)).
    """
    from ray_tpu.ops.attention import _repeat_kv
    k = _repeat_kv(k, q.shape[2])
    v = _repeat_kv(v, q.shape[2])
    sq, sk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        rows = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = rows >= cols
        s = jnp.where(keep[None, None], s, _NEG)
    m = jnp.max(s, axis=-1)                              # (b, h, sq)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(keep[None, None], p, 0.0)          # kill exp(0) on -inf rows
    l = jnp.sum(p, axis=-1)                              # (b, h, sq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(q, k, v, *, axis_name: str, causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Exact attention with seq sharded over ``axis_name``; (b, s, h, d).
    GQA k/v keep their kvh heads while rotating (n_heads/kvh less ICI
    traffic); expansion happens per-chunk inside _chunk_attn."""
    b, sq, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    sk = k.shape[1]
    q_off = idx * sq

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        o, m, l, k_cur, v_cur = carry
        src = (idx - t) % n               # whose shard is visiting this step
        k_off = src * sk

        def compute(_):
            oc, mc, lc = _chunk_attn(q, k_cur, v_cur, q_off, k_off,
                                     causal, scale)
            m_new = jnp.maximum(m, mc)
            a1 = jnp.exp(m - m_new)                      # (b, h, sq)
            a2 = jnp.exp(mc - m_new)
            a1t = jnp.transpose(a1, (0, 2, 1))[..., None]  # (b, sq, h, 1)
            a2t = jnp.transpose(a2, (0, 2, 1))[..., None]
            o2 = o * a1t + oc * a2t
            return o2, m_new, l * a1 + lc * a2

        def skip(_):
            return o, m, l

        if causal:
            # Chunk entirely in the future of every local row -> no-op.
            fully_masked = k_off > q_off + sq - 1
            o2, m2, l2 = lax.cond(fully_masked, skip, compute, None)
        else:
            o2, m2, l2 = compute(None)

        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o2, m2, l2, k_nxt, v_nxt), None

    # Derive accumulators from q so they carry q's varying-manual-axes set
    # (shard_map vma tracking; a plain zeros constant would be unvarying and
    # trip lax.cond's branch-type check).
    zeros = q.astype(jnp.float32) * 0.0
    o0 = zeros
    base = jnp.transpose(zeros[..., 0], (0, 2, 1))      # (b, h, sq)
    m0 = base + _NEG
    l0 = base
    zscalar = jnp.sum(zeros) * 0.0  # scalar carrying q's vma
    k = k + zscalar.astype(k.dtype)  # unify kv vma with q's as well
    v = v + zscalar.astype(v.dtype)
    (o, m, l, _, _), _ = lax.scan(
        jax.checkpoint(step), (o0, m0, l0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    o = o / jnp.transpose(l, (0, 2, 1))[..., None]
    return o.astype(q.dtype)
