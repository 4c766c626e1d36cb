"""A gated short convolution (the "conv" operator of the LFM2 family) for
the serving forwards, in plain ``jax.numpy``. For normed rows u (.., d):

    [B | C | X] = u W_in          (d -> 3 d, no bias, split in that order)
    z_t = B_t * X_t
    c_t = sum_{j < K} w[:, j] * z_{t - (K - 1) + j}    (``ssm.conv_taps``)
    out_t = (C_t * c_t) W_out     (d -> d)

a causal depthwise convolution of ``K`` = ``shortconv_kernel`` taps along
the tokens, no bias and NO activation. What a request keeps of such a layer
between its tokens is the conv's TAIL alone, the last ``K - 1`` rows of z
(before the conv), whatever its length: no recurrent state, nothing in
float32. The pool keeps a slot's tail FLAT, its rows side by side in the
lanes, ((K - 1) * d,): a (K - 1, d) pair of minor dimensions would lie in
tiles of 16 rows on the device, eight times the bytes.

z and the tail are in the activations' dtype (bf16 as served), the dtype
they have where they are made; the taps' products and their sum are float32
(``conv_taps``). Scopes: ``shortconv.proj``, ``shortconv.conv``,
``shortconv.out``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.ssm import conv_taps


def tail_shape(cfg) -> tuple:
    """A slot's tail of one layer as the pool keeps it: flat."""
    return ((cfg.shortconv_kernel - 1) * cfg.dim,)


def _project(u, lp):
    """u (.., d) -> (z = B * X, C), each (.., d)."""
    with jax.named_scope("shortconv.proj"):
        B, C, X = jnp.split(u @ lp["w_in"], 3, axis=-1)
        return B * X, C


def _out(C, c, lp):
    with jax.named_scope("shortconv.out"):
        # c (.., d) float32: the gate's product rounds once
        return (C.astype(jnp.float32) * c).astype(C.dtype) @ lp["w_out"]


def prefill(u, lp, cfg, tail0, length):
    """One row's tokens through the operator. u (s, d) normed rows, tail0
    ((K - 1) * d,): what the row starts from (zeros for a prompt's first
    chunk) -> (out (s, d), the tail after position ``length - 1``: rows
    ``length - (K - 1) ... length - 1`` of z, the predecessors included
    where the row is shorter)."""
    s, d = u.shape
    z, C = _project(u, lp)
    with jax.named_scope("shortconv.conv"):
        ext = jnp.concatenate([tail0.reshape(-1, d).astype(z.dtype), z])
        c = conv_taps(ext, lp["conv"], s)
        tail = lax.dynamic_slice_in_dim(ext, length,
                                        cfg.shortconv_kernel - 1)
    return _out(C, c, lp), tail.reshape(-1)


def step(u, lp, cfg, tail):
    """One token a slot. u (b, d) normed rows, tail (b, (K - 1) * d) ->
    (out (b, d), every slot's tail after the token; the caller keeps an
    idle slot's as it was)."""
    d = u.shape[-1]
    z, C = _project(u, lp)
    with jax.named_scope("shortconv.conv"):
        ext = jnp.concatenate([tail, z.astype(tail.dtype)], axis=-1)
        w = lp["conv"].astype(jnp.float32)                      # (d, K)
        c = sum(ext[:, j * d:(j + 1) * d].astype(jnp.float32) * w[:, j]
                for j in range(cfg.shortconv_kernel))
    return _out(C, c, lp), ext[:, d:]
