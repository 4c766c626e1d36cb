"""A Mamba-2 state-space mixer for the serving forwards, in plain
``jax.numpy`` / ``lax``. The one kernel is the decode step's rule on the chip,
``ops/pallas/ssm_step.py``, which a caller plugs into ``mixer_step``;
``ssd_step`` here is that kernel's reference and what runs without it.

Per head (P channels, a state ``h`` of P x N float32) and token ``t``, with
an input ``x_t`` (P), a step ``dt_t > 0``, a decay rate ``A < 0`` a head,
and ``B_t``, ``C_t`` (N) of the head's GROUP (head h reads group
``h // (heads / groups)``):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t + D x_t

``ssd_step`` is that, one token for every row of a batch (every row's state
in and out: a decode step of a few live slots among many pays for all of
them, which is what the kernel is for). ``ssd_chunk_scan``
computes the same outputs for one row of ``s`` tokens, ``chunk`` tokens at a
time (the state-space dual form): with ``a`` the running sum of ``dt A``
from a chunk's start, within a chunk ``y_i += sum_{j <= i} (C_i . B_j)
exp(a_i - a_j) dt_j x_j`` (products of chunk x chunk matrices), a chunk's
own contribution to the state ``sum_j exp(a_last - a_j) dt_j x_j B_j^T``,
and between chunks a ``lax.scan`` that carries the state: ``h <- exp(a_last)
h + that``, ``y_i += exp(a_i) C_i h``. Every exponent is <= 0. It takes a
state and returns one, and it stops at the row's true ``length``: the steps
of positions at or past it are set to 0, so they neither decay the state nor
add to it (their outputs are garbage nobody reads).

The mixer around the rule (``mixer_prefill``, ``mixer_step``), for normed
rows u: ``[z | xBC | dt] = u W_in`` (its columns three leaves, ``w_z``,
``w_xbc``, ``w_dt``); ``xBC = silu(conv(xBC) + b)``, a causal
depthwise conv of ``K`` taps along the tokens (``conv_taps``, which
``models/moe.py _conv_silu`` runs too), split into x
(heads, P), B and C (groups, N); ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; the rule; gate THEN norm: ``y * silu(z)``, RMSNorm over each
group's channels, times a weight; ``y W_out``. What a request keeps between
its tokens is the state and the conv's TAIL, the last ``K - 1`` rows of
``xBC`` before the conv. Scopes: ``ssm.proj``, ``ssm.conv``, ``ssm.scan``
(prefill), ``ssm.step`` (decode), ``ssm.gate_norm``, ``ssm.out``.

The steps, the decays and the carried state are float32, and the chunked
form's products take float32 operands at ``highest`` precision (a few
GFLOP a thousand tokens a layer): at the chip's default a product rounds its
operands to bfloat16, and the state a prefill hands to the decode steps
would differ in the third digit from the one the steps themselves carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ssd_step(x, dt, A, B, C, D, state):
    """One token a row. x (b, h, p), dt (b, h) > 0, A (h,) < 0, B and C
    (b, g, n), D (h,), state (b, h, p, n) float32 -> (y (b, h, p) float32,
    the state after the token)."""
    f32 = jnp.float32
    r = x.shape[1] // B.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    Bh = jnp.repeat(B.astype(f32), r, axis=1)               # (b, h, n)
    Ch = jnp.repeat(C.astype(f32), r, axis=1)
    decay = jnp.exp(dt * A)[..., None, None]
    state = state * decay + (dt[..., None] * x)[..., None] * Bh[:, :, None]
    y = jnp.sum(state * Ch[:, :, None], axis=-1) + D[:, None] * x
    return y, state


def ssd_chunk_scan(x, dt, A, B, C, D, state0, length, chunk: int = 128):
    """One row of s tokens. x (s, h, p), dt (s, h) > 0, A (h,) < 0, B and C
    (s, g, n), D (h,), state0 (h, p, n) float32, ``length`` () the row's
    true length -> (y (s, h, p) float32, the state after position
    ``length - 1``). Positions at or past ``length`` leave the state as it
    was."""
    f32 = jnp.float32
    s, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    pad = -s % chunk
    c = (s + pad) // chunk
    dt = jnp.where(jnp.arange(s)[:, None] < length, dt.astype(f32), 0.0)

    def chunks(v):
        v = jnp.pad(v.astype(f32), ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape(c, chunk, *v.shape[1:])
    x_, dt_, B_, C_ = chunks(x), chunks(dt), chunks(B), chunks(C)
    xd = (x_ * dt_[..., None]).reshape(c, chunk, g, r, p)
    a = jnp.cumsum(dt_ * A, axis=1).reshape(c, chunk, g, r)     # <= 0
    # within a chunk: (C_i . B_j) exp(a_i - a_j) for j <= i
    hi = lax.Precision.HIGHEST
    cb = jnp.einsum("clgn,csgn->cgls", C_, B_, precision=hi)
    i = jnp.arange(chunk)
    gap = a[:, :, None] - a[:, None]                            # (c, l, s, g, r)
    low = jnp.where((i[:, None] >= i[None])[None, :, :, None, None],
                    jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
    y = jnp.einsum("clsgr,csgrp->clgrp",
                   cb.transpose(0, 2, 3, 1)[..., None] * low, xd,
                   precision=hi)
    # a chunk's own contribution to the state, and its whole decay
    last = a[:, -1]                                             # (c, g, r)
    own = jnp.einsum("csgrp,csgn->cgrpn",
                     jnp.exp(last[:, None] - a)[..., None] * xd, B_,
                     precision=hi)

    def carry(st, xs):
        own_c, last_c = xs
        return st * jnp.exp(last_c)[..., None, None] + own_c, st
    state, starts = lax.scan(carry, state0.reshape(g, r, p, n).astype(f32),
                             (own, last))
    y = y + jnp.exp(a)[..., None] * jnp.einsum(
        "clgn,cgrpn->clgrp", C_, starts, precision=hi)
    y = y.reshape(c * chunk, h, p)[:s] + D[:, None] * x.astype(f32)
    return y, state.reshape(h, p, n)


def conv_taps(ext, w, s: int):
    """The causal depthwise conv along the tokens, ``sum_j w[..., j] x[t -
    (K - 1) + j]``: ext (..., s + K - 1, ch), ``s`` rows behind their K - 1
    predecessors (zeros, or a tail); w (..., ch, K), its leading axes ext's
    before the tokens -> (..., s, ch) float32. The linear mixer's conv too
    (``models/moe.py _conv_silu``)."""
    w = w.astype(jnp.float32)
    return sum(ext[..., j:j + s, :].astype(jnp.float32) * w[..., None, :, j]
               for j in range(w.shape[-1]))


def conv_silu(ext, w, bias, s: int):
    """silu(``conv_taps`` + bias): w (ch, K), bias (ch,) -> (..., s, ch)
    float32."""
    pre = conv_taps(ext, w, s) + bias.astype(jnp.float32)
    return pre * jax.nn.sigmoid(pre)


def _project(u, lp):
    """The in-projection of normed rows u (.., d): z (.., inner), xBC (..,
    inner + 2 g n) and the step softplus(dt + dt_bias) (.., h) float32,
    each the product with its own columns of W_in (three leaves)."""
    dt = jax.nn.softplus((u @ lp["w_dt"]).astype(jnp.float32)
                         + lp["dt_bias"])
    return u @ lp["w_z"], u @ lp["w_xbc"], dt


def _xbc(act, cfg):
    """silu(conv(xBC)) (.., ch) -> x (.., h, p), B and C (.., g, n)."""
    h, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    lead = act.shape[:-1]
    x, B, C = jnp.split(act, [h * p, h * p + g * n], axis=-1)
    return (x.reshape(*lead, h, p), B.reshape(*lead, g, n),
            C.reshape(*lead, g, n))


def _gate_norm_out(y, z, lp, cfg, dtype):
    """y (.., h, p) float32, z (.., inner) -> (.., d): y * silu(z), RMSNorm
    over each group's channels, times the norm's weight, through W_out."""
    with jax.named_scope("ssm.gate_norm"):
        lead, g = z.shape[:-1], cfg.ssm_groups
        y = y.reshape(*lead, -1) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(*lead, g, -1)
        y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
        y = y.reshape(*lead, -1).astype(dtype) * lp["ssm_norm"]
    with jax.named_scope("ssm.out"):
        return y @ lp["w_out"]


def mixer_prefill(u, lp, cfg, state0, tail0, length):
    """One row's tokens through a state layer. u (s, d) normed rows, state0
    (h, p, n) float32 and tail0 (K - 1, ch): what the row starts from (zeros
    for a prompt's first chunk) -> (out (s, d), the state and the tail after
    position ``length - 1``: the tail is rows ``length - (K - 1) ... length
    - 1`` of xBC before the conv, the predecessors included where the row
    is shorter)."""
    s = u.shape[0]
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _project(u, lp)
    with jax.named_scope("ssm.conv"):
        ext = jnp.concatenate([tail0.astype(xbc.dtype), xbc])
        x, B, C = _xbc(conv_silu(ext, lp["conv"], lp["conv_bias"], s), cfg)
        tail = lax.dynamic_slice_in_dim(ext, length, tail0.shape[0])
    with jax.named_scope("ssm.scan"):
        y, state = ssd_chunk_scan(x, dt, -jnp.exp(lp["A_log"]), B, C,
                                  lp["D"], state0, length, cfg.ssm_chunk)
    return _gate_norm_out(y, z, lp, cfg, u.dtype), state, tail


def mixer_step(u, lp, cfg, tail, rule):
    """One token a slot through a state layer. u (b, d) normed rows, tail
    (b, K - 1, ch); ``rule(x, dt, A, B, C, D) -> y (b, h, p) float32`` is
    the one-token rule against the slots' states, which its caller holds
    (llm/kvcache.py _pool_state_step: ``ssd_step`` on the layer's states, or
    the kernel that moves the live slots' in place) -> (out (b, d), the
    tail after the token)."""
    with jax.named_scope("ssm.proj"):
        z, xbc, dt = _project(u, lp)
    with jax.named_scope("ssm.conv"):
        ext = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
        x, B, C = _xbc(conv_silu(ext, lp["conv"], lp["conv_bias"], 1)[:, 0],
                       cfg)
    with jax.named_scope("ssm.step"):
        y = rule(x, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"])
    return _gate_norm_out(y, z, lp, cfg, u.dtype), ext[:, 1:]
