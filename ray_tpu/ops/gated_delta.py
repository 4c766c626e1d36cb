"""The gated delta rule of a linear-attention layer (Gated DeltaNet), in
plain ``jax.numpy`` / ``lax``: no kernel, differentiated by autodiff.

Per head, with a state ``S`` (dk x dv) and, for each token ``t``, a query
and a key (dk), a value (dv), a log-decay ``g_t <= 0`` and a write
strength ``beta_t``:

    S   = exp(g_t) S
    d   = beta_t (v_t - S^T k_t)
    S   = S + k_t d^T
    o_t = S^T q_t

``recurrent_gated_delta_step`` is that, one token. ``chunk_gated_delta_rule``
computes the same outputs a chunk of ``c`` tokens at a time. Within a chunk
(``gdn.chunk``), with ``G`` the running sum of ``g`` from the chunk's start
and ``D_ij = exp(G_i - G_j)`` for ``i >= j``: the corrected values
``U = T (beta V)`` and ``W = T (beta exp(G) K)`` with ``T = (I + A)^-1``,
``A = strict_lower(beta_i k_i.k_j D_ij)``: one unit-lower-triangular system
a chunk, inverted once by block recursion (twelve products of c x c matrices at
c = 64) and applied by matmuls. Between chunks (``gdn.scan``) a ``lax.scan``
carries the state: ``V' = U - W S``, ``o = (exp(G) Q) S + lower(Q K^T D) V'``,
``S <- exp(G_c) S + (exp(G_c - G) K)^T V'``. Every exponent is <= 0.

Layouts. ``chunk_gated_delta_rule`` takes and returns HEAD-MAJOR arrays,
(b, heads, s, width), the layout ``models/moe.py``'s mixer works in: its
entry splits ``s`` into chunks and moves the chunk axis to the front,
(n, b, h, c, width), a move of a major axis in the operands' dtype, and its
exit moves it back after the one rounding of ``o``. q and k may come with
fewer heads than v (a key head serves ``h // hk`` value heads): they are
repeated as the chunked layout is written. ``row_major`` holds an array in
memory in the order of its dimensions, on either side of both moves.

Gates, decays and the carried state are float32; the matmuls take their
operands in the dtype of ``q`` (bf16 in a bf16 model: the state is rounded
for a product, never where it is carried) and accumulate in float32. The
inverse is made in float32 at ``SOLVE_PRECISION`` (three bf16 passes a
product on a TPU, about 2^-16: it is rounded to the operands' dtype next;
at six passes the twelve products were a sixth of the linear layers' time).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

# tokens a chunk: the matrices within one are CHUNK x CHUNK
CHUNK = 64
# of the products that build a chunk's inverse (float32 operands)
SOLVE_PRECISION = lax.Precision.HIGH


def row_major(x):
    """x, held in memory in the order of its dimensions. Left to itself
    the compiler lays a head-major product out as its matmul likes it
    (tokens minor) and carries float32 converts across the move into the
    chunked layout: a constraint on either side of a move keeps the move
    in x's dtype (with either pair of the four left out a float32 copy
    comes back). Its cotangent is held the same way."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _mm(a, b, spec):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _solve(a, b):
    return jnp.matmul(a, b, precision=SOLVE_PRECISION)


def _inverse_recursion(a):
    c = a.shape[-1]
    rows = jnp.arange(c)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    m = 1
    while m < c:
        # the blocks under the diagonal that join two m-blocks into one of
        # 2m: [[P, 0], [L, Q]]^-1 = [[P^-1, 0], [-Q^-1 L P^-1, Q^-1]]
        same = (rows[:, None] // (2 * m)) == (rows[None, :] // (2 * m))
        off = same & ((rows[:, None] // m) != (rows[None, :] // m))
        inv = inv - _solve(_solve(inv, jnp.where(off, a, 0.0)), inv)
        m *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., c, c) strictly lower triangular, c a
    power of two: the block recursion of forward substitution (every
    intermediate is the inverse of a diagonal block, so it is as stable
    as substitution; a Neumann product's powers are not), as 2 log2(c)
    batched products. Its backward needs the inverse alone."""
    return _inverse_recursion(a)


def _inverse_fwd(a):
    inv = _inverse_recursion(a)
    return inv, inv


def _inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    return (-_solve(_solve(t, g), t),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """HEAD-MAJOR in and out: q, k (b, hk, s, dk), v (b, h, s, dv); g, beta
    (b, h, s) float32 -> (o (b, h, s, dv) in v's dtype, the final state
    (b, h, dk, dv) float32). ``hk`` divides ``h``: value head j reads key
    head j // (h // hk), repeated as the chunked layout is written and
    nowhere before. ``s`` is a multiple of ``chunk``, ``chunk`` a power of
    two. q and k come normalised and scaled as the layer wants them."""
    b, h, s, dv = v.shape
    dk = q.shape[-1]
    c = chunk
    assert s % c == 0 and c & (c - 1) == 0, (s, c)
    assert h % q.shape[1] == 0 and q.shape == k.shape, (q.shape, k.shape, h)
    n = s // c
    cd, f32 = q.dtype, jnp.float32

    def chunks(x):      # (b, h or hk, s, ...) -> (n, b, h, c, ...)
        x = row_major(x).reshape(*x.shape[:2], n, c, *x.shape[3:])
        x = row_major(jnp.moveaxis(x, 2, 0))
        return jnp.repeat(x, h // x.shape[2], axis=2)

    with jax.named_scope("gdn.chunk"):
        q, k, v = chunks(q), chunks(k), chunks(v)
        g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
        gc = jnp.cumsum(g, axis=-1)                          # (n, b, h, c)
        lower = jnp.tril(jnp.ones((c, c), bool))
        diff = gc[..., :, None] - gc[..., None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kb = (k.astype(f32) * beta[..., None]).astype(cd)
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        a = jnp.where(strict, _mm(kb, k, "...id,...jd->...ij") * decay, 0.0)
        t = unit_lower_inverse(a).astype(cd)
        u = _mm(t, (v.astype(f32) * beta[..., None]).astype(cd),
                "...ij,...jd->...id")
        w = _mm(t, (kb.astype(f32) * jnp.exp(gc)[..., None]).astype(cd),
                "...ij,...jd->...id").astype(cd)
        qk = (_mm(q, k, "...id,...jd->...ij") * decay).astype(cd)
        qg = (q.astype(f32) * jnp.exp(gc)[..., None]).astype(cd)
        g_end = gc[..., -1]                                  # (n, b, h)
        k_end = (k.astype(f32)
                 * jnp.exp(g_end[..., None] - gc)[..., None]).astype(cd)

    def step(state, x):
        w, u, qg, k_end, g_end = x
        sc = state.astype(cd)
        v_new = u - _mm(w, sc, "...ck,...kv->...cv")
        inter = _mm(qg, sc, "...ck,...kv->...cv")
        state = state * jnp.exp(g_end)[..., None, None] \
            + _mm(k_end, v_new.astype(cd), "...ck,...cv->...kv")
        return state, (v_new.astype(cd), inter)

    with jax.named_scope("gdn.scan"):
        state, (v_new, inter) = lax.scan(step, jnp.zeros((b, h, dk, dv), f32),
                                         (w, u, qg, k_end, g_end))
    with jax.named_scope("gdn.chunk"):
        o = inter + _mm(qk, v_new, "...ij,...jd->...id")     # (n, b, h, c, dv)
        o = jnp.moveaxis(row_major(o.astype(v.dtype)), 0, 2)
    return row_major(o.reshape(b, h, s, dv)), state


def recurrent_gated_delta_step(q, k, v, g, beta, state):
    """One token of the rule in float32: q, k (b, h, dk), v (b, h, dv), g,
    beta (b, h), state (b, h, dk, dv) -> (o (b, h, dv), the new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    hi = lax.Precision.HIGHEST
    state = state.astype(f32) * jnp.exp(g.astype(f32))[..., None, None]
    seen = jnp.einsum("bhk,bhkv->bhv", k, state, precision=hi)
    d = beta.astype(f32)[..., None] * (v - seen)
    state = state + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, state, precision=hi), state
