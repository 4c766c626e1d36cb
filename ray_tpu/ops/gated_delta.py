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
a chunk, inverted once (``gdn.solve``: its diagonal blocks of ``SOLVE_BLOCK``
rows by forward substitution, the levels above them by block recursion, four
products of c x c matrices at c = 64) and applied by matmuls. Between chunks
(``gdn.scan``) a ``lax.scan`` carries the state: ``V' = U - W S``,
``o = (exp(G) Q) S + lower(Q K^T D) V'``,
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
inverse is made in float32: the diagonal blocks elementwise (exact float32
products), the recursion's products at ``SOLVE_PRECISION`` (three bf16
passes a product on a TPU, about 2^-16: it is rounded to the operands' dtype
next). A product over (64, 4, 32, 64, 64) moves 0.8 GB (the 64-wide minor
dimension lies in 128-lane tiles: 268 MB an array) and takes 1.2-1.8 ms on a
v5e at the rate of its HBM, so the levels are counted, not their FLOPs: at
twelve products a pass (every level from single rows up) the inverse was
97 ms of a 543-ms step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

# tokens a chunk: the matrices within one are CHUNK x CHUNK
CHUNK = 64
# of the products that build a chunk's inverse (float32 operands)
SOLVE_PRECISION = lax.Precision.HIGH
# rows of the diagonal blocks of a chunk's system that are solved directly;
# the block recursion joins them from there
SOLVE_BLOCK = 16


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


def _substituted(e):
    """Strictly lower ``r x r`` blocks as (r rows, r columns, batch) ->
    the blocks of ``(I + e)^-1`` in the same form, by forward substitution
    a row a turn: ``T[i] = I[i] - sum_k e[i, k] T[k]`` over whole rows
    (``e[i, k]`` is zero from ``k = i`` on, where T's rows are still the
    identity's). Elementwise float32 on vectors of the batch: exact
    products, none on the MXU; a loop of ``r - 1`` turns, so the traced
    program does not grow with ``r``."""
    def row(i, t):
        e_i, t_i = (lax.dynamic_index_in_dim(x, i, 0, False) for x in (e, t))
        return lax.dynamic_update_index_in_dim(
            t, t_i - jnp.sum(e_i[:, None] * t, axis=0), i, 0)
    r = e.shape[0]
    eye = jnp.broadcast_to(jnp.eye(r, dtype=e.dtype)[:, :, None], e.shape)
    return lax.fori_loop(1, r, row, eye)


def _solved_blocks(a, r):
    """The block diagonal of ``(I + a)^-1`` in blocks of ``r`` rows, (...,
    c, c) like ``a``: the inverse of a diagonal block is the diagonal block
    of the inverse. The blocks leave ``a`` as one (r, c) slab a system (a
    masked sum over its row blocks: the blocks side by side), move to a
    layout whose minor dimension is the batch (there an entry of all the
    blocks is a dense vector, and the arrays are an ``r / c``-th of
    ``a``), are solved by substitution and come back the same way. The
    result is the slab under itself ``c / r`` times, masked: written so,
    the compiler reads the slab inside the first product that takes the
    result and stores no array of ``a``'s size (as a broadcast of the slab
    it stores one, 0.41 ms a call on a v5e at (64, 4, 32, 64, 64))."""
    c = a.shape[-1]
    nb, n = c // r, math.prod(a.shape[:-2])
    cols = jnp.arange(c)
    diagonal = (cols[:, None] // r) == (cols[None, :] // r)
    # masked after the split of the rows: masked before it, the compiler
    # stores the masked copy of ``a``
    slab = jnp.sum(jnp.where(diagonal.reshape(nb, r, c),
                             a.reshape(n, nb, r, c), 0.0), axis=1)
    e = slab.reshape(n, r, nb, r).transpose(1, 3, 2, 0)    # p, q, blk, n
    t = _substituted(e.reshape(r, r, nb * n)).reshape(r, r, nb, n)
    slab = row_major(t.transpose(3, 0, 2, 1).reshape(n, r, c))
    return jnp.where(diagonal, jnp.concatenate([slab] * nb, axis=1),
                     0.0).reshape(a.shape)


def _inverse_recursion(a):
    c = a.shape[-1]
    m = min(SOLVE_BLOCK, c)
    rows = jnp.arange(c)
    inv = _solved_blocks(a, m)
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
    power of two: forward substitution within diagonal blocks of
    ``SOLVE_BLOCK`` rows, then its block recursion (every intermediate is
    the inverse of a diagonal block, so it is as stable as substitution; a
    Neumann product's powers are not), as 2 log2(c / SOLVE_BLOCK) batched
    products. Its backward needs the inverse alone."""
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
        with jax.named_scope("gdn.solve"):
            t = unit_lower_inverse(a)
        t = t.astype(cd)
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
