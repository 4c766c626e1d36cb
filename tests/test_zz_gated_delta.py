"""The chunked gated delta rule (``ray_tpu/ops/gated_delta.py``) against
the token-by-token recurrence it must agree with: float32 on the CPU,
outputs, final state and the gradients of all five inputs, over at least
four chunks. Head-major, as the rule takes them: (b, h, s, ...)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import gated_delta as gd

B, S, H, DK, DV = 2, 256, 3, 16, 24
INPUTS = ("q", "k", "v", "g", "beta")


@pytest.fixture(scope="module")
def inputs():
    """Unit keys, scaled unit queries, decays from 'none a chunk' to 'gone
    in a chunk' by head, write strengths in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, S, DK))
    k = jax.random.normal(ks[1], (B, H, S, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, H, S, DV))
    g = -jax.random.uniform(ks[3], (B, H, S)) \
        * jnp.array([0.002, 0.05, 0.5])[:, None]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, S)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta, state=None):
    """The rule one token at a time: (o (b, h, s, dv), final state)."""
    if state is None:
        state = jnp.zeros((q.shape[0], q.shape[1], q.shape[3], v.shape[3]))

    def token(state, x):
        o, state = gd.recurrent_gated_delta_step(*x, state)
        return state, o
    state, o = lax.scan(token, state, tuple(
        jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2), state


@pytest.mark.parametrize("chunk", [16, 64])
def test_outputs_and_final_state(inputs, chunk):
    assert S // chunk >= 4
    o, state = gd.chunk_gated_delta_rule(*inputs, chunk=chunk)
    want_o, want_state = recurrence(*inputs)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(state, want_state, atol=5e-6)
    # the state matters: the slow head's outlives the row
    assert float(jnp.abs(want_state[:, 0]).mean()) > 0.05


def _objective(rule):
    def f(*args):
        o, state = rule(*args)
        return jnp.sum(o * o) + jnp.sum(jnp.sin(state))
    return f


@pytest.fixture(scope="module")
def want_grads(inputs):
    return jax.grad(_objective(recurrence), argnums=range(5))(*inputs)


@pytest.fixture(scope="module")
def got_grads(inputs):
    """{chunk: the five gradients}, one compile a chunk size."""
    return {chunk: jax.grad(_objective(
        lambda *a, c=chunk: gd.chunk_gated_delta_rule(*a, chunk=c)),
        argnums=range(5))(*inputs) for chunk in (16, 64)}


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("which", INPUTS)
def test_gradients(want_grads, got_grads, chunk, which):
    i = INPUTS.index(which)
    scale = float(jnp.abs(want_grads[i]).max())
    np.testing.assert_allclose(got_grads[chunk][i], want_grads[i],
                               atol=2e-5 * scale)


@pytest.mark.parametrize("cut", [64, 192])
def test_the_recurrent_step_continues_a_chunked_prefix(inputs, cut):
    """Serving's two halves: a prefix by chunks (of the module's CHUNK),
    then token by token from the state it left, is the whole row's rule."""
    assert gd.CHUNK == 64
    head = [a[:, :, :cut] for a in inputs]
    tail = [a[:, :, cut:] for a in inputs]
    _, state = gd.chunk_gated_delta_rule(*head)
    o_tail, state = recurrence(*tail, state=state)
    want_o, want_state = gd.chunk_gated_delta_rule(*inputs)
    np.testing.assert_allclose(o_tail, want_o[:, :, cut:], atol=2e-6)
    np.testing.assert_allclose(state, want_state, atol=5e-6)


def test_a_row_that_is_no_multiple_of_the_chunk_is_refused(inputs):
    with pytest.raises(AssertionError):
        gd.chunk_gated_delta_rule(*[a[:, :, :100] for a in inputs], chunk=64)


@pytest.mark.parametrize("which", ["o", "state", "q", "k"])
def test_value_heads_that_share_a_key_head(inputs, which):
    """q and k with fewer heads than v: value head j reads key head
    j // r, as if they had been repeated, and a key head's gradient is the
    sum over its value heads."""
    q, k, v, g, beta = inputs
    q, k = q[:, :1], k[:, :1]

    def repeated(q, k):
        return gd.chunk_gated_delta_rule(
            jnp.repeat(q, H, axis=1), jnp.repeat(k, H, axis=1), v, g, beta)

    def shared(q, k):
        return gd.chunk_gated_delta_rule(q, k, v, g, beta)
    if which in ("o", "state"):
        i = ("o", "state").index(which)
        np.testing.assert_allclose(shared(q, k)[i], repeated(q, k)[i],
                                   atol=1e-6)
        return
    i = ("q", "k").index(which)
    got = jax.grad(_objective(shared), argnums=i)(q, k)
    want = jax.grad(_objective(repeated), argnums=i)(q, k)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))


def _corner(c):
    """Entries just inside and just outside the corners of the directly
    solved diagonal blocks (``gd.SOLVE_BLOCK`` rows, or the whole of a
    smaller system), and nothing else."""
    r = min(gd.SOLVE_BLOCK, c)
    a = jnp.zeros((c, c))
    for blk in range(0, c, r):
        a = a.at[blk + r - 1, blk].set(0.5)           # a block's own corner
        if blk:
            a = a.at[blk, blk - 1].set(-0.75)         # across the diagonal
            a = a.at[blk, blk - r].set(1.25)          # under the corner
            a = a.at[blk + r - 1, blk - 1].set(2.0)   # beside the corner
    return a


@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_chunk_inverse_and_its_backward(c):
    """(I + a)^-1 from directly solved diagonal blocks and the block
    recursion above them, for systems smaller than a block, of one block
    and of several: also where keys repeat (a of ones: the case a Neumann
    product's powers lose) and where the only entries sit at the blocks'
    corners; and the gradient its custom rule gives against autodiff
    through a dense inverse."""
    strict = jnp.tril(jnp.ones((c, c)), -1)
    a = jax.random.normal(jax.random.PRNGKey(1), (4, c, c)) * 0.3 * strict
    a = a.at[0].set(strict).at[3].set(_corner(c))
    eye = jnp.eye(c)
    got = gd.unit_lower_inverse(a)
    np.testing.assert_allclose(got, jnp.linalg.inv(eye + a), atol=1e-4)
    np.testing.assert_allclose(got[0], eye - jnp.eye(c, k=-1), atol=1e-6)
    # the corners against a float64 inverse: float32 LU is itself 1e-6 off
    np.testing.assert_allclose(
        got[3], np.linalg.inv(np.eye(c) + np.asarray(a[3], np.float64)),
        rtol=1e-6, atol=1e-6)
    w = jax.random.normal(jax.random.PRNGKey(2), (4, c, c))
    got_g = jax.grad(lambda a: jnp.sum(gd.unit_lower_inverse(a) * w))(a)
    want_g = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * w))(a)
    np.testing.assert_allclose(got_g[1:], want_g[1:], rtol=1e-3, atol=1e-3)
