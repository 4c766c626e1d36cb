"""Attention kernels: flash (interpret mode) and ring vs the XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import attention  # package attr may be the dispatcher fn
import sys
A = sys.modules["ray_tpu.ops.attention"]
from ray_tpu.ops.ring_attention import ring_attention


def _rand_qkv(key, b=2, s=256, h=4, kvh=None, d=64, dtype=jnp.float32):
    kvh = h if kvh is None else kvh
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, s, kvh, d), dtype)
    v = jax.random.normal(k3, (b, s, kvh, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    ref = A.mha_reference(q, k, v, causal=causal)
    out = A.flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), h=8, kvh=2)
    ref = A.mha_reference(q, k, v, causal=True)
    out = A.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_grad_matches_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, s=128, h=2, d=32)

    def loss_ref(q, k, v):
        return jnp.sum(A.mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            A.flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("sq,sk", [(1, 256), (64, 256), (256, 64)])
def test_flash_cross_lengths(sq, sk):
    """sq != sk aligns the causal diagonal with the END of kv (decode: a
    single query against a long KV cache attends everything)."""
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, sq, 4, 64))
    k = jax.random.normal(k2, (2, sk, 4, 64))
    v = jax.random.normal(k3, (2, sk, 4, 64))
    ref = A.mha_reference(q, k, v, causal=True)
    out = A.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sq,sk", [(64, 256), (256, 64)])
def test_flash_cross_lengths_grad(sq, sk):
    """The offset-dependent block bounds in _dkv/_dq kernels (first_q /
    last_k) must produce correct grads at sq != sk."""
    key = jax.random.PRNGKey(8)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (1, sq, 2, 32))
    k = jax.random.normal(k2, (1, sk, 2, 32))
    v = jax.random.normal(k3, (1, sk, 2, 32))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g_ref = jax.grad(loss(lambda *a: A.mha_reference(*a, causal=True)),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda *a: A.flash_attention(
        *a, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    import jax
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:4]).reshape(4), ("context",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=2, s=128, h=2, d=32)
    ref = A.mha_reference(q, k, v, causal=causal)

    spec = P(None, "context", None, None)
    f = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="context",
                                       causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grad():
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:4]).reshape(4), ("context",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b=1, s=64, h=2, d=16)
    spec = P(None, "context", None, None)

    def ring_loss(q, k, v):
        f = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="context"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return jnp.sum(f(q, k, v) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(A.mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("s", [192, 200])
def test_flash_partial_blocks(s):
    """Seq lengths not divisible by the block size must not produce NaN."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b=1, s=s, h=2, d=32)
    for causal in (True, False):
        ref = A.mha_reference(q, k, v, causal=causal)
        out = A.flash_attention(q, k, v, causal=causal, interpret=True)
        assert not np.any(np.isnan(np.asarray(out)))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_partial_blocks_grad():
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), b=1, s=200, h=2, d=32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g_ref = jax.grad(loss(lambda *a: A.mha_reference(*a, causal=True)),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda *a: A.flash_attention(
        *a, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_fl):
        assert not np.any(np.isnan(np.asarray(b_)))
        np.testing.assert_allclose(a, b_, atol=5e-4, rtol=5e-4)
