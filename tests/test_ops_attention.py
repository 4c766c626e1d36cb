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


# --- the sub-tiled tile body (PR 34) ----------------------------------------
# A tile is walked in sub-tiles of 256 (or 128, or the tile itself): those
# above the diagonal or past kv_len are not computed, interior ones carry
# no mask, the rest are masked. The widths come from the shapes alone.

_FA = sys.modules["ray_tpu.ops.pallas.flash_attention"]

# sq, sk, block_q, block_k, causal
_GEOMETRIES = {
    "diag_tile_of_several": (512, 512, 512, 512, True),
    "tile_of_one_sub_tile": (512, 512, 256, 256, True),
    "several_tiles_of_several": (1024, 1024, 512, 512, True),
    "non_causal_several": (512, 512, 512, 512, False),
    "offset_sq_lt_sk": (256, 768, 256, 256, True),
    "offset_unaligned_to_sub_tile": (384, 512, 384, 512, True),
    "ragged_lengths": (384, 640, 256, 256, True),
    "ragged_non_causal": (300, 300, 256, 256, False),
    "block_q_lt_block_k": (512, 512, 256, 512, True),
    "block_q_gt_block_k": (512, 512, 512, 256, True),
    "block_q_128_block_k_512": (512, 512, 128, 512, True),
    "fully_masked_rows_sq_gt_sk": (512, 256, 256, 256, True),
}


def _qkv(key, sq, sk, dtype, b=1, h=2, d=128):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (b, sq, h, d), dtype),
            jax.random.normal(k2, (b, sk, h, d), dtype),
            jax.random.normal(k3, (b, sk, h, d), dtype))


def _tol(dtype):
    return dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 \
        else dict(atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_flash_sub_tiled_forward(name, dtype):
    sq, sk, bq, bk, causal = _GEOMETRIES[name]
    q, k, v = _qkv(jax.random.PRNGKey(11), sq, sk, dtype)
    ref = A.mha_reference(q, k, v, causal=causal)
    out = A.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_flash_sub_tiled_grads(name, dtype):
    """dq, dk and dv: the dq kernel walks a q sub-tile's keys, the dk/dv
    kernel a k sub-tile's queries (keys-major scores, lse as a row)."""
    sq, sk, bq, bk, causal = _GEOMETRIES[name]
    q, k, v = _qkv(jax.random.PRNGKey(12), sq, sk, dtype, d=64)
    w = jax.random.normal(jax.random.PRNGKey(13), q.shape, jnp.float32)

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)

    g_ref = jax.grad(loss(lambda *a: A.mha_reference(*a, causal=causal)),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss(lambda *a: A.flash_attention(
        *a, causal=causal, block_q=bq, block_k=bk, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=5e-4, rtol=5e-4) if dtype == jnp.float32 \
        else dict(atol=0.15, rtol=0.1)
    for a, b_ in zip(g_ref, g_fl):
        assert not np.any(np.isnan(np.asarray(b_, np.float32)))
        np.testing.assert_allclose(np.asarray(b_, np.float32),
                                   np.asarray(a, np.float32), **tol)


@pytest.mark.parametrize("sq,sk,q_offset,bq,bk", [
    (256, 1024, 512, 256, 256),     # a chunk in the middle, aligned
    (256, 1024, 384, 256, 512),     # the diagonal enters a sub-tile midway
    (512, 1152, 100, 512, 512),     # nothing aligned, ragged keys
    (128, 640, 0, 128, 128),        # the first chunk: most keys skipped
])
def test_flash_q_offset_prefill_placement(sq, sk, q_offset, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(14), sq, sk, jnp.float32)
    ref = A.mha_reference(q, k, v, causal=True, q_offset=q_offset)
    out = A.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True, q_offset=q_offset)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_tile_plan_at_the_train_cells_shape():
    """4096 causal tokens in 1024 x 1024 tiles: 16 tiles of 16 sub-tiles;
    six tiles above the diagonal (96 sub-tiles) and six sub-tiles of each
    diagonal tile are skipped, the four sub-tiles on each diagonal tile's
    own diagonal are masked, the rest carry no mask."""
    plan = _FA.tile_plan(4096, 4096, 1024, 1024)
    assert (plan["sub_q"], plan["sub_k"]) == (256, 256)
    assert plan["skipped"] == 6 * 16 + 4 * 6
    assert plan["masked"] == 4 * 4
    assert plan["interior"] == 6 * 16 + 4 * 6
    assert plan["bodies"] == 2          # an interior tile, a diagonal one
    assert plan["required_share"] >= 0.90
    assert plan["required_share"] == pytest.approx(
        (4096 * 4097 // 2) / (136 * 256 * 256))


def test_tile_plan_whole_tile_design_reads_80_percent(monkeypatch):
    """The parent's design is the plan whose sub-tile is the tile."""
    monkeypatch.setattr(_FA, "_sub_tiles", lambda bq, bk: (bq, bk))
    plan = _FA.tile_plan(4096, 4096, 1024, 1024)
    assert (plan["skipped"], plan["interior"], plan["masked"]) == (6, 6, 4)
    assert plan["required_share"] == pytest.approx(0.8, abs=1e-3)


@pytest.mark.parametrize("sq,sk,bq,bk,causal", [
    (256, 256, 256, 256, True),     # one sub-tile, on the diagonal
    (200, 200, 256, 256, True),     # the tile is the ragged sequence
    (256, 256, 256, 128, True),     # two key tiles, the diagonal in both
])
def test_tile_plan_every_sub_tile_masked(sq, sk, bq, bk, causal):
    plan = _FA.tile_plan(sq, sk, bq, bk, causal=causal)
    assert plan["interior"] == 0 and plan["skipped"] == 0
    assert plan["masked"] == -(-sk // bk)


@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_tile_plan_counts_agree_between_the_two_walks(name):
    """The dk/dv kernel cuts a tile by k sub-tile over the queries, the
    other two by q sub-tile over the keys: the same sub-tiles must come
    out skipped, masked and interior."""
    sq, sk, bq, bk, causal = _GEOMETRIES[name]
    bq, bk = min(bq, sq), min(bk, sk)
    nq, nk = -(-sq // bq), -(-sk // bk)
    kw = _FA._geometry(bq, bk, sk, sk - sq, causal)
    by_q = _FA._plans(nq, nk, "q", **kw)
    by_k = _FA._plans(nq, nk, "k", **kw)
    assert by_q.keys() == by_k.keys()
    n_q, n_k = bq // kw["sub_q"], bk // kw["sub_k"]
    for key in by_q:
        kind_q = {(i, j): "interior" if j < n_int else
                  "masked" if j < n_run else "skipped"
                  for i, (n_int, n_run) in enumerate(by_q[key])
                  for j in range(n_k)}
        kind_k = {(i, j): "skipped" if i < first_run else
                  "masked" if i < first_int else "interior"
                  for j, (first_run, first_int) in enumerate(by_k[key])
                  for i in range(n_q)}
        assert kind_q == kind_k, key
