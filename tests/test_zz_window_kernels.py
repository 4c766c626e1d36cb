"""The kernels of a sliding-window layer and of a decode step's expert
layer, on the CPU through the Pallas interpreter: the paged-decode walk
with a window against ``paged_attention_reference`` with the same mask,
flash prefill with the band against ``mha_reference``, ``tile_plan``'s
counts for the band, and the decode-shape grouped matmul (no row in most
groups) against ``lax.ragged_dot``."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.pallas import flash_attention as fa
from ray_tpu.ops.pallas import grouped_matmul as gm
from ray_tpu.ops.pallas import paged_attention as pa

A = sys.modules["ray_tpu.ops.attention"]    # the package re-exports a fn
KEY = jax.random.PRNGKey(0)


def _paged(slots=6, kvh=2, g=4, hd=128, bs=16, width=32):
    nb = 1 + slots * width
    k = jax.random.normal(KEY, (nb, kvh, bs, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (nb, kvh, bs, hd),
                          jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (slots, kvh, g, hd),
                          jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(slots * width).reshape(slots, width),
                         jnp.int32)
    return q, k, v, tables


# below, at and above the window, at and beside block edges
LENGTHS = {"below": [1, 16, 17, 100, 127, 128],
           "edges": [129, 144, 145, 160, 255, 256],
           "above": [257, 272, 273, 300, 400, 511]}


@pytest.mark.parametrize("window", [128, 40, 16])
@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_paged_decode_with_a_window_is_the_masked_reference(case, window):
    q, k, v, tables = _paged()
    lengths = jnp.asarray(LENGTHS[case], jnp.int32)
    got = pa.paged_attention(q, k, v, tables, lengths, window=window,
                             interpret=True)
    want = pa.paged_attention_reference(q, k, v, tables, lengths,
                                        window=window)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_window_walk_never_reads_a_passed_block():
    """Blocks below the window's first hold NaN: a walk that touched
    them would say so. (The freed blocks of a sequence go back to the
    pool and are written by others.)"""
    q, k, v, tables = _paged()
    lengths = np.asarray(LENGTHS["above"], np.int32)
    first = pa.first_block(lengths, 16, 128)
    poison = np.zeros(k.shape[0], bool)
    for slot, f in enumerate(first):
        poison[np.asarray(tables[slot, :f])] = True
    nan = jnp.asarray(poison)[:, None, None, None]
    got = pa.paged_attention(q, jnp.where(nan, jnp.nan, k),
                             jnp.where(nan, jnp.nan, v), tables,
                             jnp.asarray(lengths), window=128,
                             interpret=True)
    want = pa.paged_attention_reference(q, k, v, tables,
                                        jnp.asarray(lengths), window=128)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_fetched_positions_counts_the_window_walk():
    lengths = np.array([1, 128, 129, 144, 145, 300, 4096])
    full = pa.fetched_positions(lengths, 16)
    win = pa.fetched_positions(lengths, 16, 128)
    assert list(full) == [16, 128, 144, 144, 160, 304, 4096]
    # from the block that holds position length - 128 on
    assert list(win) == [16, 128, 144, 128, 144, 144, 128]
    assert (win <= 128 + 16).all()


BAND = {  # sq, sk, heads, kv heads, head dim, window, block q, block k, offset
    "one_subtile_a_tile": (512, 512, 4, 2, 64, 128, 128, 128, None),
    "subtiles_256": (1024, 1024, 2, 1, 128, 128, 512, 512, None),
    "ragged": (300, 300, 2, 2, 64, 128, 128, 128, None),
    "narrow_window": (512, 512, 2, 2, 64, 40, 128, 128, None),
    "wide_window": (512, 512, 2, 2, 64, 300, 128, 128, None),
    "chunk_at_512": (256, 768, 2, 2, 64, 128, 128, 128, 512),
    "chunk_at_256": (256, 768, 2, 2, 64, 128, 128, 128, 256),
    "one_tile": (1024, 1024, 2, 2, 64, 128, 1024, 1024, None),
}


@pytest.mark.parametrize("case", sorted(BAND))
def test_flash_prefill_with_the_band_is_the_masked_reference(case):
    sq, sk, h, kvh, d, window, bq, bk, off = BAND[case]
    q = jax.random.normal(KEY, (1, sq, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, sk, kvh, d),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, sk, kvh, d),
                          jnp.float32)
    got = A.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=True, q_offset=off, window=window)
    want = A.mha_reference(q, k, v, causal=True, q_offset=off,
                           window=window)
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_the_band_is_refused_where_it_is_not_built():
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(NotImplementedError):        # no backward
        jax.grad(lambda q: A.flash_attention(
            q, q, q, interpret=True, window=64).sum())(q)
    with pytest.raises(ValueError):                 # a band is causal
        fa.flash_attention_fwd(q[0], q[0], q[0], sm_scale=1.0, causal=False,
                               window=64)


def test_tile_plan_counts_the_band():
    """A 2,048-token prompt under a window of 128 at the serving tiles
    (128 x 128): of 256 tiles 31 are computed (16 on the diagonal, 15
    cut by the band's lower edge), all masked, none interior; without
    the window 136."""
    band = fa.tile_plan(2048, 2048, 128, 128, window=128)
    assert (band["skipped"], band["interior"], band["masked"]) == (225, 0,
                                                                   31)
    full = fa.tile_plan(2048, 2048, 128, 128)
    assert (full["skipped"], full["interior"], full["masked"]) == (120, 120,
                                                                   16)
    # required pairs: 128 a row once the window is full
    required = 128 * 129 // 2 + (2048 - 128) * 128
    assert band["required_share"] == pytest.approx(
        required / (31 * 128 * 128))
    # sub-tiles inside a wide tile: 1024 x 1024 at 256 x 256
    wide = fa.tile_plan(1024, 1024, 1024, 1024, window=128)
    assert wide["skipped"] + wide["interior"] + wide["masked"] == 16
    assert wide["masked"] == 7 and wide["interior"] == 0


DECODE_GROUPS = {
    # 32 slots x 8 assignments = 256 rows, ~32 of them on 16 held experts
    "decode_step": (256, [0, 3, 0, 0, 6, 1, 0, 0, 2, 0, 0, 0, 4, 0, 0, 16]),
    "nothing_local": (256, [0] * 16),
    "everything_local": (64, [64, 0]),
    "prefill_bucket": (1024, [100, 0, 0, 300, 5, 0, 0, 0]),
}


# the contraction in one block, and (at a step's rows) in two
GMM_CASES = [(c, 256) for c in sorted(DECODE_GROUPS)] + [
    (c, 4096) for c in sorted(DECODE_GROUPS) if DECODE_GROUPS[c][0] <= 256]


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case, k", GMM_CASES)
def test_decode_shape_grouped_matmul_is_ragged_dot(case, k, layer):
    m, sizes = DECODE_GROUPS[case]
    n = 384 if k == 256 else 128
    lhs = jax.random.normal(KEY, (m, k), jnp.float32)
    stack = jax.random.normal(jax.random.fold_in(KEY, 1),
                              (3, len(sizes), k, n), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.gmm_stacked(lhs, stack, sizes, jnp.int32(layer), True)
    want = jax.lax.ragged_dot(lhs, stack[layer], sizes)
    np.testing.assert_allclose(got, want, atol=2e-4 * (k // 256) ** 0.5)
    # rows of no group are zeros, not what the buffer held
    assert not np.asarray(got)[int(sizes.sum()):].any()
