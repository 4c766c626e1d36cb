"""A model of LATENT attention layers (multi-head latent attention: a
cache row [c | kr] a token a layer, no head axis) through the serving
forwards, the paged pool's third kind and the engine, against the plain
reference benchmarks/families/mistral4.py at tiny widths in float32. The
rotary's original length is 16 and its factor 4, so YaRN's ramp and the
query scale a(t) are crossed inside every prompt."""
import asyncio
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm import model as lm
from ray_tpu.models import llama, moe
from ray_tpu.ops.pallas import paged_attention as pa

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "serve-mistral4-longdoc-open"
CONFIG = "mistral-small-4-119b-serve-ep8"


@pytest.fixture(scope="module")
def spec():
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def fam(spec):
    """benchmarks/families/mistral4.py: the plain reference."""
    return spec.family("mistral4")


def _cfg(**kw):
    base = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
                head_size=32, ffn_dim=32, n_experts=16, experts_per_token=4,
                experts_held=4, first_expert=4, q_lora_rank=24,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=16,
                v_head_dim=32, rope_factor=4.0, rope_original_len=16,
                max_seq_len=512, dtype="float32", attn_impl="reference",
                gmm_impl="ragged_dot")
    base.update(kw)
    return moe.mistral_small_4_119b(**base)


@pytest.fixture(scope="module")
def params():
    return moe.init_params(jax.random.PRNGKey(0), _cfg())


def _prompt(n, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, vocab, n)]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --- the forwards against the reference --------------------------------------

def test_cold_prefill_is_the_reference(fam, params):
    cfg, toks = _cfg(), _prompt(40)
    want = fam.forward(params, jnp.asarray([toks]), cfg)[0]
    for n in (17, 40):      # past the original 16 positions both times
        got, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks[:n], 64)),
                             jnp.int32(n), cfg, 64)
        assert _rel(got, want[n - 1]) < 1e-5
    # what it emits for the cache: the latent rows, no head axis
    assert kv["k"].shape == (3, 64, 16) and kv["v"].shape == (3, 64, 16)


@pytest.mark.parametrize("kv_impl, interpret", [("gather", False),
                                                ("paged_flash", True)])
def test_chunked_prefill_and_decode_are_the_reference(fam, params, kv_impl,
                                                      interpret):
    """``served``: a 40-token prompt in chunks of 16 (two chunk edges, the
    last chunk once a prefix), its rows through a latent pool, 16 decode
    steps in the absorbed form across two block edges."""
    cfg, toks = _cfg(), _prompt(40, seed=1)
    got = fam.served(params, cfg, toks, buckets=(8, 16), block=8,
                     kv_impl=kv_impl, interpret=interpret,
                     cache_dtype="float32")
    assert len(got["prefills"]) == 8 and len(got["steps"]) == 16
    out = fam.compared(got, params, cfg, 40)
    assert out["finite"]
    assert max(out["prefill_rel_errs"]) < 2e-5, out["prefill_rel_errs"]
    assert max(out["decode_rel_errs"]) < 2e-5, out["decode_rel_errs"]
    # the rows the pool holds are the reference's, in every layer, the
    # prompt's (prefill, scatter) and the reply's (the decode's writer)
    assert got["rows"].shape == (3, 56, 32)
    assert np.max(out["rows_rel_errs"]) < 2e-6, out["rows_rel_errs"]
    assert out["prefill_rel_err"] == max(
        out["prefill_logits_rel_err"],
        fam.ROWS_WEIGHT * out["prefill_rows_rel_err"])


def test_the_flash_chunk_expands_the_prefix_it_attends(fam, params):
    """The flash path of a chunk (static offset): rows 0 ... offset + s
    of the accumulator are expanded, and the logits are the dynamic
    path's."""
    cfg = _cfg()
    flash = _cfg(attn_impl="flash_interpret")
    toks = _prompt(256, seed=2)

    def run(c):
        acc = {"k": jnp.zeros((3, 384, 16)), "v": jnp.zeros((3, 384, 16))}
        for off in (0, 128):
            logits, acc = lm.prefill_chunk(
                params, jnp.asarray(toks[off:off + 128], jnp.int32),
                jnp.int32(128), jnp.int32(off), acc, c)
        return logits, acc
    (want, acc_w), (got, acc_g) = run(cfg), run(flash)
    assert _rel(got, want) < 1e-5
    np.testing.assert_allclose(acc_g["k"], acc_w["k"], atol=1e-5)
    assert lm.chunk_attended_rows(flash, 128, 128, 384) == 256
    assert lm.chunk_attended_rows(cfg, 128, 128, 384) == 384
    ref = fam.forward(params, jnp.asarray([toks]), cfg)[0, -1]
    assert _rel(got, ref) < 1e-4


def test_absorbed_is_materialised(params):
    """One decode step in the absorbed form against the pool is the
    prefill's materialised attention over the same rows, to 1e-5."""
    cfg, toks = _cfg(), _prompt(41, seed=3)
    _, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks[:40], 64)),
                       jnp.int32(40), cfg, 64)
    want, _ = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks, 64)),
                         jnp.int32(41), cfg, 64)
    pool = kc.init_pool(cfg, 12, 8, jnp.float32)
    table = jnp.arange(1, 9, dtype=jnp.int32)
    pool = kc.scatter_bucket(pool, kv, table, 8, kc.pool_kinds(cfg))
    for impl, interpret in (("gather", False), ("paged_flash", True)):
        got = kc.paged_decode_logits(
            params, pool, table[None], jnp.asarray([40], jnp.int32),
            jnp.asarray([toks[40]], jnp.int32), cfg, impl=impl,
            interpret=interpret)[0]
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_verify_round_is_sequential_decode(params):
    cfg, toks = _cfg(), _prompt(44, seed=4)
    _, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks[:40], 64)),
                       jnp.int32(40), cfg, 64)
    table = jnp.arange(1, 9, dtype=jnp.int32)

    def pool():
        return kc.scatter_bucket(kc.init_pool(cfg, 12, 8, jnp.float32), kv,
                                 table, 8, kc.pool_kinds(cfg))
    at = jnp.asarray([40], jnp.int32)
    got, _ = kc.paged_verify_steps(
        params, pool(), table[None], at, jnp.asarray([toks[40:44]]), cfg,
        impl="paged_flash", interpret=True)
    p = pool()
    for j in range(4):
        want = kc.paged_decode_logits(
            params, p, table[None], at + j, jnp.asarray([toks[40 + j]]),
            cfg)[0]
        np.testing.assert_allclose(got[0, j], want, atol=1e-5)
        _, p = kc.paged_decode_steps(
            params, p, table[None], at + j, jnp.asarray([toks[40 + j]]),
            jnp.zeros((1,)), jax.random.PRNGKey(0), cfg, 1)


def test_the_train_forward_refuses_latent_layers(params):
    with pytest.raises(NotImplementedError, match="latent"):
        moe.forward(params, jnp.zeros((1, 8), jnp.int32), _cfg())


def test_latent_layers_do_not_mix_with_kv_layers():
    with pytest.raises(ValueError, match="latent"):
        lm.layer_kinds(_cfg(layer_types=("latent", "global", "latent")))
    assert lm.layer_kinds(_cfg()) == ("latent",) * 3
    assert kc.pool_kinds(_cfg()) == (("latent", (0, 1, 2)),)


# --- the rotary ---------------------------------------------------------------

def test_yarn_blends_the_frequencies_and_the_query_scale_starts_late(fam):
    cfg = _cfg()
    inv = llama.yarn_inv_freq(16, 10000.0, 4.0, 16, 32.0, 1.0)
    np.testing.assert_allclose(inv, fam.yarn_inverse_frequencies(cfg),
                               rtol=1e-6)
    plain = 10000.0 ** (-np.arange(8) / 8)
    # the fastest frequency is kept, the slowest divided by the factor
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / 4.0)
    # the published sizes: kept up to the correction dim of 32 turns (12),
    # divided by 128 from that of 1 turn (25), blended between
    big = llama.yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0)
    theta = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(big[:13], theta[:13], rtol=1e-6)
    np.testing.assert_allclose(big[25:], theta[25:] / 128.0, rtol=1e-6)
    assert all(t / 128 * 1.001 < f < t * 0.999
               for f, t in zip(big[13:25], theta[13:25]))
    pos = jnp.asarray([[0, 15, 16, 47, 48]])
    cos, sin, a = lm.rope_tables(cfg, pos)
    assert cos.shape == (1, 5, 8)
    np.testing.assert_allclose(
        a[0], 1 + 0.1 * np.log1p([0, 0, 1, 2, 3]), rtol=1e-6)
    assert lm.softmax_scale(cfg, "latent") == pytest.approx(
        32 ** -0.5 * (0.1 * np.log(4.0) + 1) ** 2)
    assert lm.softmax_scale(moe.mistral_small_4_119b(), "latent") \
        == pytest.approx(128 ** -0.5 * 1.4852 ** 2, rel=1e-4)
    assert lm.softmax_scale(llama.tiny(), "global") \
        == llama.tiny().head_dim ** -0.5


def test_the_pairs_are_interleaved():
    x = jnp.arange(8.0).reshape(1, 1, 8)
    ang = jnp.asarray([[[0.1, 0.2, 0.3, 0.4]]])
    out = llama._rope_pairs(x, jnp.cos(ang), jnp.sin(ang))[0, 0]
    for i in range(4):
        c, s = np.cos(ang[0, 0, i]), np.sin(ang[0, 0, i])
        assert out[2 * i] == pytest.approx(2 * i * c - (2 * i + 1) * s)
        assert out[2 * i + 1] == pytest.approx((2 * i + 1) * c + 2 * i * s)


@pytest.mark.parametrize("fault", ["rows_float8", "rows_int8", "kr_left_out",
                                   "query_scale_off", "yarn_off",
                                   "mscale_off"])
def test_each_fault_moves_the_reference(fam, params, fault):
    """What tools/latent_parity_sensitivity.py switches: each is far
    from float32 rounding at 40 tokens (past the original 16 positions)."""
    assert set(fam.FAULTS) >= {fault}
    cfg, toks = _cfg(), jnp.asarray([_prompt(40, seed=5)])
    sound = fam.forward(params, toks, cfg)[0, 16:]
    moved = fam.forward(params, toks, cfg, faults=(fault,))[0, 16:]
    assert _rel(moved, sound) > 2e-3, fault


@pytest.mark.parametrize("fault, least", [("pool_int8", 3e-3),
                                          ("pool_float8", 1.5e-2),
                                          ("rows_int8", 3e-3),
                                          ("rows_float8", 1.5e-2)])
def test_the_rows_comparison_sees_the_caches_precision(fam, params, fault,
                                                       least):
    """A cache kept in a lower precision, in the program's place
    (``served(pool_fault=...)``) or in the reference's: the first layer's
    rows read it, the prompt's and the reply's, whatever the logits do
    (float32 elsewhere: the sound rows read 2e-7)."""
    cfg, toks = _cfg(), _prompt(40, seed=1)
    kw = dict(buckets=(8, 16), block=8, kv_impl="gather", interpret=False,
              cache_dtype="float32")
    if fault in fam.POOL_FAULTS:
        out = fam.compared(fam.served(params, cfg, toks, pool_fault=fault,
                                      **kw), params, cfg, 40)
        # the decode steps attended that cache; the prefill's logits come
        # from the accumulator and did not
        assert out["decode_logits_rel_err"] > 1e-3
        assert out["prefill_logits_rel_err"] < 2e-5
    else:
        out = fam.compared(fam.served(params, cfg, toks, **kw), params, cfg,
                           40, (fault,))
    assert out["finite"]
    assert out["prefill_rows_rel_err"] > least
    assert out["decode_rows_rel_err"] > least
    assert out["prefill_rel_err"] >= fam.ROWS_WEIGHT * least


# --- the walk and the writer --------------------------------------------------

def _latent_pools(nb, bs, lat, rope, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((nb, bs, lat)), dtype),
            jnp.asarray(rng.standard_normal((nb, bs, rope)), dtype))


@pytest.mark.parametrize("bs", [8, 16, 128])
def test_the_latent_walk_is_its_plain_twin(bs):
    """Ragged lengths (an idle slot, a block edge, one past it, a slot of
    several chunks), table tails on the trash block, which is poisoned."""
    h, lat, rope = 4, 128, 128
    nb = 605 // bs + 12 if bs < 128 else 16
    c, r = _latent_pools(nb, bs, lat, rope, seed=bs)
    c, r = c.at[0].set(jnp.nan), r.at[0].set(jnp.nan)      # trash
    lengths = np.asarray([1, bs, bs + 1, 5 * bs + 3, 9 * bs], np.int32)
    if bs < 128:
        lengths = np.append(lengths, [600 // bs * bs + 5]).astype(np.int32)
    rng = np.random.default_rng(1)
    width = int(-(-lengths.max() // bs)) + 2
    tables = np.zeros((len(lengths), width), np.int32)
    ids = rng.permutation(np.arange(1, nb))
    for i, n in enumerate(lengths):
        live = -(-int(n) // bs)
        tables[i, :live] = ids[:live]
        ids = np.roll(ids, -3)
    q = jnp.asarray(rng.standard_normal((len(lengths), h, lat + rope)),
                    jnp.float32)
    args = (q, c, r, jnp.asarray(tables), jnp.asarray(lengths))
    got = pa.latent_decode(*args, sm_scale=0.11, interpret=True)
    want = pa.latent_attention_reference(
        q, jnp.nan_to_num(c), jnp.nan_to_num(r), *args[3:], sm_scale=0.11)
    assert got.shape == (len(lengths), h, lat)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bs", [8, 128])
def test_the_latent_walk_skips_the_slots_that_hold_no_request(bs):
    """Slots of length 0 over an all-trash row (PR 57) between live ones,
    the trash block poisoned: the live rows are, bit for bit, what the
    batch gave when idle slots were walked at length 1 + step (the
    parent's operands), and an idle row is zeros."""
    h, lat, rope = 4, 128, 128
    live_lens, idle, live = [5 * bs + 3, 9 * bs], [0, 2, 4], [1, 3]
    c, r = _latent_pools(24, bs, lat, rope, seed=bs)
    c, r = c.at[0].set(jnp.nan), r.at[0].set(jnp.nan)      # trash
    tables = np.zeros((5, 12), np.int32)
    tables[1, :6] = 1 + np.arange(6)
    tables[3, :9] = 7 + np.arange(9)
    q = jnp.asarray(np.random.default_rng(2).standard_normal(
        (5, h, lat + rope)), jnp.float32)
    walk = jax.jit(functools.partial(pa.latent_decode, sm_scale=0.11,
                                     interpret=True))

    def run(idle_len):
        lengths = np.full((5,), idle_len, np.int32)
        lengths[live] = live_lens
        return np.asarray(walk(q, c, r, jnp.asarray(tables),
                               jnp.asarray(lengths)))
    before, got = run(3), run(0)
    assert np.isnan(before[idle]).all() and np.isfinite(got).all()
    assert np.array_equal(got[live], before[live])
    assert not got[idle].any()


def test_the_walks_chunk_follows_the_rows_bytes():
    """One rule for both walks: what a position costs in the walked pool
    and the positions a chunk aims at."""
    # K and V of 8 heads x 128, bf16: 4,096 bytes a position, 128 a chunk
    assert pa.chunk_blocks(2 * 8 * 128 * 2, 16) == 8
    # a latent row of 256 + 128 values, bf16, blocks of 128: 512 a chunk
    row = (256 + 128) * 2
    assert pa.chunk_blocks(row, 128, pa.LATENT_CHUNK_POSITIONS) == 4
    assert pa.chunk_blocks(row, 16, pa.LATENT_CHUNK_POSITIONS) == 32
    assert pa.chunk_blocks(row, 1024, pa.LATENT_CHUNK_POSITIONS) == 1
    assert 2 * 4 * 128 * row <= pa.BUFFER_BYTES


def test_the_latent_writer_puts_rows_in_place():
    c, r = _latent_pools(6, 8, 128, 128, seed=3)
    blocks = jnp.asarray([2, 5, 2], jnp.int32)
    rows = jnp.asarray([0, 7, 3], jnp.int32)
    c_new, r_new = _latent_pools(3, 1, 128, 128, seed=4)
    c2, r2 = pa.latent_write(c, r, blocks, rows, c_new[:, 0], r_new[:, 0],
                             interpret=True)
    want_c = c.at[blocks, rows].set(c_new[:, 0])
    want_r = r.at[blocks, rows].set(r_new[:, 0])
    np.testing.assert_array_equal(c2, want_c)
    np.testing.assert_array_equal(r2, want_r)
    with pytest.raises(ValueError):
        pa.latent_write(c, r, blocks, rows, c_new[:, 0, :64], r_new[:, 0])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_latent_writer_skips_the_entries_that_are_none(dtype):
    """A negative block is no entry (PR 57: a slot that holds no request):
    both pools are the scatter of the other entries alone, bit for bit,
    the trash block, which idle slots used to write, included."""
    dtype = jnp.dtype(dtype)
    c, r = _latent_pools(6, 8, 128, 128, seed=5, dtype=dtype)
    blocks = jnp.asarray([2, -1, 5, -1, 2], jnp.int32)
    rows = jnp.asarray([0, 1, 7, 2, 3], jnp.int32)
    c_new, r_new = _latent_pools(5, 1, 128, 128, seed=6, dtype=dtype)
    c2, r2 = pa.latent_write(c, r, blocks, rows, c_new[:, 0], r_new[:, 0],
                             interpret=True)
    c1, r1 = pa.latent_write(c, r, jnp.maximum(blocks, 0), rows,
                             c_new[:, 0], r_new[:, 0], interpret=True)
    keep = np.asarray(blocks) >= 0
    for got, old, pool, new in ((c2, c1, c, c_new), (r2, r1, r, r_new)):
        want = pool.at[blocks[keep], rows[keep]].set(new[keep, 0])
        got, old, want = (np.asarray(x.astype(jnp.float32))
                          for x in (got, old, want))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got[1:], old[1:])
        assert (old[0] != got[0]).any()


# --- the pool's third kind ----------------------------------------------------

def test_a_latent_pool_has_no_head_axis():
    cfg = _cfg()
    pool = kc.init_pool(cfg, 9, 8, jnp.bfloat16)
    # a row's two parts, each a whole number of 128-lane tiles wide
    assert kc.row_shapes(cfg, kc.LATENT) == ((128,), (128,))
    assert {k: a.shape for k, a in pool.items()} == {
        "c": (3, 9, 8, 128), "kr": (3, 9, 8, 128)}
    assert kc.kind_block_bytes(pool) == {"latent": 3 * 8 * (128 + 128) * 2}
    assert kc.row_bytes(cfg, kc.LATENT, jnp.bfloat16) == 256 * 2
    # the published widths: c 256 | kr 64 in 128: 768 bytes a token a layer
    full = moe.mistral_small_4_119b()
    assert kc.row_shapes(full, kc.LATENT) == ((256,), (128,))
    assert kc.row_bytes(full, kc.LATENT, jnp.bfloat16) == 768
    # K/V kinds as they were
    dense = llama.tiny()
    assert kc.row_shapes(dense, kc.GLOBAL) == (
        (dense.n_kv_heads, dense.head_dim),) * 2


def test_rows_go_through_the_pool_and_back():
    cfg = _cfg()
    rng = np.random.default_rng(0)
    kv = {"k": jnp.asarray(rng.standard_normal((3, 32, 16)), jnp.float32),
          "v": jnp.asarray(rng.standard_normal((3, 32, 16)), jnp.float32)}
    pool = kc.init_pool(cfg, 8, 8, jnp.float32)
    ids = jnp.asarray([5, 2, 7, 0], jnp.int32)      # the last: trash
    pool = kc.scatter_bucket(pool, kv, ids, 4)
    np.testing.assert_array_equal(pool["c"][:, 5, :, :16], kv["k"][:, :8])
    np.testing.assert_array_equal(pool["kr"][:, 7, :, :16], kv["v"][:, 16:24])
    assert not np.asarray(pool["c"][:, :, :, 16:]).any()    # the padding
    acc = kc.gather_table(pool, {kc.LATENT: ids[:3]}, 40, kc.pool_kinds(cfg))
    assert acc["k"].shape == (3, 40, 128)
    np.testing.assert_array_equal(acc["k"][:, :24, :16], kv["k"][:, :24])
    np.testing.assert_array_equal(acc["v"][:, :24, :16], kv["v"][:, :24])
    assert not np.asarray(acc["k"][:, 24:]).any()
    pool = kc.copy_block(pool, 5, 3)
    np.testing.assert_array_equal(pool["c"][:, 3], pool["c"][:, 5])
    np.testing.assert_array_equal(pool["kr"][:, 3], pool["kr"][:, 5])


def test_the_manager_shares_parks_and_frees_latent_blocks():
    mgr = kc.KVBlockManager(13, 8, table_width=8, prefix_cache=True,
                            kind=kc.LATENT)
    toks = _prompt(40)

    def adds_up():
        used, free = mgr.used_by_kind(), mgr.free_by_kind()
        assert list(used) == list(free) == [kc.LATENT]
        assert used[kc.LATENT] + free[kc.LATENT] + mgr.cached_blocks() == 12
        assert mgr.freed_by_kind() == {}
        return used[kc.LATENT]
    a = mgr.alloc_seq("a", toks, 8)
    assert list(a["tables"]) == [kc.LATENT] and a["hit_tokens"] == 0
    assert a["table"] is a["tables"][kc.LATENT] and adds_up() == 6
    mgr.free_seq("a", toks)                 # its five full blocks cached
    assert adds_up() == 0 and mgr.cached_blocks() == 5
    # the same prompt twice: both share its first four blocks (a hit is
    # capped one token short of the prompt)
    b = mgr.alloc_seq("b", toks, 8)
    c = mgr.alloc_seq("c", toks, 8)
    assert b["hit_tokens"] == c["hit_tokens"] == 32 and adds_up() == 8
    assert list(b["table"][:4]) == list(c["table"][:4]) \
        == list(a["table"][:4])
    # another prompt that cannot fit is parked, and admitted once the
    # two are freed (cached blocks are evicted for room)
    other = _prompt(40, seed=9)
    assert mgr.alloc_seq("d", other, 8) is None and adds_up() == 8
    mgr.free_seq("b")
    mgr.free_seq("c")
    assert adds_up() == 0 and mgr.cached_blocks() > 0
    d = mgr.alloc_seq("d", other, 24)
    assert d is not None and adds_up() == 8
    mgr.free_seq("d")
    assert adds_up() == 0


# --- the engine ----------------------------------------------------------------

def _engine(params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw = {"prefix_cache": True, **kw}
    return LLMEngine(_cfg(), params, max_slots=2, max_len=128,
                     prefill_buckets=(16, 32), cache_dtype="float32",
                     kv_block_size=8, steps_per_sync=4, **kw)


def _sums(*keys):
    """{key: (sum, count)} of engine histograms, from their exposition."""
    from ray_tpu.llm.engine import engine_metrics

    def total(h, tail):
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in h.render().splitlines()
                   if ln.startswith(h.name + tail))
    return {k: (total(h, "_sum"), total(h, "_count"))
            for k, h in ((k, engine_metrics()[k]) for k in keys)}


def test_the_engine_serves_it_cold_chunked_and_from_a_prefix(fam, params):
    """A 70-token prompt (three chunks of 32) cold, then again (a prefix
    hit), then a short one (one bucket): greedy tokens are the
    reference's, the hit's are the cold run's, and the counters say what
    was expanded."""
    cfg, toks = _cfg(), _prompt(70, seed=6)
    want, seq = [], list(toks)
    for _ in range(8):
        nxt = int(np.argmax(fam.forward(params, jnp.asarray([seq]), cfg)[0, -1]))
        want.append(nxt)
        seq.append(nxt)
    keys = ("prefill_chunks", "latent_rows_expanded", "kv_blocks_latent")

    async def run():
        eng = _engine(params)
        before = _sums(*keys)
        cold = await eng.generate(toks, max_new_tokens=8)
        mid = _sums(*keys)
        hit = await eng.generate(toks, max_new_tokens=8)
        short = await eng.generate(_prompt(20, seed=8), max_new_tokens=4)
        for _ in range(200):
            if eng._inflight is None:
                break
            await asyncio.sleep(0.005)
        stats = eng.stats
        await eng.stop()
        return before, mid, _sums(*keys), cold, hit, short, stats
    before, mid, after, cold, hit, short, stats = asyncio.run(run())
    assert cold["tokens"] == want and cold["prefix_hit_tokens"] == 0
    assert hit["tokens"] == want and hit["prefix_hit_tokens"] >= 64
    assert len(short["tokens"]) == 4
    # cold: chunks at 0, 32, 64 of an accumulator of 128 + 32 rows, the
    # reference path expanding every row each time
    assert mid["prefill_chunks"][0] - before["prefill_chunks"][0] == 3
    assert mid["latent_rows_expanded"][0] \
        - before["latent_rows_expanded"][0] == 3 * 160
    # the hit: one chunk; the short one: one bucket of 32 rows
    assert after["prefill_chunks"][0] - mid["prefill_chunks"][0] == 2
    assert after["latent_rows_expanded"][0] \
        - mid["latent_rows_expanded"][0] == 160 + 32
    assert after["kv_blocks_latent"][1] > before["kv_blocks_latent"][1]
    # one name a kind: the pool a sequence holds whole has the bare one
    assert "pool_blocks_latent" not in stats and stats["pool_blocks"] > 0
    assert stats["blocks_used"] == 0
    assert stats["prefix_hit_tokens"] >= 64


def test_the_kernel_path_serves_the_same_tokens(params):
    toks = _prompt(40, seed=7)

    async def run(**kw):
        eng = _engine(params, **kw)
        out = await eng.generate(toks, max_new_tokens=6)
        await eng.stop()
        return out["tokens"]
    assert asyncio.run(run(kv_impl="paged_flash")) \
        == asyncio.run(run(kv_impl="gather"))


# --- the cell -------------------------------------------------------------------

def test_the_configuration_is_the_published_one_but_for_the_cut(spec):
    cell = spec.cell(CELL)
    m = cell["model"]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cell["config"] == CONFIG and entry["reduced"] == reduced
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        row = next(r for r in map(json.loads, open(path))
                   if r["name"] == "Mistral-Small-4-119B-2603")
        assert sorted(k for k, v in row["config"].items()
                      if m.get(k) != v) == sorted(reduced)
        assert m["source"] == entry["source"] == row["source_url"]
        assert [m["source_" + k] for k in reduced] == [
            row["config"][k] for k in reduced]
    # the cut and the floors: 9 of 36 layers, 16 of 128 experts, an
    # eighth of the vocabulary; no width differs
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (9, 16, 16384)
    assert m["num_hidden_layers"] >= 4 and m["n_routed_experts"] >= 8 \
        and m["vocab_size"] * 8 >= m["source_vocab_size"]
    assert (m["hidden_size"], m["num_attention_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["moe_intermediate_size"],
            m["num_experts_per_tok"]) == (4096, 32, 1024, 256, 64, 64, 128,
                                          2048, 4)
    rp = m["rope_parameters"]
    assert (rp["factor"], rp["original_max_position_embeddings"]) \
        == (128, 8192)
    assert set(m["assumed"]) >= {"a_scoring", "b_mscale", "c_query_scale",
                                 "d_vision", "e_torch_dtype", "weights",
                                 "row_padding"}
    assert "8 x 4" in m["stands_for"] and "8 chips" in m["stands_for"]
    dep = m["deployment"]
    assert (dep["kind"], dep["family"], dep["max_slots"], dep["max_len"],
            dep["parity_prompt_len"]) == ("serve", "mistral4", 32, 26624,
                                          10240)
    cfg = spec.family("mistral4").config(m)
    assert isinstance(cfg, moe.MoEConfig)
    assert lm.layer_kinds(cfg) == ("latent",) * 9
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_token,
            cfg.scoring, cfg.n_shared_experts) == (128, 16, 4, "softmax", 1)
    assert kc.row_bytes(cfg, kc.LATENT, dep["cache_dtype"]) == 768
    # 4.24 B parameters on the chip, as the issue counts them
    assert cfg.num_params() == pytest.approx(4.24e9, rel=5e-3)


def _reads(spec, cell) -> set:
    """What a cell's per-layer entries READ: (reader, arguments) of each
    entry's file, whatever the entry is called."""
    import json
    out = set()
    for m in cell["per_layer"]:
        mf = spec.metric_file(m["name"])
        out.add((mf["reader"], json.dumps(mf.get("args", {}),
                                          sort_keys=True)))
    return out


def _read_by(spec, names) -> set:
    """``_reads`` of the metric files ``names``."""
    return _reads(spec, {"per_layer": [{"name": n} for n in names]})


def test_the_cell_and_its_metrics_are_in_the_benchmark(spec):
    bench = spec.benchmark()
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) \
        == (CONFIG, "longdoc-open", 1)
    cell = spec.cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"tpot_p50_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    # held by reader and arguments, not by name: the cell READS what these
    # files read, under whatever name a later PR merges a copy into (and a
    # later PR may add)
    assert _reads(spec, cell) >= _read_by(spec, {
        "longdoc_ttft_p90_ms",
        "caller_late_p99_ms.longdoc", "engine_queue_mean_ms.longdoc",
        "decode_batch_mean.longdoc", "decode_steps_per_block.longdoc",
        "prefill_ms_per_ktok.longdoc", "hbm_peak.longdoc",
        "kv_bytes_per_live_token.longdoc", "moe_experts_hit_share.longdoc",
        "moe_local_share.longdoc", "moe_gmm_dev_ms_per_step.longdoc",
        "moe_gmm_roofline.longdoc", "flash_prefill_roofline.longdoc",
        "served_overhead_mean_ms.longdoc", "stream_lag_mean_ms.longdoc",
        "latent_decode_roofline.longdoc",
        "latent_decode_dev_ms_per_step.longdoc",
        "latent_write_dev_ms_per_step.longdoc",
        "decode_dev_ms_per_step.longdoc",
        "latent_expand_rows_per_prompt_token.longdoc",
        # the scheduler's and the replica's parts, where this cell's
        # stalls lie (the accepted readers under new names)
        "engine_prepare_ms_per_block.longdoc",
        "engine_account_ms_per_block.longdoc",
        "engine_emit_ms_per_block.longdoc",
        "engine_yield_ms_per_block.longdoc",
        "engine_hop_ms_per_block.longdoc", "engine_gap_ms_per_block.longdoc",
        "engine_admit_ms_per_block.longdoc",
        "engine_host_ms_per_block.longdoc",
        "stream_consume_us_per_token.longdoc", "proxy_ingress_p50_ms.longdoc",
        "decode_dev_ms_per_step_counted.longdoc", "kv_fetch_per_live.longdoc"})
    # none that needs a reply FINISHED inside the traced seconds: the
    # schedule does not promise one (PERF.md section 7)
    assert not names & {"longdoc_tpot_p95_ms", "engine_tpot_p50_ms.longdoc",
                        "proxy_tpot_p50_ms.longdoc", "proxy_token_us.longdoc"}
    for m in cell["per_layer"]:
        assert m["moves"] == "tpot_p50_ms"
        mf = spec.metric_file(m["name"])
        assert callable(spec.reader(mf["reader"]))
        for key in ("unit", "better", "source", "layer"):
            assert mf[key] == m[key], (m["name"], key)
    t = cell["traffic_params"]
    prompts = sorted({p for p, _ in t["pairs"]})
    outs = sorted({o for _, o in t["pairs"]})
    assert prompts == [8192, 9216, 10240, 11264, 12288, 14336, 16384, 18432,
                       20480, 21504, 22528, 24576]
    assert outs == [512, 640, 768, 896, 1024, 1152, 1280, 1536]
    assert len({tuple(p) for p in t["pairs"]}) == 96 and t["round"] == 12
    for i in range(0, 96, 12):      # every round carries every prompt length
        assert sorted(p for p, _ in t["pairs"][i:i + 12]) == prompts
    assert (t["kind"], t["order_seed"], t["steady_s"], t["trace_s"]) \
        == ("open", 0, 30.0, 8.0)
    assert t["arrival_gaps"] == round(t["rate_per_s"] * 50)
    assert 0.35 * t["knee_per_s"] <= t["rate_per_s"] \
        <= 0.5 * t["knee_per_s"] * 1.001
    dep = cell["model"]["deployment"]
    chunk = max(dep["prefill_buckets"])
    assert min(prompts) > chunk        # every prefill is chunked
    assert max(p + o for p, o in t["pairs"]) <= dep["max_len"]
    # the warm-up reaches every chunk offset and every bucket behind one
    import math
    met = set()
    for p, _ in t["warm_shapes"]:
        for off in range(0, p, chunk):
            met.add((off, lm.bucket_for(dep["prefill_buckets"],
                                        min(chunk, p - off))))
    need = set()
    for p in prompts + [dep["parity_prompt_len"]]:
        for off in range(0, p, chunk):
            need.add((off, lm.bucket_for(dep["prefill_buckets"],
                                         min(chunk, p - off))))
    assert need <= met, sorted(need - met)
    assert math.ceil(dep["max_len"] / chunk) == 7


def test_what_the_family_counts(fam, spec):
    m = spec.cell(CELL)["model"]
    # one slot-step over 16,384 positions: 320 values a row a layer, bf16
    assert fam.latent_decode_required_bytes(m, [16384]) == 9 * (
        16384 * 640 + 32 * (320 * 2 + 256 * 4))
    assert fam.latent_decode_required_flops(m, [16384]) \
        == 2 * 32 * (320 + 256) * 16384 * 9
    assert fam.flash_prefill_required_flops(m, [8192]) \
        == 4 * 128 * 32 * (8192 * 8193 // 2) * 9
    assert fam.flash_prefill_required_bytes(m, [8192]) \
        == 2 * 128 * 4 * 32 * 8192 * 9
    assert fam.sparse_layers(m) == 9
    assert fam.gmm_decode_required_bytes(m, 9 * 16, 32) \
        == 2 * (9 * 16 * 3 * 4096 * 2048 + 32 * (3 * 4096 + 3 * 2048))
