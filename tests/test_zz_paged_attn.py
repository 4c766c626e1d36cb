"""Paged-attention decode kernel (ops/pallas/paged_attention.py) and
its serving integration: kernel-vs-gather parity (allclose on random
values, BITWISE on integer constructions), every edge of the walk over
a slot's live blocks (chunks of C pool blocks, idle slots, a poisoned
pool, the exported fetch rule), COW-forked tables diverging mid-decode,
tensor-parallel paged engines, chunk-grid-aligned prefix hits, and the
platform's choice of the engine's decode attention.

All kernel tests run interpret=True — tier-1 (JAX_PLATFORMS=cpu)
exercises the real table walk / masking / online-softmax logic through
the Pallas interpreter, not a shadow path.

(Late-alphabet name keeps the tier-1 870 s cutoff stable.)
"""

import asyncio
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import llama
from ray_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, ffn_dim=128, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(seed, n):
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, 127, n)]


def _ref_greedy(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, jnp.array([toks], jnp.int32),
                               cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _tp_mesh(size):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:size]), ("tensor",))


def _rand_case(seed, *, b, w, bs, kvh, g, hd, nb, dtype=jnp.float32):
    """Random q/pool + disjoint per-slot block tables."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(nb, kvh, bs, hd))
                    .astype(np.float32))
    v = jnp.asarray(rng.normal(size=(nb, kvh, bs, hd))
                    .astype(np.float32))
    tables = jnp.asarray(
        (1 + np.arange(b * w)).reshape(b, w).astype(np.int32))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), tables


# one compile a shape: the walk's trip count is a run-time value, so
# every length below reuses the program of its shape
_walk = jax.jit(functools.partial(pa.paged_attention, interpret=True))


# --- kernel unit (interpret mode) -------------------------------------


def test_kernel_matches_gather_reference_uneven_lengths():
    """Random values, uneven table lengths including a single-position
    slot and a max-len slot: the fused kernel agrees with the
    gather-then-softmax reference to f32 rounding."""
    b, w, bs, kvh, g, hd = 3, 4, 8, 2, 2, 16
    q, k, v, tables = _rand_case(0, b=b, w=w, bs=bs, kvh=kvh, g=g,
                                 hd=hd, nb=1 + b * w)
    lengths = jnp.asarray([1, 7, w * bs], jnp.int32)
    got = pa.paged_attention(q, k, v, tables, lengths, interpret=True)
    want = pa.paged_attention_reference(q, k, v, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_kernel_under_jit_matches_eager():
    """The kernel composes with jax.jit (the shape it runs in inside
    paged_decode_steps' scan) without changing its output."""
    b, w, bs, kvh, g, hd = 2, 4, 8, 2, 2, 16
    q, k, v, tables = _rand_case(1, b=b, w=w, bs=bs, kvh=kvh, g=g,
                                 hd=hd, nb=1 + b * w)
    lengths = jnp.asarray([5, 20], jnp.int32)
    fn = jax.jit(functools.partial(pa.paged_attention, interpret=True))
    eager = pa.paged_attention(q, k, v, tables, lengths,
                               interpret=True)
    jitted = fn(q, k, v, tables, lengths)
    assert np.array_equal(np.asarray(eager), np.asarray(jitted))


def test_kernel_bitwise_on_integer_pow2_construction():
    """BITWISE kernel-vs-gather parity on a construction where both
    summation orders are exact: constant K makes every score equal
    (softmax weights are exactly 1/count), integer-valued V makes the
    weighted sums exact, and POWER-OF-TWO valid lengths make 1/count
    exactly representable. (The gather path divides by the softmax sum
    BEFORE accumulating, the online-softmax kernel divides AFTER — the
    two orders only agree bitwise when 1/count is exact, which is why
    the lengths here are 1/4/16/32, not arbitrary.)"""
    b, w, bs, kvh, g, hd = 4, 4, 8, 2, 2, 16
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd))
                    .astype(np.float32))
    nb = 1 + b * w
    k = jnp.ones((nb, kvh, bs, hd), jnp.float32)
    v = jnp.asarray(rng.integers(-8, 8, size=(nb, kvh, bs, hd))
                    .astype(np.float32))
    tables = jnp.asarray(
        (1 + np.arange(b * w)).reshape(b, w).astype(np.int32))
    lengths = jnp.asarray([1, 4, 16, 32], jnp.int32)   # powers of two
    got = np.asarray(
        pa.paged_attention(q, k, v, tables, lengths, interpret=True))
    want = np.asarray(
        pa.paged_attention_reference(q, k, v, tables, lengths))
    assert np.array_equal(got, want)


def test_kernel_cow_forked_tables_diverge_mid_decode():
    """Two slots share every physical block (a fork); the fork then
    COWs its last block and writes a divergent KV entry. The parent's
    attention output must be bitwise-unchanged, the fork's must follow
    its private block — the kernel reads through the TABLES, not
    through any per-slot copy."""
    b, w, bs, kvh, g, hd = 2, 4, 8, 2, 2, 16
    nb = 8
    rng = np.random.default_rng(3)
    # identical query on both slots: while the tables are fully shared
    # the two rows must come out bitwise-identical
    q = jnp.asarray(np.broadcast_to(
        rng.normal(size=(1, kvh, g, hd)).astype(np.float32),
        (b, kvh, g, hd)).copy())
    k = rng.normal(size=(nb, kvh, bs, hd)).astype(np.float32)
    v = rng.normal(size=(nb, kvh, bs, hd)).astype(np.float32)
    shared = np.asarray([[1, 2, 3, kc.TRASH]] * 2, np.int32)
    length = 20                                 # pos 19 in block 3
    lengths = jnp.asarray([length, length], jnp.int32)
    before = np.asarray(pa.paged_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(shared),
        lengths, interpret=True))
    assert np.array_equal(before[0], before[1])

    # COW: clone phys 3 -> 4, repoint the fork, diverge position 19
    k[4], v[4] = k[3], v[3]
    k[4, :, 19 % bs] += 1.0
    v[4, :, 19 % bs] -= 1.0
    forked = shared.copy()
    forked[1, 2] = 4
    after = np.asarray(pa.paged_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(forked),
        lengths, interpret=True))
    assert np.array_equal(after[0], before[0])          # parent intact
    assert not np.array_equal(after[1], before[1])      # fork diverged
    want = np.asarray(pa.paged_attention_reference(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(forked),
        lengths))
    np.testing.assert_allclose(after, want, rtol=2e-6, atol=2e-6)


# --- the walk: a slot's live blocks, C pool blocks a fetch -------------

# block 16 x 64-wide heads: chunk_blocks gives C = 8, 128 positions a
# chunk; a table of 20 blocks is two whole chunks and a half one
_BS, _W, _HD = 16, 20, 64


def _chunk_blocks(kv_heads, block_size, head_dim, itemsize):
    """C of the K/V walk: ``pa.chunk_blocks`` takes what a position costs
    in the walked pool (K and V of every KV head)."""
    return pa.chunk_blocks(2 * kv_heads * head_dim * itemsize, block_size)


_C = _chunk_blocks(2, _BS, _HD, 4)
_EDGES = {"one": 1, "block": _BS, "block_plus_1": _BS + 1,
          "chunk": _C * _BS, "chunk_plus_1": _C * _BS + 1,
          "two_chunks": 2 * _C * _BS, "full_table": _W * _BS}


def test_chunk_rule_reads_the_pool_shape_only():
    """C from the pool's shape and dtype: about 128 positions a chunk,
    the four buffers (K and V, two each) within 4 MB, never under one
    block."""
    assert _C == 8
    assert _chunk_blocks(8, 16, 128, 2) == 8       # the chat cell: 1 MB
    assert _chunk_blocks(32, 16, 128, 2) == 8      # MHA 7B: 4 MB
    assert _chunk_blocks(32, 16, 128, 4) == 4      # f32 cache: capped
    assert _chunk_blocks(8, 8, 128, 2) == 16
    assert _chunk_blocks(8, 32, 128, 2) == 4
    assert _chunk_blocks(8, 256, 128, 2) == 1
    assert _chunk_blocks(64, 128, 256, 4) == 1     # never zero
    for kvh, bs, hd, size in ((8, 16, 128, 2), (32, 16, 128, 4),
                              (8, 8, 64, 4), (32, 32, 128, 2)):
        c = _chunk_blocks(kvh, bs, hd, size)
        assert 4 * c * kvh * bs * hd * size <= pa.BUFFER_BYTES


@pytest.mark.parametrize("edge", sorted(_EDGES))
def test_walk_length_edges(edge):
    """A length on each edge of the walk (one position, a whole block,
    one past it, a whole chunk, one past it, two chunks, the full
    table) beside a mid-chunk slot: the kernel agrees with the
    gather-then-softmax reference to f32 rounding."""
    assert _W > 2 * _C and _W % _C      # the table ends inside a chunk
    q, k, v, tables = _rand_case(10, b=2, w=_W, bs=_BS, kvh=2, g=2,
                                 hd=_HD, nb=1 + 2 * _W)
    lengths = jnp.asarray([_EDGES[edge], 5 * _BS + 3], jnp.int32)
    got = _walk(q, k, v, tables, lengths)
    want = pa.paged_attention_reference(q, k, v, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_walk_idle_slots_beside_full_ones():
    """Idle slots as the engine presents them (an all-trash table row,
    length 1 + step) between slots that fill the table: each row is
    its own walk, and an idle row's value is the trash block's first
    positions, nothing of its neighbours."""
    b = 5
    q, k, v, tables = _rand_case(11, b=b, w=_W, bs=_BS, kvh=2, g=2,
                                 hd=_HD, nb=1 + b * _W)
    tables = np.asarray(tables).copy()
    tables[[1, 3, 4]] = kc.TRASH
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([_W * _BS, 1, _W * _BS, 4, 1], jnp.int32)
    got = np.asarray(_walk(q, k, v, tables, lengths))
    want = np.asarray(
        pa.paged_attention_reference(q, k, v, tables, lengths))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # one position: the softmax weight is 1 and the value is v's row
    assert np.array_equal(
        got[1], np.broadcast_to(np.asarray(v)[kc.TRASH, :, :1],
                                got[1].shape))


@pytest.mark.parametrize("window", [None, 40])
def test_walk_skips_the_slots_that_hold_no_request(window):
    """Slots that hold no request (PR 57: an all-trash table row and a
    length of 0) between live ones, over a POISONED trash block: the
    live rows are, bit for bit, what the same batch gave when an idle
    slot was walked as a length of 1 + step over the trash block (the
    parent's operands); an idle row is zeros, so nothing of the trash
    block was fetched into it; and the rule counts no block for it."""
    b, idle, live = 5, [1, 3, 4], [0, 2]
    q, k, v, tables = _rand_case(12, b=b, w=_W, bs=_BS, kvh=2, g=2,
                                 hd=_HD, nb=1 + b * _W)
    k, v = k.at[kc.TRASH].set(jnp.nan), v.at[kc.TRASH].set(jnp.nan)
    tables = np.asarray(tables).copy()
    tables[idle] = kc.TRASH
    tables = jnp.asarray(tables)
    walk = jax.jit(functools.partial(pa.paged_attention, interpret=True,
                                     window=window))
    before = np.asarray(walk(q, k, v, tables, jnp.asarray(
        [_W * _BS, 1, 5 * _BS + 3, 4, 1], jnp.int32)))
    lengths = jnp.asarray([_W * _BS, 0, 5 * _BS + 3, 0, 0], jnp.int32)
    got = np.asarray(walk(q, k, v, tables, lengths))
    assert np.isnan(before[idle]).all() and np.isfinite(got).all()
    assert np.array_equal(got[live], before[live])
    assert not got[idle].any()
    np.testing.assert_allclose(got[live], np.asarray(
        pa.paged_attention_reference(q, k, v, tables, lengths,
                                     window=window))[live],
                               rtol=2e-6, atol=2e-6)
    assert pa.live_blocks(0, _BS) == 0
    assert pa.fetched_positions(0, _BS, window) == 0
    assert pa.fetched_positions_run(0, 1, _BS, window) == 0


@pytest.mark.parametrize("kvh,g", [(8, 4), (32, 1), (2, 1)])
def test_walk_head_layouts(kvh, g):
    """GQA as the chat cell has it (8 x 4), MHA (32 x 1) and a shard of
    a tensor-parallel engine (2 x 1), bf16 pool and queries: q and k
    enter the score product as stored, and the result is the f32
    reference's on the same bf16 values."""
    bs, w, hd = 16, 10, 128
    assert _chunk_blocks(kvh, bs, hd, 2) == 8
    q, k, v, tables = _rand_case(12, b=3, w=w, bs=bs, kvh=kvh, g=g,
                                 hd=hd, nb=1 + 3 * w, dtype=jnp.bfloat16)
    lengths = jnp.asarray([1, 8 * bs + 1, w * bs], jnp.int32)
    got = _walk(q, k, v, tables, lengths)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    want = pa.paged_attention_reference(q, k, v, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bs,dtype", [(8, "float32"), (32, "float32"),
                                      (16, "bfloat16"), (128, "float32")])
def test_walk_block_sizes_and_pool_dtypes(bs, dtype):
    """Block sizes other than 16 change C (16, 4, 8, 1 blocks a chunk)
    and an f32 pool changes the score product's dtype; the walk is the
    same."""
    hd, w = 64, 160 // bs * 2 + 1               # two chunks and a block
    c = _chunk_blocks(2, bs, hd, jnp.dtype(dtype).itemsize)
    assert c == max(1, 128 // bs)
    q, k, v, tables = _rand_case(13, b=3, w=w, bs=bs, kvh=2, g=2,
                                 hd=hd, nb=1 + 3 * w,
                                 dtype=jnp.dtype(dtype))
    lengths = jnp.asarray([c * bs + 1, 2 * c * bs, w * bs], jnp.int32)
    got = _walk(q, k, v, tables, lengths)
    want = pa.paged_attention_reference(q, k, v, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_walk_mixed_dtypes_upcast_to_f32():
    """bf16 queries over an f32 pool (and the reverse): both operands
    of the score product are widened, never narrowed."""
    q, k, v, tables = _rand_case(14, b=2, w=_W, bs=_BS, kvh=2, g=2,
                                 hd=_HD, nb=1 + 2 * _W)
    lengths = jnp.asarray([3 * _BS + 5, _W * _BS], jnp.int32)
    for qd, pd in ((jnp.bfloat16, jnp.float32),
                   (jnp.float32, jnp.bfloat16)):
        qq, kk, vv = q.astype(qd), k.astype(pd), v.astype(pd)
        got = _walk(qq, kk, vv, tables, lengths)
        want = pa.paged_attention_reference(qq, kk, vv, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("edge", ["block_plus_1", "chunk", "chunk_plus_1",
                                  "full_table"])
def test_walk_poisoned_pool_is_bit_equal(edge):
    """Every block a slot does not own, and every position past its
    length inside its last block, filled with NaN: the output is
    BIT-EQUAL to the clean pool's. Nothing past the live context
    reaches the result, not even as 0 x NaN."""
    b = 3
    q, k, v, tables = _rand_case(15, b=b, w=_W, bs=_BS, kvh=2, g=2,
                                 hd=_HD, nb=1 + b * _W)
    lens = [_EDGES[edge], 1, 3 * _BS - 1]
    tables = np.asarray(tables).copy()
    tables[1] = kc.TRASH                        # an idle slot
    own = np.zeros((k.shape[0], _BS), bool)     # [block, position]
    for row, n in zip(tables, lens):
        for j in range(-(-n // _BS)):
            own[row[j], :min(_BS, n - j * _BS)] = True
    assert not own.all() and own[kc.TRASH, 0] and not own[kc.TRASH, 1]
    poison = np.where(own[:, None, :, None], 0.0, np.nan)
    kp, vp = (jnp.asarray(np.asarray(x) + poison.astype(np.float32))
              for x in (k, v))
    tables, lengths = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
    clean = np.asarray(_walk(q, k, v, tables, lengths))
    dirty = np.asarray(_walk(q, kp, vp, tables, lengths))
    assert np.isfinite(clean).all()
    assert np.array_equal(clean, dirty)


@pytest.mark.parametrize("length", [0, 1, 16, 17, 128, 129, 300, 320])
def test_fetch_rule_counts_the_blocks_the_walk_names(length):
    """``fetched_positions`` (what the engine's llm_decode_kv_fetch_
    tokens sums) against the walk itself: with equal scores and block
    j's values the indicator of column j, the output's non-zero
    columns ARE the blocks the walk attended, and their weights the
    positions it took from each."""
    bs, w, kvh, g, hd = _BS, _W, 2, 2, 128
    nb = 1 + w
    q = jnp.ones((1, kvh, g, hd), jnp.float32)
    k = jnp.zeros((nb, kvh, bs, hd), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(nb, hd)[:, None, None, :],
                         (nb, kvh, bs, hd))
    tables = jnp.asarray(1 + np.arange(w)[None], jnp.int32)
    got = np.asarray(_walk(q, k, v, tables,
                           jnp.asarray([length], jnp.int32)))[0, 0, 0]
    if length == 0:
        # a slot that holds no request: nothing walked, nothing attended
        assert not got.any() and pa.fetched_positions(0, bs) == 0
        assert pa.live_blocks(0, bs) == 0
        return
    n = length
    named = np.flatnonzero(got)
    assert list(named) == list(range(1, 1 + -(-n // bs)))
    assert pa.fetched_positions(length, bs) == len(named) * bs
    np.testing.assert_allclose(
        got[named] * n,
        [min(bs, n - j * bs) for j in range(len(named))], rtol=1e-6)
    # the rule on arrays, as the engine calls it
    assert np.array_equal(
        pa.fetched_positions(np.asarray([[length, length + bs]]), bs),
        [[len(named) * bs, (len(named) + 1) * bs]])


# --- impl resolution + Config knobs -----------------------------------


def test_resolve_attn_impl():
    # auto resolves by backend: gather on the CPU tier-1 backend
    assert kc.resolve_attn_impl("auto") == "gather"
    assert kc.resolve_attn_impl("gather") == "gather"
    assert kc.resolve_attn_impl("paged_flash") == "paged_flash"
    with pytest.raises(ValueError, match="auto|paged_flash|gather"):
        kc.resolve_attn_impl("flash")


def test_platform_picks_engine_impl(tiny_model, monkeypatch):
    """No knob picks the decode attention: the platform does (the
    kernel on a TPU, the gather view elsewhere), the kv_impl kwarg
    overrides it (the kernel's reference in parity checks), and off a
    TPU the kernel runs through the interpreter."""
    import sys
    cfg, params = tiny_model
    kw = dict(max_slots=2, max_len=32, prefill_buckets=(8,),
              cache_dtype="float32", kv_block_size=8)

    eng = LLMEngine(cfg, params, **kw)
    assert eng._kv_impl == "gather"       # this backend is no TPU
    assert not eng._kv_interpret
    assert eng.stats["kv_impl"] == "gather"
    assert eng.stats["kv_interpret"] is False

    # what resolve_attn_impl asks; the device itself stays the CPU
    monkeypatch.setattr(sys.modules["ray_tpu.ops.attention"], "_on_tpu",
                        lambda: True)
    eng = LLMEngine(cfg, params, **kw)
    assert eng._kv_impl == "paged_flash"
    assert eng._kv_interpret          # no TPU under the kernel here
    assert eng.stats["kv_interpret"] is True

    # the explicit kwarg beats the platform's choice, both ways
    eng = LLMEngine(cfg, params, kv_impl="gather", **kw)
    assert eng._kv_impl == "gather" and not eng._kv_interpret
    monkeypatch.undo()
    eng = LLMEngine(cfg, params, kv_impl="paged_flash", **kw)
    assert eng._kv_impl == "paged_flash" and eng._kv_interpret


# --- the aliased block writer (interpret mode) ------------------------


def _write_case(seed, *, layers=2, nb=6, kvh=2, bs=8, hd=128,
                dtype=jnp.float32):
    """Stacked random pools (layers, nb, kvh, bs, hd) in ``dtype``."""
    rng = np.random.default_rng(seed)
    shape = (layers, nb, kvh, bs, hd)
    return [jnp.asarray(rng.normal(size=shape).astype(np.float32))
            .astype(dtype) for _ in "kv"]


def _check_kv_write(kp, vp, layer, phys, rows):
    """kv_write on layer ``layer`` of the flat pools against the
    scatter it replaces: BIT-EQUAL on the whole of both pools."""
    nb, kvh, hd = kp.shape[1], kp.shape[2], kp.shape[4]
    rng = np.random.default_rng(len(phys))
    new = [jnp.asarray(rng.normal(size=(len(phys), kvh, hd))
                       .astype(np.float32)).astype(kp.dtype)
           for _ in "kv"]
    phys, rows = (jnp.asarray(x, jnp.int32) for x in (phys, rows))
    # the scatter's order over equal places is not defined: entries
    # for one place carry the same row, so that any order is right
    _, place = np.unique(np.stack([phys, rows], 1), axis=0,
                         return_inverse=True)
    new = [n[np.asarray(place).reshape(-1)] for n in new]
    flat = [p.reshape(-1, *p.shape[2:]) for p in (kp, vp)]
    got = jax.jit(functools.partial(pa.kv_write, interpret=True))(
        *flat, layer * nb + phys, rows, *new)
    for g, pool, n in zip(got, (kp, vp), new):
        want = pool.at[layer, phys, :, rows].set(n)
        assert g.dtype == pool.dtype
        np.testing.assert_array_equal(
            np.asarray(g.reshape(pool.shape).astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))


_WRITES = {     # (physical blocks, rows) at block size bs
    "row_0": lambda bs: ([3, 1], [0, 0]),
    "row_last": lambda bs: ([2, 5], [bs - 1, bs - 1]),
    # idle slots all write the trash block, beside a live one
    "trash_block": lambda bs: ([0, 0, 4, 0], [1, 1, 2, 1]),
    # a verify round: a slot's drafts follow each other in one block
    "two_in_a_block": lambda bs: ([3, 3, 3, 4], [bs - 2, bs - 1, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(_WRITES))
@pytest.mark.parametrize("layer", [0, 1])
def test_kv_write_is_the_scatter_bit_for_bit(case, layer):
    kp, vp = _write_case(1)
    _check_kv_write(kp, vp, layer, *_WRITES[case](kp.shape[3]))


@pytest.mark.parametrize("bs", [8, 16, 32])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kvh", [1, 8, 32])
def test_kv_write_block_sizes_dtypes_heads(bs, dtype, kvh):
    """One algorithm whose parameters are the pool's shape and dtype."""
    kp, vp = _write_case(2, kvh=kvh, bs=bs, dtype=jnp.dtype(dtype))
    _check_kv_write(kp, vp, 1, [5, 2, 2, 0], [bs - 1, 0, 3, 1])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kv_write_skips_the_entries_that_are_none(dtype):
    """An entry with a negative block (PR 57: a slot that holds no
    request) between live ones: both pools are, bit for bit, the
    scatter of the live entries alone, the trash block, which the
    same batch wrote when idle slots were pointed at it, included."""
    kp, vp = _write_case(3, dtype=jnp.dtype(dtype))
    nb, kvh, bs, hd = kp.shape[1:]
    layer, phys, rows = 1, [3, -1, 5, -1, -1, 3], [2, 1, bs - 1, 1, 0, 3]
    entries = np.asarray(phys) >= 0
    rng = np.random.default_rng(9)
    new = [jnp.asarray(rng.normal(size=(len(phys), kvh, hd))
                       .astype(np.float32)).astype(kp.dtype) for _ in "kv"]
    flat = [p.reshape(-1, *p.shape[2:]) for p in (kp, vp)]
    write = jax.jit(functools.partial(pa.kv_write, interpret=True))
    ids = jnp.asarray(phys, jnp.int32)
    got = write(*flat, jnp.where(ids < 0, -1, layer * nb + ids),
                jnp.asarray(rows, jnp.int32), *new)
    before = write(*flat, layer * nb + jnp.maximum(ids, kc.TRASH),
                   jnp.asarray(rows, jnp.int32), *new)
    for g, old, pool, n in zip(got, before, (kp, vp), new):
        want = pool.at[layer, ids[entries], :,
                       jnp.asarray(rows)[entries]].set(n[entries])
        g, old = (np.asarray(x.reshape(pool.shape).astype(jnp.float32))
                  for x in (g, old))
        np.testing.assert_array_equal(g, np.asarray(
            want.astype(jnp.float32)))
        np.testing.assert_array_equal(g[:, 1:], old[:, 1:])
        assert (old[layer, kc.TRASH] != g[layer, kc.TRASH]).any()


def test_kv_write_refuses_rows_the_pool_cannot_take():
    kp, vp = (p.reshape(-1, *p.shape[2:]) for p in _write_case(3))
    ids = jnp.zeros((2,), jnp.int32)
    rows = jnp.zeros((2, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="do not take rows"):
        pa.kv_write(kp, vp, ids, ids, rows[:, :1], rows[:, :1])
    with pytest.raises(ValueError, match="one dtype"):
        pa.kv_write(kp, vp, ids, ids, rows.astype(jnp.bfloat16),
                    rows.astype(jnp.bfloat16))


@pytest.mark.parametrize("n", [1, 8])
def test_decode_steps_kernel_matches_gather_in_place(tiny_model, n):
    """paged_decode_steps — the engine's one decode call — with the
    writer and the walk interpreted against the gather reference, n
    steps (an idle slot beside live ones, a slot crossing a block
    edge): the live slots' tokens the same; the same pools outside the
    trash block — bit for bit wherever no step wrote and in layer 0
    (its rows come from the tokens alone), to f32 rounding in the rows
    of layer 1 (the online softmax is a refactoring of the reference's,
    not its bits); the trash block, which the reference's scatter
    writes for the idle slot, untouched by the kernels; and the donated
    pool consumed, not copied."""
    cfg, params = tiny_model
    bs, w, slots = 8, 4, 4
    tables = np.zeros((slots, w), np.int32)     # slot 3 idle: trash
    tables[:3] = (1 + np.arange(3 * w)).reshape(3, w)
    lengths = np.asarray([1, 12, 6, 0], np.int32)
    tokens = jnp.asarray([3, 9, 27, 0], jnp.int32)
    temps = jnp.zeros((slots,), jnp.float32)
    fresh = kc.init_pool(cfg, 1 + 3 * w, bs, jnp.float32)
    fresh = {k: np.asarray(jax.random.normal(jax.random.PRNGKey(i),
                                             v.shape))
             for i, (k, v) in enumerate(fresh.items())}

    def run(**kw):
        pool = {k: jnp.asarray(v) for k, v in fresh.items()}
        out, new = kc.paged_decode_steps(
            params, pool, jnp.asarray(tables), jnp.asarray(lengths),
            tokens, temps, jax.random.PRNGKey(7), cfg, n, **kw)
        assert all(v.is_deleted() for v in pool.values())
        return np.asarray(out), {k: np.asarray(v) for k, v in new.items()}

    want_toks, want = run(impl="gather")
    got_toks, got = run(impl="paged_flash", interpret=True)
    assert got_toks.shape == (n, slots)
    np.testing.assert_array_equal(got_toks[:, :3], want_toks[:, :3])
    pos = lengths[:3, None] + np.arange(n)[None]            # (live, n)
    phys = np.take_along_axis(tables[:3], pos // bs, axis=1)
    wrote = np.zeros(fresh["k"].shape[1:4:2], bool)         # (nb, bs)
    wrote[phys, pos % bs] = True
    for k in "kv":
        assert (want[k][:, kc.TRASH] != fresh[k][:, kc.TRASH]).any()
        np.testing.assert_array_equal(got[k][0, 1:], want[k][0, 1:])
        np.testing.assert_allclose(got[k][:, 1:], want[k][:, 1:],
                                   rtol=2e-5, atol=2e-5)
        still = ~np.broadcast_to(wrote[None, :, None, :, None],
                                 got[k].shape)
        assert still[:, kc.TRASH].all()
        np.testing.assert_array_equal(got[k][still], fresh[k][still])
        assert (got[k][~still] != fresh[k][~still]).any()


# --- decode-path parity through the engine ----------------------------


def test_decode_step_logits_kernel_matches_gather(tiny_model):
    """kvcache.paged_decode_logits — the parity entry chip_smoke.py
    runs on the chip at real widths — here under the interpreter: one
    decode step through the whole model gives the same logits whether
    attention walks the block table or gathers the view, at a length
    of one, inside a block, and on a block edge."""
    cfg, params = tiny_model
    bs, w = 8, 4
    pool = kc.init_pool(cfg, 1 + 3 * w, bs, jnp.float32)
    pool = {k: jax.random.normal(jax.random.PRNGKey(i), v.shape)
            for i, (k, v) in enumerate(pool.items())}
    tables = jnp.asarray(
        (1 + np.arange(3 * w)).reshape(3, w).astype(np.int32))
    positions = jnp.asarray([0, 12, 23], jnp.int32)
    tokens = jnp.asarray([3, 9, 27], jnp.int32)
    want = kc.paged_decode_logits(params, pool, tables, positions,
                                  tokens, cfg, impl="gather")
    got = kc.paged_decode_logits(params, pool, tables, positions,
                                 tokens, cfg, impl="paged_flash",
                                 interpret=True)
    assert want.shape == (3, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)



def test_engine_kernel_impl_matches_gather_impl(tiny_model):
    """A/B the two decode attention impls through the full engine:
    same prompts, same greedy tokens — the fused kernel replaces the
    gathered view without moving a single sampled token. Also pins the
    per-impl metric (llm_paged_attn_steps_total tags the steps) and
    the positions the engine counts as attended (llm_decode_ctx_tokens:
    output token i >= 1 of a P-token prompt attends P + i)."""
    from ray_tpu.util import metrics as M
    cfg, params = tiny_model
    prompts = [_prompt(100 + i, 5 + 3 * i) for i in range(3)]

    async def gen(impl):
        eng = LLMEngine(cfg, params, max_slots=2, max_len=32,
                        prefill_buckets=(8,), cache_dtype="float32",
                        kv_block_size=8, prefix_cache=False,
                        kv_impl=impl)
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=8) for p in prompts])
        await eng.stop()
        return [o["tokens"] for o in outs]

    gather = asyncio.run(gen("gather"))
    reg = M._REGISTRY
    ctx0 = sum(reg["llm_decode_ctx_tokens"]._sums.values())
    flash = asyncio.run(gen("paged_flash"))
    assert flash == gather
    steps = reg["llm_paged_attn_steps_total"]._values
    assert any("paged_flash" in str(k) and v > 0
               for k, v in steps.items())
    assert any("gather" in str(k) and v > 0 for k, v in steps.items())
    ctx1 = sum(reg["llm_decode_ctx_tokens"]._sums.values())
    assert ctx1 - ctx0 == sum(
        len(p) + i for p in prompts for i in range(1, 8))


def test_engine_counts_what_the_walk_fetched(tiny_model):
    """llm_decode_kv_fetch_tokens: the kernel's fetch rule summed over
    a block's slots and steps, observed beside llm_decode_ctx_tokens.
    One request alone in three slots: output token i >= 1 of a P-token
    prompt walks ceil((P + i) / block) blocks and the two slots that
    hold no request none. The gather impl walks nothing and counts
    nothing."""
    from ray_tpu.util import metrics as M
    cfg, params = tiny_model
    prompt, new, bs, slots = _prompt(130, 13), 9, 8, 3

    async def gen(impl):
        eng = LLMEngine(cfg, params, max_slots=slots, max_len=32,
                        prefill_buckets=(16,), cache_dtype="float32",
                        kv_block_size=bs, prefix_cache=False,
                        kv_impl=impl)
        out = await eng.generate(prompt, max_new_tokens=new)
        await eng.stop()
        return out

    from ray_tpu.llm.engine import engine_metrics
    hist = engine_metrics()["kv_fetch_tokens"]
    assert hist is M._REGISTRY["llm_decode_kv_fetch_tokens"]
    before = sum(hist._sums.values())
    asyncio.run(gen("gather"))
    assert sum(hist._sums.values()) == before
    asyncio.run(gen("paged_flash"))
    want = sum(pa.fetched_positions(len(prompt) + i, bs)
               for i in range(1, new))
    assert sum(hist._sums.values()) - before == want
    # 13 + i crosses a block edge at 17 and again at 25: not a constant
    assert want > (new - 1) * 2 * bs


@pytest.mark.parametrize("impl", ["gather", "paged_flash"])
def test_engine_counts_the_slot_steps_that_held_no_request(tiny_model,
                                                           impl):
    """llm_decode_idle_slot_steps (PR 57): (slots - the block's
    requests) x its steps, a block, observed as it is enqueued. Two
    requests of different budgets in four slots: every decode step has
    two or three slots with no request, and the idle and the live
    slot-steps of the run add up to slots x steps."""
    from ray_tpu.llm.engine import engine_metrics
    cfg, params = tiny_model
    slots, news = 4, (9, 5)

    async def gen():
        eng = LLMEngine(cfg, params, max_slots=slots, max_len=32,
                        prefill_buckets=(16,), cache_dtype="float32",
                        kv_block_size=8, prefix_cache=False, kv_impl=impl)
        outs = await asyncio.gather(*[
            eng.generate(_prompt(131 + i, 11), max_new_tokens=n)
            for i, n in enumerate(news)])
        await eng.stop()
        return outs

    def sums():
        m = engine_metrics()
        return {k: sum(m[k]._sums.values())
                for k in ("idle_slot_steps", "slot_steps", "block_steps")}
    before = sums()
    outs = asyncio.run(gen())
    assert [len(o["tokens"]) for o in outs] == list(news)
    d = {k: v - before[k] for k, v in sums().items()}
    # a reply's first token is the prefill's: one decode step each other
    assert d["slot_steps"] == sum(n - 1 for n in news)
    assert d["idle_slot_steps"] == slots * d["block_steps"] \
        - d["slot_steps"]
    assert d["idle_slot_steps"] >= (slots - 2) * (max(news) - 1)


# --- tensor-parallel paged engines ------------------------------------


def test_tp_engine_runs_paged_gather(tiny_model):
    """The TP restriction is lifted: a meshed engine with a block size
    runs PAGED (pool sharded on its kv-head dim, tables replicated)
    and reproduces the reference greedy tokens."""
    cfg, params = tiny_model
    prompts = [[3, 7, 11], [9, 1], [5, 5, 5, 5]]
    refs = [_ref_greedy(cfg, params, p, 8) for p in prompts]

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=32,
                        prefill_buckets=(8,), cache_dtype="float32",
                        kv_block_size=8, prefix_cache=False,
                        kv_impl="gather", mesh=_tp_mesh(2))
        assert eng._kv_impl == "gather"
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=8) for p in prompts])
        await eng.stop()
        return outs

    outs = asyncio.run(go())
    for o, ref in zip(outs, refs):
        assert o["tokens"] == ref


def test_tp_engine_kernel_with_prefix_reuse(tiny_model):
    """Full acceptance row: tensor-parallel engine + fused kernel +
    prefix cache. The shard_mapped kernel (heads sharded, tables
    replicated) must reproduce reference tokens, and a shared-prefix
    request must land measurable hit tokens."""
    cfg, params = tiny_model
    shared = _prompt(110, 32)
    req = shared + _prompt(111, 6)
    ref = _ref_greedy(cfg, params, req, 8)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(16, 64),
                        cache_dtype="float32", kv_block_size=8,
                        prefix_cache=True, kv_impl="paged_flash",
                        mesh=_tp_mesh(2))
        assert eng._kv_impl == "paged_flash"
        await eng.generate(shared, max_new_tokens=4)
        out = await eng.generate(req, max_new_tokens=8)
        stats = eng.stats
        await eng.stop()
        return out, stats

    out, stats = asyncio.run(go())
    assert out["prefix_hit_tokens"] >= 24, out
    assert stats["prefix_hit_tokens"] >= 24
    assert out["tokens"] == ref


# --- chunk-grid-aligned prefix hits -----------------------------------


def test_prefill_start_rounds_down_to_chunk_grid(tiny_model):
    """Unit: on a flash-capable chunked-prefill path the suffix start
    rounds DOWN to the chunk grid (bounded per-offset compiles); on
    the XLA reference path the hit is used as-is."""
    cfg, params = tiny_model          # attn_impl="reference"
    eng = LLMEngine(cfg, params, max_slots=1, max_len=64,
                    prefill_buckets=(16,), cache_dtype="float32",
                    kv_block_size=8)
    assert eng._prefill_start(0) == 0
    assert eng._prefill_start(24) == 24        # reference: exact hit

    fl_cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, dtype="float32",
                        logits_dtype="float32",
                        attn_impl="flash_interpret")
    fl_params = llama.init_params(jax.random.PRNGKey(0), fl_cfg)
    eng_fl = LLMEngine(fl_cfg, fl_params, max_slots=1, max_len=512,
                       prefill_buckets=(128,), cache_dtype="float32",
                       kv_block_size=8)
    assert eng_fl._prefill_start(0) == 0
    assert eng_fl._prefill_start(8) == 0       # sub-chunk hit: recompute
    assert eng_fl._prefill_start(160) == 128   # rounds down to grid
    assert eng_fl._prefill_start(256) == 256   # already aligned


@pytest.mark.slow
def test_flash_prefix_hit_matches_cold_engine():
    """End-to-end on the flash chunked-prefill path: a prefix-hit
    request enters the compiled chunk-grid flash variants (start
    rounded down, < one chunk recomputed into trash-targeted blocks)
    and still generates exactly what a cold engine generates."""
    fl_cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, dtype="float32",
                        logits_dtype="float32",
                        attn_impl="flash_interpret")
    params = llama.init_params(jax.random.PRNGKey(0), fl_cfg)
    shared = _prompt(120, 160)
    req = shared + _prompt(121, 10)

    async def gen(prefix_cache):
        eng = LLMEngine(fl_cfg, params, max_slots=2, max_len=512,
                        prefill_buckets=(128,), cache_dtype="float32",
                        kv_block_size=8, prefix_cache=prefix_cache)
        if prefix_cache:
            await eng.generate(shared, max_new_tokens=4)
        out = await eng.generate(req, max_new_tokens=8)
        await eng.stop()
        return out

    cold = asyncio.run(gen(False))
    warm = asyncio.run(gen(True))
    assert warm["prefix_hit_tokens"] >= 128, warm
    assert warm["tokens"] == cold["tokens"]
    assert cold["prefix_hit_tokens"] == 0
