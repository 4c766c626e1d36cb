"""Paged KV cache (llm/kvcache.py): block alloc/free/refcount, prefix
reuse, COW divergence, LRU eviction under pool pressure — and the two
parity contracts the subsystem is pinned to: the paged engine
reproduces the training model's full-forward greedy tokens on
cache-cold requests, and a prefix-cache-hit request's logits
bitwise-match those of a cold request on the same chunk grid.

(Late-alphabet name keeps the tier-1 870 s cutoff stable.)
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import kvcache as kc
from ray_tpu.llm import model as lm
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import llama
# greedy continuation by the training model's full forward, no cache of
# any kind: the reference the paged engine is held to
from test_llm import _ref_greedy


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


# --- host-side block manager (no jax) ---------------------------------


def test_alloc_free_refcount():
    m = kc.KVBlockManager(20, 8, table_width=8)
    a = m.alloc_seq("a", _prompt(0, 20), 12)     # 32 tokens -> 4 blocks
    assert a["hit_tokens"] == 0
    assert len(a["new_blocks"]) == 4
    assert m.used_blocks() == 4 and m.free_blocks() == 15
    # trash (0) is never allocated
    assert kc.TRASH not in a["new_blocks"]
    # tail of the table is trash
    assert list(a["table"][4:]) == [kc.TRASH] * 4
    m.free_seq("a")     # no token stream: prompt-hash chain caches
    assert m.used_blocks() == 0
    # prompt had 2 FULL blocks (20 tokens at block 8) -> 2 cached;
    # the partial tail + horizon blocks went back to the free list
    assert m.cached_blocks() == 2
    assert m.free_blocks() == 17


def test_prefix_hit_refcounts_and_cap():
    m = kc.KVBlockManager(32, 8, table_width=8)
    toks = _prompt(1, 24)
    a = m.alloc_seq("a", toks, 8)
    m.free_seq("a", toks + [5] * 8)   # full stream: 4 full blocks cached
    assert m.cached_blocks() == 4
    # same prompt: hits are capped one token short of the prompt, so
    # a 24-token prompt hits 2 full blocks (16 tokens), never 3
    b = m.alloc_seq("b", toks, 8)
    assert b["hit_tokens"] == 16
    # shared blocks are ref-counted: still cached, now also in use
    assert m.used_blocks() == len(set(
        p for p in b["table"] if p != kc.TRASH))
    # a longer prompt extending the cached stream hits 3 blocks
    c = m.alloc_seq("c", toks + [5] * 8, 8)
    assert c["hit_tokens"] == 24
    m.free_seq("b")
    m.free_seq("c")
    assert m.used_blocks() == 0


def test_divergent_prompt_misses_after_shared_prefix():
    m = kc.KVBlockManager(32, 8, table_width=8)
    toks = _prompt(2, 32)
    m.alloc_seq("a", toks, 8)
    m.free_seq("a", toks)
    div = toks[:16] + [99] * 16       # diverges at block 2
    d = m.alloc_seq("d", div, 8)
    assert d["hit_tokens"] == 16      # only the shared blocks hit
    m.free_seq("d", div)
    # both chains now cached; the divergent suffix got its own blocks
    assert m.cached_blocks() >= 4


def test_cow_on_fork_divergence():
    m = kc.KVBlockManager(20, 8, table_width=8)
    toks = _prompt(3, 20)
    a = m.alloc_seq("a", toks, 12)
    table_a = list(m.seqs["a"].table)
    m.fork_seq("a", "b")
    # every block is now shared: writing any of them must COW
    got = m.ensure_writable("b", 2)
    assert got is not None
    old, new = got
    assert old == table_a[2] and new != old
    assert m.seqs["b"].table[2] == new
    assert m.seqs["a"].table[2] == old
    # the un-forked block of "a" is still exclusively referenced...
    m.free_seq("b")
    # ...so after the fork dies, "a"'s blocks are private again
    assert m.ensure_writable("a", 2) is None


def test_cow_protects_cached_blocks():
    """A block held by the prefix index must COW even at refcount 1 —
    writing it in place would silently corrupt the cached content
    behind its chain hash."""
    m = kc.KVBlockManager(20, 8, table_width=8)
    toks = _prompt(4, 16)
    m.alloc_seq("a", toks, 8)
    m.free_seq("a", toks)             # 2 blocks cached
    b = m.alloc_seq("b", toks, 8)
    assert b["hit_tokens"] == 8       # capped at n-1 -> 1 block
    assert m.ensure_writable("b", 0) is not None   # shared+cached: COW
    m.free_seq("b")


def test_lru_eviction_leaf_first_under_pressure():
    m = kc.KVBlockManager(9, 8, table_width=8)    # 8 usable blocks
    t1 = _prompt(5, 16)
    m.alloc_seq("a", t1, 0 or 8)
    m.free_seq("a", t1)               # chain1: 2 cached blocks
    t2 = _prompt(6, 16)
    m.alloc_seq("b", t2, 8)
    m.free_seq("b", t2)               # chain2: 2 cached blocks
    assert m.cached_blocks() == 4 and m.free_blocks() == 4
    # touch BOTH of chain1's blocks (the one-token tail lets the
    # lookup cap walk the full chain) so chain2 is the LRU victim
    hit, _ = m.lookup(t1 + [1])
    assert hit == 16
    # allocating 6 blocks forces eviction of 2: chain2's leaf FIRST,
    # then its root
    c = m.alloc_seq("c", _prompt(7, 40), 8)       # 48 tokens -> 6 blocks
    assert c is not None
    assert m.evicted_total == 2
    # chain1 survived (it was fresher)
    hit1, _ = m.lookup(t1 + [1])
    assert hit1 == 16
    hit2, _ = m.lookup(t2 + [1])
    assert hit2 == 0


def test_eviction_never_reclaims_pinned_hit_blocks():
    """Regression: alloc_seq pins its prefix-hit blocks BEFORE
    evicting for the remainder — an evicted-then-reallocated hit
    block would land in the table twice (prefix view + fresh write
    target) and silently corrupt the KV. When pinning makes the
    request unfittable, the alloc parks (None) instead."""
    m = kc.KVBlockManager(9, 8, table_width=8)    # 8 usable
    other = m.alloc_seq("c", _prompt(11, 28), 2)  # live: 4 blocks
    assert other is not None
    toks = _prompt(12, 24)
    m.alloc_seq("a", toks, 8)                     # remaining 4 blocks
    m.free_seq("a", toks + [7] * 8)               # 4 cached, 0 free
    assert m.cached_blocks() == 4 and m.free_blocks() == 0
    # b hits 2 blocks and needs 3 more; only the 2 non-hit cached
    # blocks are evictable once the hits are pinned -> park, and the
    # hit blocks' refcounts roll back
    b = m.alloc_seq("b", toks, 16)
    assert b is None
    assert m.used_blocks() == 4                   # only "c" holds refs
    # after the live seq frees, the same alloc succeeds with the hit
    # blocks intact (still cached) and no duplicates in the table
    m.free_seq("c")
    b = m.alloc_seq("b", toks, 16)
    assert b is not None and b["hit_tokens"] == 16
    live = [p for p in b["table"] if p != kc.TRASH]
    assert len(live) == len(set(live)), f"duplicate phys: {live}"
    m.free_seq("b")


def test_failed_admit_never_poisons_prefix_cache():
    """Regression: a request whose KV was never written (admit failed
    before the prefill scatter) must not index its zero/stale blocks
    under the prompt's chain hashes — free_seq(cache=False)."""
    m = kc.KVBlockManager(20, 8, table_width=8)
    toks = _prompt(13, 24)
    m.alloc_seq("dead", toks, 8)
    m.free_seq("dead", toks, cache=False)         # the engine's
    # kv_written=False path: nothing cached, everything freed
    assert m.cached_blocks() == 0
    assert m.free_blocks() == 19
    hit, _ = m.lookup(toks)
    assert hit == 0


def test_pool_exhausted_and_parked_alloc():
    m = kc.KVBlockManager(9, 8, table_width=16)
    # horizon wider than the whole pool: can NEVER fit
    with pytest.raises(kc.BlockPoolExhausted):
        m.alloc_seq("x", _prompt(8, 64), 40)
    # fits the pool but not right now (another seq holds the blocks):
    # alloc returns None (caller parks the admit) instead of raising
    m.alloc_seq("a", _prompt(9, 40), 8)           # 6 of 8 blocks
    assert m.alloc_seq("b", _prompt(10, 24), 8) is None
    m.free_seq("a")
    assert m.alloc_seq("b", _prompt(10, 24), 8) is not None


def test_config_knobs_select_paged_mode(tiny_model, monkeypatch):
    """The Config surface (kvcache_block_size / kvcache_pool_blocks /
    kvcache_prefix_cache) drives engine construction when the kwargs
    are left at None."""
    from ray_tpu.config import get_config
    cfg_obj = get_config()
    monkeypatch.setattr(cfg_obj, "kvcache_block_size", 8)
    monkeypatch.setattr(cfg_obj, "kvcache_pool_blocks", 40)
    monkeypatch.setattr(cfg_obj, "kvcache_prefix_cache", False)
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                    prefill_buckets=(16,), cache_dtype="float32")
    assert eng._block == 8 and eng.stats["block_size"] == 8
    assert eng._kv.num_blocks == 40
    assert not eng._kv.prefix_cache


# --- device parity ----------------------------------------------------


def test_paged_matches_full_forward_cold(tiny_model):
    """Acceptance pin: on cache-cold requests (block 8, two slots for
    five requests, so slots and blocks are reused) the paged engine's
    greedy tokens are IDENTICAL to the full-forward reference's."""
    cfg, params = tiny_model
    prompts = [_prompt(20 + i, 5 + 3 * i) for i in range(5)]

    async def gen():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(16,), cache_dtype="float32",
                        kv_block_size=8, prefix_cache=False)
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=10) for p in prompts])
        await eng.stop()
        return [o["tokens"] for o in outs]

    assert asyncio.run(gen()) == [_ref_greedy(cfg, params, p, 10)
                                  for p in prompts]


def test_paged_long_prompt_matches_full_forward(tiny_model):
    """Chunked prefill through the block pool (block 16, a 200-token
    prompt past the biggest bucket: four pieces through
    lm.prefill_chunk) reproduces the full-forward reference's
    tokens."""
    cfg, params = tiny_model
    prompt = _prompt(30, 200)

    async def gen():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=512,
                        prefill_buckets=(64,), cache_dtype="float32",
                        kv_block_size=16, prefix_cache=False)
        out = await eng.generate(prompt, max_new_tokens=12)
        await eng.stop()
        return out["tokens"]

    assert asyncio.run(gen()) == _ref_greedy(cfg, params, prompt, 12)


def test_prefix_hit_logits_bitwise_parity(tiny_model):
    """The satellite pin: a prefix-cache-hit request's first-token
    LOGITS and suffix KV bitwise-match those of a cold request that
    went through the SAME chunk grid (both via lm.prefill_chunk: the
    suffix piece is then one program on equal bytes, which is what
    the engine arranges with _prefill_start and the absolute chunk
    grid, and what makes a hit generate what a cold request does).
    Against the one-bucket lm.prefill the bar is argmax and an f32
    tolerance, not bits: a bucket-32 forward and an 8-row chunk at
    offset 16 are different matmul shapes with different reduction
    orders, and their f32 logits differ in the 6th-7th digit (1.7e-6
    on logits up to 2.5 here; the tolerance is twelve times that)."""
    cfg, params = tiny_model
    B, W, chunk = 8, 8, 16
    toks = _prompt(40, 24)

    def chunked(acc, start):
        """toks[start:] through the chunk grid, as the engine cuts it"""
        logits = None
        for off in range(start, len(toks), chunk):
            part = toks[off:off + chunk]
            b = lm.bucket_for((8, 16), len(part))
            logits, acc = lm.prefill_chunk(
                params, jnp.asarray(lm.pad_prompt(part, b)),
                jnp.int32(len(part)), jnp.int32(off), acc, cfg)
        return np.asarray(logits), acc

    shape = (cfg.n_layers, 64, cfg.n_kv_heads, cfg.head_dim)
    logits_cold, acc_cold = chunked(        # the accumulator is donated
        {"k": jnp.zeros(shape, jnp.float32),
         "v": jnp.zeros(shape, jnp.float32)}, 0)
    # seed the pool with the prefix's first 2 blocks (16 tokens), the
    # bytes a previous identical request would have scattered
    pool = kc.init_pool(cfg, 24, B, jnp.float32)
    phys = np.full((W,), kc.TRASH, np.int32)
    phys[0], phys[1] = 3, 4
    pool = kc.scatter_table(pool, acc_cold, jnp.asarray(phys))
    # hit path: gather the table, prefill ONLY the suffix at offset 16
    table = np.full((W,), kc.TRASH, np.int32)
    table[0], table[1], table[2] = 3, 4, 5
    acc = kc.gather_table(pool, jnp.asarray(table), 64)
    logits_hit, acc = chunked(acc, 16)
    assert np.array_equal(logits_hit, logits_cold)
    # the suffix KV it computed is also bitwise the cold request's —
    # decode then attends identical bytes
    for key in ("k", "v"):
        assert np.array_equal(np.asarray(acc[key][:, 16:24]),
                              np.asarray(acc_cold[key][:, 16:24]))
    # the one-bucket forward: same token, logits equal to f32 rounding
    logits_one, kv = lm.prefill(
        params, jnp.asarray(lm.pad_prompt(toks, 32)), jnp.int32(24),
        cfg, 32)
    logits_one = np.asarray(logits_one)
    assert int(np.argmax(logits_hit)) == int(np.argmax(logits_one))
    np.testing.assert_allclose(logits_hit, logits_one, rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(acc["k"][:, :24]),
                               np.asarray(kv["k"][:, :24]),
                               rtol=0, atol=2e-5)


def test_prefix_hit_generation_matches_cold_engine(tiny_model):
    """End-to-end through the engine: warm the prefix cache with one
    request, then a second request sharing the prefix must (a) report
    hit tokens, (b) generate exactly what a cold engine generates."""
    cfg, params = tiny_model
    shared = _prompt(50, 32)                  # 4 full blocks at B=8
    req = shared + _prompt(51, 10)            # shared prefix + suffix

    async def cold():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(16, 64),
                        cache_dtype="float32", kv_block_size=8,
                        prefix_cache=False)
        out = await eng.generate(req, max_new_tokens=12)
        await eng.stop()
        return out

    async def warm():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(16, 64),
                        cache_dtype="float32", kv_block_size=8,
                        prefix_cache=True)
        await eng.generate(shared, max_new_tokens=4)
        out = await eng.generate(req, max_new_tokens=12)
        stats = eng.stats
        await eng.stop()
        return out, stats

    cold_out = asyncio.run(cold())
    hit_out, stats = asyncio.run(warm())
    assert hit_out["prefix_hit_tokens"] >= 24, hit_out
    assert stats["prefix_hit_tokens"] >= 24
    assert hit_out["tokens"] == cold_out["tokens"]
    assert cold_out["prefix_hit_tokens"] == 0


def test_block_aligned_stream_never_caches_unwritten_tail(tiny_model):
    """Regression: each decode step writes the PREVIOUS token's KV, so
    the final sampled token's position is never written. A stream
    ending exactly on a block boundary must NOT cache that last block
    — a later request extending the stream would attend one
    stale/zero KV position and silently diverge from a cold engine."""
    cfg, params = tiny_model
    # prompt 24 + 8 generated = 32 tokens = exactly 4 blocks at B=8;
    # position 31 (the last token's KV) is never written
    warm_prompt = _prompt(80, 24)

    async def warmed():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(16, 64),
                        cache_dtype="float32", kv_block_size=8,
                        prefix_cache=True)
        first = await eng.generate(warm_prompt, max_new_tokens=8)
        # follow-up turn: the full previous stream as prompt + more
        ext = warm_prompt + first["tokens"] + _prompt(81, 5)
        out = await eng.generate(ext, max_new_tokens=10)
        await eng.stop()
        return ext, out

    ext, hit_out = asyncio.run(warmed())
    # the hit must stop short of the unwritten final position: at most
    # 31 written tokens -> 3 full blocks = 24 hit tokens
    assert hit_out["prefix_hit_tokens"] <= 24, hit_out

    async def cold(prompt):
        eng = LLMEngine(cfg, params, max_slots=2, max_len=128,
                        prefill_buckets=(16, 64),
                        cache_dtype="float32", kv_block_size=8,
                        prefix_cache=False)
        out = await eng.generate(prompt, max_new_tokens=10)
        await eng.stop()
        return out

    cold_out = asyncio.run(cold(ext))
    assert hit_out["tokens"] == cold_out["tokens"]


def test_pool_pressure_parks_admits_and_evicts(tiny_model):
    """A pool smaller than the concurrent demand: admissions park
    (requests still ALL complete, in order of arrival), and cached
    chains are LRU-evicted to make room (llm_kv_blocks_evicted_total
    counts them)."""
    cfg, params = tiny_model
    # 2 slots, horizon 4 blocks per request, pool of 9 usable blocks:
    # two live requests fit, a third must wait for a free_seq
    eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                    prefill_buckets=(16,), cache_dtype="float32",
                    kv_block_size=8, kv_pool_blocks=10,
                    prefix_cache=True)

    async def go():
        outs = await asyncio.gather(*[
            eng.generate(_prompt(60 + i, 12), max_new_tokens=10)
            for i in range(6)])
        await eng.stop()
        return outs

    outs = asyncio.run(go())
    assert all(len(o["tokens"]) == 10 for o in outs)
    # finished chains were cached, then evicted under pressure
    assert eng._kv.evicted_total > 0
    assert eng._kv.used_blocks() == 0


def test_kv_accounting_gauges(tiny_model):
    """llm_kv_blocks_{used,cached} reflect the pool; the PR 11
    llm_kv_cache_bytes attribution now reports LIVE bytes (used +
    cached blocks), not the whole preallocated pool."""
    from ray_tpu.util import metrics as M
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                    prefill_buckets=(16,), cache_dtype="float32",
                    kv_block_size=8)

    async def go():
        await eng.generate(_prompt(70, 12), max_new_tokens=8)
        await eng.stop()

    asyncio.run(go())
    reg = M._REGISTRY
    used = sum(reg["llm_kv_blocks_used"]._values.values())
    cached = sum(reg["llm_kv_blocks_cached"]._values.values())
    assert used == 0                      # request finished
    assert cached >= 1                    # its prompt chain is cached
    bb = kc.kind_block_bytes(eng._pool)[kc.GLOBAL]
    kv_bytes = sum(reg["llm_kv_cache_bytes"]._values.values())
    assert kv_bytes == bb * cached


def test_copy_block_device_cow(tiny_model):
    """The COW divergence path at the device level: after copy_block,
    the clone holds the same bytes; writing the clone leaves the
    original untouched."""
    cfg, _ = tiny_model
    pool = kc.init_pool(cfg, 6, 8, jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1),
                          pool["k"][:, 1].shape)
    pool = {"k": pool["k"].at[:, 1].set(k), "v": pool["v"]}
    pool = kc.copy_block(pool, 1, 2)
    assert np.array_equal(np.asarray(pool["k"][:, 1]),
                          np.asarray(pool["k"][:, 2]))
    pool = {"k": pool["k"].at[:, 2, 0].add(1.0), "v": pool["v"]}
    assert not np.array_equal(np.asarray(pool["k"][:, 1]),
                              np.asarray(pool["k"][:, 2]))
