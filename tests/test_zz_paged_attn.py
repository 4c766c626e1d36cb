"""Paged-attention decode kernel (ops/pallas/paged_attention.py) and
its serving integration: kernel-vs-gather parity (allclose on random
values, BITWISE on integer constructions), COW-forked tables diverging
mid-decode, tensor-parallel paged engines, chunk-grid-aligned prefix
hits, and the paged_attn_impl / paged_attn_interpret Config knobs.

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


def _rand_case(seed, *, b, w, bs, kvh, g, hd, nb):
    """Random q/pool + disjoint per-slot block tables."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(nb, kvh, bs, hd))
                    .astype(np.float32))
    v = jnp.asarray(rng.normal(size=(nb, kvh, bs, hd))
                    .astype(np.float32))
    tables = jnp.asarray(
        (1 + np.arange(b * w)).reshape(b, w).astype(np.int32))
    return q, k, v, tables


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


# --- impl resolution + Config knobs -----------------------------------


def test_resolve_attn_impl():
    # auto resolves by backend: gather on the CPU tier-1 backend
    assert kc.resolve_attn_impl("auto") == "gather"
    assert kc.resolve_attn_impl("gather") == "gather"
    assert kc.resolve_attn_impl("paged_flash") == "paged_flash"
    with pytest.raises(ValueError, match="auto|paged_flash|gather"):
        kc.resolve_attn_impl("flash")


def test_config_knobs_drive_engine_impl(tiny_model, monkeypatch):
    """paged_attn_impl / paged_attn_interpret (Config, overridable via
    RAY_TPU_PAGED_ATTN_IMPL / RAY_TPU_PAGED_ATTN_INTERPRET) select the
    decode attention path when the kv_impl kwarg is left at None; off
    TPU the engine force-enables the interpreter for the kernel impl."""
    from ray_tpu.config import get_config
    cfg_obj = get_config()
    cfg, params = tiny_model
    kw = dict(max_slots=2, max_len=32, prefill_buckets=(8,),
              cache_dtype="float32", kv_block_size=8)

    monkeypatch.setattr(cfg_obj, "paged_attn_impl", "gather")
    eng = LLMEngine(cfg, params, **kw)
    assert eng._paged and eng._kv_impl == "gather"
    assert not eng._kv_interpret
    assert eng.stats["kv_impl"] == "gather"

    monkeypatch.setattr(cfg_obj, "paged_attn_impl", "paged_flash")
    monkeypatch.setattr(cfg_obj, "paged_attn_interpret", False)
    eng = LLMEngine(cfg, params, **kw)
    assert eng._kv_impl == "paged_flash"
    assert eng._kv_interpret          # forced: no TPU backend here

    # the explicit kwarg beats the Config knob
    eng = LLMEngine(cfg, params, kv_impl="gather", **kw)
    assert eng._kv_impl == "gather"


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
        assert eng._paged
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
        assert eng._paged and eng._kv_impl == "paged_flash"
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
