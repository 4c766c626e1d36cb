"""Tensor-parallel LLM serving, sampler filters, chunked prefill, stop
sequences.

The round-3 capability set: models larger than one chip serve through a
Mesh (reference: llm/_internal/serve/configs/llm_config.py:181-186
tensor_parallel_size), the sampler covers vLLM's temperature/top_p/top_k
/stop surface, and prompts longer than the largest prefill bucket stream
through chunked prefill.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.llm import LLMEngine
from ray_tpu.llm import model as lm
from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, ffn_dim=128, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _ref_greedy(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, jnp.array([toks], jnp.int32), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _tp_mesh(size):
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:size]), ("tensor",))


# --- tensor-parallel engine -------------------------------------------


def test_sharded_engine_matches_unsharded_greedy(tiny_model):
    """tp=2 over the virtual CPU mesh: params sharded Megatron-style,
    KV cache sharded on its kv-head dim — greedy decode must reproduce
    the single-device engine token for token."""
    cfg, params = tiny_model
    prompts = [[3, 7, 11], [9, 1], [5, 5, 5, 5]]
    refs = [_ref_greedy(cfg, params, p, 8) for p in prompts]

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32",
                        mesh=_tp_mesh(2))
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=8) for p in prompts])
        await eng.stop()
        return outs

    outs = asyncio.run(go())
    for o, ref in zip(outs, refs):
        assert o["tokens"] == ref


def test_sharded_params_and_cache_are_actually_sharded(tiny_model):
    """The mesh isn't decorative: weight shards must live on distinct
    devices with per-device shapes split over the tensor axis."""
    cfg, params = tiny_model
    mesh = _tp_mesh(2)
    sharded = lm.shard_params_for_serving(params, mesh, cfg)
    wq = sharded["layers"]["wq"]
    shards = wq.addressable_shards
    assert len({s.device for s in shards}) == 2
    assert all(s.data.shape[-1] == wq.shape[-1] // 2 for s in shards)
    # the TP engine's KV pool (layers, blocks, kvh, block, hd) splits
    # its kv heads over the same axis, on both devices
    eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                    prefill_buckets=(16,), cache_dtype="float32",
                    mesh=mesh)
    for key in ("k", "v"):
        kshards = eng._pool[key].addressable_shards
        assert len({s.device for s in kshards}) == 2
        assert all(s.data.shape[2] == cfg.n_kv_heads // 2
                   and s.data.shape[:2] == eng._pool[key].shape[:2]
                   for s in kshards)


def test_sharding_divisibility_validated(tiny_model):
    cfg, params = tiny_model   # n_kv_heads=2, not divisible by 8
    with pytest.raises(ValueError, match="not divisible"):
        lm.shard_params_for_serving(params, _tp_mesh(8), cfg)


# --- sampler ----------------------------------------------------------


def _np_filter_support(logits, temp, top_p=1.0, top_k=0):
    """Numpy reference: the SET of tokens the filtered distribution may
    emit (temperature -> top-k -> top-p order)."""
    z = logits.astype(np.float64) / max(temp, 1e-6)
    if top_k > 0:
        kth = np.sort(z)[::-1][min(top_k, len(z)) - 1]
        z = np.where(z < kth, -np.inf, z)
    if top_p < 1.0:
        zm = z - z[np.isfinite(z)].max()
        p = np.exp(zm)
        p /= p.sum()
        order = np.argsort(p)[::-1]
        sp = p[order]
        keep = (np.cumsum(sp) - sp) < top_p
        thresh = sp[keep].min()
        z = np.where(p < thresh, -np.inf, z)
    return set(np.nonzero(np.isfinite(z))[0].tolist())


def test_sample_topk_topp_parity_with_numpy():
    """Device sampler vs numpy reference: every drawn token must come
    from the reference's support set, and the full support must be
    reachable (1000 draws, 16-token vocab)."""
    rng = np.random.default_rng(0)
    logits_np = rng.normal(size=(3, 16)).astype(np.float32) * 2.0
    cases = [dict(top_p=1.0, top_k=3), dict(top_p=0.6, top_k=0),
             dict(top_p=0.7, top_k=5)]
    for case in cases:
        supports = [_np_filter_support(logits_np[i], 0.8, **case)
                    for i in range(3)]
        drawn = [set() for _ in range(3)]
        logits = jnp.asarray(logits_np)
        temps = jnp.full((3,), 0.8, jnp.float32)
        tp = jnp.full((3,), case["top_p"], jnp.float32)
        tk = jnp.full((3,), case["top_k"], jnp.int32)
        for it in range(1000):
            out = lm.sample(logits, temps, jax.random.PRNGKey(it),
                            tp, tk)
            for i in range(3):
                drawn[i].add(int(out[i]))
        for i in range(3):
            assert drawn[i] <= supports[i], \
                (case, i, drawn[i] - supports[i])
            assert drawn[i] == supports[i], \
                (case, i, supports[i] - drawn[i])


def test_sample_disabled_filters_match_plain():
    """top_p=1.0 / top_k=0 must be byte-identical to the unfiltered
    sampler (same key, same draw)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    temps = jnp.full((4,), 1.0, jnp.float32)
    key = jax.random.PRNGKey(7)
    plain = lm.sample(logits, temps, key)
    filtered = lm.sample(logits, temps, key,
                         jnp.ones((4,), jnp.float32),
                         jnp.zeros((4,), jnp.int32))
    assert plain.tolist() == filtered.tolist()


def test_greedy_unaffected_by_filters():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    temps = jnp.zeros((2,), jnp.float32)
    out = lm.sample(logits, temps, jax.random.PRNGKey(0),
                    jnp.full((2,), 0.3, jnp.float32),
                    jnp.full((2,), 2, jnp.int32))
    assert out.tolist() == jnp.argmax(logits, -1).tolist()


def test_engine_topk_restricts_outputs(tiny_model):
    """Engine-level: with top_k=2 every generated token is one of the
    two highest-logit continuations of its step (checked via the
    step-by-step full forward)."""
    cfg, params = tiny_model

    async def go():
        eng = LLMEngine(cfg, params, max_slots=1, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32",
                        seed=3)
        out = await eng.generate([3, 1, 4], max_new_tokens=10,
                                 temperature=1.0, top_k=2)
        await eng.stop()
        return out

    out = asyncio.run(go())
    toks = [3, 1, 4]
    for t in out["tokens"]:
        logits = llama.forward(params, jnp.array([toks], jnp.int32), cfg)
        top2 = set(np.argsort(np.asarray(logits[0, -1]))[-2:].tolist())
        assert t in top2, (t, top2)
        toks.append(t)


# --- stop sequences ---------------------------------------------------


def test_stop_sequence_trims_and_finishes(tiny_model):
    cfg, params = tiny_model
    ref = _ref_greedy(cfg, params, [4, 8], 10)
    # stop on a 2-token subsequence of the greedy continuation
    stop = [ref[2:4]]

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32")
        stopped = await eng.generate([4, 8], max_new_tokens=10,
                                     stop=stop)
        plain = await eng.generate([4, 8], max_new_tokens=10)
        await eng.stop()
        return stopped, plain

    stopped, plain = asyncio.run(go())
    assert plain["tokens"] == ref
    assert stopped["tokens"] == ref[:2]   # matched suffix trimmed


# --- chunked prefill --------------------------------------------------


def test_chunked_prefill_matches_full_forward(tiny_model):
    """A prompt longer than the largest bucket (3.5 buckets here) must
    produce exactly the same greedy continuation as the step-by-step
    full forward — chunk boundaries are invisible."""
    cfg, params = tiny_model
    prompt = [int(x) for x in
              np.random.default_rng(5).integers(1, 100, size=28)]
    ref = _ref_greedy(cfg, params, prompt, 6)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32")
        out = await eng.generate(prompt, max_new_tokens=6)
        await eng.stop()
        return out

    out = asyncio.run(go())
    assert out["tokens"] == ref


def test_chunked_prefill_sharded(tiny_model):
    """Chunked prefill under tensor parallelism: the accumulator is
    sharded on its kv-head dim and the result still matches."""
    cfg, params = tiny_model
    prompt = list(range(1, 21))
    ref = _ref_greedy(cfg, params, prompt, 5)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=1, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32",
                        mesh=_tp_mesh(2))
        out = await eng.generate(prompt, max_new_tokens=5)
        await eng.stop()
        return out

    assert asyncio.run(go())["tokens"] == ref


def test_chunked_prefill_non_aligned_max_len(tiny_model):
    """max_len NOT a multiple of the largest bucket + a prompt close to
    max_len: the padded final chunk must not overrun the accumulator
    (dynamic_update_slice clamps the start on overrun and silently
    corrupts earlier chunks' KV — caught in round-3 review)."""
    cfg, params = tiny_model
    prompt = [int(x) for x in
              np.random.default_rng(11).integers(1, 100, size=26)]
    ref = _ref_greedy(cfg, params, prompt, 4)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=1, max_len=30,
                        prefill_buckets=(8,), cache_dtype="float32")
        out = await eng.generate(prompt, max_new_tokens=4)
        await eng.stop()
        return out

    assert asyncio.run(go())["tokens"] == ref


def test_pd_chunked_non_aligned_max_len(tiny_model):
    """Same overrun guard on the disaggregated prefill tier."""
    from ray_tpu.llm.pd import PrefillEngine
    cfg, params = tiny_model
    prompt = list(range(1, 27))
    ref = _ref_greedy(cfg, params, prompt, 4)

    async def go():
        pre = PrefillEngine(cfg, params, prefill_buckets=(8,),
                            max_len=30, cache_dtype="float32")
        shipped = pre.prefill(prompt)
        assert shipped["k"].shape[1] <= 30
        eng = LLMEngine(cfg, params, max_slots=1, max_len=30,
                        prefill_buckets=(8,), cache_dtype="float32")
        out = await eng.generate_prefilled(prompt, shipped,
                                           max_new_tokens=4)
        await eng.stop()
        return out

    assert asyncio.run(go())["tokens"] == ref
