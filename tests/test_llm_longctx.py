"""Long-context serving: chunked prefill at multi-k prompt lengths,
flash-kernel parity in the serving forward pass, and the block size
the one KV cache needs.

Reference capability: vLLM long-context serving (paged KV + chunked
prefill) behind ray.serve.llm; here prompts stream through
lm.prefill_chunk into the engine's paged pool.
"""

import asyncio

import numpy as np
import pytest

import jax


def _tiny(**kw):
    from ray_tpu.models import llama
    base = dict(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, ffn_dim=128, dtype="float32",
                logits_dtype="float32", attn_impl="reference")
    base.update(kw)
    return llama.tiny(**base)


def _params(cfg, seed=0):
    from ray_tpu.models import llama
    return llama.init_params(jax.random.PRNGKey(seed), cfg)


def _engine(cfg, params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("steps_per_sync", 4)
    return LLMEngine(cfg, params, **kw)


@pytest.mark.parametrize("how", ["kwarg_zero", "kwarg_negative",
                                 "config_zero"])
def test_block_size_below_one_is_refused(how, monkeypatch):
    """The paged pool is the engine's only KV cache: a block size of 0
    (once the switch to a second, slot-shaped cache) or less is
    refused at construction, by name, whether it comes from the kwarg
    or from Config.kvcache_block_size."""
    from ray_tpu import config as rconfig
    cfg = _tiny()
    kw = {}
    if how == "config_zero":
        monkeypatch.setattr(rconfig.get_config(), "kvcache_block_size", 0)
    else:
        kw["kv_block_size"] = 0 if how == "kwarg_zero" else -16
    with pytest.raises(ValueError, match="paged pool"):
        _engine(cfg, _params(cfg), max_len=256, prefill_buckets=(64,),
                **kw)


def test_long_prompt_chunked_equals_single_bucket():
    """A 1.3k-token prompt streamed through 256-sized chunks decodes
    the same greedy tokens as one big-bucket prefill — the chunked
    path is exact, not approximate."""
    cfg = _tiny()
    params = _params(cfg)
    prompt = [int(x) for x in
              np.random.default_rng(1).integers(1, 127, 1300)]

    chunked = _engine(cfg, params, max_len=2048,
                      prefill_buckets=(256,))
    direct = _engine(cfg, params, max_len=2048,
                     prefill_buckets=(2048,))

    async def gen(eng):
        return await eng.generate(prompt, max_new_tokens=24,
                                  temperature=0.0)

    a = asyncio.run(gen(chunked))["tokens"]
    b = asyncio.run(gen(direct))["tokens"]
    assert a == b, (a, b)


def test_flash_serving_prefill_matches_reference():
    """The pallas flash kernel (interpret mode on CPU) in the serving
    prefill produces the same greedy decode as the XLA reference —
    including the chunked path with its absolute causal offset."""
    ref_cfg = _tiny(attn_impl="reference")
    fl_cfg = _tiny(attn_impl="flash_interpret")
    params = _params(ref_cfg)
    prompt = [int(x) for x in
              np.random.default_rng(2).integers(1, 127, 200)]

    async def gen(cfg, buckets):
        eng = _engine(cfg, params, max_len=512,
                      prefill_buckets=buckets)
        return (await eng.generate(prompt, max_new_tokens=16,
                                   temperature=0.0))["tokens"]

    ref = asyncio.run(gen(ref_cfg, (256,)))       # chunked (200<256? no:
    # 200 fits bucket 256 -> single prefill) and a chunked variant:
    ref_chunked = asyncio.run(gen(ref_cfg, (128,)))   # 2 chunks
    fl = asyncio.run(gen(fl_cfg, (256,)))
    fl_chunked = asyncio.run(gen(fl_cfg, (128,)))
    assert ref == ref_chunked
    assert fl == ref, (fl, ref)
    assert fl_chunked == ref, (fl_chunked, ref)


def test_default_serve_config_is_long_context():
    from ray_tpu.serve.llm import LLMConfig
    cfg = LLMConfig()
    assert cfg.max_len >= 8192
    assert max(cfg.prefill_buckets) >= 2048
