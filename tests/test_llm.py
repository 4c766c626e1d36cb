"""LLM serving: cache-aware decode, continuous batching, serve integration.

Reference shape: python/ray/llm/tests/serve/... (engine-level generate
semantics + serve deployment wiring), with correctness pinned against
the training-side full forward instead of a vendored engine.
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


def test_cached_decode_matches_full_forward(tiny_model):
    """The serving forwards without an engine: lm.prefill into a
    bucket, scatter_bucket into the KV pool's blocks, then one
    paged_decode_logits step and a greedy paged_decode_steps block
    through slot 2's block table reproduce the training model's full
    forward."""
    from ray_tpu.llm import kvcache
    cfg, params = tiny_model
    prompt = [3, 7, 11, 19, 2]
    ref = _ref_greedy(cfg, params, prompt, 6)

    bucket, block, slots = 8, 4, 4
    logits, kv = lm.prefill(params, jnp.pad(jnp.array(prompt, jnp.int32),
                                            (0, 3)),
                            jnp.int32(len(prompt)), cfg, bucket)
    pool = kvcache.init_pool(cfg, 9, block, jnp.float32)
    tables = np.full((slots, 8), kvcache.TRASH, np.int32)
    tables[2, :3] = [5, 1, 7]               # 12 positions for slot 2
    pool = kvcache.scatter_bucket(pool, kv, jnp.asarray(tables[2, :2]), 2)
    first = int(jnp.argmax(logits))
    toks = jnp.zeros((slots,), jnp.int32).at[2].set(first)
    at = jnp.zeros((slots,), jnp.int32).at[2].set(len(prompt))
    step = kvcache.paged_decode_logits(
        params, pool, jnp.asarray(tables), at, toks, cfg, impl="gather")
    assert [first, int(jnp.argmax(step[2]))] == ref[:2]
    sampled, pool = kvcache.paged_decode_steps(
        params, pool, jnp.asarray(tables), at, toks,
        jnp.zeros((slots,), jnp.float32),               # greedy
        jax.random.PRNGKey(0), cfg, 5, impl="gather")
    assert [first] + [int(t) for t in sampled[:, 2]] == ref


def test_continuous_batching_matches_sequential(tiny_model):
    """6 concurrent requests through 2 slots: slot reuse + interleaved
    decode must reproduce per-request greedy outputs exactly."""
    cfg, params = tiny_model
    prompts = [[i + 1, 2 * i + 3, 5] for i in range(6)]
    refs = [_ref_greedy(cfg, params, p, 8) for p in prompts]

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32")
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=8) for p in prompts])
        await eng.stop()
        return outs

    outs = asyncio.run(go())
    for o, ref in zip(outs, refs):
        assert o["tokens"] == ref
        assert o["ttft_s"] >= 0


def test_admission_is_not_blocked_by_long_request(tiny_model):
    """Continuous batching: a short request admitted while a long one
    decodes must finish long before it (token-level joins)."""
    cfg, params = tiny_model

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=256,
                        prefill_buckets=(8,), cache_dtype="float32")
        long_task = asyncio.ensure_future(
            eng.generate([5, 6, 7], max_new_tokens=120))
        await asyncio.sleep(0.3)  # long request is mid-decode
        short = await eng.generate([9, 9], max_new_tokens=3)
        assert not long_task.done(), \
            "long request finished too fast to be a valid probe"
        long = await long_task
        await eng.stop()
        return short, long

    short, long = asyncio.run(go())
    assert len(short["tokens"]) == 3
    assert len(long["tokens"]) == 120


def test_eos_and_temperature(tiny_model):
    cfg, params = tiny_model

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32",
                        seed=7)
        greedy = await eng.generate([4, 8], max_new_tokens=10)
        eos = await eng.generate([4, 8], max_new_tokens=10,
                                 eos_id=greedy["tokens"][0])
        sampled = await eng.generate([4, 8], max_new_tokens=10,
                                     temperature=1.5)
        await eng.stop()
        return greedy, eos, sampled

    greedy, eos, sampled = asyncio.run(go())
    assert eos["tokens"] == greedy["tokens"][:1]
    assert len(sampled["tokens"]) == 10


def test_mixed_precision_cache(tiny_model):
    """float32 params with the default bfloat16 KV cache must work
    (prefill KV is cast into the cache dtype)."""
    cfg, params = tiny_model

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,))  # default bf16 cache
        out = await eng.generate([3, 9, 27], max_new_tokens=6)
        await eng.stop()
        return out

    out = asyncio.run(go())
    assert len(out["tokens"]) == 6


def test_prompt_validation(tiny_model):
    cfg, params = tiny_model

    async def go():
        eng = LLMEngine(cfg, params, max_slots=1, max_len=16,
                        prefill_buckets=(8,), cache_dtype="float32")
        # prompts past the largest bucket now CHUNK (no bucket cap);
        # only max_len bounds them
        with pytest.raises(ValueError, match="max_len"):
            await eng.generate(list(range(99)), max_new_tokens=1)
        with pytest.raises(ValueError, match="max_len"):
            await eng.generate([1, 2, 3], max_new_tokens=64)
        with pytest.raises(ValueError, match="max_new_tokens"):
            await eng.generate([1, 2], max_new_tokens=0)
        with pytest.raises(ValueError, match="top_p"):
            await eng.generate([1, 2], max_new_tokens=1, top_p=0.0)
        with pytest.raises(ValueError, match="top_k"):
            await eng.generate([1, 2], max_new_tokens=1, top_k=-2)
        with pytest.raises(ValueError, match="stop"):
            await eng.generate([1, 2], max_new_tokens=1, stop=[[]])
        await eng.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            await eng.generate([1, 2], max_new_tokens=1)

    asyncio.run(go())


def test_engine_phase_histograms(tiny_model):
    """Device-time telemetry: one generate populates the queue / device
    TTFT / wall TTFT / TPOT histograms, and the block_until_ready-
    bounded device TTFT can never exceed the wall TTFT."""
    from ray_tpu.util import metrics
    cfg, params = tiny_model

    def totals():
        out = {}
        for name in ("llm_queue_s", "llm_ttft_device_s",
                     "llm_ttft_wall_s", "llm_tpot_s", "llm_batch_size"):
            h = metrics._REGISTRY.get(name)
            if isinstance(h, metrics.Histogram):
                out[name] = (sum(sum(c) for c in h._counts.values()),
                             sum(h._sums.values()))
            else:
                out[name] = (0, 0.0)
        return out

    before = totals()

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32")
        out = await eng.generate([2, 4, 6], max_new_tokens=5)
        stats = eng.stats
        await eng.stop()
        return out, stats

    out, stats = asyncio.run(go())
    assert len(out["tokens"]) == 5
    # the legacy scalar surface survives the histogram refactor
    assert stats["requests"] == 1 and stats["tokens_generated"] == 5
    assert stats["ttft_count"] == 1

    after = totals()
    for name in ("llm_queue_s", "llm_ttft_device_s", "llm_ttft_wall_s",
                 "llm_tpot_s", "llm_batch_size"):
        assert after[name][0] > before[name][0], \
            f"{name} not observed"
    d_dev = after["llm_ttft_device_s"][1] - before["llm_ttft_device_s"][1]
    d_wall = after["llm_ttft_wall_s"][1] - before["llm_ttft_wall_s"][1]
    assert 0 <= d_dev <= d_wall, (d_dev, d_wall)


def test_llm_metrics_pushed_to_head(monkeypatch):
    """Acceptance: after one generate through a serve replica (its own
    worker process), the head /metrics endpoint serves the replica's
    llm_ttft histograms, worker-labelled, with device <= wall."""
    import time as _t
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.config import Config
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    monkeypatch.setenv("RAY_TPU_METRICS_EXPORT_INTERVAL_S", "0.3")
    cfg = Config.from_env(metrics_port=0,
                          metrics_export_interval_s=0.3)
    c = Cluster(config=cfg)
    agent = c.add_node(num_cpus=4)
    try:
        ray_tpu.init(address=c.address, config=cfg)
        llm_cfg = LLMConfig(
            model="tiny",
            model_overrides=dict(vocab_size=128, dim=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, ffn_dim=128,
                                 dtype="float32", logits_dtype="float32",
                                 attn_impl="reference"),
            max_slots=2, max_len=64, prefill_buckets=(8,),
            cache_dtype="float32")
        h = serve.run(build_llm_deployment(llm_cfg), name="llm")
        r = ray_tpu.get(h.generate.remote([1, 2], max_new_tokens=4),
                        timeout=180)
        assert len(r["tokens"]) == 4

        addr = agent.metrics_addr

        def pushed_sums(text, name):
            """Sum of <name>_sum samples that carry a worker label —
            i.e. series pushed from worker processes, not local ones."""
            total, found = 0.0, False
            for line in text.splitlines():
                if line.startswith(name + "_sum{") \
                        and 'worker="' in line:
                    total += float(line.rsplit(" ", 1)[1])
                    found = True
            return found, total

        deadline = _t.monotonic() + 60
        fd = fw = False
        dev = wall = 0.0
        while _t.monotonic() < deadline and not (fd and fw):
            with urllib.request.urlopen(
                    f"http://{addr[0]}:{addr[1]}/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
            fd, dev = pushed_sums(text, "llm_ttft_device_s")
            fw, wall = pushed_sums(text, "llm_ttft_wall_s")
            _t.sleep(0.4)
        assert fd and fw, "replica histograms never reached the head"
        assert 0 <= dev <= wall + 1e-9, (dev, wall)
        fq, _ = pushed_sums(text, "llm_queue_s")
        assert fq, "llm_queue_s not pushed"
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
        c.shutdown()
        from ray_tpu.util import metrics as _m
        _m.reset()


def test_serve_llm_deployment():
    """End-to-end: LLM app on serve, called via handle from the driver."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    ray_tpu.init(num_cpus=4)
    try:
        cfg = LLMConfig(
            model="tiny",
            model_overrides=dict(vocab_size=128, dim=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, ffn_dim=128,
                                 dtype="float32", logits_dtype="float32",
                                 attn_impl="reference"),
            max_slots=2, max_len=64, prefill_buckets=(8,),
            cache_dtype="float32")
        h = serve.run(build_llm_deployment(cfg), name="llm")
        outs = [h.generate.remote([i + 1, 5], max_new_tokens=6)
                for i in range(4)]
        for o in outs:
            r = ray_tpu.get(o, timeout=180)
            assert len(r["tokens"]) == 6
        stats = ray_tpu.get(h.stats.remote(), timeout=60)
        assert stats["requests"] >= 4
        assert stats["tokens_generated"] >= 24
        serve.shutdown()
    finally:
        ray_tpu.shutdown()


def test_serve_llm_streaming():
    """Tokens stream out of the replica as they are produced: the first
    token is observed before the last one is produced, and the streamed
    sequence equals the non-streamed greedy result. The order is read
    off the engine's own token counter, asked for once the first token
    is in hand: whatever it answers was produced by then at the latest,
    so an answer short of the whole generation puts the first token's
    arrival before the last token's production. (It was a share of
    wall time, first token before 0.8 of the total, which the delivery
    latency of a loaded host can eat: red in PR 29's tier-1 run.)"""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import (LLMConfig, build_llm_deployment,
                                   stream_generate)

    ray_tpu.init(num_cpus=4)
    try:
        cfg = LLMConfig(
            model="tiny",
            model_overrides=dict(vocab_size=128, dim=64, n_layers=2,
                                 n_heads=4, n_kv_heads=2, ffn_dim=128,
                                 dtype="float32", logits_dtype="float32",
                                 attn_impl="reference"),
            max_slots=2, max_len=128, prefill_buckets=(8,),
            cache_dtype="float32", steps_per_sync=1)
        h = serve.run(build_llm_deployment(cfg, name="LLMStream"),
                      name="llmstream")
        n = 100
        ref = ray_tpu.get(h.generate.remote([7, 3], max_new_tokens=n),
                          timeout=180)["tokens"]
        # the stream path's first use pays a start-up of its own
        assert list(stream_generate(h, [7, 3], max_new_tokens=2)) == ref[:2]
        before = ray_tpu.get(h.stats.remote(),
                             timeout=60)["tokens_generated"]

        asked = None
        got = []
        for tok in stream_generate(h, [7, 3], max_new_tokens=n):
            if asked is None:
                asked = h.stats.remote()    # sent after the first token
            got.append(tok)
        assert got == ref
        produced = ray_tpu.get(asked, timeout=60)["tokens_generated"]
        assert 1 <= produced - before < n, (produced - before, n)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
