"""LLM serving benchmark: throughput + TTFT of the continuous-batching
engine, in the one process that holds the chip.

Run: python scripts/llm_bench.py [--model tiny|llama2_7b] [--requests N]
Prints one JSON line, stamped with the device the engine reports.
"""

import argparse
import asyncio
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bench340m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps-per-sync", type=int, default=32)
    args = ap.parse_args()

    import jax

    from ray_tpu.llm import LLMEngine
    from ray_tpu.models import llama
    from ray_tpu.util import jaxenv

    jaxenv.setup_compile_cache()

    if args.model == "bench340m":
        cfg = llama.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=16, n_heads=16,
            n_kv_heads=16, ffn_dim=2816, max_seq_len=1024,
            dtype="bfloat16", logits_dtype="float32",
            attn_impl="reference")
    else:
        cfg = getattr(llama, args.model)(
            dtype="bfloat16", logits_dtype="float32",
            attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=args.slots,
                        max_len=1024, prefill_buckets=(64, 256),
                        steps_per_sync=args.steps_per_sync)
        await eng.generate([1, 2, 3], max_new_tokens=args.steps_per_sync + 1)
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(1, cfg.vocab_size - 1,
                                     size=args.prompt_len))
                   for _ in range(args.requests)]
        t0 = time.time()
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=args.max_new)
            for p in prompts])
        dt = time.time() - t0
        toks = sum(len(o["tokens"]) for o in outs)
        ttfts = sorted(o["ttft_s"] for o in outs)
        stats = eng.stats
        await eng.stop()
        print(json.dumps({
            "metric": "llm_serve_throughput",
            "value": round(toks / dt, 1), "unit": "tok/s",
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1000, 1),
            "ttft_max_ms": round(ttfts[-1] * 1000, 1),
            "requests": args.requests, "max_new": args.max_new,
            "slots": args.slots, "steps_per_sync": args.steps_per_sync,
            "model_params_m": round(cfg.num_params() / 1e6, 1),
            "device": stats["device"],
            "engine": {k: stats[k] for k in
                       ("kv_impl", "kv_interpret", "prefill_impl")},
        }))

    asyncio.run(go())


if __name__ == "__main__":
    main()
