"""Paged-attention decode bench: gather-view vs fused kernel A/B,
plus the tensor-parallel paged prefix-reuse row.

Three rows, all direct-engine (no HTTP — the decode loop is the thing
under test):

1. ``decode``: long-context decode TPOT with ``kv_impl=gather`` (the
   materialized-view baseline) vs ``kv_impl=auto`` (resolves to the
   fused block-table kernel on a real TPU backend, to gather on CPU —
   re-run this script unchanged on a TPU box for the real A/B). The
   per-step HBM copy the kernel removes is also committed as bytes.
2. ``kernel_parity``: the equal-logits evidence — the same prompts
   decoded with ``kv_impl=paged_flash`` (pallas interpreter off-TPU)
   must emit exactly the gather baseline's tokens.
3. ``tp_prefix``: tensor-parallel (tp=2) paged engine with prefix
   reuse — warm (shared-prefix hit) vs cold TTFT, hit tokens > 0,
   tokens equal.

Results land under SERVE_BENCH.json ``paged_attn`` and
LONGCTX_BENCH.json ``paged_attn``.

Run from the repo root: python scripts/paged_attn_bench.py
(CPU-friendly; every row stamps the device it ran on).
"""

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def _prompt(seed, n):
    return [int(x) for x in
            np.random.default_rng(seed).integers(1, 127, n)]


def _engine(cfg, params, **kw):
    from ray_tpu.llm.engine import LLMEngine
    base = dict(max_slots=4, cache_dtype="float32",
                prefix_cache=False)
    base.update(kw)
    return LLMEngine(cfg, params, **base)


def _gen_all(eng, prompts, max_new):
    async def go():
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=max_new) for p in prompts])
        await eng.stop()
        return outs
    return asyncio.run(go())


def _decode_row(cfg, params, impl, prompts, max_new, runs, **kw):
    """Median decode TPOT (ms/token) over ``runs`` fresh engines —
    TTFT (prefill) excluded: TPOT = (total - ttft) / (tokens - 1)."""
    tpots, toks = [], None
    for _ in range(runs):
        eng = _engine(cfg, params, kv_impl=impl, **kw)
        t0 = time.monotonic()
        outs = _gen_all(eng, prompts, max_new)
        total = time.monotonic() - t0
        ttft = max(o["ttft_s"] for o in outs)
        steps = max_new - 1
        tpots.append((total - ttft) / steps * 1000.0)
        toks = [o["tokens"] for o in outs]
    return {"impl": impl, "resolved": eng._kv_impl,
            "tpot_ms": round(statistics.median(tpots), 3)}, toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--long-prompt", type=int, default=512)
    ap.add_argument("--max-new", type=int, default=48)
    args = ap.parse_args()

    import jax
    from ray_tpu.llm import kvcache
    from ray_tpu.models import llama

    device = os.environ.get("JAX_PLATFORMS",
                            jax.devices()[0].platform)
    cfg = llama.tiny(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, ffn_dim=128, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    # --- row 1: long-context decode TPOT, gather vs auto ------------
    long_kw = dict(max_len=args.long_prompt + args.max_new + 16,
                   prefill_buckets=(256,), kv_block_size=16)
    prompts = [_prompt(i, args.long_prompt) for i in range(4)]
    base, base_toks = _decode_row(cfg, params, "gather", prompts,
                                  args.max_new, args.runs, **long_kw)
    auto, auto_toks = _decode_row(cfg, params, "auto", prompts,
                                  args.max_new, args.runs, **long_kw)
    assert auto_toks == base_toks, "auto impl moved tokens"
    decode = {"gather": base, "auto": auto,
              "prompt_tokens": args.long_prompt,
              "max_new": args.max_new, "slots": len(prompts)}
    print(f"# decode: {json.dumps(decode)}", file=sys.stderr)

    # --- row 2: kernel parity at equal logits (small: interpreter) --
    par_kw = dict(max_len=64, prefill_buckets=(16,), kv_block_size=8)
    par_prompts = [_prompt(50 + i, 12) for i in range(2)]
    g_out = _gen_all(_engine(cfg, params, kv_impl="gather", **par_kw),
                     par_prompts, 16)
    k_eng = _engine(cfg, params, kv_impl="paged_flash", **par_kw)
    k_resolved = k_eng._kv_impl
    k_interp = k_eng._kv_interpret
    k_out = _gen_all(k_eng, par_prompts, 16)
    parity = {"tokens_equal":
              [o["tokens"] for o in k_out] ==
              [o["tokens"] for o in g_out],
              "impl": k_resolved, "interpret": bool(k_interp)}
    print(f"# kernel_parity: {json.dumps(parity)}", file=sys.stderr)
    assert parity["tokens_equal"], "kernel diverged from gather"

    # --- row 3: tp=2 paged prefix reuse ------------------------------
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
    tp_kw = dict(max_len=512, prefill_buckets=(64, 256),
                 kv_block_size=16, mesh=mesh)
    shared = _prompt(90, 192)
    reqs = [shared + _prompt(91 + i, 8) for i in range(3)]

    def tp_run(prefix_cache):
        eng = _engine(cfg, params, kv_impl="gather",
                      prefix_cache=prefix_cache, **tp_kw)
        assert eng._paged, "TP engine must run paged"

        async def go():
            if prefix_cache:
                await eng.generate(shared, max_new_tokens=4)
            outs = []
            for r in reqs:        # serial: TTFT unpolluted by queueing
                outs.append(await eng.generate(r, max_new_tokens=16))
            stats = eng.stats
            await eng.stop()
            return outs, stats
        return asyncio.run(go())

    cold_outs, _ = tp_run(False)
    warm_outs, warm_stats = tp_run(True)
    tp_prefix = {
        "ttft_ms_cold": round(statistics.median(
            o["ttft_s"] for o in cold_outs) * 1000.0, 2),
        "ttft_ms_hit": round(statistics.median(
            o["ttft_s"] for o in warm_outs) * 1000.0, 2),
        "hit_tokens": int(warm_stats["prefix_hit_tokens"]),
        "tokens_equal": [o["tokens"] for o in warm_outs] ==
                        [o["tokens"] for o in cold_outs],
        "tp": 2}
    print(f"# tp_prefix: {json.dumps(tp_prefix)}", file=sys.stderr)
    assert tp_prefix["hit_tokens"] > 0
    assert tp_prefix["tokens_equal"]

    caveat = None
    if kvcache.resolve_attn_impl("auto") == "gather":
        caveat = ("CPU host: auto resolves to the gather view, so the "
                  "decode A/B is gather-vs-gather and the fused-kernel "
                  "row is PARITY evidence only (pallas interpreter is "
                  "not a timing proxy). Re-run unchanged on a TPU box "
                  "for the real kernel TPOT.")
    doc = {"decode": decode, "kernel_parity": parity,
           "tp_prefix": tp_prefix, "device": device,
           "model": "tiny 64d/2L fp32", "caveat": caveat}
    print(json.dumps(doc, indent=1))

    for path, key, row in (
            ("SERVE_BENCH.json", "paged_attn", doc),
            ("LONGCTX_BENCH.json", "paged_attn",
             {"prompt_tokens": args.long_prompt,
              "decode_tpot_ms_gather": base["tpot_ms"],
              "decode_tpot_ms_auto": auto["tpot_ms"],
              "auto_resolved": auto["resolved"],
              "kernel_tokens_equal": parity["tokens_equal"],
              "tp_prefix_hit_ttft_ms": tp_prefix["ttft_ms_hit"],
              "tp_prefix_cold_ttft_ms": tp_prefix["ttft_ms_cold"],
              "device": device, "caveat": caveat})):
        try:
            with open(path) as f:
                bench = json.load(f)
        except FileNotFoundError:
            bench = {}
        bench[key] = row
        with open(path, "w") as f:
            json.dump(bench, f, indent=1)
            f.write("\n")
        print(f"# wrote {path} {key} key", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
