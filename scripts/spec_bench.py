"""Speculative-decoding A/B bench: prompt-lookup drafts vs vanilla greedy.

Interleaves spec-on and spec-off runs of the SAME greedy requests
(alternating order per repetition so drift cancels) over two workloads:

- high-hit: second-turn continuations — the prompt is a first turn
  (periodic 64-token pattern) plus the model's OWN 96-token greedy
  output, and the engine generates the next 96 tokens. The stream the
  model settles into is in the prompt, so prompt-lookup drafts it —
  the agentic/multi-turn "the answer quotes the context" shape;
- low-hit: uniform-random prompts where n-gram drafting is hopeless —
  measures the overhead bound the accept-rate backoff must enforce.

Reports decode-phase TPOT (first token excluded via generate_stream, so
prefill cost doesn't dilute the ratio), the drafter accept rate from the
llm_spec_tokens_total counters, and exact-match parity of every token
stream. Writes the "spec" row of SERVE_BENCH.json with --write.

Run: python scripts/spec_bench.py [--write] [--spec-k 7] [--max-new 96]
CPU honesty: on CPU the verify forward costs roughly one decode step, so
the TPOT ratio ~= emitted tokens per forward. On a real TPU the verify
matmul is wider but the MXU is idle at decode widths anyway — the ratio
should hold or improve; the low-hit bound is the fragile side and is
what the backoff protects.
"""

import argparse
import asyncio
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

HIGH_HIT_SEEDS = (22, 15, 16, 7)
LOW_HIT_SEEDS = (0, 5, 11, 13)


def _periodic_prompt(seed, n=64, period=16):
    pat = list(np.random.default_rng(seed).integers(1, 127, period))
    return [int(t) for t in (pat * (n // period + 1))[:n]]


def _random_prompt(seed, n=64):
    return [int(t) for t in
            np.random.default_rng(1000 + seed).integers(1, 127, n)]


def _spec_counters():
    from ray_tpu.util import metrics as m
    c = m._REGISTRY.get("llm_spec_tokens_total")
    if c is None:
        return {}
    return {dict(k).get("kind"): v for k, v in c._values.items()}


async def _timed_request(eng, prompt, max_new):
    """(tokens, decode-phase TPOT ms): wall from first token to last,
    over the other max_new-1 tokens."""
    toks = []
    t_first = None
    async for t in eng.generate_stream(prompt, max_new_tokens=max_new):
        if t_first is None:
            t_first = time.monotonic()
        toks.append(t)
    dt = time.monotonic() - t_first
    return toks, dt * 1000.0 / max(1, len(toks) - 1)


async def _bench_workload(make_engine, prompts, max_new, reps):
    """Interleaved A/B over one workload. Returns the summary dict."""
    van = make_engine(spec=False)
    spc = make_engine(spec=True)
    # warm both engines' compile caches outside the timed region
    await van.generate(prompts[0], max_new_tokens=max_new)
    await spc.generate(prompts[0], max_new_tokens=max_new)

    tpot_van, tpot_spc = [], []
    match = True
    c0 = _spec_counters()
    for rep in range(reps):
        for p in prompts:
            order = ((van, tpot_van), (spc, tpot_spc))
            if rep % 2:
                order = order[::-1]
            streams = {}
            for eng, sink in order:
                toks, tpot = await _timed_request(eng, p, max_new)
                sink.append(tpot)
                streams[id(eng)] = toks
            match &= streams[id(van)] == streams[id(spc)]
    c1 = _spec_counters()
    drafted = c1.get("drafted", 0) - c0.get("drafted", 0)
    accepted = c1.get("accepted", 0) - c0.get("accepted", 0)
    await van.stop()
    await spc.stop()
    tv, ts = float(np.median(tpot_van)), float(np.median(tpot_spc))
    return {
        "tpot_vanilla_ms": round(tv, 3),
        "tpot_spec_ms": round(ts, 3),
        "tpot_ratio_x": round(tv / ts, 2),
        "accept_rate": round(accepted / drafted, 3) if drafted else 0.0,
        "drafted_tokens": int(drafted),
        "exact_match": bool(match),
        "requests": len(prompts) * reps,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help="update the spec row of SERVE_BENCH.json")
    ap.add_argument("--spec-k", type=int, default=7)
    ap.add_argument("--max-new", type=int, default=96)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    args = ap.parse_args()

    import jax

    from ray_tpu.config import get_config
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import llama

    get_config().spec_draft_tokens = args.spec_k

    # big enough that the forward pass dominates per-round host work —
    # the regime speculative decoding targets (a 64-dim toy makes the
    # bench measure Python overhead, not forward count)
    cfg = llama.tiny(vocab_size=256, dim=args.dim,
                     n_layers=args.layers, n_heads=8, n_kv_heads=4,
                     ffn_dim=args.dim * 3, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(args.seed), cfg)

    def make_engine(*, spec):
        return LLMEngine(cfg, params, max_slots=4, max_len=320,
                         prefill_buckets=(64, 192),
                         cache_dtype="float32", kv_block_size=16,
                         spec=spec)

    async def go():
        # build the second-turn prompts with an untimed vanilla engine:
        # first-turn prompt + the model's own greedy output
        builder = make_engine(spec=False)
        high_prompts = []
        for s in HIGH_HIT_SEEDS:
            p = _periodic_prompt(s)
            out = await builder.generate(p, max_new_tokens=96)
            high_prompts.append(p + out["tokens"])
        await builder.stop()
        high = await _bench_workload(
            make_engine, high_prompts, args.max_new, args.reps)
        low = await _bench_workload(
            make_engine, [_random_prompt(s) for s in LOW_HIT_SEEDS],
            args.max_new, args.reps)
        return high, low

    high, low = asyncio.run(go())
    row = {
        "what": ("prompt-lookup speculative decode vs vanilla greedy, "
                 "interleaved A/B, decode-phase TPOT (first token "
                 "excluded)"),
        "high_hit": high,
        "low_hit": low,
        "exact_match": high["exact_match"] and low["exact_match"],
        "config": {"spec_draft_tokens": args.spec_k,
                   "max_new": args.max_new,
                   "high_hit_prompt_len": 160, "low_hit_prompt_len": 64,
                   "slots": 4,
                   "model": f"tiny-{args.layers}L-d{args.dim}"},
        "device": jax.devices()[0].platform,
        "caveat": ("CPU: verify forward ~ one decode step, so the "
                   "ratio tracks emitted-tokens-per-forward; TPU "
                   "verify widths are still far below MXU saturation "
                   "but unmeasured here. low_hit bounds the backoff's "
                   "worst-case overhead on adversarial prompts."),
    }
    print(json.dumps(row, indent=1))
    if args.write:
        try:
            with open("SERVE_BENCH.json") as f:
                doc = json.load(f)
        except FileNotFoundError:
            doc = {}
        doc["spec"] = row
        with open("SERVE_BENCH.json", "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print("wrote SERVE_BENCH.json spec row", file=sys.stderr)


if __name__ == "__main__":
    main()
