"""Serve-level LLM benchmark: HTTP proxy -> replica TTFT + throughput.

Unlike scripts/llm_bench.py (engine-level), this drives the FULL serving
path the north star names: client -> HTTP proxy (SSE streaming) ->
router -> replica -> continuous-batching engine on the chip. TTFT is
measured at the CLIENT: time from request start to the first SSE data
event.

Run from the repo root: python scripts/serve_bench.py [--requests N].
Prints one JSON line per run plus an aggregate. This driver process
never touches JAX: the replica worker holds the chip, and the device
named in the aggregate is the one its engine reports.

Reference harness shape: release/llm_tests/serve/ (vLLM serve benchmark
drives the HTTP endpoint and reports TTFT percentiles).
"""

import argparse
import http.client
import json
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")


def _one_request(addr, prompt, max_new, out, idx):
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(addr["host"], addr["port"],
                                      timeout=600)
    conn.request(
        "POST", "/bench",
        body=json.dumps({"tokens": prompt, "max_new_tokens": max_new}),
        headers={"Content-Type": "application/json",
                 "Accept": "text/event-stream"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.status
    ttft = None
    n_tokens = 0
    buf = b""
    while True:
        chunk = resp.read(1)
        if not chunk:
            break
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.startswith(b"data: ") and b"token" in line:
                if ttft is None:
                    ttft = time.monotonic() - t0
                n_tokens += 1
    conn.close()
    out[idx] = {"ttft_s": ttft, "tokens": n_tokens,
                "total_s": time.monotonic() - t0}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bench340m")
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--steps-per-sync", type=int, default=8)
    ap.add_argument("--long-prompt-len", type=int, default=2048)
    ap.add_argument("--long-requests", type=int, default=12)
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment

    if args.model == "bench340m":
        overrides = dict(
            vocab_size=32000, dim=1024, n_layers=16, n_heads=16,
            n_kv_heads=16, ffn_dim=2816, max_seq_len=1024,
            dtype="bfloat16", logits_dtype="float32",
            attn_impl="reference")
        model = "tiny"
    else:
        overrides = dict(dtype="bfloat16", logits_dtype="float32",
                         attn_impl="reference")
        model = args.model

    ray_tpu.init(num_cpus=4)
    try:
        cfg = LLMConfig(
            model=model, model_overrides=overrides,
            max_slots=args.slots,
            max_len=max(1024, args.long_prompt_len + args.max_new + 64),
            prefill_buckets=(64, 256, 1024, 2048),
            steps_per_sync=args.steps_per_sync)
        handle = serve.run(build_llm_deployment(cfg, name="bench"),
                           name="bench_app", route_prefix="/bench",
                           _blocking_ready=False)
        # poll readiness with visible replica states (a silent 600s
        # block makes a slow replica init undiagnosable)
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER", namespace="serve")
        deadline = time.monotonic() + 600
        while True:
            st = ray_tpu.get(ctrl.status.remote(), timeout=30)
            reps = st.get("bench", {}).get("replicas", {})
            if any(r["state"] == "RUNNING" for r in reps.values()):
                break
            if time.monotonic() > deadline:
                raise RuntimeError(f"replica never RUNNING: {st}")
            print(f"# waiting: {st}", file=sys.stderr)
            time.sleep(5)
        addr = serve.proxy_address()

        # warmup: compile prefill buckets + decode block on the chip
        warm = {}
        _one_request(addr, [1, 2, 3], args.steps_per_sync + 1, warm, 0)

        def sweep(n_requests, prompt_len, concurrency, seed):
            rng = np.random.default_rng(seed)
            prompts = [
                [int(x) for x in rng.integers(1, 31999,
                                              size=prompt_len)]
                for _ in range(n_requests)]
            results = [None] * n_requests
            t0 = time.monotonic()
            cursor = 0
            while cursor < n_requests:
                batch = range(cursor,
                              min(cursor + concurrency, n_requests))
                threads = [
                    threading.Thread(
                        target=_one_request,
                        args=(addr, prompts[i], args.max_new,
                              results, i))
                    for i in batch]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                cursor += concurrency
            wall = time.monotonic() - t0
            ttfts = sorted(r["ttft_s"] for r in results
                           if r and r["ttft_s"] is not None)
            toks = sum(r["tokens"] for r in results if r)
            assert ttfts and toks, results[:3]

            def p(q):
                # nearest-rank: ceil(q*n)-1 (int(q*n) overshoots by one
                # — at n=100 it would report p99 as the max sample)
                import math
                return ttfts[max(0, min(len(ttfts) - 1,
                                        math.ceil(q * len(ttfts)) - 1))]

            return {"ttft_p50_ms": round(p(0.50) * 1000, 1),
                    "ttft_p95_ms": round(p(0.95) * 1000, 1),
                    "ttft_p99_ms": round(p(0.99) * 1000, 1),
                    "ttft_max_ms": round(ttfts[-1] * 1000, 1),
                    "throughput_tok_s": round(toks / wall, 1),
                    "requests": n_requests, "prompt_len": prompt_len,
                    "concurrency": concurrency}

        runs = []
        for r in range(args.runs):
            res = sweep(args.requests, args.prompt_len,
                        args.concurrency, seed=r)
            runs.append(res)
            print(json.dumps({"run": r, **res}), flush=True)

        # long-prompt row: chunked prefill under load
        long_row = None
        if args.long_requests > 0:
            long_row = sweep(args.long_requests, args.long_prompt_len,
                             min(4, args.concurrency),
                             seed=args.runs)
            print(json.dumps({"run": "long", **long_row}), flush=True)

        engine = ray_tpu.get(handle.stats.remote(), timeout=60)
        p50s = sorted(r["ttft_p50_ms"] for r in runs)
        print(json.dumps({
            "metric": "llm_serve_ttft_p50",
            "value": p50s[len(p50s) // 2], "unit": "ms",
            "runs": runs, "long_prompt": long_row,
            "max_new": args.max_new,
            "slots": args.slots, "steps_per_sync": args.steps_per_sync,
            "path": "client->HTTP proxy (SSE)->router->replica->engine",
            "device": engine["device"],
            "engine": {k: engine[k] for k in
                       ("kv_impl", "kv_interpret", "prefill_impl")},
        }))
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


if __name__ == "__main__":
    main()
