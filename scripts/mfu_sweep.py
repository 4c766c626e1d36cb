"""MFU sweep on the real chip: remat policy x batch x flash block sizes.

Run: python scripts/mfu_sweep.py [quick]
Prints one JSON line per variant; crashes (OOM) are caught and reported.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.parallel import mesh as pmesh  # noqa: E402
from ray_tpu.util import jaxenv  # noqa: E402
from ray_tpu.util.accelerators import peak_tflops  # noqa: E402

jaxenv.setup_compile_cache()


def run_variant(name, cfg, batch, iters=10, warmup=3):
    dev = jax.devices()[0]
    peak = peak_tflops(dev.device_kind)     # an unknown chip is an error
    seq = cfg.max_seq_len
    try:
        spec = pmesh.MeshSpec(data=1, fsdp=1, tensor=1, context=1)
        m = pmesh.make_mesh(spec, devices=[dev])
        init_fn, step_fn = pmesh.make_train_step(cfg, m)
        with m:
            state = init_fn(jax.random.PRNGKey(0))
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size,
                dtype=jnp.int32)
            bdict = {"tokens": tokens, "targets": tokens}
            for _ in range(warmup):
                state, metrics = step_fn(state, bdict)
            float(metrics["loss"])
            t0 = time.perf_counter()
            for _ in range(iters):
                state, metrics = step_fn(state, bdict)
            float(metrics["loss"])
            dt = time.perf_counter() - t0
        toks = batch * seq * iters / dt
        tf = toks * cfg.flops_per_token(seq) / 1e12
        mfu = 100.0 * tf / peak
        print(json.dumps({"variant": name, "mfu": round(mfu, 2),
                          "tflops": round(tf, 1),
                          "toks_per_s": round(toks, 0),
                          "batch": batch, "seq": seq}), flush=True)
        return mfu
    except Exception as e:
        print(json.dumps({"variant": name,
                          "error": f"{type(e).__name__}: {e}"[:200]}),
              flush=True)
        return 0.0


def base_cfg(**kw):
    d = dict(vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
             n_kv_heads=16, ffn_dim=5504, max_seq_len=2048,
             attn_impl="flash")
    d.update(kw)
    return llama.LlamaConfig(**d)


def main():
    big = dict(attn_block_q=1024, attn_block_k=1024)
    variants = [
        ("full_b8", base_cfg(), 8),
        ("full_b8_big", base_cfg(**big), 8),
        ("attn_b8_big", base_cfg(remat_policy="attn", **big), 8),
        ("attn_b8_big_bf16loss", base_cfg(remat_policy="attn",
                                          logits_dtype="bfloat16", **big), 8),
        ("attn_b16_big_bf16loss", base_cfg(remat_policy="attn",
                                           logits_dtype="bfloat16", **big), 16),
        ("full_b8_big_bf16loss", base_cfg(logits_dtype="bfloat16", **big), 8),
        ("dots_b4_big_bf16loss", base_cfg(remat_policy="dots",
                                          logits_dtype="bfloat16", **big), 4),
        ("attn_b8_bq512", base_cfg(remat_policy="attn",
                                   logits_dtype="bfloat16",
                                   attn_block_q=512, attn_block_k=512), 8),
        ("full_b16_big", base_cfg(attn_block_q=1024, attn_block_k=1024), 16),
        ("full_b4_big", base_cfg(attn_block_q=1024, attn_block_k=1024), 4),
        ("full_b8_q2048k1024", base_cfg(attn_block_q=2048,
                                        attn_block_k=1024), 8),
        ("full_b8_q1024k2048", base_cfg(attn_block_q=1024,
                                        attn_block_k=2048), 8),
        ("full_b8_s4096_b4", base_cfg(attn_block_q=1024, attn_block_k=1024,
                                      max_seq_len=4096), 4),
        # round-5 mechanism: fused chunked CE — the (b, s, 32000)
        # logits never materialize; frees ~1 GiB at b4 s4096 and cuts
        # the loss path's HBM traffic (cost: lm_head recompute per
        # chunk on bwd)
        ("fusedce1024_b4_s4096", base_cfg(
            logits_dtype="bfloat16", max_seq_len=4096,
            ce_chunk=1024, **big), 4),
        ("fusedce512_b4_s4096", base_cfg(
            logits_dtype="bfloat16", max_seq_len=4096,
            ce_chunk=512, **big), 4),
        ("fusedce1024_b8_s4096", base_cfg(
            logits_dtype="bfloat16", max_seq_len=4096,
            ce_chunk=1024, **big), 8),
        ("fusedce2048_b4_s4096", base_cfg(
            logits_dtype="bfloat16", max_seq_len=4096,
            ce_chunk=2048, **big), 4),
    ]
    if len(sys.argv) > 1:
        names = set(sys.argv[1].split(","))
        variants = [v for v in variants if v[0] in names]
    for name, cfg, batch in variants:
        run_variant(name, cfg, batch)


if __name__ == "__main__":
    main()
