"""Llama training-step throughput + MFU on one TPU chip.

Prints ONE JSON line:
  {"metric": "llama_train_mfu", "value": <mfu %>, "unit": "%MFU",
   "vs_baseline": <mfu / 40.0>, ...extras}

The reference publishes no Llama MFU numbers (BASELINE.md) — the north-star
target is >=40% MFU (reference: release/train_tests/benchmark/ defines only
the harness shape). vs_baseline is measured against that 40% target.

A device measurement or nothing: without a TPU, with a device kind that has
no published peak (util/accelerators.PEAK_TFLOPS), or when a case throws,
the script exits non-zero and prints no ``llama_train_mfu`` line.
"""

import json
import time


def _run_case(cfg, batch, seq, iters, warmup, dev):
    """One timed train-step config; returns (mfu, toks/s, tflops, loss)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import mesh as pmesh

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
        float(metrics["loss"])  # host fetch: waits for the device
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = step_fn(state, bdict)
        final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
    from ray_tpu.util.accelerators import peak_tflops
    toks_per_s = batch * seq * iters / dt
    achieved_tflops = toks_per_s * cfg.flops_per_token(seq) / 1e12
    return (100.0 * achieved_tflops / peak_tflops(dev.device_kind),
            toks_per_s, achieved_tflops, final_loss)


def main():
    import jax
    from ray_tpu.models import llama
    from ray_tpu.util import jaxenv
    from ray_tpu.util.accelerators import peak_tflops

    jaxenv.setup_compile_cache()
    dev = jax.devices()[0]
    device = jaxenv.describe_device(dev)
    if device["platform"] != "tpu":
        raise SystemExit(f"bench.py measures a TPU; jax found {device}")
    peak = peak_tflops(device["kind"])

    # 0.94B params (bf16 params + f32 adam fit one 16 GB chip with room).
    # Shapes from scripts/mfu_sweep.py on a v5e: 1024^2 flash blocks cut
    # the pallas grid from 32k to 512 invocations; full per-layer remat
    # beat saving attention residuals (residual HBM traffic cost more
    # than the recompute); batch 16 and 2048 blocks ran out of memory;
    # bf16 logits and batch 4 x seq 4096 beat 8 x 2048.
    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
        n_kv_heads=16, ffn_dim=5504, max_seq_len=4096,
        attn_impl="flash", attn_block_q=1024, attn_block_k=1024,
        logits_dtype="bfloat16")
    batch, seq, iters, warmup = 4, 4096, 20, 3
    mfu, toks_per_s, achieved_tflops, final_loss = _run_case(
        cfg, batch, seq, iters, warmup, dev)

    # Llama-2-7B layer shapes (dim 4096 / ffn 11008 / 32 heads / 32000
    # vocab): small-model MFU can flatter. The full 7B train state (f32
    # adam moments) cannot fit one 16 GB chip, so this runs 4 full-width
    # layers — the per-chip shard of a 7B fsdp-8 run, same MXU tile
    # shapes, FLOPs counted for this config.
    cfg7 = llama.llama2_7b(
        n_layers=4, attn_impl="flash",
        attn_block_q=1024, attn_block_k=1024,
        logits_dtype="bfloat16")
    mfu7, tps7, tf7, _ = _run_case(cfg7, 4, 4096, 20, 3, dev)

    print(json.dumps({
        "metric": "llama_train_mfu",
        "value": round(mfu, 2),
        "unit": "%MFU",
        "vs_baseline": round(mfu / 40.0, 3),
        "tokens_per_s": round(toks_per_s, 1),
        "achieved_tflops": round(achieved_tflops, 2),
        "peak_tflops": peak,
        "device": device,
        "model_params_m": round(cfg.num_params() / 1e6, 1),
        "batch": batch, "seq": seq, "final_loss": round(final_loss, 4),
        "timed_iters": iters,
        "mfu_7b_shapes": round(mfu7, 2),
        "tokens_per_s_7b_shapes": round(tps7, 1),
        "achieved_tflops_7b_shapes": round(tf7, 2),
        "config_7b_shapes": ("dim4096/ffn11008/h32/vocab32k/"
                             "4 full-width layers, b4 s4096"),
    }))


if __name__ == "__main__":
    main()
