"""The ``llama`` family: full-attention GQA decoder with SwiGLU
(Mistral-7B-v0.3 and Yi-1.5 share it). Everything the harness asks of a
model by name is here: the program's config object and module, the plain
reference, the serving comparison, and the operations a trained token
requires.

The plain reference is independent of the code under test: the
decoder's forward pass and loss in straightforward float32
``jax.numpy`` at ``highest`` matmul precision - no kernels, no cache, no
batching tricks.

The architecture: token embedding;
per layer  x += Wo.attn(rope(Wq.n1(x)), rope(Wk.n1(x)), Wv.n1(x)),
x += Wdown.(silu(Wgate.n2(x)) * Wup.n2(x)) with RMSNorm n1, n2, grouped
query heads, causal softmax attention scaled by 1/sqrt(head_dim),
rotary embedding over (first half, second half) pairs with base
``rope_theta``; final RMSNorm; untied output head. Departure from the
published description: none (Mistral-7B-v0.3 has no sliding window).
Weights are upcast one layer at a time so the float32 copy never
exceeds one layer.
"""

from __future__ import annotations

import functools

from harness import kernels
from harness.reference import rel_err


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.llama import LlamaConfig
    heads = model["num_attention_heads"]
    if model.get("head_dim", model["hidden_size"] // heads) * heads \
            != model["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration needs another")
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **overrides)


def module():
    """The program's module with the family protocol (``init_params`` /
    ``param_shardings`` / ``loss_fn``, and ``forward``)."""
    from ray_tpu.models import llama
    return llama


def _f32_forward(params, tokens, *, n_heads, n_kv_heads, head_dim,
                 rope_theta, eps):
    """tokens (b, s) int32 -> logits (b, s, vocab) float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, s = tokens.shape
    g = n_heads // n_kv_heads

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w.astype(f32)

    half = head_dim // 2
    freqs = rope_theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(s, dtype=f32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(f32), lp)
        y = rms(x, lp["attn_norm"])
        q = rope((y @ lp["wq"]).reshape(b, s, n_heads, head_dim))
        k = rope((y @ lp["wk"]).reshape(b, s, n_kv_heads, head_dim))
        v = (y @ lp["wv"]).reshape(b, s, n_kv_heads, head_dim)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(f32(head_dim))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(b, s, n_heads * head_dim) @ lp["wo"]
        y = rms(x, lp["mlp_norm"])
        x = x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
            @ lp["w_down"]
        return x, None

    x = jnp.take(params["embed"], tokens, axis=0).astype(f32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rms(x, params["final_norm"])
    return x @ params["lm_head"].astype(f32)


def _model_kw(cfg) -> dict:
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                eps=cfg.norm_eps)


def forward(params, tokens, cfg):
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(_f32_forward, **_model_kw(cfg)))(
            params, tokens)


def logits_and_loss(params, batch, cfg):
    """The reference's logits (b, s, vocab) and its mean cross-entropy
    against ``batch["targets"]``, from one forward."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, targets):
        logits = _f32_forward(params, tokens, **_model_kw(cfg))
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits, jnp.mean(logz - gold)
    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(params, batch["tokens"], batch["targets"])


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool) -> dict:
    """Prefill one seeded prompt through the served prefill, write its
    KV into a paged pool, decode one step through the block table with
    the served decode path, and compare both logits with the reference's
    full forward over the same prompt+1 tokens."""
    import random

    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    bucket = min(b for b in buckets if b >= prompt_len)
    logits, kv = lm.prefill(params, jnp.asarray(lm.pad_prompt(toks, bucket)),
                            jnp.int32(prompt_len), cfg, bucket)
    nb = bucket // block
    pool = kc.init_pool(cfg, 1 + nb, block, jnp.bfloat16)
    table = 1 + np.arange(nb, dtype=np.int32)
    pool = kc.scatter_bucket(pool, kv, jnp.asarray(table), nb)
    nxt = int(np.argmax(np.asarray(logits)))
    step = kc.paged_decode_logits(
        params, pool, jnp.asarray(table[None]),
        jnp.asarray([prompt_len], jnp.int32),
        jnp.asarray([nxt], jnp.int32), cfg, impl=kv_impl,
        interpret=interpret)
    want = np.asarray(forward(
        params, jnp.asarray([toks + [nxt]], jnp.int32), cfg))[0]
    return {"prefill_rel_err": rel_err(logits, want[prompt_len - 1]),
            "decode_rel_err": rel_err(np.asarray(step)[0],
                                      want[prompt_len]),
            "finite": bool(np.isfinite(np.asarray(step)).all()),
            "prompt_len": prompt_len}


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter (the embedding lookup is a gather, the head is a
    matmul) plus causal attention, with no recomputation."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f
    matmul = n_layers * per_layer + d * model["vocab_size"]
    pairs_per_token = (seq + 1) / 2
    attn = (kernels.flash_fwd_flops(1, h, hd)
            + kernels.flash_bwd_flops(1, h, hd)) * pairs_per_token * n_layers
    return 6.0 * matmul + attn
