"""The ``moe`` family: a full-attention decoder whose feed-forward is a
sparse mixture of experts (OLMoE-1B-7B; Mixtral by two switches).
Everything the harness asks of such a model by name is here: the
program's config object and module, the plain reference, and the
operations a trained token and a step's grouped matmuls require. No
``serve_parity``: the family cannot be served yet.

The plain reference is independent of the code under test: forward pass
and loss in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision. It has no sort, no gather, no grouped matmul and no kernel:
every expert is computed for every token, and the result is multiplied
by the (tokens, experts) matrix that holds a token's gate at its chosen
experts and 0 elsewhere.

The architecture, as ``modeling_olmoe.py`` of ``transformers`` states it
(OlmoeAttention, OlmoeSparseMoeBlock, OlmoeForCausalLM,
load_balancing_loss_func), for hidden state x:

    y = n1(x)
    q = qn(Wq y), k = kn(Wk y), v = Wv y
        qn, kn: RMSNorm with a learned weight over the WHOLE projected
        width (heads * head_dim), before the split into heads and before
        RoPE; no biases; clip_qkv null
    q, k = rope(q), rope(k)     pairs (first half, second half) of a head,
                                base rope_theta
    x += Wo softmax(q k^T / sqrt(head_dim), causal) v
    y = n2(x)
    p = softmax(y Wr)           over all experts, float32
    S = the num_experts_per_tok experts with the largest p
    g_e = p_e for e in S        as they are: norm_topk_prob false
                                (true: g_e = p_e / sum_{S} p; Mixtral)
    x += sum_{e in S} g_e Wdown_e (silu(Wgate_e y) * Wup_e y)

n1, n2 are RMSNorm (eps rms_norm_eps); every token reaches every one of
its experts, whatever the load (no capacity, nothing dropped). After the
last layer: RMSNorm, then the untied head. Loss: mean cross-entropy +
router_aux_loss_coef * load-balancing term, where the term pools the
routers of ALL layers (their tokens are concatenated):
E * sum_e f_e pbar_e with f_e = assignments to e per (token, layer) and
pbar_e = mean of p_e over (token, layer).

Departures from the published description: the router z-loss of the
OLMoE paper is not in ``modeling_olmoe.py`` and is left out here and in
the program; q/k-norm is switched by ``model_type`` ("olmoe" has it)
because ``config.json`` has no key for it. Expert weights are upcast one
expert at a time, so the float32 copy never exceeds one expert.
"""

from __future__ import annotations

from harness import kernels


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    heads = model["num_attention_heads"]
    if model.get("head_dim", model["hidden_size"] // heads) * heads \
            != model["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "heads; this configuration needs another")
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=heads,
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        n_experts=model["num_experts"],
        experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        qk_norm=model["model_type"] == "olmoe",
        aux_loss_weight=float(model["router_aux_loss_coef"]),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **overrides)


def module():
    """The program's module with the family protocol (``init_params`` /
    ``param_shardings`` / ``loss_fn``, and ``forward``)."""
    from ray_tpu.models import moe
    return moe


def _f32_forward(params, tokens, *, n_heads, n_kv_heads, head_dim,
                 rope_theta, eps, top_k, renormalise, qk_norm, keep=None):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, the
    load-balancing term). ``keep``, for the controls of
    ``tools/moe_parity_sensitivity.py`` only, maps the 0/1 matrix S
    (b, s, E) to the assignments a DEFECTIVE program would still compute
    (one that drops tokens over a capacity); None is the model."""
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

    def experts(y, p_kept, lp):
        """sum_e p_kept[..., e] * Wdown_e (silu(Wgate_e y) * Wup_e y),
        every expert computed for every token."""
        def one(acc, e):
            w_gate, w_up, w_down, p = e
            h = jax.nn.silu(y @ w_gate.astype(f32)) * (y @ w_up.astype(f32))
            return acc + p[..., None] * (h @ w_down.astype(f32)), None
        out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
            lp["w_gate"], lp["w_up"], lp["w_down"],
            jnp.moveaxis(p_kept, -1, 0)))
        return out

    def layer(x, lp):
        dense = {k: v.astype(f32) for k, v in lp.items()
                 if k not in ("w_gate", "w_up", "w_down")}
        y = rms(x, dense["attn_norm"])
        q, k, v = y @ dense["wq"], y @ dense["wk"], y @ dense["wv"]
        if qk_norm:
            q, k = rms(q, dense["q_norm"]), rms(k, dense["k_norm"])
        q = rope(q.reshape(b, s, n_heads, head_dim))
        k = rope(k.reshape(b, s, n_kv_heads, head_dim))
        v = v.reshape(b, s, n_kv_heads, head_dim)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(f32(head_dim))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(b, s, n_heads * head_dim) @ dense["wo"]
        y = rms(x, dense["mlp_norm"])
        p = jax.nn.softmax(y @ dense["router"], -1)          # (b, s, E)
        # S as a 0/1 matrix; equal probabilities go to the lower index
        chosen = jax.nn.one_hot(jax.lax.top_k(p, top_k)[1], p.shape[-1],
                                dtype=f32).sum(-2)
        p_kept = (chosen if keep is None else keep(chosen)) * p
        if renormalise:
            p_kept = p_kept / jnp.sum(p_kept, -1, keepdims=True)
        x = x + experts(y, p_kept, lp)
        return x, (jnp.mean(chosen, (0, 1)), jnp.mean(p, (0, 1)))

    x = jnp.take(params["embed"], tokens, axis=0).astype(f32)
    x, (chosen, p) = jax.lax.scan(layer, x, params["layers"])  # (L, E)
    x = rms(x, params["final_norm"])
    aux = p.shape[1] * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(p, 0))
    return x @ params["lm_head"].astype(f32), aux


def _model_kw(cfg) -> dict:
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                eps=cfg.norm_eps, top_k=cfg.experts_per_token,
                renormalise=cfg.norm_topk_prob, qk_norm=cfg.qk_norm)


def forward(params, tokens, cfg):
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: _f32_forward(
            p, t, **_model_kw(cfg))[0])(params, tokens)


def logits_and_loss(params, batch, cfg, keep=None):
    """The reference's logits (b, s, vocab) and its loss against
    ``batch["targets"]`` (mean cross-entropy + the weighted
    load-balancing term, which the program's ``loss_fn`` includes too),
    from one forward. ``keep``: see ``_f32_forward``."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, targets):
        logits, aux = _f32_forward(params, tokens, keep=keep,
                                   **_model_kw(cfg))
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits, jnp.mean(logz - gold) + cfg.aux_loss_weight * aux
    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(params, batch["tokens"], batch["targets"])


def _expert_matmul_params(model: dict) -> int:
    """Parameters of the experts ONE token is routed to, in one layer."""
    return model["num_experts_per_tok"] * 3 * model["hidden_size"] \
        * model["intermediate_size"]


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token requires: 6 per
    matmul parameter the token is ROUTED to (attention, the router, its
    num_experts_per_tok experts, the head; the embedding lookup is a
    gather) plus causal attention, with no recomputation."""
    d = model["hidden_size"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d \
        + d * model["num_experts"] + _expert_matmul_params(model)
    matmul = n_layers * per_layer + d * model["vocab_size"]
    pairs_per_token = (seq + 1) / 2
    attn = (kernels.flash_fwd_flops(1, h, hd)
            + kernels.flash_bwd_flops(1, h, hd)) * pairs_per_token * n_layers
    return 6.0 * matmul + attn


def gmm_required_flops_per_step(model: dict, n_layers: int,
                                tokens: int) -> float:
    """Operations the grouped matmuls of one train step require, from the
    configuration and not from the calls (fusing gate and up leaves it
    right): 2 per expert parameter a token is routed to, per token, per
    layer, times the passes the configured step makes over the experts -
    the forward, the full remat's second forward when the layer is
    recomputed, and two products in the backward (d_lhs, d_rhs)."""
    recomputed = model["deployment"]["model_overrides"].get(
        "remat_policy", "full") == "full"
    passes = 4 if recomputed else 3
    return 2.0 * _expert_matmul_params(model) * tokens * n_layers * passes
