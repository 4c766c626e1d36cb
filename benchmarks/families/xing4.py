"""The ``xing4`` family (Xing4.0-29B-A4B, ``model_type: xing4_0``): a
decoder of multi-head LATENT attention (MLA) layers whose residual is a
stream of ``hc_mult`` = 4 copies a position, mixed around every sub-layer
by manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606); leading dense layers, then
sigmoid-routed experts with a selection bias and a shared expert, EVERY
expert held (``ep_size: 1``). Everything the harness asks of such a model
by name is here: the program's config object and module, the plain
reference, the serving comparison, and what its kernels require (bytes,
operations) for the rooflines.

The plain reference is independent of the code under test: the forward
pass in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision, a layer at a time, the stream as (positions, 4, hidden), the
coefficients by the equations below with Sinkhorn as the written loop,
latent attention in the MATERIALISED form (per-head keys and values from
every token's latent row, full causal softmax: ``families/mistral4.py
_attention``, the same equation at this model's widths; its query scale
a(t) is 1 here), every expert computed for every token and weighted by
the (tokens, experts) matrix of gates. No cache, no kernel, no
absorption, no sort, no grouped matmul.

The architecture, from the published ``config.json`` (40 layers, hidden C
= 3584, 32 heads, vocabulary 131,072, untied; no biases), for a position
t whose stream is X (n, C), n = ``hc_mult`` = 4. A sub-layer F (attention
or feed-forward, each behind its own pre-norm RMSNorm, eps 1e-6) is
wrapped so, with its own leaves phi, b, a:

    x~     = vec(X) * rsqrt(mean(vec(X)^2) + rms_norm_eps)       (n C,)
    Hpre~  = a_pre  * (x~ @ phi_pre)  + b_pre                    (n,)
    Hpost~ = a_post * (x~ @ phi_post) + b_post                   (n,)
    Hres~  = a_res  * mat(x~ @ phi_res) + b_res                  (n, n)
    Hpre   = sigmoid(Hpre~)         Hpost = 2 * sigmoid(Hpost~)
    M_0    = exp(clip(Hres~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t    = rows(cols(M_t-1)),  t = 1 .. hc_sinkhorn_iters = 20
             cols(M) = M / (sum over the rows of each column + hc_eps)
             rows(M) = M / (sum over the columns of each row + hc_eps)
    Hres   = M_20             doubly stochastic to the iteration's accuracy
    y      = Hpre @ X                          (C,): what F reads
    X'     = Hres @ X + outer(Hpost, F(y))

    the stream: the embedding copied to the n rows before layer 0; the n
    rows summed before the final RMSNorm; untied head.
    attention (every layer), on y' = RMSNorm(y):
      cq = RMSNorm_{768}(y' Wq_a);  q = cq Wq_b -> 32 heads x 192 =
          [q_nope 128 | q_rope 64]
      [ckv 512 | kr 64] = y' Wkv_a;  c = RMSNorm_{512}(ckv)
      q_rope, kr = rope(q_rope, t), rope(kr, t); kr is ONE 64-vector a
          token, shared by all heads; pairs (2i, 2i + 1)     (assumed d)
      the cache row of token t, this layer: [c | kr], 576 values
      [k_nope 128 | v 128]_h = c Wkv_b[h];  k_h = [k_nope_h | kr]
      o_h = softmax_{j<=t}(192^-0.5 * m^2 * q_h . k_h(j)) v_h(j)
      F = [o_1 .. o_32] Wo
    rope: YaRN (``rope_scaling``): theta 10000, factor 64, original 4096,
      beta_fast 32, beta_slow 1 over the 64 rotary dims; ``mscale =
      mscale_all_dim = 1``: cos / sin carry 1, m = 0.1 ln(64) + 1 = 1.416
    feed-forward, layers 0-1 (``first_k_dense_replace`` 2): SwiGLU, 9216
    feed-forward, the rest, on y' = RMSNorm(y):
      s = sigmoid(y' Wr) over the 64, float32;  S = the 4 largest of s +
      b_sel (``topk_method: noaux_tc``; b_sel never enters a gate);  g_e =
      2 * s_e / sum_S s   (``norm_topk_prob``, ``routed_scaling_factor``
      2, ``n_group`` = ``topk_group`` = 1)
      F = sum_{e in S} g_e E_e(y') + E_shared(y')
      E(x) = Wdown (silu(Wgate x) * Wup x), width 1024

The program keeps a sub-layer's phi as one leaf (n C, 2n + n n) with the
columns [pre | post | res], b as (2n + n n,) and the three scales as a
(3,) (``hc_attn_*``, ``hc_mlp_*``), and ``Wkv_b`` as two leaves by head,
``wk_b`` (heads, 128, 512) and ``wv_b`` (heads, 512, 128); the reference
reads the same leaves.

THE CUT is in depth alone: every width, all 64 experts, the whole
vocabulary are here, so the reference is the model's own layer and there
is no share to be given.

Assumed, where ``config.json`` is silent (each also under ``assumed`` in
the configuration file; none changes a shape or a byte count):
  (a) the order of the two normalisations (columns first, rows last, so
      the rows of Hres sum to 1 exactly and its columns to the
      iteration's accuracy) and ``hc_eps`` added to each sum before the
      division;
  (b) x~ carries no learned scale (the projections phi absorb one); its
      eps is ``rms_norm_eps``;
  (c) the copy-in (the embedding to every row) and the sum-out;
  (d) RoPE on interleaved pairs, as the latent-attention family's
      published modelling has it (the config has no ``rope_interleave``);
  (e) m = 0.1 * ``mscale_all_dim`` * ln(``factor``) + 1 multiplies the
      softmax scale squared, as ``assumed.b_mscale`` of the other latent
      configuration;
  (f) ``torch_dtype`` bfloat16; the coefficients (x~, the projections,
      the sigmoids, Sinkhorn) in float32;
  (g) the multi-token-prediction block (``num_nextn_predict_layers`` 1)
      is left out of program and reference: it is no part of the
      next-token distribution.
Departures from the published description: none besides (a)-(g).
``FAULTS`` are the reference's deliberate departures, one at a time, for
``tools/mhc_parity_sensitivity.py``.
"""

from __future__ import annotations

import functools

from families.mistral4 import (DECODE_STEPS, POOL_FAULTS,      # noqa: F401
                               PREFILL_PREFIXES, _attention, _rms, _swiglu,
                               served)
from harness.reference import rel_err

# the reference's deliberate faults (tools/mhc_parity_sensitivity.py):
# Hres = identity; Sinkhorn stopped after 1 iteration; Hpost without its
# factor 2; the input-dependent part dropped (a_* = 0); the coefficients
# computed in bfloat16; and the latent family's own: kr left out of the
# scores, YaRN off, cache rows through float8 e4m3 / int8 (a scale a row)
MIXING_FAULTS = ("res_identity", "sinkhorn_1", "post_without_2",
                 "static_coefficients", "coeff_bfloat16")
FAULTS = MIXING_FAULTS + ("kr_left_out", "yarn_off", "rows_float8",
                          "rows_int8")
# the comparison's own (``compared``): "late_<fault>" is <fault> from the
# reply's position LATE_FROM on behind a sound reference before it, what
# a fault that starts at a later decode step leaves
LATE_FAULTS = ("late_kr_left_out",)


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    rp = model["rope_scaling"]
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    if model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc":
        raise ValueError("the family routes by sigmoid scores with a "
                         "selection bias")
    if rp["type"] != "yarn" or model.get("ep_size", 1) != 1 \
            or model.get("moe_layer_freq", 1) != 1:
        raise ValueError("the family's rotary is YaRN, its experts lie on "
                         "one device and every layer past the lead has them")
    n = model["num_hidden_layers"]
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_size=model["v_head_dim"],
        ffn_dim=model["moe_intermediate_size"],
        n_experts=model["n_routed_experts"],
        experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), scoring="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]),
        n_shared_experts=model["n_shared_experts"],
        n_dense_layers=model["first_k_dense_replace"],
        dense_ffn_dim=model["intermediate_size"],
        layer_types=("latent",) * n,
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_factor=float(rp["factor"]),
        rope_original_len=rp["original_max_position_embeddings"],
        rope_beta_fast=float(rp["beta_fast"]),
        rope_beta_slow=float(rp["beta_slow"]),
        rope_mscale=float(rp["mscale"]),
        rope_mscale_all_dim=float(rp["mscale_all_dim"]),
        hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"],
        hc_eps=float(model["hc_eps"]),
        hc_res_clamp_min=float(model["mhc_h_res_clamp_min"]),
        hc_res_clamp_max=float(model["mhc_h_res_clamp_max"]),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **{**model["deployment"].get("model_overrides", {}), **overrides})


def module():
    """The program's module that makes this family's parameters."""
    from ray_tpu.models import moe
    return moe


# --- the plain reference ---------------------------------------------------


def mixing(X, lp, sub: str, cfg, faults=()):
    """The coefficients of sub-layer ``sub`` ("attn" | "mlp") for the
    streams X (s, n, C) float32: (Hpre (s, n), Hpost (s, n), Hres (s, n,
    n)), by the docstring's equations."""
    import jax
    import jax.numpy as jnp
    s, n, _ = X.shape
    dt = jnp.bfloat16 if "coeff_bfloat16" in faults else jnp.float32
    phi, b, a = (lp[f"hc_{sub}_{leaf}"].astype(dt)
                 for leaf in ("phi", "b", "a"))
    if "static_coefficients" in faults:
        a = jnp.zeros_like(a)
    v = X.reshape(s, -1).astype(dt)
    xt = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                           + jnp.asarray(cfg.norm_eps, dt))
    z = xt @ phi                                        # (s, 2n + n n)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    if "post_without_2" not in faults:
        post = 2 * post
    M = jnp.exp(jnp.clip(
        a[2] * z[:, 2 * n:].reshape(s, n, n) + b[2 * n:].reshape(n, n),
        cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
    eps = jnp.asarray(cfg.hc_eps, dt)
    for _ in range(1 if "sinkhorn_1" in faults else cfg.hc_sinkhorn_iters):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)  # each column
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)  # each row
    if "res_identity" in faults:
        M = jnp.broadcast_to(jnp.eye(n, dtype=dt), M.shape)
    f32 = jnp.float32
    return pre.astype(f32), post.astype(f32), M.astype(f32)


def mixed(X, lp, sub: str, cfg, faults, F):
    """X' = Hres @ X + outer(Hpost, F(Hpre @ X)) for the streams X (s, n,
    C); ``F`` returns (its output (s, C), what else it knows)."""
    import jax.numpy as jnp
    pre, post, res = mixing(X, lp, sub, cfg, faults)
    out, aux = F(jnp.einsum("sn,snc->sc", pre, X))
    return jnp.einsum("sij,sjc->sic", res, X) \
        + post[:, :, None] * out[:, None, :], aux


def gates(x, router, bias, cfg):
    """(tokens, n_experts) float32: a token's gate at each of its chosen
    experts, 0 elsewhere. Equal scores go to the lower index."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    chosen = jax.nn.one_hot(
        jax.lax.top_k(s + bias, cfg.experts_per_token)[1], s.shape[-1],
        dtype=jnp.float32).sum(-2)
    g = chosen * s
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * cfg.routed_scaling


def routing_margin(x, router, bias, cfg):
    """(tokens,) float32, in units of the score s + b: how far a token's
    scores would have to move before it got ANOTHER set of experts: the
    gap between the last score chosen and the first left out (every
    expert is held here)."""
    import jax
    import jax.numpy as jnp
    k = cfg.experts_per_token
    v = jax.nn.sigmoid(x @ router.astype(jnp.float32)) + bias
    top = jax.lax.top_k(v, k + 1)[0]
    return top[:, k - 1] - top[:, k]


def routed(x, lp, cfg, expert):
    """sum over the chosen experts of g_e E_e(x) for rows x (tokens, C)
    float32: every expert computed for every token, one expert's float32
    copy at a time (``expert(name, e)`` gives expert e's matrix)."""
    import jax
    import jax.numpy as jnp
    g = gates(x, lp["router"], lp["router_bias"], cfg)

    def one(acc, e):
        out = _swiglu(x, expert("w_gate", e), expert("w_up", e),
                      expert("w_down", e))
        return acc + jax.lax.dynamic_index_in_dim(
            g, e, axis=1, keepdims=True) * out, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(cfg.n_experts, dtype=jnp.int32))
    return out


def _f32_layer(X, stack, row, *, cfg, faults=()):
    """Layer ``row`` (traced: one program a stack) of the stacked
    parameters on the streams X (s, n, C) float32 -> (X, every position's
    ``routing_margin`` in this layer (inf in a dense one), every
    position's cache row). The experts' matrices are taken out of the
    stack one at a time."""
    import jax
    import jax.numpy as jnp
    f32 = X.dtype
    sparse = "router" in stack
    big = ("w_gate", "w_up", "w_down") if sparse else ()
    lp = {name: jax.lax.dynamic_index_in_dim(w, row, keepdims=False)
          for name, w in stack.items() if name not in big}
    eps = cfg.norm_eps

    def attention(y):
        o, rows = _attention(_rms(y, lp["attn_norm"], eps), lp, cfg, faults)
        return o @ lp["wo"].astype(f32), rows

    def feed_forward(y):
        y = _rms(y, lp["mlp_norm"], eps)
        if not sparse:
            return _swiglu(y, lp["w_gate"], lp["w_up"], lp["w_down"]), \
                jnp.full(y.shape[:1], jnp.inf, f32)

        def expert(name, e):
            return jax.lax.dynamic_index_in_dim(
                stack[name].reshape(-1, *stack[name].shape[2:]),
                row * cfg.n_experts + e, keepdims=False)
        shared = _swiglu(y, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"])
        return routed(y, lp, cfg, expert) + shared, routing_margin(
            y, lp["router"], lp["router_bias"], cfg)

    X, rows = mixed(X, lp, "attn", cfg, faults, attention)
    X, margin = mixed(X, lp, "mlp", cfg, faults, feed_forward)
    return X, margin, rows


@functools.lru_cache(maxsize=None)
def _layer_program(cfg, faults):
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_f32_layer, cfg=cfg, faults=faults),
        in_axes=(0, None, None)))


def forward_margins(params, tokens, cfg, faults=(), at=None, rows_seen=None,
                    stream_seen=None):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, margins
    (b, s): a position's smallest ``routing_margin`` over the layers);
    with ``at`` (positions), the logits and margins of those positions
    only (the head is the widest product); ``rows_seen(layer, rows)`` is
    handed each layer's cache rows [c | kr] (b, s, 576 at the published
    widths) as they are computed, ``stream_seen(layer, X)`` the streams
    (b, s, n, C) that enter each layer. Each layer is one call of one program a
    stack, handed the whole stack, of which it reads its own row (an
    expert's matrices one expert at a time): the float32 copies alive at
    once are one layer's attention and one expert, so it runs beside the
    served model on the chip."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        X = jnp.broadcast_to(x[:, :, None, :],
                             (*x.shape[:2], cfg.hc_mult, x.shape[-1]))
        margins = jnp.full(tokens.shape, jnp.inf, jnp.float32)
        layer = _layer_program(cfg, tuple(faults))
        for i in range(cfg.n_layers):
            stack, row = ("dense_layers", i) if i < cfg.n_dense_layers \
                else ("layers", i - cfg.n_dense_layers)
            if stream_seen is not None:
                stream_seen(i, X)
            X, m, rows = layer(X, params[stack], jnp.int32(row))
            margins = jnp.minimum(margins, m)
            if rows_seen is not None:
                rows_seen(i, rows)
        x = jnp.sum(X, axis=2)
        if at is not None:
            at = jnp.asarray(at, jnp.int32)
            x, margins = x[:, at], margins[:, at]
        logits = jax.jit(lambda x, n, w: _rms(x, n, cfg.norm_eps)
                         @ w.astype(jnp.float32))(
            x, params["final_norm"], params["lm_head"])
        return logits, margins


def forward(params, tokens, cfg, faults=()):
    """tokens (b, s) int32 -> logits (b, s, vocab) float32."""
    return forward_margins(params, tokens, cfg, faults)[0]


def logits_and_loss(params, batch, cfg, faults=()):
    """The reference's logits (b, s, vocab) and its mean cross-entropy
    against ``batch["targets"]``."""
    import jax
    import jax.numpy as jnp
    logits = forward(params, batch["tokens"], cfg, faults)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                               -1)[..., 0]
    return logits, jnp.mean(logz - gold)


# --- the serving comparison -------------------------------------------------
#
# As the other latent family's (``families/mistral4.py``: ``served`` is
# its function, taken as it is: the engine's chunked prefill over an
# accumulator of latent rows, the scatter, DECODE_STEPS greedy tokens
# through the pool's writer and walk), with the logits judged by another
# statistic, because EVERY expert is held here. A token's experts are the
# 4 largest of 64 scores; the program (bf16) and the reference (float32)
# see scores that differ in the third decimal, and where the gap between
# the last chosen and the first left out is of that size the two choose
# differently, both rightly, and that position's logits differ by 0.14 to
# 0.86 where the others read 0.014 to 0.035. With a slice of the experts
# held only the swaps that touch the slice show; with all 64 held every
# swap does: 4 to 13 of a run's 31 compared positions (the configuration
# file has the readings), so a MEDIAN over a set of 15 or 16 positions is
# itself a swapped position in one run of seven. A set's LOGITS are
# therefore judged by the larger of their LOWER QUARTILE (it stays among
# the positions that chose alike unless three quarters swap; every fault
# meant for the logits moves EVERY position) and of the largest error
# among the CLEAR positions: those whose ``routing_margin`` exceeds
# CLEAR_MARGIN in every layer (over twice the widest margin at which the
# two chose differently on the chip; few positions are that clear, and
# when one is, it counts). The ROWS the served path left in the pool are
# compared in the first layer (the logits do not see the cache's
# precision): ROWS_WEIGHT times their error enters a set's number.
#
# A lower quartile lets three quarters of a set read anything, and the
# first layer's rows see a position's token and place but no attention.
# What holds EVERY position is the rows of the first layer with a router
# (layer 1 here): the stream that enters it has been through layer 0's
# attention (at decode: the pool's writer and walk), both its mixings and
# the dense feed-forward, and through no router, so no position of it can
# have chosen other experts. Each position's row there is compared with
# the reference's by relative norm, all of the prompt's and all of the
# reply's, and STREAM_WEIGHT times the LARGEST enters the set's number: a
# fault that starts at a later decode step, a block the walk misses for
# some positions or a chunk prefilled wrong shows in its own position's
# row whatever the others read (``LATE_FAULTS`` plants one; the quartile
# passes it). What is confined to a deeper layer on part of the positions
# is still held by the quartile and the clear positions alone.
#
# Neither sees the precision of the COEFFICIENTS: computed in bfloat16
# they move the logits and the rows by a tenth of what the bf16 stream's
# own rounding does (the configuration file has the readings). So the
# coefficients are compared too, alone: the program's own function
# (``llm/model.py mhc_coefficients``: x~, the product with phi, the
# sigmoids, exp, Sinkhorn) is handed the streams that ENTER layer
# COEFF_LAYER in the reference's forward at the compared positions,
# rounded to the model's dtype as the program's stream is, and its Hpre,
# Hpost and Hres are compared with the reference's for the SAME input, by
# relative norm, for both sub-layers' leaves. On one input nothing but the
# arithmetic separates the two. A set's number is the largest of its
# judged logits, ROWS_WEIGHT times its rows' error and COEFF_WEIGHT times
# the coefficients' error: the coefficients' own limit is
# ``parity_tolerance`` / COEFF_WEIGHT.
#
# in units of the score s + b (the configuration file has the readings)
CLEAR_MARGIN = 0.02
ROWS_WEIGHT = 8.0
COEFF_WEIGHT = 100.0
STREAM_WEIGHT = 2.0
# the reply's position from which a late fault holds
LATE_FROM = DECODE_STEPS // 2
# the first layer that the four rows enter unequal (layer 0 reads the
# copy-in, whose mix is the embedding times a scalar whatever Hpre is)
COEFF_LAYER = 1


def coefficients_err(params, cfg, X, faults=()) -> float:
    """The program's coefficients against the reference's on the streams X
    (s, n, C) float32, with layer COEFF_LAYER's leaves: the larger of the
    two sub-layers' relative errors over [Hpre | Hpost | Hres]."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.llm import model as lm
    stack, row = ("dense_layers", COEFF_LAYER) \
        if COEFF_LAYER < cfg.n_dense_layers \
        else ("layers", COEFF_LAYER - cfg.n_dense_layers)
    lp = {k: w[row] for k, w in params[stack].items() if k.startswith("hc_")}
    s = X.shape[0]
    Xb = X.astype(jnp.dtype(cfg.dtype))         # as the program's stream
    errs = []
    for sub in ("attn", "mlp"):
        pre, post, res = lm.mhc_coefficients(
            jnp.moveaxis(Xb, 1, 0)[:, None], lp, sub, cfg)
        got = jnp.concatenate(
            [pre[:, 0].T, post[:, 0].T,
             jnp.moveaxis(res[:, :, 0], -1, 0).reshape(s, -1)], -1)
        with jax.default_matmul_precision("highest"):
            wpre, wpost, wres = mixing(Xb.astype(jnp.float32), lp, sub, cfg,
                                       faults)
        want = jnp.concatenate([wpre, wpost, wres.reshape(s, -1)], -1)
        errs.append(float(jnp.linalg.norm(got - want)
                          / jnp.linalg.norm(want)))
    return max(errs)


def _quartile(errs) -> float:
    import statistics
    return statistics.quantiles(errs, n=4)[0]


def _judged(errs, margins) -> float:
    """The larger of the lower quartile of all positions' errors and the
    largest error of a clear position."""
    return max([_quartile(errs)]
               + [e for e, m in zip(errs, margins) if m > CLEAR_MARGIN])


def _reference(params, toks, cfg, faults, at) -> dict:
    """The reference's full forward over ``toks`` with ``faults``: the
    logits and margins of the positions ``at``, every layer's rows
    (tokens, width) and the streams that enter layer COEFF_LAYER there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rows, streams = [], []

    def stream_seen(layer, X):
        if layer == COEFF_LAYER:
            streams.append(X[0, at[0]:at[-1] + 1])

    def rows_seen(layer, r):
        # a layer at a time: the next layer's program is enqueued, and
        # its float32 temporaries allocated, when this one has run (all
        # six in flight took 1.6 GB more beside the served model)
        rows.append(jax.block_until_ready(r[0]))
    want, margins = forward_margins(
        params, jnp.asarray([toks], jnp.int32), cfg, faults, at=at,
        rows_seen=rows_seen, stream_seen=stream_seen)
    return {"logits": np.asarray(want)[0], "margins": np.asarray(margins)[0],
            "rows": rows, "stream": streams[0]}


def _late(sound: dict, faulty: dict, at, start: int) -> dict:
    """``sound`` before position ``start``, ``faulty`` from it on."""
    import jax.numpy as jnp
    cut = start - at[0]

    def join(a, b, n):
        return jnp.concatenate([a[:n], b[n:]])
    return {"logits": join(sound["logits"], faulty["logits"], cut),
            "margins": join(sound["margins"], faulty["margins"], cut),
            "rows": [join(a, b, start)
                     for a, b in zip(sound["rows"], faulty["rows"])],
            "stream": join(sound["stream"], faulty["stream"], cut)}


def compared(got: dict, params, cfg, prompt_len: int, faults=()) -> dict:
    """``served``'s logits and pool rows against the reference's full
    forward over the same tokens: for the prefills and for the decode
    steps the largest of the logits' judged error (``_judged``),
    ROWS_WEIGHT times the first layer's rows' error, STREAM_WEIGHT times
    the largest error of ANY position's row in the first layer with a
    router (``stream_layer``) and
    COEFF_WEIGHT times the coefficients' error (``coefficients_err``, on
    the compared positions' streams; it enters both sets' numbers), with
    every position's error and margin, the quartiles, the medians and
    every layer's rows' errors (the prompt's, the reply's) beside them."""
    import statistics

    import jax.numpy as jnp
    import numpy as np
    first = prompt_len - len(got["prefills"])
    n = len(got["prefills"]) + len(got["steps"])
    at = range(first, first + n)
    late = tuple(f[len("late_"):] for f in faults if f in LATE_FAULTS)
    faults = tuple(f for f in faults if f not in LATE_FAULTS)
    ref = _reference(params, got["toks"], cfg, faults, at)
    if late:
        ref = _late(ref, _reference(params, got["toks"], cfg,
                                    faults + late, at),
                    at, prompt_len + LATE_FROM)
    parts = (slice(0, prompt_len), slice(prompt_len, None))
    by_row = [jnp.linalg.norm(got["rows"][layer].astype(jnp.float32) - want,
                              axis=-1)
              for layer, want in enumerate(ref["rows"])]
    rows_errs = [[float(jnp.linalg.norm(d[part])
                        / jnp.linalg.norm(want[part])) for part in parts]
                 for d, want in zip(by_row, ref["rows"])]
    # the first layer with a router: no position has met one before it
    layer = min(cfg.n_dense_layers, cfg.n_layers - 1)
    each = np.asarray(by_row[layer]
                      / jnp.linalg.norm(ref["rows"][layer], axis=-1))
    pre_stream, dec_stream = (float(each[part].max()) for part in parts)
    coeff = coefficients_err(params, cfg, ref["stream"], faults)
    want = np.asarray(ref["logits"])
    marg = [float(m) for m in ref["margins"]]
    got_all = got["prefills"] + got["steps"]
    errs = [rel_err(g, want[i]) for i, g in enumerate(got_all)]
    cut = len(got["prefills"])
    pre, dec, pre_m, dec_m = errs[:cut], errs[cut:], marg[:cut], marg[cut:]
    pre_rows, dec_rows = rows_errs[0]
    return {"prefill_rel_err": max(_judged(pre, pre_m),
                                   ROWS_WEIGHT * pre_rows,
                                   STREAM_WEIGHT * pre_stream,
                                   COEFF_WEIGHT * coeff),
            "decode_rel_err": max(_judged(dec, dec_m),
                                  ROWS_WEIGHT * dec_rows,
                                  STREAM_WEIGHT * dec_stream,
                                  COEFF_WEIGHT * coeff),
            "coeff_rel_err": coeff,
            "prefill_logits_rel_err": _judged(pre, pre_m),
            "decode_logits_rel_err": _judged(dec, dec_m),
            "prefill_rows_rel_err": pre_rows,
            "decode_rows_rel_err": dec_rows,
            "prefill_stream_rel_err": pre_stream,
            "decode_stream_rel_err": dec_stream,
            "stream_layer": layer,
            "stream_median_rel_err": float(np.median(each)),
            "decode_stream_rel_errs": [float(e) for e in each[parts[1]]],
            "rows_rel_errs": rows_errs,
            "prefill_quartile_rel_err": _quartile(pre),
            "decode_quartile_rel_err": _quartile(dec),
            "prefill_median_rel_err": statistics.median(pre),
            "decode_median_rel_err": statistics.median(dec),
            "clear_positions": sum(m > CLEAR_MARGIN for m in marg),
            "prefill_rel_errs": pre, "decode_rel_errs": dec,
            "prefill_margins": pre_m, "decode_margins": dec_m,
            "finite": bool(np.isfinite(np.asarray(got_all)).all()
                           and np.isfinite(rows_errs).all()
                           and np.isfinite(each).all()
                           and np.isfinite(coeff)),
            "prompt_len": prompt_len}


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool,
                 cache_dtype="bfloat16") -> dict:
    """Prefill a seeded prompt LONGER than the largest bucket through the
    served chunked prefill (past YaRN's original 4,096 positions at the
    cell's length), write its rows into a paged pool of the latent kind,
    decode through the served decode path, and compare with the
    reference's full forward over the same tokens: ``served``,
    ``compared``."""
    import random
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    got = served(params, cfg, toks, buckets=buckets, block=block,
                 kv_impl=kv_impl, interpret=interpret,
                 cache_dtype=cache_dtype)
    return compared(got, params, cfg, prompt_len)


# --- what the kernels require ----------------------------------------------


def _attn(model: dict) -> dict:
    return dict(h=model["num_attention_heads"], lat=model["kv_lora_rank"],
                rope=model["qk_rope_head_dim"],
                qk=model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
                v=model["v_head_dim"], layers=model["num_hidden_layers"])


def latent_decode_required_bytes(model: dict, contexts, itemsize=2) -> int:
    """Bytes the decode attention of ``contexts`` (one entry a slot-step:
    the positions the slot holds, the new token included) REQUIRES over
    all layers: every position's cache row [c | kr] of kv_lora_rank +
    qk_rope_head_dim = 576 values ONCE (it is key and value both;
    whatever the pool pads it to), plus each slot's absorbed queries in
    (bf16) and weighted sums out (f32)."""
    a = _attn(model)
    row = a["lat"] + a["rope"]
    rows = sum(contexts) * row * itemsize
    qo = len(contexts) * a["h"] * (row * 2 + a["lat"] * 4)
    return a["layers"] * (rows + qo)


def latent_decode_required_flops(model: dict, contexts) -> int:
    """2 * heads * ((kv_lora_rank + rope) for the scores + kv_lora_rank
    for the weighted sum) a context position a layer."""
    a = _attn(model)
    return 2 * a["h"] * (2 * a["lat"] + a["rope"]) * sum(contexts) \
        * a["layers"]


def flash_prefill_required_flops(model: dict, prompts) -> int:
    """Operations the prefill attention of ``prompts`` (their lengths)
    requires over all layers on MATERIALISED heads: 2 * (192 for the
    score + 128 for the weighted sum) a (query, key) pair a head, t + 1
    pairs for the query at position t."""
    a = _attn(model)
    pairs = sum(n * (n + 1) // 2 for n in prompts)
    return 2 * (a["qk"] + a["v"]) * a["h"] * pairs * a["layers"]


def flash_prefill_required_bytes(model: dict, prompts, itemsize=2) -> int:
    """Q and K (192 a head) and V (128) read and O (128) written once a
    layer (K and V per head: the kernel attends the expanded rows)."""
    a = _attn(model)
    return itemsize * 2 * (a["qk"] + a["v"]) * a["h"] * sum(prompts) \
        * a["layers"]


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def gmm_decode_required_bytes(model: dict, experts_hit: float, rows: float,
                              itemsize=2) -> float:
    """Bytes the decode steps' grouped matmuls require: the three
    matrices of every expert that some row reached (``experts_hit``,
    summed over steps and layers: the engine's counter), plus the routed
    rows in and out of the three products (``rows`` assignments: x in
    twice, h out twice and in once, the result out)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * f + rows * (3 * d + 3 * f))


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token would require (no
    cell trains it; the program refuses to): 6 per matmul parameter the
    token reaches, plus attention inside the causal mask."""
    a = _attn(model)
    d, ql = model["hidden_size"], model["q_lora_rank"]
    attn = d * ql + ql * a["h"] * a["qk"] + d * (a["lat"] + a["rope"]) \
        + a["lat"] * a["h"] * (model["qk_nope_head_dim"] + a["v"]) \
        + a["h"] * a["v"] * d
    n = model["hc_mult"]
    mix = 2 * n * d * (2 * n + n * n)
    dense = min(model["first_k_dense_replace"], n_layers)
    ffn = d * model["n_routed_experts"] \
        + 3 * d * model["moe_intermediate_size"] \
        * (model["num_experts_per_tok"] + model["n_shared_experts"])
    matmul = n_layers * (attn + mix) + (n_layers - dense) * ffn \
        + dense * 3 * d * model["intermediate_size"] \
        + d * model["vocab_size"]
    return 6.0 * matmul + 3.5 * flash_prefill_required_flops(
        model, [seq]) / seq * n_layers / a["layers"]
