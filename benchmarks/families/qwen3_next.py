"""The ``qwen3_next`` family: a decoder whose layers alternate a linear
mixer (Gated DeltaNet: the gated delta rule behind a short causal
convolution) with a gated full-attention mixer, three to one, each
followed by a sparse mixture of experts with a gated shared expert
(Qwen3-Next-80B-A3B). Everything the harness asks of such a model by
name is here: the program's config object and module, the plain
reference, and the operations a trained token requires. No
``serve_parity``: the family cannot be served yet (a recurrent state and a
conv tail per slot beside the paged pools are not built).

The plain reference is independent of the code under test (nothing is
imported from ``ray_tpu.models`` or ``ray_tpu.ops``): forward pass and
loss in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision. The delta rule is the TOKEN-BY-TOKEN recurrence, a ``lax.scan``
over positions with no chunks; every held expert is computed for every
token and multiplied by the (tokens, experts) gate matrix; the router
scores all ``source_num_experts`` experts.

The architecture, as ``modeling_qwen3_next.py`` of ``transformers`` states
it (Qwen3NextRMSNorm, Qwen3NextGatedDeltaNet, Qwen3NextAttention,
Qwen3NextSparseMoeBlock, Qwen3NextForCausalLM, load_balancing_loss_func),
for hidden state x. Norms n(.; w) are zero-centred:
n(x; w) = x * rsqrt(mean(x^2) + rms_norm_eps) * (1 + w). Layer i is a full
layer when (i + 1) % full_attention_interval == 0, else linear. Every layer:

    x += mixer(n1(x));   x += moe(n2(x))

Linear mixer, y = n1(x); hk key heads and hv value heads of dk and dv,
r = hv / hk value heads to a key head:
    [q, k, v, z] = Wqkvz y     widths hk dk, hk dk, hv dv, hv dv
    [b, a] = Wba y             hv of each (departure (d): the columns'
                               order)
    [q, k, v] = silu(conv([q | k | v]))   depthwise, causal, kernel
                               linear_conv_kernel_dim, no bias, over the
                               2 hk dk + hv dv channels:
                               out[t] = sum_j w[:, j] in[t - (K - 1) + j]
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   float32,
                               one value a value head
    q = q / sqrt(sum q^2 + 1e-6) * dk^-0.5;  k = k / sqrt(sum k^2 + 1e-6)
                               per head; value head j reads key head j // r
    per value head, S (dk x dv) from 0, for each token t:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T
        o_t = S^T q_t
    o = o * rsqrt(mean(o^2) + eps) * w * silu(z)    per head; w a PLAIN
                               weight (not 1 + w)
    mixer = Wout o
Full mixer, y = n1(x); h query and kvh KV heads of hd:
    [q, gate] = Wq y           by head: its query (hd), its gate (hd)
    q = n(q; wq'), k = n(Wk y; wk')   zero-centred, over hd, per head
    RoPE, halves rotated, base rope_theta, on the first
        partial_rotary_factor * hd dimensions of a head only
    o = softmax(q k^T / sqrt(hd), causal) v
    mixer = Wo (o * sigmoid(gate))
Experts, y = n2(x):
    p = softmax(Wr y)          over all experts, float32
    S = the num_experts_per_tok largest; g_e = p_e / sum_S p  (norm_topk_prob)
    moe = sum_{e in S, e held here} g_e Wdown_e (silu(Wgate_e y) * Wup_e y)
          + sigmoid(wsg . y) Wsdown (silu(Wsgate y) * Wsup y)
After the last layer: n(.; w), then the untied head. Loss: mean
cross-entropy + router_aux_loss_coef * the load-balancing term pooled
over the routers of all layers, E * sum_e f_e pbar_e, over ALL experts.

The share: ``num_experts`` counts the experts HELD here, from
``first_expert`` of ``source_num_experts``; the routed sum above runs over
the held experts only, and that partial result goes on to the next layer,
in the program and here alike (the shared expert is whole: every chip of
the deployment computes it).

Departures from the published description:
(a) the multi-token-prediction block of the published model has no key in
    the catalog's ``config`` and is no part of the next-token
    distribution: left out of the program and of this reference;
(b) ``config.json`` has no key for the init of ``A_log``, ``dt_bias`` and
    the conv: the program draws them as flash-linear-attention's layer
    does (``ray_tpu/models/moe.py _init_linear``); this reference takes
    whatever parameters it is handed;
(c) ``train_required_flops_per_token`` counts the routed experts at their
    EXPECTED share, num_experts_per_tok * held / source_num_experts
    experts a token, because the train runner hands a reader no counter
    of the assignments that reached the held experts;
    ``gmm_required_flops_per_step`` counts the same share;
(d) the published checkpoint groups the columns of Wqkvz and Wba BY KEY
    HEAD (a head's q, its k, then the v and the z of the r value heads it
    serves; r values of b, r of a: ``fix_query_key_value_ordering``
    regroups the activations on every pass). The program holds them BY
    KIND, [q | k | v | z] and [b | a], each kind's heads in order, and so
    does this reference: a permutation of columns that a loader applies
    once, the same function of the same numbers.
"""

from __future__ import annotations

from harness import kernels


def _kinds(model: dict) -> tuple:
    every = model["full_attention_interval"]
    return tuple("full" if (i + 1) % every == 0 else "linear"
                 for i in range(model["num_hidden_layers"]))


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"]:
        raise ValueError("every layer must be sparse")
    held = model["num_experts"]
    total = model.get("source_num_experts", held)
    hd = model["head_dim"]
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_size=hd,
        ffn_dim=model["moe_intermediate_size"], n_experts=total,
        experts_per_token=model["num_experts_per_tok"],
        experts_held=held if held != total else 0,
        first_expert=model.get("first_expert", 0),
        norm_topk_prob=bool(model["norm_topk_prob"]),
        n_shared_experts=model["shared_expert_intermediate_size"]
        // model["moe_intermediate_size"],
        shared_expert_gate=True, layer_types=_kinds(model),
        qk_head_norm=True, attn_output_gate=True, zero_centered_norm=True,
        rotary_dim=int(hd * model["partial_rotary_factor"]),
        linear_key_heads=model["linear_num_key_heads"],
        linear_value_heads=model["linear_num_value_heads"],
        linear_key_dim=model["linear_key_head_dim"],
        linear_value_dim=model["linear_value_head_dim"],
        linear_conv_kernel=model["linear_conv_kernel_dim"],
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


# --- the plain reference ---------------------------------------------------

# what a DEFECTIVE program would compute instead, for the controls of
# ``tools/linear_attn_parity_sensitivity.py`` only; () is the model
FAULTS = ("state_reset_64", "g_zero", "beta_one", "no_conv",
          "no_output_gate", "rope_all", "top_k_less_one", "no_shared_gate",
          "all_experts", "state_bf16_64", "gates_bf16")


def expert_layer(y, lp, cfg, faults=()):
    """y (..., d) float32 normed rows, ``lp`` one layer's parameters ->
    (the held experts' gated sum, the gated shared expert, the 0/1 matrix
    of chosen experts (..., E), the router's probabilities (..., E)).
    Every held expert is computed for every row."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    first, held = cfg.first_expert, cfg.experts_held or cfg.n_experts
    top_k = cfg.experts_per_token - ("top_k_less_one" in faults)
    dense = {k: v.astype(f32) for k, v in lp.items()
             if k not in ("w_gate", "w_up", "w_down")}

    def experts(p_kept):
        """sum_e p_kept[..., e] * Wdown_e (silu(Wgate_e y) * Wup_e y);
        an expert's weights are upcast one expert at a time."""
        def one(acc, e):
            w_gate, w_up, w_down, p = e
            hid = jax.nn.silu(y @ w_gate.astype(f32)) * (y @ w_up.astype(f32))
            return acc + p[..., None] * (hid @ w_down.astype(f32)), None
        out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
            lp["w_gate"], lp["w_up"], lp["w_down"],
            jnp.moveaxis(p_kept, -1, 0)))
        return out

    p = jax.nn.softmax(y @ dense["router"], -1)              # (..., E)
    # S as a 0/1 matrix; equal probabilities go to the lower index
    chosen = jax.nn.one_hot(jax.lax.top_k(p, top_k)[1], p.shape[-1],
                            dtype=f32).sum(-2)
    p_kept = chosen * p
    if cfg.norm_topk_prob:
        p_kept = p_kept / jnp.sum(p_kept, -1, keepdims=True)
    routed = experts(p_kept[..., first:first + held])
    if "all_experts" in faults:
        # a layer that takes its held experts for all of them: every
        # token's k assignments land here
        p_here = jax.nn.softmax(
            (y @ dense["router"])[..., first:first + held], -1)
        kept = p_here * jax.nn.one_hot(
            jax.lax.top_k(p_here, top_k)[1], held, dtype=f32).sum(-2)
        routed = experts(kept / jnp.sum(kept, -1, keepdims=True))
    shared = (jax.nn.silu(y @ dense["shared_gate"])
              * (y @ dense["shared_up"])) @ dense["shared_down"]
    if "no_shared_gate" not in faults:
        shared = jax.nn.sigmoid(y @ dense["shared_expert_gate"]) * shared
    return routed, shared, chosen, p


def _f32_forward(params, tokens, cfg, faults=()):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, the
    load-balancing term)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, s = tokens.shape
    eps = cfg.norm_eps
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv, r = cfg.linear_key_dim, cfg.linear_value_dim, hv // hk

    def unit(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)

    def bf16(x):        # rounded to bfloat16's 8 bits, kept in float32
        return jax.lax.reduce_precision(x, 8, 7)

    def n(x, w):
        return unit(x) * (1.0 + w.astype(f32))

    rd = hd if "rope_all" in faults else cfg.rotary_dim
    half = rd // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(s, dtype=f32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(x):
        x1, x2, rest = x[..., :half], x[..., half:rd], x[..., rd:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                rest], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))

    def full(y, mp):
        qg = (y @ mp["wq"]).reshape(b, s, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        q = rope(n(q, mp["q_norm"]))
        k = rope(n((y @ mp["wk"]).reshape(b, s, kvh, hd), mp["k_norm"]))
        v = (y @ mp["wv"]).reshape(b, s, kvh, hd)
        k, v = jnp.repeat(k, h // kvh, axis=2), jnp.repeat(v, h // kvh, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(f32(hd))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        if "no_output_gate" not in faults:
            o = o * jax.nn.sigmoid(gate)
        return o.reshape(b, s, h * hd) @ mp["wo"]

    def linear(y, mp):
        kw, vw = hk * dk, hv * dv
        qkvz = y @ mp["w_qkvz"]                      # [q | k | v | z]
        mixed, z = qkvz[..., :2 * kw + vw], qkvz[..., 2 * kw + vw:]
        z = z.reshape(b, s, hv, dv)
        ba = y @ mp["w_ba"]                          # [b | a]
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(mp["A_log"]) * jax.nn.softplus(
            ba[..., hv:] + mp["dt_bias"])
        if "g_zero" in faults:
            g = jnp.zeros_like(g)
        if "beta_one" in faults:
            beta = jnp.ones_like(beta)
        if "gates_bf16" in faults:
            g, beta = bf16(g), bf16(beta)
        if "no_conv" not in faults:
            taps = mp["conv"].shape[1]
            padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
            mixed = sum(padded[:, j:j + s] * mp["conv"][:, j]
                        for j in range(taps))
        mixed = jax.nn.silu(mixed)
        q = mixed[..., :hk * dk].reshape(b, s, hk, dk)
        k = mixed[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
        v = mixed[..., 2 * hk * dk:].reshape(b, s, hv, dv)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)

        def token(S, x):
            t, q, k, v, g, beta = x
            if "state_reset_64" in faults:
                S = jnp.where(t % 64 == 0, 0.0, S)
            if "state_bf16_64" in faults:
                S = jnp.where(t % 64 == 0, bf16(S), S)
            S = S * jnp.exp(g)[..., None, None]              # (b, hv, dk, dv)
            d = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S))
            S = S + k[..., :, None] * d[..., None, :]
            return S, jnp.einsum("bhk,bhkv->bhv", q, S)
        _, o = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), f32), (
            jnp.arange(s), *(jnp.moveaxis(a, 1, 0)
                             for a in (q, k, v, g, beta))))
        o = unit(jnp.moveaxis(o, 0, 1)) * mp["gdn_norm"] * jax.nn.silu(z)
        return o.reshape(b, s, hv * dv) @ mp["w_out"]

    def up(tree):
        return {k: v.astype(f32) for k, v in tree.items()}

    stats, at = [], {"linear": 0, "full": 0}
    x = jnp.take(params["embed"], tokens, axis=0).astype(f32)
    for i, kind in enumerate(cfg.layer_types):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        mp = up(jax.tree.map(lambda a: a[at[kind]],
                             params[kind + "_layers"]))
        at[kind] += 1
        y = n(x, lp["attn_norm"])
        x = x + (full if kind == "full" else linear)(y, mp)
        y = n(x, lp["mlp_norm"])
        routed, shared, chosen, p = expert_layer(y, lp, cfg, faults)
        x = x + routed + shared
        stats.append((jnp.mean(chosen, (0, 1)), jnp.mean(p, (0, 1))))
    chosen, p = (jnp.stack(a) for a in zip(*stats))          # (L, E)
    x = n(x, params["final_norm"])
    aux = p.shape[1] * jnp.sum(jnp.mean(chosen, 0) * jnp.mean(p, 0))
    return x @ params["lm_head"].astype(f32), aux


def forward(params, tokens, cfg):
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: _f32_forward(p, t, cfg)[0])(
            params, tokens)


def logits_and_loss(params, batch, cfg, faults=()):
    """The reference's logits (b, s, vocab) and its loss against
    ``batch["targets"]`` (mean cross-entropy + the weighted
    load-balancing term, which the program's ``loss_fn`` includes too),
    from one forward. ``faults``: see ``FAULTS``."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, targets):
        logits, aux = _f32_forward(params, tokens, cfg, faults)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits, jnp.mean(logz - gold) + cfg.aux_loss_weight * aux
    with jax.default_matmul_precision("highest"):
        return jax.jit(f)(params, batch["tokens"], batch["targets"])


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token requires, with no
    recomputation: 6 per matmul parameter the token touches (the mixers'
    projections, the conv's taps, the router, the shared expert with its
    gate, the head; of the routed experts the EXPECTED share, departure
    (c); the embedding lookup is a gather), causal attention in the full
    layers, and in the linear layers the recurrence's own 6 dk dv a token
    a value head (decay, read, write and output: about 3 multiply-adds an
    element of the state), times 3 for forward and backward."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    h, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["head_dim"]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    kinds = _kinds({**model, "num_hidden_layers": n_layers})
    n_full = kinds.count("full")
    n_linear = n_layers - n_full
    kw, vw = hk * dk, hv * dv
    linear = d * (2 * kw + 2 * vw) + d * 2 * hv + vw * d \
        + (2 * kw + vw) * model["linear_conv_kernel_dim"]
    full = 2 * d * h * hd + 2 * d * kvh * hd + h * hd * d
    total = model.get("source_num_experts", model["num_experts"])
    routed = model["num_experts_per_tok"] * model["num_experts"] / total
    moe = d * total + d + 3 * d * model["shared_expert_intermediate_size"] \
        + routed * 3 * d * f
    matmul = n_linear * linear + n_full * full + n_layers * moe \
        + d * model["vocab_size"]
    pairs_per_token = (seq + 1) / 2
    attn = (kernels.flash_fwd_flops(1, h, hd)
            + kernels.flash_bwd_flops(1, h, hd)) * pairs_per_token * n_full
    rule = 3 * 6 * dk * dv * hv * n_linear
    return 6.0 * matmul + attn + rule


def gmm_required_flops_per_step(model: dict, n_layers: int,
                                tokens: int) -> float:
    """Operations the grouped matmuls of one train step require, as
    ``families/moe.py`` counts them (2 per expert parameter a token is
    routed to, times the passes the configured step makes: the forward, the
    full remat's second forward, d_lhs and d_rhs), with the routed experts
    at the EXPECTED share of departure (c): num_experts_per_tok * held /
    source_num_experts experts a token. The step's own ``moe_local_share``
    read 6.07%, 6.21% and 6.57% on three seeds against the expected 6.25%
    (my chip runs, PR 45), so the count is within about 5% of the rows
    that did reach the held experts."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    total = model.get("source_num_experts", model["num_experts"])
    routed = model["num_experts_per_tok"] * model["num_experts"] / total
    recomputed = model["deployment"]["model_overrides"].get(
        "remat_policy", "full") == "full"
    passes = 4 if recomputed else 3
    return 2.0 * routed * 3 * d * f * tokens * n_layers * passes
