"""The ``mistral4`` family (Mistral-Small-4-119B-2603, ``model_type:
mistral4``): a decoder of multi-head LATENT attention (MLA) layers, each
with softmax-routed experts and a shared expert, SERVED on one device's
share of an expert-parallel deployment. Everything the harness asks of
such a model by name is here: the program's config object and module, the
plain reference, the serving comparison, and what its kernels require
(bytes, operations) for the rooflines.

The plain reference is independent of the code under test: the forward
pass in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision, a layer at a time, in the MATERIALISED form only: per-head keys
and values from every token's latent row, full causal softmax. No cache,
no kernel, no absorption, no sort, no grouped matmul; attention is cut
into blocks of query rows only so that 10k tokens fit; every held expert
is computed for every token and weighted by the (tokens, experts) matrix
that holds a token's gate at its chosen experts and 0 elsewhere.

The architecture, from the published ``config.json`` (36 layers, hidden
4096, 32 heads, vocabulary 131,072, untied; no biases), for hidden state
x, position t:

    attention (every layer):
      cq = RMSNorm_{q_lora_rank=1024}(x Wq_a);   q = cq Wq_b
          -> 32 heads x 128 = [q_nope 64 | q_rope 64]
      [ckv 256 | kr 64] = x Wkv_a;   c = RMSNorm_{kv_lora_rank=256}(ckv)
      q_rope, kr = rope(q_rope, t), rope(kr, t)
          kr is ONE 64-vector a token, shared by all heads; pairs are
          (2i, 2i + 1) (``rope_interleave``)
      the cache row of token t, this layer: [c | kr], 320 values
      [k_nope 64 | v 128]_h = c Wkv_b[h];   k_h = [k_nope_h | kr]
      scale s = 128^-0.5 * m^2                               (assumed b)
      o_h = softmax_{j<=t}( a(t) * s * q_h . k_h(j) ) v_h(j)
      x = x + [o_1 .. o_32] Wo
    rope: YaRN, ``rope_parameters``: theta 10000, factor 128, original
      8192, beta_fast 32, beta_slow 1 over the 64 rotary dims: a
      per-frequency blend of theta_i and theta_i / 128 by the linear ramp
      between the two correction dims; ``mscale = mscale_all_dim = 1`` so
      cos / sin carry factor 1
    a(t) = 1 + ``llama_4_scaling_beta`` * ln(1 + floor(t / 8192))
      on the query; 1 below 8,192                            (assumed c)
    feed-forward (every layer; ``first_k_dense_replace`` 0):
      p = softmax(x Wr) over all 128, float32                (assumed a)
      S = the 4 largest;  g_e = p_e / sum_S p   (``norm_topk_prob``,
      ``routed_scaling_factor`` 1, ``n_group`` = ``topk_group`` = 1)
      x = x + sum_{e in S} g_e E_e(x) + E_shared(x)
      E(x) = Wdown (silu(Wgate x) * Wup x), width 2048
    pre-norm RMSNorm (eps 1e-6) before each sub-layer; final RMSNorm;
    untied head.

The program keeps ``Wkv_b`` as two leaves by head, ``wk_b`` (heads, 64,
256) = W_UK_h^T and ``wv_b`` (heads, 256, 128) = W_UV_h; the reference
reads the same leaves and expands every row with them.

THE SHARE. The configuration serves one chip's share of an 8-chip
expert-parallel deployment: ``experts_held`` of the 128 experts from
``first_expert`` on, and a slice of the vocabulary. The router scores all
128 experts; the reference, given the same share, sums over the chosen
experts THIS device holds (plus the shared expert) and stops there:
``layer_share``. The eight shares' routed parts plus the shared expert
once add up to the uncut layer (tests/test_zz_hybrid_serving.py).

Assumed, where ``config.json`` is silent (each also under ``assumed`` in
the configuration file; none changes a shape, a byte count or a step's
cost):
  (a) ``scoring_func`` is not in the config: softmax, the family's
      convention since Mixtral (with ``norm_topk_prob`` it equals a
      softmax over the 4 chosen logits);
  (b) m = 0.1 * ``mscale_all_dim`` * ln(``factor``) + 1 = 1.485 multiplies
      the softmax scale squared, as the modelling code that these YaRN
      keys come from does;
  (c) the query scale's form, from the key's name and the family's
      published modelling of it (Llama 4's own differs only in
      floor((t + 1) / 8192));
  (d) no vision tower: the catalog's config is the language model's and
      gives no size of the encoder; the configuration serves text;
  (e) ``torch_dtype`` bfloat16.
Departures from the published description: none besides (a)-(e) and the
share. ``FAULTS`` are the reference's deliberate departures, one at a
time, for ``tools/latent_parity_sensitivity.py``.
"""

from __future__ import annotations

import functools
import math

from harness.reference import rel_err

# the reference's deliberate faults (tools/latent_parity_sensitivity.py):
# cache rows through float8 e4m3 / int8 (one scale a row), kr left out
# of the scores, a(t) = 1, YaRN off (plain theta_i), m = 1
FAULTS = ("rows_float8", "rows_int8", "kr_left_out", "query_scale_off",
          "yarn_off", "mscale_off")


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    rp = model["rope_parameters"]
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    if model["first_k_dense_replace"] != 0:
        raise ValueError("leading dense layers beside latent attention")
    if rp["rope_type"] != "yarn" or not model["rope_interleave"]:
        raise ValueError("the family's rotary is YaRN on interleaved pairs")
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    if nope + rope != model["qk_head_dim"] \
            or model["v_head_dim"] != model["head_dim"] \
            or model["qk_head_dim"] != model["head_dim"]:
        raise ValueError("query/key and value heads of different sizes")
    n = model["num_hidden_layers"]
    held = model["n_routed_experts"]
    total = model.get("source_n_routed_experts", held)
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_size=model["head_dim"],
        ffn_dim=model["moe_intermediate_size"],
        n_experts=total, experts_per_token=model["num_experts_per_tok"],
        experts_held=held if held != total else 0,
        first_expert=model.get("first_expert", 0),
        norm_topk_prob=bool(model["norm_topk_prob"]), scoring="softmax",
        routed_scaling=float(model["routed_scaling_factor"]),
        n_shared_experts=model["n_shared_experts"],
        layer_types=("latent",) * n,
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=model["v_head_dim"],
        rope_factor=float(rp["factor"]),
        rope_original_len=rp["original_max_position_embeddings"],
        rope_beta_fast=float(rp["beta_fast"]),
        rope_beta_slow=float(rp["beta_slow"]),
        rope_mscale=float(rp["mscale"]),
        rope_mscale_all_dim=float(rp["mscale_all_dim"]),
        query_scale_beta=float(rp["llama_4_scaling_beta"]),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(rp["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **{**model["deployment"].get("model_overrides", {}), **overrides})


def module():
    """The program's module that makes this family's parameters."""
    from ray_tpu.models import moe
    return moe


# --- the plain reference ---------------------------------------------------

QUERY_BLOCK = 512       # query rows a block of the reference's attention


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _swiglu(x, w_gate, w_up, w_down):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return (jax.nn.silu(x @ w_gate.astype(f32)) * (x @ w_up.astype(f32))) \
        @ w_down.astype(f32)


def gates(x, router, cfg):
    """(tokens, n_experts) float32: a token's gate at each of its chosen
    experts, 0 elsewhere. Equal scores go to the lower index."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(x @ router.astype(jnp.float32), -1)
    chosen = jax.nn.one_hot(
        jax.lax.top_k(p, cfg.experts_per_token)[1], p.shape[-1],
        dtype=jnp.float32).sum(-2)
    g = chosen * p
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * cfg.routed_scaling


def held_margin(x, router, cfg):
    """(tokens,) float32, in units of the router's LOGITS (the softmax
    keeps their order): how far a token's logits would have to move
    before it got ANOTHER set of this share's experts. A held expert
    among the chosen falls out when it sinks under the first logit left
    out; a held expert left out comes in when it passes the last logit
    chosen; the margin is the smallest such distance."""
    import jax
    import jax.numpy as jnp
    k = cfg.experts_per_token
    v = x @ router.astype(jnp.float32)
    top = jax.lax.top_k(v, k + 1)[0]
    last_in, first_out = top[:, k - 1:k], top[:, k:k + 1]
    e = jnp.arange(v.shape[-1])
    held = (e >= cfg.first_expert) & (e < cfg.first_expert + cfg.n_held)
    chosen = v >= last_in
    falls = jnp.where(held & chosen, v - first_out, jnp.inf)
    comes = jnp.where(held & ~chosen, last_in - v, jnp.inf)
    return jnp.minimum(falls.min(-1), comes.min(-1))


def routed_share(x, lp, cfg, expert=None):
    """sum over the chosen experts THIS share holds of g_e E_e(x), for
    rows x (tokens, d) float32: every held expert computed for every
    token, one expert's float32 copy at a time. ``expert(name, e)`` gives
    held expert e's matrix; by default ``lp[name][e]``."""
    import jax
    import jax.numpy as jnp
    if expert is None:
        def expert(name, e):
            return jax.lax.dynamic_index_in_dim(lp[name], e, keepdims=False)
    g = gates(x, lp["router"], cfg)
    mine = jax.lax.dynamic_slice_in_dim(g, cfg.first_expert, cfg.n_held, 1)

    def one(acc, e):
        out = _swiglu(x, expert("w_gate", e), expert("w_up", e),
                      expert("w_down", e))
        return acc + mine[:, e][:, None] * out, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(cfg.n_held, dtype=jnp.int32))
    return out


def _shared_expert(x, lp):
    return _swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def layer_share(x, lp, cfg):
    """This share's part of one layer's feed-forward: its routed part
    plus the shared expert."""
    return routed_share(x, lp, cfg) + _shared_expert(x, lp)


def yarn_inverse_frequencies(cfg, yarn=True):
    """(rope / 2,) float64 numpy: theta_i, blended with theta_i / factor
    by YaRN's linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` turns over the original length."""
    import numpy as np
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    theta = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn or not cfg.rope_factor:
        return theta

    def correction_dim(turns):
        return dim * math.log(cfg.rope_original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return theta / cfg.rope_factor * ramp + theta * (1.0 - ramp)


def _mscale(factor, m):
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def _fake_quant(x, how):
    """x's rows rounded through float8 e4m3 or int8, one scale a row."""
    import jax
    import jax.numpy as jnp
    peak = jnp.max(jnp.abs(x), -1, keepdims=True)
    peak = jnp.where(peak > 0, peak, 1.0)   # a pool's unwritten rows are 0
    if how.endswith("int8"):
        scale = peak / 127.0
        return jnp.round(x / scale) * scale
    scale = peak / 240.0
    # by reduce_precision: the chip's compiler removes a convert to
    # float8 and back as excess precision
    return jax.lax.reduce_precision(x / scale, 4, 3) * scale


def _attention(x, lp, cfg, faults):
    """One latent attention on normed rows x (s, d) float32, materialised:
    (s, heads * v) float32 before Wo, and the positions' cache rows
    [c | kr] (s, kv_lora_rank + rope) as a cache would keep them."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = x.shape[0]
    h, eps = cfg.n_heads, cfg.norm_eps
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    lat = cfg.kv_lora_rank
    cq = _rms(x @ lp["wq_a"].astype(f32), lp["q_a_norm"], eps)
    q = (cq @ lp["wq_b"].astype(f32)).reshape(s, h, nope + rope)
    ckr = x @ lp["wkv_a"].astype(f32)
    c, kr = _rms(ckr[:, :lat], lp["kv_norm"], eps), ckr[:, lat:]
    t = jnp.arange(s)
    inv = jnp.asarray(yarn_inverse_frequencies(cfg, "yarn_off" not in faults),
                      f32)
    ang = t.astype(f32)[:, None] * inv[None, :]
    # mscale / mscale_all_dim on cos and sin: 1 for this family
    factor = _mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor

    def rotate(y):      # pairs (2i, 2i + 1) of the last axis; y (s, ..., rope)
        shape = y.shape
        y = y.reshape(*shape[:-1], rope // 2, 2)
        co = cos.reshape(s, *(1,) * (y.ndim - 3), rope // 2)
        si = sin.reshape(s, *(1,) * (y.ndim - 3), rope // 2)
        return jnp.stack([y[..., 0] * co - y[..., 1] * si,
                          y[..., 1] * co + y[..., 0] * si], -1).reshape(shape)
    q_rope, kr = rotate(q[..., nope:]), rotate(kr)
    for how in ("rows_float8", "rows_int8"):
        if how in faults:       # what a cache of that precision would keep
            c, kr = _fake_quant(c, how), _fake_quant(kr, how)
    rows = jnp.concatenate([c, kr], -1)
    k_nope = jnp.einsum("sc,hdc->shd", c, lp["wk_b"].astype(f32))
    v = jnp.einsum("sc,hcd->shd", c, lp["wv_b"].astype(f32))
    if "kr_left_out" in faults:
        kr, q_rope = jnp.zeros_like(kr), jnp.zeros_like(q_rope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, None, :], (s, h, rope))], -1)
    m = 1.0 if "mscale_off" in faults \
        else _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    scale = (nope + rope) ** -0.5 * m * m
    a = jnp.ones((s,), f32) if "query_scale_off" in faults else \
        1.0 + cfg.query_scale_beta * jnp.log1p(
            (t // max(cfg.rope_original_len, 1)).astype(f32))
    q = jnp.concatenate([q[..., :nope], q_rope], -1) * a[:, None, None]

    # full causal softmax, a block of query rows at a time
    pad = -s % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, h, nope + rope)
    tb = jnp.pad(t, (0, pad), constant_values=s).reshape(-1, QUERY_BLOCK)

    def block(args):
        qi, ti = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        sc = jnp.where((t[None, :] <= ti[:, None])[None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    o = jax.lax.map(block, (qb, tb)).reshape(-1, h * cfg.v_head_dim)[:s]
    return o, rows


def _f32_layer(x, stack, row, *, cfg, faults=()):
    """Layer ``row`` (traced: one program for every layer) of the stacked
    parameters on x (s, d) float32 -> (x, every position's
    ``held_margin`` in this layer, every position's cache row). The
    experts' matrices are taken out of the stack one at a time."""
    import jax
    f32 = x.dtype
    big = ("w_gate", "w_up", "w_down")
    lp = {name: jax.lax.dynamic_index_in_dim(w, row, keepdims=False)
          for name, w in stack.items() if name not in big}
    eps = cfg.norm_eps
    o, rows = _attention(_rms(x, lp["attn_norm"], eps), lp, cfg, faults)
    x = x + o @ lp["wo"].astype(f32)
    y = _rms(x, lp["mlp_norm"], eps)

    def expert(name, e):
        return jax.lax.dynamic_index_in_dim(
            stack[name].reshape(-1, *stack[name].shape[2:]),
            row * cfg.n_held + e, keepdims=False)
    m = routed_share(y, lp, cfg, expert) + _shared_expert(y, lp)
    return x + m, held_margin(y, lp["router"], cfg), rows


@functools.lru_cache(maxsize=None)
def _layer_program(cfg, faults):
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_f32_layer, cfg=cfg, faults=faults),
        in_axes=(0, None, None)))


def forward_margins(params, tokens, cfg, faults=(), at=None, rows_seen=None):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, margins
    (b, s): a position's smallest ``held_margin`` over the layers); with
    ``at`` (positions), the logits and margins of those positions only
    (the head is the widest product); ``rows_seen(layer, rows)`` is
    handed each layer's cache rows [c | kr] (b, s, 320 at the published
    widths) as they are computed. Each layer is one call of one
    program handed the whole parameter stack, of which it reads its own
    row (an expert's matrices one expert at a time): the float32 copies
    alive at once are one layer's attention and one expert, so it runs
    beside the served model on the chip."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        margins = jnp.full(tokens.shape, jnp.inf, jnp.float32)
        layer = _layer_program(cfg, tuple(faults))
        for row in range(cfg.n_layers):
            x, m, rows = layer(x, params["layers"], jnp.int32(row))
            margins = jnp.minimum(margins, m)
            if rows_seen is not None:
                rows_seen(row, rows)
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
# A token's experts are the 4 largest of 128 scores. The program (bf16
# activations) and the reference (float32) see logits that differ in the
# third decimal, and where the gap between the last logit chosen and the
# first left out is of that size and one of the two experts is held here,
# the two choose differently, both rightly: that position's logits then
# differ by several times what rounding gives (families/exaone_moe.py has
# the same; PERF.md section 4 has this family's readings). Such a position
# is told by its CAUSE, not by its error: the reference reports every
# position's ``held_margin``, and the comparison is the LARGEST error over
# the positions whose margin exceeds CLEAR_MARGIN, over the chunked
# prefill's logits for PREFILL_PREFIXES prefixes of the prompt and over
# DECODE_STEPS decoded positions; and the MEDIAN over all of them.
#
# The logits alone do not see the cache's precision: a row's rounding
# averages down over the positions a head attends, and rows kept in int8
# read as the sound path does (PERF.md section 4). So the ROWS are compared
# too: what the served path left in the pool, [c | kr] of every position
# (the prompt's through the chunked prefill and the scatter, the reply's
# through the decode step's writer), against the reference's rows, by
# relative norm, in the FIRST layer: there nothing but the projection's
# and the cache's own rounding separates the two (a deeper layer's rows
# carry the stream's error, which hides an int8 row's; every layer's
# reading is reported). The harness holds two numbers to the file's one
# ``parity_tolerance``, so a set's number is the larger of its judged
# logits and ROWS_WEIGHT times its rows' error: the rows' own limit is
# ``parity_tolerance`` / ROWS_WEIGHT.
PREFILL_PREFIXES = 15
DECODE_STEPS = 16
# in units of the router's logits (PERF.md section 4: over twice the
# widest margin at which the two chose differently on the chip)
CLEAR_MARGIN = 0.08
# PERF.md section 4 has the readings it lies between (bf16 rows under,
# int8 and float8 rows over)
ROWS_WEIGHT = 6.0
# the cache kept in a lower precision IN THE PROGRAM'S PLACE
# (tools/latent_parity_sensitivity.py): ``served`` rounds the pool's rows
# through it after the prefill's scatter and after every decode step's
# write, so the decode steps attend such a cache
POOL_FAULTS = ("pool_float8", "pool_int8")


def served(params, cfg, toks, *, buckets, block: int, kv_impl: str,
           interpret: bool, cache_dtype="bfloat16", pool_fault=None) -> dict:
    """The served half of ``serve_parity`` for the prompt ``toks``, as the
    engine serves a prompt longer than its largest bucket
    (``engine._prefill_into_blocks``): a block table from the block
    manager, an accumulator of latent ROWS gathered through it, the prompt
    through ``lm.prefill_chunk`` a chunk (the largest bucket) at a time,
    the last chunk once for each of the prompt's last PREFILL_PREFIXES
    prefixes (its logits are a prefix's), the rows scattered into the
    pool, and DECODE_STEPS greedy tokens decoded through it (the absorbed
    form, the pool's writer and walk); returns the tokens (prompt, then
    reply), the logits of every compared position and the rows the pool
    holds at the end, (layers, tokens, kv_lora_rank + rope), gathered
    through the table. ``pool_fault``: one of POOL_FAULTS."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    toks, prompt_len = list(toks), len(toks)
    chunk = max(buckets)
    kinds = kc.pool_kinds(cfg)
    width = -(-(prompt_len + DECODE_STEPS) // block) + 1
    mgr = kc.KVBlockManager(width + 2, block, table_width=width,
                            prefix_cache=False, kind=kc.LATENT)
    pool = kc.init_pool(cfg, width + 2, block, jnp.dtype(cache_dtype))
    table = {kc.LATENT: jnp.asarray(
        mgr.alloc_seq(0, toks, DECODE_STEPS)["tables"][kc.LATENT])}
    acc_len = (-(-width * block // chunk) + 1) * chunk
    acc = kc.gather_table(pool, table, acc_len, kinds)
    prefills, off = [], 0
    while off < prompt_len:
        part = toks[off:off + chunk]
        padded = jnp.asarray(lm.pad_prompt(
            part, lm.bucket_for(sorted(buckets), len(part))))
        last = off + len(part) == prompt_len
        lengths = range(max(1, len(part) - PREFILL_PREFIXES + 1),
                        len(part) + 1) if last else (len(part),)
        for n in lengths:   # a shorter length reads an earlier row's logits
            logits, acc = lm.prefill_chunk(
                params, padded, jnp.int32(n), jnp.int32(off), acc, cfg)
            if last:
                prefills.append(np.asarray(logits))
        off += len(part)

    def kept(pool):
        if pool_fault is None:
            return pool
        return {k: _fake_quant(v.astype(jnp.float32), pool_fault).astype(
            v.dtype) for k, v in pool.items()}

    pool = kept(kc.scatter_table(pool, acc, table, kinds))
    del acc
    tb = {k: t[None] for k, t in table.items()}
    nxt, steps = int(np.argmax(prefills[-1])), []
    zero = jnp.zeros((1,), jnp.float32)
    for i in range(DECODE_STEPS):
        at = jnp.asarray([prompt_len + i], jnp.int32)
        tok = jnp.asarray([nxt], jnp.int32)
        steps.append(np.asarray(kc.paged_decode_logits(
            params, pool, tb, at, tok, cfg, impl=kv_impl,
            interpret=interpret))[0])
        toks.append(nxt)
        out, pool = kc.paged_decode_steps(
            params, pool, tb, at, tok, zero, jax.random.PRNGKey(0), cfg, 1,
            impl=kv_impl, interpret=interpret)
        pool = kept(pool)
        nxt = int(np.asarray(out)[0, 0])
    acc = kc.gather_table(pool, table, acc_len, kinds)
    rows = jnp.concatenate(
        [acc["k"][:, :len(toks), :cfg.kv_lora_rank],
         acc["v"][:, :len(toks), :cfg.qk_rope_head_dim]], -1)
    return {"toks": toks, "prefills": prefills, "steps": steps,
            "rows": rows}


def _judged(errs, margins) -> float:
    """The larger of the median error of all positions and the largest
    error of a clear position."""
    import statistics
    return max([statistics.median(errs)]
               + [e for e, m in zip(errs, margins) if m > CLEAR_MARGIN])


def compared(got: dict, params, cfg, prompt_len: int, faults=()) -> dict:
    """``served``'s logits and pool rows against the reference's full
    forward over the same tokens: for the prefills and for the decode
    steps the larger of the logits' judged error (``_judged``) and
    ROWS_WEIGHT times the first layer's rows' error, with every
    position's error and margin, the medians and every layer's rows'
    errors (the prompt's rows, the reply's rows) beside them."""
    import statistics

    import jax.numpy as jnp
    import numpy as np
    first = prompt_len - len(got["prefills"])
    n = len(got["prefills"]) + len(got["steps"])
    rows_errs = []

    def rows_seen(layer, want):
        d = got["rows"][layer].astype(jnp.float32) - want[0]
        rows_errs.append([
            float(jnp.linalg.norm(d[part]) / jnp.linalg.norm(want[0][part]))
            for part in (slice(0, prompt_len), slice(prompt_len, None))])
    want, margins = forward_margins(
        params, jnp.asarray([got["toks"]], jnp.int32), cfg, faults,
        at=range(first, first + n), rows_seen=rows_seen)
    want, margins = np.asarray(want)[0], np.asarray(margins)[0]
    got_all = got["prefills"] + got["steps"]
    errs = [rel_err(g, want[i]) for i, g in enumerate(got_all)]
    marg = [float(m) for m in margins]
    cut = len(got["prefills"])
    pre, dec, pre_m, dec_m = errs[:cut], errs[cut:], marg[:cut], marg[cut:]
    pre_rows, dec_rows = rows_errs[0]
    return {"prefill_rel_err": max(_judged(pre, pre_m),
                                   ROWS_WEIGHT * pre_rows),
            "decode_rel_err": max(_judged(dec, dec_m),
                                  ROWS_WEIGHT * dec_rows),
            "prefill_logits_rel_err": _judged(pre, pre_m),
            "decode_logits_rel_err": _judged(dec, dec_m),
            "prefill_rows_rel_err": pre_rows,
            "decode_rows_rel_err": dec_rows,
            "rows_rel_errs": rows_errs,
            "prefill_median_rel_err": statistics.median(pre),
            "decode_median_rel_err": statistics.median(dec),
            "clear_positions": sum(m > CLEAR_MARGIN for m in marg),
            "prefill_rel_errs": pre, "decode_rel_errs": dec,
            "prefill_margins": pre_m, "decode_margins": dec_m,
            "finite": bool(np.isfinite(np.asarray(got_all)).all()
                           and np.isfinite(rows_errs).all()),
            "prompt_len": prompt_len}


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool,
                 cache_dtype="bfloat16") -> dict:
    """Prefill a seeded prompt LONGER than the largest bucket through the
    served chunked prefill (past the original 8,192 positions at the
    cell's length, so YaRN's blend and a(t) != 1 are in the comparison),
    write its rows into a paged pool of the latent kind, decode through
    the served decode path, and compare with the reference's full
    forward over the same tokens: ``served``, ``compared``."""
    import random
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    got = served(params, cfg, toks, buckets=buckets, block=block,
                 kv_impl=kv_impl, interpret=interpret,
                 cache_dtype=cache_dtype)
    return compared(got, params, cfg, prompt_len)


# --- what the kernels require ----------------------------------------------


def _attn(model: dict) -> dict:
    return dict(h=model["num_attention_heads"], hd=model["head_dim"],
                lat=model["kv_lora_rank"], rope=model["qk_rope_head_dim"],
                layers=model["num_hidden_layers"])


def latent_decode_required_bytes(model: dict, contexts, itemsize=2) -> int:
    """Bytes the decode attention of ``contexts`` (one entry a slot-step:
    the positions the slot holds, the new token included) REQUIRES over
    all layers: every position's cache row [c | kr] of kv_lora_rank +
    qk_rope_head_dim values ONCE (it is key and value both; whatever the
    pool pads it to), plus each slot's absorbed queries in (bf16) and
    weighted sums out (f32)."""
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
    requires over all layers on MATERIALISED heads: 4 * head_dim a
    (query, key) pair a head, t + 1 pairs for the query at position t."""
    a = _attn(model)
    pairs = sum(n * (n + 1) // 2 for n in prompts)
    return 4 * a["hd"] * a["h"] * pairs * a["layers"]


def flash_prefill_required_bytes(model: dict, prompts, itemsize=2) -> int:
    """Q, K, V read and O written once a layer (K and V per head: the
    kernel attends the expanded rows)."""
    a = _attn(model)
    return itemsize * a["hd"] * 4 * a["h"] * sum(prompts) * a["layers"]


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def gmm_decode_required_bytes(model: dict, experts_hit: float, rows: float,
                              itemsize=2) -> float:
    """Bytes the decode steps' grouped matmuls require: the three
    matrices of every held expert that some row reached (``experts_hit``,
    summed over steps and layers: the engine's counter), plus the routed
    rows in and out of the three products (``rows`` assignments on held
    experts: x in twice, h out twice and in once, the result out)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * f + rows * (3 * d + 3 * f))


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token would require of
    this share (no cell trains it; the program refuses to): 6 per matmul
    parameter the token reaches, plus attention inside the causal mask."""
    a = _attn(model)
    d, ql = model["hidden_size"], model["q_lora_rank"]
    attn = d * ql + ql * a["h"] * a["hd"] + d * (a["lat"] + a["rope"]) \
        + a["lat"] * a["h"] * (model["qk_nope_head_dim"]
                               + model["v_head_dim"]) \
        + a["h"] * model["v_head_dim"] * d
    ffn = d * model.get("source_n_routed_experts",
                        model["n_routed_experts"]) \
        + 3 * d * model["moe_intermediate_size"] \
        * (model["num_experts_per_tok"] + model["n_shared_experts"])
    matmul = n_layers * (attn + ffn) + d * model["vocab_size"]
    return 6.0 * matmul + 3.5 * flash_prefill_required_flops(
        model, [seq]) / seq * n_layers / a["layers"]
