"""The ``lfm2_moe`` family (LFM2-24B-A2B, ``model_type: lfm2_moe``): a
decoder of two-sub-layer blocks whose FIRST sub-layer is, by
``layer_types``, a gated short convolution ("conv") or grouped-query
attention ("full_attention"), and whose second is a dense SwiGLU (the
leading ``num_dense_layers``) or 64 sigmoid-routed experts, 4 a token, all
held, no shared expert. Everything the harness asks of such a model by name
is here: the program's config object and module, the plain reference, the
serving comparison, and what its kernels require (bytes) for the rooflines.

The plain reference is independent of the code under test: the forward
pass in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision, a layer at a time over the whole row of tokens, the convolution
as three shifted copies of the row, attention as a full causal softmax
(a block of queries at a time, so that the scores fit), every expert
computed for every token and weighted by the (tokens, experts) matrix of
gates. No cache, no tail, no kernel, no packing, no sort, no grouped
matmul, nothing imported from the program (``_rms``, ``_swiglu`` and the
cache's fake quantisation are the latent family's plain functions,
``families/mistral4.py``). It reads the program's leaves.

The architecture, from the published ``config.json`` (40 layers, hidden
2,048, vocabulary 65,536, no biases) and the family's published modelling,
for normed rows:

    h  = x + Op(RMSNorm(x));   x' = h + FF(RMSNorm(h))      eps ``norm_eps``
    Op, a "conv" layer, on u (2,048 wide):
      [B | C | X] = u W_in          (2,048 -> 6,144, split in that order)
      z_t = B_t * X_t
      c_t = sum_{j=0..2} w[:, j] * z_{t-2+j}    ``conv_L_cache`` 3 taps,
            causal, depthwise, ``conv_bias`` false, NO activation
      Op  = (C * c) W_out           (2,048 -> 2,048)
      what a request keeps between its tokens: the last TWO rows of z
    Op, a "full_attention" layer: 32 query / 8 K/V heads of 64;
      q_h = rope(RMSNorm_64(q_h)), k_h = rope(RMSNorm_64(k_h)): a norm with
      a learned weight over each head's 64 values BEFORE the rotary
      embedding (theta 1e6, the whole head, half-split pairs (i, i + 32));
      o_h = softmax_{j<=t}(64^-0.5 q_h . k_h(j)) v_h(j);  Op = [o_h] W_o
    FF, layers 0 .. num_dense_layers - 1: W2(silu(W1 h) * W3 h), 11,776
    FF, the rest, on u = RMSNorm(h):
      s = sigmoid(u W_r) over the 64, float32;  S = the 4 largest of s +
      expert_bias (``use_expert_bias``; the bias never enters a gate);
      g_e = routed_scaling_factor * s_e / (sum_S s + 1e-6)
                                                     (``norm_topk_prob``)
      FF = sum_{e in S} g_e W2_e(silu(W1_e u) * W3_e u), width 1,536
    after the last layer one RMSNorm, then the head (tied: the embedding).

The program keeps an operator's leaves in a stack a kind, the row the
layer's place among its kind (``state_layers``: ``w_in``, ``conv``
(channels, taps), ``w_out``; ``attn_layers``: ``wq``, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm``), and what every layer has (``attn_norm``,
the operator's pre-norm; ``mlp_norm``; the feed-forward) in
``dense_layers`` / ``layers``; the reference reads the same leaves.

THE CUT is in depth alone (``num_hidden_layers`` 40 -> 9, ``num_dense_layers``
2 -> 1): every width, all 64 experts and the whole vocabulary are here, so
the reference is the model's own layer and there is no share to be given.

Assumed, where ``config.json`` is silent (each also under ``assumed`` in
the configuration file; none changes a shape):
  (a) ``tie_word_embeddings`` true, ``torch_dtype`` bfloat16;
  (b) the head width 64 = hidden / heads (``head_dim`` is not given);
  (c) the q / k head norms (no config key; the family's modelling);
  (d) the split order [B | C | X] and the conv without an activation;
  (e) the 1e-6 in the gates' sum;
  (f) rotary half-split pairs over the whole head.
Departures from the published description: none besides (a)-(f).
``FAULTS`` are the reference's deliberate departures, one at a time, for
``tools/shortconv_parity_sensitivity.py``.
"""

from __future__ import annotations

import functools

from families.mistral4 import (DECODE_STEPS, PREFILL_PREFIXES,  # noqa: F401
                               QUERY_BLOCK, _fake_quant, _rms, _swiglu)
from harness.reference import rel_err

KINDS = {"conv": "state", "full_attention": "global"}
# the reference's deliberate faults (tools/shortconv_parity_sensitivity.py):
# the conv's tail zeroed where a served chunk begins; zeroed at every decode
# step; the gate C left out; the q / k head norms left out; the experts
# chosen by s without the bias
FAULTS = ("tail_zero_at_chunk", "tail_zero_at_decode", "c_left_out",
          "qk_norm_left_out", "select_without_bias")
# the served half's: what the pool keeps (K/V rows and conv tails) through
# float8 e4m3 / int8, one scale a row
POOL_FAULTS = ("pool_float8", "pool_int8")


def served_types(model: dict) -> list:
    """The kinds of the layers SERVED. ``layer_types`` is the source's
    list, copied whole; a configuration cut in depth serves the leading
    dense layers it keeps (they count once) and then what follows the
    SOURCE's dense lead, ``num_hidden_layers`` in all, and says so under
    ``served_layer_types``."""
    n, dense = model["num_hidden_layers"], model["num_dense_layers"]
    skip = model.get("source_num_dense_layers", dense) - dense
    types = list(model["layer_types"][skip:skip + n])
    if len(types) != n or set(types) - set(KINDS) \
            or model.get("served_layer_types", types) != types:
        raise ValueError(
            f"layer_types[{skip}:{skip + n}] must name the {n} served "
            f"layers 'conv' or 'full_attention', as served_layer_types "
            f"does: got {types}")
    return types


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    n = model["num_hidden_layers"]
    types = served_types(model)
    if model["conv_bias"] or not model["use_expert_bias"]:
        raise ValueError("the family's conv has no bias and its router a "
                         "selection bias")
    rp = model["rope_parameters"]
    if rp["rope_type"] != "default":
        raise ValueError("the family's rotary is plain RoPE")
    heads = model["num_attention_heads"]
    head = model.get("head_dim") or model["hidden_size"] // heads
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, n_heads=heads,
        n_kv_heads=model["num_key_value_heads"], head_size=head,
        ffn_dim=model["moe_intermediate_size"],
        n_experts=model["num_experts"],
        experts_per_token=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]), scoring="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]),
        route_eps=1e-6, n_dense_layers=model["num_dense_layers"],
        dense_ffn_dim=model["intermediate_size"], qk_head_norm=True,
        layer_types=tuple(KINDS[t] for t in types),
        shortconv_kernel=model["conv_L_cache"],
        tie_embeddings=bool(model["tie_word_embeddings"]),
        # heads of 64 values lie two a pool row of 128 lanes
        kv_row_heads=max(1, 128 // head),
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(rp["rope_theta"]),
        norm_eps=float(model["norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **{**model["deployment"].get("model_overrides", {}), **overrides})


def module():
    """The program's module that makes this family's parameters."""
    from ray_tpu.models import moe
    return moe


# --- the plain reference ---------------------------------------------------


def short_conv(u, lp, cfg, faults=(), cuts=()):
    """The gated short convolution over one row u (s, d) float32 -> (its
    output (s, d), z (s, d): the rows whose last two a request keeps).
    ``cuts``: positions before which a fault zeroes the conv's memory."""
    import jax.numpy as jnp
    f32 = jnp.float32
    s, d = u.shape
    B, C, X = jnp.split(u @ lp["w_in"].astype(f32), 3, axis=-1)
    z = B * X
    w = lp["conv"].astype(f32)                                  # (d, K)
    K = w.shape[-1]
    t = jnp.arange(s)
    c = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j                        # tap j reads z_{t - back}
        zj = jnp.pad(z, ((back, 0), (0, 0)))[:s]
        for cut in cuts:        # position t >= cut forgets rows before cut
            zj = jnp.where(((t >= cut) & (t - back < cut))[:, None], 0.0, zj)
        c = c + w[:, j] * zj
    y = c if "c_left_out" in faults else C * c
    return y @ lp["w_out"].astype(f32), z


def _rope(x, theta):
    """x (s, heads, hd) float32, half-split pairs (i, i + hd / 2)."""
    import jax.numpy as jnp
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(u, lp, cfg, faults=()):
    """Grouped-query attention over one row u (s, d) float32 -> (its output
    before W_o (s, heads * hd), the row's cache rows k and v (s, kv heads,
    hd): k after its norm and the rotary embedding)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = u.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ lp["wq"].astype(f32)).reshape(s, h, hd)
    k = (u @ lp["wk"].astype(f32)).reshape(s, kvh, hd)
    v = (u @ lp["wv"].astype(f32)).reshape(s, kvh, hd)
    if "qk_norm_left_out" not in faults:
        q = _rms(q, lp["q_norm"], cfg.norm_eps)
        k = _rms(k, lp["k_norm"], cfg.norm_eps)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    g = h // kvh
    pad = -s % QUERY_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, kvh, g, hd)
    at = jnp.arange(s + pad).reshape(-1, QUERY_BLOCK)

    def block(xs):
        qi, ti = xs                             # (Q, kvh, g, hd), (Q,)
        sc = jnp.einsum("qkgd,lkd->kgql", qi, k) * hd ** -0.5
        sc = jnp.where(jnp.arange(s)[None, None, None] <= ti[None, None, :,
                                                            None],
                       sc, -jnp.inf)
        return jnp.einsum("kgql,lkd->qkgd", jax.nn.softmax(sc, -1), v)
    o = jax.lax.map(block, (qb, at)).reshape(s + pad, h * hd)[:s]
    return o, k, v


def routing(x, router, bias, cfg, given=None, faults=()):
    """The router on rows x (tokens, d) float32 -> (gates (tokens,
    n_experts) float32: a token's gate at each of its experts, 0 elsewhere;
    a dict of (tokens,) readings: ``margin``, in units of the score s + b,
    the gap between the last score chosen and the first left out (every
    expert is held); ``near``, the experts within CLEAR_MARGIN of that
    boundary; ``parted`` and ``taken``, below). Equal scores go to the lower
    index.

    ``given`` (tokens, k) int32 is another router's choice over the same
    rows (the served program's: bf16 activations), -1 where none is given.
    Where one is given the reference takes its MEMBERSHIPS (every score and
    gate stays its own) and holds the choice to its own scores: ``parted``
    is the largest distance from the boundary of an expert on which the two
    choices differ, 0 where none does. Within CLEAR_MARGIN rounding decides
    it; past it no rounding explains it, and the position is MISROUTED
    (``compared`` counts them). ``taken``: the memberships taken against
    the reference's own choice."""
    import jax
    import jax.numpy as jnp
    k = cfg.experts_per_token
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    v = s if "select_without_bias" in faults else s + bias
    top, idx = jax.lax.top_k(v, k + 1)
    own = jax.nn.one_hot(idx[:, :k], v.shape[-1], dtype=jnp.bool_).any(-2)
    far = jnp.where(own, v - top[:, k:k + 1], top[:, k - 1:k] - v)
    near = far <= CLEAR_MARGIN
    member = theirs = own
    if given is not None:
        has = (given >= 0).all(-1, keepdims=True)
        theirs = jnp.where(has, jax.nn.one_hot(
            jnp.maximum(given, 0), v.shape[-1], dtype=jnp.bool_).any(-2), own)
        member = theirs
    g = member * s
    if cfg.norm_topk_prob:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    return g * cfg.routed_scaling, {
        "margin": top[:, k - 1] - top[:, k],
        "near": near.sum(-1),
        "parted": jnp.where(theirs != own, far, 0.0).max(-1),
        "taken": (theirs != own).sum(-1)}


def gates(x, router, bias, cfg, faults=()):
    """``routing``'s gates by the reference's own choice."""
    return routing(x, router, bias, cfg, None, faults)[0]


def routed(x, lp, cfg, expert, given=None, faults=()):
    """sum over the chosen experts of g_e E_e(x) for rows x (tokens, d)
    float32: every expert computed for every token, one expert's float32
    copy at a time (``expert(name, e)`` gives expert e's matrix) -> (the
    sum, ``routing``'s readings)."""
    import jax
    import jax.numpy as jnp
    g, readings = routing(x, lp["router"], lp["router_bias"], cfg, given,
                          faults)

    def one(acc, e):
        out = _swiglu(x, expert("w_gate", e), expert("w_up", e),
                      expert("w_down", e))
        return acc + jax.lax.dynamic_index_in_dim(
            g, e, axis=1, keepdims=True) * out, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(cfg.n_experts, dtype=jnp.int32))
    return out, readings


def _f32_layer(x, op, own, row, given, *, cfg, kind, faults=(), cuts=()):
    """One layer on one row x (s, d) float32: ``op`` the operator's leaves
    (this layer's), ``own`` the layer's stack (``dense_layers`` /
    ``layers``) of which it reads row ``row`` (traced: one program a kind
    and stack), the experts' matrices one at a time; ``given`` (s, k) int32
    or None is ``routing``'s -> (x, ``routing``'s readings of every
    position (None in a dense layer), what the layer would cache: (k, v)
    or z)."""
    import jax
    import jax.numpy as jnp
    f32 = x.dtype
    sparse = "router" in own
    big = ("w_gate", "w_up", "w_down") if sparse else ()
    lp = {name: jax.lax.dynamic_index_in_dim(w, row, keepdims=False)
          for name, w in own.items() if name not in big}
    eps = cfg.norm_eps
    u = _rms(x, lp["attn_norm"], eps)
    if kind == "state":
        out, kept = short_conv(u, op, cfg, faults, cuts)
    else:
        o, k, v = attention(u, op, cfg, faults)
        out, kept = o @ op["wo"].astype(f32), (k, v)
    x = x + out
    u = _rms(x, lp["mlp_norm"], eps)
    if not sparse:
        return x + _swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"]), \
            None, kept

    def expert(name, e):
        return jax.lax.dynamic_index_in_dim(
            own[name].reshape(-1, *own[name].shape[2:]),
            row * cfg.n_experts + e, keepdims=False)
    out, readings = routed(u, lp, cfg, expert, given, faults)
    return x + out, readings, kept


@functools.lru_cache(maxsize=None)
def _layer_program(cfg, kind, faults, cuts, given: bool):
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_f32_layer, cfg=cfg, kind=kind, faults=faults,
                          cuts=cuts),
        in_axes=(0, None, None, None, 0 if given else None)))


def forward_margins(params, tokens, cfg, faults=(), at=None, kept_seen=None,
                    cuts=(), given=None):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, ``routing``'s
    readings, each (expert layers, b, s)); with ``at`` (positions), the
    logits of those positions only (the head is the widest product);
    ``kept_seen(layer, kind, kept)`` is handed what each layer would cache,
    (k, v) (b, s, kv heads, hd) or z (b, s, d). Each layer is one call of
    one program a (kind, stack), handed the whole stack of which it reads
    its own row (an expert's matrices one expert at a time): it runs beside
    the served model on the chip.

    ``given`` (layers, b, s, k) int32: the experts every position chose in
    each layer of the served program (-1 where it has no router, or where
    no choice is given), which the reference follows where rounding decides
    and nowhere else (``routing``)."""
    import jax
    import jax.numpy as jnp
    faults, cuts = tuple(faults), tuple(int(c) for c in cuts)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        readings = []
        seen = {"state": 0, "global": 0}
        for i, kind in enumerate(cfg.layer_types):
            stack, row = ("dense_layers", i) if i < cfg.n_dense_layers \
                else ("layers", i - cfg.n_dense_layers)
            name = {"state": "state_layers", "global": "attn_layers"}[kind]
            op = {k: w[seen[kind]] for k, w in params[name].items()}
            seen[kind] += 1
            theirs = given[i] if given is not None and stack == "layers" \
                else None
            x, r, kept = _layer_program(
                cfg, kind, faults, cuts, theirs is not None)(
                x, op, params[stack], jnp.int32(row), theirs)
            if r is not None:
                readings.append(r)
            if kept_seen is not None:
                kept_seen(i, kind, kept)
        if at is not None:
            x = x[:, jnp.asarray(at, jnp.int32)]
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        logits = jax.jit(lambda x, n, w: _rms(x, n, cfg.norm_eps)
                         @ w.astype(jnp.float32))(
            x, params["final_norm"], head)
        return logits, jax.tree.map(lambda *a: jnp.stack(a), *readings)


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
# ``served`` is the engine's path for a prompt LONGER than its largest
# bucket (``engine._prefill_into_blocks``): a block table from the block
# manager, an accumulator gathered through it (the two attention layers'
# K/V rows as the pool keeps them, two heads of 64 a row of 128 lanes, and
# the seven conv layers' tails, zeros), the prompt through
# ``lm.prefill_chunk`` a chunk (the largest bucket) at a time, each conv
# layer's tail handed from chunk to chunk in the accumulator, the last chunk
# (an odd tail, its own bucket) once for each of the prompt's last
# PREFILL_PREFIXES prefixes, the rows scattered into the pool and the tails
# written to the slot (``write_state``), then DECODE_STEPS greedy tokens
# through the decode step (the packed walk, the row writer, the tails moved
# on in the pool), in slot 1 of two. LOGITS are compared, every compared
# position's, with the reference's full forward over the same tokens.
#
# THE ROUTING. EVERY expert is held, eight layers have a router, and a
# conv layer hands what a position's layers computed to the two positions
# after it: where bf16 and float32 scores order the 4th and 5th expert
# differently (the gap between them is under 0.008 in SOME layer at nearly
# every position; the stream's bf16 rounding moves a score by about 0.001)
# the program and the reference choose differently, both rightly, that
# position's logits differ by 0.03 to 0.4 where the others read 0.014, and
# the positions after it follow. Judged as ``families/xing4.py`` judges
# (lower quartile, clear positions) 17 sound runs on the chip read a
# quartile of 0.013 to 0.40 and not one position of 527 was clear, while a
# router that ignores its bias read 0.22 (PERF.md section 6, PR 61): no
# limit separates them. So the reference FOLLOWS the program's routing
# (``routing``; ``families/nemotron_h.py`` has the idea): the experts every
# position of the prompt's LAST chunk and of the reply chose in each layer
# come out of the same programs as the compared logits
# (``lm.prefill_chunk_routed``, ``paged_decode_logits(chosen=True)``), the
# reference takes those memberships, so that both compute the same
# experts' sum and the LOGITS of every compared position are held, the
# largest error a set; and the CHOICE is held to the reference's own
# scores: a position at which the program chose an expert farther than
# CLEAR_MARGIN from the boundary between the 4th and the 5th score, in any
# layer, is MISROUTED, and more than MISROUTED_LIMIT of them fail the run
# (``finite``). A sound run parts by 0.004-0.009 at the farthest of its
# 2,528 decisions, and in one run of 19 one decision lay 0.0225 out (one
# position of 316: not failed, counted); a router that ignores its bias
# misroutes 76-104 positions, up to 0.12-0.18 out. Earlier chunks'
# positions keep the reference's own choice: what they chose reaches a
# compared position through the second attention layer's rows alone, one
# of thousands each.
#
# What holds EVERY position is the K/V rows of the first attention layer
# (layer 1: behind the dense lead, so no position of it has met a router):
# each position's row [k | v] there, all of the prompt's and all of the
# reply's, gathered back from the pool, is compared with the reference's by
# relative norm, and ROWS_WEIGHT times the LARGEST enters the set's number: a
# chunk that starts from a wrong tail shows in the rows of its first two
# positions whatever the compared logits, 300 positions later, read. And the
# TAILS the slot holds after the prefill and after the last step, layer 0's
# (the embedding's own: two tokens decide it), against the reference's z at
# the last two positions, TAIL_WEIGHT times their error: the cache's
# precision where no logit sees it.
# in units of the score s + b (the configuration file has the readings)
CLEAR_MARGIN = 0.02
# positions of the 316 whose routing is compared that may lie farther out
MISROUTED_LIMIT = 8
ROWS_WEIGHT = 2.5
TAIL_WEIGHT = 2.5


def chunk_starts(prompt_len: int, buckets) -> tuple:
    """Positions at which a served chunk after the first begins."""
    chunk = max(buckets)
    return tuple(range(chunk, prompt_len, chunk))


def served(params, cfg, toks, *, buckets, block: int, kv_impl: str,
           interpret: bool, cache_dtype="bfloat16", pool_fault=None) -> dict:
    """The served half of ``serve_parity`` for the prompt ``toks`` (above).
    Returns the tokens (prompt, then reply), the logits of every compared
    position, the experts every position chose in each layer (layers,
    tokens, k; -1 before the prompt's last chunk and in the dense lead),
    the first attention layer's rows the pool holds at the end (tokens, kv
    heads, hd) x 2 and layer 0's tail in the slot after the prefill and
    after the last step. ``pool_fault``: one of POOL_FAULTS."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    toks, prompt_len = list(toks), len(toks)
    chunk = max(buckets)
    kinds = kc.pool_kinds(cfg)
    slots, slot = 2, 1
    width = -(-(prompt_len + DECODE_STEPS) // block) + 1
    mgr = kc.KVBlockManager(width + 2, block, table_width=width,
                            prefix_cache=False, state_slots=slots)
    pool = kc.init_pool(cfg, width + 2, block, jnp.dtype(cache_dtype),
                        state_slots=slots)
    row = mgr.alloc_seq(0, toks, DECODE_STEPS)["tables"][kc.GLOBAL]
    table = {kc.GLOBAL: jnp.asarray(row)}
    acc_len = (-(-width * block // chunk) + 1) * chunk
    acc = kc.gather_table(pool, table, acc_len, kinds)
    acc.update(kc.fresh_state(pool))
    experts = np.full((cfg.n_layers, prompt_len + DECODE_STEPS,
                       cfg.experts_per_token), -1, np.int32)
    prefills, off = [], 0
    while off < prompt_len:
        part = toks[off:off + chunk]
        padded = jnp.asarray(lm.pad_prompt(
            part, lm.bucket_for(sorted(buckets), len(part))))
        last = off + len(part) == prompt_len
        lengths = range(max(1, len(part) - PREFILL_PREFIXES + 1),
                        len(part) + 1) if last else (len(part),)
        for n in lengths:   # a shorter length reads an earlier row's logits
            # (the accumulator is donated: each call starts from a copy of
            # the tails the chunks before it left)
            start = {k: jnp.copy(v) for k, v in acc.items()} \
                if last else acc
            if last and n == len(part):
                # ONE program: the last prefix's logits, what the prompt
                # leaves and the routing of the chunk's rows
                logits, out, chosen = lm.prefill_chunk_routed(
                    params, padded, jnp.int32(n), jnp.int32(off), start, cfg)
                experts[:, off:prompt_len] = np.asarray(chosen)[:, :n]
            else:
                logits, out = lm.prefill_chunk(
                    params, padded, jnp.int32(n), jnp.int32(off), start, cfg)
            if last:
                prefills.append(np.asarray(logits))
        acc = out
        off += len(part)

    def kept(pool):
        if pool_fault is None:
            return pool
        return {k: _fake_quant(v.astype(jnp.float32), pool_fault).astype(
            v.dtype) for k, v in pool.items()}

    pool = kc.scatter_table(pool, acc, table, kinds)
    pool = kept(kc.write_state(pool, acc, slot))
    tail_prefilled = np.asarray(pool["conv"][0, slot], np.float32)
    del acc
    tables = np.full((slots, len(row)), kc.TRASH, np.int32)
    tables[slot] = row
    tb = {kc.GLOBAL: jnp.asarray(tables)}
    nxt, steps = int(np.argmax(prefills[-1])), []
    zero = jnp.zeros((slots,), jnp.float32)
    for i in range(DECODE_STEPS):
        at = jnp.zeros((slots,), jnp.int32).at[slot].set(prompt_len + i)
        tok = jnp.zeros((slots,), jnp.int32).at[slot].set(nxt)
        step, chosen, _ = kc.paged_decode_logits(
            params, pool, tb, at, tok, cfg, impl=kv_impl,
            interpret=interpret, chosen=True)
        steps.append(np.asarray(step)[slot])
        experts[:, prompt_len + i] = np.asarray(chosen)[:, slot]
        toks.append(nxt)
        out, pool = kc.paged_decode_steps(
            params, pool, tb, at, tok, zero, jax.random.PRNGKey(0), cfg, 1,
            impl=kv_impl, interpret=interpret)
        pool = kept(pool)
        nxt = int(np.asarray(out)[0, slot])
    acc = kc.gather_table(pool, table, acc_len, kinds)
    h, hd = cfg.n_kv_heads, cfg.head_dim
    return {"toks": toks, "prefills": prefills, "steps": steps,
            "experts": experts,
            "rows": tuple(np.asarray(
                acc[name][0, :len(toks)].reshape(len(toks), h, hd),
                np.float32) for name in ("k", "v")),
            "tail_prefilled": tail_prefilled,
            "tail": np.asarray(pool["conv"][0, slot], np.float32),
            "idle_tail_max": float(jnp.max(jnp.abs(
                pool["conv"][:, 0].astype(jnp.float32))))}


def compared(got: dict, params, cfg, prompt_len: int, faults=(),
             cuts=()) -> dict:
    """``served``'s logits, pool rows and tails against the reference's full
    forward over the same tokens, which takes from the program's routing
    what rounding decides and nothing else (the comment above; with
    ``faults``; ``cuts``: where the fault ``tail_zero_at_chunk`` zeroes the
    conv's memory, the served chunks' starts): for the prefills and for the
    decode steps the largest of the compared positions' logits' errors,
    ROWS_WEIGHT times the largest error of ANY position's row in the first
    attention layer and TAIL_WEIGHT times the error of layer 0's tail in
    the slot. ``finite`` is false where a number is not finite, where the
    idle slot did not keep its zeros, or where more than MISROUTED_LIMIT
    positions are MISROUTED."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np
    first = prompt_len - len(got["prefills"])
    n = len(got["prefills"]) + len(got["steps"])
    at = range(first, first + n)
    faults = tuple(faults)
    zero_at = tuple(cuts) if "tail_zero_at_chunk" in faults else ()
    if "tail_zero_at_decode" in faults:
        zero_at += tuple(range(prompt_len, len(got["toks"])))
    rows, zs = [], []

    def kept_seen(layer, kind, kept):
        # a layer at a time: the next layer's float32 temporaries are
        # allocated when this one has run
        jax.block_until_ready(kept)
        if kind == "global" and not rows:
            rows.extend(np.asarray(r[0]) for r in kept)
        if kind == "state" and not zs:
            zs.append(np.asarray(kept[0]))
    toks = jnp.asarray([got["toks"]], jnp.int32)
    want, r = forward_margins(
        params, toks, cfg, faults, at=at, kept_seen=kept_seen, cuts=zero_at,
        given=jnp.asarray(got["experts"])[:, None])
    want = np.asarray(want)[0]
    r = {k: np.asarray(a)[:, 0] for k, a in r.items()}  # (expert layers, s)
    given = (got["experts"][cfg.n_dense_layers:] >= 0).all(-1)
    parted = np.where(given, r["parted"], 0.0)
    n_mis = int((parted.max(0) > CLEAR_MARGIN).sum())
    # every position's row [k | v] of the first attention layer
    mine = np.concatenate([x.reshape(len(x), -1) for x in got["rows"]], -1)
    ref = np.concatenate([x.reshape(len(x), -1) for x in rows], -1)
    each = np.linalg.norm(mine - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    pre_rows, dec_rows = (float(each[:prompt_len].max()),
                          float(each[prompt_len:].max()))
    # layer 0's tail: z at the last two positions, flat
    K = cfg.shortconv_kernel
    pre_tail = rel_err(got["tail_prefilled"],
                       zs[0][prompt_len - (K - 1):prompt_len].reshape(-1))
    dec_tail = rel_err(got["tail"], zs[0][len(got["toks"]) - (K - 1):
                                          len(got["toks"])].reshape(-1))
    got_all = got["prefills"] + got["steps"]
    errs = [rel_err(g, want[i]) for i, g in enumerate(got_all)]
    cut = len(got["prefills"])
    pre, dec = errs[:cut], errs[cut:]
    margin = r["margin"][:, first:].min(0)
    return {"prefill_rel_err": max(max(pre), ROWS_WEIGHT * pre_rows,
                                   TAIL_WEIGHT * pre_tail),
            "decode_rel_err": max(max(dec), ROWS_WEIGHT * dec_rows,
                                  TAIL_WEIGHT * dec_tail),
            "prefill_logits_rel_err": max(pre),
            "decode_logits_rel_err": max(dec),
            "prefill_rows_rel_err": pre_rows,
            "decode_rows_rel_err": dec_rows,
            "rows_median_rel_err": float(np.median(each)),
            "rows_worst_position": int(np.argmax(each)),
            "prefill_tail_rel_err": pre_tail,
            "decode_tail_rel_err": dec_tail,
            "idle_tail_max": got["idle_tail_max"],
            "prefill_median_rel_err": statistics.median(pre),
            "decode_median_rel_err": statistics.median(dec),
            "misrouted_positions": n_mis,
            "parted_decisions": int((parted > 0).sum()),
            "parted_margin_max": float(parted.max()),
            "routing_decisions": int(given.sum()),
            "routing_taken": int(np.where(given, r["taken"], 0).sum()),
            "routing_near_share": float(
                np.where(given, r["near"], 0).sum()
                / max(1, given.sum() * cfg.n_experts)),
            "prefill_rel_errs": pre, "decode_rel_errs": dec,
            "margins": [float(m) for m in margin],
            "finite": bool(np.isfinite(np.asarray(got_all)).all()
                           and np.isfinite(each).all()
                           and np.isfinite([pre_tail, dec_tail]).all()
                           and got["idle_tail_max"] == 0.0
                           and n_mis <= MISROUTED_LIMIT),
            "prompt_len": prompt_len}


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool,
                 cache_dtype="bfloat16") -> dict:
    """Prefill a seeded prompt LONGER than the largest bucket, and no whole
    number of chunks, through the served chunked prefill, write its rows
    into the paged pool and its tails into a slot, decode through the
    served decode path, and compare with the reference's full forward over
    the same tokens: ``served``, ``compared``."""
    import random
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    got = served(params, cfg, toks, buckets=buckets, block=block,
                 kv_impl=kv_impl, interpret=interpret,
                 cache_dtype=cache_dtype)
    return compared(got, params, cfg, prompt_len)


# --- what the kernels require ----------------------------------------------


def attention_layers(model: dict) -> int:
    """Layers that attend: the paged walk runs once each a decode step."""
    return served_types(model).count("full_attention")


def sparse_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


def state_bytes_per_slot(model: dict, itemsize=2) -> int:
    """Bytes a slot's conv tails cost: ``conv_L_cache`` - 1 rows of the
    hidden width a conv layer."""
    return served_types(model).count("conv") \
        * (model["conv_L_cache"] - 1) * model["hidden_size"] * itemsize


def gmm_decode_required_bytes(model: dict, experts_hit: float, rows: float,
                              itemsize=2) -> float:
    """Bytes the decode steps' grouped matmuls require: the three
    matrices of every expert that some row reached (``experts_hit``,
    summed over steps and layers: the engine's counter), plus the routed
    rows in and out of the three products (``rows`` assignments: x in
    twice, h out twice and in once, the result out)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 3 * d * f + rows * (3 * d + 3 * f))


def paged_decode_required_bytes(model: dict, contexts, itemsize=2) -> int:
    """Bytes the decode attention of ``contexts`` (one entry a slot-step:
    the positions the slot holds) requires over the attention layers:
    every position's K and V once, 8 heads of 64 each."""
    row = 2 * model["num_key_value_heads"] \
        * (model["hidden_size"] // model["num_attention_heads"]) * itemsize
    return attention_layers(model) * sum(contexts) * row


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token would require (no
    cell trains it; the program refuses to): 6 per matmul parameter the
    token reaches, plus attention inside the causal mask."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    hd, kvh = d // h, model["num_key_value_heads"]
    types = served_types(model)[:n_layers]
    conv = 4 * d * d
    attn = 2 * d * h * hd + 2 * d * kvh * hd
    dense = min(model["num_dense_layers"], n_layers)
    ffn = d * model["num_experts"] + 3 * d * model["moe_intermediate_size"] \
        * model["num_experts_per_tok"]
    matmul = types.count("conv") * conv \
        + types.count("full_attention") * attn \
        + (n_layers - dense) * ffn \
        + dense * 3 * d * model["intermediate_size"] \
        + d * model["vocab_size"]
    return 6.0 * matmul + 3.5 * 2 * 2 * hd * h * (seq + 1) / 2 \
        * types.count("full_attention")
