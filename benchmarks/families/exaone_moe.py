"""The ``exaone_moe`` family (K-EXAONE-236B-A23B, ``model_type:
exaone_moe``): a decoder whose layers alternate sliding-window and global
attention, with a dense first layer and sigmoid-routed experts plus a
shared expert after it, SERVED on one device's share of an
expert-parallel deployment. Everything the harness asks of such a model
by name is here: the program's config object and module, the plain
reference, the serving comparison, and what its kernels require (bytes,
operations) for the rooflines.

The plain reference is independent of the code under test: the forward
pass in straightforward float32 ``jax.numpy`` at ``highest`` matmul
precision, a layer at a time. No cache, no kernel, no sort, no gather, no
grouped matmul, no batching: every held expert is computed for every
token and weighted by the (tokens, experts) matrix that holds a token's
gate at its chosen experts and 0 elsewhere.

The architecture, from the published ``config.json`` (48 layers, hidden
6144, 64 query heads, 8 KV heads, ``head_dim`` 128 - given, not hidden /
heads = 96 -, vocabulary 153,600, untied), for hidden state x, layer l:

    attention (every layer; no biases):
      q = qn(Wq x), k = kn(Wk x), v = Wv x     per head; qn, kn: RMSNorm
                                               over head_dim (assumed b)
      layer_types[l] == "sliding_attention":   q, k = rope(q), rope(k)
          (pairs (first half, second half) of a head, base rope_theta);
          position t attends j with t - sliding_window < j <= t
      layer_types[l] == "full_attention":      no RoPE (assumed c);
          t attends every j <= t
      x = x + n1(Wo softmax(q k^T / sqrt(head_dim)) v)    (assumed b)
    feed-forward:
      mlp_layer_types[l] == "dense" (layer 0):
          m = Wdown (silu(Wgate x) * Wup x)             width 18,432
      "sparse" (layers 1-47): 128 routed experts of width 2,048, 8 a token
          s = sigmoid(x Wr)                   float32, all 128 experts
          S = the 8 experts with the largest s + b      (assumed a)
          g_e = 2.5 * s_e / sum_{S} s         norm_topk_prob,
                                              routed_scaling_factor
          m = sum_{e in S} g_e E_e(x) + E_shared(x)
          E(x) = Wdown (silu(Wgate x) * Wup x)
          n_group = topk_group = 1: group limiting is the identity
      x = x + n2(m)                                       (assumed b)
    after the last layer: RMSNorm, then the untied head.

THE SHARE. The configuration serves one chip's share of an 8-chip
expert-parallel deployment: ``experts_held`` of the 128 experts from
``first_expert`` on, and a slice of the vocabulary (embedding rows and
head columns). The router scores all 128 experts; the reference, given
the same share, sums over the chosen experts THIS device holds (plus the
shared expert) and stops there, exactly what the program computes: the
partial sum an all-reduce over the expert-parallel group would complete.
``layer_share`` is that sum for one layer; the eight shares' routed
parts plus the shared expert once add up to the uncut layer
(tests/test_zz_hybrid_serving.py holds both sides to that).

Assumed, where ``config.json`` is silent (each also under ``assumed`` in
the configuration file; none changes a shape, a byte count or a step's
cost):
  (a) the selection bias b (``e_score_correction_bias`` in the routing
      these keys are named after): a parameter added to s for the CHOICE
      only, never to a gate; drawn small from the seed so that choosing
      and weighting differ;
  (b) RMSNorm of q and k over ``head_dim``, per head, before RoPE, and
      the sub-layer norms on each sub-layer's OUTPUT before the residual
      add, as EXAONE 4.0 publishes its block;
  (c) RoPE on the sliding layers only, none on the global ones, as the
      family's hybrid models publish;
  (d) the multi-token-prediction block (``num_nextn_predict_layers`` 1)
      is not loaded: it drafts for self-speculation and is no part of
      the next-token distribution.
Departures from the published description: none besides (a)-(d) and the
share.
"""

from __future__ import annotations

import functools

from harness.reference import rel_err

KINDS = {"sliding_attention": "window", "full_attention": "global"}


def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig
    n = model["num_hidden_layers"]
    mlp = model["mlp_layer_types"][:n]
    n_dense = sum(1 for t in mlp if t == "dense")
    if mlp != ["dense"] * n_dense + ["sparse"] * (n - n_dense):
        raise ValueError("the dense layers must lead")
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    if model["scoring_func"] != "sigmoid":
        raise ValueError(f"scoring_func {model['scoring_func']!r}")
    held = model["num_experts"]
    total = model.get("source_num_experts", held)
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_size=model["head_dim"],
        ffn_dim=model["moe_intermediate_size"],
        n_experts=total, experts_per_token=model["num_experts_per_tok"],
        experts_held=held if held != total else 0,
        first_expert=model.get("first_expert", 0),
        norm_topk_prob=bool(model["norm_topk_prob"]), scoring="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]),
        n_shared_experts=model["num_shared_experts"],
        n_dense_layers=n_dense, dense_ffn_dim=model["intermediate_size"],
        layer_types=tuple(KINDS[t] for t in model["layer_types"][:n]),
        sliding_window=model["sliding_window"],
        qk_head_norm=True, post_norm=True, rope_layers="window",
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_parameters"]["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16",
               "float32": "float32"}[model["torch_dtype"]],
        **{**model["deployment"].get("model_overrides", {}), **overrides})


def module():
    """The program's module that makes this family's parameters."""
    from ray_tpu.models import moe
    return moe


# --- the plain reference ---------------------------------------------------


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


def held_margin(x, router, bias, cfg):
    """(tokens,) float32, in units of the score s + b: how far a token's
    scores would have to move before it got ANOTHER set of this share's
    experts. A held expert among the chosen falls out when it sinks under
    the first score left out; a held expert left out comes in when it
    passes the last score chosen; the margin is the smallest such
    distance."""
    import jax
    import jax.numpy as jnp
    k = cfg.experts_per_token
    v = jax.nn.sigmoid(x @ router.astype(jnp.float32)) + bias
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
    g = gates(x, lp["router"], lp["router_bias"], cfg)
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
    """This share's part of one sparse layer's feed-forward: its routed
    part plus the shared expert."""
    return routed_share(x, lp, cfg) + _shared_expert(x, lp)


def _f32_layer(x, stack, *, cfg, kind, row):
    """Layer ``row`` of the stacked parameters ``stack`` on x (s, d)
    float32 -> (x, every position's ``held_margin`` in this layer: inf in
    a dense one). The experts' matrices are taken out of the stack one at
    a time."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    big = ("w_gate", "w_up", "w_down") if "router" in stack else ()
    lp = {name: w[row] for name, w in stack.items() if name not in big}
    s = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    eps = cfg.norm_eps
    q = _rms((x @ lp["wq"].astype(f32)).reshape(s, h, hd), lp["q_norm"], eps)
    k = _rms((x @ lp["wk"].astype(f32)).reshape(s, kvh, hd), lp["k_norm"],
             eps)
    v = (x @ lp["wv"].astype(f32)).reshape(s, kvh, hd)
    t = jnp.arange(s)
    keep = t[None, :] <= t[:, None]
    if kind == "window":
        half = hd // 2
        freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=f32) / half)
        ang = t.astype(f32)[:, None] * freqs[None, :]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

        def rope(y):
            y1, y2 = y[..., :half], y[..., half:]
            return jnp.concatenate([y1 * cos - y2 * sin,
                                    y2 * cos + y1 * sin], -1)
        q, k = rope(q), rope(k)
        keep = keep & (t[None, :] > t[:, None] - cfg.sliding_window)
    k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(hd))
    sc = jnp.where(keep[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    x = x + _rms(o.reshape(s, h * hd) @ lp["wo"].astype(f32),
                 lp["attn_norm"], eps)
    if big:
        def expert(name, e):
            return jax.lax.dynamic_index_in_dim(
                stack[name].reshape(-1, *stack[name].shape[2:]),
                row * cfg.n_held + e, keepdims=False)
        m = routed_share(x, lp, cfg, expert) + _shared_expert(x, lp)
        margin = held_margin(x, lp["router"], lp["router_bias"], cfg)
    else:
        m = _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        margin = jnp.full((s,), jnp.inf, f32)
    return x + _rms(m, lp["mlp_norm"], eps), margin


@functools.lru_cache(maxsize=None)
def _layer_program(cfg, kind, row):
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_f32_layer, cfg=cfg, kind=kind, row=row),
        in_axes=(0, None)))


def forward_margins(params, tokens, cfg):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, margins
    (b, s): a position's smallest ``held_margin`` over the sparse
    layers). Each layer is a program of its own, handed its whole
    parameter stack, of which it reads its own row (an expert's matrices
    one expert at a time): the float32 copies alive at once are one
    layer's attention and one expert, so it runs beside the served model
    on the chip."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        margins = jnp.full(tokens.shape, jnp.inf, jnp.float32)
        for i, kind in enumerate(cfg.layer_types):
            stack, row = ("dense_layers", i) if i < cfg.n_dense_layers \
                else ("layers", i - cfg.n_dense_layers)
            x, m = _layer_program(cfg, kind, row)(x, params[stack])
            margins = jnp.minimum(margins, m)
        logits = jax.jit(lambda x, n, w: _rms(x, n, cfg.norm_eps)
                         @ w.astype(jnp.float32))(
            x, params["final_norm"], params["lm_head"])
        return logits, margins


def forward(params, tokens, cfg):
    """tokens (b, s) int32 -> logits (b, s, vocab) float32."""
    return forward_margins(params, tokens, cfg)[0]


def logits_and_loss(params, batch, cfg):
    """The reference's logits (b, s, vocab) and its mean cross-entropy
    against ``batch["targets"]``."""
    import jax
    import jax.numpy as jnp
    logits = forward(params, batch["tokens"], cfg)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                               -1)[..., 0]
    return logits, jnp.mean(logz - gold)


# --- the serving comparison -------------------------------------------------
#
# A token's experts are the 8 largest of 128 scores. The program (bf16
# activations) and the reference (float32) see scores that differ in the
# third decimal, and where the gap between the last score chosen and the
# first left out is of that size and one of the two experts is held here,
# the two choose differently, both rightly: that position's logits then
# differ by 0.02-0.36 in relative norm where rounding alone gives
# 0.011-0.013 (measured on the chip: PERF.md section 4). Such a position
# is told by its CAUSE, not by its error: the reference reports every
# position's ``held_margin``, the distance its scores would have to move
# to change this share's experts in some layer, and the comparison is the
# LARGEST error over the positions whose margin exceeds CLEAR_MARGIN (the
# program's scores cannot have moved that far by rounding), over the
# prefill's logits for PREFILL_PREFIXES prefixes of the prompt and over
# DECODE_STEPS decoded positions. A fault that touches one clear position
# (a block edge, the window's first block, a freed block read again)
# fails the run; so does a MEDIAN over all positions past the tolerance.
PREFILL_PREFIXES = 15
DECODE_STEPS = 16
# in units of the score s + b: over twice the widest margin at which
# the two chose differently on the chip (PERF.md section 4)
CLEAR_MARGIN = 0.004


def served(params, cfg, toks, *, buckets, block: int, kv_impl: str,
           interpret: bool, cache_dtype="bfloat16") -> dict:
    """The served half of ``serve_parity`` for the prompt ``toks``: the
    prefill's logits for the prompt's last PREFILL_PREFIXES prefixes
    (one bucket: one program), then the whole prompt's KV written into a
    paged pool through the block manager's tables and DECODE_STEPS
    greedy tokens decoded through it; returns the tokens (prompt, then
    reply) and the logits of every compared position."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    toks, prompt_len = list(toks), len(toks)
    if prompt_len - PREFILL_PREFIXES < cfg.sliding_window:
        raise ValueError("every compared prefix must be longer than the "
                         "window")
    bucket = min(b for b in buckets if b >= prompt_len)
    prefills = []
    for n in range(prompt_len - PREFILL_PREFIXES + 1, prompt_len + 1):
        logits, kv = lm.prefill(
            params, jnp.asarray(lm.pad_prompt(toks[:n], bucket)),
            jnp.int32(n), cfg, bucket)
        prefills.append(np.asarray(logits))
    n_b = bucket // block
    ring = kc.window_ring_blocks(cfg.sliding_window, block, 1)
    mgr = kc.KVBlockManager(3 + n_b, block, table_width=n_b + 2,
                            prefix_cache=False,
                            window=(1 + ring, cfg.sliding_window, 1))
    pool = kc.init_pool(cfg, 3 + n_b, block, jnp.dtype(cache_dtype),
                        window_blocks=1 + ring)
    alloc = mgr.alloc_seq(0, toks, DECODE_STEPS)
    tables = {kc.GLOBAL: alloc["table"], kc.WINDOW: alloc["window_table"]}
    pool = kc.scatter_bucket(
        pool, kv, {k: jnp.asarray(t[:n_b]) for k, t in tables.items()},
        n_b, kc.pool_kinds(cfg))
    nxt, steps = int(np.argmax(prefills[-1])), []
    zero = jnp.zeros((1,), jnp.float32)
    for i in range(DECODE_STEPS):
        tables[kc.WINDOW] = mgr.advance_window(0, prompt_len + i, 1)
        tb = {k: jnp.asarray(t[None]) for k, t in tables.items()}
        at = jnp.asarray([prompt_len + i], jnp.int32)
        tok = jnp.asarray([nxt], jnp.int32)
        steps.append(np.asarray(kc.paged_decode_logits(
            params, pool, tb, at, tok, cfg, impl=kv_impl,
            interpret=interpret))[0])
        toks.append(nxt)
        out, pool = kc.paged_decode_steps(
            params, pool, tb, at, tok, zero, jax.random.PRNGKey(0), cfg, 1,
            impl=kv_impl, interpret=interpret)
        nxt = int(np.asarray(out)[0, 0])
    return {"toks": toks, "prefills": prefills, "steps": steps,
            "freed": mgr.window_freed_total}


def _judged(errs, margins) -> float:
    """The larger of the median error of all positions and the largest
    error of a clear position."""
    import statistics
    return max([statistics.median(errs)]
               + [e for e, m in zip(errs, margins) if m > CLEAR_MARGIN])


def compared(got: dict, params, cfg, prompt_len: int) -> dict:
    """``served``'s logits against the reference's full forward over the
    same tokens: for the prefills and for the decode steps the judged
    error (``_judged``), with every position's error and margin and the
    medians beside them."""
    import statistics

    import jax.numpy as jnp
    import numpy as np
    want, margins = forward_margins(
        params, jnp.asarray([got["toks"]], jnp.int32), cfg)
    want, margins = np.asarray(want)[0], np.asarray(margins)[0]
    first = prompt_len - len(got["prefills"])
    pre = [rel_err(p, want[first + i]) for i, p in enumerate(got["prefills"])]
    dec = [rel_err(s, want[prompt_len + i])
           for i, s in enumerate(got["steps"])]
    pre_m = [float(m) for m in margins[first:prompt_len]]
    dec_m = [float(m) for m in margins[prompt_len:prompt_len + len(dec)]]
    return {"prefill_rel_err": _judged(pre, pre_m),
            "decode_rel_err": _judged(dec, dec_m),
            "prefill_median_rel_err": statistics.median(pre),
            "decode_median_rel_err": statistics.median(dec),
            "clear_positions": sum(m > CLEAR_MARGIN for m in pre_m + dec_m),
            "prefill_rel_errs": pre, "decode_rel_errs": dec,
            "prefill_margins": pre_m, "decode_margins": dec_m,
            "finite": bool(np.isfinite(np.asarray(got["steps"])).all()
                           and np.isfinite(np.asarray(
                               got["prefills"])).all()),
            "prompt_len": prompt_len, "window_blocks_freed": got["freed"]}


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool,
                 cache_dtype="bfloat16") -> dict:
    """Prefill a seeded prompt LONGER than the window through the served
    prefill (so the band is in the comparison), write its KV into a
    paged pool through the block manager's tables (the window layers get
    only the blocks their window reaches), decode through the served
    decode path (the manager frees and allocates window blocks as the
    engine does, across block edges), and compare with the reference's
    full forward over the same tokens: ``served``, ``compared``."""
    import random
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    got = served(params, cfg, toks, buckets=buckets, block=block,
                 kv_impl=kv_impl, interpret=interpret,
                 cache_dtype=cache_dtype)
    return compared(got, params, cfg, prompt_len)


# --- what the kernels require ----------------------------------------------


def _attn(model: dict) -> dict:
    n = model["num_hidden_layers"]
    kinds = [KINDS[t] for t in model["layer_types"][:n]]
    return dict(h=model["num_attention_heads"],
                kvh=model["num_key_value_heads"], hd=model["head_dim"],
                window=model["sliding_window"],
                n_window=kinds.count("window"),
                n_global=kinds.count("global"))


def paged_decode_required_bytes(model: dict, contexts, itemsize=2) -> int:
    """Bytes the decode attention of ``contexts`` (one entry a slot-step:
    the positions the slot holds, the new token included) REQUIRES over
    all layers: K and V of every position a layer attends - the whole
    context on a global layer, min(context, window) on a window layer -
    plus each slot's queries in (bf16) and outputs out (f32)."""
    a = _attn(model)
    pos = sum(a["n_global"] * c + a["n_window"] * min(c, a["window"])
              for c in contexts)
    kv = 2 * pos * a["kvh"] * a["hd"] * itemsize
    qo = len(contexts) * (a["n_global"] + a["n_window"]) \
        * a["h"] * a["hd"] * (2 + 4)
    return kv + qo


def flash_prefill_required_flops(model: dict, prompts) -> int:
    """Operations the prefill attention of ``prompts`` (their lengths)
    requires over all layers: 4 * head_dim a (query, key) pair a head;
    a query at position t has t + 1 pairs on a global layer and
    min(t + 1, window) on a window layer."""
    a = _attn(model)

    def pairs(n, w):
        full = min(n, w)
        return full * (full + 1) // 2 + (n - full) * w
    total = sum(a["n_global"] * pairs(n, n) + a["n_window"]
                * pairs(n, a["window"]) for n in prompts)
    return 4 * a["hd"] * a["h"] * total


def flash_prefill_required_bytes(model: dict, prompts, itemsize=2) -> int:
    """Q, K, V read and O written once a layer."""
    a = _attn(model)
    return itemsize * a["hd"] * (2 * a["h"] + 2 * a["kvh"]) \
        * sum(prompts) * (a["n_global"] + a["n_window"])


def sparse_layers(model: dict) -> int:
    n = model["num_hidden_layers"]
    return sum(1 for t in model["mlp_layer_types"][:n] if t == "sparse")


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
    this share (no cell trains it): 6 per matmul parameter the token
    reaches, plus attention inside each layer's mask."""
    a = _attn(model)
    d = model["hidden_size"]
    attn = d * a["h"] * a["hd"] * 2 + 2 * d * a["kvh"] * a["hd"]
    sparse = sparse_layers(model)
    ffn = (n_layers - sparse) * 3 * d * model["intermediate_size"] \
        + sparse * (d * model.get("source_num_experts", model["num_experts"])
                    + 3 * d * model["moe_intermediate_size"]
                    * (model["num_experts_per_tok"]
                       + model["num_shared_experts"]))
    matmul = n_layers * attn + ffn + d * model["vocab_size"]
    return 6.0 * matmul + 3.5 * flash_prefill_required_flops(
        model, [seq]) / seq
