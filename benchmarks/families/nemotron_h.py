"""The ``nemotron_h`` family (NVIDIA-Nemotron-3-Nano-30B-A3B,
``model_type: nemotron_h``): a decoder whose layers are each ONE mixer
behind one norm - a Mamba-2 state-space mixer, a layer of sigmoid-routed
non-gated relu^2 experts with a shared expert, or attention - SERVED on one
device's share of an expert-parallel deployment. Everything the harness
asks of such a model by name is here: the program's config object and
module, the plain reference, the serving comparison, and what its kernels
and scopes require (bytes, operations) for the rooflines.

The plain reference is independent of the code under test: the forward pass
in straightforward float32 ``jax.numpy`` at ``highest`` matmul precision, a
layer at a time, the state-space layers by the TOKEN-BY-TOKEN recurrence (a
``lax.scan`` over positions; nothing chunked, no cache, no kernel, no sort,
no grouped matmul, none of the program's arithmetic: it reads the program's
parameter tree by the program's names for the stacks): every held expert is
computed for every token and weighted by the (tokens, experts) matrix that
holds a token's gate at its chosen experts and 0 elsewhere. The one thing
the SERVING comparison hands it from the program is said, with its limit,
under "the serving comparison" below.

The architecture, from the published ``config.json`` (52 layers by
``hybrid_override_pattern``, hidden 2688, vocabulary 131,072, untied), for
hidden state x and layer l, ``x = x + mixer_l(RMSNorm(x))`` (eps 1e-5):

    "M", Mamba-2: H = 64 heads of P = 64 channels (inner 4,096), state
    N = 128, G = 8 groups (head h reads group h // 8), conv of 4 taps with
    bias, no projection bias.
      [z | xBC | dt] = u W_in                   widths 4,096 | 6,144 | 64
      xBC = silu(conv4(xBC) + b_conv)           causal, depthwise, zeros
                                                before the row
      x (H, P), B (G, N), C (G, N) = split(xBC)
      dt = softplus(dt + dt_bias), A = -exp(A_log)            a head
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T   (P x N a head, float32)
      y_t = h_t C_t + D x_t
      y = y * silu(z); RMSNorm over each group of 512 channels, times a
      weight of 4,096 (gate THEN norm); out = y W_out
    "E", experts: 128 routed experts of width 1,856, 6 a token, non-gated:
      s = sigmoid(u W_r)                        float32, all 128 experts
      S = the 6 experts with the largest s + b  (n_group 1: no group limit)
      g_e = 2.5 * s_e / sum_{S} s               norm_topk_prob,
                                                routed_scaling_factor
      E_e(u) = W_down,e relu(W_up,e u)^2        (mlp_hidden_act relu2)
      out = sum_{e in S} g_e E_e(u) + E_shared(u)    shared width 3,712,
                                                     the same form, unweighted
    "*", attention: 32 query / 2 KV heads of 128, no bias, causal, scores
      scaled by 128 ** -0.5, NO rotary embedding (assumed a).
    after the last layer: RMSNorm, then the untied head.

THE SHARE. The configuration serves one chip's share of an 8-chip
expert-parallel deployment: ``n_routed_experts`` held of the 128 from
``first_expert`` on, and a slice of the vocabulary. The router scores all
128 experts; the reference, given the same share, sums over the chosen
experts THIS device holds (plus the shared expert) and stops there, exactly
what the program computes: the partial sum an all-reduce over the
expert-parallel group would complete. ``layer_share`` is that sum for one
layer; the eight shares' routed parts plus the shared expert once add up to
the uncut layer (tests/test_zz_nemotron_h_serving.py holds both sides to
that). Mamba-2, attention, router and shared expert are replicated.

Assumed, where ``config.json`` is silent (each also under ``assumed`` in
the configuration file; none changes a shape or a byte count):
  (a) no rotary embedding in the attention layers, as the family's
      published modelling code and report have it (the position comes from
      the Mamba layers); ``rope_theta`` and ``partial_rotary_factor`` are
      in the config and unused;
  (b) the Mamba-2 initialisation the config's three ``time_step_*`` keys
      belong to: ``dt_bias`` the inverse softplus of a step log-uniform in
      [0.001, 0.1] floored at 1e-4, ``A_log`` = log U[1, 16], ``D`` = 1;
  (c) the selection bias b (``e_score_correction_bias``): added to s for the
      CHOICE only, drawn small from the seed, as
      ``k-exaone-236b-a23b-serve-ep8`` draws it;
  (d) the recurrent state is kept in float32, the conv tail in bf16;
  (e) bf16 weights and activations (no dtype key in the catalog's config).
Departures from the published description: none besides (a)-(e) and the
share.
"""

from __future__ import annotations

import contextlib
import functools

from harness.reference import rel_err

def config(model: dict, **overrides):
    """The program's config object from the published keys."""
    from ray_tpu.models.moe import MoEConfig, pattern_kinds
    n = model["num_hidden_layers"]
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != n:
        raise ValueError(f"hybrid_override_pattern names {len(pattern)} "
                         f"layers, num_hidden_layers {n}")
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("group-limited routing is not built")
    if model["mlp_hidden_act"] != "relu2" \
            or model["mamba_hidden_act"] != "silu":
        raise ValueError("activations other than relu2 experts and a silu "
                         "mixer are not built")
    if model["mamba_proj_bias"] or model["attention_bias"] \
            or model["mlp_bias"] or not model["use_conv_bias"]:
        raise ValueError("biases: only the conv's is built")
    f, shared = (model["moe_intermediate_size"],
                 model["moe_shared_expert_intermediate_size"])
    if shared % f or model["n_shared_experts"] != 1:
        raise ValueError("the shared expert must be a whole multiple of an "
                         "expert's width")
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    held = model["n_routed_experts"]
    total = model.get("source_n_routed_experts", held)
    return MoEConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=n, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_size=model["head_dim"], ffn_dim=f, n_experts=total,
        experts_per_token=model["num_experts_per_tok"],
        experts_held=held if held != total else 0,
        first_expert=model.get("first_expert", 0),
        norm_topk_prob=bool(model["norm_topk_prob"]), scoring="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]),
        n_shared_experts=shared // f, expert_act="relu2",
        layer_types=pattern_kinds(pattern), rope_layers="none",
        ssm_heads=heads, ssm_head_dim=p, ssm_state=model["ssm_state_size"],
        ssm_groups=model["n_groups"], ssm_conv_kernel=model["conv_kernel"],
        ssm_chunk=model["chunk_size"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["layer_norm_epsilon"]),
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


def _relu2(x, w_up, w_down):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    return jnp.square(jax.nn.relu(x @ w_up.astype(f32))) @ w_down.astype(f32)


def routing(x, router, bias, cfg, given=None):
    """The router on rows x (tokens, d) float32 -> (gates (tokens,
    n_experts) float32: a token's gate at each of its experts, 0 elsewhere;
    a dict of (tokens,) readings: ``margin``, in units of the score s + b,
    how far the nearest of THIS SHARE's experts is from the boundary between
    the last score chosen and the first left out, that is how far the scores
    would have to move before the token got another set of this share's
    experts; ``near``, the experts within CLEAR_MARGIN of the boundary;
    ``parted`` and ``taken``, below). Equal scores go to the lower index.

    ``given`` (tokens, k) int32 is another router's choice over the same
    rows (the served program's: bf16 activations). An expert nearer the
    boundary than CLEAR_MARGIN is one that rounding decides, and there the
    reference takes the given membership; every other membership, and every
    score and gate, is its own. ``parted`` is the largest distance from the
    boundary of an expert (any of the n_experts) on which the two choices
    differ, 0 where none does: past CLEAR_MARGIN no rounding explains it;
    ``taken`` the memberships taken against the reference's own choice."""
    import jax
    import jax.numpy as jnp
    k = cfg.experts_per_token
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    v = s + bias
    top, idx = jax.lax.top_k(v, k + 1)
    own = jax.nn.one_hot(idx[:, :k], v.shape[-1], dtype=jnp.bool_).any(-2)
    far = jnp.where(own, v - top[:, k:k + 1], top[:, k - 1:k] - v)
    e = jnp.arange(v.shape[-1])
    held = (e >= cfg.first_expert) & (e < cfg.first_expert + cfg.n_held)
    near = far <= CLEAR_MARGIN
    member = theirs = own
    if given is not None:
        theirs = jax.nn.one_hot(given, v.shape[-1], dtype=jnp.bool_).any(-2)
        member = jnp.where(near, theirs, own)
    g = member * s
    if cfg.norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * cfg.routed_scaling, {
        "margin": jnp.where(held, far, jnp.inf).min(-1),
        "near": near.sum(-1),
        "parted": jnp.where(theirs != own, far, 0.0).max(-1),
        "taken": (near & (theirs != own)).sum(-1)}


def routed_share(x, lp, cfg, expert=None, given=None):
    """sum over the chosen experts THIS share holds of g_e E_e(x), for rows
    x (tokens, d) float32: every held expert computed for every token, one
    expert's float32 copy at a time. ``expert(name, e)`` gives held expert
    e's matrix; by default ``lp[name][e]``. Returns (the sum, ``routing``'s
    readings)."""
    import jax
    import jax.numpy as jnp
    if expert is None:
        def expert(name, e):
            return jax.lax.dynamic_index_in_dim(lp[name], e, keepdims=False)
    g, readings = routing(x, lp["router"], lp["router_bias"], cfg, given)
    mine = jax.lax.dynamic_slice_in_dim(g, cfg.first_expert, cfg.n_held, 1)

    def one(acc, e):
        out = _relu2(x, expert("w_up", e), expert("w_down", e))
        return acc + mine[:, e][:, None] * out, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(cfg.n_held, dtype=jnp.int32))
    return out, readings


def _shared_expert(x, lp):
    return _relu2(x, lp["shared_up"], lp["shared_down"])


def layer_share(x, lp, cfg):
    """This share's part of one expert layer: its routed part plus the
    shared expert."""
    return routed_share(x, lp, cfg)[0] + _shared_expert(x, lp)


def recurrence(x, dt, A, B, C, D, state0=None):
    """The state-space rule, a token at a time: x (s, H, P), dt (s, H) > 0,
    A (H,) < 0, B and C (s, G, N), D (H,) -> (y (s, H, P), the state after
    the last token (H, P, N)); float32."""
    import jax
    import jax.numpy as jnp
    s, H, P = x.shape
    r = H // B.shape[1]
    Bh, Ch = jnp.repeat(B, r, axis=1), jnp.repeat(C, r, axis=1)

    def token(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], -1) + D[:, None] * x_t
    if state0 is None:
        state0 = jnp.zeros((H, P, B.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(token, state0, (x, dt, Bh, Ch))
    return y, state


def mamba_inputs(u, lp, cfg, act=None):
    """Normed rows u (s, d) float32 -> (z, x, dt, A, B, C, D) of the rule.
    ``act``: what an ACTIVATION (u, z and xBC; not the step, which is
    float32 from its product on) is passed through, for the configuration's
    bf16 (``first_state``)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    act = act or (lambda a: a)
    s = u.shape[0]
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    K = cfg.ssm_conv_kernel
    # W_in's columns [z | xBC | dt] are three leaves of the tree
    u = act(u)
    z, xbc = (act(u @ lp[name].astype(f32)) for name in ("w_z", "w_xbc"))
    dt = u @ lp["w_dt"].astype(f32)     # the step is float32 from here on
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), f32), xbc])
    w = lp["conv"].astype(f32)                              # (channels, K)
    xbc = jax.nn.silu(sum(pad[j:j + s] * w[:, j] for j in range(K))
                      + lp["conv_bias"].astype(f32))
    x, B, C = jnp.split(xbc, [H * P, H * P + G * N], -1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    return (z, x.reshape(s, H, P), dt, -jnp.exp(lp["A_log"]),
            B.reshape(s, G, N), C.reshape(s, G, N), lp["D"])


def mamba(u, lp, cfg, act=None):
    """A Mamba-2 mixer on normed rows u (s, d) float32 -> (out (s, d), the
    state after the last token)."""
    import jax
    import jax.numpy as jnp
    z, *rule = mamba_inputs(u, lp, cfg, act)
    y, state = recurrence(*rule)
    s, G = u.shape[0], cfg.ssm_groups
    y = (y.reshape(s, -1) * jax.nn.silu(z)).reshape(s, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = y.reshape(s, -1) * lp["ssm_norm"].astype(jnp.float32)
    return y @ lp["w_out"].astype(jnp.float32), state


def _attention(u, lp, cfg):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    s = u.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ lp["wq"].astype(f32)).reshape(s, h, hd)
    k = (u @ lp["wk"].astype(f32)).reshape(s, kvh, hd)
    v = (u @ lp["wv"].astype(f32)).reshape(s, kvh, hd)
    k, v = jnp.repeat(k, h // kvh, axis=1), jnp.repeat(v, h // kvh, axis=1)
    t = jnp.arange(s)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(hd))
    sc = jnp.where((t[None, :] <= t[:, None])[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(s, h * hd) @ lp["wo"].astype(f32)


def _f32_layer(x, stack, row, given, *, cfg, kind):
    """Row ``row`` of kind ``kind``'s stacked parameters ``stack`` on x
    (s, d) float32 -> (x, ``routing``'s readings of every position in an
    expert layer, else None; ``given`` (s, k) int32 or None is
    ``routing``'s). The experts' matrices are taken out of the stack one at
    a time."""
    import jax
    import jax.numpy as jnp
    big = ("w_up", "w_down") if kind == "experts" else ()
    lp = {name: jax.lax.dynamic_index_in_dim(w, row, keepdims=False)
          for name, w in stack.items() if name not in big}
    u = _rms(x, lp["norm"], cfg.norm_eps)
    if kind == "state":
        return x + mamba(u, lp, cfg)[0], None
    if kind == "global":
        return x + _attention(u, lp, cfg), None

    def expert(name, e):
        return jax.lax.dynamic_index_in_dim(
            stack[name].reshape(-1, *stack[name].shape[2:]),
            row * cfg.n_held + e, keepdims=False)
    out, readings = routed_share(u, lp, cfg, expert, given)
    return x + out + _shared_expert(u, lp), readings


@functools.lru_cache(maxsize=None)
def _layer_program(cfg, kind, given: bool):
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_f32_layer, cfg=cfg, kind=kind),
        in_axes=(0, None, None, 0 if given else None)))


def forward_margins(params, tokens, cfg, given=None):
    """tokens (b, s) int32 -> (logits (b, s, vocab) float32, ``routing``'s
    readings, each (expert layers, b, s)). A kind's layers are ONE program,
    handed the kind's whole
    parameter stack, of which it reads its own row (an expert's matrices
    one expert at a time): the float32 copies alive at once are one mixer
    and one expert, so it runs beside the served model on the chip.

    ``given`` (expert layers, b, s, k) int32: the experts every position
    chose in each expert layer of the served program, which the reference
    follows where rounding decides and nowhere else (``routing``)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.llm.model import STACKS
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        readings = []
        seen = {kind: 0 for kind in STACKS}
        for kind in cfg.layer_types:
            theirs = given[seen[kind]] \
                if given is not None and kind == "experts" else None
            x, r = _layer_program(cfg, kind, theirs is not None)(
                x, params[STACKS[kind]], jnp.int32(seen[kind]), theirs)
            seen[kind] += 1
            if r is not None:
                readings.append(r)
        logits = jax.jit(lambda x, n, w: _rms(x, n, cfg.norm_eps)
                         @ w.astype(jnp.float32))(
            x, params["final_norm"], params["lm_head"])
        return logits, jax.tree.map(lambda *a: jnp.stack(a), *readings)


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


def first_state(params, toks, cfg, bf16=None):
    """The FIRST layer's state after the tokens ``toks`` (it must be a
    state layer: its input is the embedding alone), (H, P, N) float32, by
    the reference's recurrence. With ``bf16`` (by default: where the
    configuration's dtype is bfloat16) the layer's activations (its normed
    input, z and xBC) are rounded to bfloat16 first (assumed (e)); the step
    dt, the conv and everything after it are float32 (assumed (d)). A sound
    program's state differs from that by the order of its sums alone (2e-5
    on the chip), where the plain float32 one also differs by those
    roundings (3e-3: as much as a bfloat16 STATE does harm)."""
    import jax
    import jax.numpy as jnp
    if cfg.layer_types[0] != "state":
        raise ValueError("the first layer is not a state layer")
    if bf16 is None:
        bf16 = cfg.dtype == "bfloat16"

    def act(a):
        return jax.lax.reduce_precision(a, 8, 7) if bf16 else a

    @jax.jit
    def run(embed, stack, toks):
        lp = {name: w[0] for name, w in stack.items()}
        x = jnp.take(embed, toks, axis=0).astype(jnp.float32)
        return mamba(_rms(x, lp["norm"], cfg.norm_eps), lp, cfg, act)[1]
    with jax.default_matmul_precision("highest"):
        return run(params["embed"], params["state_layers"],
                   jnp.asarray(toks, jnp.int32))


# --- the serving comparison -------------------------------------------------
#
# WHAT THE REFERENCE TAKES FROM THE PROGRAM, AND WHY. A token's experts are
# the 6 largest of 128 scores. The program (bf16 activations) and the
# reference (float32) see scores that differ in the third decimal, and where
# an expert lies that near the boundary between the last score chosen and
# the first left out, the two choose differently, both rightly. In
# ``families/exaone_moe.py`` such a position is told by its margin and left
# out. Here that is not enough: a state-space layer CARRIES what a position
# computed to the positions after it, so one such choice moves the logits of
# the next three to six positions too (0.03-0.07 where rounding alone reads
# 0.015-0.02; my chip runs, PR 59: PERF.md section 4), and with 12 expert
# layers nearly every position has such an expert in some layer. So the
# served forwards' debug entries hand back the experts every position chose
# in each expert layer (``lm.prefill_routed``, ``kvcache.paged_decode_logits
# (chosen=True)``: the same program gives the logits and their routing), and
# the reference takes from that table the memberships of the experts that
# lie within CLEAR_MARGIN of the boundary IN ITS OWN SCORES, and nothing
# else (``routing``): every other membership, every score, gate and
# expert's output is its own float32. An expert on which the two choices
# differ FARTHER from the boundary than CLEAR_MARGIN is MISROUTED: the
# reference keeps its own choice there, and one such position fails the run
# whatever its logits read. CLEAR_MARGIN lies between two readings on the
# chip (PERF.md section 4): the farthest from the boundary that a sound
# program and the reference ever differed, and the nearest of the farthest
# a run of the control did whose program chooses by s without the selection
# bias b (``benchmarks/tools/ssm_parity_sensitivity.py router_bias_
# dropped``). ``routing_excused_share`` says how many of the (layer,
# position, expert) memberships lie within CLEAR_MARGIN, ``routing_taken``
# how many of them were in fact taken against the reference's own choice,
# ``routing_decisions_own_share`` in how many (layer, position) decisions
# no expert lies that near (``routing_held_own_share``: none of this
# share's).
# Every compared position is then judged, by the largest error: the
# prefill's logits for PREFILL_PREFIXES prefixes of the prompt, and
# DECODE_STEPS decoded positions THROUGH THE STATE: every decode step reads
# the state the step before left in the slot.
#
# The slot is a USED one: before the compared prompt is admitted to it,
# another prompt is prefilled into the same slot and decoded USED_STEPS
# steps (its blocks freed, as the engine frees them); what the compared
# request reads must be what it reads alone, and the slot beside it, which
# holds no request, must keep its zeros.
#
# The carried state's PRECISION does not show in the logits (a bfloat16
# state moves them by a tenth of what bf16 activations do), so it is
# compared alone, as the mixing coefficients of ``families/xing4.py`` are:
# the first layer's state in the slot after the prefill and the DECODE_STEPS
# steps against the REFERENCE's own recurrence over the same tokens
# (``first_state``: its activations rounded to the configuration's bf16, so
# that what is left is the state's own arithmetic), times STATE_WEIGHT into
# the decode reading (``state_vs_reference``: the limit it is held to is the
# cell's tolerance over STATE_WEIGHT). Beside it, as information: the same
# against the plain float32 recurrence (``state_vs_float32``), the state and
# the conv tail against what the served PREFILL leaves over the same tokens
# (``state_rel_err``: both carry float32 and differ by the order of float32
# sums alone), and the share of the slot's state entries, all state layers,
# that a bfloat16 holds exactly (``state_bfloat16_share``: 2 ** -16 of
# float32 values by chance, every one of a state that was rounded to
# bfloat16 where it is kept).
PREFILL_PREFIXES = 15
DECODE_STEPS = 16
USED_STEPS = 4
# in units of the score s + b, between two readings on the chip (my chip
# runs, PR 59; PERF.md section 4)
CLEAR_MARGIN = 0.02
STATE_WEIGHT = 80.0
FAULTS = ("state_bfloat16", "state_products_bfloat16")


def _drive(params, cfg, pool, mgr, seq, slot, slots, toks, steps, *,
           buckets, block, kv_impl, interpret, prefixes=1, fault=None):
    """Admit ``toks`` to ``slot`` as the engine does (a block table from the
    manager, one prefill padded to its bucket, its K/V scattered through
    the table and its state layers' states written to the slot, whole) and
    decode ``steps`` greedy tokens through the pool, each step ONE program
    that gives the step's logits, the experts it chose and the pool it
    leaves (``paged_decode_logits(chosen=True)``: ``_paged_logits_core``,
    the body of the engine's decode block); the other slots hold no
    request. Returns (pool, the prefill's logits of the prompt's last
    ``prefixes`` prefixes, each decode step's logits, the experts each
    step chose (expert layers, k), the tokens)."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    toks, n = list(toks), len(toks)
    bucket = lm.bucket_for(sorted(buckets), n)
    alloc = mgr.alloc_seq(seq, toks, steps)
    row = alloc["tables"][kc.GLOBAL]
    prefills = []
    for m in range(n - prefixes + 1, n + 1):
        logits, kv = lm.prefill(
            params, jnp.asarray(lm.pad_prompt(toks[:m], bucket)),
            jnp.int32(m), cfg, bucket)
        prefills.append(np.asarray(logits))
    n_b = bucket // block
    ids = np.full((n_b,), kc.TRASH, np.int32)
    ids[:min(n_b, len(row))] = row[:n_b]
    pool = kc.scatter_bucket(pool, kv, {kc.GLOBAL: jnp.asarray(ids)}, n_b,
                             kc.pool_kinds(cfg))
    pool = _kept(kc.write_state(pool, kv, slot), fault)
    table = np.full((slots, len(row)), kc.TRASH, np.int32)
    table[slot] = row
    tb = {kc.GLOBAL: jnp.asarray(table)}
    nxt, logits, chosen = int(np.argmax(prefills[-1])), [], []
    for i in range(steps):
        at = jnp.zeros((slots,), jnp.int32).at[slot].set(n + i)
        tok = jnp.zeros((slots,), jnp.int32).at[slot].set(nxt)
        toks.append(nxt)
        step, picked, pool = kc.paged_decode_logits(
            params, pool, tb, at, tok, cfg, impl=kv_impl,
            interpret=interpret, chosen=True)
        pool = _kept(pool, fault)
        logits.append(np.asarray(step)[slot])
        chosen.append(np.asarray(picked)[:, slot])
        nxt = int(np.argmax(logits[-1]))
    return pool, prefills, logits, chosen, toks


def _kept(pool, fault):
    """The pool as a faulty program would keep it: ``state_bfloat16``
    rounds the recurrent states to bfloat16 wherever they are stored."""
    if fault != "state_bfloat16":
        return pool
    import jax
    # by reduce_precision: the chip's compiler removes a convert to a
    # narrower type and back as excess precision
    return {**pool, "ssm": jax.lax.reduce_precision(pool["ssm"], 8, 7)}


@contextlib.contextmanager
def _faulty_program(fault):
    """The served program made wrong while it is traced and run:
    ``state_products_bfloat16`` hands the state-space rule (ops/ssm.py
    ``ssd_step``, ``ssd_chunk_scan``) its operands, x, dt, B, C and the
    state it reads, rounded to bfloat16; its sums and what it stores stay
    float32 (a state built from bf16 products: nothing a look at the stored
    values tells from a sound one)."""
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")
    if fault != "state_products_bfloat16":
        yield
        return
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import ssm

    def r(a):
        return jax.lax.reduce_precision(a.astype(jnp.float32), 8, 7)
    step, scan = ssm.ssd_step, ssm.ssd_chunk_scan
    ssm.ssd_step = lambda x, dt, A, B, C, D, state: step(
        r(x), r(dt), A, r(B), r(C), D, r(state))
    ssm.ssd_chunk_scan = lambda x, dt, A, B, C, D, state0, *a, **kw: scan(
        r(x), r(dt), A, r(B), r(C), D, r(state0), *a, **kw)
    jax.clear_caches()          # the programs traced with the sound rule
    try:
        yield
    finally:
        ssm.ssd_step, ssm.ssd_chunk_scan = step, scan
        jax.clear_caches()


def served(params, cfg, toks, *, buckets, block: int, kv_impl: str,
           interpret: bool, cache_dtype="bfloat16", used=None,
           fault=None) -> dict:
    """The served half of ``serve_parity`` for the prompt ``toks``: slot 1
    of two is first USED (the prompt ``used`` prefilled into it and decoded
    USED_STEPS steps, then freed), then ``toks`` is admitted to it,
    prefilled (logits of its last PREFILL_PREFIXES prefixes: one bucket,
    one program) and decoded DECODE_STEPS greedy tokens through the paged
    pool and the slot's state. Returns the tokens (prompt, then reply),
    every compared position's logits, the experts every position chose in
    each expert layer (the prompt's by the prefill, the reply's by the
    decode steps), the first layer's state and conv tail in the slot at the
    end, what the served prefill leaves over the same tokens, and the idle
    slot's largest absolute state value. ``fault``: one of FAULTS, the
    program made wrong in that way (the controls')."""
    with _faulty_program(fault):
        return _served(params, cfg, toks, buckets=buckets, block=block,
                       kv_impl=kv_impl, interpret=interpret,
                       cache_dtype=cache_dtype, used=used, fault=fault)


def _served(params, cfg, toks, *, buckets, block, kv_impl, interpret,
            cache_dtype, used, fault) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    slots, slot = 2, 1
    n = len(toks)
    bucket = lm.bucket_for(sorted(buckets), n + DECODE_STEPS)
    width = bucket // block
    mgr = kc.KVBlockManager(2 * width + 2, block, table_width=width,
                            prefix_cache=False, state_slots=slots)
    pool = kc.init_pool(cfg, 2 * width + 2, block, jnp.dtype(cache_dtype),
                        state_slots=slots)
    kw = dict(buckets=buckets, block=block, kv_impl=kv_impl,
              interpret=interpret, fault=fault)
    if used:
        pool, *_ = _drive(params, cfg, pool, mgr, 0, slot, slots, used,
                          USED_STEPS, **kw)
        mgr.free_seq(0)
    pool, prefills, steps, chosen, out = _drive(
        params, cfg, pool, mgr, 1, slot, slots, toks, DECODE_STEPS,
        prefixes=PREFILL_PREFIXES, **kw)
    # the served prefill over the prompt and the reply, with its routing:
    # the experts the prompt's positions chose (a position's choice does not
    # depend on what follows it, so they are the prefixes' too), and what it
    # leaves of the state every token of ``out`` has been through
    _, kv, experts = lm.prefill_routed(
        params, jnp.asarray(lm.pad_prompt(out, bucket)),
        jnp.int32(len(out)), cfg, bucket)
    experts = np.concatenate(
        [np.asarray(experts)[:, :n], np.stack(chosen, axis=1)], axis=1)
    return {"toks": out, "prefills": prefills, "steps": steps,
            "experts": experts,
            "state_bfloat16_share": float(jnp.mean(
                jax.lax.reduce_precision(pool["ssm"][:, slot], 8, 7)
                == pool["ssm"][:, slot])),
            "state": np.asarray(pool["ssm"][0, slot]),
            "tail": np.asarray(pool["conv"][0, slot], np.float32),
            "state_prefilled": np.asarray(kv["ssm"][0]),
            "tail_prefilled": np.asarray(kv["conv"][0], np.float32),
            "idle_state_max": float(jnp.max(jnp.abs(pool["ssm"][:, 0])))}


def compared(got: dict, params, cfg, prompt_len: int) -> dict:
    """``served``'s logits against the reference's full forward over the
    same tokens, which takes from the program's routing what rounding
    decides and nothing else (the comment above): the largest error of the
    prefills and of the decode steps apart; the decode reading also holds
    STATE_WEIGHT times the carried state's error against the reference's
    recurrence. ``finite`` is false where a logit or a state is not finite,
    where the idle slot did not keep its zeros, or where a position is
    MISROUTED."""
    import statistics

    import jax.numpy as jnp
    import numpy as np
    toks = jnp.asarray([got["toks"]], jnp.int32)
    want, r = forward_margins(
        params, toks, cfg, given=jnp.asarray(got["experts"])[:, None])
    want = np.asarray(want)[0]
    r = {k: np.asarray(a)[:, 0] for k, a in r.items()}  # (expert layers, s)
    parted = r["parted"]
    first = prompt_len - len(got["prefills"])
    pre = [rel_err(p, want[first + i]) for i, p in enumerate(got["prefills"])]
    dec = [rel_err(s, want[prompt_len + i])
           for i, s in enumerate(got["steps"])]
    state = rel_err(got["state"],
                    np.asarray(first_state(params, got["toks"], cfg)))
    plain = rel_err(got["state"], np.asarray(
        first_state(params, got["toks"], cfg, bf16=False)))
    # how the reference's OWN routing would have read (information: the
    # positions after a choice that rounding decided read high)
    own = np.asarray(forward_margins(params, toks, cfg)[0])[0]
    n_mis = int((parted.max(0) > CLEAR_MARGIN).sum())
    return {"prefill_rel_err": max(pre),
            "decode_rel_err": max(max(dec), STATE_WEIGHT * state),
            "decode_logits_rel_err": max(dec),
            "state_vs_reference": state,
            "state_vs_float32": plain,
            "state_rel_err": max(
                rel_err(got["state"], got["state_prefilled"]),
                rel_err(got["tail"], got["tail_prefilled"])),
            "state_bfloat16_share": got["state_bfloat16_share"],
            "idle_state_max": got["idle_state_max"],
            "prefill_median_rel_err": statistics.median(pre),
            "decode_median_rel_err": statistics.median(dec),
            "misrouted_positions": n_mis,
            "parted_decisions": int((parted > 0).sum()),
            "parted_margin_max": float(parted.max()),
            "routing_decisions": int(parted.size),
            "routing_decisions_own_share": float((r["near"] == 0).mean()),
            "routing_held_own_share": float(
                (r["margin"] > CLEAR_MARGIN).mean()),
            "routing_excused_share": float(
                r["near"].sum() / (parted.size * cfg.n_experts)),
            "routing_taken": int(r["taken"].sum()),
            "prefill_rel_errs": pre, "decode_rel_errs": dec,
            "own_routing_rel_errs": [
                rel_err(x, own[first + i])
                for i, x in enumerate(got["prefills"] + got["steps"])],
            "finite": bool(np.isfinite(np.asarray(got["steps"])).all()
                           and np.isfinite(np.asarray(got["prefills"])).all()
                           and np.isfinite(got["state"]).all()
                           and got["idle_state_max"] == 0.0
                           and n_mis == 0),
            "prompt_len": prompt_len}


def serve_parity(params, cfg, seed: int, prompt_len: int, *, buckets,
                 block: int, kv_impl: str, interpret: bool,
                 cache_dtype="bfloat16", fault=None) -> dict:
    """A seeded prompt admitted to a USED slot, prefilled through the served
    prefill, decoded through the paged pool and the slot's state, against
    the reference's full forward over the same tokens: ``served``,
    ``compared``. ``finite`` is false where the slot beside it, which holds
    no request, did not keep its zeros."""
    import random
    rng = random.Random(seed)
    toks = [rng.randrange(1, cfg.vocab_size) for _ in range(prompt_len)]
    used = [rng.randrange(1, cfg.vocab_size)
            for _ in range(max(8, prompt_len // 3))]
    got = served(params, cfg, toks, buckets=buckets, block=block,
                 kv_impl=kv_impl, interpret=interpret,
                 cache_dtype=cache_dtype, used=used, fault=fault)
    return compared(got, params, cfg, prompt_len)


# --- what the kernels and scopes require ------------------------------------


def _count(model: dict, c: str) -> int:
    return model["hybrid_override_pattern"][
        :model["num_hidden_layers"]].count(c)


def _ssm(model: dict) -> dict:
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    return dict(h=h, p=p, g=g, n=n, inner=h * p, conv=h * p + 2 * g * n,
                taps=model["conv_kernel"], layers=_count(model, "M"))


def ssm_step_required_bytes(model: dict, slot_steps: float) -> float:
    """Bytes the ``ssm.step`` scope of ``slot_steps`` (live slots summed
    over decode steps) REQUIRES over all state layers, whatever the
    implementation: a live slot's state read once and written once
    (float32), its x, B, C and dt in (float32) and its y out. The
    projections' weights are ``ssm.proj``'s and ``ssm.out``'s; the conv
    tail is ``ssm.conv``'s."""
    a = _ssm(model)
    state = 2 * a["h"] * a["p"] * a["n"] * 4
    rows = (2 * a["inner"] + 2 * a["g"] * a["n"] + a["h"]) * 4
    return float(slot_steps) * a["layers"] * (state + rows)


def ssm_step_required_flops(model: dict, slot_steps: float) -> float:
    """Operations of the same: a state element is decayed, added to (2) and
    read out (2); the outer product dt x B^T (1)."""
    a = _ssm(model)
    return float(slot_steps) * a["layers"] * 5 * a["h"] * a["p"] * a["n"]


def ssm_scan_required_flops(model: dict, prompts) -> float:
    """Operations the ``ssm.scan`` scope of a prefill of ``prompts`` (their
    lengths) requires over all state layers BY THE RECURRENCE, the least
    any form does a token: 5 a state element (as a step)."""
    a = _ssm(model)
    return float(sum(prompts)) * a["layers"] * 5 * a["h"] * a["p"] * a["n"]


def ssm_scan_required_bytes(model: dict, prompts) -> float:
    """Bytes of the same: a token's x, B, C, dt in and y out (float32), and
    a prompt's state out once."""
    a = _ssm(model)
    rows = (2 * a["inner"] + 2 * a["g"] * a["n"] + a["h"]) * 4
    state = a["h"] * a["p"] * a["n"] * 4
    return a["layers"] * (float(sum(prompts)) * rows + len(prompts) * state)


def state_bytes_per_slot(model: dict) -> int:
    """What one slot's states and conv tails cost (float32 state, bf16
    tail), all state layers."""
    a = _ssm(model)
    return a["layers"] * (a["h"] * a["p"] * a["n"] * 4
                          + (a["taps"] - 1) * a["conv"] * 2)


def _attn(model: dict) -> dict:
    return dict(h=model["num_attention_heads"],
                kvh=model["num_key_value_heads"], hd=model["head_dim"],
                layers=_count(model, "*"))


def attention_layers(model: dict) -> int:
    """The layers that attend: the paged kernel runs once each a decode
    step."""
    return _count(model, "*")


def paged_decode_required_bytes(model: dict, contexts, itemsize=2) -> int:
    """Bytes the decode attention of ``contexts`` (one entry a slot-step:
    the positions the slot holds, the new token included) REQUIRES over the
    attention layers: K and V of every position, plus each slot's queries
    in (bf16) and outputs out (f32)."""
    a = _attn(model)
    kv = 2 * sum(contexts) * a["kvh"] * a["hd"] * itemsize
    qo = len(contexts) * a["h"] * a["hd"] * (2 + 4)
    return a["layers"] * (kv + qo)


def flash_prefill_required_flops(model: dict, prompts) -> int:
    """Operations the prefill attention of ``prompts`` requires over the
    attention layers: 4 * head_dim a (query, key) pair a head, a query at
    position t has t + 1 pairs."""
    a = _attn(model)
    return 4 * a["hd"] * a["h"] * a["layers"] \
        * sum(n * (n + 1) // 2 for n in prompts)


def flash_prefill_required_bytes(model: dict, prompts, itemsize=2) -> int:
    """Q, K, V read and O written once an attention layer."""
    a = _attn(model)
    return itemsize * a["hd"] * (2 * a["h"] + 2 * a["kvh"]) \
        * sum(prompts) * a["layers"]


def sparse_layers(model: dict) -> int:
    return _count(model, "E")


def gmm_decode_required_bytes(model: dict, experts_hit: float, rows: float,
                              itemsize=2) -> float:
    """Bytes the decode steps' grouped matmuls require: the TWO matrices of
    every held expert that some row reached (``experts_hit``, summed over
    steps and layers: the engine's counter), plus the routed rows in and
    out of the two products (``rows`` assignments on held experts: x in, h
    out and in, the result out)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return itemsize * (experts_hit * 2 * d * f + rows * (2 * d + 2 * f))


def train_required_flops_per_token(model: dict, n_layers: int,
                                   seq: int) -> float:
    """Forward + backward operations one trained token would require of
    this share (no cell trains it): 6 per matmul parameter the token
    reaches, plus attention and the state's recurrence."""
    a, m = _attn(model), _ssm(model)
    d = model["hidden_size"]
    attn = a["layers"] * (2 * d * a["h"] * a["hd"] + 2 * d * a["kvh"]
                          * a["hd"])
    state = m["layers"] * (d * (m["inner"] + m["conv"] + m["h"])
                           + m["inner"] * d)
    experts = sparse_layers(model) * (
        d * model.get("source_n_routed_experts", model["n_routed_experts"])
        + 2 * d * (model["moe_intermediate_size"]
                   * model["num_experts_per_tok"]
                   + model["moe_shared_expert_intermediate_size"]))
    matmul = attn + state + experts + d * model["vocab_size"]
    return 6.0 * matmul + 3.5 * flash_prefill_required_flops(
        model, [seq]) / seq + 3.0 * ssm_scan_required_flops(model, [seq]) \
        / seq
