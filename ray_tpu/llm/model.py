"""Cache-aware forwards for inference: prefill, decode, verify.

The model side of the LLM serving stack (reference:
python/ray/llm/_internal/serve/... wraps vLLM; here the engine is native:
the training models in models/llama.py and models/moe.py are reused —
same params, same config — with inference-shaped forwards that XLA
compiles once per shape bucket; TPU rule: no dynamic shapes). Every
forward runs ONE layer body (`_layer`) over the layers as `_run_layers`
cuts them (a scan over a repeated period of layer kinds); what a model
family adds to the Llama case is listed under "what the engine is
handed" below:

- `prefill` / `prefill_chunk`: a prompt (padded to a bucket) or one
  chunk of a long one; they emit per-layer K/V in token order, which
  the engine scatters into the KV pool's blocks (llm/kvcache.py).
- `decode_logits_core` / `verify_tokens_core`: one token (or w tokens)
  for every slot against the pool, the one KV cache there is. Batch
  dimension = slots, so the MXU sees one batched matmul per layer
  regardless of how many requests are live. The jitted programs around
  them are kvcache's `paged_decode_steps`, `paged_decode_logits` and
  `paged_verify_steps`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.llm.kvcache import GLOBAL, LATENT, STATE, WINDOW
from ray_tpu.models.llama import (LlamaConfig, _rmsnorm, _rope, _rope_pairs,
                                  _rope_tables, _rope_tables_freqs,
                                  yarn_inv_freq, yarn_mscale)


def bucket_for(buckets, n: int) -> int:
    """Smallest prefill shape bucket holding an n-token prompt (shared
    by the unified and disaggregated engines so the policy can't
    drift)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pad_prompt(tokens, bucket: int):
    """Zero-pad a prompt to its bucket (numpy, int32)."""
    import numpy as np
    out = np.zeros((bucket,), np.int32)
    out[:len(tokens)] = tokens
    return out


def serve_param_specs(cfg: LlamaConfig, axis: str = "tensor") -> dict:
    """Megatron tensor-parallel PartitionSpecs for INFERENCE: attention
    heads and ffn split over `axis`; the row-parallel matmuls (wo,
    w_down) reduce over it (GSPMD inserts the psum). Unlike training's
    param_shardings there is no fsdp dim — serving replicates what it
    doesn't tensor-split, trading memory for zero gather latency on the
    decode critical path. Reference capability: vLLM's
    tensor_parallel_size per replica
    (llm/_internal/serve/configs/llm_config.py:181-186)."""
    t = axis
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, t),
            "wk": P(None, None, t),
            "wv": P(None, None, t),
            "wo": P(None, t, None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, t),
            "w_up": P(None, None, t),
            "w_down": P(None, t, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, t),
    }


def shard_params_for_serving(params: dict, mesh: Mesh, cfg: LlamaConfig,
                             axis: str = "tensor") -> dict:
    """Place params on the mesh per serve_param_specs. Validates the
    divisibility the layout needs (heads, kv heads, ffn, vocab all
    split over the tensor axis)."""
    tp = mesh.shape[axis]
    for name, n in (("n_heads", cfg.n_heads),
                    ("n_kv_heads", cfg.n_kv_heads),
                    ("ffn_dim", cfg.ffn_dim),
                    ("vocab_size", cfg.vocab_size)):
        if n % tp:
            raise ValueError(
                f"{name}={n} not divisible by tensor-parallel size {tp}")
    specs = serve_param_specs(cfg, axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


def _scan_layers(layer, x, xs):
    """``lax.scan`` of a layer body over the stacked layers, under a
    "layer" scope: a stable name in a device trace, metadata only."""
    return lax.scan(jax.named_scope("layer")(layer), x, xs)


# --- what the engine is handed ---------------------------------------------
#
# A model family is the module that defines the config's class: it makes
# the parameters (``init_params``). What the serving forwards below need
# of a config beyond the Llama fields are optional fields, read with
# ``getattr`` so that a LlamaConfig is the case "every layer global, one
# dense stack, pre-norm, RoPE everywhere, no experts":
#
#   layer_types      "global" | "window" per layer (sliding_window wide),
#                    or every layer "latent" (multi-head latent attention:
#                    the widths and the YaRN rotary of models/moe.py; its
#                    cache is a row of c and kr a token, not K and V)
#   n_dense_layers   leading layers live in ``params["dense_layers"]``
#                    (a dense SwiGLU); the rest in ``params["layers"]``,
#                    whose leaves decide the feed-forward: a ``router``
#                    means experts (models/moe.py serve_block)
#   qk_norm, qk_head_norm, post_norm, rope_layers   (models/moe.py)
#   hc_mult          n > 1: the residual stream is n copies a position,
#                    (n, b, s, d) from the embedding to the final norm,
#                    mixed by hyper-connections ("the mixed stream" below)
#
# ``layer_types`` of "state" and "experts" (beside "global") make every
# layer ONE mixer behind one norm, x + mixer(RMSNorm(x)) (``_mixer_layer``):
# a state-space mixer (ops/ssm.py; the ``ssm_*`` fields), whose cache is a
# recurrent state and a conv tail A SLOT and not a row a position; an expert
# layer with no attention before it, which caches nothing; attention alone.
# Each kind's parameters are a stack of its own, ``params[STACKS[kind]]``
# (the train forward's representation of kinds, models/moe.py
# ``_kind_layers``), and the same ``_segments`` cut them into scans: a
# period takes each kind's rows from its own stack.

#
# ``shortconv_kernel`` > 0 puts a "state" layer INSIDE the two-sub-layer
# block: its first sub-layer is a gated short convolution (ops/shortconv.py)
# where a "global" layer's is attention, the second the dense or the expert
# feed-forward as ever, behind ``n_dense_layers`` that are such layers too.
# What a slot keeps of it is the conv's tail alone. The two operators' leaves
# are stacks of their own (``STACKS``, a row the layer's place among its
# kind); ``dense_layers`` and ``layers`` keep what every layer has
# (``operator_stacks``).

EXPERTS = "experts"     # a layer that is an expert layer alone: no cache
STACKS = {STATE: "state_layers", EXPERTS: "expert_layers",
          GLOBAL: "attn_layers"}


def model_family(cfg):
    """The module that defines ``cfg``'s class (models/llama.py,
    models/moe.py): ``init_params(rng, cfg)`` makes its parameters."""
    import sys
    return sys.modules[type(cfg).__module__]


def layer_kinds(cfg) -> tuple:
    """The kind of every layer: "global" or "window" attention, or all of
    them "latent"; or, each layer one mixer alone, "state", "experts" or
    "global"."""
    kinds = tuple(getattr(cfg, "layer_types", ()) or ())
    if not kinds:
        return (GLOBAL,) * cfg.n_layers
    if len(kinds) != cfg.n_layers \
            or set(kinds) - {GLOBAL, WINDOW, LATENT, STATE, EXPERTS}:
        raise ValueError(
            f"layer_types must name {cfg.n_layers} layers 'global', "
            f"'window' or 'latent' (or, each one mixer alone, 'state', "
            f"'experts' or 'global'), got {kinds}")
    if {STATE, EXPERTS} & set(kinds) and {WINDOW, LATENT} & set(kinds):
        raise ValueError(
            "state layers (a state-space mixer, a gated short convolution; "
            "and layers that are one mixer alone) beside "
            "window or latent layers are not served: a window layer's ring "
            "and a latent layer's rows have no single-mixer body, and a "
            "state cannot give back what a window has passed")
    if operator_stacks(cfg) and EXPERTS in kinds:
        raise ValueError(
            "a short-convolution model's layers have two sub-layers: "
            "'state' or 'global', not 'experts'")
    if WINDOW in kinds and getattr(cfg, "sliding_window", 0) < 1:
        raise ValueError("window layers need sliding_window >= 1")
    if LATENT in kinds and set(kinds) != {LATENT}:
        raise ValueError(
            "latent layers beside K/V layers are not served: a prompt's "
            "token-order rows (prefill's output, the chunked prefill's "
            "accumulator) are one pair of arrays for all its layers")
    return kinds


def kind_layers(cfg) -> dict:
    """{kind: the layers of that kind, in order}; "global" first, a
    kind with no layer left out, and no kind that caches nothing
    ("experts"). The KV pool keeps one pair of arrays a kind
    (llm/kvcache.py init_pool)."""
    kinds = layer_kinds(cfg)
    return {k: tuple(i for i, x in enumerate(kinds) if x == k)
            for k in (GLOBAL, LATENT, WINDOW, STATE) if k in kinds}


def single_mixer(cfg) -> bool:
    """Whether every layer of ``cfg`` is one mixer behind one norm
    (models/moe.py ``MoEConfig.single_mixer``)."""
    return getattr(cfg, "single_mixer", False)


def operator_stacks(cfg) -> bool:
    """Whether a layer's FIRST sub-layer (attention, or a gated short
    convolution) has its parameters in its kind's own stack (``STACKS``)
    beside the two-sub-layer stacks: a model with ``shortconv_kernel``."""
    return bool(getattr(cfg, "shortconv_kernel", 0))


def window_of(cfg, kind: str):
    """The window of a layer kind: None for a global layer."""
    return cfg.sliding_window if kind == WINDOW else None


def has_experts(cfg) -> bool:
    return getattr(cfg, "n_experts", 0) > 0


@dataclasses.dataclass(frozen=True)
class _Segment:
    """``repeats`` periods of layers whose kinds are ``kinds``, rows
    ``row ...`` of ``params[stack]``, the first being layer ``layer0``;
    ``stack`` None: each kind's rows are its own stack's
    (``STACKS``), from the count of its layers before ``layer0`` on."""
    stack: str
    row: int
    layer0: int
    kinds: tuple
    repeats: int


def _segments(cfg) -> tuple:
    """The layers as runs that one scan can walk: per stack, the
    smallest period whose repetition covers the most layers, then the
    same for what is left. Twelve periods of (window, window, window,
    global) behind a dense first layer are three segments; a Llama
    model is one, of period one; four periods of (state, experts, state,
    experts, state, global, experts) are one, whatever stack a kind's rows
    lie in."""
    kinds = layer_kinds(cfg)
    n_dense = getattr(cfg, "n_dense_layers", 0)
    out = []
    for stack, lo, hi in (((None, 0, cfg.n_layers),) if single_mixer(cfg)
                          else (("dense_layers", 0, n_dense),
                                ("layers", n_dense, cfg.n_layers))):
        row = 0
        while lo + row < hi:
            rest = kinds[lo + row:hi]
            p = next(p for p in range(1, len(rest) + 1)
                     if rest[:p] * (len(rest) // p)
                     == rest[:p * (len(rest) // p)])
            out.append(_Segment(stack, row, lo + row, rest[:p],
                                len(rest) // p))
            row += p * (len(rest) // p)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class LayerRef:
    """Which layer a body is running: its attention ``kind``, its place
    among the layers of that kind (``kind_index``: the layer axis of the
    kind's pool) and in its parameter stack (``stack``, ``row``); the
    indices are Python ints or traced."""
    kind: str
    layer: object
    kind_index: object
    stack: dict
    row: object


def _affine(i, a: int, b: int):
    """``i * a + b`` with nothing traced for a = 1, b = 0."""
    if a != 1:
        i = i * a
    return i + b if b else i


def _run_layers(params, cfg, carry, body, per_layer=()):
    """Run ``body(carry, lp, ref, *extras) -> (carry, y)`` over every
    layer in order: each segment a ``lax.scan`` over its periods (the
    kinds inside a period are static, so each has its own code), a
    segment of one period unrolled. ``per_layer`` arrays have all the
    layers on their first axis and are handed to the body a layer at a
    time. Returns (carry, the ys with the layers on their first axis,
    or None where the body returns none). A model whose kinds are stacks
    of their own (``single_mixer``): ``_run_kind_stacks``."""
    if single_mixer(cfg) or operator_stacks(cfg):
        return _run_kind_stacks(params, cfg, carry, body, per_layer or {})
    kinds = layer_kinds(cfg)
    pieces = []
    for seg in _segments(cfg):
        stack, p, r = params[seg.stack], len(seg.kinds), seg.repeats
        before = {k: kinds[:seg.layer0].count(k) for k in set(seg.kinds)}
        rows = next(iter(jax.tree.leaves(stack))).shape[0]

        def period(carry, lps, extras, i, seg=seg, stack=stack, p=p,
                   before=before):
            outs = []
            for j, kind in enumerate(seg.kinds):
                ref = LayerRef(
                    kind, _affine(i, p, seg.layer0 + j),
                    _affine(i, seg.kinds.count(kind),
                            before[kind] + seg.kinds[:j].count(kind)),
                    stack, _affine(i, p, seg.row + j))
                carry, y = body(carry, lps[j], ref,
                                *(e[j] for e in extras))
                outs.append(y)
            return carry, outs

        if r == 1:
            lps = [jax.tree.map(lambda w, j=j: w[seg.row + j], stack)
                   for j in range(p)]
            extras = [[e[seg.layer0 + j] for j in range(p)]
                      for e in per_layer]
            carry, outs = period(carry, lps, extras, 0)
            pieces.extend(jax.tree.map(lambda y: y[None], o) for o in outs)
            continue
        index = jnp.arange(r, dtype=jnp.int32)
        if p == 1 and rows == r:
            # one kind, the whole stack: the stack itself is scanned
            def step(carry, xs, period=period):
                lp, i, *extras = xs
                carry, (y,) = period(carry, [lp], [[e] for e in extras], i)
                return carry, y
            carry, ys = _scan_layers(
                step, carry,
                (stack, index, *(e[seg.layer0:seg.layer0 + r]
                                 for e in per_layer)))
            pieces.append(ys)
            continue

        def cut(w, first):
            return w[first:first + r * p].reshape(r, p, *w.shape[1:])

        def step(carry, xs, period=period, p=p):
            lps, i, *extras = xs
            carry, outs = period(
                carry, [jax.tree.map(lambda w, j=j: w[j], lps)
                        for j in range(p)],
                [[e[j] for j in range(p)] for e in extras], i)
            return carry, jax.tree.map(lambda *ys: jnp.stack(ys), *outs)
        carry, ys = _scan_layers(
            step, carry,
            (jax.tree.map(lambda w: cut(w, seg.row), stack), index,
             *(cut(e, seg.layer0) for e in per_layer)))
        pieces.append(jax.tree.map(
            lambda y: y.reshape(r * p, *y.shape[2:]), ys))
    if len(pieces) == 1:
        return carry, pieces[0]
    return carry, jax.tree.map(lambda *ys: jnp.concatenate(ys), *pieces)


def _stack_row(stack, row):
    """Row ``row`` (a Python int, or traced) of every leaf of ``stack``."""
    return jax.tree.map(
        lambda w: w[row] if isinstance(row, int) else
        lax.dynamic_index_in_dim(w, row, keepdims=False), stack)


def _run_kind_stacks(params, cfg, carry, body, per_layer: dict):
    """``_run_layers`` where a kind's parameters are a stack of its own
    (``STACKS``): a period takes each kind's rows from that kind's stack.
    A layer's parameters are INDEXED in the stack by the layer's (traced)
    row, one slice a consumer, and not handed to the scan as its ``xs``:
    a turn of a scan over periods copies its period's rows of every leaf
    the body reads (PERF.md section 7, "the period scan"), every weight
    once a decode step. ``per_layer`` is {kind: arrays with THAT KIND's
    layers on their first axis}, handed to the kind's bodies a layer at a
    time; the ys come back the same way, {kind: that kind's ys, its layers
    on their first axis} (a body's y has its kind's structure; None for a
    kind that returns none). With ``operator_stacks`` a kind's stack holds
    the layers' first sub-layers alone: the body's ``lp`` is that row beside
    the layer's own row of its segment's stack (``dense_layers`` /
    ``layers``), which is ``ref.stack`` and ``ref.row`` (where the grouped
    matmuls read the experts in place)."""
    kinds = layer_kinds(cfg)
    pieces = []
    for seg in _segments(cfg):
        p, r = len(seg.kinds), seg.repeats
        # in the period's own order (a set's hangs on the process's hash seed)
        per = {k: seg.kinds.count(k) for k in dict.fromkeys(seg.kinds)}
        first = {k: kinds[:seg.layer0].count(k) for k in per}

        def period(carry, extras, i, seg=seg, p=p, per=per, first=first):
            outs = {k: [] for k in per}
            for j, kind in enumerate(seg.kinds):
                c = seg.kinds[:j].count(kind)
                row = _affine(i, per[kind], first[kind] + c)
                stack = params[STACKS[kind]]
                lp = _stack_row(stack, row)
                ref = LayerRef(kind, _affine(i, p, seg.layer0 + j), row,
                               stack, row)
                if seg.stack is not None:       # operator_stacks
                    own, at = params[seg.stack], _affine(i, p, seg.row + j)
                    lp = {**_stack_row(own, at), **lp}
                    ref = dataclasses.replace(ref, stack=own, row=at)
                carry, y = body(carry, lp, ref,
                                *(e[c] for e in extras.get(kind, ())))
                outs[kind].append(y)
            return carry, {k: jax.tree.map(lambda *ys: jnp.stack(ys), *ys)
                           for k, ys in outs.items()}

        # a kind's per-layer arrays, the rows this segment walks by period
        extras = {k: tuple(
            e[first[k]:first[k] + r * per[k]].reshape(r, per[k],
                                                      *e.shape[1:])
            for e in per_layer.get(k, ())) for k in per}
        if r == 1:
            carry, ys = period(carry, jax.tree.map(lambda e: e[0], extras), 0)
        else:
            def step(carry, xs, period=period):
                return period(carry, *xs)
            carry, ys = _scan_layers(
                step, carry, (extras, jnp.arange(r, dtype=jnp.int32)))
            ys = jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:]), ys)
        pieces.append(ys)
    out = {}
    for k in dict.fromkeys(k for ys in pieces for k in ys):
        out[k] = jax.tree.map(lambda *ys: jnp.concatenate(ys),
                              *(ys[k] for ys in pieces if k in ys))
    return carry, out


def rope_tables(cfg, positions) -> tuple:
    """What ``_layer`` rotates with at ``positions`` (b, s): (cos, sin),
    and for a latent model YaRN's tables over the rotary part of a head
    plus the positions' query scale a(t) (b, s) float32: 1 + beta *
    ln(1 + floor(t / original)), 1 below the original length."""
    if LATENT not in layer_kinds(cfg):
        return _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    dim, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    if not cfg.rope_factor:
        return (*_rope_tables(positions, dim, theta),
                jnp.ones(positions.shape, jnp.float32))
    cos, sin = _rope_tables_freqs(
        positions,
        yarn_inv_freq(dim, theta, cfg.rope_factor, cfg.rope_original_len,
                      cfg.rope_beta_fast, cfg.rope_beta_slow),
        yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    a = 1.0 + cfg.query_scale_beta * jnp.log1p(
        (positions // cfg.rope_original_len).astype(jnp.float32))
    return cos, sin, a


def softmax_scale(cfg, kind: str) -> float:
    """What a layer's scores q . k are multiplied by: head ** -0.5, for
    a latent layer times YaRN's mscale(factor, mscale_all_dim) squared."""
    if kind != LATENT:
        return cfg.head_dim ** -0.5
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) \
        if cfg.rope_factor else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _latent_qkv(y, lp, cfg, rope):
    """A latent layer's projections of normed rows y (b, s, d): (q (b,
    s, h, nope + rope) with its rope part rotated and a(t) applied, the
    positions' cache rows c (b, s, kv_lora_rank), normed, and kr (b, s,
    rope), rotated)."""
    cos, sin, a = rope
    b, s = y.shape[:2]
    eps, nope = cfg.norm_eps, cfg.qk_nope_head_dim
    with jax.named_scope("mla.q"):
        cq = _rmsnorm(y @ lp["wq_a"], lp["q_a_norm"], eps)
        # fenced as _qkv's products are (the weight read where it lies)
        q = lax.optimization_barrier(cq @ lp["wq_b"]).reshape(
            b, s, cfg.n_heads, -1)
        q = jnp.concatenate(
            [q[..., :nope], _rope_pairs(q[..., nope:], cos, sin)], axis=-1)
        q = q * a[:, :, None, None].astype(q.dtype)
    with jax.named_scope("mla.kv_down"):
        ckr = lax.optimization_barrier(y @ lp["wkv_a"])
        c = _rmsnorm(ckr[..., :cfg.kv_lora_rank], lp["kv_norm"], eps)
        kr = _rope_pairs(ckr[..., cfg.kv_lora_rank:], cos, sin)
    return q, c, kr


def latent_expand(q, c, kr, lp, cfg):
    """Materialise what a prefill attends: per-head keys [c Wk_b[h] |
    kr] and values c Wv_b[h] from rows c (1, n, kv_lora_rank), kr (1, n,
    rope), in q's dtype: (1, n, h, nope + rope) and (1, n, h, v)."""
    with jax.named_scope("mla.expand"):
        # an accumulator gathered from the pool has the pool's widths
        c = c[..., :cfg.kv_lora_rank].astype(q.dtype)
        kr = kr[..., :cfg.qk_rope_head_dim]
        k_nope = jnp.einsum("bnc,hdc->bnhd", c, lp["wk_b"])
        v = jnp.einsum("bnc,hcd->bnhd", c, lp["wv_b"])
        kr = jnp.broadcast_to(kr.astype(q.dtype)[:, :, None, :],
                              (*k_nope.shape[:3], kr.shape[-1]))
        return jnp.concatenate([k_nope, kr], axis=-1), v


def latent_absorb(q, lp, cfg):
    """The absorbed query: q (..., h, nope + rope) -> (..., h,
    kv_lora_rank + rope), [q_nope Wk_b[h] | q_rope], which scores a
    cache row [c | kr] as q scores the key expanded from it."""
    with jax.named_scope("mla.absorb"):
        nope = cfg.qk_nope_head_dim
        return jnp.concatenate(
            [jnp.einsum("...hd,hdc->...hc", q[..., :nope], lp["wk_b"]),
             q[..., nope:]], axis=-1)


def latent_unabsorb(o, lp, cfg):
    """Each head's weighted sum of c rows, o (..., h * kv_lora_rank)
    float32, through Wv_b[h]: (..., h * v) in the weights' dtype."""
    with jax.named_scope("mla.unabsorb"):
        w = lp["wv_b"]
        o = o.reshape(*o.shape[:-1], cfg.n_heads, -1).astype(w.dtype)
        o = jnp.einsum("...hc,hcd->...hd", o, w)
        return o.reshape(*o.shape[:-2], -1)


def _attend_pool(attend, ref, lp, cfg, q, k, v, pool):
    """A decode or verify forward's ``attend`` against the pool; a latent
    layer's in the absorbed form around it."""
    if ref.kind != LATENT:
        return attend(ref, q, k, v, pool)
    o, pool = attend(ref, latent_absorb(q, lp, cfg), k, v, pool)
    return latent_unabsorb(o, lp, cfg), pool


def _qkv(y, lp, cfg: LlamaConfig):
    b, s = y.shape[:2]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def heads(w, n, norm=None):
        # fenced: XLA would fold the reshape into heads into the product,
        # and then wants the WEIGHT turned (out, in): the stack copied in
        # a decode program's entry, a layer's slice of it written out
        # every step where the layers are unrolled, a transposing copy a
        # layer in prefill (tests/test_aot_tpu_compile.py holds the
        # compiled programs to reading wq, wk and wv where they lie)
        z = lax.optimization_barrier(y @ lp[w])
        if norm and getattr(cfg, "qk_norm", False):     # the whole width
            z = _rmsnorm(z, lp[norm], cfg.norm_eps)
        z = z.reshape(b, s, n, hd)
        if norm and getattr(cfg, "qk_head_norm", False):    # a head
            z = _rmsnorm(z, lp[norm], cfg.norm_eps)
        return z
    return heads("wq", h, "q_norm"), heads("wk", kvh, "k_norm"), \
        heads("wv", kvh)


def _feed_forward(y, lp, cfg, ref: LayerRef, active, routed=None):
    """The layer's feed-forward on normed rows y (b, s, d): the dense
    SwiGLU, or for a layer with a router this device's part of the
    expert layer (models/moe.py serve_block). Returns (out, the expert
    layer's counts or None). ``routed``: a list that takes the experts
    every row chose, (b * s, k) int32, -1 in a layer without a router (a
    comparison's entries ask for them: ``prefill_chunk_routed``,
    ``decode_logits_core(chosen=True)``; no serving path does)."""
    if "router" not in lp:
        if routed is not None:
            routed.append(jnp.full(
                (y.size // y.shape[-1], cfg.experts_per_token), -1,
                jnp.int32))
        with jax.named_scope("mlp"):
            return ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                    @ lp["w_down"]), None
    from ray_tpu.models import moe
    out, stats, *chosen = moe.serve_block(
        y.reshape(-1, y.shape[-1]), lp, cfg, stack=ref.stack, row=ref.row,
        active=active, choice=routed is not None)
    if routed is not None:
        routed.extend(chosen)
    return out.reshape(y.shape), stats


# --- the mixed stream --------------------------------------------------------
#
# Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
# Hyper-Connections, arXiv:2409.19606). With ``hc_mult`` n > 1 a position's
# residual is X, n rows of d, and a sub-layer F (with its own pre-norm)
# reads one mix of them and writes back through a doubly stochastic matrix:
#
#   x~    = vec(X) * rsqrt(mean(vec(X)^2) + norm_eps)    (n d,), no weight
#   z     = a * (x~ @ phi) + b        phi's columns [pre n | post n | res n n]
#   Hpre  = sigmoid(z_pre)     Hpost = 2 sigmoid(z_post)
#   M     = exp(clip(z_res, hc_res_clamp_min, hc_res_clamp_max))    (n, n)
#   hc_sinkhorn_iters times:  M = M / (its columns' sums + hc_eps),
#                             then M = M / (its rows' sums + hc_eps)
#   X'    = M @ X + outer(Hpost, F(Hpre @ X))
#
# The embedding is copied to the n rows; they are summed before the final
# norm. The coefficients are float32 from the stream to Hres (the product
# with phi at ``highest`` precision); the stream itself stays in the
# model's dtype. The forwards carry it as (n, b, s, d), the copies
# outermost: a copy is then a plain (b, s, d) slab, and every mix is
# elementwise on slabs with a (b, s) coefficient (n = 4 as a second-minor
# dimension would lie in tiles of 8 or 16 rows). Sinkhorn runs on (n, n,
# b, s), the positions on the lanes, its sums written as adds of slabs, so
# the iterations are one elementwise chain.

def _hc(cfg) -> int:
    """The stream's copies a position; 0 for the plain residual."""
    return getattr(cfg, "hc_copies", 0)


def _embed(params, tokens, cfg):
    """The stream the layers carry for ``tokens`` (...): their embeddings
    (..., d), for a mixed stream copied to its n rows, (n, ..., d)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    n = _hc(cfg)
    return jnp.broadcast_to(x[None], (n, *x.shape)) if n else x


def _final_norm(x, params, cfg):
    """What the head multiplies: the final norm of the stream, a mixed
    stream's n rows summed first."""
    if _hc(cfg):
        x = jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)
    return _rmsnorm(x, params["final_norm"], cfg.norm_eps)


def mhc_sinkhorn(m, iters: int, eps: float):
    """m (n, n, ...) positive -> doubly stochastic to the iteration's
    accuracy; its rows sum to 1 within eps (they are normalised last)."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / (sum(m[i] for i in range(n))[None] + eps)
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
    return m


def mhc_coefficients(x, lp, sub: str, cfg):
    """The stream x (n, b, s, d) -> (Hpre (n, b, s), Hpost (n, b, s), Hres
    (n, n, b, s)) float32 of sub-layer ``sub`` ("attn" | "mlp")."""
    with jax.named_scope("mhc.coeff"):
        n, d = x.shape[0], x.shape[-1]
        xf = x.astype(jnp.float32)
        r = lax.rsqrt(jnp.mean(jnp.mean(xf * xf, axis=-1), axis=0)
                      + cfg.norm_eps)                           # (b, s)
        phi = lp[f"hc_{sub}_phi"].reshape(n, d, -1)
        z = jnp.einsum("nbsd,ndk->kbs", xf, phi,
                       precision=lax.Precision.HIGHEST) * r
        a, b = lp[f"hc_{sub}_a"], lp[f"hc_{sub}_b"][:, None, None]
        pre = jax.nn.sigmoid(a[0] * z[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(a[2] * z[2 * n:] + b[2 * n:],
                             cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
        res = mhc_sinkhorn(m.reshape(n, n, *m.shape[1:]),
                           cfg.hc_sinkhorn_iters, cfg.hc_eps)
        return pre, post, res


def _mhc_read(x, pre):
    """Hpre @ X: what the sub-layer reads, (b, s, d) in x's dtype."""
    with jax.named_scope("mhc.read"):
        return sum(pre[i][..., None] * x[i].astype(jnp.float32)
                   for i in range(x.shape[0])).astype(x.dtype)


def _mhc_write(x, res, post, f):
    """Hres @ X + outer(Hpost, f): the stream after the sub-layer."""
    with jax.named_scope("mhc.write"):
        n = x.shape[0]
        xf, ff = x.astype(jnp.float32), f.astype(jnp.float32)
        return jnp.stack(
            [sum(res[i, j][..., None] * xf[j] for j in range(n))
             + post[i][..., None] * ff for i in range(n)]).astype(x.dtype)


def _residual(x, lp, cfg, sub: str, f):
    """The stream after sub-layer ``f`` (what it reads -> (its output,
    what else it returns)): x + f(x), or the mixed stream's read and
    write around it."""
    if not _hc(cfg):
        out, aux = f(x)
        return x + out, aux
    pre, post, res = mhc_coefficients(x, lp, sub, cfg)
    out, aux = f(_mhc_read(x, pre))
    return _mhc_write(x, res, post, out), aux


def _as_cached(rows, cfg):
    """K or V rows (b, s, kv heads, head) as the pool keeps them: heads
    narrower than a lane tile lie ``kv_row_heads`` a row (llm/kvcache.py
    row_shapes), the same values in the same order."""
    from ray_tpu.llm.kvcache import heads_packed
    pack = heads_packed(cfg)
    return rows if pack == 1 else rows.reshape(
        *rows.shape[:2], cfg.n_kv_heads // pack, -1)


def _heads_apart(rows, cfg):
    """``_as_cached`` back: (..., rows' heads, lanes) -> (..., kv heads,
    head)."""
    from ray_tpu.llm.kvcache import heads_packed
    return rows if heads_packed(cfg) == 1 else rows.reshape(
        *rows.shape[:-2], cfg.n_kv_heads, cfg.head_dim)


def _layer(x, lp, cfg, ref: LayerRef, rope, attend, active=None,
           state=None, routed=None):
    """One decoder layer of every serving forward. x (b, s, d), or the
    mixed stream (n, b, s, d); ``rope`` the (cos, sin) tables of the
    positions; ``attend(q, k, v, wo)`` attends (the forwards differ in
    nothing else) and returns the projected output, (b, s, d). Returns
    (x, k, v, expert counts or None); k is as the cache keeps it (after
    RoPE; ``_as_cached``). For a latent layer k and v are the cache's rows
    c and kr (``_latent_qkv``). A STATE layer's first sub-layer is a gated
    short convolution, ``state(y, lp) -> (out, what the forward keeps of
    it)``, handed in as ``attend`` is (the forwards differ in where the
    conv's tail comes from and goes to): k is what it keeps, v None.
    ``routed``: ``_feed_forward``'s."""
    post = getattr(cfg, "post_norm", False)
    eps = cfg.norm_eps

    def attention(x):
        y = x if post else _rmsnorm(x, lp["attn_norm"], eps)
        if ref.kind == STATE:
            out, kept = state(y, lp)
            return out, (kept, None)
        if ref.kind == LATENT:
            # k, v: the positions' cache rows c and kr (no head axis);
            # the forward's ``attend`` expands or absorbs
            q, k, v = _latent_qkv(y, lp, cfg, rope)
        else:
            q, k, v = _qkv(y, lp, cfg)
            if ref.kind == WINDOW \
                    or getattr(cfg, "rope_layers", "all") == "all":
                q, k = _rope(q, *rope), _rope(k, *rope)
        a = attend(q, k, v, lp["wo"])
        if ref.kind != LATENT:
            k, v = _as_cached(k, cfg), _as_cached(v, cfg)
        return (_rmsnorm(a, lp["attn_norm"], eps) if post else a), (k, v)

    def feed_forward(x):
        y = x if post else _rmsnorm(x, lp["mlp_norm"], eps)
        m, stats = _feed_forward(y, lp, cfg, ref, active, routed)
        return (_rmsnorm(m, lp["mlp_norm"], eps) if post else m), stats

    with jax.named_scope("attention." + ref.kind):
        x, (k, v) = _residual(x, lp, cfg, "attn", attention)
    x, stats = _residual(x, lp, cfg, "mlp", feed_forward)
    return x, k, v, stats


def _mixer_layer(x, lp, cfg, ref: LayerRef, rope, attend, state,
                 active=None):
    """One layer of a model whose layers are each ONE mixer behind one norm
    (``single_mixer``): x + mixer(RMSNorm(x)), x (b, s, d). An "experts"
    layer is the expert layer alone; a "state" layer ``state(y, lp) ->
    (out, what the forward keeps of it)`` (the forwards differ in where the
    state comes from and goes to); a "global" layer attention alone, through
    the forward's ``attend`` as in ``_layer``. Returns (x, what the layer
    caches: (k, v), the state layer's, or for an expert layer, which caches
    nothing, the experts each row chose, (b * s, k) int32, which a forward
    drops unless it was asked for them (``prefill_routed``,
    ``decode_logits_core(chosen=True)``); expert counts or None)."""
    y = _rmsnorm(x, lp["norm"], cfg.norm_eps)
    if ref.kind == EXPERTS:
        from ray_tpu.models import moe
        out, stats, chosen = moe.serve_block(
            y.reshape(-1, y.shape[-1]), lp, cfg, stack=ref.stack,
            row=ref.row, active=active, choice=True)
        return x + out.reshape(y.shape), chosen, stats
    if ref.kind == STATE:
        out, kept = state(y, lp)
        return x + out, kept, None
    with jax.named_scope("attention." + ref.kind):
        q, k, v = _qkv(y, lp, cfg)
        if getattr(cfg, "rope_layers", "all") == "all":
            q, k = _rope(q, *rope), _rope(k, *rope)
        return x + attend(q, k, v, lp["wo"]), (k, v), None


def _mixer_prefill_layer(x, lp, cfg, ref, rope, attend, length, start, kv):
    """A prefill forward's layer of a single-mixer model, x (1, s, d):
    ``start`` is what a state layer starts from, (state, conv tail), and it
    hands on what it leaves at the row's ``length``; ``kv(k, v)`` makes an
    attention layer's y of its new rows. Returns (x, the layer's y)."""
    from ray_tpu.ops import ssm

    def state(y, lp):
        out, st, tail = ssm.mixer_prefill(y[0], lp, cfg, *start, length)
        return out[None], (st, tail)
    x, kept, _ = _mixer_layer(x, lp, cfg, ref, rope, attend, state)
    return x, (kv(*kept) if ref.kind == GLOBAL else kept)   # EXPERTS: chosen


def _tail_prefill(cfg, start: tuple, length):
    """A prefill forward's ``state`` hook of ``_layer``: the gated short
    convolution over one row x (1, s, d) from the tail ``start`` = (tail,),
    handing on the tail at the row's ``length``."""
    from ray_tpu.ops import shortconv

    def state(y, lp):
        out, tail = shortconv.prefill(y[0], lp, cfg, start[0], length)
        return out[None], tail
    return state


def _state_step(y, lp, cfg, ref, pool, live, step):
    """A decode step's state layer: y (slots, d) normed rows against the
    layer's place (``ref.kind_index``) in the pool's states and conv tails,
    which are the layer scans' carry. The slots of ``live`` move on one
    token; another slot's state and tail stay as they are (its row of the
    output is garbage nobody reads). ``step(ref, x, dt, A, B, C, D, pool)
    -> (y, pool)`` is the one-token rule against the layer's states in the
    pool (llm/kvcache.py _pool_state_step), as ``attend`` is an attention
    layer's. Returns (out (slots, d), pool)."""
    from ray_tpu.llm.kvcache import POOL_KEYS
    from ray_tpu.ops import ssm
    tk = POOL_KEYS[STATE][1]
    l = ref.kind_index
    tl = lax.dynamic_index_in_dim(pool[tk], l, keepdims=False)

    def rule(*rows):
        nonlocal pool
        y, pool = step(ref, *rows, pool)
        return y
    out, tl2 = ssm.mixer_step(y, lp, cfg, tl, rule)
    with jax.named_scope("ssm.conv"):
        tl2 = jnp.where(live[:, None, None], tl2, tl)
        tails = lax.dynamic_update_index_in_dim(pool[tk], tl2, l, 0)
    return out, {**pool, tk: tails}


def _tail_step(y, lp, cfg, ref, pool, live):
    """A decode step's gated short convolution: y (slots, d) normed rows
    against the layer's place (``ref.kind_index``) in the pool's conv tails,
    which are the layer scans' carry. The slots of ``live`` move on by one
    row; another slot's tail stays as it is (its row of the output is
    garbage nobody reads). Returns (out (slots, d), pool)."""
    from ray_tpu.llm.kvcache import POOL_KEYS
    from ray_tpu.ops import shortconv
    tk = POOL_KEYS[STATE][1]
    l = ref.kind_index
    tl = lax.dynamic_index_in_dim(pool[tk], l, keepdims=False)
    out, tl2 = shortconv.step(y, lp, cfg, tl)
    with jax.named_scope("shortconv.conv"):
        tl2 = jnp.where(live[:, None], tl2, tl)
        tails = lax.dynamic_update_index_in_dim(pool[tk], tl2, l, 0)
    return out, {**pool, tk: tails}


def fresh_state(cfg, dtype) -> tuple:
    """What a prompt's state layers start from: (a zero state (float32), a
    zero conv tail) of one layer."""
    from ray_tpu.llm.kvcache import state_arrays
    return tuple(jnp.zeros(shape, dt)
                 for shape, dt in state_arrays(cfg, dtype).values())


def _gqa_attend_cached(q, cache_k, cache_v, lengths, cfg: LlamaConfig,
                       window=None):
    """q: (b, h, hd) current-token queries; cache_k/v: (b, L, kvh, hd);
    lengths: (b,) valid cache entries per slot (incl. current token);
    ``window``: attend the last ``window`` of them only."""
    b = q.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd).astype(jnp.float32)
    kf = cache_k.astype(jnp.float32)
    scores = jnp.einsum("bkgd,blkd->bkgl", qg, kf) / jnp.sqrt(hd)
    mask = jnp.arange(cache_k.shape[1])[None] < lengths[:, None]  # (b, L)
    if window is not None:
        mask = mask & (jnp.arange(cache_k.shape[1])[None]
                       >= lengths[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgl,blkd->bkgd", probs,
                     cache_v.astype(jnp.float32))
    return out.reshape(b, h * hd)


def _serve_attn_impl(cfg: LlamaConfig) -> str:
    """Map the model's attn_impl onto the serving prefill dispatch:
    'ring' is a training-only (context-parallel) layout — serving
    treats it as 'auto' (flash on TPU for long prompts, reference
    elsewhere)."""
    impl = getattr(cfg, "attn_impl", "auto")
    return "auto" if impl == "ring" else impl


def resolve_prefill_impl(cfg: LlamaConfig) -> str:
    """The prefill attention this process runs for ``cfg``, with
    'auto' resolved from the platform: the flash kernel on a TPU (for
    buckets of 128 tokens and more; shorter ones take the XLA
    reference by design), the reference elsewhere."""
    from ray_tpu.ops.attention import _on_tpu
    impl = _serve_attn_impl(cfg)
    if impl == "auto":
        return "flash" if _on_tpu() else "reference"
    return impl


def _prefill_attn_kw(cfg, kind: str) -> dict:
    """The prefill attention call's arguments beside the common ones: the
    config's flash tiles (the kernel's own 128 x 128 where it says
    nothing else) and, for a window layer, the window."""
    kw = {"block_q": cfg.attn_block_q, "block_k": cfg.attn_block_k}
    if kind == WINDOW:
        kw["window"] = cfg.sliding_window
    return kw


def _logits(x, params):
    """Normed rows x (..., d) through the head -> (..., vocab) float32; a
    model with tied embeddings has no ``lm_head`` leaf and multiplies with
    the embedding as it lies, (vocab, d)."""
    if "lm_head" in params:
        return (x @ params["lm_head"]).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", x, params["embed"]).astype(
        jnp.float32)


def _head(x, params, cfg, length):
    """Last valid row of x (1, s, d) -> (vocab,) float32 logits."""
    x = _final_norm(x, params, cfg)
    last = jnp.take(x[0], length - 1, axis=0)
    return _logits(last, params)


def _prefill(params: dict, tokens: jax.Array, length: jax.Array,
             cfg: LlamaConfig, max_len: int) -> Tuple[jax.Array, dict, object]:
    """One padded prompt. tokens: (s,) int32 (padded to a bucket);
    length: () actual prompt length. Returns (last-token logits (vocab,),
    per-layer kv padded to max_len: k/v (layers, max_len, kvh, hd); of a
    latent model the cache's rows under the same two names, "k" the c
    rows (layers, max_len, kv_lora_rank) and "v" the kr rows (layers,
    max_len, qk_rope_head_dim), from which its attention materialised
    per-head keys and values). Of a model with state layers k/v are its
    global layers' alone, and "ssm" / "conv" each state layer's state and
    conv tail after position length - 1 (``_mixer_layer``); a third value,
    the experts every position chose in each expert layer that is a mixer
    alone, (expert layers, s, k) int32, or None.

    Attention dispatches through ops.attention (cfg.attn_impl): the
    pallas flash kernel tiles long prompts on TPU instead of
    materializing the O(s^2) score tensor (a window layer's band
    included). Causal alone is exact here: pad keys sit at positions >=
    length, and every USED query row is < length, so causality already
    excludes them (pad rows' outputs are garbage but only row length-1
    is read)."""
    from ray_tpu.ops.attention import attention as _attention
    s = tokens.shape[0]
    x = _embed(params, tokens[None], cfg)                # (1, s, emb)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    rope = rope_tables(cfg, positions)
    h, hd = cfg.n_heads, cfg.head_dim

    def layer(x, lp, ref):
        def attend(q, k, v, wo):
            if ref.kind == LATENT:
                k, v = latent_expand(q, k, v, lp, cfg)
            o = _attention(q, k, v, causal=True,
                           sm_scale=softmax_scale(cfg, ref.kind),
                           impl=_serve_attn_impl(cfg),
                           **_prefill_attn_kw(cfg, ref.kind))
            return o.reshape(1, s, h * hd).astype(x.dtype) @ wo
        if single_mixer(cfg):
            return _mixer_prefill_layer(
                x, lp, cfg, ref, rope, attend, length,
                fresh_state(cfg, x.dtype), lambda k, v: (k[0], v[0]))
        x, k, v, _ = _layer(
            x, lp, cfg, ref, rope, attend,
            state=_tail_prefill(cfg, fresh_state(cfg, x.dtype), length)
            if ref.kind == STATE else None)
        return x, (k if ref.kind == STATE else (k[0], v[0]))

    x, ys = _run_layers(params, cfg, x, layer)
    logits = _head(x, params, cfg, length)
    # pad the rows (layers, s, ...) -> (layers, max_len, ...)
    def pad(rows):
        return jnp.pad(rows, [(0, 0), (0, max_len - s)]
                       + [(0, 0)] * (rows.ndim - 2))
    if single_mixer(cfg):
        # K and V of the global layers alone; a state layer's state and
        # conv tail at the prompt's length
        (ks, vs), (ssm, conv) = ys[GLOBAL], ys[STATE]
        return logits, {"k": pad(ks), "v": pad(vs), "ssm": ssm,
                        "conv": conv}, ys.get(EXPERTS)
    if operator_stacks(cfg):
        # K and V of the global layers alone; a short convolution's tail
        # at the prompt's length
        from ray_tpu.llm.kvcache import POOL_KEYS
        ks, vs = ys[GLOBAL]
        return logits, {"k": pad(ks), "v": pad(vs),
                        POOL_KEYS[STATE][1]: ys[STATE]}, None
    ks, vs = ys
    return logits, {"k": pad(ks), "v": pad(vs)}, None


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill(params: dict, tokens: jax.Array, length: jax.Array,
            cfg: LlamaConfig, max_len: int) -> Tuple[jax.Array, dict]:
    """``_prefill``'s (last-token logits, per-layer kv): what the engine
    admits a request with."""
    return _prefill(params, tokens, length, cfg, max_len)[:2]


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_routed(params: dict, tokens: jax.Array, length: jax.Array,
                   cfg: LlamaConfig, max_len: int):
    """``prefill`` with the experts every position chose: ONE program gives
    the logits and the routing behind them, for a comparison with a
    reference that has to follow that routing (benchmarks/families/
    nemotron_h.py; ``kvcache.paged_decode_logits(chosen=True)`` is the
    decode step's). No serving path calls it."""
    return _prefill(params, tokens, length, cfg, max_len)


def prefill_chunk(params: dict, tokens: jax.Array, length: jax.Array,
                  offset, acc: dict,
                  cfg: LlamaConfig) -> Tuple[jax.Array, dict]:
    """One CHUNK of a long prompt: process `tokens` (one padded bucket)
    starting at absolute position `offset`, attending to all earlier
    chunks' K/V in `acc` plus causally within the chunk. Lets prompts
    longer than the largest prefill bucket stream through in
    bucket-sized pieces at O(chunk x max_len) attention per piece —
    long-prompt serving without a max_len-sized compile per prompt
    (reference capability: vLLM chunked prefill).

    tokens: (s,) int32 padded chunk; length: () valid tokens in it;
    offset: () absolute start position; acc: {"k","v"}
    (layers, max_len, kvh, hd), donated — earlier chunks' KV, updated
    in place with this chunk's. Returns (logits of the chunk's last
    valid token (vocab,), updated acc). Positions in acc beyond
    offset+length may hold pad garbage; every consumer masks by total
    length, so it is never attended to. Of a model with state layers acc
    also holds "ssm" / "conv", each state layer's state and conv tail: the
    chunk starts from them and hands on what it leaves at its ``length``.

    Dispatch: flash-capable impls route to the pallas kernel with the
    chunk's absolute offset placing the causal diagonal (one compile
    per distinct offset — offsets are chunk-size multiples, so at most
    ceil(max_len / chunk) variants); otherwise the dynamic-offset XLA
    path below compiles once."""
    impl = _chunk_flash_impl(cfg, tokens.shape[0])
    if impl is not None:
        return _prefill_chunk_flash(params, tokens, length, int(offset),
                                    acc, cfg, impl)
    return _prefill_chunk_dyn(params, tokens, length,
                              jnp.asarray(offset, jnp.int32), acc, cfg)


def prefill_chunk_routed(params, tokens, length, offset, acc, cfg):
    """``prefill_chunk`` with the experts every row of the chunk chose in
    each layer, (layers, s, k) int32 in the model's layer order, -1 in a
    layer without a router: (logits, acc, chosen). ONE program gives the
    chunk's logits, what it leaves and the routing behind them, for a
    comparison with a reference that has to follow that routing where
    rounding decides it (benchmarks/families/lfm2_moe.py;
    ``decode_logits_core(chosen=True)`` is the decode step's). No serving
    path calls it; a model whose operators lie in stacks of their own
    (``operator_stacks``) alone."""
    impl = _chunk_flash_impl(cfg, tokens.shape[0])
    if impl is not None:
        return _prefill_chunk_flash(params, tokens, length, int(offset),
                                    acc, cfg, impl, routed=True)
    return _prefill_chunk_dyn(params, tokens, length,
                              jnp.asarray(offset, jnp.int32), acc, cfg,
                              routed=True)


def _chunk_flash_impl(cfg, s: int):
    """The flash impl a chunk of ``s`` tokens takes, or None for the
    dynamic-offset XLA path."""
    from ray_tpu.ops.attention import _on_tpu
    impl = _serve_attn_impl(cfg)
    if impl in ("flash", "flash_interpret"):
        return impl
    return "flash" if impl == "auto" and _on_tpu() and s >= 128 else None


def chunk_attended_rows(cfg, s: int, offset: int, acc_len: int) -> int:
    """Rows of the accumulator one ``prefill_chunk`` of ``s`` tokens at
    ``offset`` attends, and for a latent model EXPANDS to per-head keys
    and values: the prefix and the chunk on the flash path (the offset
    is static there), the whole accumulator on the dynamic-offset path
    (masked after)."""
    if _chunk_flash_impl(cfg, s) is not None:
        return min(offset + s, acc_len)
    return acc_len


def chunk_expanded_rows(cfg, s: int, offset: int, acc_len: int) -> int:
    """Cache rows that chunk expands to per-head keys and values: what
    it attends for a latent model, none for a model that caches K and V
    (the engine's ``llm_latent_rows_expanded_size``)."""
    if LATENT not in layer_kinds(cfg):
        return 0
    return chunk_attended_rows(cfg, s, offset, acc_len)


def _into_acc(acc, new, offset):
    """One layer's accumulator (L, kvh, hd) with the chunk's rows
    (1, s, kvh, hd) written at ``offset`` (latent rows: (L, width))."""
    return lax.dynamic_update_slice(
        acc, new[0].astype(acc.dtype),
        (jnp.int32(offset),) + (jnp.int32(0),) * (acc.ndim - 1))


@partial(jax.jit, static_argnames=("cfg", "offset", "impl", "routed"),
         donate_argnums=(4,))
def _prefill_chunk_flash(params: dict, tokens: jax.Array,
                         length: jax.Array, offset: int, acc: dict,
                         cfg: LlamaConfig, impl: str, routed: bool = False):
    """Flash chunked prefill: the kernel's q_offset places the causal
    diagonal at the chunk's absolute position, so no O(s x L) mask or
    score tensor is materialized. Causal alone is exact for every USED
    query row (see prefill)."""
    from ray_tpu.ops.attention import attention as _attention
    s = tokens.shape[0]
    h, hd = cfg.n_heads, cfg.head_dim
    x = _embed(params, tokens[None], cfg)                   # (1, s, emb)
    positions = (offset + jnp.arange(s, dtype=jnp.int32))[None]
    rope = rope_tables(cfg, positions)

    def layer(x, lp, ref, *acc):        # ak/av: (L, kvh, hd) the layer's
        ak, av = acc if len(acc) == 2 else (None, None)

        def attend(q, k, v, wo):
            if ref.kind != LATENT:
                k, v = _as_cached(k, cfg), _as_cached(v, cfg)
            nk, nv = _into_acc(ak, k, offset), _into_acc(av, v, offset)
            if ref.kind == LATENT:
                # the accumulator keeps latent rows; what this chunk
                # attends (the prefix and itself) is expanded here
                n = min(offset + s, nk.shape[0])
                nk, nv = latent_expand(q, nk[None, :n], nv[None, :n], lp,
                                       cfg)
            else:
                nk = _heads_apart(nk[None], cfg).astype(q.dtype)
                nv = _heads_apart(nv[None], cfg).astype(q.dtype)
            o = _attention(q, nk, nv, causal=True,
                           sm_scale=softmax_scale(cfg, ref.kind),
                           impl=impl, q_offset=offset,
                           **_prefill_attn_kw(cfg, ref.kind))
            return o.reshape(1, s, h * hd).astype(x.dtype) @ wo
        return _chunk_layer(x, lp, cfg, ref, rope, attend, length, acc,
                            offset, routed)

    return _chunk_layers(params, cfg, x, layer, acc, length, routed)


@partial(jax.jit, static_argnames=("cfg", "routed"), donate_argnums=(4,))
def _prefill_chunk_dyn(params: dict, tokens: jax.Array,
                       length: jax.Array, offset: jax.Array, acc: dict,
                       cfg: LlamaConfig,
                       routed: bool = False) -> Tuple[jax.Array, dict]:
    """Dynamic-offset XLA path (single compile; O(s x L) scores)."""
    s = tokens.shape[0]
    L = acc["k"].shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    x = _embed(params, tokens[None], cfg)                   # (1, s, emb)
    positions = (offset + jnp.arange(s, dtype=jnp.int32))[None]
    rope = rope_tables(cfg, positions)
    q_pos = positions[0]                                    # (s,)
    k_pos = jnp.arange(L, dtype=jnp.int32)                  # (L,)
    # causal over ABSOLUTE positions (covers both earlier chunks and
    # intra-chunk order), limited to valid keys
    m = (k_pos[None, :] <= q_pos[:, None]) & \
        (k_pos[None, :] < offset + length)
    masks = {GLOBAL: m, LATENT: m}
    if WINDOW in layer_kinds(cfg):
        masks[WINDOW] = m & (k_pos[None, :]
                             > q_pos[:, None] - cfg.sliding_window)

    def layer(x, lp, ref, *acc):        # ak/av: (L, kvh, hd) the layer's
        ak, av = acc if len(acc) == 2 else (None, None)

        def attend(q, k, v, wo):
            if ref.kind != LATENT:
                k, v = _as_cached(k, cfg), _as_cached(v, cfg)
            nk, nv = _into_acc(ak, k, offset), _into_acc(av, v, offset)
            if ref.kind == LATENT:          # every row expanded, then masked
                (nk,), (nv,) = latent_expand(q, nk[None], nv[None], lp, cfg)
            else:
                nk, nv = _heads_apart(nk, cfg), _heads_apart(nv, cfg)
            qg = q[0].reshape(s, kvh, g, -1).astype(jnp.float32)
            kf = nk.astype(jnp.float32)                     # (L, kvh, hd)
            scores = jnp.einsum("skgd,lkd->kgsl", qg, kf)
            scores = scores * softmax_scale(cfg, LATENT) \
                if ref.kind == LATENT else scores / jnp.sqrt(hd)
            scores = jnp.where(masks[ref.kind][None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("kgsl,lkd->skgd", probs,
                           nv.astype(jnp.float32))
            return o.reshape(1, s, h * hd).astype(x.dtype) @ wo
        return _chunk_layer(x, lp, cfg, ref, rope, attend, length, acc,
                            offset, routed)

    return _chunk_layers(params, cfg, x, layer, acc, length, routed)


def _chunk_layer(x, lp, cfg, ref, rope, attend, length, acc, offset,
                 routed=False):
    """One layer of a ``prefill_chunk`` forward: ``acc`` is the layer's part
    of the accumulator (its K and V rows; a state layer's state and conv
    tail, which the chunk starts from and hands on; nothing for a layer
    that caches nothing). Returns (x, the layer's part after the chunk;
    with ``routed``, (that, the experts the chunk's rows chose))."""
    def into(k, v):
        return _into_acc(acc[0], k, offset), _into_acc(acc[1], v, offset)
    if single_mixer(cfg):
        return _mixer_prefill_layer(x, lp, cfg, ref, rope, attend, length,
                                    acc, into)
    picked = [] if routed else None
    x, k, v, _ = _layer(
        x, lp, cfg, ref, rope, attend,
        state=_tail_prefill(cfg, acc, length) if ref.kind == STATE else None,
        routed=picked)
    y = k if ref.kind == STATE else into(k, v)
    return x, ((y, picked[0]) if routed else y)


def _in_layer_order(cfg, by_kind: dict):
    """{kind: arrays with that kind's layers on their first axis} -> one
    array with the model's layers on it, in their order."""
    import numpy as np
    order = [i for kind in by_kind for i in kind_layers(cfg)[kind]]
    return jnp.concatenate(list(by_kind.values()))[np.argsort(order)]


def _chunk_layers(params, cfg, x, layer, acc, length, routed=False):
    """A ``prefill_chunk`` forward's layers over the accumulator -> (the
    chunk's last valid token's logits, the accumulator after it; with
    ``routed`` also the experts every row chose in each layer)."""
    if routed and not operator_stacks(cfg):
        raise NotImplementedError(
            "prefill_chunk_routed runs a model whose operators lie in "
            "stacks of their own (operator_stacks) alone")
    if single_mixer(cfg):
        from ray_tpu.llm.kvcache import POOL_KEYS
        sk, tk = POOL_KEYS[STATE]
        x, ys = _run_layers(params, cfg, x, layer, per_layer={
            GLOBAL: (acc["k"], acc["v"]), STATE: (acc[sk], acc[tk])})
        (nk, nv), (st, tl) = ys[GLOBAL], ys[STATE]
        return _head(x, params, cfg, length), {"k": nk, "v": nv, sk: st,
                                               tk: tl}
    if operator_stacks(cfg):
        from ray_tpu.llm.kvcache import POOL_KEYS
        tk = POOL_KEYS[STATE][1]
        x, ys = _run_layers(params, cfg, x, layer, per_layer={
            GLOBAL: (acc["k"], acc["v"]), STATE: (acc[tk],)})
        chosen = ()
        if routed:
            chosen = (_in_layer_order(
                cfg, {kind: y[1] for kind, y in ys.items()}),)
            ys = {kind: y[0] for kind, y in ys.items()}
        nk, nv = ys[GLOBAL]
        return (_head(x, params, cfg, length),
                {"k": nk, "v": nv, tk: ys[STATE]}, *chosen)
    x, (nk, nv) = _run_layers(params, cfg, x, layer,
                              per_layer=(acc["k"], acc["v"]))
    return _head(x, params, cfg, length), {"k": nk, "v": nv}


def sample(logits: jax.Array, temps: jax.Array, key: jax.Array,
           top_ps: Optional[jax.Array] = None,
           top_ks: Optional[jax.Array] = None) -> jax.Array:
    """Per-slot sampling ON DEVICE: greedy where temp<=0, else
    temperature -> top-k -> top-p -> categorical (the standard filter
    order; reference capability = vLLM's SamplingParams temperature/
    top_p/top_k). Keeping sampling inside the jitted step means each
    decode ships 4 bytes per slot to the host instead of the full vocab
    logits — the device->host link must never carry O(vocab) per
    token.

    top_ks: (slots,) int32, 0 disables; top_ps: (slots,) f32 in (0,1],
    1.0 disables. Both filters run as sorts + masks over the vocab —
    O(V log V) on the VPU, negligible next to the decode matmuls."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    masked = filter_logits(scaled, top_ks, top_ps)
    keys = jax.random.split(key, b)
    drawn = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temps <= 0, greedy, drawn)


def filter_logits(scaled, top_ks=None, top_ps=None):
    """The top-k -> top-p logits mask, shared by the on-device sampler
    (`sample`, above) and the HOST-side rejection-sampling acceptance
    in speculative decoding (llm/spec.py). The host sampler is what
    the device sampler is parity-tested against, and the speculative
    accept must judge draft tokens under exactly the distribution the
    device would sample from — so there is ONE implementation of the
    filter order, generic over jnp (traced inside jit) and plain
    numpy (host float arrays). `scaled` is logits already divided by
    temperature, shape (slots, vocab); top_ks (slots,) int32 with 0
    disabling; top_ps (slots,) f32 in (0, 1] with 1.0 disabling.
    Returns masked logits with filtered entries at -inf."""
    import numpy as np
    onp = isinstance(scaled, np.ndarray)
    xp = np if onp else jnp
    v = scaled.shape[-1]
    masked = scaled
    if top_ks is not None:
        desc = xp.sort(scaled, axis=-1)[:, ::-1]
        kth = xp.take_along_axis(
            desc, xp.clip(top_ks - 1, 0, v - 1)[:, None], axis=1)
        masked = xp.where((top_ks[:, None] > 0) & (scaled < kth),
                          -xp.inf, masked)
    if top_ps is not None:
        if onp:
            e = np.exp(masked - np.max(masked, axis=-1, keepdims=True))
            probs = e / np.sum(e, axis=-1, keepdims=True)
        else:
            probs = jax.nn.softmax(masked, axis=-1)
        sp = xp.sort(probs, axis=-1)[:, ::-1]
        cum = xp.cumsum(sp, axis=-1)
        # nucleus rule: keep the smallest prefix of the sorted probs
        # whose mass reaches p — i.e. tokens whose EXCLUSIVE cumulative
        # mass is still < p (the top token always survives)
        keep = (cum - sp) < top_ps[:, None]
        thresh = xp.min(xp.where(keep, sp, xp.inf), axis=-1)
        enabled = (top_ps < 1.0)[:, None]
        masked = xp.where(enabled & (probs < thresh[:, None]),
                          -xp.inf, masked)
    return masked


def _add_counts(total, stats):
    """The expert layers' counts of one step, summed over its layers."""
    if stats is None:
        return total
    return stats if total is None else jax.tree.map(jnp.add, total, stats)


def decode_logits_core(params: dict, pool: dict, tokens: jax.Array,
                       positions: jax.Array, cfg: LlamaConfig, attend,
                       live: jax.Array, chosen: bool = False,
                       state_step=None):
    """THE decode-step transformer: one token for every slot against
    the KV pool (llm/kvcache.py init_pool: k/v (layers of a kind,
    blocks, kvh, block_size, hd) a layer kind). The pool is the layer
    scans' CARRY, never sliced by layer and never stacked back: a
    decode program that donates (or itself carries) it updates it in
    place. Per layer, ``attend(ref, q, k, v, pool) -> ((slots, h*hd)
    f32, pool)`` (ref: the LayerRef, its indices traced in a scan; q:
    (slots, 1, h, hd); k, v: (slots, kvh, hd), the new token's rows)
    puts the rows into the layer's place in its kind's pool and attends
    over the slot's table of that kind, the one thing callers differ
    in: the aliased writer and the kernel that walks the table itself
    (ops/pallas/paged_attention.py), or their reference, a scatter +
    table_view + _gqa_attend_cached. A LATENT layer hands ``attend`` the
    absorbed query (slots, 1, h, kv_lora_rank + rope) and the new rows c
    and kr, and takes what comes back, (slots, h * kv_lora_rank), through
    Wv_b (latent_absorb / latent_unabsorb: the same numbers as keys and
    values expanded from the rows, with none expanded). Returns ((slots, vocab) f32
    logits, pool, the expert layers' counts summed over the layers or
    None: models/moe.py serve_block over the rows of ``live`` (slots,)
    bool, the slots that hold a request. The caller reads it off the
    tables: a position says nothing, an idle slot's is the step's index
    in its block). A STATE layer's state and conv tail are in the pool too,
    a slot's at the slot's index: a live slot's move on one token in the
    carry, another's stay (``_state_step``), by ``state_step(ref, x, dt, A,
    B, C, D, pool) -> (y, pool)``, the one-token rule against the layer's
    states, handed in as ``attend`` is (a model without state layers never
    calls it and may leave it out). ``chosen`` (a model whose
    layers are each one mixer): a fourth value, the experts every slot chose
    in each expert layer, (expert layers, slots, k) int32; of a model whose
    operators lie in stacks of their own (``operator_stacks``) in EVERY
    layer, (layers, slots, k), -1 in a layer without a router. A short
    convolution's tail is the pool's too, a slot's at the slot's index,
    moved on by a row for the slots of ``live`` (``_tail_step``)."""
    x = _embed(params, tokens[:, None], cfg)                # (b, 1, emb)
    rope = rope_tables(cfg, positions[:, None])
    active = live if has_experts(cfg) else None

    def layer(carry, lp, ref):
        x, pool, counts = carry

        def attend_(q, k, v, wo):
            nonlocal pool
            o, pool = _attend_pool(attend, ref, lp, cfg, q, k[:, 0],
                                   v[:, 0], pool)
            return (o.astype(x.dtype) @ wo)[:, None]

        def state(y, lp):
            nonlocal pool
            out, pool = _state_step(y[:, 0], lp, cfg, ref, pool, live,
                                    state_step)
            return out[:, None], None
        def tail(y, lp):
            nonlocal pool
            out, pool = _tail_step(y[:, 0], lp, cfg, ref, pool, live)
            return out[:, None], None
        kept = None
        if single_mixer(cfg):
            x, kept, stats = _mixer_layer(x, lp, cfg, ref, rope, attend_,
                                          state, active)
            kept = kept if ref.kind == EXPERTS else None
        else:
            picked = [] if chosen else None
            x, _, _, stats = _layer(x, lp, cfg, ref, rope, attend_, active,
                                    tail, picked)
            kept = picked[0] if chosen else None
        return (x, pool, _add_counts(counts, stats)), kept

    counts = None
    if has_experts(cfg):
        counts = {k: jnp.int32(0)
                  for k in ("routed", "local", "experts_hit")}
    (x, pool, counts), ys = _run_layers(params, cfg, (x, pool, counts),
                                        layer)
    x = _final_norm(x, params, cfg)
    logits = _logits(x[:, 0], params)
    if chosen:
        return logits, pool, counts, ys[EXPERTS] if single_mixer(cfg) \
            else _in_layer_order(cfg, ys)
    return logits, pool, counts


def _gqa_attend_multi(q, cache_k, cache_v, lengths, cfg: LlamaConfig):
    """Multi-query twin of _gqa_attend_cached for the speculative
    verify forward: w in-flight queries per slot attend the same cache
    view under a PER-QUERY causal mask (query j sees keys < its own
    position + 1 — cached history plus the draft tokens written ahead
    of it this round). q: (b, w, h*hd); cache_k/v: (b, L, kvh, hd);
    lengths: (b, w) valid entries per query (incl. that query's own
    token). Exact-zero masking (-1e30 then softmax) keeps cache bytes
    beyond each mask bitwise-irrelevant, and the per-row reduction
    order matches the single-query path — verify row j reproduces what
    sequential decode would compute at that position."""
    b, w = q.shape[:2]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    qg = q.reshape(b, w, kvh, g, hd).astype(jnp.float32)
    kf = cache_k.astype(jnp.float32)
    scores = jnp.einsum("bwkgd,blkd->bwkgl", qg, kf) / jnp.sqrt(hd)
    mask = (jnp.arange(cache_k.shape[1])[None, None]
            < lengths[:, :, None])                      # (b, w, L)
    scores = jnp.where(mask[:, :, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bwkgl,blkd->bwkgd", probs,
                     cache_v.astype(jnp.float32))
    return out.reshape(b, w, h * hd)


def verify_tokens_core(params: dict, pool: dict, tokens: jax.Array,
                       positions: jax.Array, cfg: LlamaConfig, attend):
    """The speculative-verify transformer: decode_logits_core widened
    from one token per slot to w — same layer scan with the pool as
    its carry, same pool write, so the verify forward can never drift
    from sequential decode.
    tokens: (b, w) int32 where column 0 is the last emitted token and
    columns 1..w-1 the draft; positions: (b,) cache position of column
    0 (= tokens_so_far - 1). All w KVs are written (position p+j for
    column j); the returned logits (b, w, vocab) f32 row j is the
    model's distribution for position p+j+1 — the verdict on draft
    token j+1. No device sampling: acceptance is a host decision
    (llm/spec.py) so rejection sampling can inspect the full
    distribution.
    ``attend(ref, q, k, v, pool) -> ((b, w, h*hd) f32, pool)`` takes q
    (b, w, h, hd) and the new rows k, v (b, w, kvh, hd)."""
    if single_mixer(cfg) or STATE in layer_kinds(cfg):
        raise NotImplementedError(
            "the verify forward does not run state layers (a state-space "
            "mixer, a gated short convolution; or any layer "
            "that is one mixer alone): a rejected draft would have to roll "
            "a recurrent state or a conv tail back, and no snapshot of it "
            "is kept")
    b, w = tokens.shape
    x = _embed(params, tokens, cfg)                         # (b, w, emb)
    pos = positions[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
    rope = rope_tables(cfg, pos)

    def layer(carry, lp, ref):
        x, pool = carry

        def attend_(q, k, v, wo):
            nonlocal pool
            o, pool = _attend_pool(attend, ref, lp, cfg, q, k, v, pool)
            return o.astype(x.dtype) @ wo
        x, _, _, _ = _layer(x, lp, cfg, ref, rope, attend_)
        return (x, pool), None

    (x, pool), _ = _run_layers(params, cfg, (x, pool), layer)
    x = _final_norm(x, params, cfg)
    logits = _logits(x, params)                             # (b, w, V)
    return logits, pool
