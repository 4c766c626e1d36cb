"""Cache-aware llama forwards for inference: prefill, decode, verify.

The model side of the LLM serving stack (reference:
python/ray/llm/_internal/serve/... wraps vLLM; here the engine is native:
the training model in models/llama.py is reused — same params, same
config — with inference-shaped forwards that XLA compiles once per
shape bucket; TPU rule: no dynamic shapes):

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

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import (LlamaConfig, _rmsnorm, _rope,
                                  _rope_tables)


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


def _qkv(y, lp, cfg: LlamaConfig):
    b, s = y.shape[:2]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (y @ lp["wq"]).reshape(b, s, h, hd)
    k = (y @ lp["wk"]).reshape(b, s, kvh, hd)
    v = (y @ lp["wv"]).reshape(b, s, kvh, hd)
    return q, k, v


def _gqa_attend_cached(q, cache_k, cache_v, lengths, cfg: LlamaConfig):
    """q: (b, h, hd) current-token queries; cache_k/v: (b, L, kvh, hd);
    lengths: (b,) valid cache entries per slot (incl. current token)."""
    b = q.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd).astype(jnp.float32)
    kf = cache_k.astype(jnp.float32)
    scores = jnp.einsum("bkgd,blkd->bkgl", qg, kf) / jnp.sqrt(hd)
    mask = jnp.arange(cache_k.shape[1])[None] < lengths[:, None]  # (b, L)
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


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill(params: dict, tokens: jax.Array, length: jax.Array,
            cfg: LlamaConfig, max_len: int) -> Tuple[jax.Array, dict]:
    """One padded prompt. tokens: (s,) int32 (padded to a bucket);
    length: () actual prompt length. Returns (last-token logits (vocab,),
    per-layer kv padded to max_len: k/v (layers, max_len, kvh, hd)).

    Attention dispatches through ops.attention (cfg.attn_impl): the
    pallas flash kernel tiles long prompts on TPU instead of
    materializing the O(s^2) score tensor. Causal alone is exact here:
    pad keys sit at positions >= length, and every USED query row is
    < length, so causality already excludes them (pad rows' outputs are
    garbage but only row length-1 is read)."""
    from ray_tpu.ops.attention import attention as _attention
    s = tokens.shape[0]
    x = jnp.take(params["embed"], tokens[None], axis=0)  # (1, s, emb)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    rc, rs = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    def layer(x, lp):
        y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(y, lp, cfg)
        q, k = _rope(q, rc, rs), _rope(k, rc, rs)
        h, hd = cfg.n_heads, cfg.head_dim
        o = _attention(q, k, v, causal=True, sm_scale=hd ** -0.5,
                       impl=_serve_attn_impl(cfg))
        o = o.reshape(1, s, h * hd).astype(x.dtype)
        x = x + o @ lp["wo"]
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                 @ lp["w_down"])
        return x, (k[0], v[0])

    x, (ks, vs) = _scan_layers(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take(x[0], length - 1, axis=0)
    logits = (last @ params["lm_head"]).astype(jnp.float32)
    # pad kv (layers, s, kvh, hd) -> (layers, max_len, kvh, hd)
    pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]
    return logits, {"k": jnp.pad(ks, pad), "v": jnp.pad(vs, pad)}


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
    length, so it is never attended to.

    Dispatch: flash-capable impls route to the pallas kernel with the
    chunk's absolute offset placing the causal diagonal (one compile
    per distinct offset — offsets are chunk-size multiples, so at most
    ceil(max_len / chunk) variants); otherwise the dynamic-offset XLA
    path below compiles once."""
    from ray_tpu.ops.attention import _on_tpu
    impl = _serve_attn_impl(cfg)
    if impl == "flash" or impl == "flash_interpret" or (
            impl == "auto" and _on_tpu() and tokens.shape[0] >= 128):
        if impl == "auto":
            impl = "flash"
        return _prefill_chunk_flash(params, tokens, length, int(offset),
                                    acc, cfg, impl)
    return _prefill_chunk_dyn(params, tokens, length,
                              jnp.asarray(offset, jnp.int32), acc, cfg)


@partial(jax.jit, static_argnames=("cfg", "offset", "impl"),
         donate_argnums=(4,))
def _prefill_chunk_flash(params: dict, tokens: jax.Array,
                         length: jax.Array, offset: int, acc: dict,
                         cfg: LlamaConfig, impl: str):
    """Flash chunked prefill: the kernel's q_offset places the causal
    diagonal at the chunk's absolute position, so no O(s x L) mask or
    score tensor is materialized. Causal alone is exact for every USED
    query row (see prefill)."""
    from ray_tpu.ops.attention import attention as _attention
    s = tokens.shape[0]
    h, hd = cfg.n_heads, cfg.head_dim
    x = jnp.take(params["embed"], tokens[None], axis=0)     # (1, s, emb)
    positions = (offset + jnp.arange(s, dtype=jnp.int32))[None]
    rc, rs = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    def layer(carry, xs):
        x = carry
        lp, ak, av = xs     # ak/av: (L, kvh, hd) this layer's acc
        y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(y, lp, cfg)
        q, k = _rope(q, rc, rs), _rope(k, rc, rs)
        ak = lax.dynamic_update_slice(
            ak, k[0].astype(ak.dtype),
            (jnp.int32(offset), jnp.int32(0), jnp.int32(0)))
        av = lax.dynamic_update_slice(
            av, v[0].astype(av.dtype),
            (jnp.int32(offset), jnp.int32(0), jnp.int32(0)))
        o = _attention(q, ak[None].astype(q.dtype),
                       av[None].astype(q.dtype), causal=True,
                       sm_scale=hd ** -0.5, impl=impl, q_offset=offset)
        o = o.reshape(1, s, h * hd).astype(x.dtype)
        x = x + o @ lp["wo"]
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                 @ lp["w_down"])
        return x, (ak, av)

    x, (nk, nv) = _scan_layers(
        layer, x, (params["layers"], acc["k"], acc["v"]))
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take(x[0], length - 1, axis=0)
    logits = (last @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": nk, "v": nv}


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def _prefill_chunk_dyn(params: dict, tokens: jax.Array,
                       length: jax.Array, offset: jax.Array, acc: dict,
                       cfg: LlamaConfig) -> Tuple[jax.Array, dict]:
    """Dynamic-offset XLA path (single compile; O(s x L) scores)."""
    s = tokens.shape[0]
    L = acc["k"].shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kvh
    x = jnp.take(params["embed"], tokens[None], axis=0)     # (1, s, emb)
    positions = (offset + jnp.arange(s, dtype=jnp.int32))[None]
    rc, rs = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    q_pos = positions[0]                                    # (s,)
    k_pos = jnp.arange(L, dtype=jnp.int32)                  # (L,)
    # causal over ABSOLUTE positions (covers both earlier chunks and
    # intra-chunk order), limited to valid keys
    m = (k_pos[None, :] <= q_pos[:, None]) & \
        (k_pos[None, :] < offset + length)

    def layer(carry, xs):
        x = carry
        lp, ak, av = xs     # ak/av: (L, kvh, hd) this layer's acc
        y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(y, lp, cfg)
        q, k = _rope(q, rc, rs), _rope(k, rc, rs)
        ak = lax.dynamic_update_slice(
            ak, k[0].astype(ak.dtype), (offset, jnp.int32(0), jnp.int32(0)))
        av = lax.dynamic_update_slice(
            av, v[0].astype(av.dtype), (offset, jnp.int32(0), jnp.int32(0)))
        qg = q[0].reshape(s, kvh, g, hd).astype(jnp.float32)
        kf = ak.astype(jnp.float32)                         # (L, kvh, hd)
        scores = jnp.einsum("skgd,lkd->kgsl", qg, kf) / jnp.sqrt(hd)
        scores = jnp.where(m[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("kgsl,lkd->skgd", probs,
                       av.astype(jnp.float32))
        o = o.reshape(1, s, h * hd).astype(x.dtype)
        x = x + o @ lp["wo"]
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                 @ lp["w_down"])
        return x, (ak, av)

    x, (nk, nv) = _scan_layers(
        layer, x, (params["layers"], acc["k"], acc["v"]))
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take(x[0], length - 1, axis=0)
    logits = (last @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": nk, "v": nv}


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


def decode_logits_core(params: dict, kpool: jax.Array,
                       vpool: jax.Array, tokens: jax.Array,
                       positions: jax.Array, cfg: LlamaConfig, attend):
    """THE decode-step transformer: one token for every slot against
    the KV pool, k/v (layers, blocks, kvh, block_size, hd). The pools
    are the layer scan's CARRY, never sliced by layer and never
    stacked back: a decode program that donates (or itself carries)
    them updates them in place. Per layer, ``attend(l, q, k, v, kpool,
    vpool) -> ((slots, h*hd) f32, kpool, vpool)`` (l: the layer's
    index, traced; q: (slots, 1, h, hd); k, v: (slots, kvh, hd), the
    new token's rows) puts the rows into layer l of the pools and
    attends over the slot's table, the one thing callers differ in:
    the aliased writer and the kernel that walks the table itself
    (ops/pallas/paged_attention.py), or their reference, a scatter +
    table_view + _gqa_attend_cached. Returns ((slots, vocab) f32
    logits, kpool, vpool)."""
    x = jnp.take(params["embed"], tokens[:, None], axis=0)  # (b, 1, emb)
    rc, rs = _rope_tables(positions[:, None], cfg.head_dim,
                          cfg.rope_theta)

    def layer(carry, xs):
        x, ck, cv = carry
        lp, l = xs
        y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(y, lp, cfg)  # (b, 1, ...)
        q, k = _rope(q, rc, rs), _rope(k, rc, rs)
        o, ck, cv = attend(l, q, k[:, 0], v[:, 0], ck, cv)
        x = x + (o.astype(x.dtype) @ lp["wo"])[:, None]
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                 @ lp["w_down"])
        return (x, ck, cv), None

    index = jnp.arange(kpool.shape[0], dtype=jnp.int32)
    (x, kpool, vpool), _ = _scan_layers(
        layer, (x, kpool, vpool), (params["layers"], index))
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ params["lm_head"]).astype(jnp.float32)
    return logits, kpool, vpool


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


def verify_tokens_core(params: dict, kpool: jax.Array,
                       vpool: jax.Array, tokens: jax.Array,
                       positions: jax.Array, cfg: LlamaConfig, attend):
    """The speculative-verify transformer: decode_logits_core widened
    from one token per slot to w — same layer scan with the pools as
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
    ``attend(l, q, k, v, kpool, vpool) -> ((b, w, h*hd) f32, kpool,
    vpool)`` takes q (b, w, h, hd) and the new rows k, v
    (b, w, kvh, hd)."""
    b, w = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)           # (b, w, emb)
    pos = positions[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
    rc, rs = _rope_tables(pos, cfg.head_dim, cfg.rope_theta)

    def layer(carry, xs):
        x, ck, cv = carry
        lp, l = xs
        y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(y, lp, cfg)                          # (b, w, ...)
        q, k = _rope(q, rc, rs), _rope(k, rc, rs)
        o, ck, cv = attend(l, q, k, v, ck, cv)
        x = x + o.astype(x.dtype) @ lp["wo"]
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + ((jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"]))
                 @ lp["w_down"])
        return (x, ck, cv), None

    index = jnp.arange(kpool.shape[0], dtype=jnp.int32)
    (x, kpool, vpool), _ = _scan_layers(
        layer, (x, kpool, vpool), (params["layers"], index))
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)    # (b, w, V)
    return logits, kpool, vpool
