"""Llama-family decoder, TPU-first.

Pure-functional JAX: params are a pytree, layers are stacked on a leading
axis and driven by ``lax.scan`` (compile time independent of depth), each
layer rematerialized with ``jax.checkpoint``. Attention dispatches between
the Pallas flash kernel (single-shard seq), ring attention (context-parallel
mesh axis), and the XLA reference (CPU tests).

This is the framework's flagship model family — the analog of what reference
users run through TorchTrainer/vLLM (the reference ships no model code of its
own for this; see SURVEY.md section 3.4 for the JaxTrainer north-star path).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import attention as _attention_op, _on_tpu
from ray_tpu.ops.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    data: str = "data"
    fsdp: str = "fsdp"
    tensor: str = "tensor"
    context: str = "context"
    expert: str = "expert"   # used by the MoE family (models/moe.py)

    @property
    def batch(self):
        return (self.data, self.fsdp)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True
    # What the per-layer jax.checkpoint saves for the backward pass:
    #   "full"  — save nothing, recompute the whole layer (min memory,
    #             ~33% extra FLOPs: fwd runs twice);
    #   "dots"  — save weight-matmul outputs (checkpoint_dots_with_no_batch_dims):
    #             backward recomputes only cheap elementwise/norm ops;
    #   "attn"  — save just the attention output (skips re-running the flash
    #             kernel; weight matmuls are recomputed);
    #   "none"  — no remat (same as remat=False).
    remat_policy: str = "full"
    # Dtype of the logits / cross-entropy path. float32 is the numerically
    # conservative default; bfloat16 halves the (b, s, vocab) HBM traffic and
    # runs the exp/logsumexp passes at the faster bf16 VPU rate (loss error
    # ~1e-2 absolute — fine for throughput-oriented runs).
    logits_dtype: str = "float32"
    # Fused cross-entropy: tokens per sequence chunk. 0 = classic path
    # (materialize the full (b, s, vocab) logits). >0 = the loss scans
    # seq chunks, computing each chunk's (b, ce_chunk, vocab) logits,
    # reducing to scalars, and REMATing the chunk on backward — the
    # full logits tensor never exists in HBM (at 7B shapes b4 s4096
    # v32000 that's ~1 GiB bf16 + softmax temporaries, the largest
    # single activation in the step). Costs one extra lm_head matmul
    # per chunk on backward.
    ce_chunk: int = 0
    attn_impl: str = "auto"        # auto | reference | flash | flash_interpret | ring
    attn_block_q: int = 128        # flash kernel tile sizes (MXU-multiple)
    attn_block_k: int = 128

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        h, kvh, hd = self.n_heads, self.n_kv_heads, self.head_dim
        per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d \
            + 3 * d * f + 2 * d
        return v * d + self.n_layers * per_layer + d + d * v

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token (fwd+bwd ~ 6*N plus attention term)."""
        n_matmul = self.num_params() - self.vocab_size * self.dim  # embed is a gather
        attn = 12 * self.n_layers * self.dim * seq_len  # 2*2*3? qk + pv fwd+bwd
        return 6.0 * n_matmul + attn


def llama2_7b(**kw) -> LlamaConfig:
    """Llama-2-7B dims, set EXPLICITLY (they coincide with
    LlamaConfig's defaults, but "7b" in code must mean 7B even if the
    defaults drift)."""
    defaults = dict(vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
                    n_kv_heads=32, ffn_dim=11008, max_seq_len=4096)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def llama2_13b(**kw) -> LlamaConfig:
    defaults = dict(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                    ffn_dim=13824)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def llama3_8b(**kw) -> LlamaConfig:
    defaults = dict(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, ffn_dim=14336, rope_theta=500000.0,
                    max_seq_len=8192)
    defaults.update(kw)
    return LlamaConfig(**defaults)


def tiny(**kw) -> LlamaConfig:
    defaults = dict(vocab_size=512, dim=128, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=256, max_seq_len=256)
    defaults.update(kw)
    return LlamaConfig(**defaults)


# --- params ----------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LlamaConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, f = cfg.dim, cfg.ffn_dim
    h, kvh, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    ks = jax.random.split(rng, 9)

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    return {
        "embed": norm_init(ks[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": norm_init(ks[1], (L, d, h * hd), d),
            "wk": norm_init(ks[2], (L, d, kvh * hd), d),
            "wv": norm_init(ks[3], (L, d, kvh * hd), d),
            "wo": norm_init(ks[4], (L, h * hd, d), h * hd),
            "mlp_norm": jnp.ones((L, d), dtype),
            "w_gate": norm_init(ks[5], (L, d, f), d),
            "w_up": norm_init(ks[6], (L, d, f), d),
            "w_down": norm_init(ks[7], (L, f, d), f),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": norm_init(ks[8], (d, cfg.vocab_size), d),
    }


def param_shardings(cfg: LlamaConfig, axes: MeshAxes = MeshAxes()) -> dict:
    """PartitionSpec pytree matching init_params. Megatron-style tensor
    sharding + FSDP on the complementary dim: wq / wk / wv and w_gate /
    w_up are column-parallel over ``tensor`` (their products need no
    sum forward and one, of the norm's cotangent, backward), wo and
    w_down row-parallel (one sum forward, none backward); the norms'
    outputs and the residual stream are replicated over ``tensor``.
    Who makes those sums: ``_layer_products``."""
    t, fs = axes.tensor, axes.fsdp
    return {
        "embed": P(t, fs),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, fs, t),
            "wk": P(None, fs, t),
            "wv": P(None, fs, t),
            "wo": P(None, t, fs),
            "mlp_norm": P(None, None),
            "w_gate": P(None, fs, t),
            "w_up": P(None, fs, t),
            "w_down": P(None, t, fs),
        },
        "final_norm": P(None),
        "lm_head": P(fs, t),
    }


# --- forward ---------------------------------------------------------------

def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope_tables(positions, head_dim, theta):
    """cos/sin tables (b, s, half) f32, computed ONCE per forward — the
    sin/cos transcendentals are hoisted out of the per-layer code (they cost
    a full VPU pass per layer otherwise)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # (b, s, half)
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x, cos, sin):
    """x: (b, s, h, d); cos/sin: (b, s, d//2) precomputed tables."""
    half = x.shape[-1] // 2
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies of ``dim`` rotary dimensions, (dim // 2,)
    float32 numpy (static: they depend on the configuration alone). A
    frequency that turns more than ``beta_fast`` times over the
    ``original`` positions keeps theta_i; one that turns fewer than
    ``beta_slow`` times becomes theta_i / factor (position interpolation);
    between the two correction dimensions the blend is linear."""
    import math

    import numpy as np
    half = dim // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (freqs / factor * ramp + freqs * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction 0.1 * mscale * ln(factor) + 1."""
    import math
    return 1.0 if factor <= 1 or not mscale \
        else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables_freqs(positions, inv_freq, factor: float = 1.0):
    """cos/sin tables (b, s, half) f32 of given inverse frequencies,
    carrying the attention ``factor`` (1 leaves them as they are)."""
    angles = positions[:, :, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if factor == 1.0 else (cos * factor, sin * factor)


def _rope_pairs(x, cos, sin):
    """RoPE over INTERLEAVED pairs (2i, 2i + 1) of the last axis. x:
    (b, s, d) or (b, s, h, d); cos/sin: (b, s, d // 2)."""
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    c, s = cos.astype(x.dtype), sin.astype(x.dtype)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, cfg: LlamaConfig, mesh: Optional[Mesh],
            axes: MeshAxes):
    impl = cfg.attn_impl
    blocks = dict(block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)

    def named(out):
        # Flash paths name their own residuals (attn_out/attn_lse inside the
        # custom_vjp fwd rule); the XLA paths get a single named output so
        # the "attn" remat policy can save it.
        return _checkpoint_name(out, "attn_res")

    if mesh is None:
        if impl in ("auto", "ring"):
            impl = "flash" if _on_tpu() and q.shape[1] >= 128 \
                else "reference"
        out = _attention_op(q, k, v, causal=True, impl=impl, **blocks)
        return out if impl.startswith("flash") else named(out)

    cp = mesh.shape.get(axes.context, 1)
    bspec = P(axes.batch, axes.context, axes.tensor, None)

    if impl == "ring" or (impl == "auto" and cp > 1):
        def f(q, k, v):
            return ring_attention(q, k, v, axis_name=axes.context)
        return named(jax.shard_map(f, mesh=mesh,
                                   in_specs=(bspec, bspec, bspec),
                                   out_specs=bspec)(q, k, v))

    if cp > 1:
        # Explicit non-ring impl on a context-sharded mesh: run with global
        # semantics (GSPMD gathers the sequence axis). Only the XLA reference
        # path supports this — the Pallas kernel can't be auto-partitioned.
        if impl != "reference":
            raise ValueError(
                f"attn_impl={impl!r} cannot run under a context-parallel "
                f"mesh (context axis size {cp}); use 'ring' or 'auto'")
        return named(_attention_op(q, k, v, causal=True, impl=impl))

    if impl == "auto":
        impl = "flash" if _on_tpu() and q.shape[1] >= 128 \
            else "reference"

    def f(q, k, v):
        return _attention_op(q, k, v, causal=True, impl=impl, **blocks)
    # check_vma=False: pallas_call outputs carry no vma under shard_map.
    out = jax.shard_map(f, mesh=mesh, in_specs=(bspec, bspec, bspec),
                        out_specs=bspec, check_vma=False)(q, k, v)
    return out if impl.startswith("flash") else named(out)


def _remat(layer, cfg: LlamaConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return layer
    cp = jax.checkpoint_policies
    if cfg.remat_policy == "full":
        return jax.checkpoint(layer)
    if cfg.remat_policy == "dots":
        policy = cp.save_from_both_policies(
            cp.checkpoint_dots_with_no_batch_dims,
            cp.save_only_these_names("attn_out", "attn_lse", "row_sum"))
    elif cfg.remat_policy == "attn":
        # Saves the flash kernel outputs (o + lse residuals) so backward
        # never re-runs the attention forward; "attn_res" covers the
        # non-flash attention paths (reference/ring).
        policy = cp.save_only_these_names("attn_out", "attn_lse", "attn_res")
    else:
        raise ValueError(f"unknown remat_policy: {cfg.remat_policy!r}")
    return jax.checkpoint(layer, policy=policy)


# --- a layer's sums over `tensor` ------------------------------------------

def _tp_chunks(rows: int) -> int:
    """How many chunks of the sequence a `tensor` pair exchanges a sum
    in, for ``rows`` positions a chip: four where four divides them,
    else two, else 0 (GSPMD's all-reduce). On four v5e chips at 4,096
    rows of width 7,168 a step took 1,425.1 ms at 2 chunks, 1,420.3 at
    4 and 1,444.0 at 8 (whose ``wo`` product, 0.5 ms, no longer covers
    a chunk's flight), all under the all-reduce's 1,546.8: no size was
    found at which the blocking sum wins, so none is kept for it
    (PERF.md, PR 55)."""
    return next((n for n in (4, 2) if rows % n == 0), 0)


def _tensor_pair_products(mesh: Mesh, axis: str, n: int, rows: P):
    """Megatron's column- and row-parallel products over a mesh
    ``axis`` of TWO chips, ``(col, row)``, each with the one sum over
    ``axis`` it owes made as ``n`` exchanges, a chunk of the sequence
    at a time (``rows``: how an activation lies over the mesh's other
    axes):

    - ``row(a, w)``: ``a @ w`` with ``a``'s last and ``w``'s first
      dimension sharded. Forward: chunk i's partial product, then
      ``part + the other chip's part`` (Megatron's ``g``); backward
      plain, no sum.
    - ``col(y, *ws)``: ``y @ w`` for each ``w``, its last dimension
      sharded. Forward plain; backward: chunk i's partial ``dy`` over
      all of ``ws``, then the same sum (Megatron's ``f``).

    A sum is ONE ``ppermute`` of the chunk (for a pair it moves the
    bytes a ring all-reduce moves, and ``a + b`` is ``b + a``: both
    chips hold the all-reduce's bits), which the TPU compiler starts
    after chunk i's product and waits for after chunk i + 1's, where it
    runs an activation's ``all-reduce`` blocking. Only the product next
    to the sum is chunked: the weights' gradients stay one product
    each (a chunk's partial gradient is as large as the whole).

    The exchange runs in a ``shard_map`` over ``axis`` alone (every
    other mesh axis stays GSPMD's, so fsdp's gathers are the plain
    text's) that is never transposed: each product is a
    ``jax.custom_vjp`` around it. JAX's transpose of a ``shard_map``
    could not be used: with ``check_vma`` it has no type for "equal on
    both chips by an exchange", without it sums a replicated
    cotangent once more with a blocking ``psum``."""
    rowwise, colwise = P(axis, None), P(None, axis)
    wide = P(None, None, axis)      # an activation's last dimension sharded
    rows = jax.sharding.NamedSharding(mesh, rows)

    def keep(x):
        # a chunk lies over the other axes as the whole does: left to
        # itself GSPMD reshards chunks of 512 rows or fewer with a
        # blocking all-to-all (not those of 1,024 or more)
        return lax.with_sharding_constraint(x, rows)

    def exchanged(products, acts, in_specs):
        """``concat_i sum_over_axis(products(chunk i of the first
        ``acts`` arguments, the rest))`` of arguments laid out by
        ``in_specs``; the result is equal on the chips of ``axis``."""
        def local(*args):
            def summed(part):
                with jax.named_scope("tp.exchange"):
                    return part + lax.ppermute(part, axis, ((0, 1), (1, 0)))
            chunks = zip(*(map(keep, jnp.split(x, n, axis=1))
                           for x in args[:acts]))
            return jnp.concatenate([keep(summed(products(*c, *args[acts:])))
                                    for c in chunks], axis=1)
        return jax.shard_map(local, mesh=mesh, axis_names={axis},
                             in_specs=in_specs, out_specs=P(),
                             check_vma=False)

    @jax.custom_vjp
    def row(a, w):
        return exchanged(jnp.matmul, 1, (wide, rowwise))(a, w)

    def row_bwd(res, ct):
        a, w = res
        return (jnp.einsum("bsm,km->bsk", ct, w),
                jnp.einsum("bsk,bsm->km", a, ct))
    # named for ``_remat``'s "dots": no policy sees a product in here
    row.defvjp(lambda a, w: (_checkpoint_name(row(a, w), "row_sum"), (a, w)),
               row_bwd)

    @jax.custom_vjp
    def col(y, *ws):
        return tuple(y @ w for w in ws)

    def col_bwd(res, cts):
        y, ws = res
        k = len(ws)
        dy = exchanged(
            lambda *c: sum(jnp.einsum("bsm,km->bsk", ct, w)
                           for ct, w in zip(c[:k], c[k:])),
            k, (wide,) * k + (colwise,) * k)(*cts, *ws)
        return (dy, *(jnp.einsum("bsk,bsm->km", y, ct) for ct in cts))
    col.defvjp(lambda y, *ws: (col(y, *ws), (y, ws)), col_bwd)
    return col, row


def _layer_products(cfg: LlamaConfig, mesh: Optional[Mesh], axes: MeshAxes,
                    rows: int):
    """``(col, row)`` for a layer's weight products on ``mesh``:
    ``col(y, *ws)`` the products of a norm's output with weights whose
    last dimension ``tensor`` shards (wq / wk / wv; w_gate / w_up),
    ``row(a, w)`` the product with one whose first it shards (wo,
    w_down).

    Where the sums over ``tensor`` are made. The weights are Megatron's
    (``param_shardings``), so a ``row`` leaves partial sums on the
    chips of ``tensor`` and a ``col`` takes partial cotangents back:
    five sums of the whole residual stream a layer a step under
    ``remat_policy`` ``full`` (the MLP's recomputed ``row`` is dead).

    - No mesh, or a ``tensor`` axis of one: there is no sum.
    - A ``tensor`` axis of TWO (the sequence on one chip, in
      ``_tp_chunks`` chunks): ``_tensor_pair_products`` makes each sum
      as chunked exchanges beside the next chunk's matmul.
    - Anything else (a wider ``tensor`` axis, a ``context`` axis, heads
      or rows that two does not divide): GSPMD closes the plain
      products with its own ``all-reduce``, which this compiler runs
      blocking. A ring of ``ppermute``s over the chunks is what would
      extend the exchange to a wider axis."""
    sizes = mesh.shape if mesh is not None else {}
    n = _tp_chunks(rows)
    if (sizes.get(axes.tensor, 1) == 2 and n
            and sizes.get(axes.context, 1) == 1
            and cfg.n_kv_heads % 2 == cfg.n_heads % 2 == cfg.ffn_dim % 2 == 0):
        return _tensor_pair_products(mesh, axes.tensor, n,
                                     P(axes.batch, axes.context, None))
    return (lambda y, *ws: tuple(y @ w for w in ws)), jnp.matmul


def forward_hidden(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                   mesh: Optional[Mesh] = None,
                   axes: MeshAxes = MeshAxes()) -> jax.Array:
    """tokens: (batch, seq) int32 -> final NORMED hidden states
    (batch, seq, dim) — the pre-lm_head activations (the fused CE
    consumes these chunk by chunk instead of full logits).

    On a mesh the residual stream is held replicated over ``tensor``
    (``act_constraint`` after each sub-layer). A layer's sums over
    ``tensor`` are made inside its ``col`` / ``row`` products
    (``_layer_products``: chunked exchanges on a ``tensor`` pair, else
    GSPMD's all-reduce where the constraint closes a plain product);
    the embedding's gather and the head's backward sum are GSPMD's."""
    b, s = tokens.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def act_constraint(x, spec):
        if mesh is not None:
            return lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, spec))
        return x

    x = jnp.take(params["embed"], tokens, axis=0)
    x = act_constraint(x, P(axes.batch, axes.context, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    rope_cos, rope_sin = _rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    col, row = _layer_products(cfg, mesh, axes, s)

    # the scopes only name the ops in a device trace (metadata)
    def layer(x, lp):
        with jax.named_scope("attention"):
            y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = col(y, lp["wq"], lp["wk"], lp["wv"])
            q = _rope(q.reshape(b, s, h, hd), rope_cos, rope_sin)
            k = _rope(k.reshape(b, s, kvh, hd), rope_cos, rope_sin)
            v = v.reshape(b, s, kvh, hd)
            o = _attend(q, k, v, cfg, mesh, axes).astype(x.dtype)
            x = x + row(o.reshape(b, s, h * hd), lp["wo"])
            x = act_constraint(x, P(axes.batch, axes.context, None))
        with jax.named_scope("mlp"):
            y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
            gate, up = col(y, lp["w_gate"], lp["w_up"])
            x = x + row(jax.nn.silu(gate) * up, lp["w_down"])
            x = act_constraint(x, P(axes.batch, axes.context, None))
        return x, None

    step = _remat(layer, cfg)
    x, _ = lax.scan(step, x, params["layers"])
    return _rmsnorm(x, params["final_norm"], cfg.norm_eps)


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig,
            mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes()) -> jax.Array:
    """tokens: (batch, seq) int32 -> logits (batch, seq, vocab)."""
    x = forward_hidden(params, tokens, cfg, mesh, axes)
    return (x @ params["lm_head"]).astype(jnp.dtype(cfg.logits_dtype))


def cross_entropy(logits: jax.Array, batch: dict) -> jax.Array:
    """Masked token cross-entropy, shared by every model family.

    max/exp run in the logits dtype (bf16 when configured — faster VPU
    rate, half the HBM traffic); accumulation and the final log are f32.
    """
    targets = batch["targets"]
    m = jnp.max(logits, axis=-1, keepdims=True)
    sumexp = jnp.sum(jnp.exp(logits - m), axis=-1, dtype=jnp.float32)
    logz = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold.astype(jnp.float32)
    mask = batch.get("mask")
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def fused_cross_entropy(x: jax.Array, lm_head: jax.Array, batch: dict,
                        chunk: int, logits_dtype) -> jax.Array:
    """Chunked logits-free cross-entropy: scan seq chunks, projecting
    each (b, chunk, dim) -> (b, chunk, vocab), reducing to the masked
    NLL sums, and dropping the chunk logits. jax.checkpoint on the
    chunk body recomputes them on backward, so the peak live logits
    tensor is (b, chunk, vocab) instead of (b, s, vocab) — the classic
    big-vocab fusion (vocab stays shardable over tensor: the max /
    sumexp reductions cross the vocab axis, GSPMD inserts the psums).
    """
    b, s, d = x.shape
    n = s // chunk
    dt = jnp.dtype(logits_dtype)
    targets = batch["targets"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    # (n, b, chunk, ...) scan layout
    xc = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.astype(jnp.float32).reshape(b, n, chunk),
                      1, 0)

    @jax.checkpoint
    def body(acc, inp):
        xch, tch, mch = inp
        logits = (xch @ lm_head).astype(dt)
        m = jnp.max(logits, axis=-1, keepdims=True)
        sumexp = jnp.sum(jnp.exp(logits - m), axis=-1,
                         dtype=jnp.float32)
        logz = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
        gold = jnp.take_along_axis(
            logits, tch[..., None], axis=-1)[..., 0]
        nll = logz - gold.astype(jnp.float32)
        tot, cnt = acc
        return (tot + jnp.sum(nll * mch), cnt + jnp.sum(mch)), None

    (tot, cnt), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params: dict, batch: dict, cfg: LlamaConfig,
            mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes()) -> jax.Array:
    """batch: {"tokens": (b, s), "targets": (b, s), "mask": optional}."""
    s = batch["tokens"].shape[1]
    if cfg.ce_chunk > 0:
        if s % cfg.ce_chunk:
            # silently materializing the full logits here would undo
            # the exact memory saving the flag was set for
            raise ValueError(
                f"ce_chunk={cfg.ce_chunk} must divide seq len {s}")
        if s > cfg.ce_chunk:
            x = forward_hidden(params, batch["tokens"], cfg, mesh, axes)
            with jax.named_scope("loss"):
                return fused_cross_entropy(x, params["lm_head"], batch,
                                           cfg.ce_chunk, cfg.logits_dtype)
        # s == ce_chunk: one chunk IS the full logits — classic path
    logits = forward(params, batch["tokens"], cfg, mesh, axes)
    with jax.named_scope("loss"):
        return cross_entropy(logits, batch)
