"""Mixture-of-Experts decoder family, TPU-first: dropless top-k routing
over a grouped matmul. Mixtral and OLMoE are settings of one config.

The expert layer, for the (tokens, d) rows ``y`` of a normed hidden state:
router logits in float32, softmax over all experts, the ``k`` largest
probabilities per token (renormalised to sum to 1 when ``norm_topk_prob``,
as Mixtral does; kept as they are when not, as OLMoE does); a stable sort
of the tokens x k assignments by expert and a count give the row order and
the group sizes; the rows are gathered in that order, go through three
grouped matmuls (gate, up, down: ``ops/pallas/grouped_matmul.py`` on a
TPU, ``jax.lax.ragged_dot`` elsewhere), and come back weighted by their
gate and summed per token. Every token reaches all ``k`` of its experts
whatever the load: there is no capacity and nothing is dropped, and the
cost follows tokens x k, not experts x capacity. Dispatch and combine are
gathers in both directions (the backward of a gather by a permutation is
the gather by its inverse), so no scatter runs on the device.

Expert parallelism is a mesh axis (``MeshAxes.expert``): the expert layer
runs under ``shard_map`` with the tokens sharded over the batch and
context axes and replicated over the expert and tensor axes. A device
sorts its tokens' assignments with the other devices' experts last,
computes the groups of its own experts (for its slice of the expert
width, if the tensor axis cuts it), and the results are summed over the
expert and tensor axes. Simple, and no faster than one device at the
expert layer; a token all-to-all is a later change.

The load-balancing term is ``load_balancing_loss_func`` of
``transformers``' ``modeling_olmoe.py`` / ``modeling_mixtral.py``: over the
routers of ALL layers taken together, ``E * sum_e f_e p_e`` with ``f_e``
the assignments to expert ``e`` per token and ``p_e`` the mean router
probability of ``e``. The loss is cross-entropy + ``aux_loss_weight``
times it.

Attention is shared with the Llama family (``ray_tpu.models.llama``): RoPE
+ GQA + flash/ring kernels, identical remat policies. ``qk_norm`` adds
OLMoE's RMSNorm with a learned weight over the whole projected q and k,
before the split into heads and before RoPE.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.llama import MeshAxes, _attend, _on_tpu, _rmsnorm, \
    _rope, _rope_tables
from ray_tpu.ops.pallas import grouped_matmul


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336        # width of ONE expert
    n_experts: int = 8
    experts_per_token: int = 2
    # the published ``norm_topk_prob``: the kept gates are renormalised to
    # sum to 1 (Mixtral) or left as the softmax gave them (OLMoE)
    norm_topk_prob: bool = True
    # OLMoE: RMSNorm over the whole projected q and k, before heads and RoPE
    qk_norm: bool = False
    aux_loss_weight: float = 0.01
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"
    logits_dtype: str = "float32"
    attn_impl: str = "auto"
    attn_block_q: int = 128
    attn_block_k: int = 128
    # "auto": the Pallas kernel on a TPU, lax.ragged_dot elsewhere;
    # "pallas", "pallas_interpret" (CPU tests), "ragged_dot"
    gmm_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def _layer_params(self, experts: int) -> int:
        d, f = self.dim, self.ffn_dim
        h, kvh, hd = self.n_heads, self.n_kv_heads, self.head_dim
        attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
        if self.qk_norm:
            attn += h * hd + kvh * hd
        return attn + d * self.n_experts + 3 * experts * d * f + 2 * d

    def num_params(self) -> int:
        return 2 * self.vocab_size * self.dim + self.dim \
            + self.n_layers * self._layer_params(self.n_experts)

    def num_active_params(self) -> int:
        """Params touched per token (top-k experts, not all)."""
        return 2 * self.vocab_size * self.dim + self.dim \
            + self.n_layers * self._layer_params(self.experts_per_token)

    def flops_per_token(self, seq_len: int) -> float:
        n_matmul = self.num_active_params() - self.vocab_size * self.dim
        attn = 12 * self.n_layers * self.dim * seq_len
        return 6.0 * n_matmul + attn


def mixtral_8x7b(**kw) -> MoEConfig:
    return MoEConfig(**kw)


def olmoe_1b_7b(**kw) -> MoEConfig:
    """allenai/OLMoE-1B-7B-0125-Instruct ``config.json``."""
    defaults = dict(vocab_size=50304, dim=2048, n_layers=16, n_heads=16,
                    n_kv_heads=16, ffn_dim=1024, n_experts=64,
                    experts_per_token=8, norm_topk_prob=False, qk_norm=True,
                    max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-5)
    defaults.update(kw)
    return MoEConfig(**defaults)


def tiny(**kw) -> MoEConfig:
    defaults = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=128, n_experts=4,
                    experts_per_token=2, max_seq_len=128)
    defaults.update(kw)
    return MoEConfig(**defaults)


# --- params ----------------------------------------------------------------

def init_params(rng: jax.Array, cfg: MoEConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, f, E = cfg.dim, cfg.ffn_dim, cfg.n_experts
    h, kvh, hd, L = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    ks = jax.random.split(rng, 10)

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    layers = {
        "attn_norm": jnp.ones((L, d), dtype),
        "wq": norm_init(ks[1], (L, d, h * hd), d),
        "wk": norm_init(ks[2], (L, d, kvh * hd), d),
        "wv": norm_init(ks[3], (L, d, kvh * hd), d),
        "wo": norm_init(ks[4], (L, h * hd, d), h * hd),
        "mlp_norm": jnp.ones((L, d), dtype),
        # router in f32: tiny, and top-k tie-breaks are dtype-sensitive
        "router": (jax.random.normal(ks[5], (L, d, E), jnp.float32)
                   * (d ** -0.5)),
        "w_gate": norm_init(ks[6], (L, E, d, f), d),
        "w_up": norm_init(ks[7], (L, E, d, f), d),
        "w_down": norm_init(ks[8], (L, E, f, d), f),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, h * hd), dtype)
        layers["k_norm"] = jnp.ones((L, kvh * hd), dtype)
    return {
        "embed": norm_init(ks[0], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": norm_init(ks[9], (d, cfg.vocab_size), d),
    }


def param_shardings(cfg: MoEConfig, axes: MeshAxes = MeshAxes()) -> dict:
    t, fs, ep = axes.tensor, axes.fsdp, axes.expert
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, fs, t),
        "wk": P(None, fs, t),
        "wv": P(None, fs, t),
        "wo": P(None, t, fs),
        "mlp_norm": P(None, None),
        "router": P(None, fs, None),
        "w_gate": P(None, ep, fs, t),
        "w_up": P(None, ep, fs, t),
        "w_down": P(None, ep, t, fs),
    }
    if cfg.qk_norm:
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    return {
        "embed": P(t, fs),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(fs, t),
    }


# --- the expert layer ------------------------------------------------------

def _rows(x, index):
    """``x[index]`` along axis 0; every index is in range by construction
    (a permutation, or one divided by k), so no bounds handling is built."""
    return x.at[index].get(mode="promise_in_bounds")


@jax.custom_vjp
def _dispatch(y, order, inverse):
    """Rows of ``y`` (T, d) in sorted-assignment order (T*k, d): row ``p``
    is the token of assignment ``order[p]``."""
    return _rows(y, order // (order.shape[0] // y.shape[0]))


def _dispatch_fwd(y, order, inverse):
    return _dispatch(y, order, inverse), (inverse, y.shape[0])


def _dispatch_bwd(res, g):
    inverse, tokens = res
    dy = _rows(g, inverse).reshape(tokens, -1, g.shape[1])
    return dy.astype(jnp.float32).sum(1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, gates, order, inverse):
    """Per token, the gate-weighted sum of its k expert rows: ``rows``
    (T*k, d) in sorted order, ``gates`` (T, k) float32 -> (T, d)."""
    mine = _rows(rows, inverse).reshape(*gates.shape, -1)
    return jnp.einsum("tkd,tk->td", mine.astype(jnp.float32),
                      gates).astype(rows.dtype)


def _combine_fwd(rows, gates, order, inverse):
    return _combine(rows, gates, order, inverse), (rows, gates, order,
                                                   inverse)


def _combine_bwd(res, g):
    rows, gates, order, inverse = res
    k = gates.shape[1]
    d_rows = (_rows(g, order // k).astype(jnp.float32)
              * _rows(gates.reshape(-1), order)[:, None])
    mine = _rows(rows, inverse).reshape(*gates.shape, -1)
    d_gates = jnp.einsum("tkd,td->tk", mine.astype(jnp.float32),
                         g.astype(jnp.float32))
    return d_rows.astype(rows.dtype), d_gates, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped(x, w, group_sizes, cfg: MoEConfig):
    impl = cfg.gmm_impl
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ragged_dot"
    if impl == "ragged_dot":
        return lax.ragged_dot(x, w, group_sizes)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown gmm_impl: {cfg.gmm_impl!r}")
    return grouped_matmul.gmm(x, w, group_sizes, impl == "pallas_interpret")


def _experts(y, router, w_gate, w_up, w_down, cfg: MoEConfig,
             first_expert=0):
    """y (T, d) -> (out (T, d) from the experts ``first_expert ...`` that
    ``w_*`` hold, assignments per expert (E,), summed router probabilities
    (E,)); the last two over all experts."""
    T, k, E = y.shape[0], cfg.experts_per_token, cfg.n_experts
    local = w_gate.shape[0]
    with jax.named_scope("moe.route"):
        logits = jnp.dot(y.astype(jnp.float32), router,
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
        gates, experts = lax.top_k(probs, k)                     # (T, k)
        if cfg.norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        # other devices' experts sort last, into rows no group covers
        mine = experts.reshape(-1) - first_expert
        mine = jnp.where((mine >= 0) & (mine < local), mine, local)
        order = jnp.argsort(mine, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        # counts by compare-and-sum: a scatter-add into E bins serialises
        group_sizes = jnp.sum(mine[:, None] == jnp.arange(local), axis=0,
                              dtype=jnp.int32)
        counts = jnp.sum(experts.reshape(-1, 1) == jnp.arange(E), axis=0,
                         dtype=jnp.float32)
    with jax.named_scope("moe.experts"):
        x = _dispatch(y, order, inverse)                         # (T*k, d)
        h = jax.nn.silu(_grouped(x, w_gate, group_sizes, cfg)) \
            * _grouped(x, w_up, group_sizes, cfg)
        rows = _grouped(h, w_down, group_sizes, cfg)
    with jax.named_scope("moe.combine"):
        out = _combine(rows, gates, order, inverse)
    return out, counts, jnp.sum(probs, axis=0)


def _moe_block(y, lp, cfg: MoEConfig, mesh: Optional[Mesh],
               axes: MeshAxes):
    """y (b, s, d) normed hidden -> (expert-mixed output (b, s, d),
    assignments per expert and token (E,), mean router probability (E,))."""
    b, s, d = y.shape
    weights = (lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])
    ep, t = axes.expert, axes.tensor

    def local(y, router, w_gate, w_up, w_down):
        bl, sl, _ = y.shape
        first = lax.axis_index(ep) * w_gate.shape[0]
        out, counts, probs = _experts(y.reshape(bl * sl, d), router, w_gate,
                                      w_up, w_down, cfg, first)
        tok_axes = (*axes.batch, axes.context)
        return (lax.psum(out, (ep, t)).reshape(bl, sl, d),
                lax.psum(counts, tok_axes), lax.psum(probs, tok_axes))

    if mesh is None:
        out, counts, probs = _experts(y.reshape(b * s, d), *weights, cfg)
        out = out.reshape(b, s, d)
    else:
        # check_vma=False: pallas_call outputs carry no vma under shard_map
        out, counts, probs = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes.batch, axes.context, None), P(None, None),
                      P(ep, None, t), P(ep, None, t), P(ep, t, None)),
            out_specs=(P(axes.batch, axes.context, None), P(None), P(None)),
            check_vma=False)(y, *weights)
    return out, counts / (b * s), probs / (b * s)


# --- forward ---------------------------------------------------------------

def _forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
             mesh: Optional[Mesh], axes: MeshAxes):
    """tokens (b, s) int32 -> (logits (b, s, vocab), routing statistics
    ``{"moe_aux_loss", "moe_load_max_over_mean"}``, float32 scalars)."""
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

    # the scopes only name the ops in a device trace (metadata)
    def layer(x, lp):
        with jax.named_scope("attention"):
            y = _rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = y @ lp["wq"], y @ lp["wk"], y @ lp["wv"]
            if cfg.qk_norm:
                q = _rmsnorm(q, lp["q_norm"], cfg.norm_eps)
                k = _rmsnorm(k, lp["k_norm"], cfg.norm_eps)
            q = _rope(q.reshape(b, s, h, hd), rope_cos, rope_sin)
            k = _rope(k.reshape(b, s, kvh, hd), rope_cos, rope_sin)
            o = _attend(q, k, v.reshape(b, s, kvh, hd), cfg, mesh,
                        axes).astype(x.dtype)
            x = x + (o.reshape(b, s, h * hd) @ lp["wo"])
            x = act_constraint(x, P(axes.batch, axes.context, None))
        y = _rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        moe_out, load, prob = _moe_block(y, lp, cfg, mesh, axes)
        x = x + moe_out
        x = act_constraint(x, P(axes.batch, axes.context, None))
        return x, (load, prob)

    step = llama._remat(layer, cfg)
    x, (load, prob) = lax.scan(step, x, params["layers"])    # (L, E) each
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.dtype(cfg.logits_dtype))
    # the published term pools the routers of all layers: a token of layer
    # l is one more token, so f and p are means over layers
    aux = cfg.n_experts * jnp.sum(jnp.mean(load, 0) * jnp.mean(prob, 0))
    load = lax.stop_gradient(load)
    stats = {"moe_aux_loss": aux, "moe_load_max_over_mean": jnp.mean(
        jnp.max(load, axis=1) / jnp.mean(load, axis=1))}
    return logits, stats


def forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
            mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes()) -> jax.Array:
    """tokens (b, s) int32 -> logits (b, s, vocab)."""
    return _forward(params, tokens, cfg, mesh, axes)[0]


def loss_and_metrics(params: dict, batch: dict, cfg: MoEConfig,
                     mesh: Optional[Mesh] = None,
                     axes: MeshAxes = MeshAxes()):
    """(cross-entropy + weighted load-balancing term, the routing
    statistics ``make_train_step`` adds to a step's metrics)."""
    logits, stats = _forward(params, batch["tokens"], cfg, mesh, axes)
    with jax.named_scope("loss"):
        loss = llama.cross_entropy(logits, batch) \
            + cfg.aux_loss_weight * stats["moe_aux_loss"]
    return loss, stats


def loss_fn(params: dict, batch: dict, cfg: MoEConfig,
            mesh: Optional[Mesh] = None,
            axes: MeshAxes = MeshAxes()) -> jax.Array:
    return loss_and_metrics(params, batch, cfg, mesh, axes)[0]
