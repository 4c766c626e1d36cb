"""Mixture-of-Experts decoder family, TPU-first: dropless top-k routing
over a grouped matmul. Mixtral, OLMoE and the sigmoid-routed hybrid
(``k_exaone_236b_a23b``) are settings of one config.

The expert layer, for the (tokens, d) rows ``y`` of a normed hidden state:
router logits in float32, then one of two scorings (``scoring``).
"softmax": softmax over all experts, the ``k`` largest probabilities per
token (renormalised to sum to 1 when ``norm_topk_prob``, as Mixtral does;
kept as they are when not, as OLMoE does). "sigmoid": s = sigmoid(logits);
the ``k`` largest of s + b choose the experts (b the router's selection
bias, a parameter that never enters a gate), the gates are the chosen s,
renormalised when ``norm_topk_prob``, times ``routed_scaling``. Then a stable sort
of the tokens x k assignments by expert and a count give the row order and
the group sizes; the rows, in that order, go through three grouped
matmuls (gate, up, down: ``ops/pallas/grouped_matmul.py`` on a TPU,
``jax.lax.ragged_dot`` elsewhere), and come back weighted by their gate and
summed per token. Every token reaches all ``k`` of its experts whatever the
load: there is no capacity and nothing is dropped, and the cost follows
tokens x k, not experts x capacity. Dispatch and combine are gathers in
both directions (the backward of a gather by a permutation is the gather
by its inverse), so no scatter runs on the device where all experts are
held, through two ``custom_vjp``s. Of the six (T*k, d) gathers a train
step's layer could hold, three are written (PR 43): with a layer's own
weights and the kernel (``_gate_up``), gate and up read y's rows BY ID in
one call, forward, recomputed and for the weights' gradients, so
``_dispatch``'s copy exists only under ``ragged_dot`` and in serving;
``_combine``'s backward gathers the cotangent's rows once and takes the
gates' gradient from them in sorted order. Left: ``_combine``'s forward
(rows[inverse]), its backward (g[order // k]) and the rows' gradient going
home (``_token_sums``).
``n_shared_experts`` adds one SwiGLU of ``n_shared_experts * ffn_dim`` that
every token goes through, beside the routed sum (``_shared``).

A device can hold a SLICE of the experts without a mesh: ``experts_held``
of ``n_experts`` from ``first_expert`` on. The router still scores all
``n_experts``; the layer computes the held experts' part (plus the shared
expert) and returns that partial sum: what an expert-parallel deployment
adds up across its devices. Nothing here stands in for the other devices.

Such a device owns about ``held / n_experts`` of the assignments; the
others' sort into a tail no group covers. The TRAIN step (``_held_sum``;
under an expert mesh axis too) passes over its own rows only: the first
``_local_bound`` sorted assignments (twice the expected share, in whole
row tiles: from the shapes, no setting) are the work list of the kernels,
of the elementwise passes and of the combine, which there is a sum of the
list's gate-weighted rows INTO their tokens (a float32 scatter-add; its
backward one gather of as many rows), and so is the rows' gradient going
home. A routing that sends the device more than the bound takes the path
above over every assignment instead (``lax.cond`` on the count, in the
layer): the same sum, nothing dropped, no capacity. The step's statistics
say how many layers took the list (``moe_compact_share``). Serving
(``serve_block``) keeps the full path: a decode step pays for the held
experts' bytes, not for its 256 rows.

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

Layer kinds of a TRAINING configuration (``layer_types`` of "linear" and
"full"; none means every layer full): a "full" layer is the attention
above, with, by their switches, a ``head_size`` that is not dim / heads,
``qk_head_norm`` (RMSNorm of q and k over ``head_dim``, per head, before
RoPE), RoPE on the first ``rotary_dim`` dimensions of a head only, and
``attn_output_gate`` (the q projection is twice as wide, a head's second
half a gate: o * sigmoid(gate) before the output projection). A "linear"
layer is a Gated DeltaNet (``_gated_delta_net``; the rule itself is
``ops/gated_delta.py``). Between its input and output projections the
mixer is head-major, (b, heads, s, 128): q, k, v and z are each the
product with their own columns of ``w_qkvz``, the causal conv + silu + L2
norm of a kind (``_conv_silu_norm``) and the gated norm (``_gated_norm``)
are each one function with a hand-written backward that keeps its bf16
inputs only, and no activation is reshaped across the minor dimension or
stored in float32 on the way. ``zero_centered_norm`` makes every RMSNorm
x * rsqrt(mean(x^2) + eps) * (1 + w) but the linear layer's gated one. The
kinds' parameters are stacks of their own (``linear_layers``,
``full_layers``) beside ``layers``, which holds what every layer has; the
layers run as a ``lax.scan`` over whole periods of the pattern.
``shared_expert_gate`` weighs the shared expert by sigmoid(w . y) a token;
with ``experts_held`` the train step computes the held experts' partial sum
too, and its statistics carry ``moe_local_share``.

The serving-only shapes (``llm/model.py`` runs them; the train forward
here refuses them): ``layer_types`` of "window" (such layers attend the
last ``sliding_window`` positions) and "global", ``n_dense_layers``
leading layers with a dense SwiGLU of ``dense_ffn_dim``, ``post_norm``
(the sub-layer norms on each sub-layer's OUTPUT: x + Norm(Attn(x))),
``rope_layers`` "window" (no RoPE on global layers) and "sigmoid" scoring
(its selection bias has no training rule here), and ``layer_types`` of
"latent": multi-head latent attention, whose cache keeps ONE row a token a
layer, [c | kr] of ``kv_lora_rank + qk_rope_head_dim`` values and no head
axis: q = (RMSNorm(x Wq_a) Wq_b) by head [nope | rope], [ckv | kr] = x
Wkv_a, c = RMSNorm(ckv), RoPE over interleaved pairs on the rope parts
(YaRN's frequencies, ``rope_factor`` over ``rope_original_len``
positions), a head's key [c Wk_b[h] | kr] and value c Wv_b[h], scores
scaled by head ** -0.5 * mscale ** 2 (mscale = 0.1 * ``rope_mscale_all_dim``
* ln(factor) + 1) and the query of position t by 1 + ``query_scale_beta`` *
ln(1 + floor(t / rope_original_len)). Prefill materialises keys and values
from the rows it attends; decode absorbs ``wk_b`` into the query and
``wv_b`` into the output and attends the rows themselves (``llm/model.py``).
``hc_mult`` n > 1 (with ``hc_sinkhorn_iters``, ``hc_eps``,
``hc_res_clamp_min`` / ``hc_res_clamp_max``) widens the residual stream to
n copies a position, mixed by manifold-constrained hyper-connections
(``llm/model.py`` has the equations): each sub-layer reads sigmoid-weighted
rows of the stream and writes back through an n x n matrix that
``hc_sinkhorn_iters`` alternating column / row normalisations of
exp(clip(.)) make doubly stochastic, all from the position's own normed
stream, in float32. Its leaves, a sub-layer (``hc_attn_*``, ``hc_mlp_*``):
``phi`` (n * d, 2n + n * n) float32 with columns [pre | post | res],
``b`` (2n + n * n,) and ``a`` (3,), the three learned scales.
``layer_types`` of "state" and "experts" (beside "global") make every layer
ONE mixer behind one norm, x + mixer(RMSNorm(x)): a Mamba-2 state-space
mixer (``ops/ssm.py``; ``ssm_*``), an expert layer or attention alone, each
kind's parameters a stack of its own (``single_mixer``); ``expert_act``
"relu2" makes an expert down(relu(up x) ** 2), two matrices and no gate,
routed and shared alike (``nemotron_3_nano_30b_a3b``).
One expert layer serves both: ``serve_block`` and the train forward's ``_experts`` share ``_route``,
``_sort_by_expert``, ``_gated_sum`` and ``_shared``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.llama import MeshAxes, _attend, _on_tpu, _rmsnorm, \
    _rope, _rope_tables
from ray_tpu.ops import gated_delta
from ray_tpu.ops.pallas import grouped_matmul
from ray_tpu.ops.ssm import conv_taps


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
    # 0: dim // n_heads
    head_size: int = 0
    # "softmax", or "sigmoid" with the selection bias and this scale
    scoring: str = "softmax"
    routed_scaling: float = 1.0
    n_shared_experts: int = 0
    # this device's slice of the experts: 0 = all of them
    experts_held: int = 0
    first_expert: int = 0
    # per layer: "linear" | "full" (training), "window" | "global" or, every
    # layer, "latent" (serving: llm/kvcache.py has the kinds' names)
    layer_types: tuple = ()
    qk_head_norm: bool = False
    # the full layers of a training configuration (module docstring)
    rotary_dim: int = 0         # 0: the whole head
    attn_output_gate: bool = False
    zero_centered_norm: bool = False
    shared_expert_gate: bool = False
    # the linear (gated delta rule) layers
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0     # of one head
    linear_value_dim: int = 0
    linear_conv_kernel: int = 4
    # serving-only shapes (module docstring)
    sliding_window: int = 0
    n_dense_layers: int = 0
    dense_ffn_dim: int = 0
    post_norm: bool = False
    rope_layers: str = "all"
    # "latent" layers (multi-head latent attention; module docstring): the
    # low-rank widths of q and of the cached row, and a head's parts
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (``rope_parameters`` of type "yarn"): factor 0 = plain RoPE
    rope_factor: float = 0.0
    rope_original_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # ``llama_4_scaling_beta``: the query of position t is scaled by
    # 1 + beta * ln(1 + floor(t / rope_original_len))
    query_scale_beta: float = 0.0
    # manifold-constrained hyper-connections (module docstring): the
    # residual stream's copies a position; 0 or 1 = the plain add
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # an expert (routed and shared alike): "swiglu", down(silu(gate x) *
    # up x), three matrices; "relu2", down(relu(up x) ** 2), two and no gate
    expert_act: str = "swiglu"
    # "state" layers (a Mamba-2 mixer, ops/ssm.py): heads of ``ssm_head_dim``
    # channels with a state of ``ssm_state`` a channel, B and C by group
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # > 0: a "state" layer is a gated SHORT CONVOLUTION of this many taps
    # (ops/shortconv.py), the first sub-layer of a two-sub-layer block whose
    # second is the dense or the expert feed-forward; what a request keeps
    # of it is the conv's tail alone, ``shortconv_kernel - 1`` rows
    shortconv_kernel: int = 0
    # added to the chosen gates' sum before ``norm_topk_prob`` divides by it
    route_eps: float = 0.0
    # the head multiplies with the embedding's transpose: no ``lm_head`` leaf
    tie_embeddings: bool = False
    # K/V heads that share one row of the paged pool's 128 lanes: 2 for
    # heads of 64 values (llm/kvcache.py row_shapes)
    kv_row_heads: int = 1

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.n_heads

    @property
    def hc_copies(self) -> int:
        """The mixed residual stream's copies a position; 0: the plain add."""
        return self.hc_mult if self.hc_mult > 1 else 0

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def single_mixer(self) -> bool:
        """Whether every layer is ONE mixer behind one norm (a state layer,
        an expert layer or attention alone), its kinds' parameters stacks
        of their own (``state_layers``, ``expert_layers``, ``attn_layers``:
        ``llm/model.py STACKS``). Not a model whose state layers are short
        convolutions (``shortconv_kernel``): its layers have two sub-layers."""
        return bool({"state", "experts"} & set(self.layer_types)) \
            and not self.shortconv_kernel

    @property
    def ssm_widths(self) -> tuple:
        """(inner width: what the gate and the output projection see, the
        conv's channels [x | B | C]) of a state layer."""
        inner = self.ssm_heads * self.ssm_head_dim
        return inner, inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_mats(self) -> int:
        """Matrices an expert has: gate, up and down, or up and down."""
        return {"swiglu": 3, "relu2": 2}[self.expert_act]

    @property
    def linear_widths(self) -> tuple:
        """(key width, value width) of a linear layer, all heads."""
        return (self.linear_key_heads * self.linear_key_dim,
                self.linear_value_heads * self.linear_value_dim)

    def _attn_params(self) -> int:
        d, h, kvh, hd = self.dim, self.n_heads, self.n_kv_heads, self.head_dim
        if "latent" in self.layer_types:
            ql, kvl = self.q_lora_rank, self.kv_lora_rank
            nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim)
            return d * ql + ql + ql * h * (nope + rope) + d * (kvl + rope) \
                + kvl + kvl * h * (nope + vd) + h * vd * d + 2 * d
        q = 2 if self.attn_output_gate else 1
        attn = q * d * h * hd + 2 * d * kvh * hd + h * hd * d + 2 * d
        if self.qk_norm:
            attn += h * hd + kvh * hd
        if self.qk_head_norm:
            attn += 2 * hd
        return attn

    def _linear_params(self) -> int:
        d, (kw, vw) = self.dim, self.linear_widths
        hv = self.linear_value_heads
        return d * (2 * kw + 2 * vw) + d * 2 * hv + vw * d + 2 * d \
            + (2 * kw + vw) * self.linear_conv_kernel + 2 * hv \
            + self.linear_value_dim

    def _layer_params(self, experts: int) -> int:
        """One expert layer without its mixer."""
        d, f = self.dim, self.ffn_dim
        router = d * self.n_experts \
            + (self.n_experts if self.scoring == "sigmoid" else 0) \
            + (d if self.shared_expert_gate else 0)
        return router \
            + self.expert_mats * (experts + self.n_shared_experts) * d * f

    def _state_params(self) -> int:
        """One state layer's mixer."""
        inner, conv = self.ssm_widths
        return self.dim * (inner + conv + self.ssm_heads) + inner * self.dim \
            + conv * (self.ssm_conv_kernel + 1) + 3 * self.ssm_heads + inner

    def _shortconv_params(self) -> int:
        """One gated short convolution: the in-projection [B | C | X], the
        taps and the out-projection."""
        d = self.dim
        return d * 3 * d + d * self.shortconv_kernel + d * d

    def _mixing_params(self) -> int:
        """A layer's hyper-connection leaves (two sub-layers)."""
        n = self.hc_copies
        if not n:
            return 0
        return 2 * ((n * self.dim + 1) * (2 * n + n * n) + 3)   # phi, b; a

    def _params(self, experts: int) -> int:
        if self.single_mixer:
            # every layer ONE mixer behind one norm (llm/model.py)
            count = self.layer_types.count
            return 2 * self.vocab_size * self.dim + self.dim \
                + self.n_layers * self.dim \
                + count("state") * self._state_params() \
                + count("experts") * self._layer_params(experts) \
                + count("global") * (self._attn_params() - 2 * self.dim)
        if self.shortconv_kernel:
            # an operator a layer (attention or a short convolution), the
            # leading dense layers' included, behind the two norms
            conv = self.layer_types.count("state")
            embed = (1 if self.tie_embeddings else 2) * self.vocab_size \
                * self.dim
            return embed + self.dim \
                + conv * (self._shortconv_params() + 2 * self.dim) \
                + (self.n_layers - conv) * self._attn_params() \
                + self.n_dense_layers * 3 * self.dim * self.dense_ffn_dim \
                + (self.n_layers - self.n_dense_layers) \
                * self._layer_params(experts)
        dense = self._attn_params() + 3 * self.dim * self.dense_ffn_dim
        linear = self.layer_types.count("linear")
        mixers = linear * self._linear_params() \
            + (self.n_layers - self.n_dense_layers - linear) \
            * self._attn_params()
        return 2 * self.vocab_size * self.dim + self.dim \
            + self.n_layers * self._mixing_params() \
            + self.n_dense_layers * dense + mixers \
            + (self.n_layers - self.n_dense_layers) \
            * self._layer_params(experts)

    def num_params(self) -> int:
        """Parameters this device holds (``experts_held`` of the experts)."""
        return self._params(self.n_held)

    def num_active_params(self) -> int:
        """Params touched per token (top-k experts, not all)."""
        return self._params(self.experts_per_token)

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


def k_exaone_236b_a23b(**kw) -> MoEConfig:
    """LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``: 48 layers in the
    period window, window, window, global (window 128), layer 0 dense,
    128 sigmoid-routed experts of width 2048, 8 a token, one shared
    expert, head_dim 128 beside hidden 6144 / 64 heads."""
    defaults = dict(
        vocab_size=153600, dim=6144, n_layers=48, n_heads=64, n_kv_heads=8,
        head_size=128, ffn_dim=2048, n_experts=128, experts_per_token=8,
        norm_topk_prob=True, scoring="sigmoid", routed_scaling=2.5,
        n_shared_experts=1, n_dense_layers=1, dense_ffn_dim=18432,
        sliding_window=128, qk_head_norm=True, post_norm=True,
        rope_layers="window", max_seq_len=262144, rope_theta=1e6,
        norm_eps=1e-5)
    defaults.update(kw)
    if "layer_types" not in defaults:
        defaults["layer_types"] = tuple(
            "global" if i % 4 == 3 else "window"
            for i in range(defaults["n_layers"]))
    return MoEConfig(**defaults)


def qwen3_next_80b_a3b(**kw) -> MoEConfig:
    """Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``: 48 layers in the
    period linear, linear, linear, full; the linear layers 16 key and 32
    value heads of 128 behind a causal conv of 4; the full ones 16 query /
    2 KV heads of 256 with an output gate and RoPE on 64 dimensions; 512
    softmax-routed experts of width 512, 10 a token, and a gated shared
    expert; zero-centred norms."""
    defaults = dict(
        vocab_size=151936, dim=2048, n_layers=48, n_heads=16, n_kv_heads=2,
        head_size=256, ffn_dim=512, n_experts=512, experts_per_token=10,
        norm_topk_prob=True, n_shared_experts=1, shared_expert_gate=True,
        qk_head_norm=True, rotary_dim=64, attn_output_gate=True,
        zero_centered_norm=True, linear_key_heads=16, linear_value_heads=32,
        linear_key_dim=128, linear_value_dim=128, linear_conv_kernel=4,
        aux_loss_weight=0.001, max_seq_len=262144, rope_theta=1e7,
        norm_eps=1e-6)
    defaults.update(kw)
    if "layer_types" not in defaults:
        defaults["layer_types"] = tuple(
            "full" if i % 4 == 3 else "linear"
            for i in range(defaults["n_layers"]))
    return MoEConfig(**defaults)


def mistral_small_4_119b(**kw) -> MoEConfig:
    """mistralai/Mistral-Small-4-119B-2603 ``config.json`` (``model_type:
    mistral4``), the language model: 36 latent-attention layers (32 heads
    of nope 64 + rope 64 = 128 = v; q rank 1024; a cached row of 256 + 64),
    YaRN factor 128 over 8,192 positions on interleaved pairs, 128
    softmax-routed experts of width 2048, 4 a token, one shared expert."""
    defaults = dict(
        vocab_size=131072, dim=4096, n_layers=36, n_heads=32, n_kv_heads=32,
        head_size=128, ffn_dim=2048, n_experts=128, experts_per_token=4,
        norm_topk_prob=True, n_shared_experts=1, q_lora_rank=1024,
        kv_lora_rank=256, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=128, rope_factor=128.0, rope_original_len=8192,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, query_scale_beta=0.1,
        max_seq_len=1048576, rope_theta=10000.0, norm_eps=1e-6)
    defaults.update(kw)
    defaults.setdefault("layer_types", ("latent",) * defaults["n_layers"])
    return MoEConfig(**defaults)


def xing4_0_29b_a4b(**kw) -> MoEConfig:
    """XingChen-AGI/Xing4.0-29B-A4B ``config.json`` (``model_type:
    xing4_0``): 40 latent-attention layers (32 heads of nope 128 + rope 64,
    v 128; q rank 768; a cached row of 512 + 64), YaRN factor 64 over
    4,096 positions, 2 leading dense layers of width 9216, then 64
    sigmoid-routed experts of width 1024, 4 a token (scale 2), one shared
    expert, and a residual stream of 4 copies mixed by hyper-connections
    (20 Sinkhorn iterations). The multi-token-prediction block is not in
    the tree."""
    defaults = dict(
        vocab_size=131072, dim=3584, n_layers=40, n_heads=32, n_kv_heads=32,
        head_size=128, ffn_dim=1024, n_experts=64, experts_per_token=4,
        norm_topk_prob=True, scoring="sigmoid", routed_scaling=2.0,
        n_shared_experts=1, n_dense_layers=2, dense_ffn_dim=9216,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_factor=64.0,
        rope_original_len=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_mscale=1.0, rope_mscale_all_dim=1.0, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp_min=-30.0,
        hc_res_clamp_max=30.0, max_seq_len=262144, rope_theta=10000.0,
        norm_eps=1e-6)
    defaults.update(kw)
    defaults.setdefault("layer_types", ("latent",) * defaults["n_layers"])
    return MoEConfig(**defaults)


def nemotron_3_nano_30b_a3b(**kw) -> MoEConfig:
    """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``
    (``model_type: nemotron_h``): 52 layers, each ONE mixer behind one norm,
    by ``hybrid_override_pattern`` a Mamba-2 mixer (64 heads of 64, state
    128, 8 groups, conv 4), an expert layer (128 sigmoid-routed non-gated
    relu^2 experts of width 1856, 6 a token, scale 2.5, a shared expert of
    3712) or attention (32 query / 2 KV heads of 128, no RoPE)."""
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    defaults = dict(
        vocab_size=131072, dim=2688, n_layers=52, n_heads=32, n_kv_heads=2,
        head_size=128, ffn_dim=1856, n_experts=128, experts_per_token=6,
        norm_topk_prob=True, scoring="sigmoid", routed_scaling=2.5,
        n_shared_experts=2, expert_act="relu2", rope_layers="none",
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        ssm_conv_kernel=4, ssm_chunk=128, max_seq_len=262144,
        rope_theta=10000.0, norm_eps=1e-5)
    defaults.update(kw)
    if "layer_types" not in defaults:
        defaults["layer_types"] = pattern_kinds(
            pattern[:defaults["n_layers"]])
    return MoEConfig(**defaults)


def lfm2_24b_a2b(**kw) -> MoEConfig:
    """LiquidAI/LFM2-24B-A2B ``config.json`` (``model_type: lfm2_moe``): 40
    two-sub-layer blocks whose first sub-layer is, by ``layer_types``, a
    gated short convolution of 3 taps ("conv": served as a "state" layer
    whose state is the conv's two-row tail alone) or attention (32 query / 8
    KV heads of 64, a norm a head on q and k before RoPE at theta 1e6); 2
    leading dense layers of width 11776, then 64 sigmoid-routed experts of
    width 1536, 4 a token chosen by score + bias, gates normed over (their
    sum + 1e-6), no shared expert; tied embeddings."""
    defaults = dict(
        vocab_size=65536, dim=2048, n_layers=40, n_heads=32, n_kv_heads=8,
        head_size=64, ffn_dim=1536, n_experts=64, experts_per_token=4,
        norm_topk_prob=True, scoring="sigmoid", routed_scaling=1.0,
        route_eps=1e-6, n_dense_layers=2, dense_ffn_dim=11776,
        qk_head_norm=True, shortconv_kernel=3, tie_embeddings=True,
        kv_row_heads=2, max_seq_len=128000, rope_theta=1e6, norm_eps=1e-5)
    defaults.update(kw)
    if "layer_types" not in defaults:
        defaults["layer_types"] = tuple(
            "global" if i >= 2 and (i - 2) % 4 == 0 else "state"
            for i in range(defaults["n_layers"]))
    return MoEConfig(**defaults)


def pattern_kinds(pattern: str) -> tuple:
    """``hybrid_override_pattern`` -> layer_types: M a state layer, E an
    expert layer, * attention."""
    return tuple({"M": "state", "E": "experts", "*": "global"}[c]
                 for c in pattern)


def tiny(**kw) -> MoEConfig:
    defaults = dict(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, ffn_dim=128, n_experts=4,
                    experts_per_token=2, max_seq_len=128)
    defaults.update(kw)
    return MoEConfig(**defaults)


# --- params ----------------------------------------------------------------

TRAIN_KINDS = ("linear", "full")


def _serving_only(cfg: MoEConfig) -> bool:
    """Whether ``cfg`` has a shape only the serving forwards run."""
    return bool(set(cfg.layer_types) - set(TRAIN_KINDS)
                or cfg.n_dense_layers or cfg.post_norm
                or cfg.rope_layers != "all" or cfg.scoring != "softmax"
                or cfg.hc_copies or cfg.expert_act != "swiglu")


def _kind_layers(cfg: MoEConfig) -> dict:
    """{kind: how many layers} of a configuration with layer kinds."""
    if len(cfg.layer_types) != cfg.n_layers:
        raise ValueError(f"layer_types must name {cfg.n_layers} layers, "
                         f"got {cfg.layer_types}")
    return {kind: cfg.layer_types.count(kind)
            for kind in TRAIN_KINDS if kind in cfg.layer_types}


# elements of one float32 draw while a leaf is made: 1 GB
_DRAW = 1 << 28
# The random selection bias of a sigmoid router. Of the size of the gap
# between neighbouring scores near the 8th of 128 (0.009), so that
# choosing by s + b and weighting by s differ at most tokens; not larger:
# at 0.05 an expert's bias moved its share of the tokens fourfold, and how
# many experts a decode step reaches (its bytes) then hung on the seed.
ROUTER_BIAS_SCALE = 0.01


@functools.partial(jax.jit, static_argnames=("rows", "scale"),
                   donate_argnums=(0,))
def _fill_rows(buf, key, at, *, rows, scale):
    draw = jax.random.normal(key, (rows, *buf.shape[1:]), jnp.float32)
    return lax.dynamic_update_slice_in_dim(
        buf, (draw * scale).astype(buf.dtype), at, axis=0)


def _normal_stack(key, shape, fan_in, dtype):
    """A leaf of fan-in scaled normals, made ``_DRAW`` elements at a time
    along its first axis into one buffer: the float32 draw stays small
    beside the leaf (a stacked expert leaf is gigabytes, its float32
    twice that)."""
    per_row = 1
    for n in shape[1:]:
        per_row *= n
    rows = max(1, min(shape[0], _DRAW // per_row))
    while shape[0] % rows:
        rows -= 1
    buf = jnp.zeros(shape, dtype)
    for i, k in enumerate(jax.random.split(key, shape[0] // rows)):
        buf = _fill_rows(buf, k, i * rows, rows=rows, scale=fan_in ** -0.5)
    return buf


def _init_serving(rng: jax.Array, cfg: MoEConfig) -> dict:
    """Parameters of a config with serving-only shapes: the leading dense
    layers and the expert layers are two stacks (``dense_layers``,
    ``layers``), the experts only those this device holds."""
    if cfg.single_mixer:
        return _init_single_mixer(rng, cfg)
    dtype = jnp.dtype(cfg.dtype)
    d, f, E, held = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.n_held
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(rng, 32))
    # A post-norm stream is a sum of normed outputs. At norm weights of 1
    # beside an embedding of d ** -0.5 the token would be a hundredth of
    # it and the attention's output most: at random weights that is nearly
    # the mean of the context's values, the same for every position of a
    # window, so a request's tokens would all choose the same experts (the
    # experts a decode step reaches, its bytes, then hang on which requests
    # are live and on the seed). No trained model routes so: the embedding
    # gets unit RMS, the attention's norm a weight of 0.25.
    embed_fan_in, attn_norm = (1, 0.25) if cfg.post_norm else (d, 1.0)
    latent = "latent" in cfg.layer_types
    if latent:
        # A pre-norm stream is the embedding plus every sub-layer's output
        # (of RMS 0.2-0.5 each at fan-in scaled weights): an embedding of
        # d ** -0.5 is a thirtieth of the first layer's attention output,
        # which at random weights is nearly the mean of the context's
        # values, so again a request's tokens would all choose the same
        # experts. Unit RMS keeps the token the largest part of what the
        # router sees; the norms stay 1 (a pre-norm layer norms its INPUT).
        embed_fan_in = 1

    def stack(*shape, fan_in):
        return _normal_stack(next(keys), shape, fan_in, dtype)

    def latent_attn(L):
        """The latent attention's leaves. ``wk_b`` and ``wv_b`` are the
        published ``kv_b_proj`` by head, (heads, nope, latent) and
        (heads, latent, v): the absorbed decode multiplies a head's query
        and output with them as they lie."""
        ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        return {"attn_norm": jnp.ones((L, d), dtype),
                "wq_a": stack(L, d, ql, fan_in=d),
                "q_a_norm": jnp.ones((L, ql), dtype),
                "wq_b": stack(L, ql, h * (nope + rope), fan_in=ql),
                "wkv_a": stack(L, d, kvl + rope, fan_in=d),
                "kv_norm": jnp.ones((L, kvl), dtype),
                "wk_b": stack(L, h, nope, kvl, fan_in=kvl),
                "wv_b": stack(L, h, kvl, vd, fan_in=kvl),
                "wo": stack(L, h * vd, d, fan_in=h * vd),
                "mlp_norm": jnp.ones((L, d), dtype)}

    def mixing(L, salt):
        """The hyper-connections' leaves of a stack of L layers (module
        docstring). The input-dependent part of a coefficient is of the
        size of its bias: x~ has unit RMS, so phi at fan-in n * d gives
        x~ @ phi a unit spread, the scales a are near 1 and b is a unit
        normal; before the sigmoids and the exp each coefficient then
        spreads by about 1.4, half of it from the position: Hres is
        neither the identity nor uniform and differs by position."""
        n = cfg.hc_copies
        if not n:
            return {}
        ks = iter(jax.random.split(jax.random.fold_in(rng, salt), 6))
        out, width = {}, 2 * n + n * n
        for sub in ("attn", "mlp"):
            out[f"hc_{sub}_phi"] = jax.random.normal(
                next(ks), (L, n * d, width), jnp.float32) * (n * d) ** -0.5
            out[f"hc_{sub}_b"] = jax.random.normal(
                next(ks), (L, width), jnp.float32)
            out[f"hc_{sub}_a"] = 1.0 + 0.1 * jax.random.normal(
                next(ks), (L, 3), jnp.float32)
        return out

    def attn(L, norms=True):
        if latent:
            return latent_attn(L)
        out = {"wq": stack(L, d, h * hd, fan_in=d),
               "wk": stack(L, d, kvh * hd, fan_in=d),
               "wv": stack(L, d, kvh * hd, fan_in=d),
               "wo": stack(L, h * hd, d, fan_in=h * hd)}
        if norms:
            out = {"attn_norm": jnp.full((L, d), attn_norm, dtype), **out,
                   "mlp_norm": jnp.ones((L, d), dtype)}
        if cfg.qk_head_norm:
            # normed q and k of weight 1 would score q.k / sqrt(hd) with
            # a spread of sqrt(hd): a softmax that is one-hot, which no
            # trained model has; hd ** -0.25 each gives a unit spread
            # (a short-convolution model's norms are 1, as its
            # configuration states: its scores then spread by 1)
            w = 1.0 if cfg.shortconv_kernel else hd ** -0.25
            out["q_norm"] = jnp.full((L, hd), w, dtype)
            out["k_norm"] = jnp.full((L, hd), w, dtype)
        elif cfg.qk_norm:
            out["q_norm"] = jnp.ones((L, h * hd), dtype)
            out["k_norm"] = jnp.ones((L, kvh * hd), dtype)
        return out

    Ld, Ls = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    if cfg.shortconv_kernel:
        # a pre-norm stream, as the latent configurations' (above)
        embed_fan_in = 1
    params = {"embed": stack(cfg.vocab_size, d, fan_in=embed_fan_in),
              "final_norm": jnp.ones((d,), dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = stack(d, cfg.vocab_size, fan_in=d)
    if cfg.shortconv_kernel:
        # A layer's FIRST sub-layer is attention or a gated short
        # convolution: the operators' leaves are a stack a kind
        # (``llm/model.py STACKS``), indexed by the layer's place among its
        # kind, the leading dense layers' included; ``dense_layers`` and
        # ``layers`` keep what every layer has, the two norms and the
        # feed-forward. The taps uniform at their fan-in, as a Mamba-2
        # conv's (``_init_single_mixer``).
        K, count = cfg.shortconv_kernel, cfg.layer_types.count
        if set(cfg.layer_types) - {"state", "global"} \
                or len(cfg.layer_types) != cfg.n_layers:
            raise ValueError(
                f"layer_types of a short-convolution model name "
                f"{cfg.n_layers} layers 'state' or 'global', got "
                f"{cfg.layer_types}")
        if count("state"):
            params["state_layers"] = {
                "w_in": stack(count("state"), d, 3 * d, fan_in=d),
                "conv": jax.random.uniform(
                    next(keys), (count("state"), d, K), jnp.float32,
                    -K ** -0.5, K ** -0.5).astype(dtype),
                "w_out": stack(count("state"), d, d, fan_in=d)}
        if count("global"):
            params["attn_layers"] = attn(count("global"), norms=False)

        def own(L):
            return {"attn_norm": jnp.ones((L, d), dtype),
                    "mlp_norm": jnp.ones((L, d), dtype)}
    else:
        own = attn      # the first sub-layer's leaves lie in the layer's stack
    if Ld:
        fd = cfg.dense_ffn_dim
        params["dense_layers"] = {
            **own(Ld), **mixing(Ld, 3), "w_gate": stack(Ld, d, fd, fan_in=d),
            "w_up": stack(Ld, d, fd, fan_in=d),
            "w_down": stack(Ld, fd, d, fan_in=fd)}
    layers = {
        **own(Ls), **mixing(Ls, 4),
        "router": jax.random.normal(next(keys), (Ls, d, E), jnp.float32)
        * (d ** -0.5),
        "w_gate": stack(Ls, held, d, f, fan_in=d),
        "w_up": stack(Ls, held, d, f, fan_in=d),
        "w_down": stack(Ls, held, f, d, fan_in=f)}
    if cfg.scoring == "sigmoid":
        layers["router_bias"] = ROUTER_BIAS_SCALE * jax.random.normal(
            next(keys), (Ls, E), jnp.float32)
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        layers.update(shared_gate=stack(Ls, d, fs, fan_in=d),
                      shared_up=stack(Ls, d, fs, fan_in=d),
                      shared_down=stack(Ls, fs, d, fan_in=fs))
    params["layers"] = layers
    return params


LANES = 128     # values a lane tile of the device holds


@functools.partial(jax.jit, static_argnames=("axis", "start"),
                   donate_argnums=(0,))
def _zero_tail(buf, *, axis, start):
    """``buf`` with its entries from ``start`` on along ``axis`` zeroed."""
    index = (slice(None),) * axis + (slice(start, None),)
    return buf.at[index].set(0)


def _init_single_mixer(rng: jax.Array, cfg: MoEConfig) -> dict:
    """Parameters of a config whose layers are each one mixer
    (``single_mixer``): a stack a kind, the experts only those this device
    holds, an expert by its ``expert_act``. The embedding has unit RMS (a
    pre-norm stream: ``_init_serving``'s reason). ``config.json`` has three
    keys of the state layers' init and no more; it is Mamba-2's: ``dt_bias``
    the inverse softplus of a step drawn log-uniform in [1e-3, 1e-1] and
    floored at 1e-4, ``A_log`` = log U[1, 16], ``D`` = 1, the depthwise conv
    and its bias uniform at the fan-in of its taps: random weights then
    decay as a trained model's do, some heads within a few tokens, some over
    a thousand."""
    if set(cfg.layer_types) - {"state", "experts", "global"} \
            or len(cfg.layer_types) != cfg.n_layers:
        raise ValueError(
            f"layer_types must name {cfg.n_layers} layers 'state', "
            f"'experts' or 'global', got {cfg.layer_types}")
    if cfg.expert_act not in ("swiglu", "relu2"):
        raise ValueError(f"unknown expert_act: {cfg.expert_act!r}")
    dtype = jnp.dtype(cfg.dtype)
    d, f, E, held = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.n_held
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(rng, 32))

    def stack(*shape, fan_in):
        return _normal_stack(next(keys), shape, fan_in, dtype)

    def uniform(*shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    count = cfg.layer_types.count
    params = {"embed": stack(cfg.vocab_size, d, fan_in=1),
              "final_norm": jnp.ones((d,), dtype),
              "lm_head": stack(d, cfg.vocab_size, fan_in=d)}
    L = count("state")
    if L:
        (inner, conv), H, K = cfg.ssm_widths, cfg.ssm_heads, \
            cfg.ssm_conv_kernel
        dt = jnp.maximum(jnp.exp(uniform(L, H, lo=jnp.log(1e-3),
                                         hi=jnp.log(1e-1))), 1e-4)
        params["state_layers"] = {
            "norm": jnp.ones((L, d), dtype),
            # the in-projection's columns [z | xBC | dt] as three leaves:
            # a leaf 10,304 wide (no whole number of 128-lane tiles) lies
            # transposed on the device, and every program that multiplies
            # with it would first turn all of it back
            "w_z": stack(L, d, inner, fan_in=d),
            "w_xbc": stack(L, d, conv, fan_in=d),
            "w_dt": stack(L, d, H, fan_in=d),
            "conv": uniform(L, conv, K, lo=-K ** -0.5,
                            hi=K ** -0.5).astype(dtype),
            "conv_bias": uniform(L, conv, lo=-K ** -0.5,
                                 hi=K ** -0.5).astype(dtype),
            # float32, as the router: they set decays that compound
            "A_log": jnp.log(uniform(L, H, lo=1.0, hi=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((L, H), jnp.float32),
            "ssm_norm": jnp.ones((L, inner), dtype),
            "w_out": stack(L, inner, d, fan_in=inner)}
    L = count("experts")
    if L:
        layers = {"norm": jnp.ones((L, d), dtype),
                  "router": jax.random.normal(next(keys), (L, d, E),
                                              jnp.float32) * (d ** -0.5)}
        if cfg.scoring == "sigmoid":
            layers["router_bias"] = ROUTER_BIAS_SCALE * jax.random.normal(
                next(keys), (L, E), jnp.float32)
        fs = cfg.n_shared_experts * f
        # an expert's width as it is STORED: whole 128-lane tiles, the
        # columns (of w_gate / w_up; rows of w_down) past ``ffn_dim`` zeros,
        # which add nothing to any product (relu(0) ** 2 = silu(0) = 0).
        # The device lays a narrower minor dimension out in whole tiles
        # anyway, or transposed (1,856 is 14.5 tiles), and the grouped
        # matmul's program would then turn the whole stack back at its
        # entry: 1.9 GB a decode block
        fp = -(-f // LANES) * LANES

        def experts(*shape, fan_in, axis):
            return _zero_tail(stack(*shape, fan_in=fan_in), axis=axis,
                              start=f) if fp != f else stack(
                                  *shape, fan_in=fan_in)
        if cfg.expert_act == "swiglu":
            layers["w_gate"] = experts(L, held, d, fp, fan_in=d, axis=3)
            if fs:
                layers["shared_gate"] = stack(L, d, fs, fan_in=d)
        layers.update(w_up=experts(L, held, d, fp, fan_in=d, axis=3),
                      w_down=experts(L, held, fp, d, fan_in=f, axis=2))
        if fs:
            layers.update(shared_up=stack(L, d, fs, fan_in=d),
                          shared_down=stack(L, fs, d, fan_in=fs))
        params["expert_layers"] = layers
    L = count("global")
    if L:
        params["attn_layers"] = {
            "norm": jnp.ones((L, d), dtype),
            "wq": stack(L, d, h * hd, fan_in=d),
            "wk": stack(L, d, kvh * hd, fan_in=d),
            "wv": stack(L, d, kvh * hd, fan_in=d),
            "wo": stack(L, h * hd, d, fan_in=h * hd)}
    return params


def _full_leaves(cfg: MoEConfig) -> tuple:
    """Of one full-attention mixer: ({projection: (shape, fan-in)}, {norm
    weight: shape})."""
    d, h, kvh, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    weights = {
        "wq": ((d, (2 if cfg.attn_output_gate else 1) * h * hd), d),
        "wk": ((d, kvh * hd), d), "wv": ((d, kvh * hd), d),
        "wo": ((h * hd, d), h * hd)}
    norms = {}
    if cfg.qk_head_norm:
        norms = {"q_norm": (hd,), "k_norm": (hd,)}
    elif cfg.qk_norm:
        norms = {"q_norm": (h * hd,), "k_norm": (kvh * hd,)}
    return weights, norms


def _init_linear(key, cfg: MoEConfig, L: int, norm_init) -> dict:
    """The stack of ``L`` Gated DeltaNet mixers. ``config.json`` has no key
    for their init; it is the layer's in flash-linear-attention: ``A_log``
    = log U(0, 16), ``dt_bias`` the inverse softplus of a log-uniform step
    in [1e-3, 1e-1] (so a head's state outlives a chunk at some heads and
    fades within one at others), the depthwise conv uniform at its fan-in
    of ``linear_conv_kernel``. ``w_qkvz``'s columns are [q | k | v | z],
    ``w_ba``'s [b | a] and ``conv``'s channels [q | k | v], each kind's
    heads in order: the layout the mixer multiplies with (a checkpoint
    that groups them by key head is permuted once, on load)."""
    d, (kw, vw), hv = cfg.dim, cfg.linear_widths, cfg.linear_value_heads
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[3], (L, hv), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    bound = cfg.linear_conv_kernel ** -0.5
    return {
        "w_qkvz": norm_init(ks[0], (L, d, 2 * kw + 2 * vw), d),
        "w_ba": norm_init(ks[1], (L, d, 2 * hv), d),
        "conv": jax.random.uniform(
            ks[2], (L, 2 * kw + vw, cfg.linear_conv_kernel), jnp.float32,
            -bound, bound).astype(jnp.dtype(cfg.dtype)),
        # float32, as the router: they set decays that compound over a row
        "A_log": jnp.log(jax.random.uniform(ks[4], (L, hv), jnp.float32,
                                            1e-4, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "gdn_norm": jnp.ones((L, cfg.linear_value_dim),
                             jnp.dtype(cfg.dtype)),
        "w_out": norm_init(ks[5], (L, vw, d), vw)}


def init_params(rng: jax.Array, cfg: MoEConfig) -> dict:
    if _serving_only(cfg):
        return _init_serving(rng, cfg)
    dtype = jnp.dtype(cfg.dtype)
    d, f, E, held = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.n_held
    L = cfg.n_layers
    ks = jax.random.split(rng, 10)
    one = 0.0 if cfg.zero_centered_norm else 1.0

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def full(n):
        weights, norms = _full_leaves(cfg)
        out = {name: norm_init(ks[1 + i], (n, *shape), fan)
               for i, (name, (shape, fan)) in enumerate(weights.items())}
        out.update({name: jnp.full((n, *shape), one, dtype)
                    for name, shape in norms.items()})
        return out

    layers = {
        "attn_norm": jnp.full((L, d), one, dtype),
        "mlp_norm": jnp.full((L, d), one, dtype),
        # router in f32: tiny, and top-k tie-breaks are dtype-sensitive
        "router": (jax.random.normal(ks[5], (L, d, E), jnp.float32)
                   * (d ** -0.5)),
        "w_gate": norm_init(ks[6], (L, held, d, f), d),
        "w_up": norm_init(ks[7], (L, held, d, f), d),
        "w_down": norm_init(ks[8], (L, held, f, d), f),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(rng, 1), 4)
        layers.update(shared_gate=norm_init(k1, (L, d, fs), d),
                      shared_up=norm_init(k2, (L, d, fs), d),
                      shared_down=norm_init(k3, (L, fs, d), fs))
        if cfg.shared_expert_gate:
            layers["shared_expert_gate"] = norm_init(k4, (L, d, 1), d)
    params = {
        "embed": norm_init(ks[0], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.full((d,), one, dtype),
        "lm_head": norm_init(ks[9], (d, cfg.vocab_size), d),
    }
    if not cfg.layer_types:
        layers.update(full(L))
        return params
    kinds = _kind_layers(cfg)
    if "full" in kinds:
        params["full_layers"] = full(kinds["full"])
    if "linear" in kinds:
        params["linear_layers"] = _init_linear(
            jax.random.fold_in(rng, 2), cfg, kinds["linear"], norm_init)
    return params


def param_shardings(cfg: MoEConfig, axes: MeshAxes = MeshAxes()) -> dict:
    t, fs, ep = axes.tensor, axes.fsdp, axes.expert
    full = {"wq": P(None, fs, t), "wk": P(None, fs, t), "wv": P(None, fs, t),
            "wo": P(None, t, fs)}
    if cfg.qk_norm or cfg.qk_head_norm:
        full.update(q_norm=P(None, None), k_norm=P(None, None))
    layers = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "router": P(None, fs, None),
        "w_gate": P(None, ep, fs, t),
        "w_up": P(None, ep, fs, t),
        "w_down": P(None, ep, t, fs),
    }
    if cfg.n_shared_experts:
        layers.update(shared_gate=P(None, fs, t), shared_up=P(None, fs, t),
                      shared_down=P(None, t, fs))
        if cfg.shared_expert_gate:
            layers["shared_expert_gate"] = P(None, fs, None)
    out = {
        "embed": P(t, fs),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(fs, t),
    }
    if not cfg.layer_types:
        layers.update(full)
        return out
    kinds = _kind_layers(cfg)
    if "full" in kinds:
        out["full_layers"] = full
    if "linear" in kinds:
        # a linear layer's heads are not cut by the tensor axis (q, k, v
        # and z lie side by side in one projection): fsdp only
        out["linear_layers"] = {
            "w_qkvz": P(None, fs, None), "w_ba": P(None, fs, None),
            "conv": P(None, None, None), "A_log": P(None, None),
            "dt_bias": P(None, None), "gdn_norm": P(None, None),
            "w_out": P(None, None, fs)}
    return out


# --- the expert layer ------------------------------------------------------

def _rows(x, index):
    """``x[index]`` along axis 0; every index is in range by construction
    (a permutation, or one divided by k), so no bounds handling is built."""
    return x.at[index].get(mode="promise_in_bounds")


def _token_of(order, tokens: int):
    """The token (row of y) of each sorted assignment: ``order // k``."""
    return order // (order.shape[0] // tokens)


def _token_sums(g, inverse, tokens: int):
    """The backward of reading y's rows in sorted order: the cotangent
    rows ``g`` (T*k, d) brought back by the inverse permutation and summed
    over each token's k, in float32 -> (T, d)."""
    dy = _rows(g, inverse).reshape(tokens, -1, g.shape[1])
    return dy.astype(jnp.float32).sum(1).astype(g.dtype)


@jax.custom_vjp
def _dispatch(y, order, inverse):
    """Rows of ``y`` (T, d) in sorted-assignment order (T*k, d): row ``p``
    is the token of assignment ``order[p]``."""
    return _rows(y, _token_of(order, y.shape[0]))


def _dispatch_fwd(y, order, inverse):
    return _dispatch(y, order, inverse), (inverse, y.shape[0])


def _dispatch_bwd(res, g):
    inverse, tokens = res
    return _token_sums(g, inverse, tokens), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gate_up(y, w_gate, w_up, order, inverse, group_sizes, interpret):
    """``(x @ w_gate[e], x @ w_up[e])`` for the rows ``x = _dispatch(y)``
    of every expert ``e``, with x never written: one kernel call fetches
    each row tile of x from y by id and multiplies it by both weights
    (``grouped_matmul.gmm_rows``). The backward reads y the same way for
    the weights' gradients; the rows' gradient comes back in sorted order
    and goes home as ``_dispatch``'s does."""
    return grouped_matmul.gmm_rows(
        y, _token_of(order, y.shape[0]), (w_gate, w_up), group_sizes,
        interpret=interpret)


def _gate_up_fwd(y, w_gate, w_up, order, inverse, group_sizes, interpret):
    return (_gate_up(y, w_gate, w_up, order, inverse, group_sizes, interpret),
            (y, w_gate, w_up, order, inverse, group_sizes))


def _gate_up_bwd(interpret, res, douts):
    y, w_gate, w_up, order, inverse, group_sizes = res
    d_x = grouped_matmul.gmm_t(tuple(douts), (w_gate, w_up), group_sizes,
                               interpret)
    d_gate, d_up = grouped_matmul.tgmm(
        y, tuple(douts), group_sizes, rows=_token_of(order, y.shape[0]),
        interpret=interpret)
    # the weights' gradients before anything reads the rows': left to
    # itself the scheduler puts them after the attention's backward and
    # recomputes both cotangents for them
    d_x, d_gate, d_up = lax.optimization_barrier((d_x, d_gate, d_up))
    return (_token_sums(d_x, inverse, y.shape[0]), d_gate, d_up, None, None,
            None)


_gate_up.defvjp(_gate_up_fwd, _gate_up_bwd)


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
    # ONE gather of g's rows, in sorted order, serves both gradients: a
    # row's gate gradient is its dot with the row of ``rows`` beside it
    # (read in the pass that scales it), and only those T*k numbers go
    # back to token order
    g_rows = _rows(g, order // k).astype(jnp.float32)
    d_rows = g_rows * _rows(gates.reshape(-1), order)[:, None]
    dots = jnp.sum(rows.astype(jnp.float32) * g_rows, axis=1)
    d_gates = _rows(dots, inverse).reshape(gates.shape)
    return d_rows.astype(rows.dtype), d_gates, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gmm_impl(cfg: MoEConfig) -> str:
    impl = cfg.gmm_impl
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ragged_dot"
    if impl not in ("ragged_dot", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown gmm_impl: {cfg.gmm_impl!r}")
    return impl


def _grouped(x, w, group_sizes, cfg: MoEConfig, layer=None):
    """``w`` (E, k, n), or with ``layer`` the whole stack (L, E, k, n) of
    which the kernel reads layer ``layer`` in place (a slice of the stack
    handed to a custom call would be copied out first)."""
    impl = _gmm_impl(cfg)
    if impl == "ragged_dot":
        if layer is not None:
            w = lax.dynamic_index_in_dim(w, layer, keepdims=False)
        return lax.ragged_dot(x, w, group_sizes)
    if layer is not None:
        return grouped_matmul.gmm_stacked(x, w, group_sizes, layer,
                                          impl == "pallas_interpret")
    return grouped_matmul.gmm(x, w, group_sizes, impl == "pallas_interpret")


def _route(y, router, bias, cfg: MoEConfig):
    """(gates (T, k) float32, experts (T, k) int32, scores (T, E)): the
    module docstring's two scorings."""
    k = cfg.experts_per_token
    logits = jnp.dot(y.astype(jnp.float32), router,
                     precision=lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)                           # (T, E)
        _, experts = lax.top_k(probs + bias, k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    elif cfg.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
        gates, experts = lax.top_k(probs, k)                     # (T, k)
    else:
        raise ValueError(f"unknown scoring: {cfg.scoring!r}")
    if cfg.norm_topk_prob:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + cfg.route_eps if cfg.route_eps else total)
    if cfg.routed_scaling != 1.0:
        gates = gates * cfg.routed_scaling
    return gates, experts, probs


def _inverse(order):
    return jnp.argsort(order).astype(jnp.int32)


def _sort_by_expert(experts, first_expert, local: int):
    """(mine, order, inverse, group_sizes) of the (T, k) assignments for a
    device that holds experts ``first_expert ... + local``: the others'
    sort last, into rows no group covers."""
    mine = experts.reshape(-1) - first_expert
    mine = jnp.where((mine >= 0) & (mine < local), mine, local)
    order = jnp.argsort(mine, stable=True).astype(jnp.int32)
    inverse = _inverse(order)
    # counts by compare-and-sum: a scatter-add into E bins serialises
    group_sizes = jnp.sum(mine[:, None] == jnp.arange(local), axis=0,
                          dtype=jnp.int32)
    return mine, order, inverse, group_sizes


def _gated_sum(y, gates, order, inverse, group_sizes, w, cfg: MoEConfig,
               layer=None):
    """The routed rows of y through the experts ``w`` holds
    (``w["w_gate" | "w_up" | "w_down"]``: a layer's, or with ``layer`` the
    stacks; no ``w_gate`` under ``expert_act`` "relu2", two grouped matmuls),
    summed per token by ``gates``."""
    if cfg.expert_act == "relu2":
        with jax.named_scope("moe.experts"):
            up = _grouped(_dispatch(y, order, inverse), w["w_up"],
                          group_sizes, cfg, layer)
            rows = _grouped(jnp.square(jax.nn.relu(up)), w["w_down"],
                            group_sizes, cfg, layer)
        with jax.named_scope("moe.combine"):
            return _combine(rows, gates, order, inverse)
    with jax.named_scope("moe.experts"):
        impl = _gmm_impl(cfg)
        if layer is None and impl != "ragged_dot":
            # a layer's own weights (the train step): the differentiable
            # kernels, which read y's rows by id. The stack (serving, a
            # few rows an expert) keeps the gathered copy: 3 MB a step.
            gate, up = _gate_up(y, w["w_gate"], w["w_up"], order, inverse,
                                group_sizes, impl == "pallas_interpret")
        else:
            x = _dispatch(y, order, inverse)                     # (T*k, d)
            gate, up = (_grouped(x, w[name], group_sizes, cfg, layer)
                        for name in ("w_gate", "w_up"))
        rows = _grouped(jax.nn.silu(gate) * up, w["w_down"], group_sizes,
                        cfg, layer)
    with jax.named_scope("moe.combine"):
        return _combine(rows, gates, order, inverse)


# A device that holds FEWER experts than the router scores (a held slice,
# an expert mesh axis) owns the first ``sum(group_sizes)`` entries of
# ``order``; the rest sort into the tail no group covers. The train step
# then works on a list of ``_local_bound`` assignments ("work": the head of
# ``order``) where that covers the device's own: y's rows by id, the three
# grouped matmuls and the elementwise passes on (R, .), and the two ways
# between tokens and rows as sums of R rows INTO their tokens (a
# scatter-add, as the embedding's backward is), not gathers of T*k.

def _local_bound(assignments: int, held: int, experts: int) -> int:
    """Rows of the work list of a device that holds ``held`` of ``experts``:
    twice its expected share of the assignments, in whole row tiles. From
    the shapes alone; a routing that sends it more takes the full path."""
    tile = grouped_matmul.ROW_TILE
    return -(-2 * assignments * held // (experts * tile)) * tile


def _work_rows(order, w, cfg: MoEConfig) -> int:
    return _local_bound(order.shape[0], w["w_gate"].shape[0], cfg.n_experts)


def _sum_into_tokens(rows, token, tokens: int):
    """``rows`` (R, d) summed into the tokens ``token`` (R,) names, in
    float32 -> (T, d) float32."""
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[token].add(
        rows.astype(jnp.float32), mode="promise_in_bounds")


def _grouped_grads(x, w, group_sizes, dout, cfg: MoEConfig):
    """(d_x, d_w) of ``_grouped(x, w, group_sizes, cfg)`` for its
    cotangent ``dout``."""
    impl = _gmm_impl(cfg)
    if impl == "ragged_dot":
        return jax.vjp(lambda x, w: lax.ragged_dot(x, w, group_sizes), x,
                       w)[1](dout)
    interpret = impl == "pallas_interpret"
    return (grouped_matmul.gmm_t(dout, w, group_sizes, interpret),
            grouped_matmul.tgmm(x, dout, group_sizes, interpret=interpret))


def _local_fwd(y, gates, order, group_sizes, w, cfg: MoEConfig):
    """``_gated_sum`` over the work list, the first ``_local_bound``
    entries of ``order``, which hold every assignment of the experts ``w``
    holds (a layer's own weights) -> (out (T, d), what ``_local_bwd`` reads
    again: gate, up (R, f) and the experts' rows (R, d), zeros past the
    last group). The combine is the rows, weighted by their gates, summed
    into their tokens in float32."""
    tokens, k = gates.shape
    work = order[:_work_rows(order, w, cfg)]
    token = work // k
    with jax.named_scope("moe.experts"):
        impl = _gmm_impl(cfg)
        if impl == "ragged_dot":
            x = _rows(y, token)                                  # (R, d)
            gate, up = (_grouped(x, w[name], group_sizes, cfg)
                        for name in ("w_gate", "w_up"))
        else:
            gate, up = grouped_matmul.gmm_rows(
                y, token, (w["w_gate"], w["w_up"]), group_sizes,
                interpret=impl == "pallas_interpret")
        rows = _grouped(jax.nn.silu(gate) * up, w["w_down"], group_sizes,
                        cfg)
    with jax.named_scope("moe.combine"):
        scaled = rows.astype(jnp.float32) \
            * _rows(gates.reshape(-1), work)[:, None]
        out = _sum_into_tokens(scaled, token, tokens).astype(rows.dtype)
    return out, (gate, up, rows)


def _local_bwd(y, gates, order, group_sizes, w, kept, g, cfg: MoEConfig):
    """(d_y, d_gates, d_w) of ``_local_fwd``'s out for its cotangent ``g``
    (T, d): one gather of g's R rows serves the rows' and the gates'
    gradients (``_combine_bwd``), R gate gradients return to their (T, k)
    places (the other assignments' rows were zero, so are their gates'
    gradients), and the rows' gradient goes home as a sum into tokens (the
    list's rows past the last group come back zero from ``gmm_t``)."""
    gate, up, rows = kept
    tokens, k = gates.shape
    work = order[:rows.shape[0]]
    token = work // k
    with jax.named_scope("moe.combine"):
        g_rows = _rows(g, token).astype(jnp.float32)
        d_rows = (g_rows * _rows(gates.reshape(-1), work)[:, None]).astype(
            rows.dtype)
        dots = jnp.sum(rows.astype(jnp.float32) * g_rows, axis=1)
        d_gates = jnp.zeros(gates.size, jnp.float32).at[work].set(
            dots, mode="promise_in_bounds", unique_indices=True)
    with jax.named_scope("moe.experts"):
        hidden, hidden_vjp = jax.vjp(lambda a, b: jax.nn.silu(a) * b, gate,
                                     up)
        d_hidden, d_down = _grouped_grads(hidden, w["w_down"], group_sizes,
                                          d_rows, cfg)
        douts = hidden_vjp(d_hidden)
        impl = _gmm_impl(cfg)
        if impl == "ragged_dot":
            x = _rows(y, token)
            (d_x, d_gate), (d_xu, d_up) = (
                _grouped_grads(x, w[name], group_sizes, dout, cfg)
                for name, dout in zip(("w_gate", "w_up"), douts))
            d_x = d_x + d_xu
        else:
            interpret = impl == "pallas_interpret"
            d_x = grouped_matmul.gmm_t(douts, (w["w_gate"], w["w_up"]),
                                       group_sizes, interpret)
            d_gate, d_up = grouped_matmul.tgmm(
                y, douts, group_sizes, rows=token, interpret=interpret)
            # as ``_gate_up_bwd``: the weights' gradients before anything
            # reads the rows'
            d_x, d_gate, d_up = lax.optimization_barrier((d_x, d_gate, d_up))
        d_y = _sum_into_tokens(d_x, token, tokens).astype(y.dtype)
    return (d_y, d_gates.reshape(gates.shape),
            {"w_gate": d_gate, "w_up": d_up, "w_down": d_down})


def _full_fwd(y, gates, order, group_sizes, w, cfg: MoEConfig):
    """``_gated_sum`` over every assignment, as the other side of the
    branch: only it sorts the inverse permutation."""
    return _gated_sum(y, gates, order, _inverse(order), group_sizes, w, cfg)


def _full_bwd(y, gates, order, group_sizes, w, g, cfg: MoEConfig):
    """(d_y, d_gates, d_w) of ``_full_fwd``, which kept nothing but its
    inputs: computed anew, then differentiated."""
    return jax.vjp(lambda y, gates, w: _full_fwd(
        y, gates, order, group_sizes, w, cfg), y, gates, w)[1](g)


@functools.lru_cache(maxsize=None)
def _once(side, cfg: MoEConfig):
    """``side(*arrays, cfg=cfg)`` as ONE jitted callable a (side, cfg): a
    program that calls it again on the same shapes (the forward, the
    remat's forward and the backward of every layer of a scan turn trace
    both sides of the branch) traces and lowers its kernels once, not
    once a call: seconds of a warm start."""
    return jax.jit(functools.partial(side, cfg=cfg))


def _fits(order, group_sizes, w, cfg: MoEConfig):
    """Whether the device's own assignments fit the work list: a device
    bool."""
    return jnp.sum(group_sizes) <= _work_rows(order, w, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _bounded_sum(y, gates, order, group_sizes, w, cfg: MoEConfig):
    """``_gated_sum`` by the work list where the device's own assignments
    fit it (``_local_fwd``) and over every assignment where a routing sent
    it more (``_full_fwd``), chosen on the device (``lax.cond``): the same
    sum up to the order of float32 additions inside a token's, nothing
    dropped, no capacity. One ``custom_vjp`` with the branch inside its
    forward and inside its backward, so each is one conditional that
    passes nothing through (differentiated by JAX, a ``lax.cond`` hands
    every residual of either side, the weights among them, out of the
    branch and in again). The full side keeps nothing but its inputs."""
    return _bounded_sum_fwd(y, gates, order, group_sizes, w, cfg)[0]


def _bounded_sum_fwd(y, gates, order, group_sizes, w, cfg):
    local = _once(_local_fwd, cfg)

    def full(*operands):
        kept = jax.eval_shape(local, *operands)[1]
        return (_once(_full_fwd, cfg)(*operands),
                jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), kept))

    out, kept = lax.cond(_fits(order, group_sizes, w, cfg), local, full, y,
                         gates, order, group_sizes, w)
    return out, (y, gates, order, group_sizes, w, kept)


def _bounded_sum_bwd(cfg, res, g):
    y, gates, order, group_sizes, w, kept = res
    d_y, d_gates, d_w = lax.cond(
        _fits(order, group_sizes, w, cfg), _once(_local_bwd, cfg),
        lambda y, gates, order, sizes, w, kept, g: _once(_full_bwd, cfg)(
            y, gates, order, sizes, w, g),
        y, gates, order, group_sizes, w, kept, g)
    return d_y, d_gates, None, None, d_w


_bounded_sum.defvjp(_bounded_sum_fwd, _bounded_sum_bwd)


def _held_sum(y, gates, order, inverse, group_sizes, w, cfg: MoEConfig):
    """The train step's ``_gated_sum`` -> (out (T, d), whether it took the
    work list: a float32 1 or 0; None where the step has no such path: all
    experts held, or so few rows that the bound covers them all)."""
    if w["w_gate"].shape[0] == cfg.n_experts \
            or _work_rows(order, w, cfg) >= order.shape[0]:
        return _gated_sum(y, gates, order, inverse, group_sizes, w, cfg), None
    return (_bounded_sum(y, gates, order, group_sizes, w, cfg),
            _fits(order, group_sizes, w, cfg).astype(jnp.float32))


def _experts(y, router, w_gate, w_up, w_down, cfg: MoEConfig,
             first_expert=0):
    """y (T, d) -> (out (T, d) from the experts ``first_expert ...`` that
    ``w_*`` hold, assignments per expert (E,), summed router scores
    (E,): the last two over all experts; ``_held_sum``'s flag)."""
    E = cfg.n_experts
    with jax.named_scope("moe.route"):
        gates, experts, probs = _route(y, router, None, cfg)
        _, order, inverse, group_sizes = _sort_by_expert(
            experts, first_expert, w_gate.shape[0])
        counts = jnp.sum(experts.reshape(-1, 1) == jnp.arange(E), axis=0,
                         dtype=jnp.float32)
    out, fits = _held_sum(y, gates, order, inverse, group_sizes,
                          {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                          cfg)
    return out, counts, jnp.sum(probs, axis=0), fits


def _shared(y, lp):
    """The shared expert: one SwiGLU every token goes through (without
    ``shared_gate``, ``expert_act`` "relu2": down(relu(up y) ** 2)), weighed
    by sigmoid(w . y) a token where the layer has that gate."""
    with jax.named_scope("moe.shared"):
        if "shared_gate" not in lp:         # expert_act "relu2": no gate
            return jnp.square(jax.nn.relu(y @ lp["shared_up"])) \
                @ lp["shared_down"]
        out = (jax.nn.silu(y @ lp["shared_gate"]) * (y @ lp["shared_up"])) \
            @ lp["shared_down"]
        if "shared_expert_gate" in lp:
            out = jax.nn.sigmoid(y @ lp["shared_expert_gate"]) * out
        return out


def serve_block(y, lp, cfg: MoEConfig, *, stack=None, row=None,
                active=None, choice=False):
    """The expert layer of the serving forwards. y (T, d) normed rows ->
    (this device's part of the layer (T, d): its held experts' gated sum
    plus the shared expert, and routing counts or None; with ``choice``
    also the experts each row chose, (T, k) int32, all of them, held here
    or not).

    ``lp`` is the layer's parameters; with ``stack`` and ``row`` the
    grouped matmuls read the layer's experts in place in the stacked
    leaves. ``active`` (T,) marks the rows that are live requests (a
    decode step computes every slot): the other rows' assignments sort
    into the tail no group covers, so the grouped matmuls read only the
    experts a live row reached (such a row's part of the routed sum is
    zeros), and the counts come back, device scalars ``{"routed",
    "local", "experts_hit"}``: assignments of live rows, those of them on
    held experts, and the held experts that a live row reached (whose
    weights the step read): the groups the kernels get."""
    src, layer = (stack, row) if stack is not None else (lp, None)
    with jax.named_scope("moe.route"):
        gates, chosen, _ = _route(y, lp["router"], lp.get("router_bias"),
                                  cfg)
        experts = chosen
        if active is not None:
            experts = jnp.where(active[:, None], experts, -1)   # nobody's
        mine, order, inverse, group_sizes = _sort_by_expert(
            experts, cfg.first_expert, cfg.n_held)
        stats = None
        if active is not None:
            stats = {
                "routed": jnp.sum(active, dtype=jnp.int32)
                * cfg.experts_per_token,
                "local": jnp.sum(mine < cfg.n_held, dtype=jnp.int32),
                "experts_hit": jnp.sum(group_sizes > 0, dtype=jnp.int32)}
    out = _gated_sum(y, gates, order, inverse, group_sizes, src, cfg, layer)
    if cfg.n_shared_experts:
        out = out + _shared(y, lp)
    return (out, stats, chosen) if choice else (out, stats)


def _moe_block(y, lp, cfg: MoEConfig, mesh: Optional[Mesh],
               axes: MeshAxes):
    """y (b, s, d) normed hidden -> (the routed experts' output (b, s, d):
    with ``experts_held`` the held experts' partial sum, what an
    expert-parallel deployment adds up across its devices; the layer's
    statistics: assignments per expert and token (E,), mean router
    probability (E,) and, where the step has a work-list path
    (``_experts``), the share of the devices that took it)."""
    b, s, d = y.shape
    if cfg.experts_held and mesh is not None \
            and mesh.shape.get(axes.expert, 1) > 1:
        raise ValueError("a held slice of the experts and an expert mesh "
                         "axis are two ways to say which experts are here")
    weights = (lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])
    ep, t = axes.expert, axes.tensor

    def local(y, router, w_gate, w_up, w_down):
        bl, sl, _ = y.shape
        first = cfg.first_expert + lax.axis_index(ep) * w_gate.shape[0]
        out, counts, probs, fits = _experts(
            y.reshape(bl * sl, d), router, w_gate, w_up, w_down, cfg, first)
        tok_axes = (*axes.batch, axes.context)
        return (lax.psum(out, (ep, t)).reshape(bl, sl, d),
                lax.psum(counts, tok_axes), lax.psum(probs, tok_axes),
                () if fits is None else (lax.pmean(fits, (ep, *tok_axes)),))

    if mesh is None:
        out, counts, probs, fits = _experts(y.reshape(b * s, d), *weights,
                                            cfg, cfg.first_expert)
        out, fits = out.reshape(b, s, d), () if fits is None else (fits,)
    else:
        # check_vma=False: pallas_call outputs carry no vma under shard_map
        out, counts, probs, fits = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes.batch, axes.context, None), P(None, None),
                      P(ep, None, t), P(ep, None, t), P(ep, t, None)),
            out_specs=(P(axes.batch, axes.context, None), P(None), P(None),
                       P()),
            check_vma=False)(y, *weights)
    return out, (counts / (b * s), probs / (b * s), *fits)


# --- forward ---------------------------------------------------------------

def _norm(x, w, cfg: MoEConfig):
    """RMSNorm; zero-centred (``1 + w``, in float32) where the config says."""
    if not cfg.zero_centered_norm:
        return _rmsnorm(x, w, cfg.norm_eps)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + cfg.norm_eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope_part(x, cos, sin):
    """RoPE on the first ``2 * cos.shape[-1]`` dimensions of a head."""
    rd = 2 * cos.shape[-1]
    if rd == x.shape[-1]:
        return _rope(x, cos, sin)
    return jnp.concatenate([_rope(x[..., :rd], cos, sin), x[..., rd:]], -1)


def _attention(y, mp, cfg: MoEConfig, rope, mesh, axes):
    """A full-attention mixer: y (b, s, d) normed -> (b, s, d)."""
    b, s, _ = y.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = y @ mp["wq"], y @ mp["wk"], y @ mp["wv"]
    gate = None
    if cfg.attn_output_gate:        # a head's columns: its query, its gate
        q, gate = jnp.split(q.reshape(b, s, h, 2 * hd), 2, axis=-1)
    if cfg.qk_norm:
        q = _norm(q, mp["q_norm"], cfg)
        k = _norm(k, mp["k_norm"], cfg)
    q, k = q.reshape(b, s, h, hd), k.reshape(b, s, kvh, hd)
    if cfg.qk_head_norm:
        q = _norm(q, mp["q_norm"], cfg)
        k = _norm(k, mp["k_norm"], cfg)
    q, k = _rope_part(q, *rope), _rope_part(k, *rope)
    o = _attend(q, k, v.reshape(b, s, kvh, hd), cfg, mesh,
                axes).astype(y.dtype)
    if gate is not None:
        with jax.named_scope("attention.gate"):
            o = o * jax.nn.sigmoid(gate)
    return o.reshape(b, s, h * hd) @ mp["wo"]


def _conv_silu(x, w):
    """x (b, h, s, d), w (h, d, K) -> float32 (the depthwise causal conv
    along the tokens ``sum_j w[..., j] x[t - (K - 1) + j]``, zeros before
    the row; its sigmoid; its silu)."""
    s, taps = x.shape[2], w.shape[-1]
    pre = conv_taps(jnp.pad(x, ((0, 0), (0, 0), (taps - 1, 0), (0, 0))), w,
                    s)
    sg = jax.nn.sigmoid(pre)
    return pre, sg, pre * sg


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_silu_norm(x, w, scale):
    """One pass over a kind's heads, head-major: x (b, h, s, d) the
    projection's product, w (h, d, K) its conv channels -> silu(conv(x)),
    L2-normalised over ``d`` and times ``scale`` unless ``scale`` is None,
    in x's dtype; float32 in between. Its backward keeps x and w alone."""
    with jax.named_scope("gdn.conv"):
        a = _conv_silu(x, w)[2]
        if scale is not None:
            a = a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6) \
                * scale
        return a.astype(x.dtype)


def _conv_silu_norm_fwd(x, w, scale):
    return _conv_silu_norm(x, w, scale), (x, w)


def _conv_silu_norm_bwd(scale, res, g):
    """Three reads of x, none of a float32 array: (1) of a normed kind,
    the per-head sums ``a.a`` and ``g.a``; (2) conv, silu and norm computed
    anew, ONE gradient of the conv's pre-activation written, in x's dtype;
    (3) the transposed conv (``K`` shifted reads of it, summed in float32)
    and the conv weights' gradient from the same array. Each pass takes
    its operands through ``lax.optimization_barrier``: where two passes (or
    this backward and the remat's forward beside it) share an expression,
    the compiler stores the float32 silu(conv) for both, and fused into its
    readers the gradient would be computed again at every shift."""
    f32 = jnp.float32
    with jax.named_scope("gdn.conv"):
        x, w = lax.optimization_barrier(res)
        s, taps = x.shape[2], w.shape[-1]
        if scale is not None:
            a = _conv_silu(x, w)[2]
            sums = (jnp.sum(a * a, -1, keepdims=True),
                    jnp.sum(g.astype(f32) * a, -1, keepdims=True))
            x, w, g, (ss, ga) = lax.optimization_barrier((x, w, g, sums))
        pre, sg, a = _conv_silu(x, w)
        g = g.astype(f32)
        if scale is not None:
            r = lax.rsqrt(ss + 1e-6)
            g = (g - a * (r * r * ga)) * (r * scale)
        d_pre = lax.optimization_barrier(
            (g * sg * (1.0 + pre * (1.0 - sg))).astype(x.dtype))
        # d_pre[u + (K - 1) - j], zeros past the row: what tap j of token
        # u's input met, for the transposed conv and, against x[u], for
        # the weights' gradient (one set of shifted reads serves both)
        ahead = jnp.pad(d_pre, ((0, 0), (0, 0), (0, taps - 1), (0, 0)))
        ahead = [ahead[:, :, taps - 1 - j:taps - 1 - j + s].astype(f32)
                 for j in range(taps)]
        w32, x32 = w.astype(f32), x.astype(f32)
        d_x = sum(d * w32[:, None, :, j] for j, d in enumerate(ahead))
        d_w = jnp.stack([jnp.sum(d * x32, axis=(0, 2)) for d in ahead], -1)
        return d_x.astype(x.dtype), d_w.astype(w.dtype)


_conv_silu_norm.defvjp(_conv_silu_norm_fwd, _conv_silu_norm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_norm(o, z, w, eps):
    """``rmsnorm(o) * w * silu(z)`` over the minor dimension, head-major: o,
    z (b, h, s, d), w (d,) -> o's dtype, float32 in between and rounded
    once. Its backward keeps o, z and w alone."""
    with jax.named_scope("gdn.gate_norm"):
        o32 = o.astype(jnp.float32)
        var = jnp.mean(o32 * o32, -1, keepdims=True)
        return (o32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)
                * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _gated_norm_fwd(o, z, w, eps):
    return _gated_norm(o, z, w, eps), (o, z, w)


def _gated_norm_bwd(eps, res, g):
    o, z, w = res
    f32 = jnp.float32
    with jax.named_scope("gdn.gate_norm"):
        o32, z32, w32, g = o.astype(f32), z.astype(f32), w.astype(f32), \
            g.astype(f32)
        r = lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True) + eps)
        n, sg = o32 * r, jax.nn.sigmoid(z32)
        gn, gate = g * n, z32 * sg
        d_n = g * w32 * gate
        d_o = r * (d_n - n * jnp.mean(d_n * n, -1, keepdims=True))
        d_z = gn * w32 * (sg * (1.0 + z32 * (1.0 - sg)))
        d_w = jnp.sum(gn * gate, axis=(0, 1, 2))
        return d_o.astype(o.dtype), d_z.astype(z.dtype), d_w.astype(w.dtype)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def _gated_delta_net(y, mp, cfg: MoEConfig):
    """A linear mixer (Gated DeltaNet): y (b, s, d) normed -> (b, s, d).
    ``w_qkvz``'s columns are [q | k | v | z] and ``w_ba``'s [b | a], each
    kind's heads in order (value head j reads key head j // r); a row is
    a multiple of ``gated_delta.CHUNK`` tokens.

    Between the two projections every activation is HEAD-MAJOR, (b, heads,
    s, head width): q, k, v and z are each y's product with their own
    columns of ``w_qkvz`` (a view of the weight, (d, heads, width)), which
    writes that layout; the conv shifts along ``s``, both norms reduce over
    the minor dimension, the rule splits ``s`` into chunks, and ``w_out``'s
    product contracts heads and width. Nothing in between is reshaped
    across the minor dimension or stored in float32."""
    d = y.shape[-1]
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    kw, vw = cfg.linear_widths
    f32 = jnp.float32
    with jax.named_scope("gdn.proj"):
        def heads(first, h, width):
            w = mp["w_qkvz"][:, first:first + h * width]
            return jnp.einsum("bsd,dhk->bhsk", y, w.reshape(d, h, width))
        q, k = heads(0, hk, dk), heads(kw, hk, dk)
        v, z = heads(2 * kw, hv, dv), heads(2 * kw + vw, hv, dv)
        ba = jnp.einsum("bsd,dh->bhs", y, mp["w_ba"]).astype(f32)
        beta = jax.nn.sigmoid(ba[:, :hv])                   # (b, hv, s)
        g = -jnp.exp(mp["A_log"])[:, None] * jax.nn.softplus(
            ba[:, hv:] + mp["dt_bias"][:, None])
    conv = mp["conv"]
    q = _conv_silu_norm(q, conv[:kw].reshape(hk, dk, -1), dk ** -0.5)
    k = _conv_silu_norm(k, conv[kw:2 * kw].reshape(hk, dk, -1), 1.0)
    v = _conv_silu_norm(v, conv[2 * kw:].reshape(hv, dv, -1), None)
    o, _ = gated_delta.chunk_gated_delta_rule(q, k, v, g, beta)
    o = _gated_norm(o, z, mp["gdn_norm"], cfg.norm_eps)
    with jax.named_scope("gdn.out"):
        return jnp.einsum("bhsv,hvd->bsd", o,
                          mp["w_out"].reshape(hv, dv, d))


def _period(kinds: tuple) -> int:
    """The shortest period the layer pattern repeats with."""
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


def _forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
             mesh: Optional[Mesh], axes: MeshAxes):
    """tokens (b, s) int32 -> (logits (b, s, vocab), routing statistics
    ``{"moe_aux_loss", "moe_load_max_over_mean"}``, of a held slice
    ``"moe_local_share"`` and, where a device holds fewer experts than the
    router scores, ``"moe_compact_share"`` (the expert layers that took the
    work list of ``_experts``; 1 unless a routing overflowed its bound):
    float32 scalars)."""
    if _serving_only(cfg):
        raise NotImplementedError(
            "the train forward runs linear and full-attention layers of "
            "softmax-routed experts; window / global / latent layer_types "
            "(a latent layer has no training rule here: its absorbed and "
            "materialised forms are the cache's), n_dense_layers, "
            "post_norm, rope_layers, sigmoid scoring and a residual "
            "stream mixed by hyper-connections (hc_mult) are the serving "
            "forwards' (ray_tpu.llm.model); so are state-space ('state') "
            "layers, layers that are one mixer alone ('experts') and "
            "non-gated relu2 experts: no state-space layer has a backward "
            "here, nor has a gated short convolution (shortconv_kernel)")
    b, s = tokens.shape

    def act_constraint(x, spec):
        if mesh is not None:
            return lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, spec))
        return x

    x = jnp.take(params["embed"], tokens, axis=0)
    x = act_constraint(x, P(axes.batch, axes.context, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    rope = _rope_tables(positions, cfg.rotary_dim or cfg.head_dim,
                        cfg.rope_theta)

    # the scopes only name the ops in a device trace (metadata). ``lp``:
    # what every layer has; ``mp``: this layer's mixer
    def layer(kind, x, lp, mp):
        if kind == "full":
            with jax.named_scope("attention"):
                y = _norm(x, lp["attn_norm"], cfg)
                x = x + _attention(y, mp, cfg, rope, mesh, axes)
                x = act_constraint(x, P(axes.batch, axes.context, None))
        else:
            y = _norm(x, lp["attn_norm"], cfg)
            x = x + _gated_delta_net(y, mp, cfg)
            x = act_constraint(x, P(axes.batch, axes.context, None))
        y = _norm(x, lp["mlp_norm"], cfg)
        moe_out, stat = _moe_block(y, lp, cfg, mesh, axes)
        if cfg.n_shared_experts:
            moe_out = moe_out + _shared(y, lp)
        x = x + moe_out
        x = act_constraint(x, P(axes.batch, axes.context, None))
        return x, stat

    step = {kind: llama._remat(functools.partial(layer, kind), cfg)
            for kind in TRAIN_KINDS}
    if not cfg.layer_types:
        x, (load, prob, *fits) = lax.scan(
            lambda x, lp: step["full"](x, lp, lp), x,
            params["layers"])                                 # (L, E) each
    else:
        # one scan turn is one period of the pattern, its layers in order
        kinds = cfg.layer_types[:_period(cfg.layer_types)]
        turns = cfg.n_layers // len(kinds)

        def folded(tree, per):
            return jax.tree.map(
                lambda a: a.reshape(turns, per, *a.shape[1:]), tree)

        def period(x, xs):
            common, mixers = xs
            seen, stats = dict.fromkeys(mixers, 0), []
            for i, kind in enumerate(kinds):
                lp = jax.tree.map(lambda a: a[i], common)
                mp = jax.tree.map(lambda a: a[seen[kind]], mixers[kind])
                seen[kind] += 1
                x, stat = step[kind](x, lp, mp)
                stats.append(stat)
            return x, jax.tree.map(lambda *a: jnp.stack(a), *stats)

        x, (load, prob, *fits) = lax.scan(period, x, (
            folded(params["layers"], len(kinds)),
            {kind: folded(params[kind + "_layers"], kinds.count(kind))
             for kind in set(kinds)}))
        load = load.reshape(cfg.n_layers, -1)
        prob = prob.reshape(cfg.n_layers, -1)
    x = _norm(x, params["final_norm"], cfg)
    logits = (x @ params["lm_head"]).astype(jnp.dtype(cfg.logits_dtype))
    # the published term pools the routers of all layers: a token of layer
    # l is one more token, so f and p are means over layers
    aux = cfg.n_experts * jnp.sum(jnp.mean(load, 0) * jnp.mean(prob, 0))
    load = lax.stop_gradient(load)
    stats = {"moe_aux_loss": aux, "moe_load_max_over_mean": jnp.mean(
        jnp.max(load, axis=1) / jnp.mean(load, axis=1))}
    if cfg.experts_held:
        # assignments on the experts held here over all assignments
        held = load[:, cfg.first_expert:cfg.first_expert + cfg.n_held]
        stats["moe_local_share"] = jnp.sum(held) / jnp.sum(load)
    if fits:
        # the layers (and devices) that worked on their own rows only
        stats["moe_compact_share"] = jnp.mean(fits[0])
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
