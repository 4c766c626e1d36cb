"""Continuous-batching LLM engine: token-level scheduling over jitted steps.

The serving engine the reference delegates to vLLM for (reference:
python/ray/llm/_internal/serve/deployments/llm/llm_server.py wrapping a
vLLM engine; python/ray/llm/_internal/serve/deployments/llm/vllm/*),
rebuilt TPU-native:

- requests join and leave a fixed set of decode SLOTS at token
  granularity (continuous batching — no waiting for the batch to drain),
- every decode step is ONE jitted call over all slots (static shapes:
  the MXU sees the same batched matmuls every step, zero recompiles),
- prompts prefill through shape buckets (one compile per bucket) into
  the ONE KV cache there is, the paged block pool of llm/kvcache.py,
  admitted before each decode step for low time-to-first-token.

The engine is asyncio-native so it drops straight into a Serve replica;
device steps run on an executor thread to keep the event loop live.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import math
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from ray_tpu.llm import kvcache, model as lm, spec as specdec
from ray_tpu.util import devmon, events, tracing

logger = logging.getLogger("ray_tpu.llm.engine")

# The scheduler loop's phases. Each is one FLAT leaf span
# (``engine.<phase>`` in the profiler's host plane) and one histogram
# (``llm_loop_<phase>_s``); none encloses another, and what lies
# between two of them is thread hops and other coroutines.
PHASES = ("admit.alloc", "prefill.dispatch", "prefill.behind",
          "prefill.wait", "prefill.sample", "decode.prepare",
          "decode.dispatch", "decode.readback", "decode.account",
          "verify.prepare", "verify.dispatch", "verify.readback",
          "verify.accept", "emit", "yield", "idle")
# a phase (other than idle) over this long leaves a slow_phase event
SLOW_PHASE_S = 1.0


def _loop_key(phase: str) -> str:
    """engine_metrics() key of a phase's histogram."""
    return "loop_" + phase.replace(".", "_")


def _jx():
    """Lazy ``(jax, jax.numpy)`` accessor. jax must not be imported at
    module import time (worker processes import ray_tpu.llm without
    ever touching a backend), and every device-path method used to
    re-import it function-locally — this is the ONE copy of that
    idiom; device methods open with ``jax, jnp = _jx()``."""
    import jax
    import jax.numpy as jnp
    return jax, jnp


class KVHandoffError(RuntimeError):
    """A disaggregated request's shipped KV handle could not be
    resolved (prefill replica died / handle freed). Fails only its own
    request — never the shared scheduler loop."""


def engine_metrics() -> dict:
    """Get-or-create the engine's request-phase histograms (shared
    process registry; every engine in the process observes into the
    same series, and worker processes push them to the head via
    util/metrics.push_loop). Catalog:

      llm_queue_s        submit -> slot admission (waiting for a slot)
      llm_ttft_device_s  prefill dispatch -> its results ready: the
                         prefill's device compute and, ahead of it on
                         the device, what is left of the decode block
                         in flight it was enqueued behind. That part is
                         llm_loop_prefill_behind_s: the prefill's own
                         device time is this less that
      llm_ttft_wall_s    submit -> first token, wall clock
      llm_tpot_s         decode wall time per output token
      llm_request_tpot_s (last emit - first emit) / (tokens - 1) of a
                         finished request of two tokens or more, on the
                         engine's clock: the inside twin of a client's
                         time per output token (also ``tpot_s`` on the
                         request's engine/generate span)
      llm_request_tpot_stall_s  of that, what the request's slot
                         stalled between blocks: the llm_decode_gap_s
                         observed from its first block's dispatch to
                         its last emit, over (tokens - 1) (also
                         ``tpot_stall_s`` on the span, beside
                         ``stall_s`` and ``stall_admit_s``)
      llm_batch_size     active decode slots per step block
      llm_stream_lag_s   a streamed token's wait between the loop's
                         emit and its generate_stream consumer
      llm_stream_consume_s  what that consumer then does with the token
                         before it comes back for the next (the
                         replica's stream hop, on the engine's event
                         loop)

    The scheduler loop, timed and counted where the work is done (the
    sums and counts are what the benchmark's per-layer metrics read):

      llm_loop_<phase>_s       one per PHASES entry (dots as
                               underscores): seconds per span.
                               admit.alloc (block reservation),
                               prefill.dispatch / prefill.behind /
                               prefill.wait / prefill.sample (executor
                               thread; prefill.behind is the wait for
                               the decode block in flight the prefill
                               was enqueued behind, entered only when
                               there is one: the block's own time, not
                               the prefill's),
                               decode.prepare (block size, inputs, the
                               window layers' blocks), decode.dispatch
                               / decode.readback (executor thread: the
                               block's window), decode.account (the
                               counters below, the block's linked span
                               and device window), verify.prepare /
                               verify.dispatch / verify.readback /
                               verify.accept (a speculative round),
                               emit (tokens to their requests), yield
                               (one turn of the event loop: the stream
                               consumers wake in it), idle (parked)
      llm_decode_gap_s         what a decoding slot stalls between two
                               blocks, once a block that carries a
                               request on: from the END of the block
                               before on the device to the return of
                               this block's launch. The end is known
                               where an admission looked or waited for
                               it (prefill.behind's exit, or a look
                               during prefill.dispatch that found it
                               ended: the prompt's prefill, the host's
                               sampling, emit, prepare, hop and launch
                               then lie between the two blocks); where
                               nobody did, the block was enqueued
                               behind its predecessor, the device went
                               from one to the other, and the gap is 0.
                               With nothing in flight (a slot that
                               holds a drafter) it runs from the
                               read-back's end
      llm_decode_gap_admit_s   of that gap, the part inside
                               engine.admit.* and engine.prefill.*
                               (prefill.behind is not: it ends where
                               the gap starts)
      llm_decode_block_window_s  a block's own window: from its
                               predecessor's read-back end (its own
                               dispatch after idle) to its read-back
                               end. Windows tile the time between
                               read-backs, so over steps the sum is a
                               step's device time + the gap + what is
                               left around a step
      llm_decode_ahead_size    decode blocks enqueued and not yet read
                               back as a block is enqueued: 1 when it
                               went out ahead of its predecessor's
                               read-back, 0 when nothing was in flight
      llm_decode_hop_s         a decode block's two thread hops, summed:
                               decode.prepare's end to decode.dispatch's
                               start (loop thread to executor) and
                               decode.readback's end to decode.account's
                               start (the loop's wake-up, behind
                               whatever else its thread was running)
      llm_decode_block_steps   decode steps per block
      llm_decode_slot_steps    slots x steps per block
      llm_decode_idle_slot_steps  slots that hold no request x steps per
                               block: grid steps of the decode kernels
                               that did nothing (ops/pallas/
                               paged_attention.py)
      llm_decode_state_slot_steps  slot states a block's steps moved a
                               state layer, by the impl's own count
                               (kvcache.state_slot_steps): the block's
                               requests x its steps where the kernel
                               walks the live slots, every slot x steps
                               where the reference runs; over
                               llm_decode_slot_steps 1.0 or slots / live
                               (a model with state layers only)
      llm_decode_ctx_tokens    positions attended per block, summed
                               over its slots and steps
      llm_decode_kv_fetch_tokens  positions of K (and of V) the paged
                               kernel's walk fetched per block, summed
                               over its slots and steps (a slot that
                               is not in the block fetches nothing)
      llm_prefill_tokens       prompt tokens run through a prefill
                               forward per admit (prefix hits excluded)
      llm_prefill_chunks_size  prefill forwards per admit (a prompt past
                               the largest bucket runs a chunk at a time)
      llm_latent_rows_expanded_size  cache rows a latent layer expanded
                               to per-head K and V per admit (a prefix
                               expanded again for every chunk counts
                               every time)

    Per decode block, as its tokens are read back (the device scalars
    of a model with expert layers come back with them, no sync of their
    own) and as its blocks were set aside:

      llm_moe_routed_size        assignments (live slots x experts a
                                 token) the block's steps routed, summed
                                 over the expert layers
      llm_moe_local_size         those of them on experts this device
                                 holds
      llm_moe_experts_hit_size   held experts some row reached, summed
                                 over steps and layers (their weights
                                 were read)
      llm_moe_experts_held_size  held experts x expert layers x steps:
                                 what the hits are a share of
      llm_kv_blocks_global_size  pool blocks of the global layers in use
      llm_kv_blocks_window_size  pool blocks of the window layers in use
      llm_kv_blocks_latent_size  pool blocks of the latent layers in use
      llm_kv_blocks_state_size   states (a slot's, all state layers) held
      llm_kv_window_freed_size   window-layer blocks freed for this
                                 block (their sequences' windows passed
                                 them)
      llm_kv_used_bytes          pool bytes those blocks hold, both kinds
      llm_kv_live_tokens         positions the live requests hold

    HBM attribution (the engine half of util/devmon.py's device plane):

      llm_kv_cache_bytes           KV bytes of the pool's live blocks
      llm_kv_cache_headroom_bytes  KV bytes of the pool's free blocks
    """
    from ray_tpu.util import metrics as m
    seconds = (.0001, .00025, .0005, .001, .0025, .005, .01, .025, .05,
               .1, .25, .5, 1, 2.5, 10)
    loop = {
        _loop_key(p): m.Histogram(
            f"llm_{_loop_key(p)}_s",
            f"Seconds per engine.{p} span of the scheduler loop",
            boundaries=seconds)
        for p in PHASES}
    return {
        **loop,
        "gap": m.Histogram(
            "llm_decode_gap_s",
            "The previous decode block's end on the device (learned by "
            "the admission that looked or waited for it) to the return "
            "of this block's launch, observed when a request of the "
            "previous block is still decoding; 0 when nothing came "
            "between the two blocks on the device", boundaries=seconds),
        "gap_admit": m.Histogram(
            "llm_decode_gap_admit_s",
            "The part of llm_decode_gap_s spent inside engine.admit.* "
            "and engine.prefill.* spans (engine.prefill.behind ends "
            "where the gap starts)", boundaries=seconds),
        "block_window": m.Histogram(
            "llm_decode_block_window_s",
            "A decode block's own window: its predecessor's read-back "
            "end (its own dispatch after idle) to its read-back end",
            boundaries=seconds),
        "decode_ahead": m.Histogram(
            "llm_decode_ahead_size",
            "Decode blocks enqueued and not yet read back as a block is "
            "enqueued: 1 when it went out ahead of its predecessor's "
            "read-back, 0 when nothing was in flight",
            boundaries=(0, 1)),
        "decode_hop": m.Histogram(
            "llm_decode_hop_s",
            "A decode block's two thread hops: engine.decode.prepare's "
            "end to engine.decode.dispatch's start plus "
            "engine.decode.readback's end to engine.decode.account's "
            "start", boundaries=seconds),
        "block_steps": m.Histogram(
            "llm_decode_block_steps", "Decode steps per block",
            boundaries=(1, 2, 4, 8, 16, 32)),
        "slot_steps": m.Histogram(
            "llm_decode_slot_steps",
            "Active slots times decode steps per block",
            boundaries=(1, 4, 16, 64, 256, 1024)),
        "idle_slot_steps": m.Histogram(
            "llm_decode_idle_slot_steps",
            "Slots that hold no request times decode steps per block: "
            "grid steps the decode kernels skipped",
            boundaries=(1, 4, 16, 64, 256, 1024)),
        "state_slot_steps": m.Histogram(
            "llm_decode_state_slot_steps",
            "Slot states a decode block's steps moved a state layer, by "
            "the implementation's own count: live slots times steps under "
            "the kernel, every slot times steps under its reference",
            boundaries=(1, 4, 16, 64, 256, 1024)),
        "ctx_tokens": m.Histogram(
            "llm_decode_ctx_tokens",
            "Context positions attended per decode block, summed over "
            "its active slots and steps",
            boundaries=(64, 256, 1024, 4096, 16384, 65536, 262144,
                        1048576)),
        "kv_fetch_tokens": m.Histogram(
            "llm_decode_kv_fetch_tokens",
            "Positions of K (and of V) the paged-decode kernel's walk "
            "fetched per decode block, summed over the block's slots "
            "and its steps",
            boundaries=(64, 256, 1024, 4096, 16384, 65536, 262144,
                        1048576)),
        "prefill_tokens": m.Histogram(
            "llm_prefill_tokens",
            "Prompt tokens run through a prefill forward per admitted "
            "request (prefix-cache hits excluded)",
            boundaries=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096)),
        # dimensionless counts a block: the names end in _size (or
        # _tokens), as scripts/check_metrics_lint.py asks
        **{key: m.Histogram(
            f"llm_{key}" + ("" if key.endswith("_tokens") else "_size"),
            text, boundaries=(1, 4, 16, 64, 256, 1024, 4096, 16384, 65536))
           for key, text in (
            ("moe_routed", "Assignments of live slots routed per decode "
             "block, summed over its steps and expert layers"),
            ("moe_local", "Assignments of live slots on held experts per "
             "decode block"),
            ("moe_experts_hit", "Held experts reached by some row per "
             "decode block, summed over its steps and expert layers"),
            ("moe_experts_held", "Held experts times expert layers times "
             "steps per decode block"),
            ("kv_blocks_global", "Global-layer pool blocks in use at a "
             "decode block"),
            ("kv_blocks_window", "Window-layer pool blocks in use at a "
             "decode block"),
            ("kv_blocks_latent", "Latent-layer pool blocks in use at a "
             "decode block"),
            ("kv_blocks_state", "States (a slot's state and conv tail in "
             "every state layer) held at a decode block"),
            ("latent_rows_expanded", "Cache rows of a latent layer "
             "expanded to per-head keys and values per admitted request's "
             "prefill (one layer's count: a prefix re-expanded for every "
             "chunk counts every time, bucket padding included)"),
            ("prefill_chunks", "Prefill forwards per admitted request: 1 "
             "for a prompt inside the largest bucket, one a chunk beyond"),
            ("kv_window_freed", "Window-layer blocks freed for a decode "
             "block"),
            ("kv_live_tokens", "Positions the live requests hold at a "
             "decode block"))},
        "kv_used_bytes": m.Histogram(
            "llm_kv_used_bytes",
            "Pool bytes in use at a decode block, both layer kinds",
            boundaries=tuple(2.0 ** e for e in range(20, 36, 2))),
        "stream_lag": m.Histogram(
            "llm_stream_lag_s",
            "Wait of a streamed token between the scheduler loop's "
            "emit and its generate_stream consumer",
            boundaries=seconds),
        "stream_consume": m.Histogram(
            "llm_stream_consume_s",
            "From generate_stream's yield of a token to its consumer's "
            "return for the next: the stream hop's work on the "
            "engine's event loop",
            boundaries=(.00001, .000025, .00005) + seconds),
        "request_tpot": m.Histogram(
            "llm_request_tpot_s",
            "(last emit - first emit) / (tokens - 1) of a finished "
            "request of two tokens or more, on the engine's clock",
            boundaries=(.0005, .001, .0025, .005, .01, .025, .05, .1,
                        .25, .5, 1, 2.5)),
        "request_tpot_stall": m.Histogram(
            "llm_request_tpot_stall_s",
            "The llm_decode_gap_s seconds observed between a finished "
            "request's first decode block's dispatch and its last emit, "
            "over (tokens - 1): the stalled part of llm_request_tpot_s",
            boundaries=(.0001, .00025, .0005, .001, .0025, .005, .01,
                        .025, .05, .1, .25, .5, 1, 2.5)),
        "queue": m.Histogram(
            "llm_queue_s",
            "Wait from request submission to slot admission"),
        "ttft_device": m.Histogram(
            "llm_ttft_device_s",
            "Prefill dispatch to its results ready (forward + cache "
            "write, block_until_ready-bounded): the device time producing "
            "the first token, behind what is left of the decode block in "
            "flight (llm_loop_prefill_behind_s)"),
        "ttft_wall": m.Histogram(
            "llm_ttft_wall_s",
            "Wall time from submission to first token"),
        "tpot": m.Histogram(
            "llm_tpot_s", "Decode wall time per output token",
            boundaries=(.0005, .001, .0025, .005, .01, .025, .05, .1,
                        .25, .5, 1, 2.5)),
        "batch": m.Histogram(
            "llm_batch_size", "Active decode slots per step block",
            boundaries=(1, 2, 4, 8, 16, 32, 64, 128, 256)),
        "kv_bytes": m.Gauge(
            "llm_kv_cache_bytes",
            "Bytes of the KV pool's live blocks (referenced by requests "
            "or held by the prefix index)"),
        "kv_headroom": m.Gauge(
            "llm_kv_cache_headroom_bytes",
            "Bytes of the KV pool's free blocks (0 = admits park until "
            "a request finishes or a cached chain is evicted)"),
    }


@dataclass
class _Request:
    tokens: List[int]                       # prompt (token ids)
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    top_p: float = 1.0                      # 1.0 = disabled
    top_k: int = 0                          # 0 = disabled
    # stop sequences (token-id lists); on a suffix match generation
    # ends and the matched suffix is trimmed from the result
    stop: Optional[List[List[int]]] = None
    out: List[int] = field(default_factory=list)
    fut: Optional[asyncio.Future] = None
    stream: Optional[asyncio.Queue] = None
    submitted: float = field(default_factory=time.monotonic)
    # absolute wall-clock deadline (serve's propagated budget): the
    # scheduler refuses to admit an expired request and cancels an
    # active one at the next block boundary, reclaiming its slot
    deadline_ts: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    # the newest emit's stamp and the tokens emitted (a matched stop
    # sequence is trimmed from ``out``, not from this count): with
    # first_token_at, the engine's own time per output token
    last_token_at: Optional[float] = None
    emitted: int = 0
    # the engine's stall totals (LLMEngine._stall) as the first decode
    # block the request is a member of was dispatched: _finish reads
    # them again at the last emit, and the difference is what the
    # request's slot stalled between blocks
    stall_mark: Optional[tuple] = None
    prefill_device_s: float = 0.0           # block_until_ready-bounded
    # request trace context ambient at submission (the serve replica
    # binds it before user code): engine queue/prefill/generate spans
    # parent to the replica's handler span through it. Cleared once the
    # terminal "generate" span is recorded (one per request).
    trace: Optional[tracing.TraceContext] = None
    t_submit_wall: float = field(default_factory=time.time)
    # KV computed by a remote prefill engine (disaggregated serving):
    # {"k","v": (layers, bucket, kvh, hd) numpy, "logits": (vocab,)}
    prefilled: Optional[dict] = None
    # KV-pool state: engine-unique sequence id,
    # the block allocation handed out at admission, and the prompt
    # tokens served from cached prefix blocks (stamped on the
    # terminal trace span and surfaced in the result)
    seq: int = 0
    kv_alloc: Optional[dict] = None
    prefix_hit: int = 0
    kv_written: bool = False    # prefill scatter reached the pool
    handoff_bytes: int = 0      # disaggregated KV shipped for this req
    # speculative decoding (engine spec mode): the per-request
    # prompt-lookup drafter (accept-window state; the token history it
    # matches against IS tokens+out) and the request's draft/accept
    # totals — accept rate lands on the terminal trace span and the
    # llm_spec_accept_rate gauge
    drafter: Optional[specdec.PromptLookupDrafter] = None
    spec_drafted: int = 0
    spec_accepted: int = 0


@dataclass
class _Block:
    """One decode block from decode.prepare to its read-back. The loop
    keeps at most one enqueued and unread (``LLMEngine._inflight``)
    while it prepares and enqueues the next."""
    steps: int
    reqs: dict                  # slot -> the request it decodes there
    lens: List[int]             # positions each holds as the block starts
    tokens: np.ndarray          # (slots,) first tokens the host knows
    keep: np.ndarray            # (slots,) bool: the first token is the
                                # predecessor's last row, on the device
    lengths: np.ndarray         # (slots,) first write positions
    tables: dict                # kind -> the block tables as prepared
    temps: np.ndarray
    top_ps: np.ndarray
    top_ks: np.ndarray
    member_traces: List[str]
    first_ctx: Optional[tracing.TraceContext]
    t_prep: float = 0.0         # decode.prepare's end
    # stamped by the executor thread: dispatch start, read-back end
    t_disp: float = 0.0
    t_back: float = 0.0
    # when the block ended on the device, as far as the host learned it
    # (LLMEngine._block_ended: an admission looked, or waited, for it);
    # 0.0 when nobody did: its successor was enqueued behind it and
    # followed at once
    t_end: float = 0.0
    # device results: (steps, slots) tokens, the expert layers' counts,
    # the last row (the successor's first tokens)
    out: object = None
    counts: object = None
    last: object = None
    # KV releases of requests that ended after this block was enqueued
    # with their tables in it: run once it has been read back
    held: list = field(default_factory=list)


class LLMEngine:
    def __init__(self, cfg, params, *, max_slots: int = 8,
                 max_len: int = 1024,
                 prefill_buckets: Sequence[int] = (64, 128, 256, 512),
                 cache_dtype="bfloat16", seed: int = 0,
                 steps_per_sync: int = 8,
                 mesh=None, tensor_axis: str = "tensor",
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_impl: str = "auto",
                 spec: Optional[bool] = None,
                 detokenize: Optional[Callable[[List[int]], str]] = None):
        """``cfg`` is a model family's config object (a LlamaConfig, or
        one with the optional fields llm/model.py lists: layer kinds,
        a window, experts) and ``params`` that family's tree.

        With ``mesh``, the engine runs TENSOR-PARALLEL: params shard
        per lm.serve_param_specs (Megatron layout), the KV pool shards
        its kv-head dim, and every prefill/decode jit runs SPMD over the
        mesh with GSPMD inserting the two psums per layer. This is how a
        model larger than one chip's HBM serves (reference:
        llm/_internal/serve/configs/llm_config.py:181-186
        tensor_parallel_size + placement bundles per replica)."""
        jax, jnp = _jx()
        # jax is live in this process from here on: hook the compile
        # listeners now so even the cache-init compiles are spanned
        # (idempotent; no-op under RAY_TPU_DEVMON=0), and let the
        # device monitor query the backend this process now owns
        devmon.install()
        devmon.mark_backend_live()
        if mesh is not None and getattr(cfg, "attn_impl", "auto") in (
                "auto", "flash", "flash_interpret", "ring"):
            # Tensor-parallel serving shards the head dim via GSPMD,
            # and the pallas flash kernel cannot be auto-partitioned
            # (training wraps it in shard_map; the serving jits don't)
            # — force the XLA reference attention, which GSPMD
            # partitions fine.
            import dataclasses
            cfg = dataclasses.replace(cfg, attn_impl="reference")
        # the cache's layout, ((kind, its layers), ...): a pool, block
        # ids and a table a kind, each a dict by kind for every model
        self._layout = kvcache.pool_kinds(cfg)
        windowed = kvcache.WINDOW in dict(self._layout)
        # state layers keep a state and a conv tail a SLOT: no block ids,
        # no table, nothing the kernel walks
        stateful = kvcache.STATE in dict(self._layout)
        paged = [(kind, layers) for kind, layers in self._layout
                 if kind != kvcache.STATE]
        # (layers, window) of each layer kind the paged kernel walks:
        # what llm_decode_kv_fetch_tokens counts by
        self._walks = [(len(layers), lm.window_of(cfg, kind))
                       for kind, layers in paged]
        if mesh is not None and (windowed or stateful
                                 or kvcache.LATENT in dict(self._layout)
                                 or lm.has_experts(cfg)):
            raise NotImplementedError(
                "tensor-parallel serving shards the Llama family's "
                "parameter tree only (lm.serve_param_specs)")
        self.cfg = cfg
        self.mesh = mesh
        self.tensor_axis = tensor_axis
        # every 'auto' below is resolved here, once, from the platform
        # this process runs on, and reported in `stats`
        from ray_tpu.util import jaxenv
        self._device = jaxenv.describe_device(
            mesh.devices.flat[0] if mesh is not None else None)
        self._prefill_impl = lm.resolve_prefill_impl(cfg)
        if mesh is not None:
            params = lm.shard_params_for_serving(params, mesh, cfg,
                                                 tensor_axis)
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.buckets = tuple(sorted(b for b in prefill_buckets
                                    if b <= max_len)) or (max_len,)
        self.detokenize = detokenize
        # The KV cache is the paged pool of llm/kvcache.py: fixed-size
        # token blocks from a preallocated pool, per-request block
        # tables, and prefix reuse for shared system prompts —
        # tensor-parallel engines included (the pool shards its kv-head
        # dim over the mesh; the block-index ops and the decode
        # attention are head-local, so tables stay replicated and no
        # collective is added). None reads the Config knobs
        # (kvcache_block_size etc.).
        from ray_tpu.config import get_config
        _cfg = get_config()
        if kv_block_size is None:
            kv_block_size = int(getattr(_cfg, "kvcache_block_size", 16))
        if kv_pool_blocks is None:
            kv_pool_blocks = int(getattr(_cfg, "kvcache_pool_blocks", 0))
        if windowed:
            # a window layer frees the blocks its window has passed: a
            # cached prefix would have to keep them. Asked for: refused;
            # left to the Config's default: off
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with window layers: prefix reuse "
                    "is not supported for a model whose window layers "
                    "free the blocks they have passed")
            prefix_cache = False
        if stateful:
            # a cached chain of blocks has no state to start its suffix
            # from (no snapshot is kept at block edges). Asked for:
            # refused; left to the Config's default: off
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with state layers: prefix reuse is "
                    "not supported for a model with state-space or "
                    "short-convolution layers, whose recurrent state or "
                    "conv tail is kept a slot and not a block")
            prefix_cache = False
        if prefix_cache is None:
            prefix_cache = bool(getattr(_cfg, "kvcache_prefix_cache",
                                        True))
        if kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {kv_block_size}: the "
                "paged pool is the engine's only KV cache and its blocks "
                "need a size")
        if spec is None:
            spec = bool(getattr(_cfg, "spec_decode", False))
        if spec and windowed:
            raise ValueError(
                "speculative decoding is not supported with window "
                "layers: the verify forward attends global layers only")
        if spec and stateful:
            raise ValueError(
                "speculative decoding is not supported with state layers: "
                "a rejected draft would have to roll a recurrent state (or "
                "a short convolution's tail) back, and no snapshot of it is "
                "kept")
        # Speculative decoding (llm/spec.py): draft-and-verify rides
        # the block-table verify forward
        self._spec = bool(spec)
        self._spec_k = max(1, int(getattr(_cfg, "spec_draft_tokens", 4)))
        self._spec_ngram = max(1, int(getattr(_cfg, "spec_ngram_max", 3)))
        self._spec_window = max(1, int(getattr(_cfg,
                                               "spec_backoff_window", 16)))
        self._spec_buckets = specdec.width_buckets(self._spec_k)
        self._specm = specdec.spec_metrics() if self._spec else None
        self._kvm = kvcache.kvcache_metrics()
        # decode attention: the fused block-table kernel (paged_flash)
        # on a TPU, the materialized gather view elsewhere; the kwarg
        # overrides the platform's choice (the kernel's reference in
        # parity checks). Off a TPU the kernel runs through the pallas
        # interpreter — tier-1 exercises the real table walk, not a
        # shadow path.
        self._kv_impl = kvcache.resolve_attn_impl(kv_impl)
        self._kv_interpret = (self._kv_impl == "paged_flash"
                              and self._device["platform"] != "tpu")
        # effective block size must divide every prefill bucket
        # and max_len (prefill writes land block-aligned): shrink
        # to the gcd instead of erroring on small test buckets
        b = kv_block_size
        for v in (*self.buckets, max_len):
            b = math.gcd(b, v)
        self._block = b
        self._table_w = max_len // self._block
        # Decode block size per host sync: throughput lever when the
        # device link is latency-bound. Kept power-of-2-bucketed so XLA
        # compiles at most log2(steps_per_sync)+1 block variants.
        self.steps_per_sync = max(1, steps_per_sync)
        # bytes a block id of each kind costs (a position's row in every
        # layer of the kind: kvcache.row_bytes), and the pool of each
        # kind: the window layers' is sized exactly (a ring of blocks a
        # slot), the pool of the kind a sequence holds whole (global or
        # latent) by auto_pool_blocks from what is free beside it
        block_bytes = {
            kind: len(layers) * self._block
            * kvcache.row_bytes(cfg, kind, cache_dtype)
            for kind, layers in paged}
        whole = self._layout[0][0]
        window = None
        if windowed:
            ring = kvcache.window_ring_blocks(
                cfg.sliding_window, self._block, self.steps_per_sync)
            window = (max_slots * ring + 1, cfg.sliding_window,
                      self.steps_per_sync)
        # the pool shards its kv heads over the tensor axis
        tp = mesh.shape[tensor_axis] if mesh is not None else 1
        # sized exactly, like the window layers' rings: a state a slot
        state_bytes = max_slots * kvcache.state_slot_bytes(
            cfg, cache_dtype) if stateful else 0
        nb = kvcache.auto_pool_blocks(
            max_slots, self._table_w, block_bytes[whole] // tp,
            kv_pool_blocks,
            reserved_bytes=state_bytes + (
                window[0] * block_bytes[kvcache.WINDOW] if window else 0))
        self._pool = kvcache.init_pool(
            cfg, nb, self._block, jnp.dtype(cache_dtype),
            window_blocks=window[0] if window else 0,
            state_slots=max_slots if stateful else 0)
        if mesh is not None:
            # pool shards its kv-head dim (Megatron layout); block ids
            # index dim 1, orthogonal to the shard, so
            # scatter/gather/copy jits run under GSPMD unchanged
            from jax.sharding import NamedSharding, PartitionSpec as P
            s = NamedSharding(
                mesh, P(None, None, tensor_axis, None, None))
            self._pool = {k: jax.device_put(v, s)
                          for k, v in self._pool.items()}
            self._tok_sharding = NamedSharding(mesh, P())
        else:
            # one device: the pool is committed to it from the start,
            # as a block's first tokens are (below), so no program of
            # the engine ever meets an argument of the other kind and
            # compiles a second time for it
            from jax.sharding import SingleDeviceSharding
            self._tok_sharding = SingleDeviceSharding(
                next(iter(kvcache.pool_k(self._pool).devices())))
            self._pool = {k: jax.device_put(v, self._tok_sharding)
                          for k, v in self._pool.items()}
        self._kv = kvcache.KVBlockManager(
            nb, self._block, table_width=self._table_w,
            prefix_cache=prefix_cache, metrics=self._kvm, window=window,
            kind=whole, state_slots=max_slots if stateful else 0)
        self._block_bytes = kvcache.kind_block_bytes(self._pool)
        # every slot's block table, by kind (each kind its own ids)
        self._tables = {kind: np.full((max_slots, self._table_w),
                                      kvcache.TRASH, np.int32)
                        for kind, _ in paged}
        self._stateful = stateful
        self._state_admits = 0      # states started from zeros
        self._state_slot_steps = 0  # slot states moved a state layer
        # the layers with a router: what llm_moe_experts_held counts by
        self._expert_layers = (
            lm.layer_kinds(cfg).count(lm.EXPERTS) if lm.single_mixer(cfg)
            else cfg.n_layers - getattr(cfg, "n_dense_layers", 0))
        self._freed: dict = {}      # kind -> freed_by_kind() last seen
        self._blocked: deque = deque()   # admits parked on pool
        self._seq_counter = 0
        self._slots: List[Optional[_Request]] = [None] * max_slots
        self._waiting: "asyncio.Queue[_Request]" = asyncio.Queue()
        self._rng = np.random.default_rng(seed)
        self._key = jax.random.PRNGKey(seed)
        self._step = 0
        self._loop_task: Optional[asyncio.Task] = None
        self._stopped = False
        # Request-phase telemetry rides the metrics registry (tagged
        # histograms, pushed to the head from worker processes); the
        # scalar counters below feed the legacy `stats` surface.
        self._m = engine_metrics()
        self._phases = {p: ("engine." + p, self._m[_loop_key(p)])
                        for p in PHASES}
        # the decode block enqueued and not yet read back, if any: the
        # loop enqueues its successor before it reads this one
        self._inflight: Optional[_Block] = None
        # the stall between two decode blocks: where the last read-back
        # ended (None when no request carried over, or when the next
        # block was enqueued before it), the admit/prefill seconds
        # since then or since the block in flight ended (_Block.t_end),
        # and the device interval of the last block read back
        self._gap_from: Optional[float] = None
        self._gap_admit = 0.0
        self._dev_span = (0.0, 0.0)
        # every llm_decode_gap_s / llm_decode_gap_admit_s observation
        # so far, summed: a request's marks are taken from these
        self._stall = (0.0, 0.0)
        self._kv_account()
        self._requests = 0
        self._tokens_generated = 0
        self._ttft_sum = 0.0
        self._ttft_count = 0
        # Postmortem bundles snapshot engine state through a weakref —
        # the provider must not keep a dead engine (and its KV cache)
        # alive, and a collected engine silently drops out of dumps.
        import weakref
        from ray_tpu.util import forensics
        ref = weakref.ref(self)
        forensics.register_state_provider(
            f"llm_engine:{id(self):x}",
            lambda: (lambda e: e.stats if e is not None else None)(ref()))

    @property
    def stats(self) -> dict:
        """Scalar engine counters (the per-phase distributions live in
        the metrics registry — see engine_metrics())."""
        out = {"requests": self._requests,
               "tokens_generated": self._tokens_generated,
               "ttft_sum": self._ttft_sum,
               "ttft_count": self._ttft_count,
               "pid": os.getpid(),
               "device": dict(self._device),
               "prefill_impl": self._prefill_impl,
               "block_size": self._block,
               "blocks_cached": self._kv.cached_blocks(),
               "blocks_free": self._kv.free_blocks(),
               "prefix_hit_tokens": self._kv.hit_tokens_total,
               "kv_impl": self._kv_impl,
               "kv_interpret": self._kv_interpret,
               "spec": self._spec}
        # one name a kind: the pool a sequence holds whole (global or
        # latent layers') under the bare name, another kind's + _kind
        for kind, used in self._kv.used_by_kind().items():
            tail = "" if kind == self._kv.kind else "_" + kind
            out["pool_blocks" + tail] = \
                kvcache.pool_k(self._pool, kind).shape[1]
            out["blocks_used" + tail] = used
        for kind, freed in self._kv.freed_by_kind().items():
            out[kind + "_blocks_freed"] = freed
        if self._stateful:
            per_slot = self._block_bytes[kvcache.STATE]
            out.update(
                state_layers=len(dict(self._layout)[kvcache.STATE]),
                state_bytes=per_slot * self.max_slots,
                state_bytes_per_slot=per_slot,
                state_admits=self._state_admits,
                state_slot_steps=self._state_slot_steps)
        return out

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One leaf span of the scheduler loop (a PHASES entry): the
        ``engine.<name>`` annotation, its ``llm_loop_*`` histogram and
        the stamps, all from tracing.phase's one pair of clock reads.
        Admission and prefill time also accrues to the decode gap it
        sits in (not prefill.behind: that is the decode block's own
        time, and the gap starts at its exit), and a phase over
        SLOW_PHASE_S names itself."""
        with tracing.phase(*self._phases[name]) as ph:
            yield ph
        if name.startswith(("admit.", "prefill.")) \
                and name != "prefill.behind":
            self._gap_admit += ph.dur
        if ph.dur > SLOW_PHASE_S and name != "idle":
            active = sum(r is not None for r in self._slots)
            waiting = self._waiting.qsize() + len(self._blocked)
            events.record("engine", "slow_phase", phase=name,
                          dur=ph.dur, active=active, waiting=waiting,
                          pid=os.getpid())
            logger.warning(
                "engine.%s took %.2f s (%d active, %d waiting)",
                name, ph.dur, active, waiting)

    def _kv_account(self) -> None:
        """Publish the engine's explicit KV HBM attribution: live
        bytes = blocks referenced by live requests plus resident
        prefix-cache blocks (the pool bounds HBM by LIVE tokens, the
        vLLM property); headroom = free blocks. The gauges ride the
        worker's metrics push to the head next to util/devmon.py's
        device_hbm_* series."""
        live = self._kv.used_by_kind()
        # the prefix index holds blocks of the kind held whole only
        live[self._kv.kind] += self._kv.cached_blocks()
        self._m["kv_bytes"].set(self._bytes_of(live))
        self._m["kv_headroom"].set(self._bytes_of(self._kv.free_by_kind()))

    def _bytes_of(self, blocks: dict) -> int:
        """Device bytes of ``blocks`` = {kind: block ids of its pool}."""
        return sum(n * self._block_bytes[kind] for kind, n in blocks.items())

    # --- public API -----------------------------------------------------

    async def generate(self, tokens: Sequence[int], *,
                       max_new_tokens: int = 64,
                       temperature: float = 0.0,
                       eos_id: Optional[int] = None,
                       top_p: float = 1.0, top_k: int = 0,
                       stop: Optional[Sequence[Sequence[int]]] = None,
                       prefilled: Optional[dict] = None,
                       deadline_ts: Optional[float] = None) -> dict:
        """``prefilled`` skips the in-engine prompt forward pass: it is
        the KV payload a remote PrefillEngine computed for these tokens
        (prefill/decode disaggregation, ray_tpu/llm/pd.py; reference:
        llm/_internal/serve/serving_patterns/prefill_decode/, KV moved
        via NIXL there, via the object plane here). ``top_p``/``top_k``
        filter the on-device sampler (1.0/0 disable); ``stop`` is a list
        of token-id sequences that end generation (matched suffix
        trimmed from the result). ``deadline_ts`` (absolute wall clock,
        serve's propagated budget) cancels the request — and frees its
        decode slot for waiting requests — the moment the budget is
        spent, raising serve.DeadlineExceeded."""
        r = self._submit(tokens, max_new_tokens, temperature, eos_id,
                         top_p=top_p, top_k=top_k, stop=stop,
                         prefilled=prefilled, deadline_ts=deadline_ts)
        r.fut = asyncio.get_running_loop().create_future()
        await r.fut
        return self._result(r)

    async def generate_stream(self, tokens: Sequence[int], *,
                              max_new_tokens: int = 64,
                              temperature: float = 0.0,
                              eos_id: Optional[int] = None,
                              top_p: float = 1.0, top_k: int = 0,
                              stop: Optional[Sequence[Sequence[int]]] = None,
                              prefilled: Optional[dict] = None,
                              deadline_ts: Optional[float] = None):
        """Async generator of token ids as they are produced. NOTE:
        tokens belonging to a stop sequence may already have been
        yielded by the time the match completes — streaming consumers
        that care should trim client-side (the non-streaming result is
        always trimmed)."""
        r = self._submit(tokens, max_new_tokens, temperature, eos_id,
                         top_p=top_p, top_k=top_k, stop=stop,
                         prefilled=prefilled, deadline_ts=deadline_ts)
        r.stream = asyncio.Queue()
        while True:
            t = await r.stream.get()
            if t is None:
                return
            if isinstance(t, BaseException):
                raise t
            tok, t_put = t
            t_out = time.monotonic()
            self._m["stream_lag"].observe(t_out - t_put)
            yield tok
            # back for the next: what the consumer did with this one
            self._m["stream_consume"].observe(time.monotonic() - t_out)

    async def generate_prefilled(self, tokens, prefilled: dict,
                                 **kw) -> dict:
        return await self.generate(tokens, prefilled=prefilled, **kw)

    def generate_stream_prefilled(self, tokens, prefilled: dict, **kw):
        return self.generate_stream(tokens, prefilled=prefilled, **kw)

    def _submit(self, tokens, max_new_tokens, temperature, eos_id,
                top_p=1.0, top_k=0, stop=None, prefilled=None,
                deadline_ts=None):
        if self._stopped:
            raise RuntimeError("engine is stopped")
        if deadline_ts is not None and time.time() > deadline_ts:
            # spent before submission: fail NOW — don't occupy queue
            # space the scheduler would only throw away later
            from ray_tpu.serve.fault import DeadlineExceeded
            raise DeadlineExceeded("budget spent before submission")
        tokens = list(map(int, tokens))
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        # prompts longer than the largest bucket stream through chunked
        # prefill (lm.prefill_chunk); only max_len bounds them
        if len(tokens) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt+generation ({len(tokens)}+{max_new_tokens}) "
                f"exceeds max_len {self.max_len}")
        stop = [list(map(int, s)) for s in stop] if stop else None
        if stop and any(not s for s in stop):
            raise ValueError("empty stop sequence")
        if prefilled is not None and self._stateful:
            raise ValueError(
                "a prefilled request (the prefill/decode hand-off) is not "
                "supported with state layers: the payload carries K and V "
                "rows and no recurrent state or conv tail to decode from")
        if prefilled is not None:
            # validate at submission: a malformed payload must fail THIS
            # request, not blow up the shared scheduler loop mid-admit
            for k in ("k", "v", "logits", "length"):
                if k not in prefilled:
                    raise ValueError(f"prefilled payload missing {k!r}")
            if int(prefilled["length"]) != len(tokens):
                raise ValueError(
                    f"prefilled length {prefilled['length']} != prompt "
                    f"length {len(tokens)}")
            if prefilled["k"].shape[1] > self.max_len:
                raise ValueError(
                    f"prefilled KV spans {prefilled['k'].shape[1]} "
                    f"positions > decode max_len {self.max_len} "
                    "(prefill/decode bucket configs disagree)")
        r = _Request(tokens, max_new_tokens, temperature, eos_id,
                     top_p=float(top_p), top_k=int(top_k), stop=stop,
                     prefilled=prefilled, deadline_ts=deadline_ts,
                     trace=tracing.current_context())
        self._seq_counter += 1
        r.seq = self._seq_counter
        if self._spec:
            r.drafter = specdec.PromptLookupDrafter(
                k=self._spec_k, ngram_max=self._spec_ngram,
                window=self._spec_window)
        self._waiting.put_nowait(r)
        self._requests += 1
        self._ensure_loop()
        return r

    def _result(self, r: _Request) -> dict:
        out = {"tokens": r.out,
               "ttft_s": (r.first_token_at or 0) - r.submitted,
               "prefix_hit_tokens": r.prefix_hit}
        if self.detokenize is not None:
            out["text"] = self.detokenize(r.out)
        return out

    async def stop(self):
        self._stopped = True
        try:
            from ray_tpu.util import forensics
            forensics.unregister_state_provider(f"llm_engine:{id(self):x}")
        except Exception:  # noqa: BLE001
            pass
        if self._loop_task is not None:
            # The loop may be parked awaiting new work — cancel wakes it.
            self._loop_task.cancel()
            try:
                await self._loop_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    # --- scheduler loop -------------------------------------------------

    def _ensure_loop(self):
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.ensure_future(self._run())

    def _bucket_for(self, n: int) -> int:
        return lm.bucket_for(self.buckets, n)

    def _pop_candidate(self) -> Optional[_Request]:
        """Next admissible request: pool-parked admits first (FIFO —
        re-tried once blocks free up), then the waiting queue.
        Deadline-expired candidates fail fast here."""
        while self._blocked:
            cand = self._blocked.popleft()
            if cand.deadline_ts is not None and \
                    time.time() > cand.deadline_ts:
                self._expire(cand, None)
                continue
            return cand
        while not self._waiting.empty():
            cand = self._waiting.get_nowait()
            if cand.deadline_ts is not None and \
                    time.time() > cand.deadline_ts:
                self._expire(cand, None)
                continue
            return cand
        return None

    async def _run(self):
        loop = asyncio.get_running_loop()
        phase = self._phase
        try:
            while not self._stopped:
                # 1) admit waiting requests into free slots (prefill) —
                #    BEFORE the decode step, for low TTFT. Requests
                #    whose deadline passed while queued fail fast here:
                #    prefilling them would spend device time the client
                #    already gave up on.
                for slot in range(self.max_slots):
                    if self._slots[slot] is not None:
                        continue
                    r = self._pop_candidate()
                    if r is None:
                        continue
                    if r.kv_alloc is None:
                        # full-horizon block reservation at admission:
                        # decode can then never fail mid-flight on pool
                        # pressure — overload parks the ADMIT instead
                        # (FIFO; a parked head-of-line also blocks the
                        # queue behind it, preserving arrival order)
                        with phase("admit.alloc"):
                            try:
                                alloc = self._kv.alloc_seq(
                                    r.seq, r.tokens, r.max_new_tokens)
                            except kvcache.BlockPoolExhausted as e:
                                self._fail(r, None, e)
                                continue
                            if alloc is None:
                                self._blocked.appendleft(r)
                                break
                            r.kv_alloc = alloc
                            r.prefix_hit = alloc["hit_tokens"]
                            # publish live-bytes/headroom NOW: a wave
                            # of long decodes would otherwise report
                            # init-time gauges until the first finish
                            # — exactly the overload window the gauges
                            # exist for
                            self._kv_account()
                    try:
                        tok = await loop.run_in_executor(
                            None, self._admit_sync, slot, r)
                    except KVHandoffError as e:
                        # a dead/freed remote KV handle fails ITS request
                        # only — the shared loop and other slots live on
                        # (resolution happens before any pool write, so
                        # no partial state was left behind; the slot is
                        # passed so the table row set before the
                        # failure reverts to trash with the blocks)
                        self._fail(r, slot, e)
                        continue
                    except BaseException as e:  # noqa: BLE001
                        # any other admit failure kills the loop below —
                        # but the candidate is in no queue and no slot
                        # yet, so the outer sweep can't see it: fail it
                        # HERE or its caller hangs forever on a future
                        # nobody owns (the old behavior: a broken
                        # prefill path turned into a silent stall)
                        self._fail(r, slot, e)
                        raise
                    with phase("emit"):
                        self._emit_token(r, tok, slot)
                # deadline-cancel active slots at the block boundary:
                # the slot is reclaimed NOW (the next admit pass refills
                # it) instead of decoding to max_new_tokens for a client
                # whose budget is spent
                now = time.time()
                for i, r in enumerate(self._slots):
                    if r is not None and r.deadline_ts is not None \
                            and now > r.deadline_ts:
                        self._expire(r, i)
                active = [i for i, r in enumerate(self._slots)
                          if r is not None]
                fl = self._inflight
                if not active and fl is None:
                    self._gap_from = None   # nobody is stalled by it
                    if self._blocked:
                        # pool-parked admits with nothing running can
                        # only be waiting on eviction — re-try shortly
                        # instead of parking on the (possibly empty)
                        # waiting queue forever
                        with phase("idle"):
                            await asyncio.sleep(0.01)
                        continue
                    if self._waiting.empty():
                        # idle: park until work arrives
                        with phase("idle"):
                            r = await self._waiting.get()
                        self._waiting.put_nowait(r)
                    continue
                # 2a) speculative verify round (engine spec mode): ask
                # each active slot's drafter for a continuation guess.
                # Any drafting slot flips this round from "one decode
                # step per emitted token" to ONE batched verify forward
                # scoring 1..k+1 positions per slot — non-drafting
                # slots co-batch at width 1 (their row emits exactly
                # its first verified token). When NOBODY drafts (spec
                # off, drafters cooling off on low-hit prompts, or
                # nothing to match yet) the engine falls through to the
                # vanilla block path below — that fallback plus the
                # drafter's accept-rate backoff is what bounds the
                # adversarial-prompt overhead. A drafter reads the
                # host's copy of its request's tokens, so while a slot
                # holds one no block is left in flight (2, below) and
                # this always runs with every token read back.
                drafts: dict = {}
                if self._spec and fl is None:
                    with phase("verify.prepare"):
                        for i in active:
                            r = self._slots[i]
                            if r.drafter is None:
                                continue
                            # leave room for the bonus token and never
                            # draft past the request's horizon
                            budget = min(
                                self._spec_k,
                                r.max_new_tokens - len(r.out) - 1,
                                self.max_len - len(r.tokens)
                                - len(r.out) - 1)
                            if budget < 1:
                                continue
                            d = r.drafter.propose(r.tokens + r.out,
                                                  budget)
                            if d:
                                drafts[i] = d
                if drafts:
                    await self._spec_round(loop, active, drafts)
                    with phase("yield"):
                        await asyncio.sleep(0)
                    continue
                # 2) a BLOCK of decode steps for every slot that has
                # tokens left, ENQUEUED BEFORE THE BLOCK IN FLIGHT IS
                # READ BACK: the device runs programs in the order they
                # were enqueued, so block n + 1 starts the moment block
                # n ends, and preparing it, the thread hops, its launch,
                # the copy back of block n's tokens, their accounting,
                # their emission and the turn of the event loop in
                # which the streams' consumers wake all run behind the
                # device, not in front of it. Never more than one block
                # ahead: a request admitted at this turn's head has its
                # prefill enqueued behind the block in flight and joins
                # the block enqueued now.
                # What block n + 1 needs of block n it takes without the
                # host: sampling is on-device (lm.sample) and its first
                # tokens are block n's last row, left on the device
                # (kvcache.carry_tokens); write positions are host
                # arithmetic (block n's steps added); the tables are
                # known because admission reserved the whole horizon.
                # One host sync per block, only token ids come back.
                # Block size is bounded by each slot's remaining budget
                # AFTER the block in flight, so no request over-runs
                # max_new_tokens or the cache, and a slot whose budget
                # ends inside block n is not in block n + 1.
                # A slot that ends where the host could not foresee it
                # (eos or a stop sequence mid-block, a deadline, a
                # failure) wastes its remaining steps of block n and
                # rides out block n + 1 (discarded at emit, slot freed
                # at the sync): the batch's throughput is worth more
                # than the waste, the headroom bounds keep its cache
                # writes inside its own blocks, and _free_kv holds
                # those blocks until block n + 1 has been read back.
                left = {i: self._left(i, fl) for i in active}
                new = None
                if any(n > 0 for n in left.values()):
                    with phase("decode.prepare") as prep:
                        new = self._prepare(left, fl)
                    new.t_prep = prep.t1
                # read back the block that was in flight; with none,
                # the new one stays in flight for the next turn, unless
                # a slot's drafter needs its tokens on the host now
                back = fl
                if back is None and any(
                        self._slots[i].drafter is not None for i in active):
                    back = new
                if new is None and back is None:
                    raise RuntimeError(
                        "a live slot with no step left and nothing in "
                        "flight: it should have finished at its emit")
                await loop.run_in_executor(
                    None, self._decode_sync, new, fl, back)
                self._inflight = new if back is not new else None
                if back is None:
                    continue
                with phase("decode.account") as acc:
                    # what the block spent between the two threads
                    self._m["decode_hop"].observe(
                        (back.t_disp - back.t_prep)
                        + (acc.t0 - back.t_back))
                    self._account_block(back.lens, back.steps,
                                        back.counts)
                    # the block's own window opens where its
                    # predecessor's closed, if it was enqueued earlier:
                    # two blocks' windows never overlap
                    self._dev_span = (max(back.t_disp, self._dev_span[1]),
                                      back.t_back)
                    self._record_block(
                        len(back.reqs), back.steps, back.member_traces,
                        back.first_ctx, block=back.steps)
                    # it has run: nothing enqueued names the blocks of
                    # the requests that ended under it any more
                    for release in back.held:
                        release()
                with phase("emit"):
                    for step in range(back.steps):
                        for i, r in back.reqs.items():
                            # finished earlier this block, or before it
                            # (its rows are waste), or the slot is
                            # another request's by now
                            if self._slots[i] is not r:
                                continue
                            self._emit_token(r, int(back.out[step, i]), i)
                    # whoever is still in its slot waits for the next
                    # block from the moment this one was read back,
                    # unless that block is already enqueued
                    self._gap_from = back.t_back if (
                        self._inflight is None and any(
                            self._slots[i] is r
                            for i, r in back.reqs.items())) else None
                    self._gap_admit = 0.0
                with phase("yield"):
                    await asyncio.sleep(0)
        except BaseException as e:  # noqa: BLE001 — fail all requests
            for i, r in enumerate(self._slots):
                if r is not None:
                    self._fail(r, i, e)
            while self._blocked:
                self._fail(self._blocked.popleft(), None, e)
            while not self._waiting.empty():
                self._fail(self._waiting.get_nowait(), None, e)
            raise
        finally:
            self._discard_inflight()
            for i, r in enumerate(self._slots):
                if r is not None:
                    self._finish(r, i)

    def _discard_inflight(self) -> None:
        """The loop is ending (a failure, stop()): the block in flight
        is never read back. Its results are dropped and the releases it
        held are made now: whatever touches those blocks next is
        enqueued behind it."""
        blk, self._inflight = self._inflight, None
        if blk is not None:
            for release in blk.held:
                release()

    def _left(self, slot: int, fl: Optional[_Block]) -> int:
        """Decode steps ``slot``'s request can still take once the
        block in flight (``fl``) has run: its budget and the cache's,
        less what it has emitted and what ``fl`` will emit for it."""
        r = self._slots[slot]
        have = len(r.out)
        if fl is not None and fl.reqs.get(slot) is r:
            have += fl.steps
        return min(r.max_new_tokens - have,
                   self.max_len - len(r.tokens) - have)

    def _prepare(self, left: dict, fl: Optional[_Block]) -> _Block:
        """The loop's ``decode.prepare`` phase: the next block, over
        the slots of ``left`` (slot -> steps left after the block in
        flight) that have steps left. Its size is the smallest of them
        rounded down to a power of two (bounded variants); a slot of
        ``fl`` starts from ``fl``'s last row and ``fl.steps`` positions
        further on, any other from the host's last token."""
        slots = [i for i, n in left.items() if n > 0]
        block = min(self.steps_per_sync, *(left[i] for i in slots))
        block = 1 << (block.bit_length() - 1)       # pow2, rounded down
        n = self.max_slots
        tokens = np.zeros((n,), np.int32)
        keep = np.zeros((n,), bool)
        lengths = np.zeros((n,), np.int32)
        temps = np.zeros((n,), np.float32)
        top_ps = np.ones((n,), np.float32)
        top_ks = np.zeros((n,), np.int32)
        reqs, lens = {}, []
        for i in slots:
            r = reqs[i] = self._slots[i]
            at = len(r.tokens) + len(r.out)
            if fl is not None and fl.reqs.get(i) is r:
                keep[i] = True
                at += fl.steps
            else:
                tokens[i] = r.out[-1]
            lens.append(at)
            # the last emitted token's KV lands in the block's first
            # step
            lengths[i] = at - 1
            temps[i] = r.temperature
            top_ps[i] = r.top_p
            top_ks[i] = r.top_k
        member_traces, first_ctx = self._members(slots)
        self._set_aside(reqs, lens, block)
        # the tables as they are NOW, copied: admission and _free_kv
        # write the live ones while this block is in flight. A slot that
        # is not in the block (idle, or ending inside the block in
        # flight) gets a row of TRASH: the program reads off it that the
        # slot holds no request, and its kernels skip it.
        out = [i for i in range(n) if i not in reqs]
        tables = {kind: t.copy() for kind, t in self._tables.items()}
        for t in tables.values():
            t[out] = kvcache.TRASH
        return _Block(block, reqs, lens, tokens, keep, lengths, tables,
                      temps, top_ps, top_ks, member_traces, first_ctx)

    def _set_aside(self, reqs: dict, lens: List[int], block: int) -> None:
        """Before a decode block of ``block`` steps over ``reqs`` (slot
        -> request; ``lens`` the positions each holds as the block
        starts: prompt + emitted + what the block in flight emits): the
        manager moves the table rows that move (a window layer gets the
        blocks the block writes and gives back the ones the sequence's
        window has passed: KVBlockManager.advance); then what the pool
        holds by layer kind is observed. The block in flight may
        still read a ring block given back here: whatever writes it
        next (this block, through the sequence that takes it, or a
        later prefill) is enqueued behind the block in flight, and the
        device runs them in that order."""
        for (i, r), n in zip(reqs.items(), lens):
            for kind, row in self._kv.advance(r.seq, n - 1, block).items():
                self._tables[kind][i] = row
        used = self._kv.used_by_kind()
        for kind, n in used.items():
            self._m["kv_blocks_" + kind].observe(n)
        for kind, n in self._kv.freed_by_kind().items():
            self._m[f"kv_{kind}_freed"].observe(n - self._freed.get(kind, 0))
            self._freed[kind] = n
        self._m["kv_used_bytes"].observe(self._bytes_of(used))
        self._m["kv_live_tokens"].observe(sum(lens))

    def _account_block(self, lens: List[int], block: int,
                       counts: Optional[dict]) -> None:
        """The work of the block just read back, counted (the loop's
        ``decode.account`` phase): steps, slot-steps, the positions
        each step attended (``lens`` at its start, one more a step; a
        slot that hits eos mid-block still ran its steps), what the
        kernel's walk fetched for them, the slot states a state layer's
        rule moved, and the expert layers' device scalars that came back
        with the tokens."""
        n = len(lens)
        self._m["block_steps"].observe(block)
        self._m["slot_steps"].observe(n * block)
        self._m["ctx_tokens"].observe(
            block * sum(lens) + n * block * (block - 1) // 2)
        if self._kv_impl == "paged_flash":
            # the live blocks of every slot of the block at every step
            # (a slot that is not in it is not walked); a window layer's
            # from its window's first block on, so the mean a layer
            from ray_tpu.ops.pallas.paged_attention import \
                fetched_positions_run as run
            bs = self._block
            fetched = sum(layers * sum(run(a, block, bs, w) for a in lens)
                          for layers, w in self._walks)
            # over the layers that attend (every layer, but for a model
            # whose layers are each one mixer)
            self._m["kv_fetch_tokens"].observe(
                fetched / sum(layers for layers, _ in self._walks))
        if self._stateful:
            # a conv tail alone has no kernel: every slot's passes through
            # the step's ``where`` whatever the implementation
            kernel = kvcache.POOL_KEYS[kvcache.STATE][0] in self._pool
            moved = kvcache.state_slot_steps(
                self._kv_impl if kernel else "gather", self.max_slots, n,
                block)
            self._state_slot_steps += moved
            self._m["state_slot_steps"].observe(moved)
        if counts is not None:
            for key, per_step in counts.items():
                self._m["moe_" + key].observe(int(per_step.sum()))
            self._m["moe_experts_held"].observe(
                block * self.cfg.n_held * self._expert_layers)

    def _members(self, active: List[int]):
        """(sorted trace ids of the batch's traced requests, the first
        such request's context)."""
        ctxs = [self._slots[i].trace for i in active
                if self._slots[i] is not None
                and self._slots[i].trace is not None]
        return (sorted({c.trace_id for c in ctxs}),
                ctxs[0] if ctxs else None)

    def _record_block(self, slots: int, tokens_per_slot: float,
                      member_traces: List[str], first_ctx, **attrs):
        """What one decode block (or verify round) leaves behind, all
        from the ONE interval ``_dev_span`` holds, the block's own
        window (its predecessor's read-back end, or its own dispatch
        start where nothing was in flight, to its read-back end; a
        verify round's dispatch start to read-back end): the
        batch-size and TPOT observations, the window itself
        (``llm_decode_block_window_s``: llm_tpot_s sums per-block
        ratios and cannot be divided by steps, this sum can), one span
        linked to every member trace (the block is shared compute, so
        each member's waterfall pulls it in via the links; it names
        the attention impl the block ran), and the same interval as a
        device-compute window for the duty-cycle estimator. The
        EXEMPLAR can only name one trace: the member
        whose context was bound on the executor thread, so following
        it (`ray-tpu trace <id>`) shows any decode-path compile span
        stamped during this block, not a sibling's waterfall."""
        t0, t1 = self._dev_span
        ex = first_ctx.trace_id if first_ctx is not None else None
        self._m["batch"].observe(slots, exemplar=ex)
        self._m["tpot"].observe((t1 - t0) / tokens_per_slot,
                                exemplar=ex)
        self._m["block_window"].observe(t1 - t0)
        w0, w1 = tracing.wall(t0), tracing.wall(t1)
        tracing.record_batch_span(
            "engine", "decode", member_traces, w0, w1, slots=slots,
            kv_impl=self._kv_impl, **attrs)
        devmon.record_device_window("decode", w0, w1, trace=ex or "")

    def _admit_sync(self, slot: int, r: _Request) -> int:
        """Prefill entry (executor thread): binds the request's trace
        context for the duration of the admit so any XLA compile it
        triggers (a cold shape bucket) is stamped with the request's
        trace id — util/devmon.py's compile listener reads the ambient
        context, and the span then rides this request's `ray-tpu
        trace` waterfall as a dev:compile lane."""
        if r.trace is None:
            return self._admit_impl(slot, r)
        tok = tracing.set_request_context(r.trace)
        try:
            return self._admit_impl(slot, r)
        finally:
            tracing.reset_request_context(tok)

    @staticmethod
    def _take_handoff(x):
        """Unwrap the device-path KV handoff (reference: RDT
        tensor_transport_manager.py:37): same-process resolution
        never leaves HBM; cross-process is one fetch + device_put;
        the handle is single-use (freed here — the prefill replica's
        copy dies at handoff instead of surviving next to the decode
        copy). A dead handle becomes a per-request KVHandoffError.
        Plain arrays pass through for the host-staged path."""
        from ray_tpu.runtime.device_store import TensorRef
        if not isinstance(x, TensorRef):
            return x
        try:
            arr = x.resolve()
        except Exception as e:
            raise KVHandoffError(
                f"prefilled KV handle unresolvable: {e}") from e
        x.free()                # cache write below copies it
        return arr

    def _admit_impl(self, slot: int, r: _Request) -> int:
        """Prefill (executor thread): the scheduler already reserved
        the block table (r.kv_alloc); write the prompt's KV through it
        and return the first sampled token. Three paths: shipped-KV
        handoff (disaggregated: the forward ran on the remote tier),
        cold bucketed prefill (one lm.prefill forward padded to its
        bucket, scatter), and prefix-hit / long-prompt chunked prefill
        (gather cached prefix blocks, run lm.prefill_chunk on the
        suffix only — the prefix's device time is ~eliminated)."""
        _, jnp = _jx()
        n = len(r.tokens)
        r.admitted_at = time.monotonic()
        self._m["queue"].observe(r.admitted_at - r.submitted)
        if r.trace is not None:
            # engine hop, segment 1: submit -> slot admission
            tracing.record_request_span(
                "engine", "queue", r.trace, r.trace.span_id,
                r.t_submit_wall,
                r.t_submit_wall + (r.admitted_at - r.submitted))
        hit = r.prefix_hit
        for kind, row in r.kv_alloc["tables"].items():
            self._tables[kind][slot] = row
        with self._phase("prefill.dispatch") as disp:
            self._block_ended(disp.t0)
            if r.prefilled is not None:
                # device TTFT for a disaggregated request is the
                # handoff resolution + pool write on THIS engine
                p = r.prefilled
                r.prefilled = None      # free the host copy after write
                take = self._take_handoff
                k_np = np.asarray(take(p["k"]))
                v_np = np.asarray(take(p["v"]))
                logits, ran = take(p["logits"]), 0
                r.handoff_bytes = int(k_np.nbytes + v_np.nbytes)
                self._kvm["handoff_bytes"].inc(r.handoff_bytes)
                acc_len = self._acc_len()
                pad = acc_len - k_np.shape[1]

                def padded(rows):   # (layers, positions, ...): K/V or latent
                    return jnp.asarray(np.pad(
                        rows, ((0, 0), (0, pad))
                        + ((0, 0),) * (rows.ndim - 2)))
                acc = {"k": padded(k_np), "v": padded(v_np)}
                # shared prefix blocks (a hit makes the shipped bytes
                # for them redundant) and beyond-horizon slots write
                # to trash
                self._pool = kvcache.scatter_table(
                    self._pool, acc,
                    self._targets(slot, self._table_w, hit), self._layout)
            elif hit == 0 and n <= self.buckets[-1]:
                # cache-cold short prompt: one lm.prefill forward,
                # padded only to its bucket; pad-garbage blocks
                # redirect to trash via the table's unallocated tail
                b = self._bucket_for(n)
                padded = lm.pad_prompt(r.tokens, b)
                logits, kv = lm.prefill(self.params, jnp.asarray(padded),
                                        jnp.int32(n), self.cfg, b)
                nb = b // self._block
                self._pool = kvcache.scatter_bucket(
                    self._pool, kv, self._targets(slot, nb), nb,
                    self._layout)
                self._take_state(slot, kv)
                ran = n
                self._count_prefill(
                    1, lm.chunk_expanded_rows(self.cfg, b, 0, b))
            else:
                logits = self._prefill_into_blocks(r, hit, slot, disp.t0)
                ran = n - self._prefill_start(hit)
        return self._first_token(slot, r, disp, logits, ran)

    def _take_state(self, slot: int, state: dict) -> None:
        """An admitted request's state layers: what its prefill left at the
        prompt's length (started from zeros: ``lm.prefill``, or the chunked
        prefill's accumulator) becomes ``slot``'s, whole. Whatever the
        slot's last request left there is gone, and the slot is this
        request's until it is freed: a decode block touches it only while
        the slot's table row names the request's blocks."""
        if not self._stateful:
            return
        self._pool = kvcache.write_state(self._pool, state, slot)
        self._state_admits += 1

    def _count_prefill(self, chunks: int, rows: int) -> None:
        """One admitted request's prefill forwards and the cache rows
        they expanded to per-head K and V (none but for a latent model:
        ``lm.chunk_expanded_rows``)."""
        self._m["prefill_chunks"].observe(chunks)
        self._m["latent_rows_expanded"].observe(rows)

    def _targets(self, slot: int, width: int, hit: int = 0) -> dict:
        """The physical ids a prefill's KV goes through, by layer kind:
        ``slot``'s table rows over ``width`` blocks (a bucket wider
        than the table ends in trash: pad garbage), the first ``hit``
        tokens' blocks to trash (a shared prefix's: never written). A
        row names trash wherever it holds no block: past the horizon
        and, in a window layer, below the first decode step's window."""
        _, jnp = _jx()
        w = min(width, self._table_w)
        out = {}
        for kind, tables in self._tables.items():
            ids = np.full((width,), kvcache.TRASH, np.int32)
            ids[:w] = tables[slot, :w]
            ids[:hit // self._block] = kvcache.TRASH
            out[kind] = jnp.asarray(ids)
        return out

    def _first_token(self, slot: int, r: _Request, disp, logits,
                     ran: int) -> int:
        """The end of every admit path: wait for the prefill the
        ``disp`` phase dispatched (its logits, and the cache it
        wrote), then sample the first token on the host.
        Dispatch is async, so the wall clock alone cannot attribute a
        slow first token to compute or to queueing: dispatch start to
        wait end bounds the DEVICE portion of TTFT. ``ran`` is how
        many prompt tokens went through a prefill forward.

        The prefill was enqueued behind the decode block in flight, if
        there is one, so the wait is cut in two: ``prefill.behind``
        waits for THAT block's outputs, and its exit is when the block
        ended on the device, unless a look during the dispatch found it
        ended already (``_block_ended``). ``llm_ttft_device_s`` still
        runs from the dispatch to the results, so the prefill's own
        device time is that less ``prefill.behind`` (and, for a chunked
        prefill, less the part of the block that its first chunk's
        launch sat out inside ``prefill.dispatch``)."""
        jax, _ = _jx()
        fl = self._inflight
        if fl is not None:
            with self._phase("prefill.behind") as behind:
                jax.block_until_ready((fl.out, fl.last))
            self._block_ended(behind.t1, at=behind.t1)
        with self._phase("prefill.wait") as wait:
            logits_np = np.asarray(logits)
            jax.block_until_ready(kvcache.pool_k(self._pool))
        if ran:
            self._m["prefill_tokens"].observe(ran)
        r.kv_written = True
        r.prefill_device_s = wait.t1 - disp.t0
        self._record_prefill_span(r, disp.t0, wait.t1)
        self._slots[slot] = r
        with self._phase("prefill.sample"):
            return self._sample_one(logits_np, r)

    def _block_ended(self, opened: float,
                     at: Optional[float] = None) -> None:
        """The host learns when the decode block in flight ended on the
        device (``_Block.t_end``): where the stall of the slots that go
        on decoding starts (``_dispatch``). Called by an admission
        (executor thread) wherever it can know: with ``at`` the exit of
        the ``prefill.behind`` that waited for the block; without, a
        look that does not wait, at the opening of ``prefill.dispatch``
        and after each chunk's launch. The stamp is late by at most the
        launch before the look (6-9 ms for a chunk on a v5e, ~4 ms for
        a one-forward prefill: the block may have ended under it), and
        it is taken once a block: a second admission of the same turn
        finds it taken. The gap's admission share starts with it: the
        phase the look was made in (open since ``opened``) adds its
        WHOLE duration to ``_gap_admit`` at its exit, so what came
        before the stamp is taken off here."""
        fl = self._inflight
        if fl is None or fl.t_end:
            return
        if at is None:
            if not fl.out.is_ready():
                return
            at = time.monotonic()
        fl.t_end = at
        self._gap_admit = opened - at

    def _acc_len(self) -> int:
        """Accumulator length for block-table prefill: the full table
        span rounded to a chunk multiple PLUS one slack chunk — a
        prefix-hit suffix whose first piece starts off the chunk grid
        can bucket-pad past the next boundary, and dynamic_update_slice
        must never clamp (a clamped write silently shifts the chunk
        and corrupts earlier positions)."""
        chunk = self.buckets[-1]
        span = self._table_w * self._block
        return ((span + chunk - 1) // chunk) * chunk + chunk

    def _prefill_start(self, hit: int) -> int:
        """First position the suffix prefill computes for a
        ``hit``-token prefix hit. On a flash-capable chunked-prefill
        path the start rounds DOWN to the chunk grid: every piece then
        sits at a chunk-multiple offset and enters the per-offset
        COMPILED flash variants (bounded: ceil(max_len/chunk)
        compiles) instead of minting a fresh compile per distinct hit
        length — or falling to the dynamic-offset XLA path. The
        recomputed rows (< one chunk) land in full hit blocks, whose
        scatter targets are already trash, and recomputation is
        bitwise-identical to the cached values (same chunk grid a cold
        request ran), so reuse accounting and parity are untouched."""
        if hit == 0:
            return 0
        if self._prefill_impl not in ("flash", "flash_interpret"):
            return hit
        chunk = self.buckets[-1]
        return (hit // chunk) * chunk

    def _prefill_into_blocks(self, r: _Request, hit: int, slot: int,
                             t_disp: float):
        """Prefix-hit (and long-prompt) prefill: gather the table's
        cached blocks into a contiguous accumulator, run the suffix
        through lm.prefill_chunk at the prefix offset (pieces aligned
        to the absolute chunk grid so a cold and a hit request compute
        every suffix row identically — the bitwise-parity contract the
        tests pin), then scatter the NEW positions' KV back into the
        request's own blocks. Shared prefix blocks are never written
        (their scatter targets are the trash block).

        A chunk's launch returns only once the accumulator it is
        donated is ready, that is once the program before it has run
        (on a v5e: PERF.md, PR 56): the first chunk's launch sits out
        what is left of the decode block in flight, and each later one
        the chunk before it. So the block's end is looked for after
        every launch (``_block_ended``; ``t_disp`` is where the
        enclosing ``prefill.dispatch`` opened), and not first at the
        end of a dispatch that lasts as long as the prefill."""
        _, jnp = _jx()
        n = len(r.tokens)
        chunk = self.buckets[-1]
        acc = kvcache.gather_table(
            self._pool, self._targets(slot, self._table_w),
            self._acc_len(), self._layout)
        # a state layer's part of the accumulator: zeros, handed on from
        # chunk to chunk ({} for a model without state layers)
        acc.update(kvcache.fresh_state(self._pool))
        off = self._prefill_start(hit)
        logits = None
        chunks = rows = 0
        while off < n:
            end = min(n, ((off // chunk) + 1) * chunk)
            part = r.tokens[off:end]
            b = self._bucket_for(len(part))
            padded = lm.pad_prompt(part, b)
            logits, acc = lm.prefill_chunk(
                self.params, jnp.asarray(padded),
                jnp.int32(len(part)), jnp.int32(off), acc, self.cfg)
            self._block_ended(t_disp)
            chunks += 1
            rows += lm.chunk_expanded_rows(self.cfg, b, off,
                                           self._acc_len())
            off = end
        self._count_prefill(chunks, rows)
        self._pool = kvcache.scatter_table(
            self._pool, acc, self._targets(slot, self._table_w, hit),
            self._layout)
        self._take_state(slot, acc)
        return logits

    @staticmethod
    def _record_prefill_span(r: _Request, t0: float, t1: float) -> None:
        """Engine hop, segment 2: the prefill device compute that
        produced the first token (dispatch start to the end of the
        wait, both stamped by their phases: the DEVICE portion of
        TTFT). The same interval feeds the duty-cycle estimator as a
        device window."""
        w0, w1 = tracing.wall(t0), tracing.wall(t1)
        devmon.record_device_window(
            "prefill", w0, w1,
            trace=r.trace.trace_id if r.trace is not None else "")
        if r.trace is None:
            return
        tracing.record_request_span(
            "engine", "prefill", r.trace, r.trace.span_id, w0, w1,
            tokens=len(r.tokens))

    def _decode_sync(self, new: Optional[_Block], fl: Optional[_Block],
                     back: Optional[_Block]) -> None:
        """One turn's device work (executor thread): enqueue ``new``
        behind ``fl``, the block in flight, then read ``back`` back
        (``fl``, or ``new`` itself when it may not stay in flight).
        The first member trace of the batch is bound meanwhile so a
        decode-path XLA compile — a new block-size variant, a filter
        toggle — stamps a member's trace id onto its dev:compile span
        instead of vanishing into unattributed time."""
        ctx = (new or back).first_ctx
        tok = tracing.set_request_context(ctx) if ctx is not None else None
        try:
            if new is not None:
                self._dispatch(new, fl)
            if back is not None:
                self._readback(back)
        finally:
            if tok is not None:
                tracing.reset_request_context(tok)

    def _dispatch(self, blk: _Block, fl: Optional[_Block]) -> None:
        """Enqueue ``blk`` (asynchronous: the call returns once the
        program is launched). ``fl`` is the block in flight, not read
        back yet: the device starts ``blk`` the moment it ends, unless
        an admission's prefill was enqueued between them.

        Once the launch has returned, the stall of the slots that go on
        decoding is observed (``llm_decode_gap_s`` and its admission
        share, once a block that carries a request on), added to the
        engine's running totals, and every request new to a block takes
        its mark of them (``_finish`` reads the totals again: a
        request's marks leave out the tail of the one gap during which
        it was itself admitted)."""
        jax, jnp = _jx()
        # keep is set only for slots of the block in flight
        carried = bool(blk.keep.any())
        with self._phase("decode.dispatch") as disp:
            self._m["decode_ahead"].observe(0 if fl is None else 1)
            blk.t_disp = disp.t0
            self._step += blk.steps
            key = jax.random.fold_in(self._key, self._step)
            # The top-p/top-k filters cost two O(V log V) vocab sorts
            # per decode step: only pay them when some ACTIVE request
            # enabled a filter (None compiles the plain sampler — one
            # extra jit variant, bounded).
            filters_on = bool((blk.top_ps < 1.0).any()
                              or (blk.top_ks > 0).any())
            tp = jnp.asarray(blk.top_ps) if filters_on else None
            tk = jnp.asarray(blk.top_ks) if filters_on else None
            # first tokens: the block in flight's last row for the
            # slots that go on from it, never seen by the host; what
            # the host holds for the others. Either way they arrive
            # committed to one sharding: one compiled program a size.
            if carried:
                tokens = kvcache.carry_tokens(
                    fl.last, blk.tokens, blk.keep, self._tok_sharding)
            else:
                tokens = jax.device_put(blk.tokens, self._tok_sharding)
            blk.out, self._pool, blk.counts, blk.last = \
                kvcache.decode_steps_program(
                    self._pool, impl=self._kv_impl,
                    interpret=self._kv_interpret, mesh=self.mesh,
                    axis=self.tensor_axis)(
                    self.params, self._pool,
                    {k: jnp.asarray(t) for k, t in blk.tables.items()},
                    jnp.asarray(blk.lengths), tokens,
                    jnp.asarray(blk.temps), key, self.cfg, blk.steps,
                    tp, tk)
            self._kvm["attn_steps"].inc(
                blk.steps, tags={"impl": self._kv_impl})
            self._m["idle_slot_steps"].observe(
                (self.max_slots - len(blk.reqs)) * blk.steps)
        gap = None
        if carried:
            # a request of the block in flight goes on in this one.
            # Where an admission waited for that block the host knows
            # when it ended, and the slot stalled from there until this
            # launch returned; where nobody did, this block was
            # enqueued behind it and the device went from one to the
            # other
            gap = (max(0.0, disp.t1 - fl.t_end), self._gap_admit) \
                if fl.t_end else (0.0, 0.0)
        elif fl is None and self._gap_from is not None:
            # a request of the last block has been waiting for this one
            # since that block was read back
            gap = (max(0.0, disp.t1 - self._gap_from), self._gap_admit)
        if gap is not None:
            self._m["gap"].observe(gap[0])
            self._m["gap_admit"].observe(gap[1])
            self._stall = (self._stall[0] + gap[0],
                           self._stall[1] + gap[1])
        for r in blk.reqs.values():
            if r.stall_mark is None:
                r.stall_mark = self._stall

    def _readback(self, blk: _Block) -> None:
        """Wait for ``blk`` and copy its tokens (and the expert layers'
        counts, which come with them) to the host. A device error in
        the block surfaces here and fails the live requests."""
        jax, _ = _jx()
        with self._phase("decode.readback") as back:
            blk.out, blk.counts = jax.device_get((blk.out, blk.counts))
        blk.t_back = back.t1

    async def _spec_round(self, loop, active: List[int],
                          drafts: dict) -> None:
        """One draft-and-verify round: pad every active slot's
        [last_token, draft...] row to a verify-width bucket (repeating
        the last token — pad rows write garbage KV beyond the slot's
        logical length, masked out of every attention and overwritten
        by the next real write), score all positions in one forward,
        accept per slot (exact greedy match / rejection sampling in
        llm/spec.py), roll back the host block accounting for rejected
        tails, and emit 1..k+1 tokens per slot."""
        with self._phase("verify.prepare"):
            w = specdec.bucket_width(
                self._spec_buckets,
                1 + max(len(d) for d in drafts.values()))
            tokens_bw = np.zeros((self.max_slots, w), np.int32)
            lengths = np.zeros((self.max_slots,), np.int32)
            for i in active:
                r = self._slots[i]
                row = [r.out[-1]] + drafts.get(i, [])
                row += [row[-1]] * (w - len(row))
                tokens_bw[i] = row
                lengths[i] = len(r.tokens) + len(r.out) - 1
            member_traces, first_ctx = self._members(active)
        logits = await loop.run_in_executor(
            None, self._verify_sync, tokens_bw, lengths, first_ctx)
        self._gap_from = None       # llm_decode_gap_s is the plain
        emitted_total = 0           # block path's, not a verify round's
        with self._phase("verify.accept"):
            for i in active:
                r = self._slots[i]
                if r is None:
                    continue
                d = drafts.get(i, [])
                emitted, n_acc = specdec.accept_tokens(
                    logits[i, :len(d) + 1], d,
                    temperature=r.temperature, top_k=r.top_k,
                    top_p=r.top_p, rng=self._rng)
                if d:
                    r.drafter.record(len(d), n_acc)
                    r.spec_drafted += len(d)
                    r.spec_accepted += n_acc
                    self._specm["tokens"].inc(len(d),
                                              tags={"kind": "drafted"})
                    if n_acc:
                        self._specm["tokens"].inc(
                            n_acc, tags={"kind": "accepted"})
                    if len(d) > n_acc:
                        self._specm["tokens"].inc(
                            len(d) - n_acc, tags={"kind": "rejected"})
                        # host-side rollback of the rejected tail.
                        # Under the engine's full-horizon reservation
                        # this frees no blocks (min_blocks pins the
                        # reservation — giving promised blocks back
                        # could deadlock a re-acquire against a newer
                        # admit); it keeps the sequence's hash chain
                        # honest and IS the real rollback for COW
                        # forks (tests pin both).
                        self._kv.truncate_seq(
                            r.seq,
                            len(r.tokens) + len(r.out) + len(emitted),
                            min_blocks=self._kv.blocks_needed(
                                len(r.tokens), r.max_new_tokens))
                emitted_total += len(emitted)
                for t in emitted:
                    if self._slots[i] is not r:
                        break   # finished mid-accept (eos/stop/
                                # max_new): the tail of an accepted
                                # draft is dropped
                    self._emit_token(r, int(t), i)
        with self._phase("decode.account"):
            self._record_block(
                len(active),
                max(1.0, emitted_total / max(1, len(active))),
                member_traces, first_ctx, block=emitted_total,
                spec_k=w - 1)

    def _verify_sync(self, tokens_bw: np.ndarray, lengths: np.ndarray,
                     trace_ctx: Optional[tracing.TraceContext] = None
                     ) -> np.ndarray:
        """Returns (slots, w, vocab) f32 verify logits; binds the first
        member trace like _decode_sync so a cold verify-width compile
        is attributed to a real request."""
        if trace_ctx is None:
            return self._verify_impl(tokens_bw, lengths)
        tok = tracing.set_request_context(trace_ctx)
        try:
            return self._verify_impl(tokens_bw, lengths)
        finally:
            tracing.reset_request_context(tok)

    def _verify_impl(self, tokens_bw: np.ndarray,
                     lengths: np.ndarray) -> np.ndarray:
        _, jnp = _jx()
        with self._phase("verify.dispatch") as disp:
            logits, self._pool = kvcache.paged_verify_steps(
                self.params, self._pool,
                {k: jnp.asarray(t) for k, t in self._tables.items()},
                jnp.asarray(lengths), jnp.asarray(tokens_bw), self.cfg,
                impl=self._kv_impl, interpret=self._kv_interpret,
                mesh=self.mesh, axis=self.tensor_axis)
            self._kvm["attn_steps"].inc(1, tags={"impl": self._kv_impl})
        with self._phase("verify.readback") as back:
            logits = np.asarray(logits)
        self._dev_span = (disp.t0, back.t1)
        return logits

    def _sample_one(self, logits: np.ndarray, r: _Request) -> int:
        """Host-side sampling for the FIRST token (prefill output is a
        single logits vector). Built on spec.host_probs ->
        lm.filter_logits — the ONE temperature -> top-k -> top-p
        transform shared with the on-device sampler and the
        speculative verify-acceptance path, so the three can never
        drift (this host path is also the numpy reference the device
        sampler is parity-tested against)."""
        if r.temperature <= 0:
            return int(np.argmax(logits))
        p = specdec.host_probs(np.asarray(logits), r.temperature,
                               r.top_k, r.top_p)
        return int(self._rng.choice(len(p), p=p))

    def _emit_token(self, r: _Request, tok: int, slot: int):
        """Append one sampled token; finish the request if done."""
        r.last_token_at = now = time.monotonic()
        r.emitted += 1
        if r.first_token_at is None:
            r.first_token_at = now
            wall = now - r.submitted
            self._ttft_sum += wall
            self._ttft_count += 1
            self._m["ttft_wall"].observe(wall)
            # device time is a sub-interval of the wall interval; min()
            # guards the invariant against clock jitter. The exemplar
            # links the TTFT bucket to the concrete request trace.
            self._m["ttft_device"].observe(
                min(r.prefill_device_s, wall),
                exemplar=r.trace.trace_id if r.trace else None)
        r.out.append(tok)
        self._tokens_generated += 1
        if r.stream is not None:
            r.stream.put_nowait((tok, now))
        if r.stop:
            for seq in r.stop:
                if len(r.out) >= len(seq) and r.out[-len(seq):] == seq:
                    del r.out[-len(seq):]   # trim the stop sequence
                    self._finish(r, slot)
                    return
        if (len(r.out) >= r.max_new_tokens
                or (r.eos_id is not None and tok == r.eos_id)):
            self._finish(r, slot)

    def _record_done(self, r: _Request, error: bool, **extra) -> None:
        """Terminal engine span for one request: submit -> done, with
        the produced token count, what _finish measured (``extra``: the
        engine's own time per output token ``tpot_s`` and the stalled
        part of it, ``stall_s`` / ``stall_admit_s`` / ``tpot_stall_s``)
        and the request's KV high-watermark (prompt + generated
        positions priced at the cache's per-token bytes) — the trace
        drill-down shows what the request cost in HBM, not just time.
        Recorded at most once (finish, fail, and the loop's shutdown
        sweep can all reach a request)."""
        # the accept-rate gauge tracks every finished speculative
        # request, traced or not (the span extra below needs a trace)
        if r.spec_drafted and self._specm is not None:
            self._specm["accept_rate"].set(r.spec_accepted / r.spec_drafted)
        if r.trace is None:
            return
        extra["prefix_hit_tokens"] = r.prefix_hit
        if r.handoff_bytes:
            extra["kv_handoff_bytes"] = r.handoff_bytes
        if r.spec_drafted:
            rate = r.spec_accepted / r.spec_drafted
            extra["spec_accept_rate"] = round(rate, 4)
        tracing.record_request_span(
            "engine", "generate", r.trace, r.trace.span_id,
            r.t_submit_wall, time.time(), error=error,
            tokens=len(r.out),
            kv_bytes=(self._block_bytes[self._kv.kind] // self._block
                      * (len(r.tokens) + len(r.out))), **extra)
        r.trace = None

    def _free_kv(self, r: _Request, slot: Optional[int]) -> None:
        """Return a finished/failed request's blocks to the pool; its
        full prompt+output block chain enters the prefix index (a
        follow-up conversation turn extends the same chain). The
        slot's table row reverts to trash at once, so no block prepared
        from here on names them.

        The block IN FLIGHT may: it was enqueued, with a copy of the
        tables, before the host could know the request would end (eos
        or a stop sequence inside the block before it, a deadline, a
        failure), and it writes one position a step past the request's
        end through that copy. So the release, to the pool and to the
        prefix index alike, WAITS until that block has been read back:
        until then the blocks are the request's own, nothing can be
        handed them or be served them as a cached prefix, and the
        wasted writes land where nobody reads. Nothing is in flight, or
        the block in flight does not hold the request (its budget ended
        inside the block before: foreseen, it was left out): released
        now."""
        if r.kv_alloc is None:
            return
        # kv_written gates the prefix-cache insert: a request that
        # failed BEFORE its prefill scatter holds zero/stale blocks —
        # caching them under the prompt's hashes would serve garbage
        # KV to every later request sharing the prefix. The FINAL
        # sampled token is excluded from the cached chain: each decode
        # step writes the PREVIOUS token's KV, so the last token's
        # position is never written — a stream ending exactly on a
        # block boundary would otherwise cache one stale position.
        stream = list(r.tokens) + list(r.out)
        if r.out:
            stream = stream[:-1]
        r.kv_alloc = None
        if slot is not None:
            for t in self._tables.values():
                t[slot] = kvcache.TRASH
        seq, cache = r.seq, r.kv_written

        def release():
            self._kv.free_seq(seq, stream, cache=cache)
            self._kv_account()
        fl = self._inflight
        if fl is not None and any(x is r for x in fl.reqs.values()):
            fl.held.append(release)
        else:
            release()

    def _finish(self, r: _Request, slot: Optional[int]):
        """A request's normal end, at its last emit.

        The engine's own measure of a token, from ONE pair of stamps to
        two sinks: the histogram and the request's generate span (a
        median over requests survives a stalled window, a histogram's
        sum does not). Beside it, what of that time the request's slot
        stalled between blocks: the engine's stall totals now less the
        request's mark of them (``_dispatch``), whole (``stall_s``),
        inside admissions (``stall_admit_s``) and a token
        (``tpot_stall_s``, the same denominator as ``tpot_s``). The
        marks leave out the tail of the one gap during which the
        request itself was admitted, and a request that was in no decode
        block (one token; a speculative engine's verify rounds) reads
        0."""
        mark = r.stall_mark or self._stall
        done = {"stall_s": self._stall[0] - mark[0],
                "stall_admit_s": self._stall[1] - mark[1]}
        if r.emitted >= 2:
            ex = r.trace.trace_id if r.trace else None
            n = r.emitted - 1
            done["tpot_s"] = (r.last_token_at - r.first_token_at) / n
            done["tpot_stall_s"] = done["stall_s"] / n
            self._m["request_tpot"].observe(done["tpot_s"], exemplar=ex)
            self._m["request_tpot_stall"].observe(done["tpot_stall_s"],
                                                  exemplar=ex)
        self._record_done(r, error=False, **done)
        self._free_kv(r, slot)
        if slot is not None and self._slots[slot] is r:
            self._slots[slot] = None
        if r.stream is not None:
            r.stream.put_nowait(None)
        if r.fut is not None and not r.fut.done():
            r.fut.set_result(True)

    def _expire(self, r: _Request, slot: Optional[int]):
        """Cancel one request whose deadline budget is spent (queued or
        mid-generation); its slot — if it held one — is reclaimed for
        the next admit pass."""
        from ray_tpu.serve.fault import DeadlineExceeded, fault_metrics
        fault_metrics()["deadline"].inc(tags={"where": "engine"})
        self._fail(r, slot, DeadlineExceeded(
            f"generation cancelled at the deadline after "
            f"{len(r.out)} token(s)"))

    def _fail(self, r: _Request, slot: Optional[int], e: BaseException):
        from ray_tpu.serve.fault import DeadlineExceeded
        self._record_done(r, error=True)
        self._free_kv(r, slot)
        # deadline cancellations cross the serve boundary TYPED so the
        # proxy can answer 504 instead of a generic 500
        err = e if isinstance(e, DeadlineExceeded) else RuntimeError(
            f"llm engine failed: {e}")
        if slot is not None and self._slots[slot] is r:
            self._slots[slot] = None
        if r.stream is not None:
            r.stream.put_nowait(err)  # raised by generate_stream
        if r.fut is not None and not r.fut.done():
            r.fut.set_exception(err)
