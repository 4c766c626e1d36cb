"""Prefill/decode disaggregation: compute-bound prefill on one set of
replicas, latency-bound decode on another.

The TPU-native analog of the reference's prefill-decode serving pattern
(reference: llm/_internal/serve/serving_patterns/prefill_decode/builder.py:184
+ engines/vllm/kv_transfer/nixl.py — there the KV cache moves GPU-to-GPU
over NIXL; here it moves host-staged over the runtime's shared-memory
object plane, sliced to the prompt's prefill bucket so the transfer is
proportional to the prompt, not max_len).

Why disaggregate on TPU: a prefill of a long prompt is one large
MXU-bound matmul burst that stalls every decode slot sharing the chip;
separate prefill replicas keep decode steps (latency-bound, small
batches) off the critical path. Decode admits shipped KV with one
dynamic_update_slice — no forward pass.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

import numpy as np

from ray_tpu.llm import model as lm


class PrefillEngine:
    """Stateless prompt prefill: tokens -> {kv, logits, length}.

    Shape-bucketed like LLMEngine's in-engine prefill (one compile per
    bucket); the returned KV is sliced to BLOCK granularity (the KV
    pool's token-block size) before shipping, so the handoff moves
    ceil(n / block) blocks instead of a whole padded bucket — a
    65-token prompt ships 80 positions at block 16, not 128. The
    decode engine re-pads on arrival (into its accumulator) and frees
    the prefill side's copy at handoff (TensorRef handles are single-use; the host-staged numpy
    copy dies with the request object).
    """

    def __init__(self, cfg, params, *,
                 prefill_buckets: Sequence[int] = (64, 128, 256, 512),
                 max_len: int = 1024,
                 cache_dtype: str = "bfloat16",
                 block_size: Optional[int] = None):
        if lm.STATE in lm.layer_kinds(cfg):
            raise ValueError(
                "the prefill/decode hand-off is not supported with state "
                "layers: it ships K and V rows at block granularity, and a "
                "recurrent state (or a short convolution's tail) is no row "
                "of a block")
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.buckets = tuple(sorted(b for b in prefill_buckets
                                    if b <= max_len)) or (max_len,)
        self.cache_dtype = cache_dtype
        if block_size is None:
            from ray_tpu.config import get_config
            block_size = int(getattr(get_config(),
                                     "kvcache_block_size", 16))
        # same gcd adjustment the engine applies, so both tiers agree
        # on what a block is
        for v in (*self.buckets, max_len):
            block_size = math.gcd(block_size, v)
        self.block_size = block_size

    def _ship_len(self, n: int, upper: int) -> int:
        """Positions to ship for an n-token prompt: the smallest block
        multiple covering it."""
        b = self.block_size
        return min(upper, -(-n // b) * b)

    def prefill(self, tokens: Sequence[int], *,
                device: bool = False) -> dict:
        """Runs the prompt forward pass; returns
        {"k","v": (layers, bucket, kvh, hd), "logits": (vocab,),
        "length": n} ready to ship to a decode engine. Prompts longer
        than the largest bucket stream through lm.prefill_chunk in
        bucket-sized pieces (chunked prefill — long prompts are the
        very case disaggregation targets), shipping KV padded to the
        smallest bucket multiple that holds them.

        ``device=True`` keeps k/v ON DEVICE and returns TensorRef
        handles (runtime/device_store.py — the RDT analog): a decode
        engine in the same process admits them without the KV ever
        touching the host; a remote decode engine pays exactly one
        host hop (fetch + device_put). ``device=False`` is the fully
        host-staged numpy payload (rides the object plane as before)."""
        import jax.numpy as jnp
        tokens = list(map(int, tokens))
        n = len(tokens)
        if n == 0:
            raise ValueError("empty prompt")
        if n > self.max_len:
            raise ValueError(
                f"prompt of {n} tokens exceeds max_len {self.max_len}")
        dt = jnp.dtype(self.cache_dtype)
        big = self.buckets[-1]
        if n <= big:
            b = lm.bucket_for(self.buckets, n)
            padded = lm.pad_prompt(tokens, b)
            # compute at the bucket shape (bounded compiles), ship
            # only the covering BLOCKS: the payload scales with the
            # prompt at block granularity, not bucket granularity
            logits, kv = lm.prefill(self.params, jnp.asarray(padded),
                                    jnp.int32(n), self.cfg, b)
            ship = self._ship_len(n, b)
            k, v = kv["k"][:, :ship], kv["v"][:, :ship]
        else:
            cfg = self.cfg
            # accumulate into the smallest bucket-multiple >= n: chunk
            # compile shapes and the shipped payload stay bucketed
            # (bounded compile variants, prompt-proportional transfer),
            # AND a padded final chunk can never overrun the buffer —
            # dynamic_update_slice would CLAMP the start on overrun and
            # silently corrupt earlier chunks' KV
            ship = ((n + big - 1) // big) * big
            shape = (cfg.n_layers, ship, cfg.n_kv_heads, cfg.head_dim)
            acc = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            off = 0
            logits = None
            while off < n:
                part = tokens[off:off + big]
                b = lm.bucket_for(self.buckets, len(part))
                padded = lm.pad_prompt(part, b)
                logits, acc = lm.prefill_chunk(
                    self.params, jnp.asarray(padded),
                    jnp.int32(len(part)), jnp.int32(off), acc, cfg)
                off += len(part)
            # ship the covering blocks (capped at max_len — decode
            # caches span max_len positions; anything past is garbage)
            ship = self._ship_len(n, self.max_len)
            k = acc["k"][:, :ship]
            v = acc["v"][:, :ship]
        if device:
            from ray_tpu.runtime.device_store import put_device
            return {"k": put_device(k.astype(dt)),
                    "v": put_device(v.astype(dt)),
                    "logits": np.asarray(logits),
                    "length": n}
        return {"k": np.asarray(k.astype(dt)),
                "v": np.asarray(v.astype(dt)),
                "logits": np.asarray(logits),
                "length": n}
