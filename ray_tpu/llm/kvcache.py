"""Paged KV cache: fixed-size token blocks, prefix reuse, COW, LRU.

The memory subsystem production serving needs (reference capability:
vLLM's PagedAttention — block tables over a fixed pool bound HBM by
LIVE tokens, and ref-counted block sharing lets requests with a common
system-prompt prefix skip prefill for the shared blocks). Rebuilt
TPU-native on the engine's static-shape rules:

- the POOL is one preallocated tensor pair per engine,
  ``(layers, num_blocks, kv_heads, block_size, head_dim)`` — shapes
  never change, so XLA compiles the paged decode step exactly once.
  It is head-major so that one physical block is one contiguous
  ``(kv_heads, block_size, head_dim)`` slab: the decode kernel moves
  all KV heads of a block with one DMA, and each head's rows are
  whole tiles (ops/pallas/paged_attention.py);
- each request owns a BLOCK TABLE (fixed width ``max_len //
  block_size``) of physical block ids. Decode attends through it:
  the kernel walks the table's LIVE entries and fetches those blocks
  itself, or (impl ``gather``, the kernel's reference) the table's
  blocks are gathered into the attention view (the same bytes in
  token order; masked tail positions contribute exact zeros); the new
  token's KV is written back through the table either way;
- a PREFIX CHAIN INDEX (hash-chained per full token block, the radix
  structure flattened into parent links) maps prompt prefixes to
  cached block chains: a request sharing a cached prefix adopts those
  blocks ref-counted and prefills only its suffix (lm.prefill_chunk at
  the prefix offset — the spike-verified bitwise-parity path);
- blocks are copy-on-write: a shared (or cached) block is never
  written; ``ensure_writable`` gives a forked sequence its own copy at
  the first divergent write;
- refcount-0 chains stay cached and are LRU-evicted LEAF-FIRST under
  pool pressure (a parent evicted before its child would orphan the
  child: chain lookups walk from the root).

A model with STATE layers (a state-space mixer, ops/ssm.py) keeps for
each of them ONE recurrent state and one conv tail A SLOT, not a row a
position: the kind ``state``'s two arrays are indexed (the kind's layers,
slot, ...), sized from the engine's slots, written whole by a prefill at
the prompt's length, updated in place by every decode step of a live
slot, and the manager reports them beside the blocks (``state_slots``).
What a decode step moves of them: under impl 'paged_flash' a LIVE slot's
state of a layer once in and once out (the kernel ``ssm_step`` walks the
live slots; the stack is its aliased operand) and nothing of an idle
slot's; under 'gather', its reference, every slot's state twice in and
once out (``_pool_state_step``). The conv tails (a hundredth of the
bytes) move whole either way, in plain XLA. A
state cannot be rolled back or cut at a block edge, so prefix reuse,
speculation and the prefill/decode hand-off are refused for such a model
(llm/engine.py).

A model with WINDOW layers (sliding-window attention: a query attends
the last ``sliding_window`` positions) has a second, small pool for
them, with block ids and a table of its own a sequence, accounted for
by the same manager: a window layer holds a sequence's blocks from the
one with the window's first position on, at most
``window_ring_blocks`` of them; ``advance_window`` allocates the
blocks the next decode dispatch writes and FREES the ones the window
has passed (their table entries revert to trash; the kernel's walk
starts past them, so they are never read again). The global layers'
blocks are reserved for the full horizon as before. Prefix reuse is
refused for such a model: a cached chain would have to keep every
window block it freed.

Physical block 0 is the TRASH block: bucket-padding garbage and the
gather reference's writes for finished/empty slots are redirected there
so freed blocks can be reallocated immediately without a device sync.
A decode step reads off a row of TRASH that its slot holds no request
(``_live``), and the kernel path then skips the slot: no walk, no write
(not even there), no row in an expert layer's groups.

Host bookkeeping (``KVBlockManager``) is pure python/numpy — unit-
testable without jax; device ops (pool init, gather/scatter, the paged
decode step) live beside it and are only imported by the engine.
"""

from __future__ import annotations

import hashlib
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

TRASH = 0   # physical block 0: garbage-write target, never allocated


def kvcache_metrics() -> dict:
    """Get-or-create the paged-KV gauges/counters (shared process
    registry, pushed to the head like every llm_* series). Catalog:

      llm_kv_blocks_used          blocks referenced by live requests
      llm_kv_blocks_cached        refcount-0 blocks held by the prefix
                                  index (reclaimable via LRU eviction)
      llm_kv_blocks_evicted_total cached chains evicted under pressure
      llm_prefix_hit_tokens_total prompt tokens whose prefill was
                                  skipped via a prefix-cache hit
      llm_kv_handoff_bytes_total  KV bytes shipped prefill->decode at
                                  block granularity (llm/pd.py)
      llm_paged_attn_steps_total  paged decode steps by attention impl
                                  ({impl}: paged_flash | gather)
      llm_kv_window_blocks_used   window-layer pool blocks held by live
                                  requests
      llm_kv_window_blocks_freed_total  window-layer blocks freed because
                                  their sequence's window passed them
    """
    from ray_tpu.util import metrics as m
    return {
        "used": m.Gauge(
            "llm_kv_blocks_used",
            "KV pool blocks referenced by live requests"),
        "cached": m.Gauge(
            "llm_kv_blocks_cached",
            "Refcount-0 KV pool blocks held by the prefix index "
            "(reclaimable by LRU eviction)"),
        "evicted": m.Counter(
            "llm_kv_blocks_evicted_total",
            "Cached KV blocks evicted from the prefix index under "
            "pool pressure"),
        "hit_tokens": m.Counter(
            "llm_prefix_hit_tokens_total",
            "Prompt tokens served from cached prefix blocks instead "
            "of prefill compute"),
        "handoff_bytes": m.Counter(
            "llm_kv_handoff_bytes_total",
            "KV bytes shipped prefill->decode at block granularity "
            "in the disaggregated path"),
        "window_used": m.Gauge(
            "llm_kv_window_blocks_used",
            "Window-layer KV pool blocks held by live requests"),
        "window_freed": m.Counter(
            "llm_kv_window_blocks_freed_total",
            "Window-layer KV pool blocks freed because their sequence's "
            "window passed them"),
        "attn_steps": m.Counter(
            "llm_paged_attn_steps_total",
            "Paged decode steps taken, tagged by attention impl "
            "(paged_flash = fused block-table kernel, gather = "
            "materialized view)",
            tag_keys=("impl",)),
    }


def chain_hashes(tokens: Sequence[int], block_size: int, *,
                 seed: bytes = b"", start_block: int = 0) -> List[str]:
    """One digest per FULL block of ``tokens`` from ``start_block``
    on; each digest covers the entire prefix up to that block's end
    (hash chaining), so equal digests imply equal prefixes — the
    prefix-index key. ``seed`` is the digest of block start_block-1
    (chain extension: free_seq continues a stored prompt chain over
    the generated tokens without rehashing the prompt)."""
    out: List[str] = []
    h = seed
    for i in range(start_block, len(tokens) // block_size):
        blk = tokens[i * block_size:(i + 1) * block_size]
        d = hashlib.blake2b(digest_size=16)
        d.update(h)
        d.update(np.asarray(blk, np.int64).tobytes())
        h = d.digest()
        out.append(h.hex())
    return out


@dataclass
class _CacheEntry:
    phys: int
    hash: str
    parent: Optional[str]       # previous block's chain hash
    children: int = 0           # cached continuations (evict leaves 1st)
    last_used: int = 0          # manager tick, LRU order


@dataclass
class _Seq:
    table: List[int]            # logical block idx -> physical id
    n_prompt: int
    hit_tokens: int
    hashes: List[str] = field(default_factory=list)  # full prompt blocks


class BlockPoolExhausted(RuntimeError):
    """The request can NEVER fit: its full horizon needs more blocks
    than the pool holds even if everything cacheable were evicted."""


class KVBlockManager:
    """Host-side accounting for one engine's block pool. Not
    thread-safe by itself — the engine serializes admits/frees on its
    scheduler loop."""

    def __init__(self, num_blocks: int, block_size: int, *,
                 table_width: int, prefix_cache: bool = True,
                 metrics: Optional[dict] = None,
                 window: Optional[Tuple[int, int, int]] = None,
                 kind: str = "global", state_slots: int = 0):
        """``state_slots``: the states a model with state layers has, one a
        slot of the engine's, which bounds the sequences; here they are
        only reported, a state a live sequence.
        ``window`` = (blocks of the window layers' pool, the sliding
        window, the steps one decode dispatch runs at most), for a
        model with window layers. ``kind``: the layer kind whose pool
        the ``num_blocks`` ids are of, the one a sequence holds from its
        first position on (global layers' K and V, or latent layers'
        rows): the name its tables and reports go by."""
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (one is trash)")
        if window is not None and prefix_cache:
            raise ValueError(
                "prefix caching is not supported with window layers: a "
                "window layer frees the blocks its window has passed, "
                "and a cached prefix would have to keep them")
        if state_slots and prefix_cache:
            raise ValueError(
                "prefix caching is not supported with state layers: a "
                "cached chain of blocks has no recurrent state to start "
                "its suffix from")
        self.state_slots = int(state_slots)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.table_width = int(table_width)
        self.prefix_cache = bool(prefix_cache)
        self.kind = kind
        self.free: deque = deque(range(1, num_blocks))   # 0 = trash
        self.ref: Dict[int, int] = {}                    # phys -> count
        self.entries: Dict[str, _CacheEntry] = {}        # hash -> entry
        self.by_phys: Dict[int, _CacheEntry] = {}
        self.seqs: Dict[object, _Seq] = {}
        self.evicted_total = 0
        self.hit_tokens_total = 0
        self._tick = 0
        self._m = metrics
        # window layers: ids of their own pool (0 = its trash block),
        # and per live sequence {logical block: physical id}
        self.window = None
        self.wfree: deque = deque()
        self.wseqs: Dict[object, Dict[int, int]] = {}
        self.window_freed_total = 0
        if window is not None:
            wblocks, self.window, steps = (int(x) for x in window)
            self.window_blocks = wblocks
            self.ring = window_ring_blocks(self.window, self.block_size,
                                           steps)
            if wblocks - 1 < self.ring:
                raise ValueError(
                    f"the window layers' pool needs {self.ring} blocks a "
                    f"sequence and a trash block, got {wblocks}")
            self.wfree = deque(range(1, wblocks))

    # -- window layers ---------------------------------------------------

    def window_used_blocks(self) -> int:
        """Window-layer blocks live sequences hold: the ids that are not
        on the free list (id 0 is the trash block)."""
        return self.window_blocks - 1 - len(self.wfree) if self.window \
            else 0

    def advance(self, seq_id, length: int, steps: int) -> Dict[str, object]:
        """{kind: the sequence's table row} for the kinds whose row
        MOVES before a decode dispatch (advance_window): not the global
        layers', which admission reserved whole."""
        if self.window is None:
            return {}
        return {WINDOW: self.advance_window(seq_id, length, steps)}

    def advance_window(self, seq_id, length: int, steps: int = 1):
        """Before a decode dispatch that writes positions ``length ...
        length + steps - 1`` of ``seq_id`` (its first query attends
        positions >= length + 1 - window): free the window-layer blocks
        that lie wholly below that, allocate those the dispatch writes,
        and return the sequence's window table (table_width,) int32.
        Never fails for an admitted sequence: it holds at most ``ring``
        blocks, which admission set aside."""
        held = self.wseqs[seq_id]
        first = max(length + 1 - self.window, 0) // self.block_size
        last = min((length + steps - 1) // self.block_size,
                   self.table_width - 1)
        for logical in [b for b in held if b < first]:
            self.wfree.append(held.pop(logical))
            self.window_freed_total += 1
            if self._m is not None:
                self._m["window_freed"].inc()
        for logical in range(first, last + 1):
            if logical not in held:
                held[logical] = self.wfree.popleft()
        if self._m is not None:
            self._m["window_used"].set(self.window_used_blocks())
        row = np.full((self.table_width,), TRASH, np.int32)
        for logical, phys in held.items():
            row[logical] = phys
        return row

    # -- introspection ---------------------------------------------------

    def used_blocks(self) -> int:
        return sum(1 for c in self.ref.values() if c > 0)

    def cached_blocks(self) -> int:
        return sum(1 for h, e in self.entries.items()
                   if self.ref.get(e.phys, 0) == 0)

    def free_blocks(self) -> int:
        return len(self.free)

    def used_by_kind(self) -> Dict[str, int]:
        """{kind: blocks of its pool live sequences hold}."""
        used = {self.kind: self.used_blocks()}
        if self.window is not None:
            used[WINDOW] = self.window_used_blocks()
        if self.state_slots:
            used[STATE] = len(self.seqs)        # a state a sequence
        return used

    def free_by_kind(self) -> Dict[str, int]:
        """{kind: blocks of its pool on the free list}."""
        free = {self.kind: len(self.free)}
        if self.window is not None:
            free[WINDOW] = len(self.wfree)
        if self.state_slots:
            free[STATE] = self.state_slots - len(self.seqs)
        return free

    def freed_by_kind(self) -> Dict[str, int]:
        """{kind: blocks freed so far because their sequence passed
        them}, for the kinds that free that way."""
        return {} if self.window is None else {
            WINDOW: self.window_freed_total}

    def _publish(self) -> None:
        if self._m is None:
            return
        self._m["used"].set(self.used_blocks())
        self._m["cached"].set(self.cached_blocks())

    def blocks_needed(self, n_tokens: int, max_new: int) -> int:
        """Full-horizon reservation: admission allocates every block
        the request can ever touch, so decode can never fail mid-
        flight on pool pressure (the pool's overload answer is a
        queued admit, not a dropped stream)."""
        return -(-(n_tokens + max_new) // self.block_size)

    # -- prefix lookup ---------------------------------------------------

    def lookup(self, tokens: Sequence[int]) -> Tuple[int, List[int]]:
        """(hit_tokens, physical blocks) for the longest cached chain
        of FULL prompt blocks — capped one token short of the prompt
        so the last token's logits always come from live compute (a
        full-hit request still needs something to sample from)."""
        hit, phys, _ = self._lookup(tokens)
        return hit, phys

    def _lookup(self, tokens: Sequence[int]
                ) -> Tuple[int, List[int], List[str]]:
        """lookup + the prompt's chain hashes (alloc_seq records them
        on the sequence — hashing a long prompt once, not twice)."""
        hashes = chain_hashes(tokens, self.block_size) \
            if self.prefix_cache else []
        if not self.prefix_cache:
            return 0, [], hashes
        cap_blocks = (len(tokens) - 1) // self.block_size
        phys: List[int] = []
        self._tick += 1
        for h in hashes[:cap_blocks]:
            e = self.entries.get(h)
            if e is None:
                break
            e.last_used = self._tick
            phys.append(e.phys)
        return len(phys) * self.block_size, phys, hashes

    # -- allocation ------------------------------------------------------

    def alloc_seq(self, seq_id, tokens: Sequence[int],
                  max_new: int) -> Optional[dict]:
        """Admit one request: adopt the cached prefix (ref-counted),
        reserve fresh blocks for the rest of its horizon. Returns
        {"tables": {kind: np.int32 (table_width,)}, "hit_tokens": int,
        "new_blocks": [phys]} — or None when the pool can't cover it
        right now (caller re-queues the request; eviction of
        refcount-0 chains was already attempted). Raises
        BlockPoolExhausted when the request can never fit. (The rows
        are also there as "table" and "window_table", the names the
        benchmark's drives read.)"""
        if seq_id in self.seqs:
            raise ValueError(f"seq {seq_id!r} already allocated")
        n = len(tokens)
        total = self.blocks_needed(n, max_new)
        if total > self.table_width:
            raise BlockPoolExhausted(
                f"request horizon spans {total} blocks > table width "
                f"{self.table_width}")
        if total > self.num_blocks - 1:
            raise BlockPoolExhausted(
                f"request horizon needs {total} blocks; pool holds "
                f"{self.num_blocks - 1}")
        if self.window is not None and \
                (len(self.wseqs) + 1) * self.ring > self.window_blocks - 1:
            return None     # every ring of the window layers' pool is out
        hit_tokens, hit_phys, hashes = self._lookup(tokens)
        # pin the hit blocks BEFORE any eviction: at refcount 0 they
        # are themselves eviction candidates once their chain suffix
        # is gone, and an evicted-then-reallocated hit block would
        # appear TWICE in the table (prefix view + fresh write target)
        # — silent KV corruption
        for p in hit_phys:
            self.ref[p] = self.ref.get(p, 0) + 1
        need = total - len(hit_phys)
        if need > len(self.free):
            self.evict(need - len(self.free))
        if need > len(self.free):
            for p in hit_phys:          # un-pin; caller re-queues
                self._release(p)
            return None
        table = np.full((self.table_width,), TRASH, np.int32)
        for i, p in enumerate(hit_phys):
            table[i] = p
        new_blocks = []
        for i in range(len(hit_phys), total):
            p = self.free.popleft()
            self.ref[p] = 1
            table[i] = p
            new_blocks.append(p)
        self.seqs[seq_id] = _Seq(list(table), n, hit_tokens, hashes)
        self.hit_tokens_total += hit_tokens
        if self._m is not None and hit_tokens:
            self._m["hit_tokens"].inc(hit_tokens)
        self._publish()
        if self.window is not None:
            self.wseqs[seq_id] = {}
        # a window layer's: what the first decode step's window reaches
        tables = {self.kind: table, **self.advance(seq_id, n, 0)}
        return {"tables": tables, "hit_tokens": hit_tokens,
                "new_blocks": new_blocks,
                **{_TABLE_NAMES[kind]: row for kind, row in tables.items()}}

    def _release(self, phys: int) -> None:
        """Drop one live reference; a block neither referenced nor
        cached returns to the free list."""
        c = self.ref.get(phys, 0) - 1
        if c > 0:
            self.ref[phys] = c
            return
        self.ref.pop(phys, None)
        if phys not in self.by_phys and phys != TRASH:
            self.free.append(phys)

    def free_seq(self, seq_id, out_tokens: Sequence[int] = (),
                 cache: bool = True) -> None:
        """Finish one request: insert its full-block chain (prompt +
        generated tokens — a follow-up turn extends the same chain)
        into the prefix index, then drop the live references. Cached
        blocks stay resident at refcount 0 until LRU eviction.
        ``cache=False`` skips the insert — REQUIRED for a request
        whose KV was never written (admit failed before the scatter):
        indexing its zero/stale blocks under the prompt's chain hashes
        would poison every later request sharing the prefix."""
        seq = self.seqs.pop(seq_id, None)
        if seq is None:
            return
        self.wfree.extend(self.wseqs.pop(seq_id, {}).values())
        if self._m is not None and self.window is not None:
            self._m["window_used"].set(self.window_used_blocks())
        if self.prefix_cache and cache:
            # ``out_tokens`` is the FULL token stream (prompt +
            # generated) when the caller wants generated full blocks
            # cached too (a follow-up conversation turn extends the
            # same chain); absent, the alloc-time prompt hashes
            # serve. The stored prompt chain is EXTENDED from its
            # last digest — the prompt (a 100k shared context on the
            # target workload) is never rehashed at finish.
            hashes = seq.hashes
            if len(out_tokens) >= seq.n_prompt:
                seed = bytes.fromhex(hashes[-1]) if hashes else b""
                hashes = hashes + chain_hashes(
                    list(out_tokens), self.block_size, seed=seed,
                    start_block=len(hashes))
            self._tick += 1
            parent: Optional[str] = None
            for i, h in enumerate(hashes):
                phys = seq.table[i]
                if phys == TRASH:
                    break
                cur = self.entries.get(h)
                if cur is None:
                    # only cache blocks this seq exclusively owns or
                    # already-cached shared ones; a shared-but-uncached
                    # block (fork) must not be indexed under a hash
                    # another writer could invalidate
                    e = _CacheEntry(phys, h, parent,
                                    last_used=self._tick)
                    if phys in self.by_phys:
                        # same phys already cached under another hash
                        # (can't happen via chain hashing; guard)
                        break
                    self.entries[h] = e
                    self.by_phys[phys] = e
                    if parent is not None and parent in self.entries:
                        self.entries[parent].children += 1
                else:
                    cur.last_used = self._tick
                parent = h
        for phys in seq.table:
            if phys != TRASH:
                self._release(phys)
        self._publish()

    # -- copy-on-write / fork --------------------------------------------

    def fork_seq(self, src_id, dst_id) -> List[int]:
        """Share every block of ``src`` with a new sequence (parallel
        sampling / beam fork). Writes to shared blocks must go through
        ensure_writable."""
        src = self.seqs.get(src_id)
        if src is None:
            raise KeyError(src_id)
        if dst_id in self.seqs:
            raise ValueError(f"seq {dst_id!r} already allocated")
        for p in src.table:
            if p != TRASH:
                self.ref[p] = self.ref.get(p, 0) + 1
        self.seqs[dst_id] = _Seq(list(src.table), src.n_prompt,
                                 src.hit_tokens, list(src.hashes))
        self._publish()
        return list(src.table)

    def ensure_writable(self, seq_id,
                        logical: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write guard: before writing into ``logical``, a
        block that is shared (refcount > 1) or held by the prefix
        index is replaced by a private copy. Returns (old_phys,
        new_phys) when the caller must issue the device block copy,
        None when the block was already private."""
        seq = self.seqs[seq_id]
        phys = seq.table[logical]
        if phys == TRASH:
            return None
        if self.ref.get(phys, 0) <= 1 and phys not in self.by_phys:
            return None
        if not self.free:
            self.evict(1)
        if not self.free:
            return None     # caller treats as pool pressure
        new = self.free.popleft()
        self.ref[new] = 1
        seq.table[logical] = new
        self._release(phys)
        self._publish()
        return phys, new

    def truncate_seq(self, seq_id, n_tokens: int, *,
                     min_blocks: int = 0) -> List[int]:
        """Roll a live sequence back to its first ``n_tokens`` tokens —
        the speculative-decode rejection path, and the branch-abandon
        primitive for COW forks. Table blocks whose every position lies
        beyond ``n_tokens`` are released (refcount decrement: a shared
        or prefix-indexed block survives for its other holders — the
        prefix index's own accounting is never touched) and the row is
        re-pointed at trash. The sequence's hash chain is cut to the
        full blocks ``n_tokens`` still covers, so a digest over
        truncated content can never reach the prefix index at
        ``free_seq`` — a rolled-back draft tail must never satisfy a
        later prefix hit.

        ``min_blocks`` keeps at least that many leading table rows
        (the engine passes its full-horizon reservation so a rollback
        never returns blocks admission already promised the request —
        re-acquiring them later could deadlock against a newer admit).
        No device op: rejected-draft KV lives beyond the sequence's
        logical length, so it is masked out of every attention (exact
        zeros) and overwritten by the next real write at that position.
        Returns the physical blocks released."""
        seq = self.seqs.get(seq_id)
        if seq is None:
            raise KeyError(seq_id)
        keep = max(-(-n_tokens // self.block_size), min_blocks)
        freed: List[int] = []
        for i in range(len(seq.table) - 1, keep - 1, -1):
            phys = seq.table[i]
            if phys == TRASH:
                continue
            seq.table[i] = TRASH
            self._release(phys)
            freed.append(phys)
        seq.hashes = seq.hashes[:n_tokens // self.block_size]
        seq.n_prompt = min(seq.n_prompt, n_tokens)
        self._publish()
        return freed

    # -- eviction --------------------------------------------------------

    def evict(self, k: int) -> int:
        """Evict up to ``k`` cached refcount-0 blocks, LRU leaf-first
        (children evict before parents so surviving chains stay
        walkable from the root). One heapify + O(k log n) — this runs
        on the engine's serialized admit path, so a per-block rescan
        of every cache entry would stall in-flight streams under a
        large prefix cache. Returns blocks actually freed."""
        import heapq
        heap = [(e.last_used, e.hash) for e in self.entries.values()
                if e.children == 0 and self.ref.get(e.phys, 0) == 0]
        heapq.heapify(heap)
        freed = 0
        while freed < k and heap:
            _, h = heapq.heappop(heap)
            e = self.entries.get(h)
            if e is None or e.children != 0 \
                    or self.ref.get(e.phys, 0) != 0:
                continue            # stale heap entry
            del self.entries[h]
            self.by_phys.pop(e.phys, None)
            if e.parent is not None:
                p = self.entries.get(e.parent)
                if p is not None:
                    p.children -= 1
                    if p.children == 0 and \
                            self.ref.get(p.phys, 0) == 0:
                        heapq.heappush(heap, (p.last_used, p.hash))
            self.free.append(e.phys)
            freed += 1
            self.evicted_total += 1
            if self._m is not None:
                self._m["evicted"].inc()
        if freed:
            self._publish()
        return freed


# --- device ops (jax only from here down) ------------------------------


def _jx():
    import jax
    import jax.numpy as jnp
    return jax, jnp


# A layer KIND ("global": attends everything; "window": the last
# sliding_window positions) has its own pair of pool arrays, its own
# block ids and its own table a sequence. A model of global layers only
# (the Llama family) has the one pair it always had. (llm/model.py
# takes the two names from here: it imports jax, and this module must
# not at import time.)
#
# A LATENT layer (multi-head latent attention) keeps ONE row a position,
# [c | kr], and no head axis: its pair of arrays is the row's two parts,
# c (kv_lora_rank wide: what keys and values are expanded from) and kr
# (qk_rope_head_dim wide: the rotary key every head shares). Like a
# global layer it attends everything, so a sequence holds its blocks
# from the first position on and a prefix's blocks can be shared. An
# array's width is a whole number of 128-lane tiles (LANES): the device
# lays a narrower minor dimension out so anyway, and a kernel's DMA
# cannot take part of a tile; the widths are the configuration's (c 256 or
# 512 values, kr 64 in the served ones: 768 or 1,280 bytes a row in bf16),
# and a kr of 64 values lies in 128, the rest zero.
#
# A STATE layer (a state-space mixer) keeps no row a position: its pair of
# arrays is one recurrent state (float32: it is carried, never rounded) and
# one conv tail A SLOT, (the kind's layers, slots, ...). It has no block ids
# and no table; the slot is the index. A state layer that is a gated SHORT
# CONVOLUTION (ops/shortconv.py) keeps the tail ALONE, "conv" and no "ssm":
# a pool holds the arrays of ``state_arrays``, and every walk over
# ``POOL_KEYS[STATE]`` takes the keys the pool has.
#
# A K/V head NARROWER than the 128 lanes of a tile (64 values) shares a
# pool row's lanes with its neighbour: ``row_shapes`` gives (kv_heads / 2,
# 128), heads 2j and 2j + 1 side by side, the same bytes in the same order
# as (kv_heads, 64) and no padding (a minor dimension of 64 bf16 values
# would lie in whole 128-lane tiles, twice the bytes, and the walk's DMA
# cannot take half a tile). ``_pool_attend`` packs a step's queries to
# match and takes each head's half of what comes back.
GLOBAL, WINDOW, LATENT, STATE = "global", "window", "latent", "state"
LANES = 128
POOL_KEYS = {GLOBAL: ("k", "v"), WINDOW: ("wk", "wv"), LATENT: ("c", "kr"),
             STATE: ("ssm", "conv")}
_TABLE_NAMES = {GLOBAL: "table", WINDOW: "window_table",
                LATENT: "table"}                            # alloc_seq


def pool_kinds(cfg) -> tuple:
    """How a model's cache is laid out: ``((kind, its layers), ...)``,
    the kind a sequence holds whole (global or latent) first (hashable:
    the device ops below are built per value); ``((GLOBAL, (0, ..., L -
    1)),)`` for the Llama family."""
    from ray_tpu.llm.model import kind_layers
    kinds = kind_layers(cfg)
    if STATE in kinds and set(kinds) != {GLOBAL, STATE}:
        raise NotImplementedError(
            "state layers are served beside global layers only: a model "
            "whose other layers are window or latent layers (or that has "
            "none) is not served yet")
    if set(kinds) == {WINDOW}:
        raise NotImplementedError(
            "a model whose layers are all window layers is not served "
            "yet: admission reserves a sequence's blocks of the kind it "
            "holds whole")
    return tuple(kinds.items())


def row_shapes(cfg, kind: str) -> tuple:
    """What one position of one layer of ``kind`` keeps: the shapes of
    its row in the kind's two arrays (``POOL_KEYS``), heads then width.
    THE geometry rule: the pool's arrays, a block's bytes and the walk's
    chunk all follow from it."""
    if kind == LATENT:
        return tuple((-(-w // LANES) * LANES,)
                     for w in (cfg.kv_lora_rank, cfg.qk_rope_head_dim))
    if kind == STATE:       # a slot's, whatever the position
        return tuple(shape for shape, _ in state_arrays(cfg, None).values())
    pack = heads_packed(cfg)
    return ((cfg.n_kv_heads // pack, cfg.head_dim * pack),) * 2


def heads_packed(cfg) -> int:
    """K/V heads that share one pool row's 128 lanes (the config's
    ``kv_row_heads``): 1, or 2 for heads of 64 values."""
    pack = getattr(cfg, "kv_row_heads", 1) or 1
    if pack > 1 and (cfg.n_kv_heads % pack or cfg.head_dim * pack > LANES):
        raise ValueError(
            f"kv_row_heads={pack}: {cfg.n_kv_heads} K/V heads of "
            f"{cfg.head_dim} do not lie {pack} a row of {LANES} lanes")
    return pack


def row_bytes(cfg, kind: str, dtype) -> int:
    """Bytes one position of one layer of ``kind`` costs (both arrays)."""
    _, jnp = _jx()
    return sum(int(np.prod(shape)) for shape in row_shapes(cfg, kind)) \
        * jnp.dtype(dtype).itemsize


def state_dtypes(dtype) -> tuple:
    """The dtypes of a STATE layer's two arrays: the recurrent state is
    float32 whatever the cache's dtype, the conv tail the cache's."""
    _, jnp = _jx()
    return jnp.dtype(jnp.float32), jnp.dtype(dtype)


def state_arrays(cfg, dtype) -> dict:
    """{pool key: (a slot's shape of ONE state layer, its dtype; None
    without ``dtype``)}: a Mamba-2 mixer's float32 state and its conv tail
    (the last K - 1 rows of xBC), or a gated short convolution's tail alone,
    flat (ops/shortconv.py)."""
    sk, tk = POOL_KEYS[STATE]
    f32, dt = state_dtypes(dtype) if dtype is not None else (None, None)
    if getattr(cfg, "shortconv_kernel", 0):
        from ray_tpu.ops.shortconv import tail_shape
        return {tk: (tail_shape(cfg), dt)}
    return {sk: ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), f32),
            tk: ((cfg.ssm_conv_kernel - 1, cfg.ssm_widths[1]), dt)}


def state_slot_bytes(cfg, dtype) -> int:
    """Bytes one slot's states and conv tails cost, all state layers."""
    layers = dict(pool_kinds(cfg)).get(STATE, ())
    return len(layers) * sum(
        int(np.prod(shape)) * dt.itemsize
        for shape, dt in state_arrays(cfg, dtype).values())


def init_pool(cfg, num_blocks: int, block_size: int, dtype,
              window_blocks: int = 0, state_slots: int = 0) -> dict:
    """The pool tensors, a pair a layer kind (``POOL_KEYS``), each (the
    kind's layers, its blocks, *a row's heads, block_size, a row's
    width): k/v (global layers, num_blocks, kv_heads, block_size,
    head_dim); for window layers wk/wv (window layers, window_blocks,
    ...); for latent layers c/kr (latent layers, num_blocks, block_size,
    kv_lora_rank | qk_rope_head_dim); for state layers ssm/conv (state
    layers, ``state_slots``, *a slot's state | its conv tail)."""
    _, jnp = _jx()
    pool = {}
    for kind, layers in pool_kinds(cfg):
        if kind == STATE:
            if state_slots < 1:
                raise ValueError("a model with state layers needs "
                                 "state_slots >= 1: a state a slot")
            for key, (row, dt) in state_arrays(cfg, dtype).items():
                pool[key] = jnp.zeros((len(layers), state_slots, *row), dt)
            continue
        blocks = max(2, window_blocks) if kind == WINDOW else num_blocks
        for key, row in zip(POOL_KEYS[kind], row_shapes(cfg, kind)):
            pool[key] = jnp.zeros(
                (len(layers), blocks, *row[:-1], block_size, row[-1]), dtype)
    return pool


def pool_k(pool: dict, kind: Optional[str] = None):
    """A kind's first array, (its layers, its blocks, ..., block_size,
    width): what a geometry is read off (the block size is axis -2 for
    every kind). Without ``kind``: of the first kind the pool holds."""
    return pool[_held_keys(pool, kind or _held_kinds(pool)[0])[0]]


def kind_block_bytes(pool: dict) -> dict:
    """{kind: device bytes one block id of that kind costs (k + v, all
    the kind's layers)}."""
    return {kind: sum(pool[key].nbytes // pool[key].shape[1]
                      for key in _held_keys(pool, kind))
            for kind in _held_kinds(pool)}


def window_ring_blocks(window: int, block_size: int, steps: int) -> int:
    """Blocks of a window layer one sequence holds at most: the window
    plus the ``steps`` positions one decode dispatch writes ahead, cut
    into blocks, plus one for where the cut falls."""
    return -(-(window + steps - 1) // block_size) + 1


def auto_pool_blocks(slots: int, table_width: int, block_bytes: int,
                     configured: int = 0, reserved_bytes: int = 0) -> int:
    """Blocks of the pool of the kind a sequence holds whole (the global
    layers', or the latent layers'): the explicit knob wins;
    otherwise worst case (every slot at max_len) plus one full chain of
    prefix-cache headroom, capped at a quarter of the free HBM of the
    fullest local device when the backend reports a capacity
    (devmon.hbm_snapshot; the CPU backend reports none). A block's cost
    differs by layer kind, so the rule is stated in bytes: the quarter
    is of what is free AFTER ``reserved_bytes``, the window layers'
    pool, which the engine sizes first and exactly (slots x
    window_ring_blocks: a window layer never holds more, so there is
    nothing to cap), and ``block_bytes`` is what a block id of the
    global layers costs (k + v over the global layers only). The decode
    program holds ONE pool-sized buffer since PR 31 (the donated pool, updated in place: the
    compiler's memory analysis at the chat cell's geometry gives 3.1
    GB of pool and 1.3 MB of temporaries, where the layer scan's
    stacked outputs took 3.9 GB); the quarter dates from the three it
    held before, and is kept until a PR of its own raises it (a larger
    pool moves the memory reading and the admission, ROADMAP Speed 1).
    The prefill-side programs (scatter_bucket, scatter_table,
    copy_block) and the prompt's accumulator need room beside it.
    ``block_bytes`` is what one block costs PER DEVICE.
    The cap never shrinks below ONE full-horizon request
    (table_width blocks): a max_len-sized request must be servable —
    serially — on any pool the engine auto-sizes."""
    if configured:
        return max(2, int(configured))
    base = slots * table_width + table_width
    from ray_tpu.util import devmon
    headrooms = [r["limit"] - r["used"]
                 for r in devmon.hbm_snapshot(record=False)
                 if r["limit"]]
    if headrooms:
        cap = int(max(0, min(headrooms) - reserved_bytes) // 4
                  // max(1, block_bytes))
        base = max(table_width, min(base, cap))
    return base + 1     # + trash block


_JITS: dict = {}    # (op, pool geometry, dtype) -> jitted callable


def _pool_key(pool: dict) -> tuple:
    """Cache-key component identifying one pool's compiled geometry:
    the first array's shape, the dtype, then every other array's shape."""
    shapes = [tuple(pool[key].shape) for kind in _held_kinds(pool)
              for key in _held_keys(pool, kind)]
    return (shapes[0], str(pool_k(pool).dtype), *shapes[1:])


# Block tables, write targets and physical ids are dicts by layer kind
# from the block manager to the kernel, {GLOBAL: ...} for a model of
# global layers only. The PUBLIC entries also take what callers outside
# the engine hand them, a bare array for such a model and no layout:
# these two functions, at an entry's first line, are all that knows it.

def _held_keys(pool: dict, kind: str) -> tuple:
    """The kind's arrays that ``pool`` has (a STATE kind's may be its conv
    tail alone)."""
    return tuple(key for key in POOL_KEYS[kind] if key in pool)


def _held_kinds(pool: dict) -> tuple:
    return tuple(kind for kind in POOL_KEYS if _held_keys(pool, kind))


def _by_kind(ids, pool: dict) -> dict:
    return ids if isinstance(ids, dict) else {_held_kinds(pool)[0]: ids}


def _layout(pool: dict, kinds=None) -> tuple:
    """``pool_kinds`` of the model ``pool`` was made for: the caller's,
    which must agree with the pool, or read off a pool of one kind. What
    comes back is the kinds that keep a row a POSITION, their layers as rows
    of a prompt's token-order K/V (prefill's output, the chunked prefill's
    accumulator): the model's layer indices, but for a model with state
    layers, whose token-order rows are its global layers' alone."""
    held = tuple((kind, pool_k(pool, kind).shape[0])
                 for kind in _held_kinds(pool))
    kinds = tuple(kinds or ((kind, tuple(range(n))) for kind, n in held))
    layers = sorted(l for _, ls in kinds for l in ls)
    # beside state layers a model has layers that cache nothing at all
    whole = len(set(layers)) == len(layers) if STATE in dict(kinds) \
        else layers == list(range(len(layers)))
    if tuple((kind, len(ls)) for kind, ls in kinds) != held or not whole:
        raise ValueError(f"the pool holds (kind, layers) {held}, not the "
                         f"layout {kinds}: pass its model's pool_kinds(cfg)")
    rows = sorted(l for kind, ls in kinds if kind != STATE for l in ls)
    return tuple((kind, tuple(rows.index(l) for l in ls))
                 for kind, ls in kinds if kind != STATE)


def _pad_last(x, width: int):
    """x's last axis zero-padded to ``width`` (a latent row's part to
    its pool array's whole tiles)."""
    _, jnp = _jx()
    pad = width - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def _to_blocks(kv, nb: int, pool):
    """The first ``nb * block`` positions of token-order rows (layers,
    positions, kvh, hd), or (layers, positions, width) of a kind with no
    head axis, as ``nb`` pool blocks (layers, nb, kvh, block, hd) /
    (layers, nb, block, width: the rows zero-padded to the pool's), in
    the pool's dtype."""
    bs = pool.shape[-2]
    blocks = kv[:, :nb * bs].reshape(kv.shape[0], nb, bs, *kv.shape[2:])
    if kv.ndim == 4:
        blocks = blocks.transpose(0, 1, 3, 2, 4)        # head-major
    else:                                               # to whole tiles
        blocks = _pad_last(blocks, pool.shape[-1])
    return blocks.astype(pool.dtype)


def _from_blocks(g):
    """``_to_blocks`` back: gathered pool blocks (layers, w, ..., block,
    width) as token-order rows (layers, w * block, ...)."""
    if g.ndim == 5:
        g = g.transpose(0, 1, 3, 2, 4)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def _jit(name: str, pool: dict, kinds: tuple = ()):
    """Build-once cache for the jitted device ops: jax must not be
    imported at module import time (the engine's lazy-import rule),
    and a fresh jax.jit wrapper per call would retrace every call.
    Keyed on (op, pool geometry, dtype, layout) — NOT op name alone:
    one process serving two model configs (two replicas, a debug engine
    next to a prod one) must not replay a callable whose donated
    buffers and reshape constants were traced for the other pool's
    shape. ``kinds`` (pool_kinds): which layers of the token-order KV
    go to which kind's arrays, each through that kind's physical ids
    (``phys`` is a dict by kind)."""
    key = (name, *_pool_key(pool), kinds)
    fn = _JITS.get(key)
    if fn is not None:
        return fn
    jax, jnp = _jx()
    model = tuple(range(sum(len(layers) for _, layers in kinds)))

    def rows(x, layers: tuple):
        """Rows ``layers`` of ``x``: ``x`` itself where they are all of
        them in order (known here, so a model of one kind is never
        gathered from or stacked back)."""
        return x if layers == model else x[np.asarray(layers)]

    if name == "gather_table":
        # back into the model's layer order
        order = tuple(np.argsort([l for _, ls in kinds for l in ls]))

        @partial(jax.jit, static_argnames=("acc_len",))
        def fn(pool, phys, acc_len):
            out = {}
            for i, src in enumerate(("k", "v")):
                views = []
                for kind, _ in kinds:
                    dst, ids = POOL_KEYS[kind][i], phys[kind]
                    g = _from_blocks(pool[dst][:, ids])
                    pad = acc_len - g.shape[1]
                    if pad > 0:
                        g = jnp.pad(g, ((0, 0), (0, pad))
                                    + ((0, 0),) * (g.ndim - 2))
                    views.append(g)
                out[src] = rows(jnp.concatenate(views), order)
            return out
    elif name == "scatter_table":
        @partial(jax.jit, donate_argnums=(0,))
        def fn(pool, acc, phys):
            return {**pool, **{
                dst: pool[dst].at[:, phys[kind]].set(_to_blocks(
                    rows(acc[src], layers), phys[kind].shape[0], pool[dst]))
                for kind, layers in kinds
                for src, dst in zip(("k", "v"), POOL_KEYS[kind])}}
    elif name == "copy_block":
        @partial(jax.jit, donate_argnums=(0,))
        def fn(pool, src, dst):
            return {**pool, **{
                key: pool[key].at[:, dst].set(pool[key][:, src])
                for kind, keys in POOL_KEYS.items()
                if kind not in (WINDOW, STATE)
                for key in keys if key in pool}}
    elif name == "write_state":
        @partial(jax.jit, donate_argnums=(0,))
        def fn(pool, state, slot):
            return {**pool, **{
                key: pool[key].at[:, slot].set(
                    state[key].astype(pool[key].dtype))
                for key in _held_keys(pool, STATE)}}
    else:
        raise KeyError(name)
    _JITS[key] = fn
    return fn


def scatter_bucket(pool: dict, kv: dict, phys, nb: int,
                   kinds=None) -> dict:
    """Write a bucket-padded prefill's KV into ``nb`` physical blocks
    (pad-garbage blocks redirected to trash by the caller's phys):
    scatter_table over a bucket's width, one compile per bucket size."""
    phys = _by_kind(phys, pool)
    if any(ids.shape[0] != nb for ids in phys.values()):
        raise ValueError(f"a bucket of {nb} blocks needs {nb} ids a kind")
    return scatter_table(pool, kv, phys, kinds)


def gather_table(pool: dict, phys, acc_len: int, kinds=None) -> dict:
    """Gather one block table's KV into a contiguous accumulator
    (layers, acc_len, kvh, hd) for chunked prefill over a cached
    prefix. acc_len >= table_width * block_size (zero tail). No
    longer on the decode hot path — decode attends straight through
    the table (ops/pallas/paged_attention.py); this stays for the
    prefix-hit prefill accumulator and debug/parity tooling."""
    return _jit("gather_table", pool, _layout(pool, kinds))(
        pool, _by_kind(phys, pool), acc_len)


def scatter_table(pool: dict, acc: dict, phys, kinds=None) -> dict:
    """Write an accumulator's first positions back through a physical
    target vector (shared-prefix and beyond-horizon slots point at trash
    so shared blocks are never written). One compile a width."""
    return _jit("scatter_table", pool, _layout(pool, kinds))(
        pool, acc, _by_kind(phys, pool))


def fresh_state(pool: dict) -> dict:
    """What a request's state layers start from: a zero state and a zero
    conv tail a layer, {"ssm": (state layers, ...), "conv": ...}; {} for a
    model without state layers."""
    _, jnp = _jx()
    return {key: jnp.zeros((pool[key].shape[0], *pool[key].shape[2:]),
                           pool[key].dtype)
            for key in POOL_KEYS[STATE] if key in pool}


def write_state(pool: dict, state: dict, slot: int) -> dict:
    """A prefill's state layers' outputs ({"ssm", "conv"}: the state and
    the conv tail a layer at the prompt's length) become ``slot``'s, whole:
    whatever the slot held before is gone."""
    _, jnp = _jx()
    return _jit("write_state", pool)(
        pool, {key: state[key] for key in _held_keys(pool, STATE)},
        jnp.int32(slot))


def copy_block(pool: dict, src: int, dst: int) -> dict:
    """Device-side block copy (the COW divergence path; blocks of the
    kind a sequence holds whole, global or latent: the only ones that
    are ever shared)."""
    _, jnp = _jx()
    return _jit("copy_block", pool)(pool, jnp.int32(src),
                                    jnp.int32(dst))


def resolve_attn_impl(impl: str) -> str:
    """Resolve the paged decode attention impl knob. ``auto`` picks
    the fused block-table kernel on a real TPU backend and the gather
    view elsewhere (CPU tier-1 still exercises the kernel explicitly
    via impl='paged_flash' + interpret)."""
    if impl not in ("auto", "paged_flash", "gather"):
        raise ValueError(
            f"paged attn impl must be auto|paged_flash|gather, "
            f"got {impl!r}")
    if impl == "auto":
        from ray_tpu.ops.attention import _on_tpu
        return "paged_flash" if _on_tpu() else "gather"
    return impl


def _places(tables, pos, bs):
    """{kind: (physical block, row in it)} of positions ``pos`` (slots,)
    or (slots, w) in each kind's tables."""
    _, jnp = _jx()
    out = {}
    for kind, tb in tables.items():
        blk = jnp.clip(pos // bs, 0, tb.shape[1] - 1)
        phys = (tb[jnp.arange(tb.shape[0]), blk] if pos.ndim == 1
                else jnp.take_along_axis(tb, blk, axis=1))
        out[kind] = (phys, pos % bs)
    return out


def _entries(blocks, lens):
    """The block ids the pool's writer gets, flat: ``blocks`` (slots,) of
    a decode step, negative (no entry: ops/pallas/paged_attention.py
    kv_write) where the slot attends 0 positions, or (slots, w) of a
    verify round as they are."""
    _, jnp = _jx()
    if lens.ndim == 1:
        blocks = jnp.where(lens > 0, blocks, -1)
    return blocks.reshape(-1)


def _pool_attend(cfg, tables, at, lens, *, impl, interpret, mesh, axis):
    """The attention hook of lm.decode_logits_core / verify_tokens_core
    against the block pool, ``attend(ref, q, k, v, pool) -> (o, pool)``:
    the new rows k, v go into the layer's place (``ref.kind_index``) in
    the stacked pools of its kind at ``at[kind]`` = (physical block, row
    in it), and q attends through ``tables[kind]`` over ``lens`` valid
    positions, a window layer over the last ``sliding_window`` of them.
    ``at`` and ``lens`` are (slots,) for a decode step, (slots, w) for
    a verify round, which attends with the multi-query functions.
    A decode step's slot with ``lens`` 0 holds no request (``_live``):
    impl 'paged_flash' hands the writer a negative block for it
    (``_entries``), so neither kernel moves a byte for such a slot, and
    its row comes back zeros.

    Both impls see a layer as a WINDOW of its kind's flat pool (every
    layer's blocks in one (layers * blocks, kvh, block_size, hd) array:
    a reshape of the row-major stacked pool, no copy), by adding
    ``kind_index * blocks`` to the block ids: nothing slices a layer
    out or stacks one back, so a program that donates the pool holds
    one buffer of it.

    impl='paged_flash': the aliased block writer, then the kernel
    that walks each slot's LIVE table entries (``ceil(length /
    block_size)`` of them, a run-time count; a window layer's from the
    block that holds its window's first position) and fetches those
    blocks with its own DMAs (ops/pallas/paged_attention.py kv_write,
    paged_attention) — no gathered view, no pool-sized copy. XLA
    performs no write on the pool itself: it would lay the pool out
    token-major for it and convert the whole pool back for the kernel
    on every layer. A verify round attends through the gather twin
    (paged_attention_verify) after the same write. With ``mesh`` both
    run under one shard_map: kv heads sharded over ``axis``, tables,
    ids and lengths replicated — each shard works on its own head
    slice, no collectives.

    impl='gather': the reference, for the CPU and for parity. A
    scatter on the carry, then table_view (the table's blocks in
    table order, so masked tail positions contribute exact zeros)
    under lm._gqa_attend_cached / _gqa_attend_multi. Same f32 math;
    the kernel's online softmax agrees with it to f32 rounding
    (bitwise on the integer constructions tests/test_zz_paged_attn.py
    pins). GSPMD partitions it as it is."""
    jax, _ = _jx()
    from ray_tpu.llm import model as lm
    from ray_tpu.ops.pallas import paged_attention as pa
    lead = lens.shape                   # (slots,) or (slots, w)
    multi = len(lead) == 2
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if multi and WINDOW in tables:
        raise NotImplementedError(
            "the verify forward attends global layers only")
    # heads of 64 values lie two a pool row (``row_shapes``): the walk sees
    # kvh / pack heads of pack * hd lanes, each with pack * group queries
    # that are zero outside their own head's lanes, so a score is the
    # head's own q . k and its lanes of the output the head's own sum
    pack = heads_packed(cfg)
    if pack > 1 and multi:
        raise NotImplementedError(
            "the verify forward attends K/V heads of a whole lane tile "
            "only: heads packed two a pool row have no multi-query walk")
    own = np.eye(pack, dtype=bool)[:, None, :, None]    # (pack, 1, pack, 1)

    def packed(qg):
        """(slots, kvh, g, hd) -> (slots, kvh / pack, pack * g, pack * hd)"""
        if pack == 1:
            return qg
        _, jnp = _jx()
        g = qg.shape[-2]
        qg = qg.reshape(-1, kvh // pack, pack, g, 1, hd)
        return jnp.where(own, qg, 0).reshape(
            -1, kvh // pack, pack * g, pack * hd)

    def unpacked(o):
        """``packed`` back for the walk's output: each head's own lanes."""
        if pack == 1:
            return o
        _, jnp = _jx()
        g = o.shape[-2] // pack
        o = o.reshape(-1, kvh // pack, pack, g, pack, hd)
        return jnp.sum(jnp.where(own, o, 0), axis=-2)

    def row(x, pool):
        """A step's new rows (n, kvh, hd) as the pool keeps them."""
        return x.reshape(-1, *pool.shape[2:3], pool.shape[-1]).astype(
            pool.dtype)

    def flat(pool):
        return pool.reshape(-1, *pool.shape[2:])

    if LATENT in tables:
        return _latent_attend(cfg, tables[LATENT], at[LATENT], lens,
                              flat, impl=impl, interpret=interpret)

    def window_kw(kind):
        return {} if kind == GLOBAL else {"window": cfg.sliding_window}

    scale = {"head_dim": hd} if pack > 1 else {}
    if impl == "paged_flash":
        def writer(kind):
            def write_attend(qg, k, v, kf, vf, tb, blocks, rows, ln):
                kf, vf = pa.kv_write(kf, vf, blocks, rows, k, v,
                                     interpret=interpret)
                o = (pa.paged_attention_verify(qg, kf, vf, tb, ln) if multi
                     else pa.paged_attention(qg, kf, vf, tb, ln,
                                             interpret=interpret,
                                             **window_kw(kind), **scale))
                return o, kf, vf

            if mesh is None:
                return write_attend
            from jax.sharding import PartitionSpec as P
            heads, new = P(None, axis, None, None), P(None, axis, None)
            qs = P(*(None,) * len(lead), axis, None, None)
            return jax.shard_map(
                write_attend, mesh=mesh,
                in_specs=(qs, new, new, heads, heads, P(), P(), P(),
                          P()),
                out_specs=(qs, heads, heads), check_vma=False)
        write_attend = {kind: writer(kind) for kind in tables}

        def attend(ref, q, k, v, pool):
            kk, vk = POOL_KEYS[ref.kind]
            kp, vp = pool[kk], pool[vk]
            phys, off = at[ref.kind]
            base = ref.kind_index * kp.shape[1]
            o, kf, vf = write_attend[ref.kind](
                packed(q.reshape(*lead, kvh, h // kvh, hd)),
                row(k, kp), row(v, vp),
                flat(kp), flat(vp), tables[ref.kind] + base,
                _entries(phys + base, lens), off.reshape(-1), lens)
            return unpacked(o).reshape(*lead, h * hd), {
                **pool, kk: kf.reshape(kp.shape), vk: vf.reshape(vp.shape)}
    else:
        attn = lm._gqa_attend_multi if multi else lm._gqa_attend_cached

        def attend(ref, q, k, v, pool):
            kk, vk = POOL_KEYS[ref.kind]
            kp, vp = pool[kk], pool[vk]
            phys, off = at[ref.kind]
            l = ref.kind_index
            kp = kp.at[l, phys, :, off].set(
                k.reshape(*lead, *kp.shape[2:3], -1).astype(kp.dtype))
            vp = vp.at[l, phys, :, off].set(
                v.reshape(*lead, *vp.shape[2:3], -1).astype(vp.dtype))
            tb = tables[ref.kind] + l * kp.shape[1]

            def view(pool):     # (slots, positions, kvh, hd), heads apart
                g = pa.table_view(flat(pool), tb)
                return g.reshape(*g.shape[:2], kvh, hd)
            o = attn(q.reshape(*lead, h * hd), view(kp), view(vp), lens,
                     cfg, **window_kw(ref.kind))
            return o, {**pool, kk: kp, vk: vp}
    return attend


def _latent_attend(cfg, tables, at, lens, flat, *, impl, interpret):
    """``_pool_attend`` for LATENT layers (a model of them alone): q is
    the ABSORBED query (..., heads, kv_lora_rank + qk_rope_head_dim:
    lm.latent_absorb), k and v the new positions' c and kr rows, and
    what comes back is each head's attention-weighted sum of the c rows
    (..., heads * kv_lora_rank), for lm.latent_unabsorb. One shared row
    a position: multi-query attention with a key of [c | kr] and the
    value c, the row fetched once. impl='paged_flash': the aliased row
    writer, then the walk (ops/pallas/paged_attention.py latent_write,
    latent_decode; a verify round the gather twin). impl='gather': a
    scatter on the carry and the plain reference."""
    _, jnp = _jx()
    from ray_tpu.llm import model as lm
    from ray_tpu.ops.pallas import paged_attention as pa
    lead = lens.shape
    multi = len(lead) == 2
    scale = lm.softmax_scale(cfg, LATENT)
    ck, rk = POOL_KEYS[LATENT]
    phys, off = at

    def tiles(x, pool):
        return _pad_last(x, pool.shape[-1])

    def attend(ref, q, c, kr, pool):
        cp, rp = pool[ck], pool[rk]
        l = ref.kind_index
        tb = tables + l * cp.shape[1]
        lat = cfg.kv_lora_rank
        q = jnp.concatenate([tiles(q[..., :lat], cp), tiles(q[..., lat:], rp)],
                            axis=-1).reshape(*lead, cfg.n_heads, -1)
        c, kr = tiles(c, cp), tiles(kr, rp)
        if impl == "paged_flash":
            cf, rf = pa.latent_write(
                flat(cp), flat(rp), _entries(phys + l * cp.shape[1], lens),
                off.reshape(-1), c.reshape(-1, c.shape[-1]).astype(cp.dtype),
                kr.reshape(-1, kr.shape[-1]).astype(rp.dtype),
                interpret=interpret)
            o = (pa.latent_attention_reference(q, cf, rf, tb, lens,
                                               sm_scale=scale) if multi
                 else pa.latent_decode(q, cf, rf, tb, lens, sm_scale=scale,
                                       interpret=interpret))
            cp, rp = cf.reshape(cp.shape), rf.reshape(rp.shape)
        else:
            cp = cp.at[l, phys, off].set(c.astype(cp.dtype))
            rp = rp.at[l, phys, off].set(kr.astype(rp.dtype))
            o = pa.latent_attention_reference(q, flat(cp), flat(rp), tb,
                                              lens, sm_scale=scale)
        return o[..., :lat].reshape(*lead, -1), {**pool, ck: cp, rk: rp}
    return attend


def _pool_state_step(pool, live, *, impl, interpret):
    """The state hook of lm.decode_logits_core against the pool's stack of
    states, ``step(ref, x, dt, A, B, C, D, pool) -> (y (slots, h, p)
    float32, pool)``: the slots of ``live`` (slots,) bool move on one token
    in the layer's place (``ref.kind_index``) in ``pool["ssm"]``, the row
    index being the slot; another slot's state stays bit for bit. None for a
    pool without state layers: such a model's programs trace nothing of it.

    impl='paged_flash': the kernel that walks the LIVE slots and moves each
    one's state of the layer once, in place in the stack, which is its
    aliased operand (ops/pallas/ssm_step.py): the list of live slots is made
    here, once a step, outside the layer scan; an idle slot costs no byte of
    state and its row of y is zeros. Nothing slices a layer out of the
    stack or puts one back.

    impl='gather': the reference, for the CPU and for parity. The layer's
    states sliced out, ``ops/ssm.py ssd_step`` over every slot,
    ``where(live, new, old)`` and the layer put back into the carry: every
    slot's state read twice and written once whatever the load (an idle
    slot's row of y is the rule's, garbage nobody reads)."""
    sk = POOL_KEYS[STATE][0]
    if sk not in pool:
        return None
    _, jnp = _jx()
    from jax import lax
    from ray_tpu.ops import ssm
    if impl == "paged_flash":
        from ray_tpu.ops.pallas import ssm_step as kernel
        ids, count = kernel.live_slots(live)

        def step(ref, x, dt, A, B, C, D, pool):
            y, states = kernel.ssm_step(pool[sk], ref.kind_index, ids, count,
                                        x, dt, A, B, C, D,
                                        interpret=interpret)
            return y, {**pool, sk: states}
    else:
        def step(ref, x, dt, A, B, C, D, pool):
            l = ref.kind_index
            st = lax.dynamic_index_in_dim(pool[sk], l, keepdims=False)
            y, new = ssm.ssd_step(x, dt, A, B, C, D, st)
            new = jnp.where(live[:, None, None, None], new, st)
            return y, {**pool, sk: lax.dynamic_update_index_in_dim(
                pool[sk], new, l, 0)}
    return step


def state_slot_steps(impl: str, slots: int, live: int, steps: int) -> int:
    """Slot states ``steps`` decode steps with ``live`` of ``slots`` slots
    holding a request move A STATE LAYER, by the implementation's own rule
    (``_pool_state_step``; the engine's counter): the kernel walks the
    live slots, its reference passes over every slot."""
    return (live if impl == "paged_flash" else slots) * steps


def _live(tables):
    """(slots,) bool: the slots that hold a request, read off the tables.
    A slot that is not in the decode block has a row of TRASH in every
    kind (llm/engine.py _prepare); a request's row of the kind it holds
    whole starts with a block of its own (a window layer's row gives its
    first blocks back as the window passes them, so it cannot say)."""
    return next(tb for kind, tb in tables.items()
                if kind != WINDOW)[:, 0] != TRASH


def _paged_logits_core(params, pool, tables, lengths, tokens, cfg, *,
                       impl="gather", interpret=False, mesh=None,
                       axis="tensor", chosen=False):
    """One decode step's (slots, vocab) f32 logits for every slot
    against the paged pool: lm.decode_logits_core with the new token's
    place in the pool worked out from the tables, and the write and
    the attention over the table plugged in (_pool_attend). ONE vector
    says which slots hold a request (``_live``): the others attend 0
    positions, write nothing and are no row of an expert layer's groups,
    so they cost the step's kernels nothing (their logits are garbage
    nobody reads); a state layer's rule moves the same slots' states
    (_pool_state_step). Returns (logits, pool, the expert layers' counts or
    None)."""
    _, jnp = _jx()
    from ray_tpu.llm.model import decode_logits_core
    bs = pool_k(pool).shape[-2]
    positions = lengths
    live = _live(tables)
    return decode_logits_core(
        params, pool, tokens, positions, cfg,
        _pool_attend(cfg, tables, _places(tables, positions, bs),
                     jnp.where(live, positions + 1, 0), impl=impl,
                     interpret=interpret, mesh=mesh, axis=axis),
        live, chosen,
        _pool_state_step(pool, live, impl=impl, interpret=interpret))


def paged_decode_logits(params, pool, tables, lengths, tokens, cfg, *,
                        impl="gather", interpret=False, mesh=None,
                        axis="tensor", chosen=False):
    """The (slots, vocab) f32 logits of ONE decode step against the
    block pool, which is left untouched (not donated) — the parity
    entry point: the same step under impl='paged_flash' and
    impl='gather' must agree (chip_smoke.py checks that on the chip at
    real widths; tests/test_zz_paged_attn.py under the interpreter).
    ``tables``: (slots, width), or by layer kind for a model with
    window layers. ``chosen`` (a model whose layers are each one mixer):
    (logits, the experts every slot chose in each expert layer: lm.
    decode_logits_core, the pool AFTER the step, a new one: a comparison
    then goes on from exactly what the step of these logits wrote)."""
    impl = resolve_attn_impl(impl)
    key_ = ("paged_decode_logits", *_pool_key(pool), impl,
            bool(interpret), mesh, axis, *(("chosen",) if chosen else ()))
    fn = _JITS.get(key_)
    if fn is None:
        jax, _ = _jx()

        @partial(jax.jit, static_argnames=("cfg",))
        def paged_decode_logits(params, pool, tables, lengths, tokens,
                                cfg):
            out = _paged_logits_core(
                params, pool, _by_kind(tables, pool), lengths, tokens, cfg,
                impl=impl, interpret=interpret, mesh=mesh, axis=axis,
                chosen=chosen)
            return (out[0], out[3], out[1]) if chosen else out[0]
        fn = _JITS[key_] = paged_decode_logits
    return fn(params, pool, tables, lengths, tokens, cfg)


def paged_decode_steps(params, pool, tables, lengths, tokens, temps,
                       key, cfg, n: int, top_ps=None, top_ks=None, *,
                       impl="gather", interpret=False, mesh=None,
                       axis="tensor"):
    """n chained decode steps against the block pool in ONE dispatch
    (lax.scan on device; step i samples under fold_in(key, i)), which
    amortizes the host<->device roundtrip. Returns (tokens (n, slots)
    int32, pool). The pool is DONATED and is the carry of the step
    scan and of each step's layer scan: the program updates it in
    place (tests/test_aot_tpu_compile.py holds the compiled program to
    that). A slot whose table row is TRASH holds no request: it
    attends nothing, writes nothing (under impl='paged_flash' not even
    the trash block) and its tokens are garbage to discard; the caller
    masks on eos and bounds n by each slot's horizon.
    ``impl``/``interpret``/``mesh`` are trace-time
    constants — each combination (x pool geometry) compiles its own
    variant, cached in _JITS. (The program itself, decode_steps_program,
    also returns the expert layers' counts a step; the engine reads
    them with the tokens.)"""
    outs, pool, _, _ = decode_steps_program(
        pool, impl=impl, interpret=interpret, mesh=mesh, axis=axis)(
        params, pool, tables, lengths, tokens, temps, key, cfg, n,
        top_ps, top_ks)
    return outs, pool


def decode_steps_program(pool, *, impl="gather", interpret=False,
                         mesh=None, axis="tensor"):
    """The jitted program behind paged_decode_steps for a pool of this
    geometry (arrays or their shapes), built once a variant. It
    returns (tokens (n, slots), pool, counts, last): counts, for a
    model with expert layers, ``{"routed", "local", "experts_hit"}``
    (n,) int32 each, a step's sums over its layers (models/moe.py
    serve_block), else None; ``last`` (slots,) is the final step's
    row of tokens, the scan's carry: what the NEXT block starts from,
    left on the device for an engine that enqueues that block before
    it reads this one back (carry_tokens)."""
    impl = resolve_attn_impl(impl)
    key_ = ("paged_decode_steps", *_pool_key(pool), impl,
            bool(interpret), mesh, axis)
    fn = _JITS.get(key_)
    if fn is None:
        jax, jnp = _jx()
        from jax import lax as _lax
        from ray_tpu.llm.model import sample

        @partial(jax.jit, static_argnames=("cfg", "n"),
                 donate_argnums=(1,))
        def paged_decode_steps(params, pool, tables, lengths, tokens,
                               temps, key, cfg, n, top_ps, top_ks):
            tables = _by_kind(tables, pool)

            def body(carry, i):
                pool, toks = carry
                at, step_key = lengths + i, jax.random.fold_in(key, i)
                logits, pool, counts = _paged_logits_core(
                    params, pool, tables, at, toks, cfg, impl=impl,
                    interpret=interpret, mesh=mesh, axis=axis)
                out = sample(logits, temps, step_key, top_ps, top_ks)
                return (pool, out), (out, counts)
            (pool, last), (outs, counts) = _lax.scan(
                body, (pool, tokens), jnp.arange(n, dtype=jnp.int32))
            return outs, pool, counts, last
        fn = _JITS[key_] = paged_decode_steps
    return fn


def carry_tokens(last, tokens, keep, sharding):
    """The first tokens of a decode block that is enqueued before its
    predecessor is read back: ``last`` (the predecessor's final row,
    still on the device) where ``keep``, else the host's ``tokens`` (a
    slot admitted since, or an idle one): (slots,) int32 under
    ``sharding``, the one a block's tokens always arrive in, so both
    ways of making them meet the same compiled decode program. One
    program a sharding, whatever the predecessor's size."""
    key_ = ("carry_tokens", sharding)
    fn = _JITS.get(key_)
    if fn is None:
        jax, jnp = _jx()

        @partial(jax.jit, out_shardings=sharding)
        def carry_tokens(last, tokens, keep):
            return jnp.where(keep, last, tokens)
        fn = _JITS[key_] = carry_tokens
    return fn(last, tokens, keep)


def _paged_verify_core(params, pool, tables, lengths, tokens, cfg, *,
                       impl="gather", interpret=False, mesh=None,
                       axis="tensor"):
    """Speculative verify against the block pool: score w in-flight
    tokens per slot (last emitted + up to w-1 drafts) in ONE forward.
    Runs lm.verify_tokens_core — decode_logits_core widened to w — with
    the same table arithmetic, pool write and attention choice as
    _paged_logits_core (_pool_attend), so verify numerics can never
    drift from sequential paged decode.

    tokens: (b, w) int32, column 0 at cache position ``lengths``;
    writes all w KVs through the table (positions past a slot's table
    clamp into its last row — within the full-horizon reservation
    those writes land beyond the logical length, masked out of every
    attention and overwritten by the next real write, so no rollback
    device op exists). Returns ((b, w, vocab) f32 logits, pool): row j
    is the distribution for position lengths+j+1, the verdict on
    draft j+1. Acceptance is a host decision (llm/spec.py) — the
    device ships w*vocab floats per slot per ROUND, not per token.

    impl='paged_flash' attends with the gather-twin multi-query
    attention (ops/pallas/paged_attention.paged_attention_verify) —
    the fused single-query kernel doesn't take multi-query rows yet;
    the twin still gathers ONCE per round where sequential decode
    gathered per token, which is the spec-decode win the bench
    measures."""
    _, jnp = _jx()
    from ray_tpu.llm.model import verify_tokens_core
    wq = tokens.shape[1]
    bs = pool_k(pool).shape[-2]
    pos = lengths[:, None] + jnp.arange(wq, dtype=jnp.int32)[None]
    return verify_tokens_core(
        params, pool, tokens, lengths, cfg,
        _pool_attend(cfg, tables, _places(tables, pos, bs), pos + 1,
                     impl=impl, interpret=interpret, mesh=mesh, axis=axis))


def paged_verify_steps(params, pool, tables, lengths, tokens, cfg, *,
                       impl="gather", interpret=False, mesh=None,
                       axis="tensor"):
    """One speculative verify round in one dispatch — the verify twin
    of paged_decode_steps (pool donated, updated in place). tokens:
    (b, w) with w drawn from the engine's verify-width buckets; each
    (pool geometry, w, impl) combination compiles exactly once, cached
    in _JITS (the compile-discipline tests count both the _JITS keys
    and devmon's jit(paged_verify_steps) compile spans)."""
    return verify_steps_program(
        pool, int(tokens.shape[1]), impl=impl, interpret=interpret,
        mesh=mesh, axis=axis)(params, pool, tables, lengths, tokens,
                              cfg)


def verify_steps_program(pool, wq: int, *, impl="gather",
                         interpret=False, mesh=None, axis="tensor"):
    """The jitted program behind paged_verify_steps for a pool of this
    geometry and a verify width, built once a variant."""
    impl = resolve_attn_impl(impl)
    key_ = ("paged_verify_steps", wq, *_pool_key(pool), impl,
            bool(interpret), mesh, axis)
    fn = _JITS.get(key_)
    if fn is None:
        jax, _ = _jx()

        @partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
        def paged_verify_steps(params, pool, tables, lengths, tokens,
                               cfg):
            return _paged_verify_core(
                params, pool, _by_kind(tables, pool), lengths, tokens, cfg,
                impl=impl, interpret=interpret, mesh=mesh, axis=axis)
        fn = _JITS[key_] = paged_verify_steps
    return fn
