"""Pallas TPU paged-attention decode kernel, and the pool's block writer.

Fuses the two reference designs the serving stack sits between:
vLLM's PagedAttention (block tables over a fixed KV pool) and the
flash-attention tiling already in ``flash_attention.py`` (online
softmax over chunks of the context). One decode step used to cost a
full ``gather_table`` — an O(slots x max_len x layers) HBM copy
materializing the contiguous ``(slots, max_len)`` attention view —
before any attention math ran. Here the kernel walks each slot's block
table DIRECTLY and fetches the slot's physical pool blocks itself; the
gathered view never exists. ``gather_table`` stays only on the
prefix-hit prefill path and in debug/parity tooling.

The walk. The grid is ``(slots,)``; both pools stay in HBM
(``memory_space=pl.ANY``), and tables and lengths are the two
scalar-prefetch operands. For a slot of ``length`` valid positions the
kernel runs ``ceil(live / C)`` turns of a loop, ``live =
live_blocks(length, block_size)``: a run-time trip count, so a slot
with 37 tokens costs one turn and one with 4,096 costs 32, and no
table width, bucket or compiled variant enters into it. A turn works
on one CHUNK of C pool blocks: for each of the chunk's table entries
that is live it starts one DMA ``k_pool[table[b, i*C + c]] -> kbuf``
and one for V. The pool is HEAD-MAJOR — one layer is ``(num_blocks,
kv_heads, block_size, head_dim)`` — so one physical block is
contiguous and one descriptor moves ALL KV heads of it (32 KB at 8
heads x 16 x 128 bf16). Two buffers each for K and V: chunk ``i + 1``
is started before chunk ``i`` is waited for and computed. Entries
past a slot's last live block are neither started nor waited for
(``fetched_positions`` is the rule, exported for the engine's
counter), so the kernel never reads a block the slot does not own;
what an unfetched part of a buffer still holds is masked out of both
products. A slot that holds no request arrives with length 0 (PR 57;
llm/kvcache.py _paged_logits_core reads it off the tables): its grid
step fetches nothing, computes nothing and writes a row of zeros, and
the writers below skip an entry whose block id is negative, so a decode
step's kernels cost what its live slots cost.

A WINDOW layer (``window=w``: the query at position ``length - 1``
attends positions ``>= length - w`` only) starts its walk at the block
that holds position ``length - w`` (``first_block``): the chunks before
it are never entered, the entries before it in its first chunk are not
fetched, and the positions below ``length - w`` in that block are
masked. So the blocks a sequence has passed are never read, and the
cache manager frees them (llm/kvcache.py). The window is a static
parameter; the kernel's operands are the same five, and the variant is
named ``paged_decode_window``.

``chunk_blocks`` is the one place that chooses C, from what a position
costs in the pool (a kind's row: llm/kvcache.py row_shapes): about 128
positions a chunk (one lane-width of scores; 512 for a latent row, a
sixth to a third of the bytes by the configuration's kv_lora_rank), capped
so that the buffers stay within 4 MB of VMEM.

A LATENT layer (multi-head latent attention in its absorbed form) keeps
one row a position, [c | kr], and no head axis: every head attends the
same rows, the key being the whole row and the value its c part.
``latent_decode`` is the same walk over such rows: one DMA a block for c
and one for kr, the row fetched ONCE and used for both products;
``latent_write`` is their ``kv_write``; ``latent_attention_reference``
the plain twin (and the verify round's attention).

(Until PR 29 the grid was ``(slots, kv_heads, table_width)`` with one
``(block_size, head_dim)`` tile a step, blocks past the last live one
clamped in the index_map on the belief that the pipeliner's elided
fetch made short slots free. The fetch was elided; the grid step was
not: 65,536 steps a layer at 32 slots x 8 heads x 256 blocks whatever
the contexts held, and the benchmark's ledger read 0.22% of the HBM
roofline.)

Numerics mirror ``ray_tpu.llm.model._gqa_attend_cached`` (the gather
path's attention): f32 scores, post-dot ``/ sqrt(head_dim)`` scale,
f32 exp, f32 running max / sum / accumulator, f32 output. q and k
enter the score product as stored when both are bf16 (a product of
two bf16 values is exact in f32, so the sum is the one the upcast
gave); p stays f32 into the PV product and v is widened. Online
softmax is an exact refactoring of the masked softmax, so the two
impls agree to f32 rounding (and bitwise on integer-valued
constructions; see tests/test_zz_paged_attn.py).

``kv_write`` is the pool's one writer on the decode path (PR 31): the
new token's K and V rows of every slot go into their blocks through a
custom call that aliases both pools in to out, a read-modify-write of
each entry's block. The walk needs the pool row-major and head-major;
a write that XLA performs itself makes XLA lay the pool out token-major
and convert the whole pool back on every layer. With the writer beside
the walk no XLA instruction touches the pool, and a decode program
carries one buffer of it from its first step to its last.

``table_view`` is the one place that turns the head-major layout back
into the contiguous ``(slots, len, kv_heads, head_dim)`` view the
gather path and the references attend over.

Interpret mode (``interpret=True``) runs the same kernel logic through
the Pallas interpreter — tier-1 (JAX_PLATFORMS=cpu) exercises the real
table walk, DMAs, masking, and online-softmax phases, not a shadow
implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_POSITIONS = 128           # one lane-width of scores a chunk
# a latent row's bytes are its configuration's: 768 in the pool at c 256
# values + kr 64 (in an array 128 wide), 1,280 at c 512 + kr 64, where 8 KV
# heads' K and V are 4,096: four lane-widths a chunk, so that a fetch is
# 384 KB / 640 KB (the K/V walk's is 512 KB). Reasoned at the first width,
# not swept: no run has varied it (PERF.md section 7)
LATENT_CHUNK_POSITIONS = 512
BUFFER_BYTES = 4 * 1024 * 1024  # a kind's two arrays, two buffers each


def chunk_blocks(row_bytes, block_size, positions=CHUNK_POSITIONS):
    """C: the pool blocks one fetch of the walk brings in, from what a
    position costs in the walked pool alone (``row_bytes``: both of a
    kind's arrays, llm/kvcache.py row_bytes) and the ``positions`` a
    chunk aims at."""
    return max(1, min(positions // block_size,
                      BUFFER_BYTES // (2 * block_size * row_bytes)))


def live_blocks(length, block_size):
    """Pool blocks the walk visits for a slot with ``length`` valid
    positions: ``ceil(length / block_size)``, none for an idle slot
    (length 0: one that holds no request). The kernel's trip count and
    the engine's counter both come from here, so it is written to work
    on ints, numpy arrays and traced scalars alike."""
    return (length + block_size - 1) // block_size


def first_block(length, block_size, window=None):
    """The first pool block the walk visits: 0, or for a window layer
    the block that holds position ``length - window``. Ints, numpy
    arrays and traced scalars alike."""
    if window is None:
        return length * 0
    return (length - window) * (length > window) // block_size


def fetched_positions(length, block_size, window=None):
    """Positions of K (and of V) the walk fetches for a slot with
    ``length`` valid positions: its live blocks from the first one the
    walk visits, whole, and nothing after them (the tail of a chunk is
    guarded, not rounded up)."""
    return (live_blocks(length, block_size)
            - first_block(length, block_size, window)) * block_size


def _ceil_sum(n, bs):
    """Sum of ``ceil(x / bs)`` over x = 1 .. n."""
    q, r = divmod(n, bs)
    return bs * q * (q + 1) // 2 + (q + 1) * r


def _floor_sum(m, bs):
    """Sum of ``y // bs`` over y = 1 .. m."""
    q, r = divmod(m, bs)
    return bs * q * (q - 1) // 2 + q * (r + 1)


def fetched_positions_run(length, steps, block_size, window=None):
    """``fetched_positions`` summed over ``steps`` consecutive decode
    steps of a slot that starts at ``length`` (lengths ``length ...
    length + steps - 1``; one under 1 fetches nothing), in closed form
    on plain ints: the engine counts a decode block's fetches with it,
    once a slot that is in the block."""
    a, b = max(length, 1), length + steps - 1
    blocks = 0
    if b >= a:
        blocks = _ceil_sum(b, block_size) - _ceil_sum(a - 1, block_size)
    if window is not None and b > window:
        blocks -= _floor_sum(b - window, block_size) \
            - _floor_sum(max(a - 1 - window, 0), block_size)
    return blocks * block_size


def _walk_kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                 kbuf, vbuf, sems, *, bs, cb, width, window=None,
                 head_dim=None):
    b_ = pl.program_id(0)
    kvh, g, hd = q_ref.shape[1:]
    t = cb * bs                                 # positions a chunk
    length = lengths_ref[b_]

    @pl.when(length < 1)
    def _():
        # a slot that holds no request: no fetch, no chunk, and a
        # finite row for the products that follow
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length >= 1)
    def _():
        live = jnp.minimum(live_blocks(length, bs), width)
        n_chunks = (live + cb - 1) // cb
        first = 0 if window is None else jnp.minimum(
            first_block(length, bs, window), live - 1)
        chunk0 = 0 if window is None else first // cb
        floor = None if window is None else length - window

        def fetch(i, buf, wait):
            """Start (or wait for) the DMAs of chunk ``i`` into buffer
            ``buf``: one a pool block for K, one for V, the live ones
            only."""
            def entry(c, carry):
                # a wait only needs the copy's shape, not its source
                blk = 0 if wait else tables_ref[b_, i * cb + c]
                rows = pl.ds(pl.multiple_of(c * bs, bs), bs)
                for s, (pool, dst) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf))):
                    cp = pltpu.make_async_copy(
                        pool.at[blk], dst.at[buf, :, rows, :],
                        sems.at[s, buf])
                    cp.wait() if wait else cp.start()
                return carry

            jax.lax.fori_loop(
                0 if window is None else jnp.maximum(first - i * cb, 0),
                jnp.minimum(live - i * cb, cb), entry, 0)

        q = q_ref[0]                                # (kvh, g, hd)
        if not (q.dtype == kbuf.dtype == jnp.bfloat16):
            q = q.astype(jnp.float32)
        if window is None:
            fetch(0, 0, wait=False)
        else:
            fetch(chunk0, jax.lax.rem(chunk0, 2), wait=False)

        def chunk(i, carry):
            m_prev, l_prev, acc = carry
            buf = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_chunks)
            def _():
                fetch(i + 1, 1 - buf, wait=False)

            fetch(i, buf, wait=True)
            k = kbuf[buf].astype(q.dtype)           # (kvh, t, hd)
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) / jnp.sqrt(
                    jnp.float32(head_dim or hd))    # (kvh, g, t)
            at = i * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            keep = at < length
            if window is not None:
                keep = jnp.logical_and(keep, at >= floor)
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            # rows past ``length`` hold whatever the pool or an earlier
            # chunk left there: p is 0 for them, and 0 x NaN is not
            at = i * t + jax.lax.broadcasted_iota(jnp.int32, (kvh, t, hd), 1)
            rows = at < length
            if window is not None:
                rows = jnp.logical_and(rows, at >= floor)
            v = jnp.where(rows, vbuf[buf].astype(jnp.float32), 0.0)
            acc = acc * alpha + jax.lax.dot_general(
                p, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # (kvh, g, hd)
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            chunk0, n_chunks, chunk,
            (jnp.full((kvh, g, 1), NEG_INF, jnp.float32),
             jnp.zeros((kvh, g, 1), jnp.float32),
             jnp.zeros((kvh, g, hd), jnp.float32)))
        o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    window=None, interpret=False, head_dim=None):
    """Single-token decode attention straight through block tables.

    q: (slots, kv_heads, group, head_dim) — grouped queries, one token
    per slot; k_pool/v_pool: (num_blocks, kv_heads, block_size,
    head_dim) — ONE layer of the engine pool; tables: (slots, width)
    int32 physical block ids (trash-padded); lengths: (slots,) int32
    valid positions per slot INCLUDING the current token (>= 1), or 0
    for a slot that holds no request: nothing of its table is read and
    its output is zeros.
    Returns (slots, kv_heads, group, head_dim) float32 — the same
    value ``_gqa_attend_cached`` computes from the gathered view, with
    no gathered view. ``window`` (static): attend the last ``window``
    positions only, walking from the block that holds the first of them.
    ``head_dim``: the width the scores are scaled by where it is not the
    rows' (heads narrower than a lane tile packed two a row, the queries
    zero outside their own head's lanes: llm/kvcache.py _pool_attend).
    """
    b, kvh, g, hd = q.shape
    nb, kvh_p, bs, hd_p = k_pool.shape
    if (kvh_p, hd_p) != (kvh, hd):
        raise ValueError(
            f"pool heads/dim {(kvh_p, hd_p)} != query {(kvh, hd)}")
    w = tables.shape[1]
    cb = min(chunk_blocks(2 * kvh * hd * k_pool.dtype.itemsize, bs), w)

    def _qmap(b_, t, ln):
        return (b_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd), _qmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd), _qmap),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, cb * bs, hd), k_pool.dtype),
            pltpu.VMEM((2, kvh, cb * bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_walk_kernel, bs=bs, cb=cb, width=w,
                               window=window, head_dim=head_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        # a slot's walk starts and waits for its own DMAs: slots are
        # independent, and a chip with two cores may split them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_decode" if window is None else "paged_decode_window",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool,
      v_pool)


def _kv_write_kernel(blocks_ref, rows_ref, k_new, v_new, k_in, v_in,
                     k_out, v_out, buf, sems):
    del k_in, v_in                  # the same buffers as k_out, v_out
    i = pl.program_id(0)
    blk, row = blocks_ref[i], rows_ref[i]
    pools = (k_out, v_out)

    @pl.when(blk >= 0)              # a negative block: no entry here
    def _():
        reads = [pltpu.make_async_copy(pool.at[blk], buf.at[s], sems.at[s])
                 for s, pool in enumerate(pools)]
        writes = [pltpu.make_async_copy(buf.at[s], pool.at[blk], sems.at[s])
                  for s, pool in enumerate(pools)]
        for cp in reads:
            cp.start()
        for s, new in enumerate((k_new, v_new)):
            reads[s].wait()
            block = buf[s]                      # (kvh, bs, hd)
            here = jax.lax.broadcasted_iota(
                jnp.int32, block.shape, 1) == row
            buf[s] = jnp.where(here, new[0][:, None, :], block)
            writes[s].start()
        for cp in writes:
            cp.wait()


def kv_write(k_pool, v_pool, blocks, rows, k_new, v_new, *,
             interpret=False):
    """Write ``n`` new positions' K and V rows into the pools IN PLACE:
    ``pool[blocks[i], :, rows[i]] = new[i]`` for i = 0..n-1, in that
    order (a later entry for the same place wins, and two entries in
    one block both land). An entry with ``blocks[i] < 0`` is no entry:
    its grid step moves nothing (a slot that holds no request; the
    decode steps no longer write the trash block).

    k_pool/v_pool: (num_blocks, kv_heads, block_size, head_dim) —
    every layer of the engine pool flattened along its first two axes,
    so ``blocks`` are ``layer * blocks_a_layer + physical block``;
    blocks, rows: (n,) int32; k_new/v_new: (n, kv_heads, head_dim) in
    the pools' dtypes. Returns the two pools: the same buffers, aliased
    in to out, so a program that donates or carries the pool copies
    nothing pool-sized.

    Why a kernel for a 2-KB write: XLA would lay the pool out
    token-major for a one-row scatter, the walk kernel above needs it
    row-major and head-major, and XLA then converts the WHOLE pool
    between the two around every layer (eight pool-sized copies a
    decode step, three quarters of the chat cell's device time before
    PR 31). A custom call pins the layout. A row of a bf16 pool is a
    sixteenth of a (16, 128) tile and cannot be the target of a DMA,
    so each entry is a read-modify-write of its whole block: the block
    (all heads: 32 KB at 8 x 16 x 128 bf16) to VMEM, row ``rows[i]``
    replaced under an iota mask, the block back. K and V move
    together; entries run one after another, since two may name one
    block."""
    n, kvh, hd = k_new.shape
    _, kvh_p, bs, hd_p = k_pool.shape
    if (kvh_p, hd_p) != (kvh, hd) or v_pool.shape != k_pool.shape \
            or v_new.shape != k_new.shape:
        raise ValueError(
            f"pools {k_pool.shape}, {v_pool.shape} do not take rows "
            f"{k_new.shape}, {v_new.shape}")
    if {k_new.dtype, v_new.dtype, v_pool.dtype} != {k_pool.dtype}:
        raise ValueError("pools and new rows must share one dtype")

    def _row(i, blocks, rows):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, kvh, hd), _row),
            pl.BlockSpec((1, kvh, hd), _row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, kvh, bs, hd), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands count the two scalar-prefetch arrays: 4, 5 are the pools
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kv_write",
    )(blocks.astype(jnp.int32), rows.astype(jnp.int32), k_new, v_new,
      k_pool, v_pool)


def table_view(pool, tables):
    """One layer of the head-major pool seen through block tables as
    the contiguous attention view: (num_blocks, kv_heads, block_size,
    head_dim) x (slots, width) -> (slots, width * block_size, kv_heads,
    head_dim). The same bytes in token order: attention over the view
    is the kernel's reference."""
    b, w = tables.shape
    _, kvh, bs, hd = pool.shape
    g = pool[tables]                        # (b, w, kvh, bs, hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(b, w * bs, kvh, hd)


def paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                              window=None):
    """Gather-then-softmax reference (the exact math
    ``_gqa_attend_cached`` runs on the gathered view) — the parity
    target the kernel is tested against, and the debug tool for
    bisecting a kernel/table discrepancy on device."""
    hd = q.shape[-1]
    vk = table_view(k_pool, tables)
    vv = table_view(v_pool, tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bkgd,blkd->bkgl", qf,
                        vk.astype(jnp.float32)) / jnp.sqrt(hd)
    pos = jnp.arange(vk.shape[1])[None]
    mask = pos < lengths[:, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgl,blkd->bkgd", probs,
                      vv.astype(jnp.float32))


def paged_attention_verify(q, k_pool, v_pool, tables, lengths):
    """Multi-query verify attention through block tables — the gather
    twin of ``paged_attention`` widened to w in-flight queries per
    slot for speculative decoding: query j attends the cached history
    PLUS the draft tokens written ahead of it this round, under a
    per-query causal mask.

    q: (slots, w, kv_heads, group, head_dim) — the last emitted token
    plus up to w-1 draft tokens per slot; k_pool/v_pool: one layer of
    the engine pool as in ``paged_attention``; tables: (slots, width)
    int32; lengths: (slots, w) int32 valid positions per QUERY
    including that query's own token (column j = cached + j + 1).
    Returns (slots, w, kv_heads, group, head_dim) float32.

    This is a gather-based implementation (materializes the table view
    per layer, like ``paged_attention_reference``): one verify round
    replaces w sequential decode steps, so it pays ONE gather where
    the sequential gather path paid w — the win the spec-decode bench
    measures. Extending the fused one-query-per-block-walk kernel
    above to multi-query rows is future work; exact-zero masking
    (NEG_INF then softmax) keeps pool bytes beyond each query's mask
    bitwise-irrelevant, so verify rows reproduce sequential decode's
    attention exactly."""
    hd = q.shape[-1]
    vk = table_view(k_pool, tables)
    vv = table_view(v_pool, tables)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bwkgd,blkd->bwkgl", qf,
                        vk.astype(jnp.float32)) / jnp.sqrt(hd)
    mask = (jnp.arange(vk.shape[1])[None, None]
            < lengths[:, :, None])                  # (b, wq, w*bs)
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bwkgl,blkd->bwkgd", probs,
                      vv.astype(jnp.float32))


# --- latent rows -----------------------------------------------------------


def _latent_walk_kernel(tables_ref, lengths_ref, q_ref, c_hbm, r_hbm, o_ref,
                        cbuf, rbuf, sems, *, bs, cb, width, sm_scale):
    b_ = pl.program_id(0)
    h = q_ref.shape[2]
    lat = cbuf.shape[-1]
    t = cb * bs                                 # positions a chunk
    length = lengths_ref[b_]

    @pl.when(length < 1)
    def _():
        # a slot that holds no request: no fetch, no chunk, and a
        # finite row for the products that follow
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length >= 1)
    def _():
        live = jnp.minimum(live_blocks(length, bs), width)
        n_chunks = (live + cb - 1) // cb

        def fetch(i, buf, wait):
            """Start (or wait for) the DMAs of chunk ``i`` into buffer
            ``buf``: one a live pool block for c, one for kr."""
            def entry(c, carry):
                blk = 0 if wait else tables_ref[b_, i * cb + c]
                rows = pl.ds(pl.multiple_of(c * bs, bs), bs)
                for s, (pool, dst) in enumerate(((c_hbm, cbuf),
                                                 (r_hbm, rbuf))):
                    cp = pltpu.make_async_copy(
                        pool.at[blk, 0], dst.at[buf, rows, :],
                        sems.at[s, buf])
                    cp.wait() if wait else cp.start()
                return carry

            jax.lax.fori_loop(0, jnp.minimum(live - i * cb, cb), entry, 0)

        q = q_ref[0, 0]                             # (h, lat + rope)
        if not (q.dtype == cbuf.dtype == jnp.bfloat16):
            q = q.astype(jnp.float32)
        qc, qr = q[:, :lat], q[:, lat:]
        fetch(0, 0, wait=False)

        def chunk(i, carry):
            m_prev, l_prev, acc = carry
            buf = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_chunks)
            def _():
                fetch(i + 1, 1 - buf, wait=False)

            fetch(i, buf, wait=True)
            c = cbuf[buf]                           # (t, lat): key AND value
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(qc, c.astype(q.dtype), nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr, rbuf[buf].astype(q.dtype), nt,
                                       preferred_element_type=jnp.float32)
                 ) * sm_scale                       # (h, t)
            keep = i * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
                < length
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            # rows past ``length`` hold whatever the pool or an earlier
            # chunk left there: p is 0 for them, and 0 x NaN is not
            rows = i * t + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0) \
                < length
            v = jnp.where(rows, c, jnp.zeros_like(c))
            if v.dtype == jnp.bfloat16:
                p = p.astype(jnp.bfloat16)          # one MXU pass, f32 sums
            else:
                v = v.astype(jnp.float32)
            acc = acc * alpha + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_chunks, chunk,
            (jnp.full((h, 1), NEG_INF, jnp.float32),
             jnp.zeros((h, 1), jnp.float32),
             jnp.zeros((h, lat), jnp.float32)))
        o_ref[0, 0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def latent_decode(q, c_pool, kr_pool, tables, lengths, *, sm_scale,
                  interpret=False):
    """Single-token decode attention over LATENT rows straight through
    block tables: every head of a slot attends the same rows.

    q: (slots, heads, kv_lora_rank + qk_rope_head_dim), the absorbed
    queries [q_nope W_UK | q_rope]; c_pool: (num_blocks, block_size,
    kv_lora_rank) and kr_pool: (num_blocks, block_size,
    qk_rope_head_dim), ONE layer of the engine pool (no head axis);
    tables, lengths as ``paged_attention``. Returns (slots, heads,
    kv_lora_rank) float32: softmax_j(sm_scale * q . [c_j | kr_j]) c_j,
    each head's weighted sum of the c rows, which the caller takes
    through W_UV. The walk is ``paged_attention``'s (a run-time count of
    live chunks, double-buffered DMAs, the running-max softmax); a row is
    fetched once and is key and value both. The kernel sees the rows as
    one shared KV head, (slots, 1, heads, width) against (num_blocks, 1,
    block_size, width): multi-query attention, the same five operands as
    the K/V walk. With bf16 rows the probabilities enter the second
    product in bf16 (float32 sums), as in the flash kernels; float32
    rows keep them float32."""
    b, h, width = q.shape
    nb, bs, lat = c_pool.shape
    rope = kr_pool.shape[-1]
    if kr_pool.shape[:2] != (nb, bs) or lat + rope != width:
        raise ValueError(
            f"rows {c_pool.shape} | {kr_pool.shape} do not take queries "
            f"{q.shape}")
    w = tables.shape[1]
    cb = min(chunk_blocks((lat + rope) * c_pool.dtype.itemsize, bs,
                          LATENT_CHUNK_POSITIONS), w)

    def _qmap(b_, t, ln):
        return (b_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h, width), _qmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, h, lat), _qmap),
        scratch_shapes=[
            pltpu.VMEM((2, cb * bs, lat), c_pool.dtype),
            pltpu.VMEM((2, cb * bs, rope), kr_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_latent_walk_kernel, bs=bs, cb=cb, width=w,
                               sm_scale=float(sm_scale))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h, lat), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="latent_decode",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q[:, None],
      c_pool[:, None], kr_pool[:, None])[:, 0]


def _latent_write_kernel(blocks_ref, rows_ref, c_new, r_new, c_in, r_in,
                         c_out, r_out, cbuf, rbuf, sems):
    del c_in, r_in                  # the same buffers as c_out, r_out
    i = pl.program_id(0)
    blk, row = blocks_ref[i], rows_ref[i]
    pairs = ((c_out, cbuf, c_new), (r_out, rbuf, r_new))

    @pl.when(blk >= 0)              # a negative block: no entry here
    def _():
        reads = [pltpu.make_async_copy(pool.at[blk], buf, sems.at[s])
                 for s, (pool, buf, _) in enumerate(pairs)]
        writes = [pltpu.make_async_copy(buf, pool.at[blk], sems.at[s])
                  for s, (pool, buf, _) in enumerate(pairs)]
        for cp in reads:
            cp.start()
        for s, (_, buf, new) in enumerate(pairs):
            reads[s].wait()
            block = buf[...]                    # (bs, width)
            here = jax.lax.broadcasted_iota(
                jnp.int32, block.shape, 0) == row
            buf[...] = jnp.where(here, new[0], block)
            writes[s].start()
        for cp in writes:
            cp.wait()


def latent_write(c_pool, kr_pool, blocks, rows, c_new, kr_new, *,
                 interpret=False):
    """``kv_write`` for latent rows: ``c_pool[blocks[i], rows[i]] =
    c_new[i]`` and the same for kr, in order, IN PLACE (both pools
    aliased in to out; each entry a read-modify-write of its block, an
    entry with ``blocks[i] < 0`` nothing at all).
    c_pool: (num_blocks, block_size, kv_lora_rank), kr_pool:
    (num_blocks, block_size, qk_rope_head_dim), every layer of the
    engine pool flattened along its first two axes; c_new: (n,
    kv_lora_rank), kr_new: (n, qk_rope_head_dim)."""
    n, lat = c_new.shape
    nb, bs, lat_p = c_pool.shape
    rope = kr_pool.shape[-1]
    if lat_p != lat or kr_pool.shape[:2] != (nb, bs) \
            or kr_new.shape != (n, rope):
        raise ValueError(
            f"pools {c_pool.shape}, {kr_pool.shape} do not take rows "
            f"{c_new.shape}, {kr_new.shape}")
    if {c_new.dtype, kr_new.dtype, kr_pool.dtype} != {c_pool.dtype}:
        raise ValueError("pools and new rows must share one dtype")

    def _row(i, blocks, rows):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, lat), _row),
            pl.BlockSpec((1, 1, rope), _row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((bs, lat), c_pool.dtype),
            pltpu.VMEM((bs, rope), kr_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _latent_write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(c_pool.shape, c_pool.dtype),
                   jax.ShapeDtypeStruct(kr_pool.shape, kr_pool.dtype)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_write",
    )(blocks.astype(jnp.int32), rows.astype(jnp.int32), c_new[:, None],
      kr_new[:, None], c_pool, kr_pool)


def latent_attention_reference(q, c_pool, kr_pool, tables, lengths, *,
                               sm_scale):
    """Gather-then-softmax twin of ``latent_decode``, and the verify
    round's attention: q (slots, heads, width) with lengths (slots,), or
    w queries a slot, (slots, w, heads, width) with lengths (slots, w)
    (query j attends positions < lengths[:, j]). float32 throughout;
    returns q's shape with kv_lora_rank in place of width."""
    single = q.ndim == 3
    if single:
        q, lengths = q[:, None], lengths[:, None]
    b, w = tables.shape
    rows = jnp.concatenate([c_pool[tables], kr_pool[tables]], axis=-1)
    rows = rows.reshape(b, w * c_pool.shape[1], -1).astype(jnp.float32)
    scores = jnp.einsum("bwhd,bld->bwhl", q.astype(jnp.float32),
                        rows) * sm_scale
    mask = jnp.arange(rows.shape[1])[None, None] < lengths[:, :, None]
    scores = jnp.where(mask[:, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bwhl,bld->bwhd", probs,
                     rows[..., :c_pool.shape[-1]])
    return out[:, 0] if single else out
