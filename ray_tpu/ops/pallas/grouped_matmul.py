"""Pallas TPU grouped matmul: rows of one matrix, sorted into groups,
each group multiplied by its own weight matrix.

The hot op of a dropless mixture-of-experts layer (``models/moe.py``):
``gmm(lhs (m, k), rhs (E, k, n), group_sizes (E,)) -> (m, n)`` computes
``lhs[o_e : o_e + s_e] @ rhs[e]`` for every group ``e`` (``o`` the running
sum of the sizes). Rows at or past ``sum(group_sizes)`` belong to no group
and come out zero, which is ``jax.lax.ragged_dot``'s convention too.

Tiling (the group metadata follows the public description in
``jax/experimental/pallas/ops/tpu/megablox``): the rows are cut into tiles
of ``tm``; the grid walks the tiles group by group. A tile that straddles
a group boundary is visited once for each group it holds rows of; every
visit multiplies the whole tile and stores only the rows of its own group
(the rest of the output tile stays as the earlier visits left it). Visits
of one tile are consecutive, so the output tile stays in VMEM between
them. The number of visits depends on the sizes, the grid does not: it
has the upper bound ``m / tm + E`` steps, and the steps past the last
visit point at the last visit's blocks (no copy) and do nothing.

bf16 (or whatever the operands are) in, float32 accumulate. With the
contraction and the output width in one block each (the default up to
2048) the weight matrix of a group is fetched once for all of the group's
row tiles, and the rows are read once.

The backward needs two more products. ``d_lhs`` is the same kernel on the
transposed weights (``gmm_t``, ``transpose_rhs``: the block is contracted
over its last axis, which the MXU does for free); handed several (dout,
rhs) pairs it sums their products in float32 before the one rounding,
which is how gate and up, who share their lhs, return one gradient for it.
``d_rhs[e] = lhs_e^T @ dout_e`` is a second kernel, ``tgmm``: it walks the
same visits and accumulates over each group's row tiles; a group with no
rows is visited once with every row masked, so its gradient is written as
zeros. ``tgmm`` takes several ``dout`` too (one walk over lhs, one
gradient each).

Rows read by id (``gmm_rows``, ``tgmm(..., rows=)``): the lhs of a
mixture-of-experts layer's first products is a gathered view, row ``p`` =
``src[rows[p]]`` of the (T, k) hidden state, k times as large as ``src``.
Handed ``src`` and the ids, the kernels never have it written: a visit's
``tm`` rows come into VMEM by the kernel's own DMAs, the next tile's in
flight under this tile's products (``_with_fetched_tile``, which also says
what the form of the 256 starts costs). A DMA moves whole (8, 128) tiles of
32-bit words and a row of a bf16 (T, 2048) array is a strip of a tile
sixteen rows deep, so the kernels read a PACKED copy of ``src``
(``_pack_rows``: one pass over ``src``, 0.3 ms for 67 MB): a row is then
4 KB that lie together, two bf16 halves of the row to a word, unpacked in
VMEM by strided loads, a shift and a mask. The ids are a sixth prefetched
scalar array (131,072 int32, 512 KB, fit). A tile is fetched whole if any
step visits it: ``gmm_rows`` neither fetches nor multiplies the tiles that
lie wholly in the tail and writes their zeros; ``tgmm`` visits one only
for an empty group's masked visit. The tail is the assignments of experts
another device holds (a held slice, an expert mesh axis). The train step
hands the kernels its work list (``models/moe.py _held_sum``: the first
``R`` sorted assignments, ``R`` twice the device's expected share in
whole row tiles), so a call's tail is at most ``R`` less the device's own
rows, about half of ``R``, and the row ids ``R`` int32 (80 KB in the cell
whose 163,840 assignments took 640 KB). The tail of EVERY other device's
assignments (fifteen rows of sixteen at 32 experts of 512) still comes
where a routing overflows ``R`` (that step's full path) and in serving
(``gmm_stacked`` below, and ``serve_block`` on a layer's own weights).
Fused over gate and up (two rhs, two outputs; two dout, two gradients) a
13-us visit hides the fetch to within 1.8 us (``gmm_rows``: 7.7 ms for
6.7) and 1.5-3.3 us (``tgmm``: 8.0-9.0 for 7.1): measured, PERF.md, PR 43.
The gradient of ``src`` is not this module's: ``d_lhs`` comes back in
sorted order and the caller, who knows what ``rows`` is a permutation of,
brings it home.

Serving (``gmm_stacked``, forward only) has two more needs. The weights of
all layers are one stacked array and a decode step must not copy a layer
out of it, so the kernel is handed the whole stack and the layer's index
(a fifth prefetched scalar) and reads ``stack[layer, group]`` in place. And
a device that holds a slice of the experts sees most rows belong to no
group of its own: a decode step of 32 tokens x 8 has 256 rows, ~32 of them
on 16 held experts, 0-6 a group. Rows come in tiles of ``DECODE_ROW_TILE``
there (a visit multiplies its whole tile, and at 256 rows a tile every
visit would do eight times the rows the step has for it), an empty group is
never visited (its weights are not read), and a visit of the tail writes
its zeros without a product and without a weight block: its steps, like
those past the last visit, name the block the last multiplying visit held
(the train kernels keep their tail's product: their tail is empty or
under ``R`` rows, and their contraction is one block, so a repeated step
names one block too).
The small-tile variant is named ``moe_gmm_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the scoped default (16 MiB) is too small for the 2048-wide blocks below;
# a v5e core has 128 MiB
VMEM_LIMIT = 96 * 1024 * 1024
# rows a tile. Every group boundary inside a tile costs one more visit of
# the whole tile, so a large tile wastes work (64 groups on 131,072 rows:
# a quarter more at 512 rows, an eighth at 256), and a small one feeds the
# MXU badly: on a v5e 256 measured best (155-158 TFLOP/s forward against
# 149 at 512 and 153 at 128; PERF.md, PR 28)
ROW_TILE = 256
# serving, up to DECODE_ROWS rows a call (a decode step's slots x k)
DECODE_ROW_TILE = 32
DECODE_ROWS = 512


def _tile(dim: int, want: int, unit: int) -> int:
    """The largest tile <= ``want`` that divides ``dim`` and is a multiple
    of ``unit``; the whole dimension where there is none (a block equal to
    the array is always legal)."""
    if dim <= want:
        return dim
    for t in range(want - want % unit, 0, -unit):
        if dim % t == 0:
            return t
    return dim


def _visits(group_sizes, m: int, tm: int, *, tail: bool):
    """Which (group, row tile) each grid step works on.

    Returns ``offsets (G + 1,)``, ``group_ids (V,)``, ``tile_ids (V,)`` and
    ``n_visits (1,)``, all int32, for ``V = m / tm + G - 1`` grid steps.
    With ``tail`` the rows past the last group form one more group, number
    ``E``, that has no weights (``gmm`` writes zeros there) and no visit
    when it is empty; without it every group is visited at least once
    (``tgmm`` has to write an empty group's zeros) and rows past the last
    group are never visited.
    """
    sizes = group_sizes.astype(jnp.int32)
    if tail:
        sizes = jnp.concatenate([sizes, (m - jnp.sum(sizes))[None]])
    n_groups = sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first_tile = jnp.minimum(offsets[:-1] // tm, tiles_m - 1)
    n_tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first_tile,
                        0 if tail else 1)
    n_visits = jnp.sum(n_tiles)
    n_steps = tiles_m + n_groups - 1
    # steps past the last visit repeat it: same blocks, nothing to copy
    step = jnp.minimum(jnp.arange(n_steps, dtype=jnp.int32), n_visits - 1)
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), n_tiles,
                           total_repeat_length=n_steps)[step]
    first_visit = jnp.cumsum(n_tiles) - n_tiles
    tile_ids = first_tile[group_ids] + step - first_visit[group_ids]
    return offsets, group_ids, tile_ids, n_visits[None]


def _own_rows(offsets, group, tile, tm: int, width: int):
    """(tm, width) mask of the tile's rows that belong to ``group``."""
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return jnp.logical_and(rows >= offsets[group], rows < offsets[group + 1])


def _n_multiplying(visits, n_groups: int):
    """How many of ``_visits``' steps multiply: the visits of a group that
    has weights (the tail's, and the steps past the last visit, follow
    them)."""
    _, group_ids, _, n_visits = visits
    return jnp.sum(jnp.logical_and(
        group_ids < n_groups,
        jnp.arange(group_ids.shape[0]) < n_visits[0]), dtype=jnp.int32)


# --- rows read by id -------------------------------------------------------

def row_words(k: int, dtype) -> int:
    """Sublane rows of 128 32-bit words that one row of a (T, k) array
    packs into, or 0 where the kernels cannot fetch such a row by id: a
    DMA addresses whole (8, 128) tiles, so a row has to be a whole number
    of them (4 KB: 2048 bf16 or 1024 float32 values), and only bf16 and
    4-byte values are unpacked."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.bfloat16 and dtype.itemsize != 4:
        return 0
    nbytes = k * dtype.itemsize
    return 0 if nbytes % 4096 else nbytes // 512


def _pack_rows(src):
    """(T, k) -> (T * w, 128) uint32, ``w = row_words(k)``: row ``t`` is
    the ``w`` sublane rows from ``t * w`` on, whole tiles that lie
    together in HBM. As ``src`` itself lies, a row is a strip of a tile
    sixteen rows deep (bf16: two rows to a word), which no DMA can take
    alone. A bf16 word holds column ``c`` (low half) and column
    ``c + k / 2`` (high half), so unpacking is a shift and a mask."""
    if src.dtype.itemsize == 4:
        words = jax.lax.bitcast_convert_type(src, jnp.uint32)
    else:
        half = src.shape[1] // 2
        lo, hi = (jax.lax.bitcast_convert_type(x, jnp.uint16).astype(
            jnp.uint32) for x in (src[:, :half], src[:, half:]))
        words = lo | (hi << 16)
    return words.reshape(-1, 128)


def _unpack_tile(buf, tm: int, w: int, dtype):
    """The (tm, k) row tile whose packed rows ``buf`` (tm * w, 128) holds:
    word ``a`` of every row is one strided load (a sublane of each
    tile)."""
    words = [buf[pl.ds(a, tm, stride=w), :] for a in range(w)]
    if jnp.dtype(dtype).itemsize == 4:
        return jnp.concatenate([pltpu.bitcast(x, dtype) for x in words],
                               axis=1)
    # a bf16 value is the high half of the float32 of the same value
    lo = [pltpu.bitcast(x << 16, jnp.float32).astype(dtype) for x in words]
    hi = [pltpu.bitcast(x & jnp.uint32(0xFFFF0000), jnp.float32).astype(
        dtype) for x in words]
    return jnp.concatenate(lo + hi, axis=1)


def _with_fetched_tile(rows, src, buf, sem, tile_ref, v, tile_ids,
                       n_fetching, products, *, tm, w):
    """The walk's row fetch, once a grid step ``v``: for each of the first
    ``n_fetching`` steps (the others name no tile of their own), run
    ``products()`` with the step's row tile in ``tile_ref`` (tm, k), row
    ``p`` of it ``src[rows[p]]``. ``src`` is packed (``_pack_rows``) and
    stays in HBM; a tile comes into the VMEM buffer ``buf`` by one DMA a
    row (``w`` sublane rows, 4 KB at 2048 bf16), each tile once and whole:
    consecutive steps name the same tile or the next one, so the first
    visit of a tile waits for its copies, unpacks them into ``tile_ref``
    and starts the NEXT tile's into the buffer just emptied, where they run
    under this tile's products.

    How the 256 starts are written decides what they cost (the fused gate
    and up call at the cell's shape, beside 6.71 ms for two calls on a
    gathered copy; PERF.md, PR 43). A loop runs on the scalar unit in
    front of the products, 15 ns a start: 9.13 ms. Unrolled, in a block
    of their own: 8.59. Unrolled in ONE block with the products, which
    read ``tile_ref`` and no buffer a copy writes, the scheduler lays
    them beside the products: 7.7. So the last tile starts its own copies
    once more, waited for at the last fetching step, rather than branch
    around the starts. The loop is unrolled where the kernel is lowered,
    not in Python (1.5 s to trace at every call, in a cell whose set-up is
    25 s), and ``products`` is traced twice; four times, ``tgmm`` outgrew
    the core's instruction memory and ran at 19.5 ms for 8.0."""
    tile = tile_ids[v]
    last_tile = tile_ids[jnp.maximum(n_fetching - 1, 0)]
    first_visit = jnp.logical_or(
        v == 0, tile_ids[jnp.maximum(v - 1, 0)] != tile)
    first_visit = jnp.logical_and(v < n_fetching, first_visit)

    def starts(t, unroll):
        def start(r, _):
            at = pl.multiple_of(rows[t * tm + r] * w, w)
            pltpu.make_async_copy(
                src.at[pl.ds(at, w), :],
                buf.at[pl.ds(pl.multiple_of(r * w, w), w), :], sem).start()

        jax.lax.fori_loop(0, tm, start, None, unroll=unroll)

    def wait():
        # one wait for a tile's tm copies: the semaphore counts bytes,
        # and a wait needs only a copy's shape
        pltpu.make_async_copy(buf, buf, sem).wait()

    @pl.when(first_visit)
    def _first():
        @pl.when(v == 0)
        def _cold():
            starts(tile, unroll=False)

        wait()
        tile_ref[...] = _unpack_tile(buf, tm, w, tile_ref.dtype)
        starts(jnp.minimum(tile + 1, last_tile), unroll=True)
        products()

    @pl.when(jnp.logical_and(v < n_fetching, jnp.logical_not(first_visit)))
    def _again():
        products()

    @pl.when(v == n_fetching - 1)
    def _drain():
        wait()


def _fetch_scratch(tm: int, k: int, dtype) -> list:
    return [pltpu.VMEM((tm * row_words(k, dtype), 128), jnp.uint32),
            pltpu.SemaphoreType.DMA(()), pltpu.VMEM((tm, k), dtype)]


def _gmm_rows_kernel(offsets, group_ids, tile_ids, n_visits, more, rows,
                     src, *refs, tm, w, n_rhs):
    rhs_refs, out_refs = refs[:n_rhs], refs[n_rhs:2 * n_rhs]
    buf, sem, tile_ref = refs[2 * n_rhs:]
    v = pl.program_id(1)
    own = _own_rows(offsets, group_ids[v], tile_ids[v], tm,
                    out_refs[0].shape[1])

    def products():
        for rhs_ref, out_ref in zip(rhs_refs, out_refs):
            acc = jnp.dot(tile_ref[...], rhs_ref[...],
                          preferred_element_type=jnp.float32)
            out_ref[...] = jnp.where(own, acc, out_ref[...].astype(
                jnp.float32)).astype(out_ref.dtype)

    _with_fetched_tile(rows, src, buf, sem, tile_ref, v, tile_ids, more[0],
                       products, tm=tm, w=w)

    # the tail has no weights: its rows are zeros, without a fetch or a
    # product
    @pl.when(jnp.logical_and(v >= more[0], v < n_visits[0]))
    def _tail():
        for out_ref in out_refs:
            out_ref[...] = jnp.where(own, jnp.zeros_like(out_ref),
                                     out_ref[...])


def gmm_rows(src, rows, rhs, group_sizes, *, tiles=None, interpret=False):
    """``gmm`` of the rows ``src[rows]`` against each of ``rhs`` (a tuple
    of (E, k, n) weights: gate and up read one fetched tile), the gathered
    (m, k) array never written: src (T, k), rows (m,) int32, every one in
    range -> a tuple of (m, n). Forward only (``tgmm`` takes ``rows`` for
    the weights' gradient; the gradient of ``src`` is the caller's, who
    knows what ``rows`` is a permutation of). Where a row of ``src`` cannot
    be fetched by id (``row_words``) the rows are gathered first."""
    (m,), k = rows.shape, src.shape[1]
    n_groups, _, n = rhs[0].shape
    w = row_words(k, src.dtype)
    # the contraction is one block (a fetched row is whole)
    tm, tn = tiles or (_tile(m, ROW_TILE, 8),
                       _tile(n, max(128, 2048 * 2048 // k), 128))
    if not w:
        lhs = src.at[rows].get(mode="promise_in_bounds")
        return tuple(_gmm_call(lhs, r, group_sizes, interpret=interpret)
                     for r in rhs)
    if any(r.shape != (n_groups, k, n) for r in rhs) or m % tm:
        raise ValueError(f"gmm_rows: src {src.shape}, {m} rows in tiles of "
                         f"{tm}, rhs {[r.shape for r in rhs]}")
    visits = _visits(group_sizes, m, tm, tail=True)
    more = _n_multiplying(visits, n_groups)[None]

    def rhs_index(n_i, v, o, group_ids, t, nv, more, rows):
        # a step that multiplies nothing keeps the last weight block
        at = jnp.where(v < more[0], v, jnp.maximum(more[0] - 1, 0))
        return jnp.minimum(group_ids[at], n_groups - 1), 0, n_i

    def out_index(n_i, v, o, g, tile_ids, nv, more, rows):
        return tile_ids[v], n_i

    return tuple(pl.pallas_call(
        functools.partial(_gmm_rows_kernel, tm=tm, w=w, n_rhs=len(rhs)),
        out_shape=[jax.ShapeDtypeStruct((m, n), src.dtype)] * len(rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((None, k, tn), rhs_index)] * len(rhs),
            out_specs=[pl.BlockSpec((tm, tn), out_index)] * len(rhs),
            grid=(n // tn, visits[1].shape[0]),
            scratch_shapes=_fetch_scratch(tm, k, src.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_rows",
    )(*visits, more, rows.astype(jnp.int32), _pack_rows(src), *rhs))


def _gmm_kernel(offsets, group_ids, tile_ids, n_visits, *refs, tm, tiles_k,
                n_groups, transpose_rhs, stacked=False, pairs=1):
    # ``stacked``: one more prefetched scalar (the layer), and a visit of
    # the tail multiplies nothing. ``pairs``: that many lhs, then as many
    # rhs; their products are summed in float32
    refs = refs[1:] if stacked else refs
    lhs_refs, rhs_refs = refs[:pairs], refs[pairs:2 * pairs]
    out_ref, *scratch = refs[2 * pairs:]
    v, k_i = pl.program_id(1), pl.program_id(2)
    group = group_ids[v]
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product():
        return sum(jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], contract,
            preferred_element_type=jnp.float32)
            for lhs_ref, rhs_ref in zip(lhs_refs, rhs_refs))

    def store(acc):
        own = _own_rows(offsets, group, tile_ids[v], tm, out_ref.shape[1])
        # the tail (group == n_groups) has no weights: its rows are zeros
        acc = jnp.where(group < n_groups, acc, 0.0)
        out_ref[...] = jnp.where(own, acc, out_ref[...].astype(
            jnp.float32)).astype(out_ref.dtype)

    if stacked:
        @pl.when(jnp.logical_and(v < n_visits[0], group >= n_groups))
        def _tail():
            @pl.when(k_i == tiles_k - 1)
            def _zeros():
                store(jnp.zeros(out_ref.shape, jnp.float32))

    @pl.when(jnp.logical_and(v < n_visits[0], group < n_groups)
             if stacked else v < n_visits[0])
    def _visit():
        if tiles_k == 1:
            store(product())
            return
        acc_ref, = scratch

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += product()

        @pl.when(k_i == tiles_k - 1)
        def _store():
            store(acc_ref[...])


def _gmm_call(lhs, rhs, group_sizes, *, transpose_rhs=False, tiles=None,
              interpret=False):
    """``lhs`` and ``rhs`` may be tuples of as many arrays of one shape
    each: the sum of the pairs' grouped products, rounded once."""
    lhss = lhs if isinstance(lhs, tuple) else (lhs,)
    rhss = rhs if isinstance(rhs, tuple) else (rhs,)
    lhs, rhs = lhss[0], rhss[0]
    m, k = lhs.shape
    n_groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if rhs.shape[2 if transpose_rhs else 1] != k or len(lhss) != len(rhss) \
            or any(x.shape != lhs.shape for x in lhss) \
            or any(x.shape != rhs.shape for x in rhss):
        raise ValueError(f"gmm: lhs {[x.shape for x in lhss]} does not "
                         f"contract with rhs {[x.shape for x in rhss]} "
                         f"(transpose_rhs={transpose_rhs})")
    tm, tk, tn = tiles or (_tile(m, ROW_TILE, 8), _tile(k, 2048, 128),
                           _tile(n, 2048, 128))
    if m % tm or k % tk or n % tn:
        raise ValueError(f"gmm: tiles {(tm, tk, tn)} do not divide "
                         f"{(m, k, n)}")
    tiles_k = k // tk
    meta = _visits(group_sizes, m, tm, tail=True)

    def rhs_index(n_i, v, k_i, offsets, group_ids, tile_ids, n_visits):
        group = jnp.minimum(group_ids[v], n_groups - 1)     # the tail's
        return (group, n_i, k_i) if transpose_rhs else (group, k_i, n_i)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          n_groups=n_groups, transpose_rhs=transpose_rhs,
                          pairs=len(lhss)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, o, g, t, nv:
                             (t[v], k_i))] * len(lhss) + [
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_index)] * len(rhss),
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, o, g, t,
                                   nv: (t[v], n_i)),
            grid=(n // tn, meta[1].shape[0], tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tiles_k > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_t" if transpose_rhs else "moe_gmm",
    )(*meta, *lhss, *rhss)


def _tgmm_kernel(offsets, group_ids, tile_ids, n_visits, *refs, tm, n_steps,
                 n_dout, w=0):
    # ``w``: lhs is (rows, src) and its tile is fetched by id (one more
    # prefetched scalar array, three more scratch entries)
    if w:
        rows, src, *refs = refs
        *refs, buf, sem, lhs_ref = refs
    else:
        lhs_ref, *refs = refs
    dout_refs, out_refs, acc_refs = (refs[i * n_dout:(i + 1) * n_dout]
                                     for i in range(3))
    v = pl.program_id(2)
    group = group_ids[v]
    before = group_ids[jnp.maximum(v - 1, 0)]
    after = group_ids[jnp.minimum(v + 1, n_steps - 1)]

    @pl.when(jnp.logical_or(v == 0, before != group))
    def _zero():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def products():
        # every row of lhs and dout is real data, so masking one operand
        # removes the other groups' rows from the product
        own = _own_rows(offsets, group, tile_ids[v], tm,
                        dout_refs[0].shape[1])
        for dout_ref, acc_ref in zip(dout_refs, acc_refs):
            dout = dout_ref[...]
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], jnp.where(own, dout, jnp.zeros_like(dout)),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if w:
        _with_fetched_tile(rows, src, buf, sem, lhs_ref, v, tile_ids,
                           n_visits[0], products, tm=tm, w=w)
    else:
        pl.when(v < n_visits[0])(products)

    @pl.when(jnp.logical_or(v == n_steps - 1, after != group))
    def _store():
        for out_ref, acc_ref in zip(out_refs, acc_refs):
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, dout, group_sizes, *, rows=None, tiles=None, interpret=False):
    """``out[e] = lhs[rows of e].T @ dout[rows of e]``: lhs (m, k), dout
    (m, n), group_sizes (E,) -> (E, k, n) in lhs's dtype; zeros for a
    group with no rows. ``dout`` may be a tuple of (m, n) arrays (one walk
    over lhs, a tuple comes back). With ``rows`` (m,) int32, row ``p`` of
    lhs is ``lhs[rows[p]]``, fetched by the kernel (``gmm_rows``; gathered
    first where a row cannot be)."""
    douts = dout if isinstance(dout, tuple) else (dout,)
    (m, n), k = douts[0].shape, lhs.shape[1]
    n_groups = group_sizes.shape[0]
    w = row_words(k, lhs.dtype) if rows is not None else 0
    if rows is not None and not w:
        lhs, rows = lhs.at[rows].get(mode="promise_in_bounds"), None
    if tiles is None:
        # a group's whole gradient block in VMEM where it fits (2M
        # float32 values): both operands are then read once
        tk = k if w else _tile(k, 2048, 128)
        tiles = _tile(m, ROW_TILE, 8), tk, _tile(n, max(
            128, 2048 * 1024 // tk), 128)
    tm, tk, tn = tiles
    if m % tm or k % tk or n % tn or (w and tk != k) \
            or any(d.shape != (m, n) for d in douts):
        raise ValueError(f"tgmm: tiles {(tm, tk, tn)} do not divide "
                         f"{(m, k, n)} (a fetched row is whole), or dout "
                         f"{[d.shape for d in douts]} differ")
    meta = _visits(group_sizes, m, tm, tail=False)
    n_steps = meta[1].shape[0]
    n_dout, dtype = len(douts), lhs.dtype
    if w:
        meta = (*meta, rows.astype(jnp.int32))
        lhs_spec = pl.BlockSpec(memory_space=pl.ANY)
        fetch = _fetch_scratch(tm, k, dtype)
        lhs = _pack_rows(lhs)
    else:
        lhs_spec = pl.BlockSpec((tm, tk), lambda k_i, n_i, v, o, g, t, nv:
                                (t[v], k_i))
        fetch = []
    outs = pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, n_steps=n_steps,
                          n_dout=n_dout, w=w),
        out_shape=[jax.ShapeDtypeStruct((n_groups, k, n), dtype)] * n_dout,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            in_specs=[lhs_spec] + [
                pl.BlockSpec((tm, tn), lambda k_i, n_i, v, o, g, t, *_:
                             (t[v], n_i))] * n_dout,
            out_specs=[pl.BlockSpec((None, tk, tn), lambda k_i, n_i, v, o,
                                    g, *_: (g[v], k_i, n_i))] * n_dout,
            grid=(k // tk, n // tn, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)] * n_dout
            + fetch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_drhs_rows" if w else "moe_gmm_drhs",
    )(*meta, lhs, *douts)
    return tuple(outs) if isinstance(dout, tuple) else outs[0]


def gmm_stacked(lhs, stack, group_sizes, layer, interpret=False):
    """``gmm`` against layer ``layer`` (a traced or Python int) of the
    stacked weights ``stack`` (L, E, k, n), read in place; forward only.
    lhs (m, k), group_sizes (E,) -> (m, n); rows past the last group are
    zeros."""
    m, k = lhs.shape
    n_layers, n_groups, k_w, n = stack.shape
    if k_w != k:
        raise ValueError(f"gmm_stacked: lhs {lhs.shape} does not contract "
                         f"with the stack {stack.shape}")
    decode = m <= DECODE_ROWS
    tm = _tile(m, DECODE_ROW_TILE if decode else ROW_TILE, 8)
    tk, tn = _tile(k, 2048, 128), _tile(n, 2048, 128)
    tiles_k = k // tk
    visits = _visits(group_sizes, m, tm, tail=True)
    # the visits that multiply: a step after them (the tail's, or past
    # the last visit) keeps the weight block the last of them held, so
    # nothing is fetched for it, whatever its k step
    meta = (*visits, jnp.stack([jnp.asarray(layer, jnp.int32),
                                _n_multiplying(visits, n_groups)]))

    def rhs_index(n_i, v, k_i, offsets, group_ids, tile_ids, n_visits, more):
        real = v < more[1]
        group = jnp.minimum(
            group_ids[jnp.where(real, v, jnp.maximum(more[1] - 1, 0))],
            n_groups - 1)
        return (more[0] * n_groups + group,
                jnp.where(real, k_i, tiles_k - 1), n_i)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          n_groups=n_groups, transpose_rhs=False,
                          stacked=True),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, o, g, t, nv, l:
                             (t[v], k_i)),
                pl.BlockSpec((None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, o, g, t,
                                   nv, l: (t[v], n_i)),
            grid=(n // tn, meta[1].shape[0], tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tiles_k > 1 else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_decode" if decode else "moe_gmm",
    )(*meta, lhs, stack.reshape(n_layers * n_groups, k, n))


def gmm_t(dout, rhs, group_sizes, interpret=False):
    """``dout[rows of e] @ rhs[e].T``: dout (m, n), rhs (E, k, n) ->
    (m, k), zeros past the last group: ``gmm``'s gradient of lhs. Tuples
    of as many ``dout`` and ``rhs``: the sum over the pairs, in one walk
    (gate and up share their lhs, so its gradient is one array)."""
    return _gmm_call(dout, rhs, group_sizes, transpose_rhs=True,
                     interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, group_sizes, interpret=False):
    """``out[rows of e] = lhs[rows of e] @ rhs[e]``: lhs (m, k), rhs
    (E, k, n), group_sizes (E,) int32 -> (m, n) in lhs's dtype; rows past
    the last group are zeros. Differentiable in lhs and rhs."""
    return _gmm_call(lhs, rhs, group_sizes, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return (_gmm_call(lhs, rhs, group_sizes, interpret=interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    d_lhs = gmm_t(dout, rhs, group_sizes, interpret)
    d_rhs = tgmm(lhs, dout, group_sizes, interpret=interpret)
    # both before anything reads either (``models/moe.py _gate_up_bwd``)
    d_lhs, d_rhs = jax.lax.optimization_barrier((d_lhs, d_rhs))
    return d_lhs, d_rhs.astype(rhs.dtype), np.zeros(
        group_sizes.shape, jax.dtypes.float0)


gmm.defvjp(_gmm_fwd, _gmm_bwd)
