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
transposed weights (``transpose_rhs``: the block is contracted over its
last axis, which the MXU does for free). ``d_rhs[e] = lhs_e^T @ dout_e``
is a second kernel, ``tgmm``: it walks the same visits and accumulates over
each group's row tiles; a group with no rows is visited once with every
row masked, so its gradient is written as zeros.

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
(the train kernels keep their tail's product: their tail is empty, and
their contraction is one block, so a repeated step names one block too).
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


def _gmm_kernel(offsets, group_ids, tile_ids, n_visits, *refs, tm, tiles_k,
                n_groups, transpose_rhs, stacked=False):
    # ``stacked``: one more prefetched scalar (the layer), and a visit of
    # the tail multiplies nothing
    lhs_ref, rhs_ref, out_ref, *scratch = refs[1:] if stacked else refs
    v, k_i = pl.program_id(1), pl.program_id(2)
    group = group_ids[v]
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def product():
        return jax.lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                                   preferred_element_type=jnp.float32)

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
    m, k = lhs.shape
    n_groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if rhs.shape[2 if transpose_rhs else 1] != k:
        raise ValueError(f"gmm: lhs {lhs.shape} does not contract with rhs "
                         f"{rhs.shape} (transpose_rhs={transpose_rhs})")
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
                          n_groups=n_groups, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, o, g, t, nv:
                             (t[v], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_index),
            ],
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
    )(*meta, lhs, rhs)


def _tgmm_kernel(offsets, group_ids, tile_ids, n_visits, lhs_ref, dout_ref,
                 out_ref, acc_ref, *, tm, n_steps):
    v = pl.program_id(2)
    group = group_ids[v]
    before = group_ids[jnp.maximum(v - 1, 0)]
    after = group_ids[jnp.minimum(v + 1, n_steps - 1)]

    @pl.when(jnp.logical_or(v == 0, before != group))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v < n_visits[0])
    def _visit():
        # every row of lhs and dout is real data, so masking one operand
        # removes the other groups' rows from the product
        dout = dout_ref[...]
        own = _own_rows(offsets, group, tile_ids[v], tm, dout.shape[1])
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], jnp.where(own, dout, jnp.zeros_like(dout)),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(v == n_steps - 1, after != group))
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, dout, group_sizes, *, tiles=None, interpret=False):
    """``out[e] = lhs[rows of e].T @ dout[rows of e]``: lhs (m, k), dout
    (m, n), group_sizes (E,) -> (E, k, n) in lhs's dtype; zeros for a
    group with no rows."""
    m, k = lhs.shape
    n = dout.shape[1]
    n_groups = group_sizes.shape[0]
    if tiles is None:
        # a group's whole gradient block in VMEM where it fits (2M
        # float32 values): both operands are then read once
        tk = _tile(k, 2048, 128)
        tiles = _tile(m, ROW_TILE, 8), tk, _tile(n, max(
            128, 2048 * 1024 // tk), 128)
    tm, tk, tn = tiles
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tgmm: tiles {(tm, tk, tn)} do not divide "
                         f"{(m, k, n)}")
    meta = _visits(group_sizes, m, tm, tail=False)
    n_steps = meta[1].shape[0]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, n_steps=n_steps),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k_i, n_i, v, o, g, t, nv:
                             (t[v], k_i)),
                pl.BlockSpec((tm, tn), lambda k_i, n_i, v, o, g, t, nv:
                             (t[v], n_i)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda k_i, n_i, v, o, g,
                                   t, nv: (g[v], k_i, n_i)),
            grid=(k // tk, n // tn, n_steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_drhs",
    )(*meta, lhs, dout)


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
    steps = visits[1].shape[0]
    n_real = jnp.sum(jnp.logical_and(
        visits[1] < n_groups, jnp.arange(steps) < visits[3][0]),
        dtype=jnp.int32)
    meta = (*visits, jnp.stack([jnp.asarray(layer, jnp.int32), n_real]))

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
    d_lhs = _gmm_call(dout, rhs, group_sizes, transpose_rhs=True,
                      interpret=interpret)
    d_rhs = tgmm(lhs, dout, group_sizes, interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), np.zeros(
        group_sizes.shape, jax.dtypes.float0)


gmm.defvjp(_gmm_fwd, _gmm_bwd)
