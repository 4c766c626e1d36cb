"""Pallas TPU flash-attention (forward + backward kernels).

The hot op of the framework's model stack. Online-softmax tiling keeps the
S x S score matrix out of HBM; blocks are sized for the MXU (128 lanes) and
VMEM residency. Used by :mod:`ray_tpu.ops.attention` which wires it into a
``jax.custom_vjp``.

Design notes (measured on a v5e at the train cells' shapes: b*h 128, 4096
causal tokens, head 128, 1024 x 1024 tiles, bf16; PR 34, PERF.md section 6):
- Matmul operands stay in the input dtype (bf16) with f32 MXU accumulation;
  upcasting operands to f32 would halve MXU throughput.
- ``sm_scale`` is folded into ``q`` before the kernels run, saving a full
  elementwise pass over the S x S score matrix in every kernel. The dq output
  is rescaled once outside (O(S*D), negligible).
- A tile's body is STRAIGHT-LINE code over its sub-tiles. What a loop turn
  costs is the pipeline's fill and drain, ~600 cycles, against the 256 MXU
  cycles of a 256 x 256 forward sub-tile: the same walk as run-time loops
  over the sub-tiles (``fori_loop``, or ``pl.when`` around each) took 21.9 ms
  a forward call against the whole-tile body's 7.45; as one body per
  distinct tile it takes 5.65. So the sub-tile kinds are decided when the
  kernel is traced (the tiles that differ are few and known from the
  shapes) and the program ids only pick the body.
- The masks were never the cost: the whole-tile body without them on the six
  interior tiles of ten read 7.25 ms against 7.45. What the sub-tiles buy is
  the diagonal tiles' skipped work (a diagonal tile does 62.5% of a full
  one's) with every sub-tile still in one basic block.
- Per-row statistics stay lane-replicated (rows, LANES) from scratch to
  scratch; slicing a column out ([:, :1]) and broadcasting it back costs a
  cross-lane move per 8 rows per use. The forward's whole-tile body went
  7.25 -> 6.25 ms with this (and no loop). Deferring the row SUM's cross-lane
  reduction to the last tile (lane-partial sums) was 2% slower, not faster.
- dk/dv computes its scores keys-major (s^T = k q'^T; lse and delta as
  rows): p^T and ds^T then feed the dv and dk products as they are. The
  queries-major form transposes both (1024 x 1024) operands: 10.18 ms a call
  against 8.49 whole-tile, 7.43 sub-tiled.
- A tile above the diagonal is not fetched: its index map names the block
  the previous step held. Forward 5.65 -> 5.33 ms, dk/dv 7.43 -> 6.72 (a
  skipped step still moved q and do, 0.5 MB), dq 6.58 -> 6.30.
- Sub-tiles of 256 x 256: 128-row strips are slower forward (6.29 ms), 512
  slower everywhere (5.76 / 7.73 / 6.83) and under 90% required work.
- lse and delta travel as rows (BH, 1, S), one value a lane: the forward
  turns its column into a row once a q tile (a transpose), dq turns the
  rows into lane-replicated columns once a q tile, dk/dv reads them as they
  are. Forward 5.17 -> 4.89 ms (no 512-KB lse block to write), dq 6.30 ->
  5.98; and the train step loses two 268-MB broadcasts a layer. A version
  that sliced a row out of the lane-replicated lse made XLA copy 268 MB a
  layer, and in the dense cell, which fills its chip, recompute one more
  FFN product a layer: +43 ms a step, more than the kernels had won.
  As shipped: forward 4.89 ms (57.1% of the compute roofline by the
  benchmark's count), dk/dv 6.72 + dq 5.98 (54.9%); before 7.45 (37.5%) and
  10.18 + 7.49 (39.5%).
- exp runs in the input dtype, as before. On this chip f32 exp is FASTER in
  the forward (5.26 against 5.65 ms; no change backward) and more exact, but
  it changes the numbers every parity check reads: left for its own change.

- A BAND (``window=w``, serving prefill of a sliding-window layer: row i
  attends keys in (i + offset - w, i + offset]) is the same plan with a lower
  edge: a q sub-tile's keys are skipped below the band, masked where its
  lower edge cuts, interior between the edges, masked on the diagonal; tiles
  wholly below the band are neither computed nor fetched. Forward only.

Sequence lengths need not divide the block size: wrappers zero-pad to block
multiples and kernels mask out-of-bounds columns (padded rows are sliced off
and padded inputs are zeros, so gradients through padding vanish).

Capability analog of what the reference delegates to vLLM/FlashAttention CUDA
kernels (reference has no TPU attention kernel; see SURVEY.md section 5.7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # per-row statistics are replicated along the lane dim


def _pad_seq(x, block):
    """Zero-pad (bh, s, d) along s to a multiple of block."""
    s = x.shape[1]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


# The (block_q, block_k) tile is what the grid and the DMAs move. The
# body cuts it into (sub_q, sub_k) sub-tiles, each one of three kinds:
# above the causal diagonal or wholly past kv_len (never computed),
# interior (no iota, no compare, no select) or cut by the diagonal or by
# the kv_len edge (masked). Where the diagonal and the edge cross a tile
# depends on two numbers only (``_tile_key``), so the few tiles that
# differ are known from the shapes: each gets its own straight-line body
# (a ``plan``), chosen by the program ids.
_SUB_WIDTHS = (256, 128)


def _sub_tiles(block_q, block_k):
    """(sub_q, sub_k): the widest listed width that divides the block,
    or the block itself (a tile of one sub-tile)."""
    def width(block):
        return next((w for w in _SUB_WIDTHS if block % w == 0), block)
    return width(block_q), width(block_k)


def _geometry(block_q, block_k, kv_len, offset, causal, window=None):
    """What the plans and the three kernels are built from."""
    sub_q, sub_k = _sub_tiles(block_q, block_k)
    geom = dict(block_q=block_q, block_k=block_k, sub_q=sub_q, sub_k=sub_k,
                kv_len=kv_len, offset=offset, causal=causal)
    if window is not None:
        if not causal:
            raise ValueError("a window is a band under the causal diagonal")
        geom["window"] = int(window)
    return geom


def _k_bounds(d, r, sub_q, sub_k, n_sub, causal):
    """The keys of one q sub-tile, in sub-tiles of ``sub_k``: ``(n_int,
    n_run)`` with [0, n_int) interior, [n_int, n_run) masked and the
    rest skipped. ``d`` is the last key, counted from the tile's first,
    that the sub-tile's FIRST row attends (row i attends keys
    <= i + offset), ``r`` the keys left before kv_len."""
    lo, hi = r - 1, r - 1           # last key every row / any row attends
    if causal:
        lo, hi = min(lo, d), min(hi, d + sub_q - 1)
    return (min(max(lo + 1, 0) // sub_k, n_sub),
            min(max(hi + sub_k, 0) // sub_k, n_sub))


def _band_bounds(d, r, sub_q, sub_k, n_sub, window):
    """``_k_bounds`` for a band: ``(n_skip, n_low, n_int, n_run)`` with
    [0, n_skip) below the band (skipped), [n_skip, n_low) cut by its
    lower edge (masked), [n_low, n_int) interior, [n_int, n_run) masked
    (diagonal, kv_len) and the rest skipped. Row i of the sub-tile
    attends the keys in (d + i - window, d + i]."""
    n_int, n_run = _k_bounds(d, r, sub_q, sub_k, n_sub, True)
    n_skip = min(max(d - window + 1, 0) // sub_k, n_run)     # first row's
    n_low = min(max(d + sub_q - window + sub_k - 1, 0) // sub_k, n_run)
    return n_skip, max(n_low, n_skip), max(n_int, n_low, n_skip), n_run


def _q_bounds(d, r, sub_q, sub_k, n_sub, causal):
    """The same cut seen from one k sub-tile, over the tile's queries in
    sub-tiles of ``sub_q``: ``(first_run, first_int)`` with
    [0, first_run) skipped, [first_run, first_int) masked and the rest
    interior. ``d`` is the last key, counted from the SUB-TILE's first,
    that the tile's first row attends; ``r`` the keys left from there."""
    first_run = first_int = 0
    if causal:          # rows that reach the first key; that reach the last
        first_run = max(-d, 0) // sub_q
        first_int = max(sub_k - 1 - d + sub_q - 1, 0) // sub_q
    if r < sub_k:       # the kv_len edge cuts every row
        first_int = n_sub
    if r <= 0:
        first_run = n_sub
    return min(first_run, n_sub), min(first_int, n_sub)


def _tile_key(qi, ki, block_q, block_k, kv_len, offset, causal,
              window=None):
    """What a tile's plan depends on: how far the diagonal is from the
    tile's corner (clamped where it no longer cuts the tile; with a
    window, where the tile lies wholly below the band) and the keys
    left before kv_len. Python ints or traced scalars."""
    lo, hi = (min, max) if isinstance(qi, int) else (jnp.minimum,
                                                     jnp.maximum)
    r = lo(kv_len - ki * block_k, block_k)
    if not causal:
        return block_k - 1, r
    return lo(hi(qi * block_q + offset - ki * block_k, -block_q),
              block_k - 1 + (window or 0)), r


def _plans(nq, nk, by, *, block_q, block_k, sub_q, sub_k, kv_len, offset,
           causal, window=None):
    """{key: plan} over the grid's tiles. A plan lists the bounds of
    each q sub-tile over the tile's keys (``by`` "q": forward and dq) or
    of each k sub-tile over its queries ("k": dk/dv); with a window
    (forward only) the four bounds of ``_band_bounds``."""
    plans = {}
    for qi in range(nq):
        for ki in range(nk):
            d, r = _tile_key(qi, ki, block_q, block_k, kv_len, offset,
                             causal, window)
            if window is not None:
                plan = tuple(
                    _band_bounds(d + i * sub_q, r, sub_q, sub_k,
                                 block_k // sub_k, window)
                    for i in range(block_q // sub_q))
            elif by == "q":
                plan = tuple(
                    _k_bounds(d + i * sub_q, r, sub_q, sub_k,
                              block_k // sub_k, causal)
                    for i in range(block_q // sub_q))
            else:
                plan = tuple(
                    _q_bounds(d - j * sub_k, r - j * sub_k, sub_q, sub_k,
                              block_q // sub_q, causal)
                    for j in range(block_k // sub_k))
            plans[d, r] = plan
    return plans


def _band4(bounds):
    """A plan entry as ``_band_bounds`` gives it: a causal entry
    ``(n_int, n_run)`` has nothing below it."""
    return bounds if len(bounds) == 4 else (0, 0, *bounds)


def tile_plan(sq, sk, block_q, block_k, *, causal=True, q_offset=None,
              window=None):
    """What the kernels do at these shapes, counted from the plans they
    are built from: sub-tiles ``skipped`` / ``interior`` / ``masked``
    over the tiles of one head, the distinct tile ``bodies``, and
    ``required_share``: the (query, key) pairs attention requires over
    the pairs computed."""
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    offset = (sk - sq) if q_offset is None else int(q_offset)
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    geom = _geometry(block_q, block_k, sk, offset, causal, window)
    sub_q, sub_k = geom["sub_q"], geom["sub_k"]
    plans = _plans(nq, nk, "q", **geom)
    n_sub = block_k // sub_k
    out = {"sub_q": sub_q, "sub_k": sub_k, "skipped": 0, "interior": 0,
           "masked": 0,
           "bodies": sum(any(_band4(b)[3] > _band4(b)[0] for b in p)
                         for p in plans.values())}
    for qi in range(nq):
        for ki in range(nk):
            for bounds in plans[_tile_key(qi, ki, block_q, block_k, sk,
                                          offset, causal, window)]:
                n_skip, n_low, n_int, n_run = _band4(bounds)
                out["interior"] += n_int - n_low
                out["masked"] += (n_low - n_skip) + (n_run - n_int)
                out["skipped"] += n_sub - n_run + n_skip
    required = sum((min(sk, i + offset + 1)
                    - max(i + offset + 1 - (window or sk), 0))
                   if causal else sk
                   for i in range(sq) if not causal or i + offset >= 0)
    computed = (out["interior"] + out["masked"]) * sub_q * sub_k
    out["required_share"] = required / computed if computed else 1.0
    return out


def _on_plan(plans, key, body):
    """Run ``body(plan)`` of the tile whose key the program ids give."""
    for (d, r), plan in plans.items():
        pl.when(jnp.logical_and(key[0] == d, key[1] == r))(
            functools.partial(body, plan))


def _keep(row0, col0, shape, kv_len, causal, offset, keys_dim=1,
          window=None):
    """Bounds + causal mask (and the band's lower edge) of a masked piece
    whose first query is ``row0`` and first key ``col0``; keys run along
    ``keys_dim``."""
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, keys_dim)
    keep = cols < kv_len - col0
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - keys_dim)
        keep = jnp.logical_and(keep, cols - rows <= row0 + offset - col0)
        if window is not None:
            keep = jnp.logical_and(
                keep, cols - rows > row0 + offset - col0 - window)
    return keep


def _mask_piece(x, keep, lo, hi, fill):
    """``x`` with its columns [lo, hi) put under ``keep``."""
    if lo == hi:
        return x
    parts = [jnp.where(keep, x[:, lo:hi], fill)]
    if lo:
        parts.insert(0, x[:, :lo])
    if hi < x.shape[1]:
        parts.append(x[:, hi:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _lanes(x, n):
    """A lane-replicated (rows, LANES) statistic, ``n`` columns wide."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.concatenate([x] * (n // LANES), axis=1)


def _to_row(col):
    """A lane-replicated (n, LANES) statistic as a row (1, n)."""
    return col.T[:1, :]


def _to_col(row):
    """A row (1, n) as a lane-replicated (n, LANES) column."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _pdt(dtype):
    """exp and p in the input dtype (p feeds an MXU matmul in it
    anyway); f32 inputs keep f32."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _last_k_block(qi, block_q, block_k, num_kv_blocks, offset):
    """Last kv block (inclusive) a causal q block attends to, clamped so the
    finalize step always fires even for fully-masked q blocks."""
    last = ((qi + 1) * block_q - 1 + offset) // block_k
    return jnp.clip(last, 0, num_kv_blocks - 1)


def _kv_map(nk, *, causal, block_q, block_k, offset, window=None, **_):
    """K and V blocks over a (b, qi, ki) grid. A tile above the diagonal
    is not fetched either: it names the block the step before it held;
    tiles below a band name the band's first."""
    def index(b, qi, ki):
        if causal:
            ki = jnp.minimum(ki, _last_k_block(qi, block_q, block_k, nk,
                                               offset))
        if window is not None:
            first = jnp.maximum(qi * block_q + offset - window + 1, 0) \
                // block_k
            ki = jnp.maximum(ki, jnp.minimum(first, nk - 1))
        return (b, ki, 0)
    return index


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, plans, causal, block_q,
                block_k, sub_q, sub_k, num_kv_blocks, kv_len, offset,
                with_lse, window=None):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    d = v_ref.shape[-1]         # the values' width, which may not be q's

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(plan):
        pdt = _pdt(q_ref.dtype)
        for iq, bounds in enumerate(plan):
            n_skip, n_low, n_int, n_run = _band4(bounds)
            if n_run == n_skip:
                continue
            rows = slice(iq * sub_q, (iq + 1) * sub_q)
            k0 = n_skip * sub_k                     # keys from here on,
            lo, w = (n_int - n_skip) * sub_k, (n_run - n_skip) * sub_k
            low = (n_low - n_skip) * sub_k          # the band's edge in [0, low)
            q = q_ref[0, rows, :]                   # (sub_q, d), pre-scaled
            v = v_ref[0, k0:k0 + w, :]              # (w, d)
            s = _dot(q, k_ref[0, k0:k0 + w, :], _NT)    # (sub_q, w) f32
            keep = None if lo == w else _keep(
                qi * block_q + iq * sub_q, ki * block_k + (k0 + lo),
                (sub_q, w - lo), kv_len, causal, offset, window=window)
            below = None if not low else _keep(
                qi * block_q + iq * sub_q, ki * block_k + k0,
                (sub_q, low), kv_len, causal, offset, window=window)
            s = _mask_piece(_mask_piece(s, keep, lo, w, NEG_INF),
                            below, 0, low, NEG_INF)
            # m, l and alpha are lane-replicated (sub_q, LANES)
            m_prev = m_scr[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp((s - _lanes(m_new, w)).astype(pdt))
            # a fully masked row's exp(0) too
            p = _mask_piece(_mask_piece(p, keep, lo, w, pdt(0.0)),
                            below, 0, low, pdt(0.0))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rows, :] = alpha * l_scr[rows, :] + jnp.sum(
                p.astype(jnp.float32), axis=-1, keepdims=True)
            acc_scr[rows, :] = acc_scr[rows, :] * _lanes(alpha, d) + _dot(
                p.astype(v.dtype), v, _NN)
            m_scr[rows, :] = m_new

    _on_plan(plans, _tile_key(qi, ki, block_q, block_k, kv_len, offset,
                              causal, window), tile)

    # Last kv block this q block attends to (inclusive).
    if causal:
        last_k = _last_k_block(qi, block_q, block_k, num_kv_blocks, offset)
    else:
        last_k = num_kv_blocks - 1

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows
        o_ref[0] = (acc_scr[...] / _lanes(l, d)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = _to_row(m_scr[...] + jnp.log(l))


def flash_attention_fwd(q, k, v, *, sm_scale, causal, block_q=128, block_k=128,
                        interpret=False, with_lse=True, q_offset=None,
                        window=None):
    """q,k,v: (BH, S, D) -> (o: (BH, S, D), lse: (BH, 1, S) f32 | None).
    v may be (BH, S, Dv) with Dv != D (a latent layer's heads: keys of
    nope + rope, values of v_head_dim); o is then (BH, S, Dv). Forward
    only: the backward kernels take one width.

    lse is the row logsumexp saved as a backward residual, one value a
    lane (a lane-replicated (BH, S, LANES) copy is 128 times the bytes,
    and in a train step that fills its chip the temporaries it costs
    make XLA recompute a matmul). Inference callers pass with_lse=False
    to skip the HBM write (pallas outputs are never DCE'd).

    ``q_offset`` places the causal diagonal: query row i attends keys
    <= i + q_offset. Default (None) = sk - sq, i.e. queries are the
    LAST sq rows of the kv sequence. Chunked prefill passes the chunk's
    absolute start position instead (queries sit mid-sequence, not at
    the end); must be static — one compile per distinct offset.
    ``window`` (static, with ``causal``): row i attends the ``window``
    keys up to i + q_offset only."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    dv = v.shape[-1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    offset = (sk - sq) if q_offset is None else int(q_offset)
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)  # fold scale in
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k

    tiles = _geometry(block_q, block_k, sk, offset, causal, window)
    kernel = functools.partial(
        _fwd_kernel, plans=_plans(nq, nk, "q", **tiles), num_kv_blocks=nk,
        with_lse=with_lse, **tiles)
    kv_map = _kv_map(nk, **tiles)

    out_specs = [pl.BlockSpec((1, block_q, dv), lambda b, qi, ki: (b, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((*qp.shape[:2], dv), q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, 1, qp.shape[1]), jnp.float32))

    res = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd" if window is None else "flash_fwd_window",
    )(qp, kp, vp)
    if with_lse:
        out, lse = res
        return out[:, :sq], lse[:, :, :sq]
    return res[0][:, :sq], None


# ---------------------------------------------------------------------------
# Backward. lse comes from the forward kernel (saved residual — no recompute
# pass). Kernels: (1) dk/dv with grid over kv blocks, inner loop over q
# blocks; (2) dq with grid over q blocks, inner loop over kv blocks. p is
# recomputed per tile from q,k and lse; delta = rowsum(do * o).
# q arrives pre-scaled by sm_scale, so p = exp(q'k - lse) directly and
# ds needs no extra scale for dk; dq is rescaled by the wrapper.
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, plans, causal, block_q,
                block_k, sub_q, sub_k, num_q_blocks, kv_len, offset):
    """Scores are computed keys-major, s^T = k q'^T, so that p^T and
    ds^T feed the dv and dk products as they are; lse and delta arrive
    as rows (1, block_q), one value a lane."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(plan):
        pdt = _pdt(q_ref.dtype)
        for jk, (first_run, first_int) in enumerate(plan):
            if first_run * sub_q == block_q:
                continue
            cols = slice(jk * sub_k, (jk + 1) * sub_k)
            r0 = first_run * sub_q                  # queries from here on,
            hi = first_int * sub_q - r0             # masked up to here
            k = k_ref[0, cols, :]                   # (sub_k, d)
            v = v_ref[0, cols, :]
            q = q_ref[0, r0:, :]                    # (n, d), pre-scaled
            do = do_ref[0, r0:, :]
            s = _dot(k, q, _NT)                     # (sub_k, n) f32
            p = jnp.exp((s - lse_ref[0, :, r0:]).astype(pdt))
            if hi:
                p = _mask_piece(p, _keep(
                    qi * block_q + r0, ki * block_k + jk * sub_k,
                    (sub_k, hi), kv_len, causal, offset, keys_dim=0),
                    0, hi, pdt(0.0))
            # dv += p^T do
            dv_scr[cols, :] += _dot(p.astype(do.dtype), do, _NN)
            # dp^T = v do^T ; ds^T = p^T * (dp^T - delta)
            ds = p * (_dot(v, do, _NT) - delta_ref[0, :, r0:])
            # dk = ds^T q'  (q' = sm_scale*q: the scale is included)
            dk_scr[cols, :] += _dot(ds.astype(q.dtype), q, _NN)

    _on_plan(plans, _tile_key(qi, ki, block_q, block_k, kv_len, offset,
                              causal), tile)

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, lse_scr, delta_scr, *, plans, causal,
               block_q, block_k, sub_q, sub_k, num_kv_blocks, kv_len,
               offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # the rows lse and delta arrive as, turned once a q tile into
        # lane-replicated (block_q, LANES) columns
        lse_scr[...] = _to_col(lse_ref[0])
        delta_scr[...] = _to_col(delta_ref[0])

    def tile(plan):
        pdt = _pdt(q_ref.dtype)
        for iq, (n_int, n_run) in enumerate(plan):
            if not n_run:
                continue
            rows = slice(iq * sub_q, (iq + 1) * sub_q)
            lo, w = n_int * sub_k, n_run * sub_k    # keys: unmasked, all
            q = q_ref[0, rows, :]                   # pre-scaled
            do = do_ref[0, rows, :]
            k = k_ref[0, :w, :]
            s = _dot(q, k, _NT)                     # (sub_q, w) f32
            p = jnp.exp((s - _lanes(lse_scr[rows, :], w)).astype(pdt))
            if lo < w:
                p = _mask_piece(p, _keep(
                    qi * block_q + iq * sub_q, ki * block_k + lo,
                    (sub_q, w - lo), kv_len, causal, offset),
                    lo, w, pdt(0.0))
            ds = p * (_dot(do, v_ref[0, :w, :], _NT)
                      - _lanes(delta_scr[rows, :], w))
            # dq' = ds k ; wrapper multiplies by sm_scale once outside.
            dq_scr[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    _on_plan(plans, _tile_key(qi, ki, block_q, block_k, kv_len, offset,
                              causal), tile)

    if causal:
        last_k = _last_k_block(qi, block_q, block_k, num_kv_blocks, offset)
    else:
        last_k = num_kv_blocks - 1

    @pl.when(ki == last_k)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, do, lse, *, sm_scale, causal,
                        block_q=128, block_k=128, interpret=False):
    """lse: (BH, 1, S) f32 from flash_attention_fwd."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    offset = sk - sq
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)  # fold scale in
    qp = _pad_seq(q, block_q)
    kp, vp = _pad_seq(k, block_k), _pad_seq(v, block_k)
    op, dop = _pad_seq(o, block_q), _pad_seq(do, block_q)
    sqp, skp = qp.shape[1], kp.shape[1]
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, sqp - sq)))
    nq = sqp // block_q
    nk = skp // block_k
    tiles = _geometry(block_q, block_k, sk, offset, causal)
    kv_map = _kv_map(nk, **tiles)

    def first_q(ki, qi):    # dk/dv's twin of kv_map: queries not fetched
        if causal:
            first = jnp.maximum(0, ki * block_k - offset) // block_q
            qi = jnp.maximum(qi, jnp.minimum(first, nq - 1))
        return qi

    # like lse one value a lane, (bh, 1, sqp): dk/dv has its queries
    # along the lanes, dq turns both into columns once a q tile
    delta = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq,
                          plans=_plans(nq, nk, "k", **tiles), **tiles),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, ki, qi: (b, first_q(ki, qi), 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda b, ki, qi: (b, first_q(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, ki, qi: (b, 0, first_q(ki, qi))),
            pl.BlockSpec((1, 1, block_q),
                         lambda b, ki, qi: (b, 0, first_q(ki, qi))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, skp, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv_blocks=nk,
                          plans=_plans(nq, nk, "q", **tiles), **tiles),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lse, delta)

    dq = (dq[:, :sq].astype(jnp.float32) * sm_scale).astype(q.dtype)
    return dq, dk[:, :sk], dv[:, :sk]
