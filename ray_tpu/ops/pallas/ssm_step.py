"""Pallas TPU kernel for a decode step's state-space rule: the live slots'
states of one layer move ONCE, in place.

``ops/ssm.py ssd_step`` is the rule, one token a slot, for head h with its
state ``s`` (P x N float32), input ``x`` (P), step ``dt``, decay rate ``A``
and its group's ``B``, ``C`` (N):

    s <- exp(dt A) s + (dt x) B^T            y = s C + D x

Written in plain XLA over the pool's stack of states ``(state layers, slots,
h, p, n)`` it is two fusions a layer that each pass over EVERY slot's state
(the read-out, and ``where(live, new, old)`` + the write into the layer
scan's carry): 3 x slots x 2 MB a layer at the served widths whatever the
load (PR 59: 7.1 of a 13.4-ms step). Here the stack is the kernel's aliased
operand (``input_output_aliases``, as ``kv_write``'s pools are): it stays
in HBM (``memory_space=pl.ANY``), nothing slices a layer out of it or puts
one back, and the kernel walks the slots that HOLD A REQUEST: the list of
live slot ids and their count are scalar operands (made once a step from
the one ``live`` vector, llm/kvcache.py), and a loop whose trip count is the
live count fetches each live slot's state of the layer with its own DMAs,
decays it, adds to it, reduces it against ``C`` and writes it back to the
place it came from. A slot that holds no request costs no byte of state: no
DMA names it, so its state is bit for bit what it was (also in a step with
no live slot, whose loop does not run), and its row of ``y`` is zeros (the
walk's rule since PR 57).

Why own DMAs and no grid: a grid step that ``pl.when`` skips still has the
output block its ``BlockSpec`` maps to it copied back, and a grid of slots x
head blocks costs ~0.35 us a step at ANY load (12 layers x 256 steps = 1.1
ms a decode step). The loop pays for the live slots alone.

The walk. A slot's state is cut into CHUNKS of ``hb`` heads (``chunk_heads``:
256 KB at 64 x 128 float32 a head, 8 heads, one group's), each one contiguous
DMA in and one out through a ring of ``BUFFERS`` VMEM buffers: chunk ``k +
AHEAD`` is started (once the write-back that last used its buffer is done)
before chunk ``k`` is waited for, updated IN its buffer and started on its
way back, so reads, the vector unit and writes overlap across chunks and
across slots. ONE run-time loop walks the chunks of all live slots and its
body is one chunk, ``hb`` heads written out in Python: the served program
holds the kernel three times and a process builds six such programs, each
lowered anew in every process, so what is written out is paid in
``setup_s`` (a body of a slot's 64 heads took 1.64 ms a step at 18 live
slots where this takes 1.76, and 4.6 s a decode program to lower inside the
engine where this takes 0.7: my chip runs, PR 60).

Within a chunk a head's state is a (P, N) tile, N on the lanes. What varies
along P (``dt x``, and ``y``) is a COLUMN: ``x`` arrives transposed and
padded to whole lane tiles, (slots, p, 128), so that head h's column is lane
h of the slot's block; a chunk rolls the block so that ITS heads lie on
lanes 0 .. hb - 1 (one cross-lane rotate by a run-time amount, where a
run-time lane index would not compile), takes its columns there, selects its
columns of ``y`` into the same lanes, adds ``D x`` on the whole tile (``D`` a
row rolled the same way: a column a head added to the reduction's column
would be turned lane by lane first, which cost 1.3 ms a step) and rolls the
tile back into the slot's block of ``y``, which leaves as ``x`` came (the
caller turns both; 32 KB a slot). What varies along N (``B``, ``C``) is a
row of the group's. The decay and ``dt`` are scalars a (slot, head), read
from scalar memory. The read-out is the lane reduction ``sum(s * C, -1)``:
float32 products and sums on the vector and cross-lane units, no matrix unit
(a float32 read-out through it needs six passes); it costs 0.14 ms of the
1.76 (the DMAs alone take 1.49: scripts/ssm_step_bench.py and throw-away
variants of this body, my chip runs, PR 60).

Numerics are ``ssd_step``'s: decay, update, carried state and read-out in
float32, the same products in the same order; the lane reduction adds in
another order than XLA's, so the two agree to float32 rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Chosen by measurement at the served widths (scripts/ssm_step_bench.py, my
# chip runs, PR 60; ms a decode step of 12 state layers at 18 live slots of
# 64; 819 GB/s would take 1.11): 256 KB x 4 buffers, 2 ahead 1.762 (62.8%);
# 512 KB x 4, 2 ahead 1.807; 256 KB x 6, 3 ahead 1.847; 128 KB x 8, 4 ahead
# 1.950; with two buffers, or two ahead in three, a fetch waits for the
# write-back just started (2.36-2.38 in the first build). The same order at
# 1 to 64 live slots.
CHUNK_BYTES = 256 * 1024        # one DMA of the walk
BUFFERS = 4                     # the ring of chunk buffers
AHEAD = 2                       # chunks fetched ahead of the one computed


def chunk_heads(h: int, p: int, n: int, chunk_bytes: int = CHUNK_BYTES) -> int:
    """Heads a chunk of the walk holds: the largest divisor of ``h`` whose
    float32 states fit ``chunk_bytes`` (one head where none does)."""
    fit = max(1, chunk_bytes // (4 * p * n))
    return max(d for d in range(1, h + 1) if h % d == 0 and d <= fit)


def _ssm_step_kernel(ids_ref, meta_ref, dec_ref, dt_ref, d_ref, x_ref, b_ref,
                     c_ref, st_in, y_ref, st_ref, buf, sems, *, slots, hb,
                     nbuf, ahead):
    del st_in                       # the same buffer as st_ref
    count, base = meta_ref[0], meta_ref[1] * slots
    p, lanes = x_ref.shape[1:]      # the heads, padded to whole lane tiles
    h = st_ref.shape[1]
    r = h // b_ref.shape[1]         # heads a group
    nc = h // hb                    # chunks a slot
    total = count * nc              # chunks the walk moves
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 1)
    y_ref[...] = jnp.zeros_like(y_ref)

    def copy(k, back: bool):
        """The DMA of chunk ``k`` of the walk (chunk ``k % nc`` of the ``k //
        nc``-th live slot's state) through buffer ``k % nbuf``: in, or
        ``back`` out to where it came from."""
        b = jax.lax.rem(k, nbuf)
        hbm = st_ref.at[base + ids_ref[k // nc], pl.ds((k % nc) * hb, hb)]
        if back:
            return pltpu.make_async_copy(buf.at[b], hbm, sems.at[1, b])
        return pltpu.make_async_copy(hbm, buf.at[b], sems.at[0, b])

    def wait(k, back: bool):
        # a wait needs the copy's shape and semaphore, not its place
        copy(jax.lax.rem(k, nbuf), back).wait()

    for j in range(ahead):
        @pl.when(j < total)
        def _(j=j):
            copy(j, False).start()

    def chunk(k, carry):
        @pl.when(k + ahead < total)
        def _():
            @pl.when(k + ahead >= nbuf)
            def _():
                wait(k + ahead, True)       # the buffer's last write-back
            copy(k + ahead, False).start()

        wait(k, False)
        b = jax.lax.rem(k, nbuf)
        s, h0 = ids_ref[k // nc], (k % nc) * hb
        # the chunk's heads' columns (and their D) brought to lanes 0 .. hb - 1
        here = jax.lax.rem(lanes - h0, lanes)
        xs = pltpu.roll(x_ref[s], here, 1)
        ys = jnp.zeros_like(xs)
        for j in range(hb):
            hh = h0 + j
            grp = pl.ds(hh // r, 1)
            new = buf[b, j] * dec_ref[s, hh] \
                + (dt_ref[s, hh] * xs[:, j:j + 1]) * b_ref[s, grp, :]
            buf[b, j] = new
            ycol = jnp.sum(new * c_ref[s, grp, :], axis=-1, keepdims=True)
            ys = jnp.where(lane == j, ycol, ys)
        copy(k, True).start()
        # + D x on whole tiles (a column a head here would be turned lane by
        # lane: 1.3 ms a step), and back to the heads' own lanes
        ys = jnp.where(lane < hb, ys + pltpu.roll(d_ref[...], here, 1) * xs,
                       0.0)
        y_ref[s] += pltpu.roll(ys, h0, 1)
        return carry

    jax.lax.fori_loop(0, total, chunk, 0)
    for b in range(nbuf):           # the write-backs still in flight
        @pl.when(b < total)
        def _(b=b):
            wait(b, True)


def ssm_step(states, layer, ids, count, x, dt, A, B, C, D, *,
             interpret=False, chunk_bytes=CHUNK_BYTES, buffers=BUFFERS,
             ahead=AHEAD):
    """One token for the live slots against layer ``layer`` of the stack of
    states, IN PLACE. states (state layers, slots, h, p, n) float32; layer
    () int32 (traced in a layer scan); ids (slots,) int32, the slots that
    hold a request first, and ``count`` () how many they are (entries past
    it are not read; no slot twice); x (slots, h, p), dt (slots, h) > 0, A
    (h,) < 0, B and C (slots, g, n), D (h,), as ``ops/ssm.py ssd_step``
    takes them, a row a slot. Returns (y (slots, h, p) float32, the stack:
    the same buffer, aliased in to out). The ``count`` live slots' states
    of the layer have moved on one token and their rows of y are
    ``ssd_step``'s; every other state is untouched and every other row of y
    zeros."""
    if states.dtype != jnp.float32:
        raise ValueError(f"the carried state is float32, not {states.dtype}")
    layers, slots, h, p, n = states.shape
    if x.shape != (slots, h, p) or B.shape != C.shape \
            or B.shape[::2] != (slots, n) or h % B.shape[1]:
        raise ValueError(f"states {states.shape} do not take x {x.shape}, "
                         f"B {B.shape}, C {C.shape}")
    if not 0 < ahead < buffers:
        raise ValueError(f"{ahead} chunks ahead need more than {buffers} "
                         "buffers: a fetch must not land on a chunk in use")
    return _call(states, ids.astype(jnp.int32),
                 jnp.stack([count, layer]).astype(jnp.int32), x, dt, A, B, C,
                 D, interpret=interpret, hb=chunk_heads(h, p, n, chunk_bytes),
                 buffers=buffers, ahead=ahead)


@functools.partial(jax.jit, static_argnames=("interpret", "hb", "buffers",
                                             "ahead"))
def _call(states, ids, meta, x, dt, A, B, C, D, *, interpret, hb, buffers,
          ahead):
    """The kernel's call, jitted on its own: a served program holds it once
    a state layer of a scanned period (three times) and a process builds six
    such programs, inside the engine, where tracing and lowering run beside
    the loop's threads at a seventh of their speed alone. This way the body
    is traced once a process and lowered once a program (the calls share one
    function): called bare it cost the cell ~10 s of ``setup_s`` (my chip
    runs, PR 60)."""
    f32 = jnp.float32
    layers, slots, h, p, n = states.shape
    x, dt = x.astype(f32), dt.astype(f32)
    lanes = -(-h // 128) * 128

    def columns(a):             # (.., h) -> (.., whole lane tiles)
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, lanes - h),))
    smem, vmem, hbm = (pl.BlockSpec(memory_space=space)
                       for space in (pltpu.SMEM, pltpu.VMEM, pl.ANY))
    kernel = functools.partial(_ssm_step_kernel, slots=slots, hb=hb,
                               nbuf=buffers, ahead=ahead)
    yt, flat = pl.pallas_call(
        kernel,
        in_specs=[smem, smem, smem, smem, vmem, vmem, vmem, vmem, hbm],
        out_specs=[vmem, hbm],
        out_shape=[jax.ShapeDtypeStruct((slots, p, lanes), f32),
                   jax.ShapeDtypeStruct((layers * slots, h, p, n), f32)],
        scratch_shapes=[pltpu.VMEM((buffers, hb, p, n), f32),
                        pltpu.SemaphoreType.DMA((2, buffers))],
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssm_step",
    )(ids, meta, jnp.exp(dt * A.astype(f32)), dt, columns(D.astype(f32)[None]),
      columns(x.transpose(0, 2, 1)), B.astype(f32), C.astype(f32),
      states.reshape(layers * slots, h, p, n))
    return yt[:, :, :h].transpose(0, 2, 1), flat.reshape(states.shape)


def live_slots(live):
    """(ids (slots,) int32, count () int32) of a (slots,) bool: the slots
    that hold a request first, in order, and how many they are."""
    ids = jnp.nonzero(live, size=live.shape[0], fill_value=0)[0]
    return ids.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)
