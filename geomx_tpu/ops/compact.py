"""The compaction of an exact top-``k`` selection on the TPU: the
selected positions of a float32 vector, ascending, and their raw words,
with no element gather or scatter.

The selection is given, not searched: ``t`` the ``k``-th largest
magnitude's order bits (``|x|`` as int32, NaN as ``0x7FFFFFFF``) and
``cut`` the last position taken among the ties at ``t``.  An element is
selected if its bits exceed ``t``, or equal ``t`` at or before ``cut``.
The vector is read as ``(rows, 128)`` words, and ``p`` is the inclusive
prefix of each row's selected count: slot ``s`` of the output belongs to
the first row with ``p > s``, at the rank ``s - p[row - 1]`` among that
row's selected lanes.

One Pallas kernel, ``bsc_compact``, fills ``SLOTS`` output slots a grid
step from the run of rows that holds them, read ``ROWS`` rows a visit by
a DMA from HBM that the previous visit started (at 1% a step's slots
span about 200 rows: one visit).  A visit

* transposes its words, so that a source row's lanes run down a column
  and the slots along the lanes of all that follows;
* recomputes its selection from the words, the in-row prefix counts (a
  triangular int8 product on the MXU) and each row's first and last
  slot (a triangular bf16 product of the rows' counts);
* pulls each slot's row through the MXU: a one-hot of the slots against
  the visit's rows times int8 planes of the rows: each byte of the
  words, the exclusive in-row prefix, and the row's index and first
  slot in 7-bit pieces.  A one-hot sum of one term is exact;
* takes the slot's lane as the number of lanes whose exclusive prefix
  is at most its rank, less one, and that lane's four bytes.

A step writes a row of words and a row of positions into an aligned
``(8, SLOTS)`` block.  Outside the kernel, with no gather or scatter,
each step's first row (a multiple of 8), the count selected before it
and its visits come from comparing ``p`` at every eighth row with the
steps' slots: scalars in SMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SLOTS = 256     # output slots a grid step fills
ROWS = 256      # source rows a visit reads
SUB = 8         # a step's first row is a multiple of this (the HBM tile)
NAN_BITS = np.int32(0x7FFFFFFF)
INF_BITS = np.int32(0x7F800000)
EXTRA = 32      # int8 rows of each source row's index and first slot
TRANS_B = (((1,), (1,)), ((), ()))


def order_bits(words):
    """A float32 word's magnitude as int32 of the same order; every NaN
    is ``0x7FFFFFFF``, above ``inf``."""
    mag = words & NAN_BITS
    return jnp.where(mag > INF_BITS, NAN_BITS, mag)


def _kernel(start_ref, base_ref, visits_ref, tc_ref, words_hbm, tri_ref,
            cum_ref, out_ref, buf, sem, seq_ref):
    j = pl.program_id(0)
    last = pl.num_programs(0) - 1
    nrows = words_hbm.shape[0]
    t, cut = tc_ref[0], tc_ref[1]

    def row_of(tile, v):
        # a run that ends near the last row reads the last ROWS rows
        return jnp.minimum(start_ref[tile] + v * ROWS, nrows - ROWS)

    def copy(tile, v, slot):
        row = pl.multiple_of(row_of(tile, v), SUB)
        return pltpu.make_async_copy(words_hbm.at[pl.ds(row, ROWS)],
                                     buf.at[slot], sem.at[slot])

    @pl.when(j == 0)
    def _():
        seq_ref[0] = 0
        copy(0, 0, 0).start()

    nv = visits_ref[j]
    nxt = jnp.minimum(j + 1, last)
    i32, i8 = jnp.int32, jnp.int8
    # the visit's words transposed: a source row's lanes run down a column
    lane_of = lax.broadcasted_iota(i32, (LANES, ROWS), 0)
    row_of_col = lax.broadcasted_iota(i32, (LANES, ROWS), 1)
    pos = row_of_col * LANES + lane_of
    slot_lane = lax.broadcasted_iota(i32, (LANES, SLOTS), 0)
    extra_at = lax.broadcasted_iota(i32, (EXTRA, ROWS), 0)
    extra_row = lax.broadcasted_iota(i32, (EXTRA, ROWS), 1)
    s = j * SLOTS + lax.broadcasted_iota(i32, (1, SLOTS), 1)
    slot_of = lax.broadcasted_iota(i32, (SLOTS, ROWS), 0)

    def visit(v, carry):
        words_acc, idx_acc, base = carry
        slot = seq_ref[0] % 2

        @pl.when(v + 1 < nv)
        def _():
            copy(j, v + 1, 1 - slot).start()

        @pl.when((v + 1 == nv) & (j < last) & (visits_ref[nxt] > 0))
        def _():
            copy(nxt, 0, 1 - slot).start()

        copy(j, v, slot).wait()
        seq_ref[0] += 1
        row0 = start_ref[j] + v * ROWS
        first = row_of(j, v)
        w = buf[slot].T                                      # (LANES, ROWS)
        mag = order_bits(w)
        # rows an earlier visit took (a clamped run's) count nothing
        sel = ((((mag > t) | ((mag == t) & (pos <= cut - first * LANES)))
                & (row_of_col >= row0 - first))).astype(i32)
        # in-row inclusive prefix down each column, on the MXU
        incl = jnp.dot(tri_ref[...], sel.astype(i8),
                       preferred_element_type=i32)
        count = incl[LANES - 1:]                             # (1, ROWS)
        ends = jnp.dot(jnp.broadcast_to(count, (SUB, ROWS)).astype(
            jnp.bfloat16), cum_ref[...], preferred_element_type=jnp.float32)
        ends = ends[:1].astype(i32)                          # (1, ROWS)
        starts = ends - count
        here = s - base                                      # (1, SLOTS)
        # slot by source row; the product below takes its transpose
        first_slot = base - j * SLOTS
        onehot = ((starts + first_slot <= slot_of)
                  & (slot_of < ends + first_slot)).astype(i8)  # (SLOTS, ROWS)
        # each source row's index and starting slot in 7-bit pieces
        extra = jnp.where(extra_at == 0, extra_row & 127,
                jnp.where(extra_at == 1, extra_row >> 7,
                jnp.where(extra_at == 2, starts & 127,
                jnp.where(extra_at == 3, (starts >> 7) & 127,
                jnp.where(extra_at == 4, starts >> 14, 0)))))

        def gather(planes):
            return lax.dot_general(planes.astype(i8), onehot, TRANS_B,
                                   preferred_element_type=i32)

        got = gather(jnp.concatenate([incl - sel, extra], axis=0))
        row = got[LANES:LANES + 1] + (got[LANES + 1:LANES + 2] << 7)
        before = (got[LANES + 2:LANES + 3] + (got[LANES + 3:LANES + 4] << 7)
                  + (got[LANES + 4:LANES + 5] << 14))
        rank = here - before
        lane = jnp.sum((got[:LANES] <= rank).astype(i32),
                       axis=0, keepdims=True) - 1
        pick = slot_lane == lane
        word = None
        for b in range(4):
            byte = jnp.sum(jnp.where(pick, gather((w << (24 - 8 * b)) >> 24),
                                     0), axis=0, keepdims=True)
            byte = (byte & 255) << (8 * b) if b < 3 else byte << 24
            word = byte if word is None else word | byte
        total = jnp.max(ends, axis=1, keepdims=True)         # (1, 1)
        inside = (here >= 0) & (here < total)
        return (jnp.where(inside, word, words_acc),
                jnp.where(inside, (first + row) * LANES + lane, idx_acc),
                base + total)

    zeros = jnp.zeros((1, SLOTS), i32)
    words_acc, idx_acc, _ = lax.fori_loop(
        0, nv, visit, (zeros, zeros, jnp.full((1, 1), base_ref[j], i32)))
    for r in range(SUB):
        @pl.when(j % SUB == r)
        def _(r=r):
            out_ref[0, r:r + 1, :] = words_acc
            out_ref[1, r:r + 1, :] = idx_acc


def padded_rows(n: int) -> int:
    """The rows of the ``(rows, 128)`` view that :func:`compact` takes
    for a vector of ``n`` elements: a multiple of ``SUB``, at least
    ``ROWS``."""
    return max(-(-n // (LANES * SUB)) * SUB, ROWS)


def _schedule(p, k: int):
    """Per grid step: the first row (a multiple of ``SUB``), the selected
    count before it and the visits of ``ROWS`` rows that hold its slots;
    from ``p`` (the inclusive prefix of each row's selected count, the
    rows a multiple of ``SUB``) at every ``SUB``-th row."""
    tiles = -(-k // SLOTS)
    steps = -(-tiles // SUB) * SUB
    ends = p.reshape(-1, SUB)[:, SUB - 1]
    lo = jnp.arange(steps, dtype=jnp.int32) * SLOTS
    hi = jnp.minimum(lo + SLOTS, k) - 1
    done = ends[None, :] <= lo[:, None]          # groups wholly before
    start = SUB * jnp.sum(done, axis=1, dtype=jnp.int32)
    base = jnp.max(jnp.where(done, ends[None, :], 0), axis=1)
    stop = SUB * (jnp.sum(ends[None, :] <= hi[:, None], axis=1,
                          dtype=jnp.int32) + 1)
    visits = jnp.where(lo < k, -(-(stop - start) // ROWS), 0)
    return start, base, visits


def compact(words, p, t, cut, k: int):
    """``(vals, idx)``: the ``k`` selected positions of the float32
    vector whose raw words are ``words`` (``(rows, 128)`` int32, rows a
    multiple of ``SUB`` and at least ``ROWS``) ascending, and their words.
    ``p`` is each row's inclusive prefix count of selected elements,
    ``t`` and ``cut`` the selection (module docstring)."""
    nrows = words.shape[0]
    assert nrows % SUB == 0 and nrows >= ROWS, nrows
    start, base, visits = _schedule(p, k)
    steps = start.shape[0]
    tri = jnp.tril(jnp.ones((LANES, LANES), jnp.int8))
    cum = jnp.triu(jnp.ones((ROWS, ROWS), jnp.bfloat16))
    tc = jnp.stack([t, cut]).astype(jnp.int32)
    out = pl.pallas_call(
        _kernel, name="bsc_compact",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((LANES, LANES), lambda j, *_: (0, 0)),
                      pl.BlockSpec((ROWS, ROWS), lambda j, *_: (0, 0))],
            out_specs=pl.BlockSpec((2, SUB, SLOTS),
                                   lambda j, *_: (0, j // SUB, 0)),
            scratch_shapes=[pltpu.VMEM((2, ROWS, LANES), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((2, steps, SLOTS), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(start, base, visits, tc, words, tri, cum)
    return out[0].reshape(-1)[:k], out[1].reshape(-1)[:k]
