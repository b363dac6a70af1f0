"""Block compaction Pallas kernel: one pass over a lane's span of the
running match count.

``ranks`` is a mask's running match count (inclusive cumsum), so match k
(0-based) sits at the first row whose count reaches k + 1. A block of
``cap`` consecutive matches [off, off + cap) lies in one contiguous span of
rows. The kernel walks that span once, in tiles copied from HBM with
double-buffered DMAs, instead of binary-searching the whole count once per
match:

- a row matches where its count differs from the previous row's, and its
  slot in the block is ``count - 1 - off``;
- a tile whose count does not move inside the block's window is skipped;
- each (8, 128) tile's matches are placed by compare: per sublane row, a
  one-hot of slot-within-128 against a one-hot of the output row, reduced
  on the MXU with the match's lane (1..128, exact in bfloat16) as the
  value. Mosaic has no in-kernel 1-D gather or vector scatter.

Only the span's first and last rows are searched outside the kernel (two
scalar binary searches), which fixes the dynamic trip count of the walk.
The result is the same vector ``jnp.searchsorted`` gives: matches in
ascending row order, ``fill`` past the last match.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128
SUB = 8                        # sublanes a placement handles: 1024 rows
CH = 64                        # sublanes one DMA brings: 8192 rows, 32 KiB
TILE_ROWS = SUB * LANES
CHUNK_ROWS = CH * LANES
WIN = 2 * SUB                  # output rows a tile's matches can reach


def _compact_kernel(meta_ref, ranks_hbm, out_ref, buf, sem, *, cap, fill):
    off, c0, nch, before0 = (meta_ref[0], meta_ref[1], meta_ref[2],
                             meta_ref[3])
    rows = out_ref.shape[0]
    slot_ids = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    # a query count off + j + 1 <= 0 is reached at row 0 (searchsorted)
    out_ref[...] = jnp.where(slot_ids < -off, 0, fill)

    def copy(k, slot):
        return pltpu.make_async_copy(
            ranks_hbm.at[pl.ds((c0 + k) * CH, CH)], buf.at[slot],
            sem.at[slot])

    def in_window(before, last):
        # the counts (before, last] of a tile meet the window [off+1, off+cap]
        return (last > before) & (last > off) & (before < off + cap)

    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 1)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (WIN, LANES), 0)
    out_col = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lane_val = (jax.lax.broadcasted_iota(jnp.int32, (WIN, LANES), 1) + 1)

    def place(tile, before, row0):
        prev_l = pltpu.roll(tile, 1, 1)              # [s, l-1]; l=0 wraps
        prev_s = pltpu.roll(prev_l, 1, 0)            # l=0: [s-1, 127]
        prev = jnp.where(lane == 0,
                         jnp.where(sub == 0, before, prev_s), prev_l)
        slot = tile - 1 - off
        hit = (tile > prev) & (slot >= 0) & (slot < cap)
        # the tile's slots lie in [lo, lo + 1024): from the 1024-aligned
        # row below lo they reach at most WIN output rows
        base = (jnp.maximum(before - off, 0) // TILE_ROWS) * SUB
        rel = slot - base * LANES
        r, c = rel // LANES, rel % LANES
        acc = jnp.zeros((WIN, LANES), jnp.int32)
        for s in range(SUB):
            onto = ((out_col == c[s:s + 1]) & hit[s:s + 1]) \
                .astype(jnp.bfloat16)                         # [c, l]
            at = jnp.where(out_row == r[s:s + 1], lane_val, 0) \
                .astype(jnp.bfloat16)                         # [r, l]: l+1
            w = jax.lax.dot_general(at, onto, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            w = w.astype(jnp.int32)
            acc = acc + jnp.where(w > 0, w + s * LANES, 0)
        base = pl.multiple_of(base, SUB)
        cur = out_ref[pl.ds(base, WIN), :]
        out_ref[pl.ds(base, WIN), :] = jnp.where(acc > 0, row0 + acc - 1,
                                                 cur)

    @pl.when(nch > 0)
    def _():
        copy(0, 0).start()

    def chunk(k, before):
        slot = k % 2

        @pl.when(k + 1 < nch)
        def _():
            copy(k + 1, 1 - slot).start()

        copy(k, slot).wait()
        last = jnp.max(buf[slot])

        @pl.when(in_window(before, last))
        def _():
            def tile_step(t, b):
                tile = buf[slot, pl.ds(pl.multiple_of(t * SUB, SUB), SUB), :]
                tl = jnp.max(tile)

                @pl.when(in_window(b, tl))
                def _():
                    place(tile, b, (c0 + k) * CHUNK_ROWS + t * TILE_ROWS)
                return tl

            jax.lax.fori_loop(0, CH // SUB, tile_step, before)
        return last

    jax.lax.fori_loop(0, nch, chunk, before0)


@functools.partial(jax.jit, static_argnames=("cap", "fill", "interpret"))
def compact_block_pallas(ranks: jnp.ndarray, off, cap: int, fill: int = 0,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Matches [off, off + cap) of running match count ``ranks`` as a
    (cap,) int32 row vector, ``fill`` past the last match.

    ``ranks`` is flattened and, unless its length is a multiple of
    ``CHUNK_ROWS`` (as the scan leaves it), padded with its last entry,
    which adds no match. One compiled program serves every ``off``.
    """
    flat = ranks.reshape(-1)
    pad = -flat.shape[0] % CHUNK_ROWS
    if pad:
        flat = jnp.pad(flat, (0, pad), mode="edge")
    off = jnp.asarray(off, jnp.int32)
    count = flat[-1]
    q_lo = jnp.maximum(off + 1, 1)
    q_hi = jnp.minimum(off + cap, count)
    # rows of the window's first and last match: the span to walk
    first, last = jnp.searchsorted(flat, jnp.stack([q_lo, q_hi]),
                                   side="left").astype(jnp.int32)
    c0 = first // CHUNK_ROWS
    nch = jnp.where(q_lo <= q_hi, last // CHUNK_ROWS - c0 + 1, 0)
    before0 = jnp.where(c0 > 0, flat[jnp.maximum(c0 * CHUNK_ROWS - 1, 0)], 0)
    meta = jnp.stack([off, c0, nch, before0]).astype(jnp.int32)
    out_rows = -(-cap // TILE_ROWS) * SUB + SUB
    out = pl.pallas_call(
        functools.partial(_compact_kernel, cap=cap, fill=fill),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((2, CH, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=resolve_interpret(interpret),
    )(meta, flat.reshape(-1, LANES))
    return out.reshape(-1)[:cap]
