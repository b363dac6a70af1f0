"""Predicate-scan Pallas kernel: filters evaluated on resident packed words.

The paper's featurization story runs selection in code space ("simple
calculations on small integers"); this kernel pushes that selection all the
way into the packed residency layer built by the gather kernels. Each grid
step unpacks a BN-row window of every predicate column straight from the
device-width (bits | 32) word streams — the bitunpack shift/mask recipe,
fields never straddle words at divisor widths — evaluates the per-column
term and AND/OR-combines across columns, writing one selection-bitmap tile.
int32 code streams never exist on host or device; the bitmap feeds
device-side compaction and then ``adv_gather_packed_rows``, so a filtered
serve is one device pipeline.

Term forms (static per compiled predicate, unrolled like the gather
kernels' column loops):

- ``kind 0`` — contiguous code range ``[lo, hi]``: two VPU compares.
  Equality and (on sorted dictionaries) value ranges compile to this.
- ``kind 1`` — arbitrary code set via a K-entry LUT probe: IN-sets and
  unsorted-dictionary ranges. The probe (``jnp.take``) is exact in
  interpret mode; Mosaic refuses it for a TPU ("Only 2D gather is
  supported"), like the random-row packed gather kernel (ROADMAP S2).

Grid: (N/BN,). The whole word stream stays resident across grid steps (it
is 32/db x smaller than the codes it encodes); range bounds ride in a tiny
(T, 2) block and every LUT in one flat (1, L) block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _predicate_scan_kernel(words_ref, bounds_ref, lut_ref, out_ref, *,
                           cols: tuple, kinds: tuple, dbs: tuple,
                           word_offs: tuple, lut_offs: tuple,
                           lut_lens: tuple, combine: str):
    i = pl.program_id(0)
    bn = out_ref.shape[1]
    acc = None
    for t, c in enumerate(cols):                # static unroll over terms
        db = dbs[c]
        s = 32 // db
        nw = bn // s                            # words per BN-row window
        w = words_ref[:, pl.ds(word_offs[c] + i * nw, nw)]   # (1, NW) u32
        shifts = jax.lax.broadcasted_iota(jnp.uint32, (nw, s), 1) \
            * jnp.uint32(db)
        fields = w.reshape(nw, 1) >> shifts     # (NW, S) word-major
        if db < 32:
            fields = fields & jnp.uint32((1 << db) - 1)
        codes = fields.reshape(1, bn).astype(jnp.int32)
        if kinds[t] == 0:                       # contiguous code range
            m = (codes >= bounds_ref[t, 0]) & (codes <= bounds_ref[t, 1])
        else:                                   # K-entry LUT probe
            lut = lut_ref[...][0]
            idx = jnp.minimum(codes.reshape(bn), lut_lens[t] - 1)
            m = (jnp.take(lut, lut_offs[t] + idx) != 0).reshape(1, bn)
        if acc is None:
            acc = m
        else:
            acc = (acc & m) if combine == "and" else (acc | m)
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("n", "bn", "cols", "kinds", "dbs",
                                    "word_offs", "lut_offs", "lut_lens",
                                    "combine", "interpret"))
def predicate_scan_pallas(words: jnp.ndarray, bounds: jnp.ndarray,
                          lut: jnp.ndarray, n: int, bn: int = 1024,
                          cols: tuple = (), kinds: tuple = (),
                          dbs: tuple = (), word_offs: tuple = (),
                          lut_offs: tuple = (), lut_lens: tuple = (),
                          combine: str = "and",
                          interpret: bool | None = None) -> jnp.ndarray:
    """words (W,) uint32 concatenated streams, bounds (T, 2) int32 range
    rows, lut (L,) int32 concatenated LUTs -> (n,) int32 selection bitmap.

    Preconditions (enforced by ops.py): n % bn == 0, bn % 32 == 0 (every
    window word-aligned at every divisor width), column c's stream covers
    n * dbs[c] / 32 words from word_offs[c], at least one term.
    """
    w = words.shape[0]
    t = bounds.shape[0]
    l = lut.shape[0]
    grid = (n // bn,)
    return pl.pallas_call(
        functools.partial(_predicate_scan_kernel, cols=cols, kinds=kinds,
                          dbs=dbs, word_offs=word_offs, lut_offs=lut_offs,
                          lut_lens=lut_lens, combine=combine),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((t, 2), lambda i: (0, 0)),
            pl.BlockSpec((1, l), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(words.reshape(1, w), bounds, lut.reshape(1, l)).reshape(n)
