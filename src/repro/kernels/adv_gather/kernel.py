"""ADV gather Pallas kernel: out[i, :] = table[codes[i], :] (paper §6.3).

TPU adaptation (DESIGN.md §2): dictionaries are small (K ≤ 2**19 per IMCU,
typically ≪), so the ADV table is pinned in VMEM while code blocks stream
from HBM. The gather itself is executed as a one-hot × table matmul on the
MXU — the one-hot matrix lives only in VREG/VMEM for one (BN × BK) tile and
is never materialized in HBM, which is exactly the paper's 'look it up,
don't recompute/materialize it' insight mapped onto systolic hardware.

Grid: (N/BN, K/BK). The K axis is innermost and accumulates into the same
output tile (out index_map ignores k), the standard Pallas revisiting
pattern. MXU alignment: BN, BK, F padded to multiples of 128 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _adv_gather_kernel(codes_ref, table_ref, out_ref, *, bk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                      # (1, BN) int32
    tbl = table_ref[...]                        # (BK, F) f32
    bn = codes.shape[1]
    # one-hot tile for codes that fall in this K block: (BN, BK)
    local = codes.reshape(bn, 1) - k * bk
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, tbl.shape[0]), 1)
    onehot = (local == col).astype(tbl.dtype)
    out_ref[...] += jnp.dot(onehot, tbl,
                            preferred_element_type=out_ref.dtype)


def _adv_gather_multi_kernel(codes_ref, table_ref, out_ref, *, bk: int):
    """Fused multi-table gather-concat (one pass, paper §6 'single step').

    ``table_ref`` tiles a block-diagonal super-table: column c's (K_c, F_c)
    ADV table occupies rows [row_off_c, row_off_c+K_c) and cols
    [col_off_c, col_off_c+F_c). ``codes_ref`` holds C pre-offset code rows
    (code + row_off_c), so the C one-hot tiles sum into one *multi-hot*
    (BN, BK) matrix — column-disjoint blocks make the single matmul produce
    the concatenated feature row for all C source tables at once.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                      # (C, BN) int32, pre-offset
    tbl = table_ref[...]                        # (BK, F_total) f32
    c_count, bn = codes.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, tbl.shape[0]), 1)
    multihot = jnp.zeros((bn, tbl.shape[0]), tbl.dtype)
    for c in range(c_count):                    # static unroll over columns
        local = codes[c].reshape(bn, 1) - k * bk
        multihot += (local == col).astype(tbl.dtype)
    out_ref[...] += jnp.dot(multihot, tbl,
                            preferred_element_type=out_ref.dtype)


def _adv_gather_packed_kernel(words_ref, row_off_ref, limits_ref, table_ref,
                              out_ref, *, bk: int, dbs: tuple,
                              word_offs: tuple):
    """Fused unpack -> clamp -> multi-hot gather: int32 codes never exist.

    ``words_ref`` holds every column's device-width (bits | 32) packed words
    concatenated into one stream; column c's words start at ``word_offs[c]``
    and are packed at ``dbs[c]`` bits. Each grid step unpacks just the BN-row
    window it gathers (the bitunpack shift/mask recipe — fields never
    straddle words at divisor widths, so the unpack is lane-parallel), clamps
    to the column's cardinality, shifts into the block-diagonal super-table's
    row space, and accumulates the multi-hot x table matmul. The unpacked
    codes live only in VREGs for one tile — neither host RAM nor HBM ever
    holds a 32-bit code stream.
    """
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tbl = table_ref[...]                        # (BK, F_total) f32
    bn = out_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, tbl.shape[0]), 1)
    multihot = jnp.zeros((bn, tbl.shape[0]), tbl.dtype)
    for c, db in enumerate(dbs):                # static unroll over columns
        s = 32 // db
        nw = bn // s                            # words per BN-row window
        w = words_ref[:, pl.ds(word_offs[c] + i * nw, nw)]   # (1, NW) u32
        shifts = jax.lax.broadcasted_iota(jnp.uint32, (nw, s), 1) \
            * jnp.uint32(db)
        fields = w.reshape(nw, 1) >> shifts     # (NW, S) word-major
        if db < 32:
            fields = fields & jnp.uint32((1 << db) - 1)
        codes = fields.reshape(bn, 1).astype(jnp.int32)
        codes = jnp.clip(codes, 0, limits_ref[c, 0]) + row_off_ref[c, 0]
        multihot += ((codes - k * bk) == col).astype(tbl.dtype)
    out_ref[...] += jnp.dot(multihot, tbl,
                            preferred_element_type=out_ref.dtype)


def _adv_gather_packed_rows_kernel(rows_ref, words_ref, row_off_ref,
                                   limits_ref, table_ref, out_ref, *,
                                   bk: int, dbs: tuple, word_offs: tuple):
    """Random-row variant of the packed kernel: indices in, features out.

    ``rows_ref`` holds a BN-row tile of arbitrary table row indices. For each
    column c the kernel computes the word index (``row // s``, s = 32/db,
    fields never straddle words at divisor widths) and bit offset
    (``(row % s) * db``) against the RESIDENT word stream, extracts the
    field, clamps, shifts into the block-diagonal super-table's row space and
    accumulates the multi-hot x table matmul — one pass, int32 code streams
    never exist, and the only per-launch host->device traffic is the index
    vector itself (4B x N, independent of column count).

    The in-kernel word gather (``jnp.take``) is exact in interpret mode;
    Mosaic refuses it for a TPU ("Only 2D gather is supported"), so a TPU
    executor raises ``KernelRefused`` for this kernel until it gathers by
    DMA (ROADMAP S2).
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tbl = table_ref[...]                        # (BK, F_total) f32
    rows = rows_ref[...][0]                     # (BN,) int32
    words = words_ref[...][0]                   # (W,) uint32, all columns
    bn = rows.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, tbl.shape[0]), 1)
    multihot = jnp.zeros((bn, tbl.shape[0]), tbl.dtype)
    for c, db in enumerate(dbs):                # static unroll over columns
        s = 32 // db
        w = jnp.take(words, word_offs[c] + rows // s)       # (BN,) u32
        fields = w >> ((rows % s).astype(jnp.uint32) * jnp.uint32(db))
        if db < 32:
            fields = fields & jnp.uint32((1 << db) - 1)
        codes = fields.astype(jnp.int32)
        codes = jnp.clip(codes, 0, limits_ref[c, 0]) + row_off_ref[c, 0]
        multihot += ((codes.reshape(bn, 1) - k * bk) == col).astype(tbl.dtype)
    out_ref[...] += jnp.dot(multihot, tbl,
                            preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n", "bn", "bk", "dbs", "word_offs",
                                    "interpret"))
def adv_gather_packed_rows_pallas(rows: jnp.ndarray, words: jnp.ndarray,
                                  row_offsets: jnp.ndarray,
                                  card_limits: jnp.ndarray,
                                  table: jnp.ndarray, n: int, bn: int = 256,
                                  bk: int = 512, dbs: tuple = (),
                                  word_offs: tuple = (),
                                  interpret: bool | None = None) -> jnp.ndarray:
    """rows (n,) int32 arbitrary row indices, words (W,) uint32 resident
    streams, table (K_total, F_total) block-diagonal -> (n, F_total).

    Preconditions (enforced by ops.py): n % bn == 0, K_total % bk == 0,
    every row index covered by column c's stream at word_offs[c].
    """
    c_count = row_offsets.shape[0]
    k_rows, f = table.shape
    w = words.shape[0]
    grid = (n // bn, k_rows // bk)
    return pl.pallas_call(
        functools.partial(_adv_gather_packed_rows_kernel, bk=bk, dbs=dbs,
                          word_offs=word_offs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, k: (0, i)),
            pl.BlockSpec((1, w), lambda i, k: (0, 0)),
            pl.BlockSpec((c_count, 1), lambda i, k: (0, 0)),
            pl.BlockSpec((c_count, 1), lambda i, k: (0, 0)),
            pl.BlockSpec((bk, f), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), table.dtype),
        interpret=resolve_interpret(interpret),
    )(rows.reshape(1, n), words.reshape(1, w), row_offsets, card_limits,
      table)


@functools.partial(jax.jit,
                   static_argnames=("n", "bn", "bk", "dbs", "word_offs",
                                    "interpret"))
def adv_gather_packed_pallas(words: jnp.ndarray, row_offsets: jnp.ndarray,
                             card_limits: jnp.ndarray, table: jnp.ndarray,
                             n: int, bn: int = 256, bk: int = 512,
                             dbs: tuple = (), word_offs: tuple = (),
                             interpret: bool | None = None) -> jnp.ndarray:
    """words (W,) uint32 (all columns' device-width streams concatenated),
    table (K_total, F_total) block-diagonal -> (n, F_total) features.

    Preconditions (enforced by ops.py): n % bn == 0, bn % 32 == 0 (so every
    window is word-aligned for every divisor width), K_total % bk == 0,
    column c's stream covers n * dbs[c] / 32 words from word_offs[c].
    The whole word stream stays resident across grid steps — it is 32/db x
    smaller than the int32 codes it replaces.
    """
    c_count = row_offsets.shape[0]
    k_rows, f = table.shape
    w = words.shape[0]
    grid = (n // bn, k_rows // bk)
    return pl.pallas_call(
        functools.partial(_adv_gather_packed_kernel, bk=bk, dbs=dbs,
                          word_offs=word_offs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, w), lambda i, k: (0, 0)),
            pl.BlockSpec((c_count, 1), lambda i, k: (0, 0)),
            pl.BlockSpec((c_count, 1), lambda i, k: (0, 0)),
            pl.BlockSpec((bk, f), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), table.dtype),
        interpret=resolve_interpret(interpret),
    )(words.reshape(1, w), row_offsets, card_limits, table)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "interpret"))
def adv_gather_multi_pallas(codes: jnp.ndarray, table: jnp.ndarray,
                            bn: int = 256, bk: int = 512,
                            interpret: bool | None = None) -> jnp.ndarray:
    """codes (C, N) int32 pre-offset into block-diagonal rows, table
    (K_total, F_total) -> (N, F_total) concatenated features.

    Preconditions (enforced by ops.py): N % bn == 0, K_total % bk == 0,
    F_total % 128 == 0 on real TPU; every codes[c, i] lands inside block c.
    """
    c_count, n = codes.shape
    k_rows, f = table.shape
    grid = (n // bn, k_rows // bk)
    return pl.pallas_call(
        functools.partial(_adv_gather_multi_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_count, bn), lambda i, k: (0, i)),
            pl.BlockSpec((bk, f), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), table.dtype),
        interpret=resolve_interpret(interpret),
    )(codes, table)


@functools.partial(jax.jit,
                   static_argnames=("bn", "bk", "interpret"))
def adv_gather_pallas(codes: jnp.ndarray, table: jnp.ndarray,
                      bn: int = 256, bk: int = 512,
                      interpret: bool | None = None) -> jnp.ndarray:
    """codes (N,) int32, table (K, F) float -> (N, F).

    Preconditions (enforced by ops.py): N % bn == 0, K % bk == 0,
    F % 128 == 0 on real TPU.
    """
    n = codes.shape[0]
    k_rows, f = table.shape
    grid = (n // bn, k_rows // bk)
    return pl.pallas_call(
        functools.partial(_adv_gather_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda i, k: (0, i)),
            pl.BlockSpec((bk, f), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, f), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f), table.dtype),
        interpret=resolve_interpret(interpret),
    )(codes.reshape(1, n), table)
