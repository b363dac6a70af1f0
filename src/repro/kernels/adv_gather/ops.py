"""Public jit'd wrappers for the ADV gather kernels.

Handles padding to MXU-aligned block shapes and falls back to the XLA gather
(`ref`) for huge-K tables where one-hot tiling is wasteful (K > 64k — e.g.
full LM vocabularies, which are sharded and gathered natively instead,
see repro.models.lm).

Three entry points:

- :func:`adv_gather` — single (K, F) table, code vector of any shape.
- :func:`fuse_tables` + :func:`adv_gather_fused` — C tables fused into one
  block-diagonal super-table resident on device; per-batch work is ONE kernel
  pass over a (C, N) code matrix producing the concatenated (N, ΣF) features,
  instead of C ``take`` calls + a ``concatenate``. The super-table costs
  ΣK × ΣF floats (vs Σ K_c·F_c unfused), the price of the single-matmul
  layout — ``FusedTables.nbytes`` reports it so planners can budget.
- :func:`adv_gather_packed` — the packed fast path: per-column device-width
  packed word windows go straight into a fused unpack→clamp→multi-hot-gather
  kernel, so int32 code streams never exist on host or device. Guarded by
  :func:`fused_kernel_fits` (ΣK×ΣF VMEM budget): oversized plans fall back
  to :func:`adv_gather_packed_split` (device unpack + per-table gathers —
  still packed transfer, just unfused compute). :func:`autotune_packed`
  sweeps (bn, bk, bw) block shapes and caches the winner per workload shape.
- :func:`adv_gather_packed_rows` — random-row packed gather: a device vector
  of arbitrary row indices goes into a kernel that computes word index + bit
  offset against the RESIDENT word streams, then unpack→clamp→multi-hot
  gather in the same pass. Host->device traffic per call is the index vector
  (4B × N), independent of column count; the same VMEM budget falls back to
  :func:`adv_gather_packed_rows_split`. :func:`autotune_fused` is the int32
  fused kernel's (bn, bk) sweep, ported from the packed path.
"""
from __future__ import annotations

import timeit
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.adv_gather.kernel import (adv_gather_pallas,
                                             adv_gather_multi_pallas,
                                             adv_gather_packed_pallas,
                                             adv_gather_packed_rows_pallas)
from repro.kernels.adv_gather.ref import (adv_gather_ref, adv_gather_multi_ref,
                                          adv_gather_packed_ref,
                                          adv_gather_packed_rows_ref)

MAX_ONEHOT_K = 1 << 16
# fused block-diagonal super-table must fit comfortably in VMEM (~16MB/core)
PACKED_VMEM_BUDGET = 16 << 20


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def adv_gather(table: jnp.ndarray, codes: jnp.ndarray,
               bn: int = 256, bk: int = 512,
               interpret: bool | None = None) -> jnp.ndarray:
    """Gather ADV rows for a code vector of any shape; returns (*codes.shape, F)."""
    shape = codes.shape
    flat = codes.reshape(-1).astype(jnp.int32)
    k, f = table.shape
    if k > MAX_ONEHOT_K:
        out = adv_gather_ref(flat, table)
        return out.reshape(*shape, f)
    # clamp OOB codes so both paths agree (the one-hot kernel would
    # otherwise emit silent all-zero rows where the ref path clips)
    flat = jnp.clip(flat, 0, k - 1)
    n = flat.shape[0]
    n_pad = _pad_to(max(n, 1), bn)
    k_pad = _pad_to(k, bk)
    f_pad = _pad_to(f, 128)
    flat_p = jnp.pad(flat, (0, n_pad - n))
    table_p = jnp.pad(table, ((0, k_pad - k), (0, f_pad - f)))
    out = adv_gather_pallas(flat_p, table_p, bn=bn, bk=bk,
                            interpret=interpret)
    return out[:n, :f].reshape(*shape, f)


# -- fused multi-table gather-concat ---------------------------------------------


@dataclass(frozen=True)
class FusedTables:
    """Block-diagonal super-table + code offsets for the fused kernel.

    Built once at plan-compile time and kept device-resident (the paper's
    'created once, easily amortized'); per-batch traffic is codes only.
    """
    table: jnp.ndarray            # (K_pad, F_pad) block-diagonal, on device
    row_offsets: jnp.ndarray      # (C, 1) int32 — code shift per source table
    card_limits: jnp.ndarray      # (C, 1) int32 — K_c - 1, for OOB clamping
    dims: tuple[int, ...]         # per-table feature width F_c
    cards: tuple[int, ...]        # per-table cardinality K_c
    bn: int
    bk: int

    @property
    def n_tables(self) -> int:
        return len(self.dims)

    @property
    def out_dim(self) -> int:
        return int(sum(self.dims))

    @property
    def nbytes(self) -> int:
        return int(self.table.size) * self.table.dtype.itemsize


def fuse_tables(tables, bn: int = 256, bk: int = 512,
                dtype=jnp.float32) -> FusedTables:
    """Pack C (K_c, F_c) host tables into one device-resident block diagonal."""
    tables = [np.asarray(t, np.float32) for t in tables]
    cards = tuple(int(t.shape[0]) for t in tables)
    dims = tuple(int(t.shape[1]) for t in tables)
    k_total, f_total = sum(cards), sum(dims)
    k_pad = _pad_to(max(k_total, 1), bk)
    f_pad = _pad_to(max(f_total, 1), 128)
    host = np.zeros((k_pad, f_pad), np.float32)
    row_offsets = np.zeros((len(tables), 1), np.int32)
    r = c = 0
    for i, t in enumerate(tables):
        row_offsets[i, 0] = r
        host[r:r + t.shape[0], c:c + t.shape[1]] = t
        r += t.shape[0]
        c += t.shape[1]
    limits = np.asarray(cards, np.int32)[:, None] - 1
    return FusedTables(table=jnp.asarray(host, dtype),
                       row_offsets=jnp.asarray(row_offsets),
                       card_limits=jnp.asarray(limits),
                       dims=dims, cards=cards, bn=bn, bk=bk)


def place_fused(fused: FusedTables, device) -> FusedTables:
    """Copy of ``fused`` with its device arrays committed to ``device``.

    Mesh-sharded serving replicates the block-diagonal super-table to every
    shard's device (the tables are K-row sized — 'created once, easily
    amortized' — while the word streams stay partitioned): per-shard
    launches then run entirely against device-local operands, never pulling
    the table across the mesh. Idempotent: when the super-table already
    lives wholly on ``device`` (a hot-shard replica landing where another
    shard — or the plan itself — placed it) the same object is returned, so
    adaptive replication never duplicates the table on one device.
    """
    try:
        if fused.table.devices() == {device}:
            return fused
    except Exception:       # pragma: no cover - non-committed/tracer arrays
        pass
    import dataclasses
    return dataclasses.replace(
        fused,
        table=jax.device_put(fused.table, device),
        row_offsets=jax.device_put(fused.row_offsets, device),
        card_limits=jax.device_put(fused.card_limits, device))


def gather_fused_parts(table: jnp.ndarray, row_offsets: jnp.ndarray,
                       codes: jnp.ndarray, out_dim: int,
                       card_limits: jnp.ndarray | None = None,
                       bn: int = 256, bk: int = 512,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Functional core of :func:`adv_gather_fused`.

    Takes the super-table and offsets as plain arrays so callers can jit
    over them as *arguments* (refreshed tables flow through; only shape
    changes retrace) instead of baking them in as trace-time constants.
    ``card_limits`` ((C, 1) int32, = K_c - 1) clamps out-of-range codes to
    their own table's block, matching ``jnp.take``'s clamp semantics — an
    unclamped OOB code would silently gather from the NEXT table's rows.
    """
    n = codes.shape[1]
    codes = codes.astype(jnp.int32)
    if card_limits is not None:
        codes = jnp.clip(codes, 0, card_limits)
    shifted = codes + row_offsets
    n_pad = _pad_to(max(n, 1), bn)
    # padded lanes re-point at block 0 row 0; their output rows are sliced off
    shifted = jnp.pad(shifted, ((0, 0), (0, n_pad - n)))
    out = adv_gather_multi_pallas(shifted, table, bn=bn, bk=bk,
                                  interpret=interpret)
    return out[:n, :out_dim]


def adv_gather_fused(fused: FusedTables, codes: jnp.ndarray,
                     interpret: bool | None = None) -> jnp.ndarray:
    """codes (C, N) int32 (codes[c] indexes source table c) -> (N, ΣF)."""
    c_count = codes.shape[0]
    if c_count != fused.n_tables:
        raise ValueError(f"expected {fused.n_tables} code rows, got {c_count}")
    return gather_fused_parts(fused.table, fused.row_offsets, codes,
                              fused.out_dim, card_limits=fused.card_limits,
                              bn=fused.bn, bk=fused.bk, interpret=interpret)


# -- packed fast path: unpack fused into the gather -------------------------------


def fused_kernel_fits(cards, dims,
                      budget: int = PACKED_VMEM_BUDGET) -> bool:
    """VMEM-budget guard for every fused block-diagonal kernel.

    The super-table costs ΣK × ΣF f32; past ~16MB it no longer fits in VMEM
    alongside the code/word tiles, so callers must split into unfused
    per-table gathers. Originally the packed path's guard; the int32 fused
    gather-concat kernel shares the exact same layout and therefore the
    exact same budget (the ported ROADMAP item).
    """
    sk, sf = sum(cards), sum(dims)
    return sk <= MAX_ONEHOT_K and 4 * sk * sf <= budget


# back-compat name from PR 2, when only the packed path was guarded
packed_kernel_fits = fused_kernel_fits


def adv_gather_packed(windows, dbs, fused_table: jnp.ndarray,
                      row_offsets: jnp.ndarray, card_limits: jnp.ndarray,
                      n: int, out_dim: int, bn: int = 256, bk: int = 512,
                      bw: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """Fused unpack+gather: packed word windows -> (n, out_dim) features.

    ``windows[c]`` holds column c's device-width (``dbs[c]`` | 32) packed
    words covering the batch; int32 codes are materialized nowhere — the
    kernel unpacks each (bn)-row tile in VREGs. ``bn`` must be a multiple of
    32 so every tile is word-aligned at every divisor width; ``bw`` pads the
    concatenated word stream to lane-aligned width.
    """
    if bn % 32:
        raise ValueError(f"bn must be a multiple of 32, got {bn}")
    if len(windows) != len(dbs):
        raise ValueError("one device width per window required")
    n_pad = _pad_to(max(n, 1), bn)
    parts, offs, off = [], [], 0
    for win, db in zip(windows, dbs):
        if 32 % db:
            raise ValueError(f"device width {db} does not divide 32")
        need = n_pad * db // 32
        w = jnp.asarray(win, jnp.uint32)[:need]     # over-provisioned slice
        if w.shape[0] < need:
            w = jnp.pad(w, (0, need - w.shape[0]))
        parts.append(w)
        offs.append(off)
        off += need
    flat = jnp.concatenate(parts) if parts else jnp.zeros(0, jnp.uint32)
    flat = jnp.pad(flat, (0, _pad_to(max(off, 1), bw) - off))
    out = adv_gather_packed_pallas(flat, row_offsets, card_limits,
                                   fused_table, n=n_pad, bn=bn, bk=bk,
                                   dbs=tuple(dbs), word_offs=tuple(offs),
                                   interpret=interpret)
    return out[:n, :out_dim]


def adv_gather_packed_rows(flat_words: jnp.ndarray, word_offs, dbs,
                           fused_table: jnp.ndarray,
                           row_offsets: jnp.ndarray,
                           card_limits: jnp.ndarray, rows: jnp.ndarray,
                           out_dim: int, bn: int = 256, bk: int = 512,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Random-row fused unpack+gather: row indices -> (len(rows), out_dim).

    ``flat_words`` concatenates every column's resident device-width word
    stream (column c's words start at ``word_offs[c]``, packed at ``dbs[c]``
    bits); ``rows`` is a device vector of arbitrary table row indices. The
    kernel computes word index + bit offset in-kernel, so the per-call
    host->device traffic is the 4B × N index vector — int32 code streams
    never exist, for ANY access pattern, not just aligned ranges.
    """
    if len(word_offs) != len(dbs):
        raise ValueError("one word offset per device width required")
    for db in dbs:
        if 32 % db:
            raise ValueError(f"device width {db} does not divide 32")
    rows = jnp.asarray(rows, jnp.int32).reshape(-1)
    n = rows.shape[0]
    n_pad = _pad_to(max(n, 1), bn)
    if n_pad > n:
        # repeat the last row: always a valid index, outputs sliced off
        rows = jnp.pad(rows, (0, n_pad - n), mode="edge")
    out = adv_gather_packed_rows_pallas(rows, flat_words, row_offsets,
                                        card_limits, fused_table, n=n_pad,
                                        bn=bn, bk=bk, dbs=tuple(dbs),
                                        word_offs=tuple(word_offs),
                                        interpret=interpret)
    return out[:n, :out_dim]


def adv_gather_packed_rows_split(flat_words: jnp.ndarray, word_offs, dbs,
                                 tables, rows: jnp.ndarray) -> jnp.ndarray:
    """Unfused fallback for the random-row path: word gather + field
    extract + XLA table gathers, all on device, index-only transfer.

    Op-count-minimal XLA rendering (CPU per-op overhead dominates small
    batches, so the per-column shift/mask pipeline of the oracle would cost
    ~9 ops × C): ONE gather pulls every column's words from the
    concatenated resident stream via a (C, N) word-index matrix, then one
    broadcasted shift/mask extracts all fields at once — per-column work is
    just the final table take. Bit-exact vs
    :func:`adv_gather_packed_rows_ref`; used when ΣK×ΣF exceeds the VMEM
    budget or ΣK exceeds the one-hot tiling guard.
    """
    rows = jnp.asarray(rows, jnp.int32).reshape(-1)
    if not dbs:
        return jnp.zeros((rows.shape[0], 0), jnp.float32)
    # per-column constants, broadcast over the row axis (s = 32/db is a
    # power of two, so // and % become shift and mask)
    log2s = np.array([(32 // db).bit_length() - 1 for db in dbs], np.int32)
    sub_mask = np.array([(32 // db) - 1 for db in dbs], np.int32)
    dbv = np.array(dbs, np.uint32)
    field_mask = np.array([(1 << db) - 1 if db < 32 else 0xFFFFFFFF
                           for db in dbs], np.uint32)
    offv = np.array(word_offs, np.int32)
    widx = offv[:, None] + (rows[None, :] >> log2s[:, None])     # (C, N)
    w = jnp.take(flat_words, widx.reshape(-1),
                 mode="clip").reshape(len(dbs), -1)
    sub = (rows[None, :] & sub_mask[:, None]).astype(jnp.uint32)
    codes = ((w >> (sub * dbv[:, None])) & field_mask[:, None]) \
        .astype(jnp.int32)
    # stop XLA CPU from fusing the extraction into every table gather —
    # the re-fused loop de-vectorizes and costs ~4x the two plain stages
    codes = jax.lax.optimization_barrier(codes)
    return jnp.concatenate(
        [jnp.take(t, codes[c], axis=0, mode="clip")
         for c, t in enumerate(tables)], axis=-1)


def adv_gather_packed_split(windows, dbs, tables, n: int) -> jnp.ndarray:
    """Unfused fallback: per-column device unpack + XLA gather + concat.

    Same packed host->device transfer as the fused kernel (the bytes win is
    preserved); only the compute is split — used when ΣK×ΣF exceeds the
    VMEM budget or ΣK exceeds the one-hot tiling guard.
    """
    return adv_gather_packed_ref(windows, dbs, tables, n)


# one winner per workload signature — the sweep is pure wall-clock timing of
# the real call, so it is only worth paying once per (dbs, n, table) shape
_PACKED_TUNE_CACHE: dict[tuple, tuple[int, int, int]] = {}
PACKED_BLOCK_CANDIDATES = ((128, 512, 512), (256, 256, 512), (256, 512, 512),
                           (256, 512, 1024), (512, 512, 512))


def autotune_packed(windows, dbs, fused: FusedTables, n: int,
                    candidates=PACKED_BLOCK_CANDIDATES, repeats: int = 3,
                    interpret: bool | None = None) -> tuple[int, int, int]:
    """Sweep (bn, bk, bw) for the fused packed kernel; return the fastest.

    Invalid candidates (bn not word-aligned, bk that does not tile the
    already-padded super-table) are skipped. Results are cached per
    (dbs, n, table-shape) so a serving plan pays the sweep once.
    """
    key = (tuple(dbs), n, tuple(fused.table.shape))
    hit = _PACKED_TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    best, best_t = (fused.bn, fused.bk, 512), float("inf")
    for bn, bk, bw in candidates:
        if bn % 32 or fused.table.shape[0] % bk:
            continue

        def call(bn=bn, bk=bk, bw=bw):
            adv_gather_packed(windows, dbs, fused.table, fused.row_offsets,
                              fused.card_limits, n, fused.out_dim, bn=bn,
                              bk=bk, bw=bw,
                              interpret=interpret).block_until_ready()
        call()                                     # compile outside the clock
        t = min(timeit.repeat(call, number=1, repeat=repeats))
        if t < best_t:
            best, best_t = (bn, bk, bw), t
    _PACKED_TUNE_CACHE[key] = best
    return best


# the int32 fused kernel has no word-stream width to tune, so candidates are
# (bn, bk) pairs — the same row/table tilings the packed sweep explores
_FUSED_TUNE_CACHE: dict[tuple, tuple[int, int]] = {}
FUSED_BLOCK_CANDIDATES = ((128, 512), (256, 256), (256, 512), (512, 512))
_ROWS_TUNE_CACHE: dict[tuple, tuple[int, int]] = {}


def autotune_packed_rows(flat_words, word_offs, dbs, fused: FusedTables,
                         n: int, candidates=FUSED_BLOCK_CANDIDATES,
                         repeats: int = 3,
                         interpret: bool | None = None) -> tuple[int, int]:
    """Sweep (bn, bk) for the random-row packed kernel; return the fastest.

    Times :func:`adv_gather_packed_rows` ITSELF (its in-kernel word gather
    has a different cost profile than the range kernel's contiguous
    windows, so the range sweep's winner does not transfer). Cached per
    (dbs, n, table-shape); uses row 0 repeated — gather cost in interpret
    mode is index-value independent.
    """
    key = (tuple(dbs), n, tuple(fused.table.shape))
    hit = _ROWS_TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    rows = jnp.zeros(n, jnp.int32)
    best, best_t = (fused.bn, fused.bk), float("inf")
    for bn, bk in candidates:
        if bn % 32 or fused.table.shape[0] % bk:
            continue

        def call(bn=bn, bk=bk):
            adv_gather_packed_rows(flat_words, word_offs, dbs, fused.table,
                                   fused.row_offsets, fused.card_limits,
                                   rows, fused.out_dim, bn=bn, bk=bk,
                                   interpret=interpret).block_until_ready()
        call()                                     # compile outside the clock
        t = min(timeit.repeat(call, number=1, repeat=repeats))
        if t < best_t:
            best, best_t = (bn, bk), t
    _ROWS_TUNE_CACHE[key] = best
    return best


def autotune_fused(codes: jnp.ndarray, fused: FusedTables, n: int,
                   candidates=FUSED_BLOCK_CANDIDATES, repeats: int = 3,
                   interpret: bool | None = None) -> tuple[int, int]:
    """Sweep (bn, bk) for the int32 fused gather-concat kernel (the packed
    path's autotune, ported per the ROADMAP item); return the fastest.

    ``codes`` is a representative (C, n) int32 batch used purely for wall-
    clock timing. Invalid candidates (bk that does not tile the padded
    super-table) are skipped; results are cached per (C, n, table-shape) so
    a serving plan pays the sweep once per bucket shape.
    """
    codes = jnp.asarray(codes, jnp.int32)
    key = (codes.shape[0], n, tuple(fused.table.shape))
    hit = _FUSED_TUNE_CACHE.get(key)
    if hit is not None:
        return hit
    best, best_t = (fused.bn, fused.bk), float("inf")
    for bn, bk in candidates:
        if fused.table.shape[0] % bk:
            continue

        def call(bn=bn, bk=bk):
            gather_fused_parts(fused.table, fused.row_offsets, codes,
                               fused.out_dim, card_limits=fused.card_limits,
                               bn=bn, bk=bk,
                               interpret=interpret).block_until_ready()
        call()                                     # compile outside the clock
        t = min(timeit.repeat(call, number=1, repeat=repeats))
        if t < best_t:
            best, best_t = (bn, bk), t
    _FUSED_TUNE_CACHE[key] = best
    return best
