"""Public wrappers for the predicate-scan kernel, device bitmap compaction
and dict-aware masked aggregates.

Two evaluation paths over the SAME resident word streams, mirroring the
gather kernels' split/fused discipline:

- :func:`predicate_scan` — the fused Pallas kernel: per-term word windows
  are sliced (and zero-padded) out of the resident flat stream on device,
  then one kernel pass unpacks + compares + combines per BN-row tile.
- :func:`predicate_scan_split` — the op-count-minimal XLA rendering used by
  default on CPU (interpret-mode Pallas is Python-speed): ONE broadcast
  shift/mask unpack per referenced column directly against the resident
  flat stream, then vectorized compares / LUT takes, all inside one jit —
  bit-exact vs :func:`repro.kernels.predicate_scan.ref.predicate_scan_ref`.

Downstream pieces of the pushdown pipeline:

- :func:`compact_rows` — device-side bitmap -> row-index compaction with a
  static output shape (the pad-to-static-bucket contract), feeding
  ``adv_gather_packed_rows`` so "scan -> compact -> gather" never leaves
  the device. :func:`compact_block` is its windowed form over the mask's
  running match count (:func:`predicate_scan_split_ranks`): a fixed-size
  block of matches at a traced offset, so one compiled shape serves every
  match count. A TPU renders it as one pass over the block's span of the
  count (``compact.py``), other backends as a binary search per match.
- :func:`masked_counts` — masked per-code histogram over a column's
  resident words (the ``kernels/hist`` machinery with a mask lane):
  count/sum/mean of the column under a predicate then follow from K
  dictionary entries, never the N-row value stream.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.bitunpack.ops import (concat_row_blocks, map_row_blocks,
                                         rows_of, unpack_rows)
from repro.kernels.predicate_scan.compact import (CHUNK_ROWS,
                                                  compact_block_pallas)
from repro.kernels.predicate_scan.kernel import predicate_scan_pallas
from repro.kernels.hist.ops import masked_hist


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ScanTerm:
    """One column's compiled code-space predicate term.

    ``kind`` 0 matches the contiguous code range ``[lo, hi]`` (pure VPU
    compares on device — an empty range, ``hi < lo``, matches nothing);
    kind 1 matches where ``lut[code] != 0`` (arbitrary IN-sets; ``lut`` has
    one entry per dictionary code). Produced by
    :func:`repro.columnar.query.compile_predicate`.
    """
    col: int
    kind: int
    lo: int = 0
    hi: int = -1
    lut: np.ndarray | None = field(default=None, compare=False)


def _pack_terms(terms, dbs):
    """Split a term list into the kernel's static structure + traced data.

    Returns (bounds (T, 2) int32, flat_lut (L,) int32, statics) where
    statics = (cols, kinds, lut_offs, lut_lens) — identically-shaped
    predicates (same columns/kinds, different constants) share one compile.
    """
    if not terms:
        raise ValueError("need at least one predicate term")
    cols, kinds, lut_offs, lut_lens = [], [], [], []
    bounds = np.zeros((len(terms), 2), np.int32)
    luts, off = [], 0
    for t, term in enumerate(terms):
        if not 0 <= term.col < len(dbs):
            raise ValueError(f"term column {term.col} outside plan "
                             f"(C={len(dbs)})")
        cols.append(term.col)
        kinds.append(term.kind)
        if term.kind == 0:
            bounds[t] = (term.lo, term.hi)
            lut_offs.append(0)
            lut_lens.append(1)
        else:
            lut = np.asarray(term.lut, np.int32).reshape(-1)
            if lut.shape[0] == 0:
                raise ValueError("LUT term needs a K-entry table")
            luts.append(lut)
            lut_offs.append(off)
            lut_lens.append(lut.shape[0])
            off += lut.shape[0]
    flat_lut = (np.concatenate(luts) if luts else np.zeros(1, np.int32))
    return (jnp.asarray(bounds), jnp.asarray(flat_lut),
            (tuple(cols), tuple(kinds), tuple(lut_offs), tuple(lut_lens)))


def predicate_scan(flat_words: jnp.ndarray, word_offs, dbs, terms, n: int,
                   combine: str = "and", bn: int = 1024,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Fused Pallas scan: resident flat stream + compiled terms -> (n,)
    bool selection mask.

    Only the referenced columns' windows enter the kernel stream — sliced
    (statically) from the resident flat words and zero-padded to the tile
    quantum on device, so padding rows decode to code 0 and their mask
    lanes are sliced off with the rest of [n, n_pad).
    """
    if bn % 32:
        raise ValueError(f"bn must be a multiple of 32, got {bn}")
    if combine not in ("and", "or"):
        raise ValueError(f"unknown combinator {combine!r}")
    bounds, flat_lut, (cols, kinds, lut_offs, lut_lens) = \
        _pack_terms(terms, dbs)
    n_pad = _pad_to(max(n, 1), bn)
    used = sorted(set(cols))
    remap = {c: i for i, c in enumerate(used)}
    parts, offs, off = [], [], 0
    for c in used:
        db = dbs[c]
        need = n_pad * db // 32
        w = jnp.asarray(flat_words, jnp.uint32)[word_offs[c]:
                                                word_offs[c] + need]
        if w.shape[0] < need:
            w = jnp.pad(w, (0, need - w.shape[0]))
        parts.append(w)
        offs.append(off)
        off += need
    stream = jnp.concatenate(parts)
    mask = predicate_scan_pallas(
        stream, bounds, flat_lut, n=n_pad, bn=bn,
        cols=tuple(remap[c] for c in cols), kinds=kinds,
        dbs=tuple(dbs[c] for c in used), word_offs=tuple(offs),
        lut_offs=lut_offs, lut_lens=lut_lens, combine=combine,
        interpret=interpret)
    return mask[:n] != 0


def _scan_body(flat_words, bounds, flat_lut, *, word_offs, dbs, n, cols,
               kinds, lut_offs, lut_lens, combine):
    """XLA split scan: broadcast unpack + vectorized terms per row block.

    Few large fused ops per block (CPU per-op overhead dominates tile
    loops), one unpack per referenced column even when several terms share
    it; the blocks (:func:`map_row_blocks`) bound the padded intermediates
    XLA:TPU would otherwise lay out over the whole column.
    """
    def block(start, size):
        acc = None
        codes_cache = {}
        for t, c in enumerate(cols):
            codes = codes_cache.get(c)
            if codes is None:
                codes = codes_cache[c] = unpack_rows(
                    flat_words, word_offs[c], dbs[c], start, size)
            if kinds[t] == 0:
                m = (codes >= bounds[t, 0]) & (codes <= bounds[t, 1])
            else:
                idx = jnp.minimum(codes, lut_lens[t] - 1)
                m = jnp.take(flat_lut, lut_offs[t] + idx, mode="clip") != 0
            acc = m if acc is None else \
                ((acc & m) if combine == "and" else (acc | m))
        return acc

    return concat_row_blocks(*map_row_blocks(block, n), n)


_SCAN_STATICS = ("word_offs", "dbs", "n", "cols", "kinds", "lut_offs",
                 "lut_lens", "combine")
_scan_split = functools.partial(jax.jit,
                                static_argnames=_SCAN_STATICS)(_scan_body)


@functools.partial(jax.jit, static_argnames=_SCAN_STATICS)
def _scan_split_count(flat_words, bounds, flat_lut, *, word_offs, dbs, n,
                      cols, kinds, lut_offs, lut_lens, combine):
    """Scan + popcount in ONE launch: the match count (the compaction's
    static launch shape) rides along with the mask, so the filtered-serving
    hot path syncs one scalar without a separate eager reduction dispatch."""
    mask = _scan_body(flat_words, bounds, flat_lut, word_offs=word_offs,
                      dbs=dbs, n=n, cols=cols, kinds=kinds,
                      lut_offs=lut_offs, lut_lens=lut_lens, combine=combine)
    return mask, jnp.sum(mask.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=_SCAN_STATICS)
def _scan_split_ranks(flat_words, bounds, flat_lut, *, word_offs, dbs, n,
                      cols, kinds, lut_offs, lut_lens, combine):
    """Scan + running match count in ONE launch: ``ranks[i]`` is the number
    of matches in rows [0, i], so ``ranks[-1]`` is the count and
    :func:`compact_block` finds any block of matches without a rescan.
    The count runs on over padding rows that never match, to a multiple
    of ``CHUNK_ROWS``: the compaction kernel reads it in whole tiles."""
    mask = _scan_body(flat_words, bounds, flat_lut, word_offs=word_offs,
                      dbs=dbs, n=n, cols=cols, kinds=kinds,
                      lut_offs=lut_offs, lut_lens=lut_lens, combine=combine)
    mask = jnp.pad(mask, (0, -n % CHUNK_ROWS))
    ranks = jnp.cumsum(mask.astype(jnp.int32))
    return ranks, ranks[-1]


def pack_terms(terms, dbs):
    """Pre-pack a term list for repeated scans: (bounds, flat_lut, statics)
    with the data halves already on device. A deployed filter family scans
    on every request — re-shipping two small arrays per call is pure
    dispatch overhead, so executors cache this per compiled predicate."""
    return _pack_terms(terms, dbs)


def predicate_scan_split(flat_words: jnp.ndarray, word_offs, dbs, terms,
                         n: int, combine: str = "and",
                         packed=None) -> jnp.ndarray:
    """Unfused fallback/CPU default: same resident stream, same (n,) bool
    mask, rendered as one jit of broadcast unpacks + compares."""
    if combine not in ("and", "or"):
        raise ValueError(f"unknown combinator {combine!r}")
    bounds, flat_lut, (cols, kinds, lut_offs, lut_lens) = \
        packed if packed is not None else _pack_terms(terms, dbs)
    return _scan_split(flat_words, bounds, flat_lut,
                       word_offs=tuple(word_offs), dbs=tuple(dbs), n=n,
                       cols=cols, kinds=kinds, lut_offs=lut_offs,
                       lut_lens=lut_lens, combine=combine)


def predicate_scan_split_count(flat_words: jnp.ndarray, word_offs, dbs,
                               terms, n: int, combine: str = "and",
                               packed=None):
    """Split scan variant returning (mask, match-count) from one launch."""
    if combine not in ("and", "or"):
        raise ValueError(f"unknown combinator {combine!r}")
    bounds, flat_lut, (cols, kinds, lut_offs, lut_lens) = \
        packed if packed is not None else _pack_terms(terms, dbs)
    return _scan_split_count(flat_words, bounds, flat_lut,
                             word_offs=tuple(word_offs), dbs=tuple(dbs),
                             n=n, cols=cols, kinds=kinds, lut_offs=lut_offs,
                             lut_lens=lut_lens, combine=combine)


def predicate_scan_split_ranks(flat_words: jnp.ndarray, word_offs, dbs,
                               terms, n: int, combine: str = "and",
                               packed=None):
    """Split scan variant returning (ranks, match-count) from one launch:
    the (n,) int32 running match count and its last entry."""
    if combine not in ("and", "or"):
        raise ValueError(f"unknown combinator {combine!r}")
    bounds, flat_lut, (cols, kinds, lut_offs, lut_lens) = \
        packed if packed is not None else _pack_terms(terms, dbs)
    return _scan_split_ranks(flat_words, bounds, flat_lut,
                             word_offs=tuple(word_offs), dbs=tuple(dbs),
                             n=n, cols=cols, kinds=kinds, lut_offs=lut_offs,
                             lut_lens=lut_lens, combine=combine)


@functools.partial(jax.jit, static_argnames=("cap", "fill"))
def compact_block(ranks: jnp.ndarray, off, cap: int,
                  fill: int = 0) -> jnp.ndarray:
    """Matches [off, off + cap) as a static-shape (cap,) int32 row vector.

    ``ranks`` is a mask's running match count (inclusive cumsum): entry j
    is the row of match ``off + j`` (0-based), the first row whose running
    count reaches ``off + j + 1``. Entries past the last match hold
    ``fill``, a valid row index, so the block feeds the indexed gather
    directly and callers slice the valid prefix off the output. ``off`` is
    traced: one compiled shape serves every offset and every count.

    One result, rendered by the platform the program is compiled for: a
    TPU walks the block's span of the count once
    (:func:`repro.kernels.predicate_scan.compact.compact_block_pallas`);
    elsewhere each match is a binary search of the count.
    """
    def searched(ranks, off):
        q = off + jnp.arange(1, cap + 1, dtype=jnp.int32)
        rows = jnp.searchsorted(ranks, q, side="left")
        return jnp.where(rows < ranks.shape[0], rows, fill).astype(jnp.int32)

    def walked(ranks, off):
        return compact_block_pallas(ranks, off, cap, fill, interpret=False)

    return jax.lax.platform_dependent(ranks, off, tpu=walked,
                                      default=searched)


@functools.partial(jax.jit, static_argnames=("cap", "fill"))
def compact_rows(mask: jnp.ndarray, cap: int, fill: int = 0) -> jnp.ndarray:
    """Device-side bitmap compaction: ascending matching row indices as a
    static-shape (cap,) int32 vector.

    Entries past the true match count hold ``fill`` — a valid row index,
    per the pad-to-static-bucket contract — so the vector can feed the
    indexed gather directly and callers slice the valid prefix off the
    OUTPUT, exactly like ``pad_rows_edge`` on the host side.

    Rendered as cumsum + searchsorted (the j-th match is the first row
    whose running count reaches j+1) rather than ``jnp.nonzero``: the
    all-gather form avoids XLA:CPU's element-at-a-time scatter lowering,
    which costs ~8x more at serving shapes.
    """
    return compact_block(jnp.cumsum(mask.astype(jnp.int32)), 0, cap, fill)


@functools.partial(jax.jit, static_argnames=("off", "db", "n", "k"))
def _masked_counts_split(flat_words, mask, *, off, db, n, k):
    """XLA masked histogram against the resident words: a broadcast unpack
    + a segment-sum scatter-add per row block, summed over the blocks."""
    def block(start, size):
        codes = unpack_rows(flat_words, off, db, start, size)
        hits = rows_of(mask, start, size).astype(jnp.int32)
        return jnp.zeros(k, jnp.int32).at[codes].add(hits, mode="drop")

    full, tail = map_row_blocks(block, n)
    counts = jnp.zeros(k, jnp.int32) if tail is None else tail
    return counts if full is None else counts + full.sum(axis=0)


def masked_counts(flat_words: jnp.ndarray, off: int, db: int,
                  mask: jnp.ndarray, k: int, n: int,
                  use_kernel: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Masked GROUP BY over a resident column: (k,) int32 per-code counts
    of the rows where ``mask`` is set.

    This is the dict-aware aggregate core: ``counts @ values`` gives the
    masked sum, ``counts.sum()`` the masked count, their ratio the mean —
    K dictionary entries of tail work, never an N-row value decode.
    ``use_kernel=True`` routes the histogram through the masked
    ``kernels/hist`` Pallas kernel (the count-metadata build kernel with a
    mask lane); the default is the one-jit XLA scatter-add.
    """
    mask = jnp.asarray(mask).reshape(-1)[:n]
    if use_kernel:
        codes = concat_row_blocks(*map_row_blocks(
            lambda start, size: unpack_rows(flat_words, off, db, start,
                                            size), n), n)
        return masked_hist(codes, mask, k, interpret=interpret)
    return _masked_counts_split(flat_words, mask, off=off, db=db, n=n, k=k)
