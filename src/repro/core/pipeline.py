"""Feature pipeline split into a compile-time plan and a run-time executor.

The paper's device pipeline is 'codes in, features out' (§6, Fig 2): only
dictionary codes (b-bit packed) and K-row ADV tables move to the device;
row-space float features are produced on-device by the fused ADV gather and
consumed immediately — never materialized in host memory or HBM-resident
files, the data-movement/duplication win over the CSV-export workflow of
Fig 1.

Layering (this module):

- :class:`FeaturePlan` — the compile-time half. Builds the per-column fused
  K-row ADV tables, puts them on device ONCE (amortized forever), and lays
  out the host code streams in one of two forms:

  * ``packed=False`` — a single (C, N) int32 matrix; a batch slice is ONE
    fancy-index + ONE host->device transfer.
  * ``packed=True``  — the packed fast path: per-column uint32 word streams
    repacked once at ``tpu_width(bits)`` (straight from the Column/IMCU
    device views), sliced per batch on word boundaries. int32 code streams
    never exist — neither in host RAM nor on the wire.

  Both layouts are maintained under streaming inserts via
  :meth:`FeaturePlan.refresh` (only columns whose AugmentedDictionary
  actually changed are re-put; packed streams are repacked in place only
  when a dictionary grows across a tpu_width boundary). Plans can be
  partitioned per IMCU (:meth:`FeaturePlan.imcu_shards`) so a shard touches
  only its own partition's codes.
- :class:`FeatureExecutor` — the run-time half. One jit'd gather over the
  stacked code batch per bucket shape; optional fused multi-table Pallas
  kernel (one kernel pass instead of per-column take + concatenate); a
  double-buffered :meth:`FeatureExecutor.batches` iterator that overlaps
  host code-slicing for batch i+1 with the device gather for batch i via
  ``jax.device_put`` prefetch (depth >= 2). In packed mode the word streams
  are kept DEVICE-resident (they are 32/bits x smaller than the int32
  matrix they replace), so a word-aligned range batch moves nothing but a
  start index — the fused ``adv_gather_packed`` kernel (or its split XLA
  fallback past the VMEM budget) unpacks in-register and gathers in one
  pass.
- :class:`ShardedFeatureExecutor` — the mesh half. ``imcu_shards()`` of a
  packed plan yields per-IMCU word-stream SLICES (zero-copy at word-aligned
  boundaries, seam repack otherwise); each slice is committed to its own
  serve-mesh device with replicated ADV tables, and arbitrary-row requests
  are routed to the shard that owns them — featurization compute moves to
  the columnar data, never shard bytes to one compute device.
- :class:`FeaturePipeline` — the original facade, kept API-compatible.

Data-movement accounting is built in (``bytes_moved_*``) so benchmarks and
EXPERIMENTS.md can quantify the claim. Host->device bytes per batch row, by
path (b = dictionary bits, db = tpu_width(b) <= 2b, F = feature dim):

    ========================  =================================  ==========
    path                      bytes/row                          example*
    ========================  =================================  ==========
    recompute (Fig 1 CSV)     4 x F                              232
    int32 codes (packed=0)    4 x C                              16
    packed words (packed=1)   sum_c db_c / 8                     3.25
    packed + device-resident  ~0 (words moved once, amortized)   ~0
    ========================  =================================  ==========

    *4-column mixed-cardinality serve workload (db = 8,8,8,2; F = 58).
"""
from __future__ import annotations

import bisect
import functools
import itertools
from collections import deque
from dataclasses import dataclass
from typing import Iterator, Mapping

import numpy as np
import jax
import jax.numpy as jnp

from repro.columnar.bitpack import (pack_bits, packed_gather, packed_nbytes,
                                    unpack_bits)
from repro.columnar.rle import rle_decode, rle_encode, rle_nbytes
from repro.columnar import query as colquery
from repro.columnar.table import Table
from repro.core.adv import AugmentedDictionary
from repro.core.feature_spec import FeatureSet
from repro import kernels
from repro.kernels.adv_gather import ops as adv_ops
from repro.kernels.bitunpack.kernel import tpu_width
from repro.kernels.predicate_scan import ops as scan_ops
from repro.spans import span


# matches one lane of a filtered ("where") launch compacts and gathers: the
# launch shape of a where request is (lanes, WHERE_LANE) whatever its match
# count, so one compiled program serves every predicate of a structure
WHERE_LANE = 65536


def _pad32(n: int) -> int:
    """Round up to the word-alignment quantum: a row index that is a
    multiple of 32 is word-aligned at EVERY divisor width (32/db | 32)."""
    return ((max(n, 1) + 31) // 32) * 32


def _agg_from_counts(d, counts: np.ndarray, agg: str) -> float:
    """Dict-aware aggregate tail: a masked per-code histogram + the K
    dictionary values give count/sum/mean without touching any row."""
    counts = np.asarray(counts, np.float64)
    n = float(counts.sum())
    if agg == "count":
        return n
    if not d.is_numeric():
        raise TypeError(f"{agg} requires a numeric dictionary "
                        f"(column {d.name!r} is {d.values.dtype})")
    s = float(np.dot(d.values.astype(np.float64), counts))
    if agg == "sum":
        return s
    if agg == "mean":
        return s / n if n else float("nan")
    raise ValueError(f"unknown agg {agg!r}")


def pad_rows_edge(rows: np.ndarray, to: int) -> np.ndarray:
    """Right-pad a row-index vector to a static shape by repeating the last
    row — always a valid index; callers slice the padded outputs off. The
    ONE encoding of the pad-to-static-bucket contract on the host side."""
    pad = to - rows.shape[0]
    if pad <= 0:
        return rows
    return np.concatenate([rows, np.full(pad, rows[-1], dtype=rows.dtype)])


def _slice_words(flat: jnp.ndarray, off: int, start, batch: int, db: int):
    """Device-side window into the flat resident stream: the batch's words
    for the column whose stream begins at ``off`` (start % 32 == 0,
    batch % 32 == 0, so the division is exact at any divisor width)."""
    s = 32 // db
    return jax.lax.dynamic_slice(flat, (off + start // s,), (batch // s,))


def _multi_windows(flat: jnp.ndarray, off: int, starts, batch: int, db: int):
    """K stacked word windows flattened into one (K * batch/s,) stream —
    windows are word-aligned, so concatenation preserves code order."""
    s = 32 // db
    return jax.vmap(
        lambda st: jax.lax.dynamic_slice(flat, (off + st // s,),
                                         (batch // s,)))(starts).reshape(-1)


@functools.partial(jax.jit, static_argnames=("dbs", "offs", "batch"))
def _packed_split_range(flat, tables, start, *, dbs, offs, batch):
    """Packed range batch, split path: per-column device unpack + gather."""
    wins = [_slice_words(flat, off, start, batch, db)
            for off, db in zip(offs, dbs)]
    return adv_ops.adv_gather_packed_split(wins, dbs, tables, batch)


@functools.partial(jax.jit, static_argnames=("dbs", "offs", "batch",
                                             "out_dim", "bn", "bk", "bw"))
def _packed_fused_range(flat, table, row_offsets, card_limits, start, *,
                        dbs, offs, batch, out_dim, bn, bk, bw):
    """Packed range batch through the fused one-pass Pallas kernel."""
    wins = [_slice_words(flat, off, start, batch, db)
            for off, db in zip(offs, dbs)]
    return adv_ops.adv_gather_packed(wins, dbs, table, row_offsets,
                                     card_limits, batch, out_dim,
                                     bn=bn, bk=bk, bw=bw)


@functools.partial(jax.jit, static_argnames=("dbs", "offs", "batch"))
def _packed_split_multi(flat, tables, starts, *, dbs, offs, batch):
    """K coalesced range batches in ONE launch -> (K, batch, out_dim).

    Amortizes per-launch overhead (dispatch + per-op fixed cost) across K
    batches — the serving pump's answer to many small range requests.
    """
    k = starts.shape[0]
    wins = [_multi_windows(flat, off, starts, batch, db)
            for off, db in zip(offs, dbs)]
    out = adv_ops.adv_gather_packed_split(wins, dbs, tables, k * batch)
    return out.reshape(k, batch, -1)


@functools.partial(jax.jit, static_argnames=("dbs", "offs", "batch",
                                             "out_dim", "bn", "bk", "bw"))
def _packed_fused_multi(flat, table, row_offsets, card_limits, starts, *,
                        dbs, offs, batch, out_dim, bn, bk, bw):
    """K coalesced range batches through the fused Pallas kernel."""
    k = starts.shape[0]
    wins = [_multi_windows(flat, off, starts, batch, db)
            for off, db in zip(offs, dbs)]
    out = adv_ops.adv_gather_packed(wins, dbs, table, row_offsets,
                                    card_limits, k * batch, out_dim,
                                    bn=bn, bk=bk, bw=bw)
    return out.reshape(k, batch, out_dim)


@functools.partial(jax.jit, static_argnames=("dbs", "word_offs"))
def _packed_split_rows(flat_words, tables, rows, *, dbs, word_offs):
    """Arbitrary-row indexed gather, split path: one coalesced word gather
    + broadcast field extract + per-table gathers. Index-only host->device
    traffic — the device computes word index + bit offset itself."""
    return adv_ops.adv_gather_packed_rows_split(flat_words, word_offs, dbs,
                                                tables, rows)


@functools.partial(jax.jit, static_argnames=("dbs", "word_offs", "out_dim",
                                             "bn", "bk"))
def _packed_fused_rows(flat_words, table, row_offsets, card_limits, rows, *,
                       dbs, word_offs, out_dim, bn, bk):
    """Arbitrary-row indexed gather through the fused one-pass Pallas
    kernel: unpack -> clamp -> multi-hot gather against resident words."""
    return adv_ops.adv_gather_packed_rows(flat_words, word_offs, dbs, table,
                                          row_offsets, card_limits, rows,
                                          out_dim, bn=bn, bk=bk)


@functools.partial(jax.jit, static_argnames=("lane",))
def _where_compact(ranks, offs, *, lane):
    """Lanes of matches -> one (lanes * lane,) row vector in ONE launch:
    lane i holds the matches [offs[i], offs[i] + lane) of the running match
    count ``ranks[i]``, so the shape is the same whatever the match count
    (a lane past the last match holds row 0; callers slice it off)."""
    return jnp.concatenate([scan_ops.compact_block(r, offs[i], lane)
                            for i, r in enumerate(ranks)])


class _ShardStats(dict):
    """Per-shard stats that roll every numeric delta up into the parent.

    ``imcu_shards()`` used to hand every shard the PARENT's dict, so
    per-shard ``words_put``/``tables_put`` counts were unattributable. Each
    shard now owns one of these: ``shard.stats['words_put'] += 1`` bumps the
    shard-local counter AND forwards the delta to the plan total, so the
    parent's numbers keep meaning 'whole plan' while each shard's dict
    answers 'who did it'.
    """

    def __init__(self, parent: dict, init: Mapping | None = None):
        super().__init__(init or {})
        self._parent = parent

    def __setitem__(self, key, value):
        old = self.get(key, 0)
        if isinstance(value, (int, float)) and isinstance(old, (int, float)):
            self._parent[key] = self._parent.get(key, 0) + (value - old)
        super().__setitem__(key, value)


@dataclass
class ColumnPlan:
    """One column's compiled gather plan."""
    column: str
    adv_names: list[str]
    fused_host: np.ndarray        # (K, F_col) host copy (refresh diffing)
    fused_table: jnp.ndarray      # (K, F_col) resident on device
    bits: int
    aug_version: int              # AugmentedDictionary.version at build time

    @property
    def out_dim(self) -> int:
        return int(self.fused_table.shape[1])

    @property
    def cardinality(self) -> int:
        return int(self.fused_table.shape[0])


class FeaturePlan:
    """Compile-time artifact: device-resident ADV tables + host code layout."""

    def __init__(self, table: Table, features: FeatureSet,
                 augmented: dict[str, AugmentedDictionary] | None = None,
                 packed: bool = False):
        self.table = table
        self.features = features
        self.augmented = augmented if augmented is not None \
            else features.build(table)
        self.packed = packed
        self.stats = {"tables_put": 0, "tables_refreshed": 0,
                      "fused_rebuilds": 0, "words_repacked": 0,
                      "words_put": 0, "rle_encoded": 0, "rehydrated": 0}
        self.plans: list[ColumnPlan] = []
        for column, aug in self.augmented.items():
            names = [s.adv_name for s in features.specs if s.column == column]
            self.plans.append(self._compile_column(column, aug, names))
        if packed:
            # packed fast path: per-column device-width word streams from the
            # Column/IMCU device views — the (C, N) int32 matrix never exists
            self._codes_matrix = None
            self._n_rows = table.n_rows
            self.packed_words: list[np.ndarray] = []
            self.device_bits: list[int] = []
            # packed_versions bumps on ANY stream change (repack or append);
            # packed_layout_versions bumps ONLY on a width-boundary repack.
            # Interior IMCU shards key their slices on the layout version —
            # a streaming append rewrites the tail, so only the open-ended
            # LAST shard (and the parent) must re-sync for it
            self.packed_versions: list[int] = []
            self.packed_layout_versions: list[int] = []
            for p in self.plans:
                words, db = table[p.column].device_words()
                self.packed_words.append(words)
                self.device_bits.append(db)
                self.packed_versions.append(0)
                self.packed_layout_versions.append(0)
        else:
            codes = [table[p.column].codes() for p in self.plans]
            # (C, N): one row-aligned int32 code stream per planned column —
            # a batch slice is ONE fancy-index + ONE host->device transfer
            self._codes_matrix = (np.stack(codes) if codes
                                  else np.zeros((0, table.n_rows), np.int32))
        # one-slot box so IMCU shard plans share (and co-invalidate) the
        # fused super-table with their parent, like `plans` and `stats`
        self._fused_box: dict[str, adv_ops.FusedTables | None] = {"t": None}

    def _compile_column(self, column: str, aug: AugmentedDictionary,
                        names: list[str],
                        count_put: bool = True) -> ColumnPlan:
        fused_host = aug.fused_table(names)
        if count_put:
            self.stats["tables_put"] += 1
        return ColumnPlan(column=column, adv_names=names,
                          fused_host=fused_host,
                          fused_table=jnp.asarray(fused_host),
                          bits=aug.dictionary.bits, aug_version=aug.version)

    # -- shape info -------------------------------------------------------------
    @property
    def columns(self) -> list[str]:
        return [p.column for p in self.plans]

    @property
    def codes_matrix(self) -> np.ndarray:
        if self.packed:
            raise RuntimeError(
                "packed plan never materializes the int32 code matrix — "
                "use packed_words / host_codes()")
        return self._codes_matrix

    @property
    def n_rows(self) -> int:
        return self._n_rows if self.packed else int(self._codes_matrix.shape[1])

    @property
    def out_dim(self) -> int:
        return sum(p.out_dim for p in self.plans)

    # -- host-side code access ---------------------------------------------------
    def host_codes(self, rows: np.ndarray) -> np.ndarray:
        """(C, len(rows)) int32 codes for arbitrary rows.

        int32 plans: one fancy-index on the stacked matrix. Packed plans:
        per-column word gather — touches O(len(rows)) uint32 words and never
        unpacks the stream (the only int32 ever built is the batch itself,
        for consumers that need arbitrary-row access: recompute baselines
        and non-range service requests).
        """
        if not self.packed:
            return self._codes_matrix[:, rows]
        rows = np.asarray(rows)
        out = np.empty((len(self.plans), rows.shape[0]), np.int32)
        for i, (w, db) in enumerate(zip(self.packed_words, self.device_bits)):
            out[i] = packed_gather(w, db, rows)
        return out

    def host_features(self, rows: np.ndarray) -> np.ndarray:
        """(len(rows), F) features computed ENTIRELY on the host — the
        degraded-mode slow path for rows whose resident device stream is
        gone (device loss before the emergency rebuild lands). Gathers
        codes from the host packed words and indexes the host fused ADV
        tables with the same OOB clamp as the device paths
        (``mode="clip"``), so results stay bit-exact with a device launch
        over the same plan state."""
        rows = np.asarray(rows)
        if not self.plans:
            return np.zeros((rows.shape[0], 0), np.float32)
        codes = self.host_codes(rows)
        outs = [p.fused_host[np.clip(codes[i], 0,
                                     p.fused_host.shape[0] - 1)]
                for i, p in enumerate(self.plans)]
        return np.concatenate(outs, axis=-1)

    # -- fused multi-table layout (one-kernel-pass path) -------------------------
    def fused_tables(self) -> adv_ops.FusedTables:
        """Block-diagonal super-table for the fused gather-concat kernel."""
        if self._fused_box["t"] is None:
            self._fused_box["t"] = adv_ops.fuse_tables(
                [p.fused_host for p in self.plans])
            self.stats["fused_rebuilds"] += 1
        return self._fused_box["t"]

    # -- maintenance (§6.3: streaming inserts) -----------------------------------
    def refresh(self, new_codes: Mapping[str, np.ndarray] | None = None) -> int:
        """Incremental plan refresh after ``Dictionary.add_rows``.

        Re-derives ADVs for grown dictionaries (``extend_for_new_codes``) and
        re-puts device tables ONLY for columns whose AugmentedDictionary
        changed since compile — untouched columns keep their resident tables.
        ``new_codes`` optionally appends freshly inserted rows (codes from
        ``add_rows``) to the plan's code layout; it must cover every planned
        column with equal lengths. Packed plans repack a column's word
        stream only when its dictionary grew across a tpu_width boundary,
        and append new rows by rewriting at most one partial tail word.
        Returns the number of columns refreshed.
        """
        fresh = None
        if new_codes is not None:          # validate BEFORE mutating anything
            missing = [c for c in self.columns if c not in new_codes]
            if missing:
                raise KeyError(f"new_codes missing columns {missing}")
            fresh = np.stack([np.asarray(new_codes[c], np.int32).reshape(-1)
                              for c in self.columns])
        refreshed = 0
        for i, p in enumerate(self.plans):
            aug = self.augmented[p.column]
            aug.extend_for_new_codes()
            if aug.version == p.aug_version:
                continue
            self.plans[i] = self._compile_column(p.column, aug, p.adv_names,
                                                 count_put=False)
            self.stats["tables_refreshed"] += 1
            refreshed += 1
        if refreshed:
            self._fused_box["t"] = None    # all shard views rebuild lazily
        if self.packed:
            for i, p in enumerate(self.plans):
                db = tpu_width(p.bits)
                if db != self.device_bits[i]:   # grew across a width boundary
                    codes = unpack_bits(self.packed_words[i],
                                        self.device_bits[i], self._n_rows)
                    self.packed_words[i] = pack_bits(codes, db)
                    self.device_bits[i] = db
                    self.packed_versions[i] += 1
                    self.packed_layout_versions[i] += 1
                    self.stats["words_repacked"] += 1
            if fresh is not None:
                for i in range(len(self.plans)):
                    self._append_packed(i, fresh[i])
                self._n_rows += fresh.shape[1]
        elif fresh is not None:
            self._codes_matrix = np.concatenate(
                [self._codes_matrix, fresh], axis=1)
        return refreshed

    def _append_packed(self, i: int, codes: np.ndarray) -> None:
        """Append rows to column i's word stream, rewriting at most the one
        partial tail word (fields at divisor widths never straddle words)."""
        db = self.device_bits[i]
        s = 32 // db
        words = self.packed_words[i]
        tail = self._n_rows % s
        if tail:
            codes = np.concatenate([unpack_bits(words[-1:], db, tail), codes])
            words = words[:-1]
        self.packed_words[i] = np.concatenate([words, pack_bits(codes, db)])
        self.packed_versions[i] += 1

    # -- partitioning (per-IMCU shard plans) --------------------------------------
    def imcu_shards(self) -> list["FeaturePlan"]:
        """One plan per IMCU partition, sharing this plan's device tables.

        int32 plans: shard k's code matrix is a zero-copy view into this
        plan's already materialized matrix, windowed to the IMCU's row
        range. Packed plans: shard k carries its own per-column word-stream
        slice (:class:`_PackedShardPlan`) — zero-copy when the IMCU boundary
        is word-aligned at the column's device width, repacked once per
        refresh generation only at unaligned seams — so a sharded executor
        can keep each slice resident on its own mesh device. The LAST shard
        is open-ended: rows appended by :meth:`refresh` extend it. Either
        way device-resident ADV tables (and the fused super-table) are
        shared and co-invalidated, not re-put, and every shard gets its own
        stats dict whose counts roll up into this plan's totals
        (``stats['per_shard']`` indexes them).
        """
        bounds = self.imcu_bounds()
        shard_stats = [
            _ShardStats(self.stats, {k: 0 for k, v in self.stats.items()
                                     if isinstance(v, (int, float))})
            for _ in bounds]
        self.stats["per_shard"] = shard_stats
        if self.packed:
            return [_PackedShardPlan(self, start, stop, st,
                                     last=(i == len(bounds) - 1))
                    for i, ((start, stop), st) in
                    enumerate(zip(bounds, shard_stats))]
        shards = []
        for (start, stop), st in zip(bounds, shard_stats):
            shard = FeaturePlan.__new__(FeaturePlan)
            shard.table = self.table
            shard.features = self.features
            shard.augmented = self.augmented
            shard.packed = False
            shard.stats = st                       # rolls up into self.stats
            shard.plans = self.plans               # shared device tables
            shard._codes_matrix = self._codes_matrix[:, start:stop]
            shard._fused_box = self._fused_box      # shared, co-invalidated
            shards.append(shard)
        return shards

    def imcu_bounds(self) -> list[tuple[int, int]]:
        if not self.plans:
            raise ValueError("plan has no feature columns to partition")
        return self.table[self.plans[0].column].imcu_bounds()

    # -- adaptive re-shard (tail split under streaming growth) --------------------
    def split_tail_shard(self, tail: "_PackedShardPlan", cut: int,
                         close: bool = True) -> "_PackedShardPlan":
        """Split the open tail shard at parent row ``cut``; return the NEW
        open tail shard covering [cut, n_rows).

        The answer to unbounded streaming growth: appends extend the LAST
        shard only, so once it outgrows its row budget the tail is split —
        the new shard's stream slice is zero-copy when ``cut`` is
        word-aligned at a column's device width (``cut % 32 == 0`` aligns
        at EVERY width) and seam-repacked otherwise, exactly like compile-
        time IMCU boundaries. The new shard gets a fresh rolled-up stats
        dict APPENDED to ``stats['per_shard']`` (existing shard indices —
        and their accumulated deltas — never move: continuity across
        shard-set changes). ``close=False`` leaves the old tail open so a
        caller can swap its routing table first and close after
        (:meth:`_PackedShardPlan.close_at`); until then both views serve
        [cut, n_rows) bit-identically from the same parent bytes.
        """
        if not self.packed:
            raise RuntimeError("tail re-shard applies to packed plans only")
        if not isinstance(tail, _PackedShardPlan) or tail._parent is not self:
            raise ValueError("tail is not a shard view of this plan")
        if not tail._last:
            raise ValueError("only the open tail shard can split")
        start, stop = tail.shard_bounds
        if not start < cut <= stop:
            raise ValueError(f"cut {cut} outside open tail ({start}, {stop}]")
        st = _ShardStats(self.stats,
                         {k: 0 for k, v in self.stats.items()
                          if isinstance(v, (int, float))})
        new = _PackedShardPlan(self, cut, stop, st, last=True)
        self.stats.setdefault("per_shard", []).append(st)
        if close:
            tail.close_at(cut)
        return new

    # -- data-movement accounting (paper's central claim) --------------------------
    def bytes_moved_adv(self, batch_rows: int) -> int:
        """Host->device bytes per batch on the ADV path, for THIS plan's
        layout: device-width packed words (``packed=True``) vs 4-byte int32
        codes. The K-row fused tables are resident either way (moved once,
        amortized across all batches, the paper's 'dictionary created once
        ... easily amortized') — and a packed executor additionally keeps
        the word streams device-resident, so range serving amortizes even
        the code traffic to ~0.
        """
        if self.packed:
            return sum(packed_nbytes(batch_rows, db)
                       for db in self.device_bits)
        return 4 * batch_rows * len(self.plans)

    def bytes_moved_recompute(self, batch_rows: int) -> int:
        """Traditional path ships row-space f32 features."""
        return 4 * batch_rows * self.out_dim

    def bytes_resident_tables(self) -> int:
        return sum(int(p.fused_table.size) * 4 for p in self.plans)

    def bytes_resident_codes(self) -> int:
        """Host bytes held by the code layout (the duplication the packed
        path avoids: 32/db x smaller than the int32 matrix)."""
        if self.packed:
            return sum(int(w.nbytes) for w in self.packed_words)
        return int(self._codes_matrix.nbytes)


class _PackedShardPlan(FeaturePlan):
    """One IMCU partition of a packed plan: a per-column word-stream slice.

    Shares the parent's AugmentedDictionaries, device-resident ADV tables
    and fused super-table box (co-invalidated on refresh); what is
    partitioned is exactly the resident word streams. A shard's slice of
    column i is zero-copy when the partition boundary is word-aligned at
    the column's device width (``start % (32/db) == 0`` — always true for
    the default 2**19-row IMCUs); an unaligned seam repacks JUST this
    shard's rows, once per parent refresh generation (cached against
    ``packed_versions[i]``). ``last=True`` marks the open-ended tail shard:
    rows appended by the PARENT's :meth:`FeaturePlan.refresh` extend it, so
    a sharded service keeps serving streaming inserts without resharding.
    Refresh always goes through the parent — the word streams, dictionaries
    and versions live there.
    """

    def __init__(self, parent: FeaturePlan, start: int, stop: int,
                 stats: _ShardStats, last: bool = False):
        # deliberately NOT calling FeaturePlan.__init__: every layout
        # artifact is derived from the parent
        self._parent = parent
        self._start = start
        self._stop = stop
        self._last = last
        self.packed = True
        self.table = parent.table
        self.features = parent.features
        self.augmented = parent.augmented
        self.plans = parent.plans               # shared device tables
        self._fused_box = parent._fused_box     # shared, co-invalidated
        self.stats = stats                      # rolls up into parent totals
        self._words_cache: dict[int, tuple[int, np.ndarray]] = {}
        # cold residency tier: col -> (rle values, run lengths, cum ends).
        # Non-None means this shard holds NO packed copy of its own — host
        # reads decode the runs directly (see host_codes override)
        self._rle: dict[int, tuple[np.ndarray, np.ndarray,
                                   np.ndarray]] | None = None

    @property
    def shard_bounds(self) -> tuple[int, int]:
        """[start, stop) in parent rows (stop tracks appends when last)."""
        stop = self._parent.n_rows if self._last else self._stop
        return self._start, max(stop, self._start)

    @property
    def _n_rows(self) -> int:                   # FeaturePlan.n_rows reads this
        start, stop = self.shard_bounds
        return stop - start

    @property
    def device_bits(self) -> list[int]:
        return self._parent.device_bits

    @property
    def packed_versions(self) -> list[int]:
        # executors key their resident-stream sync on these, so a parent
        # refresh transparently re-puts the shard views it actually moved:
        # a width-boundary repack changes every shard's slice (layout
        # version), but a streaming APPEND only rewrites the open-ended
        # tail — interior shards' bytes are untouched, so they keep their
        # resident streams (no n_shards x full re-put per insert)
        if self._last:
            return self._parent.packed_versions
        return self._parent.packed_layout_versions

    @property
    def packed_words(self) -> list[np.ndarray]:
        return [self._shard_words(i) for i in range(len(self.plans))]

    def _shard_words(self, i: int) -> np.ndarray:
        if self._rle is not None:
            # cold shard: no packed copy is retained — rebuild column i's
            # words from its runs at the CURRENT device width (codes never
            # change for existing rows, so runs survive width repacks).
            # Deliberately uncached: rehydrate() is the bulk warm-up path
            values, lengths, _ = self._rle[i]
            return pack_bits(rle_decode(values, lengths),
                             self._parent.device_bits[i])
        parent = self._parent
        version = self.packed_versions[i]
        hit = self._words_cache.get(i)
        if hit is not None and hit[0] == version:
            return hit[1]
        db = parent.device_bits[i]
        s = 32 // db
        start, stop = self.shard_bounds
        if start % s == 0:                      # word-aligned boundary
            words = parent.packed_words[i][start // s:(stop + s - 1) // s]
        else:                                   # seam: repack this shard only
            codes = packed_gather(parent.packed_words[i], db,
                                  np.arange(start, stop))
            words = pack_bits(codes, db)
            self.stats["words_repacked"] += 1
        self._words_cache[i] = (version, words)
        return words

    def refresh(self, new_codes=None) -> int:
        raise RuntimeError("shard plans are views — refresh the parent "
                           "FeaturePlan; every shard re-syncs automatically")

    # -- residency ladder: cold tier (RLE runs, no packed copy) ------------------
    @property
    def is_cold(self) -> bool:
        return self._rle is not None

    def demote_cold(self) -> int:
        """Demote this CLOSED shard to the cold tier: encode every column's
        codes as RLE runs and drop the host packed slice — the shard's only
        storage becomes the runs (plus zero-copy parent views it can always
        re-derive from). Returns the run bytes held. Correctness rests on
        codes being immutable for existing rows (dictionaries only grow):
        the runs stay valid across any later width repack, and rehydration
        simply packs them at the then-current device width. The open tail
        is refused — appends extend it and would stale the runs."""
        if self._last:
            raise ValueError("the open tail shard cannot go cold: streaming "
                             "appends extend it and would stale the runs")
        if self._rle is not None:
            return self.rle_bytes()
        runs = {}
        for i in range(len(self.plans)):
            codes = unpack_bits(self._shard_words(i),
                                self._parent.device_bits[i], self._n_rows)
            values, lengths = rle_encode(codes)
            runs[i] = (values, lengths, np.cumsum(lengths))
        self._rle = runs
        self._words_cache.clear()               # the packed copy is dropped
        self.stats["rle_encoded"] += 1
        return self.rle_bytes()

    def rehydrate(self) -> None:
        """Promote out of the cold tier: decode every column's runs and
        repack at the CURRENT device width, priming the slice cache so the
        executor's next version-keyed re-put finds host words ready."""
        if self._rle is None:
            return
        for i in range(len(self.plans)):
            values, lengths, _ = self._rle[i]
            words = pack_bits(rle_decode(values, lengths),
                              self._parent.device_bits[i])
            self._words_cache[i] = (self.packed_versions[i], words)
        self._rle = None
        self.stats["rehydrated"] += 1

    def rle_bytes(self) -> int:
        """Host bytes held by the cold runs (0 when not cold)."""
        if self._rle is None:
            return 0
        return sum(rle_nbytes(v, l, self._parent.device_bits[i])
                   for i, (v, l, _) in self._rle.items())

    def host_codes(self, rows: np.ndarray) -> np.ndarray:
        """Cold shards gather codes straight from the runs — one
        searchsorted per column against the cumulative run ends, never
        materializing a packed or decoded stream. Warm/hot shards use the
        inherited packed-word gather."""
        if self._rle is None:
            return super().host_codes(rows)
        rows = np.asarray(rows)
        out = np.empty((len(self.plans), rows.shape[0]), np.int32)
        for i, (values, lengths, ends) in self._rle.items():
            run = np.searchsorted(ends, rows, side="right")
            out[i] = values[np.minimum(run, values.size - 1)]
        return out

    def close_at(self, cut: int) -> None:
        """Close this open tail shard at parent row ``cut`` (it becomes an
        interior shard bounded by [start, cut)). Internal half of
        :meth:`FeaturePlan.split_tail_shard` — callers that swapped routing
        first may close last, so readers never see rows go unowned. The
        slice cache must drop: the version SOURCE switches from full packed
        versions to layout versions on close, and a numerically equal
        version must not revive a slice with the old open-ended bounds."""
        if not self._last:
            raise ValueError("only the open tail shard can close")
        start, stop = self.shard_bounds
        if not start < cut <= stop:
            raise ValueError(f"cut {cut} outside open tail ({start}, {stop}]")
        self._stop = cut
        self._last = False
        self._words_cache.clear()


class _DeviceTableCache:
    """Per-DEVICE cache of placed ADV tables (plain + fused).

    Shard executors that share a device (more IMCU shards than mesh
    devices) share one of these, so the replicated tables exist once per
    device — never once per shard — and ``tables_put`` counts real
    transfers."""

    def __init__(self):
        self.tables: tuple | None = None
        self.tables_key: tuple | None = None
        self.fused_src = None
        self.fused = None


# process-unique launch-stream identity (see FeatureExecutor.stream_token):
# unlike id(executor), a token is never reused after an executor is dropped,
# so health state keyed on it can never alias onto a NEW stream
_STREAM_TOKENS = itertools.count()


class FeatureExecutor:
    """Run-time half: jit'd stacked gather + double-buffered batch iterator.

    ADV tables enter the jit'd gathers as *arguments*, not trace-time
    constants, so a :meth:`FeaturePlan.refresh` flows into already-compiled
    batch shapes automatically (only a table *shape* change retraces).

    Packed plans additionally keep the word streams device-resident
    (re-put incrementally when a refresh bumps a column's version) and serve
    word-aligned ranges via :meth:`batch_range` with zero per-batch
    host->device code traffic — and ARBITRARY rows via the jit-cached
    indexed gather (:meth:`_rows_future`,
    compiled once per static batch shape like the range path): the device
    computes word index + bit offset against its resident streams, so the
    only per-call traffic is the 4B x N index vector, independent of column
    count. ``autotune=True`` sweeps the fused packed kernel's (bn, bk, bw)
    block shapes once per workload shape, and the int32 fused kernel's
    (bn, bk) likewise (:func:`adv_ops.autotune_fused`).
    """

    def __init__(self, plan: FeaturePlan, use_kernel: bool = False,
                 prefetch: int = 2, autotune: bool = False, device=None,
                 table_cache: _DeviceTableCache | None = None,
                 commit: bool = True):
        if prefetch < 1:
            raise ValueError("prefetch depth must be >= 1")
        self.plan = plan
        self.use_kernel = use_kernel
        self.prefetch = prefetch
        self.autotune = autotune
        self.packed = plan.packed
        # mesh placement: device=None serves from the process default (the
        # original single-device behavior); a concrete device COMMITS every
        # resident operand (word stream, per-plan tables, fused super-table)
        # there, so launches against this executor run on that device — the
        # sharded-serving building block (one executor per IMCU shard).
        # ``table_cache`` lets executors sharing a device share the placed
        # table copies (ShardedFeatureExecutor passes one per device).
        self.device = device
        # stable launch-stream identity for per-stream health state
        # (breakers): survives as a dict key where id(self) would be
        # recycled by the allocator after a drop_replica/evict
        self.stream_token = next(_STREAM_TOKENS)
        self._tcache = table_cache if table_cache is not None \
            else _DeviceTableCache()
        self._jit_take = jax.jit(self._take_impl)
        self._jit_fused = jax.jit(self._fused_impl,
                                  static_argnames=("out_dim", "bn", "bk"))
        self._fused_blocks_cache: dict[int, tuple[int, int]] = {}
        # compiled-predicate cache: a deployed filter family scans on every
        # request, so the code-set compile + the device put of the packed
        # term arrays must not repeat per call (keyed also by dictionary
        # cardinalities — appends that grow a dictionary can change what a
        # value predicate matches). Unconditional: int32 plans still reach
        # _compiled_pred to raise the packed-plan guard.
        self._pred_cache: dict = {}
        if self.kernel_active:
            plan.fused_tables()        # build eagerly, not inside the jit trace
        if use_kernel and self.packed and (
                device or jax.devices()[0]).platform == "tpu":
            # refuse before any word stream takes HBM
            self._require_compiled_kernels(device or jax.devices()[0])
        if self.packed:
            # ONE flat device-resident stream holds every column's words
            # (column c's start at _word_offs[c]); range windows are
            # dynamic_slices into it and the random-row kernels gather from
            # it directly — no per-column duplicate buffers
            self._flat_words: jnp.ndarray | None = None
            self._word_offs: tuple[int, ...] = ()
            self._words_sig: tuple | None = None
            self._capacity = 0
            self._blocks: dict[int, tuple[int, int, int]] = {}
            self._rows_blocks_cache: dict[int, tuple[int, int]] = {}
            # commit=False defers the word-stream device put (tiered
            # residency: a warm shard's executor exists but holds no HBM
            # until promotion calls ensure_range_capacity — any direct
            # launch still self-commits through the same call)
            if commit:
                self.ensure_range_capacity(plan.n_rows)

    def _require_compiled_kernels(self, device) -> None:
        """Compile, for ``device`` and this plan's widths, every Pallas
        kernel a ``use_kernel=True`` packed executor launches; raise
        :class:`repro.kernels.KernelRefused` naming each one the compiler
        refuses, with its reason. Nothing runs and nothing is placed."""
        plan = self.plan
        n = plan.n_rows
        dbs = tuple(plan.device_bits)
        cap = _pad32(n)
        offs = tuple(itertools.accumulate(
            (cap * db // 32 for db in dbs[:-1]), initial=0))
        on_dev = jax.sharding.SingleDeviceSharding(device)

        def shape(shp, dtype):
            return jax.ShapeDtypeStruct(shp, dtype, sharding=on_dev)

        words = shape((sum(cap * db // 32 for db in dbs),), jnp.uint32)
        k0 = plan.plans[0].cardinality
        probes = {
            "predicate_scan": (lambda w: scan_ops.predicate_scan(
                w, offs, dbs, (scan_ops.ScanTerm(0, 0, 0, 0),
                               scan_ops.ScanTerm(0, 1, lut=np.ones(k0))),
                n, interpret=False), words),
            "masked_hist": (lambda w, m: scan_ops.masked_counts(
                w, 0, dbs[0], m, k0, n, use_kernel=True, interpret=False),
                words, shape((n,), jnp.bool_)),
        }
        if self.kernel_active:
            f = plan.fused_tables()
            probes["adv_gather_packed_rows"] = (
                lambda w, t, ro, cl, r: adv_ops.adv_gather_packed_rows(
                    w, offs, dbs, t, ro, cl, r, f.out_dim, bn=f.bn, bk=f.bk,
                    interpret=False),
                words, shape(f.table.shape, f.table.dtype),
                shape(f.row_offsets.shape, jnp.int32),
                shape(f.card_limits.shape, jnp.int32),
                shape((f.bn,), jnp.int32))
        refused = {name: why for name, (fn, *args) in probes.items()
                   if (why := kernels.compile_refusal(fn, *args))}
        if refused:
            raise kernels.KernelRefused(refused)

    @property
    def kernel_active(self) -> bool:
        """Fused one-hot kernel path, guarded like the single-table op: huge-K
        plans fall back to the XLA gather (one-hot tiling is wasteful there),
        and BOTH fused kernels (packed and int32) respect the ΣK×ΣF VMEM
        budget (past it the gathers split into unfused per-table takes)."""
        if not self.use_kernel:
            return False
        return adv_ops.fused_kernel_fits(
            [p.cardinality for p in self.plan.plans],
            [p.out_dim for p in self.plan.plans])

    def _take_impl(self, codes: jnp.ndarray, tables) -> jnp.ndarray:
        # mode="clip" matches the fused kernel's OOB clamp (jax's default
        # would NaN-fill, and the two paths must agree)
        outs = [jnp.take(t, codes[i], axis=0, mode="clip")
                for i, t in enumerate(tables)]
        return jnp.concatenate(outs, axis=-1)

    def _fused_impl(self, codes: jnp.ndarray, table: jnp.ndarray,
                    row_offsets: jnp.ndarray, card_limits: jnp.ndarray,
                    out_dim: int, bn: int, bk: int) -> jnp.ndarray:
        # fused multi-table Pallas kernel: ONE pass over the code matrix
        return adv_ops.gather_fused_parts(table, row_offsets, codes, out_dim,
                                          card_limits=card_limits,
                                          bn=bn, bk=bk)

    def _device_tables(self) -> tuple:
        """Per-plan fused tables as launch arguments, on this executor's
        device. device=None passes the plan's own resident tables through
        (shared across executors, refresh flows as jit arguments); a
        committed executor keeps its own device copies, re-put only when a
        column's AugmentedDictionary version moves."""
        if self.device is None:
            return tuple(p.fused_table for p in self.plan.plans)
        key = tuple(p.aug_version for p in self.plan.plans)
        if self._tcache.tables_key != key:
            self._tcache.tables = tuple(
                jax.device_put(p.fused_host, self.device)
                for p in self.plan.plans)
            self._tcache.tables_key = key
            self.plan.stats["tables_put"] += len(self.plan.plans)
        return self._tcache.tables

    def _device_fused(self) -> adv_ops.FusedTables:
        """The shared block-diagonal super-table, committed to this
        executor's device (replicated per shard; the word streams are what
        stays partitioned). Re-placed only when a refresh rebuilds it."""
        fused = self.plan.fused_tables()
        if self.device is None:
            return fused
        if self._tcache.fused_src is not fused:
            self._tcache.fused = adv_ops.place_fused(fused, self.device)
            self._tcache.fused_src = fused
        return self._tcache.fused

    def _fused_blocks(self, batch: int) -> tuple[int, int]:
        """(bn, bk) for the int32 fused kernel — swept per batch shape when
        ``autotune=True`` (the packed path's sweep, ported), else the
        fuse-time defaults."""
        blocks = self._fused_blocks_cache.get(batch)
        if blocks is None:
            fused = self._device_fused()
            if self.autotune:
                probe = jnp.zeros((len(self.plan.plans), batch), jnp.int32)
                blocks = adv_ops.autotune_fused(probe, fused, batch)
            else:
                blocks = (fused.bn, fused.bk)
            self._fused_blocks_cache[batch] = blocks
        return blocks

    def gather_device(self, dev_codes: jnp.ndarray) -> jnp.ndarray:
        """(C, B) stacked device codes -> (B, out_dim) concatenated features."""
        if self.kernel_active:
            fused = self._device_fused()
            bn, bk = self._fused_blocks(int(dev_codes.shape[1]))
            return self._jit_fused(dev_codes, fused.table, fused.row_offsets,
                                   fused.card_limits, out_dim=fused.out_dim,
                                   bn=bn, bk=bk)
        return self._jit_take(dev_codes, self._device_tables())

    # -- packed fast path: device-resident words, range batches -------------------
    def ensure_range_capacity(self, limit: int) -> None:
        """Grow the device word stream to cover rows [0, pad32(limit)).

        Padding words are zeros -> code 0 (a valid row of every table); any
        features gathered past the real row count are sliced off by callers.
        """
        if not self.packed:
            raise RuntimeError("range capacity applies to packed plans only")
        self._capacity = max(self._capacity, _pad32(limit))
        self._sync_device_words()

    def _sync_device_words(self) -> None:
        """Re-put the flat resident stream when any column's words moved.

        One concatenated buffer replaces per-column arrays, so a refresh
        that touches any column re-puts the whole stream — word streams are
        32/db x smaller than the codes they encode, so one put stays cheap,
        and holding a single copy (instead of flat + per-column duplicates)
        keeps device residency at exactly Σ stream bytes.
        """
        plan = self.plan
        sig = (tuple(plan.packed_versions), tuple(plan.device_bits),
               self._capacity)
        if self._words_sig == sig:
            return
        parts, offs, off = [], [], 0
        for i in range(len(plan.plans)):
            need = self._capacity * plan.device_bits[i] // 32
            w = plan.packed_words[i]
            if w.shape[0] < need:
                w = np.concatenate([w, np.zeros(need - w.shape[0],
                                                np.uint32)])
            else:
                w = w[:need]
            parts.append(w)
            offs.append(off)
            off += need
        flat = (np.concatenate(parts) if parts
                else np.zeros(0, np.uint32))
        self._flat_words = jax.device_put(np.ascontiguousarray(flat),
                                          self.device)
        self._word_offs = tuple(offs)
        self._words_sig = sig
        plan.stats["words_put"] += 1

    # -- tiered residency: per-stream HBM accounting ------------------------------
    def resident_bytes(self) -> int:
        """Device bytes currently held by this stream's resident words."""
        if not self.packed or self._flat_words is None:
            return 0
        return int(self._flat_words.size) * 4

    def stream_nbytes(self) -> int:
        """Projected device bytes of a FULL commit at the current capacity
        (what a promotion would charge) — defined whether or not the words
        are resident right now."""
        if not self.packed:
            return 0
        plan = self.plan
        cap = max(self._capacity, _pad32(plan.n_rows))
        return sum(cap * db // 32 * 4 for db in plan.device_bits)

    def evict_words(self) -> int:
        """Release the resident word stream (demotion to a host tier);
        returns the bytes freed. The device buffer is dereferenced, NOT
        deleted: an in-flight launch may still hold it, and refcounting
        frees it the moment the last launch retires. Any later launch (or
        an explicit promotion) re-puts through the version-keyed sync."""
        freed = self.resident_bytes()
        self._flat_words = None
        self._words_sig = None
        return freed

    def _kernel_blocks(self, batch: int) -> tuple[int, int, int]:
        """(bn, bk, bw) for the fused packed RANGE kernel — autotuned per
        batch shape on first use when requested, else fuse-time defaults."""
        blocks = self._blocks.get(batch)
        if blocks is None:
            fused = self._device_fused()
            if self.autotune:
                dbs = tuple(self.plan.device_bits)
                wins, flat = [], self._flat_words
                for off, db in zip(self._word_offs, dbs):
                    wins.append(flat[off:off + batch * db // 32])
                blocks = adv_ops.autotune_packed(wins, dbs, fused, batch)
            else:
                blocks = (fused.bn, fused.bk, 512)
            self._blocks[batch] = blocks
        return blocks

    def _rows_kernel_blocks(self, n: int) -> tuple[int, int]:
        """(bn, bk) for the fused random-row kernel — swept on the rows
        kernel ITSELF (its gather cost profile differs from the range
        kernel's) when ``autotune=True``, else fuse-time defaults."""
        blocks = self._rows_blocks_cache.get(n)
        if blocks is None:
            fused = self._device_fused()
            if self.autotune:
                blocks = adv_ops.autotune_packed_rows(
                    self._flat_words, self._word_offs,
                    tuple(self.plan.device_bits), fused, n)
            else:
                blocks = (fused.bn, fused.bk)
            self._rows_blocks_cache[n] = blocks
        return blocks

    def _range_future(self, start: int, batch: int) -> jnp.ndarray:
        """Async gather of rows [start, start+batch) from resident words.

        Per-batch host->device traffic: ONE scalar (the start index).
        Returns the full (batch, out_dim) device buffer; callers slice the
        valid prefix when retiring.
        """
        if start % 32 or batch % 32:
            raise ValueError("packed ranges must be word-aligned "
                             f"(start % 32 == 0, batch % 32 == 0); got "
                             f"[{start}, {start + batch})")
        self.ensure_range_capacity(max(start + batch, self.plan.n_rows))
        dbs = tuple(self.plan.device_bits)
        if self.kernel_active:
            fused = self._device_fused()
            bn, bk, bw = self._kernel_blocks(batch)
            return _packed_fused_range(
                self._flat_words, fused.table, fused.row_offsets,
                fused.card_limits, start, dbs=dbs, offs=self._word_offs,
                batch=batch, out_dim=fused.out_dim, bn=bn, bk=bk, bw=bw)
        return _packed_split_range(
            self._flat_words, self._device_tables(),
            start, dbs=dbs, offs=self._word_offs, batch=batch)

    def _multi_range_future(self, starts, batch: int) -> jnp.ndarray:
        """Async gather of K coalesced ranges -> (K, batch, out_dim) buffer.

        ONE device launch serves all K ranges; the only host->device traffic
        is the (K,) start-index vector. This is what lets a serving pump
        amortize launch overhead across many small queued requests.
        """
        starts = np.asarray(starts, np.int64).reshape(-1)
        if starts.size == 0:
            raise ValueError("need at least one range start")
        if batch % 32 or (starts % 32).any():
            raise ValueError("packed ranges must be word-aligned "
                             "(starts % 32 == 0, batch % 32 == 0)")
        self.ensure_range_capacity(max(int(starts.max()) + batch,
                                       self.plan.n_rows))
        sv = jnp.asarray(starts, jnp.int32)
        dbs = tuple(self.plan.device_bits)
        if self.kernel_active:
            fused = self._device_fused()
            bn, bk, bw = self._kernel_blocks(batch)
            return _packed_fused_multi(
                self._flat_words, fused.table, fused.row_offsets,
                fused.card_limits, sv, dbs=dbs, offs=self._word_offs,
                batch=batch, out_dim=fused.out_dim, bn=bn, bk=bk, bw=bw)
        return _packed_split_multi(
            self._flat_words, self._device_tables(),
            sv, dbs=dbs, offs=self._word_offs, batch=batch)

    def batch_range(self, start: int, n: int) -> jnp.ndarray:
        """Featurize the contiguous rows [start, start+n) (start % 32 == 0)
        without any host code work: unpack happens inside the gather."""
        return self._range_future(start, _pad32(n))[:n]

    # -- packed random-row path: indices in, features out -------------------------
    def _rows_future(self, rows) -> jnp.ndarray:
        """Async indexed gather of arbitrary rows from the resident words.

        Per-call host->device traffic: the (N,) int32 index vector — 4B per
        row, independent of column count. One compiled shape per index
        length (callers pad to static bucket shapes, the range path's
        compiled-shape discipline). The serving pump's unified launch:
        K coalesced bucket-padded row sets arrive here flattened.
        """
        if not self.packed:
            raise RuntimeError("indexed row gather applies to packed plans "
                               "only; int32 plans ship code slices")
        # the stream must cover every live row: refresh() appends can push
        # n_rows past the capacity the stream was last put at, and an index
        # past the stream would silently clip into another column's words
        self.ensure_range_capacity(self.plan.n_rows)
        # np rows go straight into the jit: its argument transfer IS the
        # 4B x N host->device index shipment (a separate device_put would
        # just add one more dispatch on the serving hot path)
        dev_rows = rows if isinstance(rows, jnp.ndarray) \
            else np.ascontiguousarray(rows, dtype=np.int32)
        dbs = tuple(self.plan.device_bits)
        if self.kernel_active:
            fused = self._device_fused()
            bn, bk = self._rows_kernel_blocks(int(dev_rows.shape[0]))
            return _packed_fused_rows(
                self._flat_words, fused.table, fused.row_offsets,
                fused.card_limits, dev_rows, dbs=dbs,
                word_offs=self._word_offs, out_dim=fused.out_dim,
                bn=bn, bk=bk)
        return _packed_split_rows(
            self._flat_words, self._device_tables(),
            dev_rows, dbs=dbs, word_offs=self._word_offs)

    # -- predicate pushdown: scan -> compact -> gather on resident words ----------
    def _scan_terms(self, pred) -> tuple[tuple, str]:
        """Compile a value-space predicate to device scan terms: each leaf
        runs once over its column's K dictionary entries, and column names
        resolve to this plan's resident stream slots."""
        if not self.packed:
            raise RuntimeError("predicate pushdown runs on packed plans "
                               "only; int32 plans filter host-side")
        dicts = {c: self.plan.augmented[c].dictionary
                 for c in self.plan.columns}
        cp = colquery.compile_predicate(pred, dicts)
        slot = {c: i for i, c in enumerate(self.plan.columns)}
        terms = tuple(scan_ops.ScanTerm(col=slot[t.column], kind=t.kind,
                                        lo=t.lo, hi=t.hi, lut=t.lut)
                      for t in cp.terms)
        return terms, cp.combine

    def _compiled_pred(self, pred):
        """(terms, combine, packed device arrays) for a predicate, cached.

        Cache key includes every dictionary's cardinality: dictionaries
        only ever GROW (appends may add values), and a grown dictionary can
        change a value predicate's matching code set, so stale entries age
        out naturally the first request after such a refresh."""
        key = (pred, tuple(self.plan.augmented[c].dictionary.cardinality
                           for c in self.plan.columns))
        hit = self._pred_cache.get(key)
        if hit is None:
            terms, combine = self._scan_terms(pred)
            packed = scan_ops.pack_terms(terms,
                                         tuple(self.plan.device_bits))
            hit = self._pred_cache[key] = (terms, combine, packed)
        return hit

    def _mask_future(self, terms: tuple, combine: str,
                     packed=None) -> jnp.ndarray:
        """Async device scan: compiled terms -> (n_rows,) bool selection
        mask against the resident word streams. No decoded code stream
        exists anywhere — the scan unpacks in-register."""
        self.ensure_range_capacity(self.plan.n_rows)
        dbs = tuple(self.plan.device_bits)
        if self.use_kernel:
            return scan_ops.predicate_scan(
                self._flat_words, self._word_offs, dbs, terms,
                self.plan.n_rows, combine)
        return scan_ops.predicate_scan_split(
            self._flat_words, self._word_offs, dbs, terms,
            self.plan.n_rows, combine, packed=packed)

    def _mask_count_future(self, pred):
        """(mask, count) device futures from one scan launch (split path;
        the Pallas path adds an eager reduction)."""
        terms, combine, packed = self._compiled_pred(pred)
        if self.use_kernel:
            mask = self._mask_future(terms, combine)
            return mask, mask.sum()
        self.ensure_range_capacity(self.plan.n_rows)
        return scan_ops.predicate_scan_split_count(
            self._flat_words, self._word_offs,
            tuple(self.plan.device_bits), terms, self.plan.n_rows,
            combine, packed=packed)

    def _ranks_count_future(self, pred):
        """(ranks, count) device futures: the selection mask's running
        match count and its last entry, from one scan launch (split path;
        the Pallas path adds an eager cumsum)."""
        terms, combine, packed = self._compiled_pred(pred)
        if self.use_kernel:
            ranks = jnp.cumsum(self._mask_future(terms, combine)
                               .astype(jnp.int32))
            return ranks, ranks[-1]
        self.ensure_range_capacity(self.plan.n_rows)
        return scan_ops.predicate_scan_split_ranks(
            self._flat_words, self._word_offs,
            tuple(self.plan.device_bits), terms, self.plan.n_rows,
            combine, packed=packed)

    def predicate_mask(self, pred) -> jnp.ndarray:
        """(n_rows,) bool device mask for a value-space predicate."""
        terms, combine, packed = self._compiled_pred(pred)
        return self._mask_future(terms, combine, packed)

    def count_where(self, pred) -> int:
        """SELECT COUNT(*) WHERE pred — one device scan + reduction."""
        return int(self._mask_count_future(pred)[1])

    def where_ranks(self, pred) -> tuple[jnp.ndarray, int]:
        """(device running match count, match count) of ``pred``: one
        scan launch and one scalar sync. The ranks stay on the device and
        feed :meth:`where_lanes`, block by block."""
        ranks, cnt = self._ranks_count_future(pred)
        return ranks, int(cnt)

    @property
    def where_lane(self) -> int:
        """Matches one lane of a where launch covers: ``WHERE_LANE``, or
        the whole padded table when it is shorter."""
        return min(WHERE_LANE, _pad32(self.plan.n_rows))

    def where_lanes(self, ranks: tuple, offs) -> tuple[jnp.ndarray,
                                                       jnp.ndarray]:
        """One compaction launch and one gather launch of ``len(ranks)``
        lanes: lane i is the matches [offs[i], offs[i] + where_lane) of
        running match count ``ranks[i]``. Returns device (rows, features)
        of (lanes * where_lane) rows — one compiled shape per lane count,
        whatever the offsets and counts; the row indices go from the
        compaction to the gather without visiting the host."""
        rows = _where_compact(tuple(ranks), np.asarray(offs, np.int32),
                              lane=self.where_lane)
        return rows, self._rows_future(rows)

    def _where_rows(self, ranks: jnp.ndarray, cnt: int) -> np.ndarray:
        """The ``cnt`` matching rows of ``ranks`` on the host, compacted
        on device one ``where_lane`` block at a time."""
        lane = self.where_lane
        blocks = [scan_ops.compact_block(ranks, off, lane)
                  for off in range(0, cnt, lane)]
        return np.concatenate([np.asarray(b) for b in blocks])[:cnt] \
            .astype(np.int64)

    def filtered_rows(self, pred) -> np.ndarray:
        """Matching row indices (ascending int64), compacted on device."""
        ranks, cnt = self.where_ranks(pred)
        if cnt == 0:
            return np.zeros(0, np.int64)
        return self._where_rows(ranks, cnt)

    def _where_blocks(self, ranks: jnp.ndarray, cnt: int) -> list:
        """Dispatch one 1-lane :meth:`where_lanes` launch per block of
        ``cnt`` matches; their (rows, features) device futures."""
        return [self.where_lanes((ranks,), [off])
                for off in range(0, cnt, self.where_lane)]

    def batch_where(self, pred) -> tuple[np.ndarray, jnp.ndarray]:
        """Filtered featurization: scan -> compact -> indexed gather, all
        against the resident streams. Returns (rows, features) for the
        matching rows in ascending row order. The ONE host sync before the
        gathers is the match count; each block of matches compacts and
        gathers in one fixed-shape launch, so no count compiles anew."""
        ranks, cnt = self.where_ranks(pred)
        if cnt == 0:
            return (np.zeros(0, np.int64),
                    jnp.zeros((0, self.plan.out_dim), jnp.float32))
        blocks = self._where_blocks(ranks, cnt)
        rows = np.concatenate([np.asarray(r) for r, _ in blocks])[:cnt]
        feats = jnp.concatenate([f for _, f in blocks])[:cnt]
        return rows.astype(np.int64), feats

    def _masked_counts_from(self, column: str, mask: jnp.ndarray) -> jnp.ndarray:
        """Async (K,) per-code counts of ``column`` under a device mask."""
        try:
            ci = self.plan.columns.index(column)
        except ValueError:
            raise KeyError(f"column {column!r} not in plan "
                           f"({self.plan.columns})") from None
        d = self.plan.augmented[column].dictionary
        return scan_ops.masked_counts(
            self._flat_words, self._word_offs[ci],
            self.plan.device_bits[ci], mask, d.cardinality,
            self.plan.n_rows, use_kernel=self.use_kernel)

    def groupby_where(self, column: str, pred) -> tuple[np.ndarray, np.ndarray]:
        """GROUP BY column COUNT(*) WHERE pred — masked histogram over the
        resident words; returns (values, counts) like ``groupby_count``."""
        counts = self._masked_counts_from(column,
                                          self.predicate_mask(pred))
        d = self.plan.augmented[column].dictionary
        return d.values, np.asarray(counts).astype(np.int64)

    def agg_where(self, pred, column: str, agg: str = "count") -> float:
        """Masked count/sum/mean of ``column`` under ``pred`` — K-entry
        dictionary tail work on top of the device masked histogram."""
        with span("query.agg_where", column=column, agg=agg):
            counts = self._masked_counts_from(column,
                                              self.predicate_mask(pred))
            with span("query.fetch") as fetch:
                counts = np.asarray(counts)
                fetch.set_metadata(nbytes=counts.nbytes)
            d = self.plan.augmented[column].dictionary
            return _agg_from_counts(d, counts, agg)

    # -- single batch -------------------------------------------------------------
    def slice_codes(self, row_idx: np.ndarray) -> np.ndarray:
        """Host-side work for one batch: one fancy-index on the code matrix
        (int32 plans) or a per-column word gather (packed plans)."""
        return self.plan.host_codes(row_idx)

    def batch(self, row_idx: np.ndarray) -> jnp.ndarray:
        """Featurize the given rows. int32 plans ship the stacked code slice;
        packed plans ship ONLY the row indices — the device computes word
        index + bit offset against its resident streams (no host code
        materialization for any access pattern)."""
        if self.packed:
            rows = np.asarray(row_idx, np.int64).reshape(-1)
            n = rows.shape[0]
            if n == 0:                 # match the int32 path's empty gather
                return jnp.zeros((0, self.plan.out_dim), jnp.float32)
            if rows.min() < 0 or rows.max() >= self.plan.n_rows:
                # numpy fancy-indexing raised on the old host-gather path;
                # the device gather clips, which would silently read
                # ANOTHER column's words — keep the error contract
                raise IndexError(
                    f"row indices out of range [0, {self.plan.n_rows})")
            rows = pad_rows_edge(rows, _pad32(n))
            return self._rows_future(rows.astype(np.int32))[:n]
        return self.gather_device(jax.device_put(self.slice_codes(row_idx),
                                                 self.device))

    # -- double-buffered iteration --------------------------------------------------
    def batches(self, batch_size: int, seed: int = 0,
                epochs: int = 1) -> Iterator[tuple[np.ndarray, jnp.ndarray]]:
        """Shuffled minibatch iterator with ``prefetch``-deep async pipeline.

        Up to ``prefetch`` device gathers are kept in flight: the host slices
        and ``device_put``s the codes for batch i+1 (i+2, ...) while the
        device still works on batch i, so consumers that block on each result
        hide the host-side slicing and transfer latency.

        Packed plans shuffle at word-aligned BLOCK granularity (the order of
        contiguous ``batch_size``-row ranges is permuted, rows within a range
        stay contiguous) so batches slice on word boundaries and no int32
        codes are ever built; ``batch_size`` must be a multiple of 32.
        """
        rng = np.random.default_rng(seed)
        n = self.plan.n_rows

        if self.packed:
            if batch_size % 32:
                raise ValueError("packed plans need batch_size % 32 == 0 "
                                 f"(word-aligned ranges), got {batch_size}")
            # a per-epoch word-aligned jitter rotates which remainder rows
            # fall outside the epoch's blocks (mirroring the int32 path's
            # fresh permutation); only a sub-word tail (< 32 rows, when
            # n % 32 != 0) is never range-reachable
            leftover = (n % batch_size) // 32 * 32

            def ranges():
                for _ in range(epochs):
                    jitter = 32 * rng.integers(0, leftover // 32 + 1)
                    yield from rng.permutation(
                        np.arange(jitter, n - batch_size + 1, batch_size))

            inflight: deque[tuple[np.ndarray, jnp.ndarray]] = deque()
            for start in ranges():
                idx = np.arange(start, start + batch_size)
                inflight.append((idx, self._range_future(int(start),
                                                         batch_size)))
                if len(inflight) >= self.prefetch:
                    yield inflight.popleft()
            while inflight:
                yield inflight.popleft()
            return

        def indices():
            for _ in range(epochs):
                perm = rng.permutation(n)
                for start in range(0, n - batch_size + 1, batch_size):
                    yield perm[start:start + batch_size]

        inflight: deque[tuple[np.ndarray, jnp.ndarray]] = deque()
        for idx in indices():
            dev_codes = jax.device_put(self.slice_codes(idx), self.device)
            inflight.append((idx, self.gather_device(dev_codes)))
            if len(inflight) >= self.prefetch:
                yield inflight.popleft()
        while inflight:
            yield inflight.popleft()


class ShardedFeatureExecutor:
    """Mesh half of per-IMCU serving: one committed executor per shard.

    The plan is partitioned by :meth:`FeaturePlan.imcu_shards` and each
    shard's resident word stream is placed on its own mesh device
    (:func:`repro.distributed.sharding.serve_devices` round-robins shards
    over the serve mesh when devices outnumber or undernumber shards) —
    'move compute to the data': a featurization launch for rows owned by
    shard k runs on shard k's device against shard-local operands only.
    ADV tables (K-row, amortized) are replicated per device; the word
    streams — the part that scales with table rows — are what stays
    partitioned, so aggregate resident bytes stay at Σ stream bytes.

    :meth:`batch` is the synchronous routed gather (host buckets the rows
    by owning shard, per-shard sub-launches run concurrently, results are
    reassembled in request order). The serving pump drives the per-shard
    executors directly (one launch queue per shard) for the async path.

    The shard set is ADAPTIVE (feedback re-shapes layout, the paper's
    cycle): :meth:`add_replica` places a second committed copy of a hot
    shard's resident stream on another device and :meth:`next_executor`
    round-robins read launches across the copies (read fan-out — each
    stream brings its own device queue, so a hot shard's capacity scales
    with replicas; writes need no fan-in because every stream re-syncs
    from the parent plan's versioned words at its next launch);
    :meth:`split_tail` closes the open tail shard at a cut row and opens a
    fresh tail on another device once streaming appends outgrow a row
    budget. Routing state (``starts`` + bisect list) is swapped as one
    atomic snapshot tuple, and the split orders create-new → swap-routing
    → close-old so a reader holding either snapshot stays bit-exact.
    Mutators themselves are NOT safe against a concurrent :meth:`batch` —
    FeatureService serializes them behind its pump; standalone users must
    quiesce first.
    """

    def __init__(self, plan: FeaturePlan, use_kernel: bool = False,
                 prefetch: int = 2, autotune: bool = False, devices=None,
                 hbm_budget_bytes: int | None = None):
        if not plan.packed:
            raise ValueError("sharded executors serve packed plans; int32 "
                             "plans route host code slices instead")
        from repro.distributed.sharding import DeviceBudget, serve_devices
        self.plan = plan
        self.use_kernel = use_kernel
        self.prefetch = prefetch
        self.autotune = autotune
        self.hbm_budget_bytes = hbm_budget_bytes
        self.shards = plan.imcu_shards()
        self.device_pool = (list(devices) if devices is not None
                            else jax.devices())
        self.devices = serve_devices(len(self.shards), self.device_pool)
        # tables replicate once per DEVICE, not per shard: shards placed on
        # the same device (more IMCUs than mesh devices) share the copies —
        # the cache dict persists so replicas/splits landing on a device
        # later reuse the same placed tables (place_fused reuse)
        self._caches = {id(dev): _DeviceTableCache() for dev in self.devices}
        # tiered residency at build time: walk the shards in order and
        # commit each stream only while it fits the per-device byte budget
        # (DeviceBudget ledger); the rest stay WARM — executor built, no
        # HBM held — and the serving layer's promotion ladder takes over.
        # No budget (the default) commits everything, today's behavior.
        ledger = DeviceBudget(hbm_budget_bytes)
        self.executors = []
        for sp, dev in zip(self.shards, self.devices):
            ex = FeatureExecutor(sp, use_kernel=use_kernel, prefetch=prefetch,
                                 autotune=autotune, device=dev,
                                 table_cache=self._caches[id(dev)],
                                 commit=False)
            if ledger.fits(id(dev), ex.stream_nbytes()):
                ex.ensure_range_capacity(sp.n_rows)
                ledger.charge(id(dev), ex.resident_bytes())
            self.executors.append(ex)
        self.replicas: list[list[FeatureExecutor]] = [[] for _ in self.shards]
        self._rr = [0] * len(self.shards)   # read-fan-out cursor per shard
        self._set_routing()

    def _cache_for(self, dev) -> _DeviceTableCache:
        return self._caches.setdefault(id(dev), _DeviceTableCache())

    def _set_routing(self) -> None:
        """Swap the routing table as ONE snapshot: readers grab the tuple
        once, so a concurrent swap can never hand them a torn view (new
        starts with an old bisect list)."""
        starts = np.array([sp._start for sp in self.shards], np.int64)
        self.starts = starts
        self._starts_list = starts.tolist()  # bisect beats np for O(1)
        self._routing = (starts, self._starts_list)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    # -- adaptive shard management -------------------------------------------------
    def n_streams(self, shard: int) -> int:
        """Launch streams serving this shard (primary + replicas)."""
        return 1 + len(self.replicas[shard])

    def stream_executors(self, shard: int) -> list[FeatureExecutor]:
        return [self.executors[shard], *self.replicas[shard]]

    def next_executor(self, shard: int) -> FeatureExecutor:
        """Read fan-out: round-robin the shard's launch streams. With no
        replicas this is exactly the primary (zero-cost fast path)."""
        reps = self.replicas[shard]
        if not reps:
            return self.executors[shard]
        i = self._rr[shard]
        self._rr[shard] = (i + 1) % (1 + len(reps))
        return self.executors[shard] if i == 0 else reps[i - 1]

    def device_load(self) -> dict[int, int]:
        """Resident launch streams per device (``id(dev)`` keyed) — the
        placement pressure the replica/split policies balance against."""
        load: dict[int, int] = {}
        for ex in self.executors:
            load[id(ex.device)] = load.get(id(ex.device), 0) + 1
        for reps in self.replicas:
            for ex in reps:
                load[id(ex.device)] = load.get(id(ex.device), 0) + 1
        return load

    def device_bytes(self) -> dict[int, int]:
        """LIVE resident word-stream bytes per device (``id(dev)`` keyed),
        summed over every launch stream (primaries + replicas). Computed
        from the buffers actually held — never a ledger that could drift —
        so budget enforcement and tests measure ground truth. Replicated
        ADV tables are excluded by design: K-row constants shared per
        device, while the budget governs what scales with table rows."""
        out: dict[int, int] = {}
        for s in range(self.n_shards):
            for ex in self.stream_executors(s):
                b = ex.resident_bytes()
                if b:
                    out[id(ex.device)] = out.get(id(ex.device), 0) + b
        return out

    def budget_ledger(self):
        """A :class:`repro.distributed.sharding.DeviceBudget` seeded from
        the live per-device bytes — the fits/headroom view the promotion
        and demotion policies consult."""
        from repro.distributed.sharding import DeviceBudget
        ledger = DeviceBudget(self.hbm_budget_bytes)
        for dev_id, n in self.device_bytes().items():
            ledger.charge(dev_id, n)
        return ledger

    def add_replica(self, shard: int, device=None,
                    avoid=frozenset()) -> FeatureExecutor:
        """Commit a REPLICA of ``shard``'s resident word stream (plus the
        replicated tables, reused per device) to an under-loaded device and
        fan reads out over it. The replica shares the shard's plan view, so
        its puts attribute to the same ``per_shard`` stats entry, and a
        parent ``refresh()`` re-puts it lazily at its next launch exactly
        like the primary (version-keyed sync — write fan-in for free).
        ``avoid`` (device ids) marks unhealthy devices the default
        placement should route around — the failover path's 're-replicate
        elsewhere'."""
        sp = self.shards[shard]
        if device is None:
            from repro.distributed.sharding import replica_device
            held = {id(e.device) for e in self.stream_executors(shard)}
            device = replica_device(self.device_pool, self.device_load(),
                                    exclude=held, unhealthy=avoid)
        ex = FeatureExecutor(sp, use_kernel=self.use_kernel,
                             prefetch=self.prefetch, autotune=self.autotune,
                             device=device, table_cache=self._cache_for(device))
        self.replicas[shard].append(ex)
        self._rr[shard] = 0
        return ex

    def drop_replica(self, shard: int, index: int = -1) -> FeatureExecutor:
        """Retire one of ``shard``'s replicas (future launches stop routing
        to it; in-flight launches already hold their operands)."""
        if not self.replicas[shard]:
            raise ValueError(f"shard {shard} has no replicas to drop")
        ex = self.replicas[shard].pop(index)
        self._rr[shard] = 0
        return ex

    def evict_device(self, dev_id: int):
        """Remove every launch stream resident on a DEAD device
        (``dev_id = id(device)``) — the first half of device-loss
        recovery. Replicas on the device are dropped outright; a shard
        whose PRIMARY died promotes its first surviving replica (the
        promoted stream already holds the resident words, so serving
        continues without a transfer). Returns ``(removed, orphans)``:
        ``removed`` is ``[(shard, executor), ...]`` for every stream taken
        out of rotation (the caller retires their health state), and
        ``orphans`` lists shards left with NO live stream — their dead
        primary stays in place as a routing placeholder and the caller
        must serve them from host words until :meth:`rebuild_on` lands.
        """
        removed: list[tuple[int, FeatureExecutor]] = []
        orphans: list[int] = []
        for s in range(self.n_shards):
            reps = self.replicas[s]
            dead = [ex for ex in reps if id(ex.device) == dev_id]
            if dead:
                self.replicas[s] = [ex for ex in reps
                                    if id(ex.device) != dev_id]
                removed.extend((s, ex) for ex in dead)
                self._rr[s] = 0
            if id(self.executors[s].device) == dev_id:
                removed.append((s, self.executors[s]))
                if self.replicas[s]:           # failover: promote a replica
                    self.executors[s] = self.replicas[s].pop(0)
                    self.devices[s] = self.executors[s].device
                    self._rr[s] = 0
                else:
                    orphans.append(s)
        self._caches.pop(dev_id, None)         # placed tables died with it
        return removed, orphans

    def rebuild_on(self, shard: int, device=None,
                   lost=frozenset()) -> FeatureExecutor:
        """Emergency rebuild of ``shard``'s primary stream on a healthy
        device — the second half of device-loss recovery. The fresh
        executor re-commits the shard's resident word stream from the HOST
        packed words through the same version-keyed put path a refresh
        uses (plus the per-device table cache), so the rebuilt stream is
        bit-exact with the lost one by construction. Default placement
        routes around ``lost`` devices (ids) and anything already holding
        a stream of this shard. Raises if the surviving pool is empty —
        the caller keeps host-serving until hardware returns."""
        if device is None:
            from repro.distributed.sharding import (replica_device,
                                                    surviving_devices)
            pool = surviving_devices(self.device_pool, lost)
            if not pool:
                raise ValueError(
                    f"no surviving device to rebuild shard {shard} on")
            held = {id(e.device) for e in self.stream_executors(shard)}
            device = replica_device(pool, self.device_load(),
                                    exclude=held, unhealthy=lost)
        ex = FeatureExecutor(self.shards[shard], use_kernel=self.use_kernel,
                             prefetch=self.prefetch, autotune=self.autotune,
                             device=device, table_cache=self._cache_for(device))
        self.executors[shard] = ex
        self.devices[shard] = device
        self._rr[shard] = 0
        return ex

    def tail_rows(self) -> int:
        """Rows currently owned by the open tail shard (append pressure)."""
        start, stop = self.shards[-1].shard_bounds
        return stop - start

    def split_tail(self, cut: int | None = None, device=None) -> int:
        """Split the open tail shard at parent row ``cut`` (default: the
        word-aligned midpoint) and serve the new tail [cut, n_rows) from
        its own committed executor on an under-loaded device. Returns the
        new shard's index.

        Swap order keeps every reader bit-exact throughout: the new shard
        plan + executor exist first, the routing snapshot flips second
        (rows >= cut now route to the new stream), and the old tail closes
        LAST — a reader holding the pre-swap snapshot still finds rows >=
        cut valid in the then-still-open old tail.
        """
        tail = self.shards[-1]
        start, stop = tail.shard_bounds
        if cut is None:
            # word-aligned midpoint, clamped so the default stays valid on
            # a sub-32-row tail (cut == stop closes it behind an empty one)
            cut = min(start + max(32, (stop - start) // 2 // 32 * 32), stop)
        new_plan = self.plan.split_tail_shard(tail, cut, close=False)
        if device is None:
            from repro.distributed.sharding import replica_device
            device = replica_device(self.device_pool, self.device_load())
        ex = FeatureExecutor(new_plan, use_kernel=self.use_kernel,
                             prefetch=self.prefetch, autotune=self.autotune,
                             device=device, table_cache=self._cache_for(device))
        self.shards.append(new_plan)
        self.executors.append(ex)
        self.replicas.append([])
        self._rr.append(0)
        self.devices.append(device)
        self._set_routing()
        tail.close_at(cut)
        return len(self.shards) - 1

    def shard_of(self, rows: np.ndarray) -> np.ndarray:
        """Owning shard per row. Rows past the last compile-time bound
        (streaming appends) belong to the open-ended last shard."""
        starts, _ = self._routing
        s = np.searchsorted(starts, rows, side="right") - 1
        return np.minimum(s, len(starts) - 1)

    @staticmethod
    def _shard_scalar(slist: list[int], row: int) -> int:
        return min(bisect.bisect_right(slist, row) - 1, len(slist) - 1)

    def route(self, rows: np.ndarray, lo: int | None = None,
              hi: int | None = None):
        """Bucket request rows by owning shard: [(shard, local_rows, dest)].

        ``dest`` gives each local row's position in the original request
        (``None`` = the whole request in order — the clustered-lookup fast
        path: two scalar bisects, no per-row work, no index
        materialization). Local rows are shard-relative, so every
        sub-launch's indices stay within its device's stream. Callers that
        already know the request's min/max row pass them in (the submit hot
        path validates on them anyway).
        """
        starts, slist = self._routing       # one snapshot, never torn
        rows = np.asarray(rows, np.int64).reshape(-1)
        if lo is None:
            lo, hi = int(rows.min()), int(rows.max())
        s_lo = self._shard_scalar(slist, lo)
        s_hi = self._shard_scalar(slist, hi)
        if s_lo == s_hi:                   # whole request owned by one shard
            return [(s_lo, rows - starts[s_lo], None)]
        s = np.searchsorted(starts, rows, side="right") - 1
        shard = np.minimum(s, len(starts) - 1)
        out = []
        for s in np.unique(shard):
            (dest,) = np.nonzero(shard == s)
            out.append((int(s), rows[dest] - starts[s], dest))
        return out

    # -- predicate pushdown, sharded: scan per shard, serve matches locally -------
    def _shard_masks(self, pred) -> list[tuple[int, FeatureExecutor, jnp.ndarray]]:
        """Dispatch every shard's device scan before blocking on any count.

        The predicate compiles ONCE (dictionaries are shared across shard
        views); each shard's scan runs on the executor that owns (or
        replicates) its resident stream, so filter evaluation happens where
        the data lives — compute to the data, like the gathers.
        """
        terms = combine = None
        out = []
        for s in range(self.n_shards):
            ex = self.next_executor(s)
            if terms is None:
                terms, combine = ex._scan_terms(pred)
            out.append((s, ex, ex._mask_future(terms, combine)))
        return out

    def count_where(self, pred) -> int:
        return sum(int(m.sum()) for _, _, m in self._shard_masks(pred))

    def _shard_ranks(self, pred) -> list[tuple[int, FeatureExecutor,
                                               jnp.ndarray, int]]:
        """(shard, executor, running match count, count) of every shard
        with a match: every shard's scan is dispatched before any count is
        read, each on the executor that owns (or replicates) its stream."""
        futs = []
        for s in range(self.n_shards):
            ex = self.next_executor(s)
            futs.append((s, ex, *ex._ranks_count_future(pred)))
        return [(s, ex, ranks, int(cnt)) for s, ex, ranks, cnt in futs
                if int(cnt)]

    def filtered_rows(self, pred) -> np.ndarray:
        """Matching GLOBAL row indices, ascending (shards are ordered by
        start row, so shard-order concatenation IS global row order)."""
        starts, _ = self._routing
        parts = [ex._where_rows(ranks, cnt) + int(starts[s])
                 for s, ex, ranks, cnt in self._shard_ranks(pred)]
        return np.concatenate(parts) if parts else np.zeros(0, np.int64)

    def batch_where(self, pred) -> tuple[np.ndarray, jnp.ndarray]:
        """Filtered featurization across the mesh: each shard scans its own
        resident stream, then compacts and gathers its matches LOCALLY in
        fixed-shape blocks — no shard ships bytes to another device; the
        host only assembles the per-shard results in global row order."""
        starts, _ = self._routing
        futs = [(s, cnt, ex._where_blocks(ranks, cnt))
                for s, ex, ranks, cnt in self._shard_ranks(pred)]
        if not futs:
            return (np.zeros(0, np.int64),
                    jnp.zeros((0, self.plan.out_dim), jnp.float32))
        total = sum(cnt for _, cnt, _ in futs)
        rows_out = np.empty(total, np.int64)
        feats_out = np.empty((total, self.plan.out_dim), np.float32)
        off = 0
        for s, cnt, blocks in futs:    # all dispatched; block in order
            rows = np.concatenate([np.asarray(r) for r, _ in blocks])
            feats = np.concatenate([np.asarray(f) for _, f in blocks])
            rows_out[off:off + cnt] = rows[:cnt] + int(starts[s])
            feats_out[off:off + cnt] = feats[:cnt]
            off += cnt
        return rows_out, jnp.asarray(feats_out)

    def groupby_where(self, column: str,
                      pred) -> tuple[np.ndarray, np.ndarray]:
        """GROUP BY column COUNT(*) WHERE pred across the mesh: per-shard
        masked histograms (local words, local mask) summed on the host —
        K-entry partials, never row-space traffic."""
        futs = [ex._masked_counts_from(column, mask)
                for _, ex, mask in self._shard_masks(pred)]
        counts = np.sum([np.asarray(f) for f in futs], axis=0)
        d = self.plan.augmented[column].dictionary
        return d.values, counts.astype(np.int64)

    def agg_where(self, pred, column: str, agg: str = "count") -> float:
        with span("query.agg_where", column=column, agg=agg):
            futs = [ex._masked_counts_from(column, mask)
                    for _, ex, mask in self._shard_masks(pred)]
            with span("query.fetch") as fetch:
                parts = [np.asarray(f) for f in futs]
                fetch.set_metadata(nbytes=sum(a.nbytes for a in parts))
            d = self.plan.augmented[column].dictionary
            return _agg_from_counts(d, np.sum(parts, axis=0), agg)

    def batch(self, row_idx: np.ndarray) -> jnp.ndarray:
        """Routed featurization of arbitrary rows, request order preserved.

        Dispatches every shard's sub-launch before blocking on any result,
        so independent shards gather concurrently.
        """
        rows = np.asarray(row_idx, np.int64).reshape(-1)
        n = rows.shape[0]
        if n == 0:
            return jnp.zeros((0, self.plan.out_dim), jnp.float32)
        lo, hi = int(rows.min()), int(rows.max())
        if lo < 0 or hi >= self.plan.n_rows:
            raise IndexError(
                f"row indices out of range [0, {self.plan.n_rows})")
        routed = self.route(rows, lo, hi)
        futs = []
        for s, local, dest in routed:      # dispatch all, block after
            padded = pad_rows_edge(local, _pad32(local.shape[0]))
            futs.append((self.next_executor(s)._rows_future(
                padded.astype(np.int32)), local.shape[0], dest))
        if len(futs) == 1:
            return futs[0][0][:n]
        out = np.empty((n, self.plan.out_dim), np.float32)
        for fut, m, dest in futs:
            out[dest] = np.asarray(fut)[:m]
        return jnp.asarray(out)


class FeaturePipeline:
    """Facade over (FeaturePlan, FeatureExecutor) — the original seed API."""

    def __init__(self, table: Table, features: FeatureSet,
                 use_kernel: bool = False, prefetch: int = 2,
                 packed: bool = False):
        self.table = table
        self.features = features
        self.plan = FeaturePlan(table, features, packed=packed)
        self.executor = FeatureExecutor(self.plan, use_kernel=use_kernel,
                                        prefetch=prefetch)
        self.augmented = self.plan.augmented
        self.use_kernel = use_kernel

    @property
    def out_dim(self) -> int:
        return self.plan.out_dim

    # -- device path ---------------------------------------------------------------
    def batch(self, row_idx: np.ndarray) -> jnp.ndarray:
        return self.executor.batch(row_idx)

    def batches(self, batch_size: int, seed: int = 0, epochs: int = 1):
        yield from self.executor.batches(batch_size, seed=seed, epochs=epochs)

    # -- host baseline (Fig 1 traditional path) -------------------------------------
    def batch_recompute(self, row_idx: np.ndarray) -> np.ndarray:
        """Decode values + row-space transform + ship f32 — the CSV workflow."""
        outs = []
        codes_all = self.plan.host_codes(row_idx)
        for i, p in enumerate(self.plan.plans):
            aug = self.augmented[p.column]
            for name in p.adv_names:
                outs.append(aug.featurize_recompute(name, codes_all[i]))
        return np.concatenate(outs, axis=1)

    # -- data-movement accounting ----------------------------------------------------
    def bytes_moved_adv(self, batch_rows: int) -> int:
        return self.plan.bytes_moved_adv(batch_rows)

    def bytes_moved_recompute(self, batch_rows: int) -> int:
        return self.plan.bytes_moved_recompute(batch_rows)

    def bytes_resident_tables(self) -> int:
        return self.plan.bytes_resident_tables()
