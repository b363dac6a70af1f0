"""Criteo Kaggle day: its raw data from the seed, and the plain reference.

Numpy only; nothing here imports the system under test. The raw data is
what DLRM's Kaggle preprocessing hands a model: 13 non-negative integer
columns and 26 categorical columns as vocabulary indices, plus one
16-wide float32 embedding table per categorical column. Each column is
kept in encoded form (its distinct values and one code per row), which is
the raw column exactly: ``values[codes]``.

The reference featurizes a row from those raw values alone: ``log(1 + x)``
of each integer value in float64, and the embedding row of each category.
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from chipbench.load import affine, rng_for, zipf_ranks
from chipbench.work import FEATURE_BYTES, device_width


@dataclass
class Raw:
    rows: int
    dense_names: list[str]
    dense_values: list[np.ndarray]    # every integer in [0, K), ascending
    dense_codes: list[np.ndarray]     # (rows,) index into dense_values
    sparse_names: list[str]
    sparse_maps: list[tuple[int, int]]  # code k -> category (a k + b) mod K
    sparse_codes: list[np.ndarray]    # (rows,) code of each row's category
    tables: list[np.ndarray]          # (K, dim) float32, row k for code k

    def sparse_values(self, c: int) -> np.ndarray:
        """Column c's categories in code order (its dictionary values)."""
        k = self.tables[c].shape[0]
        a, b = self.sparse_maps[c]
        return (a * np.arange(k, dtype=np.int64) + b) % k


def sizes(cfg: dict, rehearse: bool) -> tuple[int, list[int], list[int]]:
    rows = cfg["rows"]
    dense_k = list(cfg["dense"]["cardinality"])
    sparse_k = list(cfg["sparse"]["cardinality"])
    if rehearse:
        cap = cfg["rehearse"]["cardinality_cap"]
        rows = cfg["rehearse"]["rows"]
        dense_k = [min(k, cap) for k in dense_k]
        sparse_k = [min(k, cap) for k in sparse_k]
    return rows, dense_k, sparse_k


def _dense_column(cfg, seed, j, rows, k):
    rng = rng_for(seed, 1, j)
    v = zipf_ranks(rng, rows, k, cfg["dense"]["zipf_s"])
    miss = cfg["dense"]["missing_share"][j]
    if miss:
        v[rng.random(rows) < miss] = 0
    # every value in [0, K) is in the dictionary, drawn or not, so it has
    # the same size under every seed
    return np.arange(k, dtype=np.int64), v.astype(np.int32)


def _sparse_column(cfg, seed, c, rows, k):
    rng = rng_for(seed, 2, c)
    mapping = affine(rng, k)
    scramble_a, scramble_b = affine(rng, max(k - 1, 1))
    ranks = zipf_ranks(rng, rows, max(k - 1, 1), cfg["sparse"]["zipf_s"])
    codes = (1 + (scramble_a * ranks + scramble_b) % max(k - 1, 1))
    codes = np.minimum(codes, k - 1).astype(np.int32)
    miss = cfg["sparse"]["missing_share"][c]
    if miss:
        codes[rng.random(rows) < miss] = 0
    dim = cfg["sparse"]["embedding_dim"]
    bound = np.float32(math.sqrt(1.0 / k))
    table = rng_for(seed, 3, c).random((k, dim), dtype=np.float32)
    table *= 2 * bound
    table -= bound
    return mapping, codes, table


def generate(cfg: dict, seed: int, rehearse: bool = False,
             workers: int = 8) -> Raw:
    """The day's raw columns and embedding tables, from the seed alone."""
    rows, dense_k, sparse_k = sizes(cfg, rehearse)
    with ThreadPoolExecutor(workers) as pool:
        dense = list(pool.map(lambda jk: _dense_column(cfg, seed, jk[0], rows,
                                                       jk[1]),
                              enumerate(dense_k)))
        sparse = list(pool.map(lambda ck: _sparse_column(cfg, seed, ck[0],
                                                         rows, ck[1]),
                               enumerate(sparse_k)))
    return Raw(rows=rows,
               dense_names=list(cfg["dense"]["names"]),
               dense_values=[v for v, _ in dense],
               dense_codes=[c for _, c in dense],
               sparse_names=list(cfg["sparse"]["names"]),
               sparse_maps=[m for m, _, _ in sparse],
               sparse_codes=[c for _, c, _ in sparse],
               tables=[t for _, _, t in sparse])


def work(raw: Raw) -> dict:
    """What the device stages read, for :mod:`chipbench.work`."""
    dim = raw.tables[0].shape[1]
    cards = ([v.size for v in raw.dense_values]
             + [t.shape[0] for t in raw.tables])
    out_dim = len(raw.dense_values) + dim * len(raw.tables)
    return {"rows": raw.rows, "columns": len(cards),
            "device_bits": [device_width(k) for k in cards],
            "table_bytes_per_row": out_dim * FEATURE_BYTES,
            "out_bytes_per_row": out_dim * FEATURE_BYTES}


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to bfloat16 (nearest, ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def features(raw: Raw, rows: np.ndarray, control: bool = False):
    """(dense, sparse) features of ``rows`` from the raw values: dense is
    (n, 13) log(1 + x), sparse (n, 26 * dim) embedding rows. float64, or
    with ``control`` the same computed in bfloat16."""
    rows = np.asarray(rows, np.int64)
    dense = np.empty((rows.size, len(raw.dense_values)), np.float64)
    for j, (values, codes) in enumerate(zip(raw.dense_values,
                                            raw.dense_codes)):
        x = values[codes[rows]].astype(np.float64)
        dense[:, j] = (_bf16(np.log1p(_bf16(x.astype(np.float32))))
                       if control else np.log1p(x))
    parts = []
    for c, (codes, table) in enumerate(zip(raw.sparse_codes, raw.tables)):
        k = table.shape[0]
        a, b = raw.sparse_maps[c]
        category = (a * codes[rows].astype(np.int64) + b) % k
        row = ((category - b) * pow(a, -1, k)) % k if k > 1 else category
        emb = table[row]
        parts.append(_bf16(emb) if control else emb)
    sparse = np.concatenate(parts, axis=1).astype(np.float64)
    return dense, sparse


def compare_rows(raw: Raw, rows: np.ndarray, served: np.ndarray,
                 control: bool = False) -> dict[str, float]:
    """The numbers compared for served ``rows``: embedding entries that
    differ from the reference at all, and the widest relative gap of a
    dense feature. With ``control`` the reference in bfloat16 stands in
    for the served rows."""
    dense_ref, sparse_ref = features(raw, rows)
    if control:
        dense_got, sparse_got = features(raw, rows, control=True)
    else:
        nd = dense_ref.shape[1]
        if served.shape != (rows.size, nd + sparse_ref.shape[1]):
            return {"emb_mismatches": float(sparse_ref.size),
                    "dense_max_rel_gap": math.inf}
        dense_got = served[:, :nd].astype(np.float64)
        sparse_got = served[:, nd:].astype(np.float64)
    gap = np.abs(dense_got - dense_ref) / np.maximum(np.abs(dense_ref),
                                                     1e-30)
    return {"emb_mismatches": float(np.count_nonzero(sparse_got
                                                     != sparse_ref)),
            "dense_max_rel_gap": float(gap.max()) if gap.size else 0.0}
