"""Criteo Kaggle day loaded into the system: dictionaries, columns, plan.

Each column's ``Dictionary`` and codes come straight from the raw data
(``criteo_kaggle_day_ref.generate``), so no encode pass over the rows runs:
``Column(dictionary, codes)`` -> ``Table`` -> ``FeaturePlan(packed=True)``.
The integer columns get the ``log`` ADV; each categorical column gets its
embedding table as a learned 16-wide ADV.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.columnar import Column, Dictionary, Table
from repro.core import FeaturePlan, FeatureSet
from repro.core.adv import AugmentedDictionary


def _column(name: str, values: np.ndarray, codes: np.ndarray) -> Column:
    counts = np.bincount(codes, minlength=values.size)
    col = Column(Dictionary(values, counts, name=name,
                            sorted_codes=bool(np.all(np.diff(values) > 0))),
                 codes, use_rle=False)
    col.device_words()      # repack to the device width here, in parallel
    return col


def plan(cfg: dict, raw, workers: int = 8) -> FeaturePlan:
    dense = list(zip(raw.dense_names, raw.dense_values, raw.dense_codes))
    sparse = [(name, raw.sparse_values(c), raw.sparse_codes[c])
              for c, name in enumerate(raw.sparse_names)]
    with ThreadPoolExecutor(workers) as pool:
        columns = list(pool.map(lambda a: _column(*a), dense + sparse))
    table = Table({c.dictionary.name: c for c in columns})
    features = FeatureSet()
    augmented = {}
    for name in raw.dense_names:
        features.add(name, cfg["dense"]["feature"])
        augmented[name] = AugmentedDictionary(table[name].dictionary)
        augmented[name].add(f"{name}.{cfg['dense']['feature']}",
                            cfg["dense"]["feature"])
    for name, emb in zip(raw.sparse_names, raw.tables):
        features.add(name, "embedding")
        augmented[name] = AugmentedDictionary(table[name].dictionary)
        augmented[name].add_learned(f"{name}.embedding", emb)
    return FeaturePlan(table, features, augmented=augmented, packed=True)
