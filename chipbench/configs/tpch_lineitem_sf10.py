"""TPC-H lineitem loaded into the system, and its queries as predicates.

Dictionaries in value order (``sorted_codes=True``), so Q6's ranges
compile to code ranges; codes come straight from the raw data, so no
encode pass over the rows runs.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.columnar import Column, Dictionary, Table
from repro.columnar.query import between, lt
from repro.core import FeaturePlan, FeatureSet

from chipbench.configs.tpch_lineitem_sf10_ref import day


def _column(name: str, raw) -> Column:
    values, codes = raw.dictionary(name)
    counts = np.bincount(codes, minlength=values.size)
    col = Column(Dictionary(values, counts, name=name, sorted_codes=True),
                 codes, use_rle=False)
    col.device_words()      # repack to the device width here, in parallel
    return col


def plan(cfg: dict, raw, workers: int = 8) -> FeaturePlan:
    with ThreadPoolExecutor(workers) as pool:
        columns = list(pool.map(lambda c: _column(c, raw), cfg["columns"]))
    table = Table({c.dictionary.name: c for c in columns})
    years = [day(f"{y}-01-01") for y in range(1993, 1999)]
    features = FeatureSet()
    for column, kind in cfg["features"]:
        if kind == "bucketize_year":
            features.add(column, "bucketize", boundaries=years)
        else:
            features.add(column, kind)
    return FeaturePlan(table, features, packed=True)


def query(name: str, p: dict):
    """(predicate, column, aggregate) of a query with its parameters."""
    if name != "q6":
        raise KeyError(f"no query {name!r} for lineitem")
    d0, d1 = day(f"{p['year']}-01-01"), day(f"{p['year'] + 1}-01-01")
    lo, hi = (p["discount"] - 1) / 100, (p["discount"] + 1) / 100
    pred = (between("l_shipdate", d0, d1 - 1)
            & between("l_discount", lo - 1e-9, hi + 1e-9)
            & lt("l_quantity", p["quantity"]))
    return pred, "l_extendedprice", "sum"
