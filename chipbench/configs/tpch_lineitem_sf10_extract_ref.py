"""TPC-H lineitem as training rows: the plain featurization of the raw
rows a Q6-family predicate matches, and the comparison with what was
served.

Numpy only; nothing here imports the system under test. The raw rows are
those of ``tpch_lineitem_sf10_ref`` (the dbgen rules of clause 4.2.3).
Each row becomes 36 features, computed here in float64 from the raw
values: quantity's z-score (mean and population standard deviation over
the table), log(1 + extendedprice), extendedprice scaled to [0, 1] over
every price the rules can make, one-hots of discount, tax, return flag,
line status and ship mode, and the ship date's calendar year counted from
1993. The one-hot and year columns are whole numbers and must match
exactly; the three continuous ones are compared by relative gap.
"""
from __future__ import annotations

import numpy as np

from chipbench.configs.tpch_lineitem_sf10_ref import (  # noqa: F401
    LINESTATUS, RETURNFLAGS, SHIPMODES, Raw, day, generate, q6_mask)
from chipbench.configs.tpch_lineitem_sf10_ref import work as _scan_work
from chipbench.work import FEATURE_BYTES

YEARS = [day(f"{y}-01-01") for y in range(1993, 1999)]
ONEHOTS = (("discount", 11), ("tax", 9), ("returnflag", RETURNFLAGS.size),
           ("linestatus", LINESTATUS.size), ("shipmode", SHIPMODES.size))
OUT_DIM = 3 + sum(k for _, k in ONEHOTS) + 1
CONTINUOUS = (0, 1, 2)          # zscore, log, minmax
EXACT = tuple(range(3, OUT_DIM))
# qgen's parameters (clause 2.4.6.3): 5 years x 8 discounts x 2 quantities
PAIRS = [(y, d) for y in range(1993, 1998) for d in range(2, 10)]
QUANTITIES = (24, 25)
PARAMS = [{"year": y, "discount": d, "quantity": q}
          for y, d in PAIRS for q in QUANTITIES]


def work(raw: Raw) -> dict:
    """What the device stages read, for :mod:`chipbench.work`: the scan's
    columns as for Q6, and per served row one index, a packed word of
    each of the 8 columns, their table rows and the output row."""
    return {**_scan_work(raw), "columns": 8,
            "table_bytes_per_row": OUT_DIM * FEATURE_BYTES,
            "out_bytes_per_row": OUT_DIM * FEATURE_BYTES}


class _Walk:
    """One client stream's walk over qgen's 80 parameter triples."""

    def __init__(self, rng: np.random.Generator):
        self.rng, self.todo = rng, []

    def next(self) -> dict:
        if not self.todo:
            for i in self.rng.permutation(len(PAIRS)):
                qs = QUANTITIES[::-1] if self.rng.integers(2) \
                    else QUANTITIES
                self.todo += [(*PAIRS[i], q) for q in qs]
            self.todo.reverse()
        y, d, q = self.todo.pop()
        return {"year": y, "discount": d, "quantity": q}


_WALKS: dict[np.random.Generator, _Walk] = {}


def every_params(name: str) -> list[dict]:
    """Every parameter triple :func:`query_params` can draw."""
    if name != "q6":
        raise KeyError(f"no query {name!r} for lineitem")
    return list(PARAMS)


def query_params(name: str, rng: np.random.Generator) -> dict:
    """The next substitution parameters of the stream ``rng`` draws: the
    80 triples qgen draws from (clause 2.4.6.3), walked in an order the
    stream shuffles, each (year, discount) pair once a pass with both
    quantities back to back. The same distribution as qgen's, stratified,
    so that a window's mix of parameters does not move its rate."""
    if name != "q6":
        raise KeyError(f"no query {name!r} for lineitem")
    walk = _WALKS.get(rng)
    if walk is None:
        walk = _WALKS[rng] = _Walk(rng)
    return walk.next()


def _columns(raw: Raw, rows: np.ndarray) -> list[np.ndarray]:
    """Per feature group of ``rows``: quantity, price in dollars (float64),
    the one-hot codes in :data:`ONEHOTS` order, and the year bucket."""
    return [raw.quantity[rows].astype(np.float64),
            raw.cents[rows] / 100.0,
            raw.discount[rows], raw.tax[rows], raw.returnflag[rows],
            raw.linestatus[rows], raw.shipmode[rows],
            np.searchsorted(YEARS, raw.shipdate[rows], side="right")]


def _moments(raw: Raw) -> tuple[float, float, float, float]:
    """(quantity mean, quantity std, lowest price, highest price)."""
    q = raw.quantity.astype(np.float64)
    mean = float(q.mean())
    std = float(np.sqrt(np.mean((q - mean) ** 2)))
    return mean, std, raw.prices[0] / 100.0, raw.prices[-1] / 100.0


def features(raw: Raw, rows: np.ndarray, moments) -> np.ndarray:
    """(len(rows), 36) float64 features of ``rows``."""
    mean, std, lo, hi = moments
    qty, price, *codes, year = _columns(raw, rows)
    out = np.zeros((rows.size, OUT_DIM))
    out[:, 0] = (qty - mean) / std
    out[:, 1] = np.log1p(price)
    out[:, 2] = (price - lo) / (hi - lo)
    col = 3
    for (_, k), c in zip(ONEHOTS, codes):
        out[np.arange(rows.size), col + c] = 1.0
        col += k
    out[:, col] = year
    return out


def column_sums(raw: Raw, rows: np.ndarray, moments) -> np.ndarray:
    """The 36 column sums of :func:`features` over ``rows``, in float64,
    without building the (rows, 36) matrix."""
    mean, std, lo, hi = moments
    qty, price, *codes, year = _columns(raw, rows)
    sums = [(qty.sum() - rows.size * mean) / std, np.log1p(price).sum(),
            (price.sum() - rows.size * lo) / (hi - lo)]
    for (_, k), c in zip(ONEHOTS, codes):
        sums += list(np.bincount(c, minlength=k).astype(np.float64))
    return np.array(sums + [float(year.sum())])


def bf16(x: np.ndarray) -> np.ndarray:
    """float64 rounded to bfloat16 (nearest, ties to even), as float64."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32) \
        .astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def _rel(got: np.ndarray, want: np.ndarray) -> float:
    if not got.size:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-300)))


def compare_answers(raw: Raw, answered: list[tuple[dict, dict]],
                    control: bool = False) -> dict[str, float]:
    """The numbers compared, over every answer kept:

    - ``count_mismatches``: answers whose row count is not the number of
      rows the predicate matches;
    - ``exact_mismatches``: one-hot and year entries of the sampled rows,
      and one-hot and year column sums, that differ at all;
    - ``cont_max_rel_gap``: the widest relative gap of a sampled
      continuous feature from float64;
    - ``colsum_max_rel_gap``: the widest relative gap of a continuous
      column's float64 sum over the whole answer.

    An answer (``chipbench/ops/where.py``) holds ``count``, ``pos`` (sampled
    positions in the answer), ``rows`` (the features there) and ``sums``.
    With ``control`` the reference's own features, rounded to bfloat16,
    are served (``pos`` is then drawn as the op draws it)."""
    moments = _moments(raw)
    counts = exact = 0
    cont = colsum = 0.0
    for p, got in answered:
        rows = np.flatnonzero(q6_mask(raw, p))
        want_sums = column_sums(raw, rows, moments)
        if control:
            pos = sample_positions(p, rows.size)
            served = bf16(features(raw, rows, moments))
            got = {"count": rows.size, "pos": pos, "rows": served[pos],
                   "sums": served.sum(axis=0)}
        counts += got["count"] != rows.size
        pos = np.asarray(got["pos"])
        if got["count"] != rows.size or (pos.size and pos.max() >= rows.size):
            continue
        want = features(raw, rows[pos], moments)
        served = np.asarray(got["rows"], np.float64)
        sums = np.asarray(got["sums"], np.float64)
        exact += int((served[:, EXACT] != want[:, EXACT]).sum())
        exact += int((sums[list(EXACT)] != want_sums[list(EXACT)]).sum())
        cont = max(cont, _rel(served[:, CONTINUOUS], want[:, CONTINUOUS]))
        colsum = max(colsum, _rel(sums[list(CONTINUOUS)],
                                  want_sums[list(CONTINUOUS)]))
    return {"count_mismatches": float(counts),
            "exact_mismatches": float(exact),
            "cont_max_rel_gap": cont, "colsum_max_rel_gap": colsum}


SAMPLE = 256
LANE = 1 << 16


def sample_positions(p: dict, count: int) -> np.ndarray:
    """Positions of an answer of ``count`` rows that are kept and
    compared: ``SAMPLE`` drawn from the parameters, the first and last
    rows, and both sides of every multiple of 65,536 (where fixed-size
    blocks of an answer would meet)."""
    if count == 0:
        return np.zeros(0, np.int64)
    rng = np.random.default_rng([p["year"], p["discount"], p["quantity"],
                                 count])
    edges = np.arange(LANE, count, LANE)
    pos = np.concatenate([rng.choice(count, min(count, SAMPLE),
                                     replace=False),
                          [0, count - 1], edges - 1, edges])
    return np.unique(pos).astype(np.int64)
