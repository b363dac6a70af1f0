"""TPC-H lineitem: its raw data from the seed, and the plain reference.

Numpy only; nothing here imports the system under test. Rows follow the
dbgen rules of clause 4.2.3: orders dated uniformly in [STARTDATE,
ENDDATE - 151 days] with 1-7 lineitems each; per line a quantity in
[1, 50], a part uniform over SF * 200,000 with the spec's retail price, a
discount in [0.00, 0.10], a tax in [0.00, 0.08], a ship date 1-121 days
after the order, a receipt date 1-30 days after shipping, the return flag
and line status by CURRENTDATE, and one of 7 ship modes.

Each column's dictionary lists its values in ascending order, as a
warehouse loads them, and holds every value the rules can make, so it has
the same size under every seed; ``codes`` index it. The Q6 reference sums
``l_extendedprice`` in exact integer cents over the raw values.
"""
from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from chipbench.load import rng_for
from chipbench.work import FEATURE_BYTES, device_width

SHIPMODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP",
                      "TRUCK"])
RETURNFLAGS = np.array(["A", "N", "R"])
LINESTATUS = np.array(["F", "O"])
CHUNK = 1 << 22


def day(iso: str) -> int:
    """Days since 1970-01-01."""
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents (clause 4.2.3): 90000 + ((key / 10) mod
    20001) + 100 * (key mod 1000)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


@dataclass
class Raw:
    rows: int
    quantity: np.ndarray       # int8, 1..50
    cents: np.ndarray          # int32, l_extendedprice in cents
    discount: np.ndarray       # int8, l_discount in hundredths
    tax: np.ndarray            # int8, l_tax in hundredths
    returnflag: np.ndarray     # int8 index into RETURNFLAGS
    linestatus: np.ndarray     # int8 index into LINESTATUS
    shipdate: np.ndarray       # int16 days since 1970-01-01
    shipmode: np.ndarray       # int8 index into SHIPMODES
    prices: np.ndarray         # every l_extendedprice the rules can make,
                               # in cents, ascending: the same for every seed
    ship_days: np.ndarray      # every l_shipdate the rules can make

    def dictionary(self, column: str) -> tuple[np.ndarray, np.ndarray]:
        """(ascending distinct values, codes) of one lineitem column."""
        if column == "l_quantity":
            return np.arange(1, 51, dtype=np.int64), \
                self.quantity.astype(np.int32) - 1
        if column == "l_discount":
            return np.arange(11) / 100.0, self.discount.astype(np.int32)
        if column == "l_tax":
            return np.arange(9) / 100.0, self.tax.astype(np.int32)
        if column == "l_returnflag":
            return RETURNFLAGS, self.returnflag.astype(np.int32)
        if column == "l_linestatus":
            return LINESTATUS, self.linestatus.astype(np.int32)
        if column == "l_shipmode":
            return SHIPMODES, self.shipmode.astype(np.int32)
        if column == "l_extendedprice":
            rank = np.zeros(int(self.prices[-1]) + 1, np.int32)
            rank[self.prices] = np.arange(self.prices.size, dtype=np.int32)
            return self.prices / 100.0, rank[self.cents]
        if column == "l_shipdate":
            return self.ship_days, \
                (self.shipdate - self.ship_days[0]).astype(np.int32)
        raise KeyError(column)


def possible_prices(parts: int) -> np.ndarray:
    """Every quantity x P_RETAILPRICE over the parts, in cents, ascending:
    the extended prices the rules can make. A dictionary of these has the
    same size under every seed, so every seed runs the same shapes."""
    price = np.flatnonzero(np.bincount(retail_cents(np.arange(1, parts + 1))))
    present = np.zeros(50 * int(price[-1]) + 1, bool)
    for q in range(1, 51):
        present[q * price] = True
    return np.flatnonzero(present)


def generate(cfg: dict, seed: int, rehearse: bool = False,
             workers: int = 8) -> Raw:
    sf = cfg["rehearse"]["scale_factor"] if rehearse else cfg["scale_factor"]
    n = cfg["rehearse"]["rows"] if rehearse else cfg["rows"]
    start, end = day(cfg["start_date"]), day(cfg["end_date"])
    current = day(cfg["current_date"])
    parts = max(int(cfg["parts_per_sf"] * sf), 1)
    rng = rng_for(seed, 0)
    lines = rng.integers(1, 8, n // 3 + 1000, dtype=np.int8)
    used = int(np.searchsorted(np.cumsum(lines, dtype=np.int64), n)) + 1
    order_of = np.repeat(np.arange(used, dtype=np.int32),
                         lines[:used])[:n]
    orderdate = rng.integers(start, end - 151 + 1, used).astype(np.int16)
    raw = Raw(rows=n, quantity=np.empty(n, np.int8),
              cents=np.empty(n, np.int32), discount=np.empty(n, np.int8),
              tax=np.empty(n, np.int8), returnflag=np.empty(n, np.int8),
              linestatus=np.empty(n, np.int8),
              shipdate=np.empty(n, np.int16), shipmode=np.empty(n, np.int8),
              prices=possible_prices(parts),
              ship_days=np.arange(start + 1, end - 151 + 121 + 1,
                                  dtype=np.int64))

    def chunk(i: int) -> None:
        lo, hi = i * CHUNK, min(n, (i + 1) * CHUNK)
        m = hi - lo
        r = rng_for(seed, 1, i)
        q = r.integers(1, 51, m)
        raw.quantity[lo:hi] = q
        raw.cents[lo:hi] = q * retail_cents(r.integers(1, parts + 1, m))
        raw.discount[lo:hi] = r.integers(0, 11, m)
        raw.tax[lo:hi] = r.integers(0, 9, m)
        ship = orderdate[order_of[lo:hi]] + r.integers(1, 122, m)
        receipt = ship + r.integers(1, 31, m)
        raw.shipdate[lo:hi] = ship
        returned = np.where(r.random(m) < 0.5, 2, 0)       # R or A
        raw.returnflag[lo:hi] = np.where(receipt <= current, returned, 1)
        raw.linestatus[lo:hi] = ship > current
        raw.shipmode[lo:hi] = r.integers(0, SHIPMODES.size, m)

    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(chunk, range(-(-n // CHUNK))))
    return raw


def work(raw: Raw) -> dict:
    """What the device stages read, for :mod:`chipbench.work`."""
    cards = {"l_quantity": 50, "l_discount": 11, "l_tax": 9,
             "l_returnflag": 3, "l_linestatus": 2, "l_shipmode": 7,
             "l_extendedprice": raw.prices.size,
             "l_shipdate": raw.ship_days.size}
    return {"rows": raw.rows, "cardinality": cards,
            "device_bits": {c: device_width(k) for c, k in cards.items()},
            "feature_bytes": FEATURE_BYTES}


def query_params(name: str, rng: np.random.Generator) -> dict:
    """Substitution parameters as qgen draws them (clause 2.4.6.3)."""
    if name != "q6":
        raise KeyError(f"no query {name!r} for lineitem")
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)),
            "quantity": int(rng.integers(24, 26))}


def q6_mask(raw: Raw, p: dict) -> np.ndarray:
    d0, d1 = day(f"{p['year']}-01-01"), day(f"{p['year'] + 1}-01-01")
    return ((raw.shipdate >= d0) & (raw.shipdate < d1)
            & (raw.discount >= p["discount"] - 1)
            & (raw.discount <= p["discount"] + 1)
            & (raw.quantity < p["quantity"]))


def answer(raw: Raw, p: dict, control: bool = False) -> float:
    """sum(l_extendedprice) under Q6's predicate: exact in integer cents,
    or with ``control`` summed in float32."""
    mask = q6_mask(raw, p)
    if control:
        dollars = raw.cents[mask].astype(np.float32) / np.float32(100)
        return float(np.sum(dollars, dtype=np.float32))
    return int(raw.cents[mask].sum(dtype=np.int64)) / 100


def compare_answers(raw: Raw, answered: list[tuple[dict, float]],
                    control: bool = False) -> dict[str, float]:
    """The number compared: the widest relative gap of a served sum from
    the exact one. With ``control`` the float32 reference is served."""
    gap = 0.0
    for p, got in answered:
        want = answer(raw, p)
        if control:
            got = answer(raw, p, control=True)
        gap = max(gap, abs(got - want) / max(abs(want), 1e-300))
    return {"sum_max_rel_gap": gap}
