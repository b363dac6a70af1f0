"""Operations and bytes of each device stage, as functions of the work.

The counts depend only on what a stage has to read and write, not on how
the program renders it, so a Pallas or DMA rendering of the same stage is
read against the same numbers. All stages here are bandwidth-bound: their
operation counts are a few integer ops per field and stay far below the
compute peak.
"""
from __future__ import annotations

import math

WORD_BYTES = 4
FEATURE_BYTES = 4           # float32 features and ADV table entries


def device_width(cardinality: int) -> int:
    """Bits a code of a ``cardinality``-entry dictionary takes on the
    device: ceil(log2 K), at least 1, rounded up to a divisor of 32."""
    bits = max(1, math.ceil(math.log2(cardinality))) if cardinality > 1 else 1
    for w in (1, 2, 4, 8, 16, 32):
        if bits <= w:
            return w
    raise ValueError(f"cardinality {cardinality} needs more than 32 bits")


def gather(rows: int, columns: int, table_bytes_per_row: int,
           out_bytes_per_row: int) -> tuple[float, float]:
    """(ops, bytes) of serving ``rows`` requested rows: per row one index,
    one packed word per column, each column's table row, and the output
    row. Padding rows are not work."""
    nbytes = rows * (WORD_BYTES + columns * WORD_BYTES
                     + table_bytes_per_row + out_bytes_per_row)
    ops = rows * columns * 4        # word index, shift, mask, table offset
    return float(ops), float(nbytes)


def scan(n: int, column_bits: list[int]) -> tuple[float, float]:
    """(ops, bytes) of one predicate scan over ``n`` rows: the predicate
    columns' packed words at device width, and an n-bit mask written."""
    nbytes = n * sum(column_bits) / 8 + n / 8
    ops = n * len(column_bits) * 4  # shift, mask, two compares
    return float(ops), float(nbytes)


def hist(n: int, column_bits: int, k: int) -> tuple[float, float]:
    """(ops, bytes) of one masked histogram over ``n`` rows: the column's
    packed words, the n-bit mask, and k counts written."""
    nbytes = n * column_bits / 8 + n / 8 + k * WORD_BYTES
    ops = n * 3                     # shift, mask, add
    return float(ops), float(nbytes)
