"""Property tests for the RLE codec (``columnar/rle.py``).

Round-trips ``rle_encode``/``rle_decode``/``rle_decode_jnp`` across bit
widths 1..16 with controlled run structure (codes built as
``np.repeat(values, lengths)``), plus the degenerate shapes the codec must
survive: empty input, a single run, and unaligned tails interacting with
``pack_bits``. Also pins the ``rle_nbytes`` formula to the run-length
dtype's real width.
"""
from __future__ import annotations

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.columnar.bitpack import pack_bits, unpack_bits
from repro.columnar.rle import rle_decode, rle_decode_jnp, rle_encode, rle_nbytes


def _runs(bits: int, max_runs: int = 12, max_len: int = 9):
    """Strategy producing (values, lengths) lists with the given bit width."""
    return st.lists(
        st.lists(st.integers(0, (1 << bits) - 1), min_size=2, max_size=2).map(
            lambda vl: (vl[0], 1 + vl[1] % max_len)
        ),
        min_size=0,
        max_size=max_runs,
    )


def _codes_from_runs(runs) -> np.ndarray:
    if not runs:
        return np.zeros(0, dtype=np.int32)
    vals = np.asarray([v for v, _ in runs], dtype=np.int32)
    lens = np.asarray([l for _, l in runs], dtype=np.int64)
    return np.repeat(vals, lens).astype(np.int32)


@settings(max_examples=40)
@given(st.integers(1, 16).map(lambda b: b))
def test_roundtrip_all_bit_widths(bits):
    rng = np.random.default_rng(bits)
    lens = rng.integers(1, 7, size=rng.integers(0, 20))
    vals = rng.integers(0, 1 << bits, size=lens.size)
    codes = np.repeat(vals, lens).astype(np.int32)
    values, lengths = rle_encode(codes)
    out = rle_decode(values, lengths)
    np.testing.assert_array_equal(out, codes)
    assert out.dtype == np.int32


@settings(max_examples=30)
@given(_runs(bits=8))
def test_roundtrip_structured_runs(runs):
    codes = _codes_from_runs(runs)
    values, lengths = rle_encode(codes)
    np.testing.assert_array_equal(rle_decode(values, lengths), codes)
    # Total decoded length always matches the input.
    assert int(lengths.sum()) == codes.size


@settings(max_examples=30)
@given(_runs(bits=4))
def test_adjacent_encoded_values_differ(runs):
    codes = _codes_from_runs(runs)
    values, _ = rle_encode(codes)
    if values.size > 1:
        assert np.all(values[1:] != values[:-1])


@settings(max_examples=20, deadline=None)
@given(_runs(bits=6, max_runs=8))
def test_device_decode_matches_host(runs):
    codes = _codes_from_runs(runs)
    values, lengths = rle_encode(codes)
    if values.size == 0:
        return  # searchsorted clip needs >= 1 run; empty is host-only
    dev = np.asarray(rle_decode_jnp(values, lengths, codes.size))
    np.testing.assert_array_equal(dev, codes)


def test_empty_input():
    values, lengths = rle_encode(np.zeros(0, dtype=np.int32))
    assert values.size == 0 and lengths.size == 0
    assert lengths.dtype == np.int64
    assert rle_decode(values, lengths).size == 0
    assert rle_nbytes(values, lengths, 16) == 0


def test_single_run():
    codes = np.full(1000, 7, dtype=np.int32)
    values, lengths = rle_encode(codes)
    assert values.tolist() == [7]
    assert lengths.tolist() == [1000]
    np.testing.assert_array_equal(rle_decode(values, lengths), codes)
    np.testing.assert_array_equal(
        np.asarray(rle_decode_jnp(values, lengths, 1000)), codes
    )


@settings(max_examples=20)
@given(st.integers(1, 16), st.integers(1, 97))
def test_unaligned_tail_pack_interop(bits, n):
    # n deliberately not a multiple of 32: the packed words carry a ragged
    # tail, and RLE must round-trip through pack/unpack bit-exactly.
    rng = np.random.default_rng(bits * 131 + n)
    codes = np.repeat(
        rng.integers(0, 1 << bits, size=(n + 2) // 3), 3
    )[:n].astype(np.int32)
    assert codes.size == n
    values, lengths = rle_encode(codes)
    decoded = rle_decode(values, lengths)
    np.testing.assert_array_equal(decoded, codes)
    words = pack_bits(decoded, bits)
    np.testing.assert_array_equal(unpack_bits(words, bits, n), codes)


@settings(max_examples=30)
@given(_runs(bits=12), st.integers(1, 16))
def test_nbytes_honest_dtype_width(runs, bits):
    codes = _codes_from_runs(runs)
    values, lengths = rle_encode(codes)
    n_runs = values.size
    expect = (n_runs * bits + 7) // 8 + lengths.dtype.itemsize * n_runs
    assert rle_nbytes(values, lengths, bits) == expect
    # int64 lengths must be charged 8 bytes per run.
    assert lengths.dtype.itemsize == 8


def test_rejects_2d_input():
    import pytest

    with pytest.raises(ValueError):
        rle_encode(np.zeros((2, 2), dtype=np.int32))
