"""Property suite for the predicate-scan kernel: bits 1-16, word-boundary
straddles, empty/full match sets, post-refresh appends, composed predicates —
every path (Pallas kernel, XLA split, executor wiring) bit-exact against the
numpy host oracle that unpacks the SAME packed word streams.

``PREDICATE_SCAN_SWEEP=full`` widens the bit-width sweep from the smoke
subset to all of 1..16 (the nightly lane); the per-PR default keeps the
boundary-interesting widths.
"""
import os

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.columnar.bitpack import pack_bits
from repro.columnar.column import Column
from repro.columnar.table import Table
from repro.columnar import query as Q
from repro.core import FeaturePlan, FeatureExecutor
from repro.core.feature_spec import FeatureSet
from repro.core.pipeline import _pad32
from repro.kernels.bitunpack.kernel import tpu_width
from repro.kernels.hist import masked_hist
from repro.kernels.hist.ref import masked_hist_ref
from repro.kernels.predicate_scan import (ScanTerm, predicate_scan,
                                          predicate_scan_split, compact_rows,
                                          compact_block, masked_counts)
from repro.kernels.predicate_scan.compact import (CHUNK_ROWS,
                                                  compact_block_pallas)
from repro.kernels.predicate_scan.ref import (predicate_scan_ref,
                                              compact_rows_ref,
                                              masked_counts_ref)

# smoke: the widths where packing geometry changes (1 code/bit edge, the
# divisor widths, and straddle-forcing odd widths that repack to them);
# PREDICATE_SCAN_SWEEP=full = the nightly full 1..16 sweep
if os.environ.get("PREDICATE_SCAN_SWEEP") == "full":
    BITS = list(range(1, 17))
else:
    BITS = [1, 2, 3, 5, 8, 11, 16]


def _stream(rng, bits_list, n):
    """Build a multi-column resident-style flat stream at _pad32 capacity.

    Returns (flat_words jnp, word_offs, dbs, per-col codes, per-col words).
    """
    dbs, offs, parts, codes_list, words_list = [], [], [], [], []
    off = 0
    for bits in bits_list:
        db = tpu_width(bits)
        k = 1 << bits
        codes = rng.integers(0, k, n).astype(np.int32)
        w = pack_bits(codes, db)
        need = _pad32(n) * db // 32
        w = np.pad(w, (0, need - w.shape[0]))
        dbs.append(db)
        offs.append(off)
        off += need
        parts.append(w)
        codes_list.append(codes)
        words_list.append(w)
    return (jnp.asarray(np.concatenate(parts)), tuple(offs), tuple(dbs),
            codes_list, words_list)


def _random_terms(rng, bits_list, n_terms):
    terms = []
    for _ in range(n_terms):
        c = int(rng.integers(0, len(bits_list)))
        k = 1 << bits_list[c]
        if rng.integers(0, 2):                      # range term
            lo = int(rng.integers(0, k))
            hi = int(rng.integers(lo, k))
            terms.append(ScanTerm(col=c, kind=0, lo=lo, hi=hi))
        else:                                       # IN-set LUT term
            m = int(rng.integers(1, min(k, 8) + 1))
            lut = np.zeros(k, np.int32)
            lut[rng.choice(k, size=m, replace=False)] = 1
            terms.append(ScanTerm(col=c, kind=1, lut=lut))
    return terms


@settings(max_examples=30, deadline=None)
@given(bits=st.sampled_from(BITS),
       n=st.integers(1, 700),
       n_terms=st.integers(1, 3),
       seed=st.integers(0, 2**31 - 1),
       combine=st.sampled_from(["and", "or"]))
def test_scan_matches_reference(bits, n, n_terms, seed, combine):
    """Split path and Pallas kernel agree bit-exactly with the host oracle
    across widths, row counts off every word boundary, and composed
    multi-column AND/OR predicates."""
    rng = np.random.default_rng(seed)
    bits_list = [bits, int(rng.integers(1, 17))]
    flat, offs, dbs, _, words = _stream(rng, bits_list, n)
    terms = _random_terms(rng, bits_list, n_terms)
    ref = predicate_scan_ref(words, dbs, terms, n, combine)
    split = np.asarray(predicate_scan_split(flat, offs, dbs, terms, n,
                                            combine))
    np.testing.assert_array_equal(split, ref)
    pal = np.asarray(predicate_scan(flat, offs, dbs, terms, n, combine,
                                    bn=128))
    np.testing.assert_array_equal(pal, ref)


@pytest.mark.parametrize("bits", BITS)
def test_word_boundary_straddles(bits):
    """Rows on either side of every word boundary evaluate correctly: a
    predicate selecting exactly the rows adjacent to word seams must come
    back as exactly those rows."""
    rng = np.random.default_rng(bits)
    db = tpu_width(bits)
    s = 32 // db
    n = 4 * s + 3                     # several words + a partial tail word
    k = 1 << bits
    flat, offs, dbs, codes_list, words = _stream(rng, [bits], n)
    codes = codes_list[0]
    # mark the straddle-adjacent rows (last of word w, first of word w+1)
    seam_rows = [r for w in range(1, (n + s - 1) // s)
                 for r in (w * s - 1, w * s) if r < n]
    target = codes[seam_rows[0]]
    terms = [ScanTerm(col=0, kind=0, lo=int(target), hi=int(target))]
    ref = predicate_scan_ref(words, dbs, terms, n)
    for got in (predicate_scan_split(flat, offs, dbs, terms, n),
                predicate_scan(flat, offs, dbs, terms, n, bn=32)):
        np.testing.assert_array_equal(np.asarray(got), ref)
    np.testing.assert_array_equal(ref, codes == target)


@pytest.mark.parametrize("bits", [1, 4, 7, 16])
def test_empty_and_full_match_sets(bits):
    rng = np.random.default_rng(100 + bits)
    n = 333
    k = 1 << bits
    flat, offs, dbs, _, _ = _stream(rng, [bits], n)
    empty = [ScanTerm(col=0, kind=0, lo=1, hi=0)]          # hi < lo
    full = [ScanTerm(col=0, kind=0, lo=0, hi=k - 1)]
    assert not np.asarray(
        predicate_scan_split(flat, offs, dbs, empty, n)).any()
    assert not np.asarray(predicate_scan(flat, offs, dbs, empty, n)).any()
    assert np.asarray(predicate_scan_split(flat, offs, dbs, full, n)).all()
    assert np.asarray(predicate_scan(flat, offs, dbs, full, n)).all()
    lut_none = [ScanTerm(col=0, kind=1, lut=np.zeros(k, np.int32))]
    lut_all = [ScanTerm(col=0, kind=1, lut=np.ones(k, np.int32))]
    assert not np.asarray(
        predicate_scan_split(flat, offs, dbs, lut_none, n)).any()
    assert np.asarray(predicate_scan(flat, offs, dbs, lut_all, n)).all()


def test_term_validation():
    rng = np.random.default_rng(0)
    flat, offs, dbs, _, _ = _stream(rng, [4], 64)
    with pytest.raises(ValueError):
        predicate_scan_split(flat, offs, dbs, [], 64)
    with pytest.raises(ValueError):
        predicate_scan_split(flat, offs, dbs,
                             [ScanTerm(col=3, kind=0, lo=0, hi=1)], 64)
    with pytest.raises(ValueError):
        predicate_scan_split(flat, offs, dbs,
                             [ScanTerm(col=0, kind=0, lo=0, hi=1)], 64,
                             combine="xor")
    with pytest.raises(ValueError):
        predicate_scan(flat, offs, dbs,
                       [ScanTerm(col=0, kind=0, lo=0, hi=1)], 64, bn=100)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 500), seed=st.integers(0, 2**31 - 1))
def test_compact_rows_matches_reference(n, seed):
    rng = np.random.default_rng(seed)
    mask = rng.integers(0, 2, n).astype(bool)
    ref = compact_rows_ref(mask)
    cap = _pad32(max(int(mask.sum()), 1))
    got = np.asarray(compact_rows(jnp.asarray(mask), cap))[:ref.shape[0]]
    np.testing.assert_array_equal(got, ref)
    # fill rows past the valid prefix are the fill value (gatherable)
    full = np.asarray(compact_rows(jnp.asarray(mask), cap, fill=7))
    assert (full[ref.shape[0]:] == 7).all()


def _mask_of(n, rows=(), p=0.0, every=0, seed=0):
    mask = np.random.default_rng(seed).random(n) < p
    mask[list(rows)] = True
    if every:
        mask[::every] = True
    return mask


# (mask, off, cap, fill): the span walk's edges. CHUNK_ROWS (8,192) rows
# come in one copy and are placed 1,024 at a time
COMPACT_CASES = {
    "empty": (_mask_of(5000), 0, 256, 0),
    "first_and_last_row": (_mask_of(20000, rows=(0, 19999)), 0, 128, 0),
    "q6_density": (_mask_of(40000, p=0.018, seed=1), 200, 1024, 0),
    "half": (_mask_of(20000, p=0.5, seed=2), 3000, 4096, 0),
    "every_row": (_mask_of(10000, p=1.0), 1000, 2048, 0),
    "window_mid_tile_across_chunks": (_mask_of(30000, p=0.3, seed=3), 2000,
                                      2500, 0),
    "off_past_count": (_mask_of(5000, p=0.5, seed=4), 4000, 256, 7),
    "ragged_n": (_mask_of(2 * CHUNK_ROWS + 77, p=0.1, seed=5), 0, 2048, 0),
    "fill_nonzero": (_mask_of(9000, p=0.2, seed=6), 100, 2048, 8999),
    "sparse_span": (_mask_of(6 * 4 * CHUNK_ROWS, every=4 * CHUNK_ROWS + 13),
                    1, 96, 3),
}


@pytest.mark.parametrize("case", list(COMPACT_CASES))
def test_compact_block_kernel_matches_reference(case):
    """The span-walk kernel (what a TPU runs, here interpreted) gives
    matches [off, off + cap) in row order and ``fill`` after them, bit for
    bit what the binary-search rendering gives."""
    mask, off, cap, fill = COMPACT_CASES[case]
    ref = compact_rows_ref(mask)[off:off + cap]
    want = np.full(cap, fill, np.int32)
    want[:ref.shape[0]] = ref
    ranks = jnp.cumsum(jnp.asarray(mask, jnp.int32))
    got = np.asarray(compact_block_pallas(ranks, off, cap, fill,
                                          interpret=True))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(compact_block(ranks, off, cap, fill)), want)


@settings(max_examples=20, deadline=None)
@given(bits=st.sampled_from(BITS), n=st.integers(1, 600),
       seed=st.integers(0, 2**31 - 1))
def test_masked_counts_matches_reference(bits, n, seed):
    rng = np.random.default_rng(seed)
    flat, offs, dbs, codes_list, _ = _stream(rng, [bits], n)
    codes = codes_list[0]
    k = 1 << bits
    mask = rng.integers(0, 2, n).astype(bool)
    ref = masked_counts_ref(codes, mask, k)
    for use_kernel in (False, True):
        got = np.asarray(masked_counts(flat, offs[0], dbs[0],
                                       jnp.asarray(mask), k, n,
                                       use_kernel=use_kernel))
        np.testing.assert_array_equal(got, ref)
    # the hist-package masked variant agrees with ITS oracle too
    mh = np.asarray(masked_hist(jnp.asarray(codes), jnp.asarray(mask), k))
    np.testing.assert_array_equal(
        mh, np.asarray(masked_hist_ref(jnp.asarray(codes),
                                       jnp.asarray(mask), k)))
    np.testing.assert_array_equal(mh, ref)


def _plan_fixture(rng, n, imcu_rows=500):
    age = rng.integers(18, 91, n)
    state = rng.integers(0, 51, n)
    device = rng.integers(0, 5, n)
    t = Table({"age": Column.from_data(age, "age", imcu_rows=imcu_rows),
               "state": Column.from_data(state, "state",
                                         imcu_rows=imcu_rows),
               "device": Column.from_data(device, "device",
                                          imcu_rows=imcu_rows)})
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("device", "onehot"))
    return t, fs, age, state, device


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), use_kernel=st.booleans())
def test_executor_mask_matches_host_reference(seed, use_kernel):
    """Executor-level scan (resident flat stream, compiled predicate)
    agrees with the host per-IMCU mask path on both scan backends."""
    rng = np.random.default_rng(seed)
    t, fs, age, state, _ = _plan_fixture(rng, int(rng.integers(100, 2000)))
    plan = FeaturePlan(t, fs, packed=True)
    ex = FeatureExecutor(plan, use_kernel=use_kernel)
    pick = rng.choice(51, size=3, replace=False).tolist()
    lo = int(rng.integers(18, 91))
    pred = Q.isin("state", pick) & Q.ge("age", lo)
    exp = Q.predicate_mask_host(t, pred)
    np.testing.assert_array_equal(np.asarray(ex.predicate_mask(pred)), exp)
    np.testing.assert_array_equal(ex.filtered_rows(pred),
                                  np.flatnonzero(exp))


def test_post_refresh_append_scan():
    """Streaming appends (FeaturePlan.refresh with new_codes) extend the
    resident streams; the scan sees the appended rows bit-exactly —
    including appends that land mid-word and grow a dictionary."""
    rng = np.random.default_rng(7)
    t, fs, age, state, device = _plan_fixture(rng, 777)   # off every width
    plan = FeaturePlan(t, fs, packed=True)
    ex = FeatureExecutor(plan)
    pred = Q.between("age", 30, 40) | Q.eq("device", 2)
    age_all, dev_all = age.copy(), device.copy()
    for step in range(3):
        extra = 50 + 13 * step                            # mid-word tails
        na = rng.integers(18, 91, extra)
        ns = rng.integers(0, 51, extra)
        nd = rng.integers(0, 5, extra)
        new_codes = {"age": t["age"].dictionary.add_rows(na),
                     "state": t["state"].dictionary.add_rows(ns),
                     "device": t["device"].dictionary.add_rows(nd)}
        plan.refresh(new_codes)
        age_all = np.concatenate([age_all, na])
        dev_all = np.concatenate([dev_all, nd])
        exp = ((age_all >= 30) & (age_all <= 40)) | (dev_all == 2)
        got = np.asarray(ex.predicate_mask(pred))
        assert got.shape[0] == age_all.shape[0]
        np.testing.assert_array_equal(got, exp)
        rows, feats = ex.batch_where(pred)
        np.testing.assert_array_equal(rows, np.flatnonzero(exp))
        np.testing.assert_array_equal(np.asarray(feats),
                                      np.asarray(ex.batch(rows)))
