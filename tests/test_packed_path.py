"""Packed-code fast path: fused unpack+gather, device-resident word serving.

The invariant under test everywhere: ``packed=True`` output is BIT-exact
(assert_array_equal, not allclose) against the int32 take+concat reference —
the packed path changes the representation that moves, never the math.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.columnar import Table
from repro.columnar.bitpack import pack_bits, packed_gather, packed_nbytes
from repro.core import FeatureSet, FeaturePipeline, FeaturePlan, FeatureExecutor
from repro.kernels.adv_gather import (adv_gather_packed,
                                      adv_gather_packed_split,
                                      adv_gather_packed_rows,
                                      adv_gather_packed_rows_split,
                                      autotune_packed, packed_kernel_fits,
                                      fuse_tables)
from repro.kernels.adv_gather.ref import (adv_gather_multi_ref,
                                          adv_gather_packed_ref,
                                          adv_gather_packed_rows_ref)
from repro.kernels.bitunpack.kernel import tpu_width
from repro.serve import FeatureService, default_classes

# satellite requirement: every storage width class, incl. non-divisors
# (3 -> 4, 6 -> 8, 12 -> 16) that force a device-width repack
BITS_SWEEP = (1, 2, 3, 4, 6, 8, 12, 16)


def _column_data(rng, bits, n):
    """Integer column whose dictionary needs exactly ``bits`` bits."""
    # minimal cardinality with bits_needed(k) == bits; n must be >= k
    k = 2 if bits == 1 else (1 << (bits - 1)) + 1
    base = np.arange(k)
    return np.concatenate([base, rng.integers(0, k, n - k)])


def _packed_vs_int32(table, fs, use_kernel):
    plan_i = FeaturePlan(table, fs)
    plan_p = FeaturePlan(table, fs, packed=True)
    ex_i = FeatureExecutor(plan_i)
    ex_p = FeatureExecutor(plan_p, use_kernel=use_kernel)
    return plan_i, plan_p, ex_i, ex_p


# -- kernel parity -----------------------------------------------------------------
@pytest.mark.parametrize("bits_set,n", [
    ((1, 3), 64), ((2, 6, 8), 300), ((12,), 257), ((4, 16), 40),
])
def test_packed_kernel_matches_multi_ref(bits_set, n):
    rng = np.random.default_rng(sum(bits_set) + n)
    cards = [1 << b for b in bits_set]
    dbs = [tpu_width(b) for b in bits_set]
    dims = [int(rng.integers(1, 9)) for _ in cards]
    tables = [rng.standard_normal((k, f)).astype(np.float32)
              for k, f in zip(cards, dims)]
    codes = [rng.integers(0, k, n).astype(np.int32) for k in cards]
    windows = [jnp.asarray(pack_bits(c, db)) for c, db in zip(codes, dbs)]
    fused = fuse_tables(tables)
    got = np.asarray(adv_gather_packed(
        windows, dbs, fused.table, fused.row_offsets, fused.card_limits,
        n, fused.out_dim))
    want = np.asarray(adv_gather_multi_ref(
        jnp.asarray(np.stack(codes)), [jnp.asarray(t) for t in tables]))
    np.testing.assert_array_equal(got, want)       # one-hot matmul is exact
    # split fallback and pure-jnp oracle agree too
    jt = [jnp.asarray(t) for t in tables]
    np.testing.assert_array_equal(
        np.asarray(adv_gather_packed_split(windows, dbs, jt, n)), want)
    np.testing.assert_array_equal(
        np.asarray(adv_gather_packed_ref(windows, dbs, jt, n)), want)


def test_packed_kernel_overprovisioned_windows():
    """Whole-stream windows (more words than the batch needs) are sliced,
    mirroring the bitunpack over-provisioning fix."""
    rng = np.random.default_rng(0)
    table = rng.standard_normal((256, 3)).astype(np.float32)
    codes = rng.integers(0, 256, 1000).astype(np.int32)
    words = jnp.asarray(pack_bits(codes, 8))       # covers all 1000 rows
    fused = fuse_tables([table])
    got = np.asarray(adv_gather_packed(
        [words], [8], fused.table, fused.row_offsets, fused.card_limits,
        64, fused.out_dim))
    np.testing.assert_array_equal(got, table[codes[:64]])


def test_packed_vmem_guard_and_autotune():
    assert packed_kernel_fits((100, 50), (4, 4))
    assert not packed_kernel_fits((1 << 17,), (4,))          # K guard
    assert not packed_kernel_fits((1 << 15, 1 << 15), (64, 64))  # ~16MB guard
    rng = np.random.default_rng(1)
    tables = [rng.standard_normal((64, 2)).astype(np.float32)]
    codes = rng.integers(0, 64, 128).astype(np.int32)
    windows = [jnp.asarray(pack_bits(codes, 8))]
    fused = fuse_tables(tables)
    bn, bk, bw = autotune_packed(windows, (8,), fused, 128, repeats=1)
    assert bn % 32 == 0 and fused.table.shape[0] % bk == 0
    # cached: second call returns the same winner without re-sweeping
    assert autotune_packed(windows, (8,), fused, 128) == (bn, bk, bw)


# -- random-row indexed gather (indices in, features out) ---------------------------
def _rows_fixture(rng, bits_set, n):
    """Full resident streams + fused tables + reference codes for bits_set."""
    cards = [2 if b == 1 else (1 << (b - 1)) + 1 for b in bits_set]
    dbs = [tpu_width(b) for b in bits_set]
    dims = [int(rng.integers(1, 9)) for _ in cards]
    tables = [rng.standard_normal((k, f)).astype(np.float32)
              for k, f in zip(cards, dims)]
    codes = [rng.integers(0, k, n).astype(np.int32) for k in cards]
    streams = [jnp.asarray(pack_bits(c, db)) for c, db in zip(codes, dbs)]
    offs, off = [], 0
    for s in streams:
        offs.append(off)
        off += int(s.shape[0])
    flat = jnp.concatenate(streams)
    return cards, dbs, tables, codes, streams, tuple(offs), flat


def _straddling_rows(rng, dbs, n, m=120):
    """Arbitrary rows biased to sit on BOTH sides of every column's word
    boundary (row % (32/db) in {s-1, 0, 1}), plus uniform filler."""
    picks = []
    for db in dbs:
        s = 32 // db
        base = np.arange(s, n - s, max(n // 8, s))
        picks += [base // s * s - 1, base // s * s, base // s * s + 1]
    rows = np.concatenate(picks + [rng.integers(0, n, m)])
    return np.clip(rows, 0, n - 1)


@pytest.mark.parametrize("bits_set,n", [
    ((1, 3), 96), ((2, 6, 8), 300), ((12,), 257), ((4, 16), 64),
    (BITS_SWEEP, 200),
])
def test_packed_rows_kernel_matches_reference(bits_set, n):
    """Fused random-row kernel == take reference, bit-exact, for arbitrary
    rows including ones straddling every tpu_width word boundary."""
    rng = np.random.default_rng(sum(bits_set) + n)
    cards, dbs, tables, codes, streams, offs, flat = \
        _rows_fixture(rng, bits_set, n)
    fused = fuse_tables(tables)
    rows = _straddling_rows(rng, dbs, n)
    want = np.concatenate([t[np.clip(c[rows], 0, len(t) - 1)]
                           for t, c in zip(tables, codes)], axis=1)
    got = np.asarray(adv_gather_packed_rows(
        flat, offs, dbs, fused.table, fused.row_offsets, fused.card_limits,
        jnp.asarray(rows), fused.out_dim))
    np.testing.assert_array_equal(got, want)       # one-hot matmul is exact
    # split fallback (index-only transfer preserved) and pure-jnp oracle
    jt = [jnp.asarray(t) for t in tables]
    np.testing.assert_array_equal(
        np.asarray(adv_gather_packed_rows_split(flat, offs, dbs, jt,
                                                jnp.asarray(rows))), want)
    np.testing.assert_array_equal(
        np.asarray(adv_gather_packed_rows_ref(streams, dbs, jt,
                                              jnp.asarray(rows))), want)


@pytest.mark.parametrize("n0,appended", [
    (203, 5),      # mid-word tail append, stays inside the pad32 capacity
    (224, 10),     # n0 IS the pad32 boundary: append must GROW the resident
                   # stream, else indices past it clip into the next column
])
def test_packed_rows_after_refresh_appends(n0, appended):
    """The indexed gather serves rows appended by FeaturePlan.refresh —
    mid-word tail appends AND appends that cross the executor's word-stream
    capacity — bit-exact vs the int32 layout."""
    rng = np.random.default_rng(21)
    t = Table.from_data({"a": rng.integers(0, 100, n0),
                         "b": rng.integers(0, 9, n0)})
    fs = FeatureSet().add("a", "zscore").add("b", "onehot")
    plan_i = FeaturePlan(t, fs)
    plan_p = FeaturePlan(t, fs, packed=True)
    ex_i = FeatureExecutor(plan_i)
    ex_p = FeatureExecutor(plan_p)
    np.asarray(ex_p.batch(np.arange(64)))          # compile + put pre-refresh
    new = {"a": t["a"].dictionary.add_rows(rng.integers(0, 100, appended)),
           "b": t["b"].dictionary.add_rows(rng.integers(0, 9, appended))}
    plan_p.refresh(new)
    plan_i.refresh(new)
    rows = np.array([0, 31, 32, 33, n0 - 2, n0 - 1, n0,
                     n0 + appended - 1])
    np.testing.assert_array_equal(np.asarray(ex_p.batch(rows)),
                                  np.asarray(ex_i.batch(rows)))


def test_packed_batch_keeps_int32_error_contract():
    """Empty and out-of-range batches behave like the int32 path: empty ->
    (0, F), OOB -> IndexError (never a silent clipped gather)."""
    rng = np.random.default_rng(24)
    t = Table.from_data({"a": rng.integers(0, 100, 224)})
    fs = FeatureSet().add("a", "zscore")
    ex_p = FeatureExecutor(FeaturePlan(t, fs, packed=True))
    ex_i = FeatureExecutor(FeaturePlan(t, fs))
    empty = np.array([], dtype=np.int64)
    assert np.asarray(ex_p.batch(empty)).shape == \
        np.asarray(ex_i.batch(empty)).shape
    for bad in ([500], [-1]):
        with pytest.raises(IndexError):
            ex_p.batch(np.array(bad))


def test_packed_rows_autotune_sweeps_rows_kernel():
    """autotune=True on the rows path sweeps the rows kernel itself and
    still serves bit-exact."""
    rng = np.random.default_rng(23)
    t = Table.from_data({"a": rng.integers(0, 100, 512)})
    fs = FeatureSet().add("a", "zscore")
    plan_p = FeaturePlan(t, fs, packed=True)
    ex_p = FeatureExecutor(plan_p, use_kernel=True, autotune=True)
    ex_i = FeatureExecutor(FeaturePlan(t, fs))
    rows = rng.integers(0, 512, 96)
    np.testing.assert_array_equal(np.asarray(ex_p.batch(rows)),
                                  np.asarray(ex_i.batch(rows)))
    assert 96 in ex_p._rows_blocks_cache           # swept once per shape
    bn, bk = ex_p._rows_blocks_cache[96]
    assert bn % 32 == 0 and plan_p.fused_tables().table.shape[0] % bk == 0


def test_packed_service_serves_rows_past_initial_capacity():
    """Service-level regression: a request for rows appended after compile
    (past the word stream's original pad32 capacity) is served bit-exact,
    not silently clipped into another column's words."""
    rng = np.random.default_rng(22)
    t = Table.from_data({"a": rng.integers(0, 100, 224),
                         "b": rng.integers(0, 9, 224)})
    fs = FeatureSet().add("a", "zscore").add("b", "onehot")
    pipe = FeaturePipeline(t, fs)
    plan_p = FeaturePlan(t, fs, packed=True)
    svc = FeatureService(plan_p, buckets=(64,))
    svc.result(svc.submit(np.arange(64)))          # puts words at cap 224
    new = {"a": t["a"].dictionary.add_rows(rng.integers(0, 100, 10)),
           "b": t["b"].dictionary.add_rows(rng.integers(0, 9, 10))}
    plan_p.refresh(new)
    pipe.plan.refresh(new)
    rows = np.arange(220, 234)                     # spans the old capacity
    np.testing.assert_array_equal(svc.result(svc.submit(rows)),
                                  np.asarray(pipe.batch(rows)))
    svc.shutdown()


# -- executor bit-exactness across the bits sweep ------------------------------------
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_executor_bit_exact_across_bits(use_kernel):
    rng = np.random.default_rng(7)
    n = 33024                  # bits=16 needs cardinality 2**15 + 1 <= n
    data = {f"c{b}": _column_data(rng, b, n) for b in BITS_SWEEP}
    table = Table.from_data(data)
    fs = FeatureSet()
    for b in BITS_SWEEP:
        fs = fs.add(f"c{b}", "zscore").add(f"c{b}", "minmax")
    plan_i, plan_p, ex_i, ex_p = _packed_vs_int32(table, fs, use_kernel)
    assert [tpu_width(b) for b in BITS_SWEEP] == plan_p.device_bits
    for start, m in ((0, 128), (512, 128), (96, 100)):
        idx = np.arange(start, start + m)
        np.testing.assert_array_equal(np.asarray(ex_p.batch_range(start, m)),
                                      np.asarray(ex_i.batch(idx)))
    # arbitrary rows fall back to the host word-gather, still bit-exact
    ridx = rng.integers(0, n, 333)
    np.testing.assert_array_equal(np.asarray(ex_p.batch(ridx)),
                                  np.asarray(ex_i.batch(ridx)))
    # coalesced multi-range launch == per-range launches
    multi = np.asarray(ex_p._multi_range_future([0, 224, 512], 128))
    for k, st in enumerate((0, 224, 512)):
        np.testing.assert_array_equal(multi[k],
                                      np.asarray(ex_i.batch(
                                          np.arange(st, st + 128))))


@given(st.integers(0, 2**31), st.sampled_from(BITS_SWEEP),
       st.integers(33, 500))
@settings(max_examples=10, deadline=None)
def test_packed_executor_property(seed, bits, n):
    rng = np.random.default_rng(seed)
    k = 2 if bits == 1 else (1 << (bits - 1)) + 1
    table = Table.from_data({"c": _column_data(rng, bits, max(n, k))})
    fs = FeatureSet().add("c", "zscore")
    plan_i, plan_p, ex_i, ex_p = _packed_vs_int32(table, fs, False)
    m = int(rng.integers(1, table.n_rows))
    np.testing.assert_array_equal(
        np.asarray(ex_p.batch_range(0, m)),
        np.asarray(ex_i.batch(np.arange(m))))


def test_packed_batches_iterator_block_shuffled():
    rng = np.random.default_rng(3)
    table = Table.from_data({"a": rng.integers(0, 50, 512)})
    fs = FeatureSet().add("a", "zscore")
    plan_i, plan_p, ex_i, ex_p = _packed_vs_int32(table, fs, False)
    got = list(ex_p.batches(128, seed=5, epochs=2))
    assert len(got) == 8
    starts = sorted(int(idx[0]) for idx, _ in got[:4])
    assert starts == [0, 128, 256, 384]            # one epoch covers all
    for idx, feats in got:
        np.testing.assert_array_equal(np.asarray(feats),
                                      np.asarray(ex_i.batch(idx)))
    with pytest.raises(ValueError):
        next(ex_p.batches(100))                    # not word-aligned


# -- refresh across a tpu_width boundary ---------------------------------------------
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_refresh_across_width_boundary(use_kernel):
    """K=4 (2 bits, db=2) grows to K=5 (3 bits, db=4): the word stream must
    repack in place and stay bit-exact vs the int32 layout, including the
    appended rows and already-compiled batch shapes."""
    rng = np.random.default_rng(4)
    n = 400
    vals = np.array(["CA", "OR", "WA", "NY"])[rng.integers(0, 4, n)]
    ages = rng.integers(18, 80, n)
    t = Table.from_data({"state": vals, "age": ages})
    fs = FeatureSet().add("state", "onehot").add("age", "zscore")
    plan_i = FeaturePlan(t, fs)
    plan_p = FeaturePlan(t, fs, packed=True)
    ex_i = FeatureExecutor(plan_i)
    ex_p = FeatureExecutor(plan_p, use_kernel=use_kernel)
    np.asarray(ex_p.batch_range(0, 128))           # compile pre-refresh
    assert plan_p.device_bits == [2, 8]
    new = {"state": t["state"].dictionary.add_rows(
               np.array(["TX", "CA", "TX"])),      # K 4 -> 5: bits 2 -> 3
           "age": t["age"].dictionary.add_rows(np.array([150, 25, 33]))}
    assert plan_p.refresh(new) == 2
    assert plan_i.refresh(new) == 2                # separate augmented dicts
    assert plan_p.device_bits == [4, 8]            # crossed db 2 -> 4
    assert plan_p.stats["words_repacked"] == 1
    assert plan_p.n_rows == plan_i.n_rows == n + 3
    idx = np.arange(n - 32, n + 3)                 # spans old rows + appended
    np.testing.assert_array_equal(np.asarray(ex_p.batch(idx)),
                                  np.asarray(ex_i.batch(idx)))
    # compiled range shape serves the repacked stream (db is a static arg,
    # so the width change retraces; values must be the new tables')
    np.testing.assert_array_equal(
        np.asarray(ex_p.batch_range(n - n % 32, 32 + (n + 3) % 32)[:3 + n % 32]),
        np.asarray(ex_i.batch(np.arange(n - n % 32, n + 3))))


def test_packed_refresh_tail_word_append():
    """Appends that land mid-word rewrite exactly one tail word."""
    rng = np.random.default_rng(5)
    t = Table.from_data({"a": rng.integers(0, 100, 203)})  # db=8, 203 % 4 = 3
    fs = FeatureSet().add("a", "minmax")
    plan_p = FeaturePlan(t, fs, packed=True)
    for step in range(3):
        codes = t["a"].dictionary.add_rows(rng.integers(0, 100, 5))
        plan_p.refresh({"a": codes})
        np.testing.assert_array_equal(
            plan_p.host_codes(np.arange(plan_p.n_rows - 5,
                                        plan_p.n_rows))[0], codes)
    assert plan_p.n_rows == 218


# -- service over a packed plan -----------------------------------------------------
@pytest.mark.parametrize("use_kernel", [False, True])
def test_packed_service_matches_pipeline(use_kernel):
    rng = np.random.default_rng(6)
    n = 2048
    t = Table.from_data({
        "age": rng.integers(18, 80, n),
        "state": np.array(["CA", "OR", "WA", "NY"])[rng.integers(0, 4, n)],
        "income": rng.integers(20, 200, n) * 1000,
    })
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    pipe = FeaturePipeline(t, fs)
    svc = FeatureService(FeaturePlan(t, fs, packed=True),
                         use_kernel=use_kernel, buckets=(64, 256))
    reqs = [np.arange(0, 256),                     # aligned range chunk(s)
            np.arange(992, 1056),                  # aligned, mid-table
            rng.integers(0, n, 200),               # arbitrary rows: fallback
            np.arange(7, 40),                      # contiguous, unaligned
            np.arange(1984, 2048),                 # tail range
            np.arange(0, 520)]                     # multi-chunk, mixed tail
    tickets = [svc.submit(r) for r in reqs]
    for r, tk in zip(reqs, tickets):
        np.testing.assert_array_equal(svc.result(tk), np.asarray(pipe.batch(r)))
    assert svc.stats["packed_ranges"] >= 4
    assert svc.stats["bytes_h2d"] > 0              # fallbacks shipped codes


def test_packed_service_coalesces_launches():
    rng = np.random.default_rng(8)
    n = 4096
    t = Table.from_data({"a": rng.integers(0, 100, n)})
    fs = FeatureSet().add("a", "zscore")
    pipe = FeaturePipeline(t, fs)
    svc = FeatureService(FeaturePlan(t, fs, packed=True), buckets=(128,),
                         coalesce=4)
    # pause holds the pump so the whole burst queues before any launch —
    # the deterministic maximal-coalescing schedule
    svc.pause()
    starts = [0, 512, 1024, 2048, 3072, 256]
    tickets = [svc.submit(np.arange(s, s + 128)) for s in starts]
    svc.resume()
    out = svc.drain()
    assert set(out) == set(tickets)
    # 6 chunks in groups of <= 4 -> 2 launches
    assert svc.stats["launches"] == 2
    assert svc.stats["packed_ranges"] == 6
    for s, tk in zip(starts, tickets):
        np.testing.assert_array_equal(out[tk],
                                      np.asarray(pipe.batch(
                                          np.arange(s, s + 128))))


@pytest.mark.parametrize("bucket", [64, 256])
@pytest.mark.parametrize("klass,lanes", [
    ("interactive", 1), ("batch", 4), ("background", 4)])
def test_packed_launch_width_is_the_class_coalesce_depth(klass, lanes,
                                                         bucket):
    """A launch gathers its class's coalesce depth of lanes: one
    ``bucket`` lane for ``interactive`` (coalesce 1), the service's 4 for
    classes without their own depth — surplus lanes of a partial group
    included. ``stats['launched_rows']`` counts exactly those lanes."""
    rng = np.random.default_rng(17)
    n = 4096
    t = Table.from_data({
        "age": rng.integers(18, 80, n),
        "state": np.array(["CA", "OR", "WA", "NY"])[rng.integers(0, 4, n)],
    })
    fs = FeatureSet().add("age", "zscore").add("state", "onehot")
    pipe = FeaturePipeline(t, fs)
    reqs = [rng.integers(0, n, bucket - 9 * i) for i in range(5)]
    with FeatureService(FeaturePlan(t, fs, packed=True), buckets=(64, 256),
                        coalesce=4, classes=default_classes()) as svc:
        assert svc.coalesce == 4
        svc.pause()                 # the whole burst queues: full groups
        tickets = [svc.submit(r, klass=klass) for r in reqs]
        svc.resume()
        out = svc.drain()
        st = dict(svc.stats)
    launches = -(-len(reqs) // lanes)
    assert st["launches"] == launches
    assert st["launched_rows"] == launches * lanes * bucket
    assert st["rows"] == sum(r.size for r in reqs)
    for r, tk in zip(reqs, tickets):
        np.testing.assert_array_equal(out[tk], np.asarray(pipe.batch(r)))


def test_packed_service_poll_flushes_partial_group():
    """A single queued range (partial coalescing group) must still complete
    through poll() alone — flushing is part of the pump, not result()."""
    import time
    rng = np.random.default_rng(9)
    t = Table.from_data({"a": rng.integers(0, 100, 512)})
    fs = FeatureSet().add("a", "zscore")
    svc = FeatureService(FeaturePlan(t, fs, packed=True), buckets=(64,))
    tk = svc.submit(np.arange(64, 128))
    deadline = time.perf_counter() + 30.0
    while not svc.poll(tk):
        assert time.perf_counter() < deadline
        time.sleep(0.001)
    pipe = FeaturePipeline(t, fs)
    np.testing.assert_array_equal(svc.result(tk),
                                  np.asarray(pipe.batch(np.arange(64, 128))))


def test_packed_sharding_supported_but_no_codes_matrix():
    """Packed plans shard per IMCU (word-stream slices) since the mesh PR;
    what they still never do is materialize the int32 code matrix, and a
    shard view refuses refresh (that belongs to the parent)."""
    rng = np.random.default_rng(10)
    t = Table.from_data({"a": rng.integers(0, 10, 256)}, imcu_rows=128)
    plan = FeaturePlan(t, FeatureSet().add("a", "zscore"), packed=True)
    shards = plan.imcu_shards()
    assert [s.n_rows for s in shards] == [128, 128]
    with pytest.raises(RuntimeError):
        plan.codes_matrix
    with pytest.raises(RuntimeError):
        shards[0].codes_matrix
    with pytest.raises(RuntimeError):
        shards[0].refresh()
    with FeatureService(plan, sharded=True, buckets=(64,)) as svc:
        assert svc.n_shards == 2
        rows = rng.integers(0, 256, 100)
        got = svc.result(svc.submit(rows))
        want = np.asarray(FeaturePipeline(t, FeatureSet().add("a", "zscore"))
                          .batch(rows))
        np.testing.assert_array_equal(got, want)


def test_packed_vmem_fallback_still_serves():
    """A plan past the VMEM budget keeps use_kernel off (split gathers) but
    the packed transfer/serving path still works."""
    rng = np.random.default_rng(12)
    t = Table.from_data({"zip": rng.integers(0, 1 << 17, 4096)})
    # ~4000 distinct codes x ~4000 one-hot dims: ΣKxΣF blows the ~16MB budget
    fs = FeatureSet().add("zip", "onehot", max_cardinality=4096)
    plan = FeaturePlan(t, fs, packed=True)
    ex = FeatureExecutor(plan, use_kernel=True)
    assert not ex.kernel_active
    ex_i = FeatureExecutor(FeaturePlan(t, fs))
    np.testing.assert_array_equal(np.asarray(ex.batch_range(0, 256)),
                                  np.asarray(ex_i.batch(np.arange(256))))


# -- data movement accounting --------------------------------------------------------
def test_packed_bytes_moved_table2_mixed_cardinality():
    """Paper Table 2 mixed-cardinality workload: the packed layout ships
    >= 4x fewer host->device bytes than the int32 code matrix."""
    rng = np.random.default_rng(13)
    n = 4096
    t = Table.from_data({
        "binary_gender": rng.integers(0, 2, n),          # 1 bit  -> db 1
        "season": rng.integers(0, 4, n),                 # 2 bits -> db 2
        "months": rng.integers(0, 12, n),                # 4 bits -> db 4
        "us_states": rng.integers(0, 50, n),             # 6 bits -> db 8
        "countries": rng.integers(0, 195, n),            # 8 bits -> db 8
    })
    fs = FeatureSet()
    for c in t.names:
        fs = fs.add(c, "zscore")
    plan_i = FeaturePlan(t, fs)
    plan_p = FeaturePlan(t, fs, packed=True)
    b = 1024
    assert plan_i.bytes_moved_adv(b) == 4 * b * 5
    assert plan_p.bytes_moved_adv(b) == sum(
        packed_nbytes(b, db) for db in (1, 2, 4, 8, 8))
    ratio = plan_i.bytes_moved_adv(b) / plan_p.bytes_moved_adv(b)
    assert ratio >= 4.0
    # resident duplication shrinks by the same factor
    assert plan_i.bytes_resident_codes() / plan_p.bytes_resident_codes() >= 4


def test_packed_gather_host_util():
    rng = np.random.default_rng(14)
    for db in (1, 2, 4, 8, 16, 32):
        codes = rng.integers(0, min(1 << db, 1 << 31), 500)
        words = pack_bits(codes, db)
        rows = rng.integers(0, 500, 99)
        np.testing.assert_array_equal(packed_gather(words, db, rows),
                                      codes[rows])
    with pytest.raises(ValueError):
        packed_gather(np.zeros(4, np.uint32), 6, np.array([0]))
