"""Filtered serving end-to-end: predicate compiler, executor/sharded
pushdown (scan -> compact -> local gather), FeatureService submit(where=),
dict-aware masked aggregates, and the query.py bugfix regressions
(per-IMCU filter_mask decode, vectorized join_codes).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.columnar.column import Column
from repro.columnar.table import Table
from repro.columnar import query as Q
from repro.core import FeaturePlan, FeatureExecutor, ShardedFeatureExecutor
from repro.core.feature_spec import FeatureSet
from repro.serve.feature_service import FeatureService


def _fixture(seed=0, n=4000, imcu_rows=700):
    rng = np.random.default_rng(seed)
    age = rng.integers(18, 91, n)
    state = rng.integers(0, 51, n)
    income = np.round(rng.lognormal(10, 1, n), -2)
    device = rng.integers(0, 5, n)
    t = Table({"age": Column.from_data(age, "age", imcu_rows=imcu_rows),
               "state": Column.from_data(state, "state",
                                         imcu_rows=imcu_rows),
               "income": Column.from_data(income, "income",
                                          imcu_rows=imcu_rows),
               "device": Column.from_data(device, "device",
                                          imcu_rows=imcu_rows)})
    fs = (FeatureSet().add("age", "zscore")
          .add("state", "onehot")
          .add("income", "minmax").add("income", "log")
          .add("device", "onehot"))
    return t, fs, dict(age=age, state=state, income=income, device=device)


PRED = Q.isin("state", [3, 7, 11]) & Q.gt("age", 60)


def _expected_mask(cols):
    return np.isin(cols["state"], [3, 7, 11]) & (cols["age"] > 60)


# -- predicate AST + compiler -------------------------------------------------------
def test_predicate_compile_classification():
    t, _, _ = _fixture()
    dicts = {c: t[c].dictionary for c in t.columns}
    # equality on any dictionary is a 1-wide range
    cp = Q.compile_predicate(Q.eq("device", 2), dicts)
    (term,) = cp.terms
    assert term.kind == 0 and term.lo == term.hi
    # a value range over load-order codes is (generically) a LUT
    cp = Q.compile_predicate(Q.between("state", 10, 20), dicts)
    assert cp.terms[0].kind in (0, 1)
    lut_term = Q.compile_predicate(Q.isin("state", [1, 17, 40]),
                                   dicts).terms[0]
    assert lut_term.match.shape[0] == 3
    # sorted dictionary -> range compiles to kind 0
    d_sorted, codes = __import__(
        "repro.columnar.dictionary",
        fromlist=["Dictionary"]).Dictionary.from_data(
            np.arange(100) % 37, sort_values=True)
    cp = Q.compile_predicate(Q.between("x", 5, 11), {"x": d_sorted})
    assert cp.terms[0].kind == 0
    # empty match set compiles to the hi < lo empty range
    cp = Q.compile_predicate(Q.eq("device", 99), dicts)
    assert cp.terms[0].kind == 0 and cp.terms[0].hi < cp.terms[0].lo
    with pytest.raises(KeyError):
        Q.compile_predicate(Q.eq("nope", 1), dicts)


def test_predicate_mixed_combinators_raise():
    with pytest.raises(ValueError):
        (Q.eq("a", 1) & Q.eq("b", 2)) | Q.eq("c", 3)
    with pytest.raises(ValueError):
        (Q.eq("a", 1) | Q.eq("b", 2)) & Q.eq("c", 3)
    # same-op composition flattens
    p = Q.eq("a", 1) & Q.eq("b", 2) & Q.eq("c", 3)
    assert len(p.parts) == 3 and p.op == "and"


# -- query.py bugfix regressions ----------------------------------------------------
def test_filter_mask_decodes_per_imcu_only():
    """Regression: filter_mask must never materialize the full code stream
    (col.codes()) — pruning leaves few live IMCUs and only those decode."""
    rng = np.random.default_rng(1)
    # clustered values so IMCU min/max pruning actually prunes
    data = np.repeat(np.arange(8), 500)
    col = Column.from_data(data, "clustered", imcu_rows=500, use_rle=False)
    full_decodes = []
    orig = Column.codes
    Column.codes = lambda self: full_decodes.append(1) or orig(self)
    try:
        mask = Q.filter_mask(col, lambda v: v == 3)
    finally:
        Column.codes = orig
    assert not full_decodes, "filter_mask decoded the WHOLE column"
    np.testing.assert_array_equal(mask, data == 3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), nl=st.integers(0, 150),
       nr=st.integers(0, 150))
def test_join_codes_vectorized(seed, nl, nr):
    rng = np.random.default_rng(seed)
    l = Column.from_data(rng.integers(0, 12, max(nl, 1)), "k")
    r = Column.from_data(rng.integers(4, 18, max(nr, 1)), "k")
    li, ri = Q.join_codes(l, r)
    lv = l.dictionary.values[l.codes()]
    rv = r.dictionary.values[r.codes()]
    expected = {(i, j) for i in range(lv.shape[0])
                for j in range(rv.shape[0]) if lv[i] == rv[j]}
    assert set(zip(li.tolist(), ri.tolist())) == expected
    assert li.shape[0] == len(expected)
    np.testing.assert_array_equal(lv[li], rv[ri])


# -- executor pushdown --------------------------------------------------------------
def test_executor_filtered_rows_and_batch_where():
    t, fs, cols = _fixture()
    plan = FeaturePlan(t, fs, packed=True)
    ex = FeatureExecutor(plan)
    exp = _expected_mask(cols)
    assert ex.count_where(PRED) == int(exp.sum())
    rows = ex.filtered_rows(PRED)
    np.testing.assert_array_equal(rows, np.flatnonzero(exp))
    r2, feats = ex.batch_where(PRED)
    np.testing.assert_array_equal(r2, rows)
    np.testing.assert_array_equal(np.asarray(feats),
                                  np.asarray(ex.batch(rows)))
    # empty selection
    r0, f0 = ex.batch_where(Q.eq("state", 12345))
    assert r0.shape == (0,) and f0.shape == (0, plan.out_dim)


def test_executor_pushdown_guards():
    t, fs, _ = _fixture(n=500)
    plan32 = FeaturePlan(t, fs, packed=False)
    ex = FeatureExecutor(plan32)
    with pytest.raises(RuntimeError):
        ex.predicate_mask(PRED)
    plan = FeaturePlan(t, fs, packed=True)
    exp = FeatureExecutor(plan)
    with pytest.raises(KeyError):
        exp.groupby_where("not_a_column", PRED)


def test_masked_aggregates_dict_aware():
    t, fs, cols = _fixture()
    plan = FeaturePlan(t, fs, packed=True)
    ex = FeatureExecutor(plan)
    exp = _expected_mask(cols)
    vals, counts = ex.groupby_where("device", PRED)
    np.testing.assert_array_equal(
        counts, np.bincount(t["device"].codes()[exp], minlength=5))
    np.testing.assert_array_equal(vals, t["device"].dictionary.values)
    assert ex.agg_where(PRED, "age", "count") == exp.sum()
    assert np.isclose(ex.agg_where(PRED, "age", "sum"),
                      cols["age"][exp].sum())
    assert np.isclose(ex.agg_where(PRED, "age", "mean"),
                      cols["age"][exp].mean())
    # empty selection mean is NaN, count/sum 0
    none = Q.eq("state", 777)
    assert ex.agg_where(none, "age", "count") == 0
    assert ex.agg_where(none, "age", "sum") == 0.0
    assert np.isnan(ex.agg_where(none, "age", "mean"))
    with pytest.raises(ValueError):
        ex.agg_where(PRED, "age", "median")


# -- sharded pushdown ---------------------------------------------------------------
def test_sharded_pushdown_serves_matches_locally():
    t, fs, cols = _fixture()
    plan = FeaturePlan(t, fs, packed=True)
    sx = ShardedFeatureExecutor(plan)
    assert sx.n_shards > 1
    exp = _expected_mask(cols)
    assert sx.count_where(PRED) == int(exp.sum())
    np.testing.assert_array_equal(sx.filtered_rows(PRED),
                                  np.flatnonzero(exp))
    rows, feats = sx.batch_where(PRED)
    np.testing.assert_array_equal(rows, np.flatnonzero(exp))
    ref = FeatureExecutor(FeaturePlan(t, fs, packed=True)).batch(rows)
    np.testing.assert_allclose(np.asarray(feats), np.asarray(ref),
                               rtol=1e-6)
    vals, counts = sx.groupby_where("device", PRED)
    np.testing.assert_array_equal(
        counts, np.bincount(t["device"].codes()[exp], minlength=5))
    assert np.isclose(sx.agg_where(PRED, "age", "mean"),
                      cols["age"][exp].mean())


# -- service submit(where=) ---------------------------------------------------------
@pytest.mark.parametrize("sharded", [False, True])
def test_service_filtered_submit(sharded):
    t, fs, cols = _fixture()
    plan = FeaturePlan(t, fs, packed=True)
    exp_rows = np.flatnonzero(_expected_mask(cols))
    with FeatureService(plan, sharded=sharded) as svc:
        ref = svc.result(svc.submit(exp_rows))
        out = svc.result(svc.submit(where=PRED))
        np.testing.assert_array_equal(out, ref)
        assert out.shape == (exp_rows.shape[0], plan.out_dim)
        assert svc.stats["filtered_requests"] == 1
        # service-level query helpers
        assert svc.count_where(PRED) == exp_rows.shape[0]
        np.testing.assert_array_equal(svc.filtered_rows(PRED), exp_rows)
        _, counts = svc.groupby_where("device", PRED)
        assert counts.sum() == exp_rows.shape[0]
        assert np.isclose(svc.agg_where(PRED, "age", "mean"),
                          cols["age"][_expected_mask(cols)].mean())


def test_service_filtered_empty_selection_short_circuits():
    t, fs, _ = _fixture(n=600)
    plan = FeaturePlan(t, fs, packed=True)
    with FeatureService(plan) as svc:
        tk = svc.submit(where=Q.eq("state", 99999))
        assert svc.poll(tk)                       # already on host
        out = svc.result(tk)
        assert out.shape == (0, plan.out_dim)
        assert svc.stats["filtered_requests"] == 1
        assert svc.stats["launches"] == 0         # nothing hit the pump


def test_service_filtered_guards():
    t, fs, cols = _fixture(n=600)
    plan32 = FeaturePlan(t, fs, packed=False)
    with FeatureService(plan32) as svc:
        with pytest.raises(RuntimeError):
            svc.submit(where=PRED)
        with pytest.raises(RuntimeError):
            svc.count_where(PRED)
        with pytest.raises(ValueError):
            svc.submit()
    plan = FeaturePlan(t, fs, packed=True)
    with FeatureService(plan) as svc:
        with pytest.raises(ValueError):
            svc.submit(np.arange(4), where=PRED)


def test_service_filtered_interleaves_with_plain_requests():
    t, fs, cols = _fixture()
    plan = FeaturePlan(t, fs, packed=True)
    exp_rows = np.flatnonzero(_expected_mask(cols))
    rng = np.random.default_rng(5)
    with FeatureService(plan, sharded=True) as svc:
        plain = [rng.integers(0, t.n_rows, 200) for _ in range(4)]
        tickets = []
        for i, rows in enumerate(plain):
            tickets.append(("plain", rows, svc.submit(rows)))
            tickets.append(("where", None, svc.submit(where=PRED)))
        ref_ex = FeatureExecutor(FeaturePlan(t, fs, packed=True))
        where_ref = np.asarray(ref_ex.batch(exp_rows))
        for kind, rows, tk in tickets:
            out = svc.result(tk)
            if kind == "plain":
                np.testing.assert_allclose(
                    out, np.asarray(ref_ex.batch(rows)), rtol=1e-6)
            else:
                np.testing.assert_allclose(out, where_ref, rtol=1e-6)


# -- where lanes on TPC-H lineitem: the served path against the plain reference ----
def _lineitem():
    """(plan, reference module, raw rows) of TPC-H lineitem at the
    benchmark's rehearsal size (50,000 rows) from a fixed seed."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.run import Bench, rehearsed
    cfg, ref, prog = Bench(Path(root)).config("tpch_lineitem_sf10_extract")
    cfg = rehearsed(cfg, True)
    raw = ref.generate(cfg, 2 ** 31 + 17, True)
    return prog, ref, raw, prog.plan(cfg, raw)


@pytest.fixture(scope="module")
def lineitem():
    return _lineitem()


def _check_against_reference(ref, raw, p, got):
    """Served float32 rows against the float64 reference: one-hot and year
    columns exactly; the continuous ones to 1e-5 relative, because the
    program builds their ADV tables in float32 from float32-cast
    dictionary values (about 1e-6 of quantity's z-score)."""
    rows = np.flatnonzero(ref.q6_mask(raw, p))
    assert got.shape == (rows.size, 36)
    want = ref.features(raw, rows, ref._moments(raw))
    np.testing.assert_array_equal(got[:, ref.EXACT], want[:, ref.EXACT])
    np.testing.assert_allclose(got[:, ref.CONTINUOUS],
                               want[:, ref.CONTINUOUS], rtol=1e-5)


QGEN = [{"year": 1993, "discount": 2, "quantity": 24},
        {"year": 1995, "discount": 6, "quantity": 25},
        {"year": 1997, "discount": 9, "quantity": 24}]


@pytest.mark.parametrize("p", QGEN, ids=lambda p: "{year}-{discount}-{quantity}"
                         .format(**p))
def test_service_where_serves_the_lineitem_reference(lineitem, p):
    prog, ref, raw, plan = lineitem
    from repro.serve import FeatureFrontend, default_classes
    with FeatureService(plan, classes=default_classes()) as svc:
        fe = FeatureFrontend(svc)
        got = fe.result(fe.submit(where=prog.where("q6", p), klass="batch"))
        _check_against_reference(ref, raw, p, got)
        assert svc.stats["filtered_rows"] == got.shape[0] > 0
        assert svc.stats["rows"] == got.shape[0]


@pytest.mark.parametrize("lanes", ["one_exactly", "four_exactly", "across"])
def test_service_where_lanes_cross_and_fill_blocks(lineitem, monkeypatch,
                                                    lanes):
    """Counts that fill one lane exactly, fill four (one whole launch)
    exactly, and cross lane boundaries with a partial last lane: every
    match is served once, in row order."""
    from repro.core import pipeline
    prog, ref, raw, plan = lineitem
    fits = {"one_exactly": 1, "four_exactly": 4, "across": 1}[lanes]
    p, count = next((p, c) for p in ref.PARAMS
                    if (c := int(ref.q6_mask(raw, p).sum())) % fits == 0)
    lane = count // fits if lanes != "across" else count // 3 - 5
    monkeypatch.setattr(pipeline, "WHERE_LANE", lane)
    with FeatureService(plan) as svc:
        assert svc._executor.where_lane == lane
        got = svc.result(svc.submit(where=prog.where("q6", p)))
        _check_against_reference(ref, raw, p, got)
        st = svc.stats
        assert st["launched_rows"] == st["launches"] * 4 * lane
        assert st["padded_rows"] == -(-count // lane) * lane - count
        if lanes != "across":
            assert st["padded_rows"] == 0


def test_service_where_empty_selection_on_lineitem(lineitem):
    prog, ref, raw, plan = lineitem
    p = {"year": 1994, "discount": 4, "quantity": 1}     # quantity < 1
    assert not ref.q6_mask(raw, p).any()
    with FeatureService(plan) as svc:
        out = svc.result(svc.submit(where=prog.where("q6", p)))
        assert out.shape == (0, plan.out_dim)
        assert svc.stats["launches"] == 0
        assert svc.stats["filtered_requests"] == 1
        assert svc.stats["filtered_rows"] == 0


def test_where_launches_reuse_one_executable_across_match_counts(lineitem):
    """The compaction and the gather of a where request compile once: a
    second request with another match count runs the same executables."""
    from repro.core import pipeline
    prog, ref, raw, plan = lineitem
    counts, sizes = [], []
    with FeatureService(plan) as svc:
        for p in QGEN:
            counts.append(int(svc.result(svc.submit(
                where=prog.where("q6", p))).shape[0]))
            sizes.append((pipeline._where_compact._cache_size(),
                          pipeline._packed_split_rows._cache_size()))
    assert len(set(counts)) == len(counts)
    assert sizes[1] == sizes[0] == sizes[-1]


def test_executor_where_paths_compile_once_across_match_counts(lineitem):
    """``filtered_rows`` and ``batch_where``, on one executor and across
    shards, take the served path's count-independent shapes: a second
    and third match count compile nothing new."""
    from repro.core import pipeline
    from repro.kernels.predicate_scan import ops as scan_ops
    prog, ref, raw, plan = lineitem
    ex, sx = FeatureExecutor(plan), ShardedFeatureExecutor(plan)
    jits = (scan_ops.compact_block, pipeline._where_compact,
            scan_ops._scan_split_ranks)
    sizes = []
    for p in QGEN:
        want = np.flatnonzero(ref.q6_mask(raw, p))
        for x in (ex, sx):
            np.testing.assert_array_equal(x.filtered_rows(
                prog.where("q6", p)), want)
            rows, feats = x.batch_where(prog.where("q6", p))
            np.testing.assert_array_equal(rows, want)
            _check_against_reference(ref, raw, p, np.asarray(feats))
        sizes.append(tuple(j._cache_size() for j in jits))
    assert sizes[0] == sizes[1] == sizes[2]


def test_explicit_row_launches_keep_their_class_shapes(lineitem):
    """Where lanes leave explicit-row launches as they were: interactive
    gathers one lane, batch four, over the (64, 256, 1024) buckets, and
    neither ever coalesces with a where lane."""
    from repro.serve import default_classes
    prog, ref, raw, plan = lineitem
    rng = np.random.default_rng(3)
    sizes = (10, 100, 1000, 2500)
    for klass, lanes in (("interactive", 1), ("batch", 4)):
        with FeatureService(plan, classes=default_classes()) as svc:
            ex = svc._executor
            seen = {"rows": set(), "where": set()}
            gather = ex._rows_future

            def record(rows, seen=seen, gather=gather):
                kind = "rows" if isinstance(rows, np.ndarray) else "where"
                seen[kind].add(int(rows.shape[0]))
                return gather(rows)

            ex._rows_future = record
            svc.pause()
            reqs = [rng.integers(0, plan.n_rows, n) for n in sizes]
            tickets = [svc.submit(r, klass=klass) for r in reqs]
            where = svc.submit(where=prog.where("q6", QGEN[0]), klass=klass)
            svc.resume()
            out = svc.drain()
        assert seen["rows"] == {lanes * b for b in (64, 256, 1024)}
        assert seen["where"] == {lanes * ex.where_lane}
        for r, tk in zip(reqs, tickets):
            np.testing.assert_array_equal(out[tk], plan.host_features(r))
        _check_against_reference(ref, raw, QGEN[0], out[where])
