"""Front door suite: request classes, admission control, priority pump
scheduling, and the serving-accounting bugfix regressions.

What the tentpole guarantees, stated as invariants:

- priority classes drain strictly by priority when aging is off, and a
  starved low-priority class JUMPS the queue once it has aged past the
  high-priority class (anti-starvation) — both observable from per-class
  latency extrema after a paused-stage / resume drain;
- admission is BOUNDED by construction: ``max_inflight + queue_depth``
  outstanding per class, then a typed :class:`Overloaded` carrying a
  retry-after hint; a rejected submit enqueues nothing, an admitted one
  is never dropped (availability over admitted work stays 1.0);
- the three accounting bugs stay fixed: percentiles cover EVERY
  completed ticket (not a sliding window of the recent ones),
  ``throughput_stats`` is JSON-safe at ``wall_s == 0`` (no ``inf``), and
  pending work is reported as *pending*, not failed-availability.

Deterministic by construction where it matters: ordering tests stage
work while the pumps are PAUSED, so the drain order on resume depends
only on the scheduler's class selection, not on submission timing. The
randomized sweep reads ``FRONTEND_SWEEP_SEEDS`` (nightly raises it).
"""
import asyncio
import dataclasses
import json
import os
import time
from collections import deque

import numpy as np
import pytest

from repro.columnar import Table
from repro.core import FeatureSet, FeaturePlan, FeatureExecutor
from repro.serve import (DeadlineExceeded, FaultInjector, FaultPolicy,
                         FeatureFrontend, FeatureService, LatencyHistogram,
                         Overloaded, RequestClass, ServeError,
                         default_classes)


def _mixed_table(n=3000, imcu_rows=700, seed=0):
    rng = np.random.default_rng(seed)
    t = Table.from_data({
        "age": rng.integers(18, 80, n),
        "state": np.array(["CA", "OR", "WA", "NY"])[rng.integers(0, 4, n)],
        "income": rng.integers(20, 200, n) * 1000,
    }, imcu_rows=imcu_rows)
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    return t, fs


def _reference(t, fs, requests):
    ex = FeatureExecutor(FeaturePlan(t, fs))
    return [np.asarray(ex.batch(r)) for r in requests]


def _svc(classes, **kw):
    t, fs = _mixed_table()
    return t, fs, FeatureService(FeaturePlan(t, fs), classes=classes, **kw)


# -- request classes / construction --------------------------------------------------
def test_request_class_validation():
    with pytest.raises(ValueError):
        RequestClass("")
    with pytest.raises(ValueError):
        RequestClass("x", priority=-1)
    with pytest.raises(ValueError):
        RequestClass("x", deadline_ms=0)
    with pytest.raises(ValueError):
        RequestClass("x", max_inflight=0)
    with pytest.raises(ValueError):
        RequestClass("x", queue_depth=-1)
    with pytest.raises(ValueError):
        RequestClass("x", coalesce=0)
    with pytest.raises(ValueError):
        RequestClass("x", aging_s=0)
    names = [rc.name for rc in default_classes()]
    assert names == ["interactive", "batch", "background"]


def test_service_rejects_duplicate_and_unknown_classes():
    t, fs = _mixed_table(n=1400)
    with pytest.raises(ValueError):
        FeatureService(FeaturePlan(t, fs),
                       classes=(RequestClass("a"), RequestClass("a")))
    with FeatureService(FeaturePlan(t, fs),
                        classes=(RequestClass("a"),)) as svc:
        with pytest.raises(ValueError):
            svc.submit(np.arange(8), klass="nope")
        assert set(svc.classes) == {"default", "a"}


def test_frontend_needs_classes():
    t, fs = _mixed_table(n=1400)
    with FeatureService(FeaturePlan(t, fs)) as svc:
        with pytest.raises(ValueError):
            FeatureFrontend(svc)
    with FeatureService(FeaturePlan(t, fs),
                        classes=default_classes()) as svc:
        with pytest.raises(ValueError):
            FeatureFrontend(svc, default_klass="nope")
        fe = FeatureFrontend(svc)
        # default class is the highest-priority one
        assert fe.default_klass == "interactive"
        with pytest.raises(ValueError):
            fe.submit(np.arange(8), klass="nope")


# -- LatencyHistogram: the unbiased-p99 fix ------------------------------------------
def test_histogram_unbiased_where_sliding_window_lies():
    """The bug this fixes: a maxlen deque forgets the slow head of a long
    run, so its p99 collapses to the recent fast tail. The histogram
    sees every sample."""
    window = deque(maxlen=64)                  # the old accounting
    hist = LatencyHistogram()
    for _ in range(100):                       # slow early phase: 100 ms
        window.append(0.1)
        hist.record(0.1)
    for _ in range(900):                       # fast steady state: 1 ms
        window.append(0.001)
        hist.record(0.001)
    # the window only holds recent fast samples -> biased p99
    assert np.percentile(window, 99) == pytest.approx(0.001)
    # the histogram still knows 10% of all samples took 100 ms
    assert hist.count == 1000
    assert hist.percentile(99) == pytest.approx(0.1, rel=0.15)
    assert hist.percentile(50) == pytest.approx(0.001, rel=0.15)
    assert hist.mean_s == pytest.approx(0.0109, rel=1e-6)
    s = hist.summary()
    assert s["samples"] == 1000
    assert s["min_ms"] == pytest.approx(1.0)
    assert s["max_ms"] == pytest.approx(100.0)
    json.dumps(s, allow_nan=False)


def test_histogram_edges_and_merge():
    h = LatencyHistogram()
    assert h.percentile(99) == 0.0 and h.mean_s == 0.0
    assert h.summary()["min_ms"] == 0.0
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        LatencyHistogram(lo_s=0)
    # out-of-range samples clamp to edge buckets but report exact extrema
    h.record(1e-9)
    h.record(5e3)
    assert h.percentile(0) == pytest.approx(1e-9)
    assert h.percentile(100) == pytest.approx(5e3)
    other = LatencyHistogram()
    other.record(0.01)
    h.merge(other)
    assert h.count == 3 and h.max_s == 5e3
    with pytest.raises(ValueError):
        h.merge(LatencyHistogram(buckets_per_decade=12))


def test_service_percentiles_cover_all_ticket_history():
    """Regression for the window-biased p99: the streaming histograms
    cover every completed ticket, not a window of the most recent ones —
    each latency the service's ledger summed is in the histogram."""
    t, fs, svc = _svc((RequestClass("interactive", priority=3),))
    with svc:
        fe = FeatureFrontend(svc)
        for i in range(100):
            fe.submit(np.arange(i % 600, i % 600 + 24),
                      klass="interactive")
        fe.collect()
        assert svc.stats["latency_samples_total"] == 100
        cs = svc.class_stats()["interactive"]
        assert cs["samples"] == cs["completed"] == 100
        hist = svc._class_stats["interactive"]["hist"]
        assert int(hist.counts.sum()) == hist.count == 100
        assert hist.total_s == pytest.approx(svc.stats["latency_s_total"])
        assert svc.latency_percentile(99) > 0.0
        assert svc.latency_percentile(99, "interactive") > 0.0
        # a fresh observation window zeroes coverage but not the ledger
        svc.reset_latency_window()
        assert svc.stats["latency_samples_total"] == 0
        assert svc.latency_percentile(99) == 0.0
        assert svc.class_stats()["interactive"]["samples"] == 0
        assert svc.class_stats()["interactive"]["completed"] == 100


# -- throughput_stats: inf + availability fixes --------------------------------------
def test_throughput_stats_json_safe_at_zero_wall():
    """Regression: ``wall_s <= 0`` used to yield rows_per_s = inf, which
    json.dump renders as the non-standard ``Infinity`` token."""
    t, fs, svc = _svc(None)
    with svc:
        tk = svc.submit(np.arange(64))
        svc.result(tk, timeout=30)
        for wall in (0.0, -1.0):
            st = svc.throughput_stats(wall)
            assert st["wall_s_invalid"] is True
            assert st["rows_per_s"] == 0.0
            json.dumps(st, allow_nan=False)
        ok = svc.throughput_stats(1.0)
        assert ok["wall_s_invalid"] is False
        assert ok["rows_per_s"] == pytest.approx(64.0)


def test_availability_reports_pending_not_failed():
    """Regression: mid-flight ``throughput_stats`` used to count still-
    pending tickets as availability loss (completed/requests). Pending
    work is pending; availability covers resolved tickets only."""
    t, fs, svc = _svc(None)
    with svc:
        svc.pause()
        tks = [svc.submit(np.arange(16 * i, 16 * i + 16)) for i in range(3)]
        st = svc.throughput_stats(1.0)
        assert st["pending"] == 3
        assert st["completed"] == 0
        assert st["availability"] == 1.0       # nothing RESOLVED failed
        svc.resume()
        for tk in tks:
            svc.result(tk, timeout=30)
        st = svc.throughput_stats(1.0)
        assert st["pending"] == 0
        assert st["completed"] == 3 and st["availability"] == 1.0


# -- priority pump scheduling --------------------------------------------------------
def test_priority_classes_drain_strictly_by_priority():
    """Paused-stage background FIRST, interactive second, with aging
    effectively off (huge aging_s): on resume the pump must drain ALL
    interactive before any background, so every background latency
    exceeds every interactive latency (background also started its clock
    earlier — the inequality is doubly forced)."""
    t, fs, svc = _svc((
        RequestClass("interactive", priority=3, aging_s=1000.0),
        RequestClass("background", priority=1, aging_s=1000.0),
    ))
    reqs_bg = [np.arange(700 * 2 + 32 * i, 700 * 2 + 32 * i + 32)
               for i in range(6)]
    reqs_in = [np.arange(32 * i, 32 * i + 32) for i in range(6)]
    want = _reference(t, fs, reqs_bg + reqs_in)
    with svc:
        svc.pause()
        tks = [svc.submit(r, klass="background") for r in reqs_bg]
        tks += [svc.submit(r, klass="interactive") for r in reqs_in]
        svc.resume()
        got = [svc.result(tk, timeout=60) for tk in tks]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    cs = svc.class_stats()
    assert cs["interactive"]["completed"] == 6
    assert cs["background"]["completed"] == 6
    assert cs["interactive"]["max_ms"] < cs["background"]["min_ms"]


def test_aging_rescues_background_from_starvation():
    """The inverse staging: a large interactive flood ahead of two
    background chunks whose aging_s is tiny. Strict priority would drain
    background LAST (max background latency above max interactive);
    anti-starvation aging must pull it forward instead."""
    t, fs, svc = _svc((
        RequestClass("interactive", priority=3, aging_s=1000.0),
        RequestClass("background", priority=1, aging_s=0.001),
    ))
    reqs_in = [np.arange(s, s + 48) for s in
               np.linspace(0, 2300, 60).astype(int)]
    reqs_bg = [np.arange(1400 + 64 * i, 1400 + 64 * i + 64)
               for i in range(2)]
    with svc:
        svc.pause()
        tks = [svc.submit(r, klass="interactive") for r in reqs_in]
        tks += [svc.submit(r, klass="background") for r in reqs_bg]
        svc.resume()
        for tk in tks:
            svc.result(tk, timeout=60)
    cs = svc.class_stats()
    assert cs["background"]["completed"] == 2
    # background finished BEFORE the interactive flood drained: submitted
    # after every interactive request yet completed with smaller latency
    assert cs["background"]["max_ms"] < cs["interactive"]["max_ms"]


# -- admission control ---------------------------------------------------------------
def test_admission_bounds_and_recovers():
    t, fs, svc = _svc((
        RequestClass("interactive", priority=3, max_inflight=2,
                     queue_depth=2),
    ))
    reqs = [np.arange(24 * i, 24 * i + 24) for i in range(5)]
    want = _reference(t, fs, reqs[:4])
    with svc:
        fe = FeatureFrontend(svc)
        svc.pause()
        tks = [fe.submit(r, tenant="app") for r in reqs[:4]]
        with pytest.raises(Overloaded) as ei:
            fe.submit(reqs[4], tenant="app")
        e = ei.value
        assert e.klass == "interactive" and e.tenant == "app"
        assert e.outstanding == 4 and e.bound == 4
        assert e.retry_after_s > 0
        st = fe.stats()
        adm = st["classes"]["interactive"]
        assert adm["admitted"] == 4 and adm["rejected"] == 1
        assert adm["admitted_queued"] == 2     # past max_inflight=2
        assert adm["outstanding"] == 4
        assert st["tenants"]["app"] == {
            "requests": 5, "admitted": 4, "rejected": 1}
        svc.resume()
        got = [fe.result(tk, timeout=30) for tk in tks]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # retrieval freed the window: the rejected request now admits
        tk = fe.submit(reqs[4], tenant="app")
        np.testing.assert_array_equal(
            fe.result(tk, timeout=30),
            _reference(t, fs, [reqs[4]])[0])
        st = fe.stats()
        assert st["classes"]["interactive"]["outstanding"] == 0
        assert st["availability_admitted"] == 1.0


def test_admission_zero_queue_depth_rejects_at_window():
    t, fs, svc = _svc((
        RequestClass("solo", max_inflight=1, queue_depth=0),))
    with svc:
        fe = FeatureFrontend(svc)
        svc.pause()
        fe.submit(np.arange(16))
        with pytest.raises(Overloaded):
            fe.submit(np.arange(16))
        svc.resume()
        fe.collect()
        fe.submit(np.arange(16))               # window freed
        fe.collect()


def test_admission_slot_survives_timeout_releases_on_error():
    """The window frees on OUTCOME retrieval: a plain wait timeout keeps
    the slot (the ticket is still outstanding); a resolved typed error or
    an unknown ticket releases it."""
    t, fs = _mixed_table()
    inj = FaultInjector().delay_launches(0.25, 1, shard=0)
    with FeatureService(FeaturePlan(t, fs), faults=inj,
                        classes=(RequestClass("a", max_inflight=1,
                                              queue_depth=0),)) as svc:
        fe = FeatureFrontend(svc)
        tk = fe.submit(np.arange(32))
        with pytest.raises(TimeoutError):
            fe.result(tk, timeout=0.01)
        assert fe.stats()["classes"]["a"]["outstanding"] == 1
        with pytest.raises(Overloaded):
            fe.submit(np.arange(32))           # slot still held
        np.testing.assert_array_equal(
            fe.result(tk, timeout=30),
            _reference(t, _mixed_table()[1], [np.arange(32)])[0])
        assert fe.stats()["classes"]["a"]["outstanding"] == 0
        # unknown ticket: KeyError propagates, release is a no-op
        with pytest.raises(KeyError):
            fe.result(999_999)
        assert fe.stats()["classes"]["a"]["outstanding"] == 0


def test_admission_releases_on_serve_error():
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(10, shard=0)
    pol = FaultPolicy(max_retries=1, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs), faults=inj, fault_policy=pol,
                        classes=(RequestClass("a", max_inflight=1,
                                              queue_depth=0),)) as svc:
        fe = FeatureFrontend(svc)
        tk = fe.submit(np.arange(16))
        with pytest.raises(ServeError):
            fe.result(tk, timeout=30)
        st = fe.stats()
        assert st["classes"]["a"]["outstanding"] == 0
        assert st["classes"]["a"]["failed"] == 1
        assert st["availability_admitted"] == 0.0


def test_class_width_launches_refuse_and_fail_what_padded_ones_do():
    """The burst a host freeze leaves at the front door — ``max_inflight
    + queue_depth + 50`` requests at once, pump paused — against the
    preset ``interactive`` class (1-lane launches) and the same class at
    the service's 4-lane width (coalesce 4, no linger). Launch width
    changes no failure semantics: both refuse exactly the 50 past the
    bound with Overloaded, fail none with ServeError, and serve the rest
    bit-exact."""
    t, fs = _mixed_table()
    rng = np.random.default_rng(23)
    slim = default_classes()[0]
    padded = dataclasses.replace(slim, coalesce=4, linger_us=0.0)
    bound = slim.max_inflight + slim.queue_depth
    reqs = [rng.integers(0, 3000, int(rng.integers(8, 65)))
            for _ in range(bound + 50)]
    want = _reference(t, fs, reqs[:bound])
    launches = {}
    for rc in (slim, padded):
        with FeatureService(FeaturePlan(t, fs, packed=True), buckets=(64,),
                            coalesce=4, classes=(rc,)) as svc:
            fe = FeatureFrontend(svc)
            fe.result(fe.submit(reqs[0]), timeout=60)   # compile first
            before = dict(svc.stats)
            svc.pause()
            tickets, refused = [], 0
            for r in reqs:
                try:
                    tickets.append(fe.submit(r))
                except Overloaded:
                    refused += 1
            svc.resume()
            got, failed = [], 0
            for tk in tickets:
                try:
                    got.append(fe.result(tk, timeout=60))
                except ServeError:
                    failed += 1
            st = dict(svc.stats)
        assert (refused, failed, len(got)) == (50, 0, bound)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        n = st["launches"] - before["launches"]
        assert st["launched_rows"] - before["launched_rows"] == \
            n * svc._coalesce_for(rc) * 64
        launches[rc.coalesce] = n
    assert launches == {1: bound, 4: bound // 4}


# -- per-class deadlines -------------------------------------------------------------
def test_class_default_deadline_applies_and_overrides():
    t, fs, svc = _svc((
        RequestClass("tight", deadline_ms=20.0),))
    with svc:
        fe = FeatureFrontend(svc)
        svc.pause()
        tk_default = fe.submit(np.arange(24))              # class's 20 ms
        tk_long = fe.submit(np.arange(24), deadline_ms=60_000.0)
        time.sleep(0.08)                                   # age past 20 ms
        svc.resume()
        with pytest.raises(DeadlineExceeded):
            fe.result(tk_default, timeout=30)
        np.testing.assert_array_equal(
            fe.result(tk_long, timeout=30),
            _reference(t, fs, [np.arange(24)])[0])
        st = fe.stats()
        assert st["classes"]["tight"]["outstanding"] == 0
        assert st["classes"]["tight"]["failed"] == 1


# -- class-scoped fault injection ----------------------------------------------------
def test_faults_scope_to_request_class():
    inj = (FaultInjector().fail_launches(2, klass="batch"))
    with pytest.raises(Exception):
        inj.before_launch(0, 0, klass="batch")
    inj.before_launch(0, 0, klass="interactive")           # unscoped: fine
    inj.before_launch(0, 0)                                # classless: fine
    with pytest.raises(Exception):
        inj.before_launch(1, 2, klass="batch")
    assert inj.faults_injected == 2


def test_class_scoped_chaos_isolates_one_tenant_class():
    """Inject enough class-scoped faults that every batch launch fails
    through its retries: batch tickets resolve to typed ServeErrors while
    interactive work completes bit-exact — per-tenant-class blast radius."""
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(50, klass="batch")
    pol = FaultPolicy(max_retries=1, backoff_s=0.001, breaker_fails=1000)
    reqs_in = [np.arange(48 * i, 48 * i + 48) for i in range(4)]
    reqs_ba = [np.arange(1400 + 48 * i, 1400 + 48 * i + 48)
               for i in range(3)]
    want = _reference(t, fs, reqs_in)
    with FeatureService(FeaturePlan(t, fs), faults=inj, fault_policy=pol,
                        classes=(RequestClass("interactive", priority=3),
                                 RequestClass("batch", priority=2)),
                        ) as svc:
        fe = FeatureFrontend(svc)
        tks_in = [fe.submit(r, klass="interactive") for r in reqs_in]
        tks_ba = [fe.submit(r, klass="batch") for r in reqs_ba]
        for tk, w in zip(tks_in, want):
            np.testing.assert_array_equal(fe.result(tk, timeout=60), w)
        for tk in tks_ba:
            with pytest.raises(ServeError):
                fe.result(tk, timeout=60)
    cs = svc.class_stats()
    assert cs["interactive"]["completed"] == 4
    assert cs["interactive"]["failed"] == 0
    assert cs["batch"]["failed"] == 3
    assert inj.faults_injected >= 6            # 3 tickets x (1 + 1 retry)


# -- the async + dict edges ----------------------------------------------------------
def test_async_featurize_bit_exact():
    t, fs, svc = _svc(default_classes())
    reqs = [np.arange(64), np.arange(800, 880), np.arange(1500, 1532)]
    want = _reference(t, fs, reqs)

    async def go(fe):
        return await asyncio.gather(
            fe.featurize(reqs[0], klass="interactive"),
            fe.featurize(reqs[1], klass="batch"),
            fe.featurize(reqs[2], klass="background"),
        )

    with svc:
        fe = FeatureFrontend(svc)
        got = asyncio.run(go(fe))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert fe.stats()["availability_admitted"] == 1.0


def test_handle_request_response_contract():
    t, fs, svc = _svc((
        RequestClass("interactive", priority=3, max_inflight=1,
                     queue_depth=0),
        RequestClass("batch", priority=2),
    ))
    want = _reference(t, fs, [np.arange(40)])[0]
    with svc:
        fe = FeatureFrontend(svc)
        r = fe.handle({"op": "featurize", "rows": np.arange(40),
                       "klass": "batch", "tenant": "app"})
        assert r["ok"] and isinstance(r["ticket"], int)
        out = fe.handle({"op": "result", "ticket": r["ticket"],
                         "timeout": 30})
        assert out["ok"]
        np.testing.assert_array_equal(out["features"], want)
        # stats endpoint must serialize strictly (the inf regression)
        st = fe.handle({"op": "stats"})
        assert st["ok"]
        json.dumps(st["stats"], allow_nan=False)
        # typed failure paths come back as tagged responses, not raises
        assert fe.handle({"op": "transmogrify"})["error"] == "bad_request"
        assert fe.handle({"op": "result", "ticket": 12345}
                         )["error"] == "unknown_ticket"
        assert fe.handle({"op": "featurize", "rows": [0, 1],
                          "klass": "nope"})["error"] == "bad_request"
        svc.pause()
        t1 = fe.handle({"op": "featurize", "rows": np.arange(8),
                        "klass": "interactive"})
        assert t1["ok"]
        over = fe.handle({"op": "featurize", "rows": np.arange(8),
                          "klass": "interactive", "tenant": "greedy"})
        assert over["error"] == "overloaded"
        assert over["klass"] == "interactive"
        assert over["tenant"] == "greedy"
        assert over["retry_after_ms"] > 0
        svc.resume()
        fe.collect()


# -- randomized sweep (nightly raises FRONTEND_SWEEP_SEEDS) --------------------------
@pytest.mark.parametrize("seed", range(int(
    os.environ.get("FRONTEND_SWEEP_SEEDS", 2))))
def test_frontend_sweep_mixed_classes_bit_exact(seed):
    """Randomized mixed-class traffic through the front door: whatever
    the class mix and admission pressure, every admitted ticket resolves
    bit-exact vs the fault-free reference and the ledger balances
    (availability 1.0, nothing pending, histogram covers everything)."""
    rng = np.random.default_rng(100 + seed)
    t, fs, svc = _svc((
        RequestClass("interactive", priority=3, coalesce=1, linger_us=0,
                     max_inflight=64, queue_depth=64),
        RequestClass("batch", priority=2, max_inflight=64, queue_depth=64),
        RequestClass("background", priority=1, aging_s=0.01,
                     max_inflight=64, queue_depth=64),
    ))
    names = ("interactive", "batch", "background")
    reqs = []
    for _ in range(24):
        lo = int(rng.integers(0, 2900))
        n = int(rng.integers(8, 96))
        reqs.append((np.arange(lo, min(lo + n, 3000)),
                     names[int(rng.integers(0, 3))]))
    want = _reference(t, fs, [r for r, _ in reqs])
    with svc:
        fe = FeatureFrontend(svc)
        tks = [fe.submit(r, klass=k, tenant=f"t{i % 3}")
               for i, (r, k) in enumerate(reqs)]
        got = [fe.result(tk, timeout=60) for tk in tks]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    st = fe.stats()
    assert st["availability_admitted"] == 1.0
    assert sum(c["outstanding"] for c in st["classes"].values()) == 0
    assert sum(c["pending"] for c in st["classes"].values()) == 0
    assert svc.stats["latency_samples_total"] == 24
    ts = svc.throughput_stats(1.0)
    assert ts["availability"] == 1.0 and ts["pending"] == 0
    json.dumps(st, allow_nan=False)
