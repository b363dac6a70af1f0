"""Program spans and the counters beside them: the seven spans land in a
profiler trace with their metadata and nesting, a span costs nothing with
the profiler off, and the pump counts every launched lane and every
chunk's queue wait."""
import glob
import itertools
import time
import tracemalloc

import jax
import numpy as np
import pytest

from repro.columnar import query as Q
from repro.columnar.column import Column
from repro.columnar.table import Table
from repro.core import FeaturePlan
from repro.core.feature_spec import FeatureSet
from repro.serve import FeatureFrontend, FeatureService, default_classes
from repro.spans import OFF, ids, span

SPANS = ("serve.submit", "pump.wait", "pump.launch", "pump.retire",
         "pump.fetch", "query.agg_where", "query.fetch")
PRED = Q.gt("age", 60)


def _plan(n=4000):
    rng = np.random.default_rng(3)
    age = rng.integers(18, 91, n)
    device = rng.integers(0, 5, n)
    t = Table({"age": Column.from_data(age, "age", imcu_rows=1000),
               "device": Column.from_data(device, "device",
                                          imcu_rows=1000)})
    fs = FeatureSet().add("age", "zscore").add("device", "onehot")
    return FeaturePlan(t, fs, packed=True), age


def _events(trace_dir):
    """{span name: [(line key, start, end, metadata)]} of the trace's host
    planes; the line key tells threads apart."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = {name: [] for name in SPANS}
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append(
                        ((p, i), ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


def _inside(child, parents):
    key, s, e, _ = child
    return any(k == key and ps <= s and e <= pe for k, ps, pe, _ in parents)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A packed service behind the front door, requests one at a time and
    one masked aggregate, under the profiler with the benchmark's options
    (host events at level 2, no Python tracer)."""
    plan, age = _plan()
    svc = FeatureService(plan, classes=default_classes())
    fe = FeatureFrontend(svc)
    try:
        for n in (40, 200):                         # compile outside
            fe.result(fe.submit(np.arange(n), klass="interactive"), 60)
        svc.agg_where(PRED, "age", "sum")
        before = dict(svc.stats)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        d = tmp_path_factory.mktemp("trace")
        jax.profiler.start_trace(str(d), profiler_options=opts)
        try:
            tickets = []
            for n in (40, 200, 40, 200):
                tickets.append(fe.submit(np.arange(n) + 7,
                                         klass="interactive"))
                fe.result(tickets[-1], timeout=60)
                time.sleep(0.01)
            total = svc.agg_where(PRED, "age", "sum")
        finally:
            jax.profiler.stop_trace()
        after = dict(svc.stats)
    finally:
        fe.shutdown()
    assert total == float(age[age > 60].sum())
    return _events(d), tickets, before, after, svc.coalesce


def test_every_span_lands_in_the_trace(traced):
    ev, tickets, *_ = traced
    assert {n for n in SPANS if ev[n]} == set(SPANS)
    assert len(ev["serve.submit"]) == 4
    assert len(ev["query.agg_where"]) == len(ev["query.fetch"]) == 1
    (_, _, _, agg), = ev["query.agg_where"]
    assert agg["column"] == "age" and agg["agg"] == "sum"
    (_, _, _, fetch), = ev["query.fetch"]
    assert fetch["nbytes"] > 0
    for *_, meta in ev["pump.fetch"]:
        assert meta["nbytes"] > 0


def test_children_nest_in_their_parents(traced):
    ev, *_ = traced
    assert all(_inside(c, ev["pump.retire"]) for c in ev["pump.fetch"])
    assert all(_inside(c, ev["query.agg_where"]) for c in ev["query.fetch"])
    # launches and retires run on the pump, submits on the caller's thread
    pump = {k for k, *_ in ev["pump.launch"] + ev["pump.retire"]
            + ev["pump.wait"]}
    assert len(pump) == 1
    assert not pump & {k for k, *_ in ev["serve.submit"]}


def test_a_ticket_is_followed_from_submit_to_launch_and_retire(traced):
    ev, tickets, _, _, coalesce = traced
    submitted = [meta["ticket"] for *_, meta in ev["serve.submit"]]
    assert submitted == tickets
    assert [m["rows"] for *_, m in ev["serve.submit"]] == [40, 200, 40, 200]

    def ticket_ids(spans):
        return [int(t) for *_, m in spans for t in str(m["tickets"]).split()]

    assert sorted(ticket_ids(ev["pump.launch"])) == sorted(tickets)
    assert sorted(ticket_ids(ev["pump.retire"])) == sorted(tickets)
    launch_seq = {m["seq"] for *_, m in ev["pump.launch"]}
    assert {m["seq"] for *_, m in ev["pump.retire"]} == launch_seq
    assert {m["seq"] for *_, m in ev["pump.fetch"]} == launch_seq
    for *_, m in ev["pump.launch"]:
        assert m["klass"] == "interactive" and m["lanes_used"] == 1
        # the class's depth (1), not the service's
        assert m["lanes"] == 1 < coalesce
        assert m["bucket"] in (64, 256) and m["shard"] == 0


def test_launched_rows_counts_every_lane_of_the_traced_launches(traced):
    ev, _, before, after, coalesce = traced
    launched = after["launched_rows"] - before["launched_rows"]
    assert launched == sum(m["lanes"] * m["bucket"]
                           for *_, m in ev["pump.launch"])
    assert launched == 2 * 64 + 2 * 256 < coalesce * (2 * 64 + 2 * 256)
    assert after["rows"] - before["rows"] == 480 < launched


def test_span_is_the_shared_no_op_without_a_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("pump.launch", seq=1, shard=0) is OFF
    assert span("pump.wait") is OFF
    with span("x") as sp:
        assert sp is OFF
        sp.set_metadata(tickets="1 2")
    assert ids([1, 2, 7]) == "1 2 7"

    def calls(n):
        for _ in itertools.repeat(None, n):
            with span("pump.launch", seq=123456, shard=0) as sp:
                sp.set_metadata(nbytes=4096)

    calls(100)
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        calls(10_000)
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # 10,000 calls: a byte kept or built per call would show 10 KB
    assert now - base < 1024
    assert peak - base < 1024


def test_launched_rows_is_coalesce_times_bucket_per_launch():
    plan, _ = _plan()
    with FeatureService(plan, classes=default_classes()) as svc:
        fe = FeatureFrontend(svc)
        for i in range(12):
            fe.result(fe.submit(np.arange(i, i + 50), klass="interactive"),
                      timeout=60)
        st = svc.throughput_stats(1.0)
    assert st["launches"] == 12
    # interactive's coalesce depth is 1: one 64-row lane a launch
    rc = svc.classes["interactive"]
    assert rc.coalesce == 1 < svc.coalesce
    assert st["launched_rows"] == rc.coalesce * 64 * st["launches"]
    assert st["rows"] == 600


def test_queue_wait_is_reported_per_class_and_reset():
    plan, _ = _plan()
    with FeatureService(plan, classes=default_classes()) as svc:
        fe = FeatureFrontend(svc)
        svc.pause()
        tickets = [fe.submit(np.arange(64), klass="batch")
                   for _ in range(3)]
        time.sleep(0.05)                 # every chunk waits >= 50 ms
        svc.resume()
        for t in tickets:
            fe.result(t, timeout=60)
        batch = svc.class_stats()["batch"]
        assert batch["queue_p50_ms"] >= 50.0
        assert batch["queue_p99_ms"] >= batch["queue_p50_ms"]
        assert svc.class_stats()["interactive"]["queue_p99_ms"] == 0.0
        stats = fe.handle({"op": "stats"})["stats"]["classes"]["batch"]
        assert stats["queue_p99_ms"] == batch["queue_p99_ms"]
        svc.reset_latency_window()
        batch = svc.class_stats()["batch"]
        assert batch["queue_p50_ms"] == batch["queue_p99_ms"] == 0.0
