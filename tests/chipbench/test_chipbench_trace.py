"""The trace reduction, on small traces recorded on a TPU v5e and kept in
``data/``: two rehearsal-size runs (``--rehearse --trace 1 --seconds 0.5``)
of ``criteo-lookup`` (seed 11, 20 requests) and ``lineitem-q6`` (seed 11,
205 queries)."""
from __future__ import annotations

import gzip
import json
import pathlib
from types import SimpleNamespace

import pytest

from chipbench_helpers import ROOT

DATA = pathlib.Path(__file__).resolve().parent / "data"


def profile(name: str):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        gzip.open(DATA / f"{name}.xplane.pb.gz").read())


@pytest.fixture(scope="module")
def lookup():
    from chipbench import trace
    return trace.reduce_profile(profile("lookup_tiny"), 0.48892443799999796)


@pytest.fixture(scope="module")
def q6():
    from chipbench import trace
    return trace.reduce_profile(profile("q6_tiny"), 0.5054030609999955)


def test_device_busy_time_is_the_union_of_op_intervals(lookup, q6):
    # the values the recorded runs printed as busy_s
    assert lookup.chips == 1 and q6.chips == 1
    assert lookup.busy_s == pytest.approx(0.023658626, rel=1e-9)
    assert q6.busy_s == pytest.approx(0.079354484, rel=1e-9)
    assert 0 < lookup.idle_share < 1 and 0 < q6.idle_share < 1


def test_stages_are_found_by_the_program_s_jit_names(lookup, q6):
    seconds, n = lookup.stage(r"_packed_split_rows")
    assert n == 19 and lookup.busy_s <= seconds <= lookup.window_s
    assert q6.stage(r"_scan_body")[1] == q6.stage(r"_masked_counts_split")[1] \
        == 205
    assert lookup.stage(r"_scan_body") == (0.0, 0)


def test_breakdown_lists_are_capped(lookup, q6):
    for t in (lookup, q6):
        assert 0 < len(t.top_ops) <= 10 and len(t.idle_gaps) <= 10
        assert all(s > 0 for _, s in t.top_ops)
        assert [s for _, s in t.idle_gaps] == sorted(
            (s for _, s in t.idle_gaps), reverse=True)
    assert q6.idle_gaps[0][0] == "python3: np.asarray(jax.Array)"


def test_roofline_readers_on_recorded_traces(lookup, q6):
    from chipbench.run import Bench, rehearsed
    bench = Bench(ROOT)
    cfg, ref, _ = bench.config("tpch_lineitem_sf10")
    work = ref.work(ref.generate(rehearsed(cfg, True), 11, True))
    obs = SimpleNamespace(trace=q6, work=work, device_kind="TPU v5 lite")
    for name in ("scan_roofline.q6", "hist_roofline.q6"):
        share = bench.reader(name).read(obs)
        assert 0 < share <= 100, name
    assert bench.reader("device_idle.q6").read(obs) == pytest.approx(
        100 * q6.idle_share)
    cfg, ref, _ = bench.config("criteo_kaggle_day")
    work = ref.work(ref.generate(rehearsed(cfg, True), 11, True))
    obs = SimpleNamespace(trace=lookup, work=work, device_kind="TPU v5 lite",
                          rows_traced=7000)
    share = bench.reader("gather_roofline.lookup").read(obs)
    assert 0 < share <= 100
    obs.device_kind = "cpu"
    from chipbench import peaks
    with pytest.raises(peaks.UnknownDevice):
        bench.reader("gather_roofline.lookup").read(obs)
    obs.trace = None
    assert bench.reader("gather_roofline.lookup").read(obs) is None


def test_union_and_gaps_on_a_hand_made_profile():
    from chipbench import trace

    def ev(name, start, dur):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)

    def line(name, events):
        return SimpleNamespace(name=name, events=events)

    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Ops", [ev("a", 0, 100), ev("b", 50, 100), ev("c", 400, 100)]),
        line("XLA Modules", [ev("jit_f(1)", 0, 150), ev("jit_g(2)", 400, 100)]),
        line("Async XLA Ops", [ev("copy", 0, 10_000)])])
    host = SimpleNamespace(name="/host:CPU", lines=[
        line("python3", [ev("outer", 100, 500), ev("inner", 200, 100)])])
    t = trace.reduce_profile(SimpleNamespace(planes=[dev, host]), 1e-6)
    assert t.busy_s == pytest.approx(250e-9)     # [0,150) and [400,500)
    assert t.stage(r"jit_f") == (pytest.approx(150e-9), 1)
    assert t.idle_gaps == [("python3: inner", pytest.approx(250e-9))]
    assert t.idle_share == pytest.approx(0.75)
    json.dumps(t.top_ops)


def test_the_heartbeat_reads_its_longest_sleep_before_the_trace():
    import threading
    import time

    from chipbench.load import HEARTBEAT_S, Tracer
    tracer = Tracer(None)
    beat = threading.Thread(target=tracer._beat)
    beat.start()
    time.sleep(0.2)
    tracer.t_host = time.perf_counter()     # the profiler starts here
    before = tracer.stall_max_s
    time.sleep(0.1)
    tracer._closed.set()
    beat.join()
    assert HEARTBEAT_S <= before < 0.2
    assert tracer.stall_max_s == before
