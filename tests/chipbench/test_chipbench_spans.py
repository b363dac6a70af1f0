"""The span readers (``chipbench/spans.py``) and the metrics on them: on
hand-made intervals and profiles, and on two small traces recorded on a
TPU v5e with the program's spans and kept in ``data/``: rehearsal-size
runs (``--rehearse --trace 1``) of ``criteo-lookup`` (seed 11, 0.5 s, 20
requests) and ``lineitem-q6`` (seed 11, 0.3 s, 106 queries)."""
from __future__ import annotations

import gzip
import math
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_helpers import ROOT

DATA = pathlib.Path(__file__).resolve().parent / "data"
LOOKUP = ("idle_host_share.lookup", "idle_fetch_share.lookup",
          "queue_wait_p99_ms.lookup", "launch_row_use.lookup")


def reader(name):
    from chipbench.run import Bench
    return Bench(ROOT).reader(name)


def span(start, end, line=(1, 0), **args):
    from chipbench.spans import Span
    return Span(start, end, args, line)


def program(idle=(), **spans):
    from chipbench.spans import NAMES, ProgramTrace
    named = {n: [] for n in NAMES}
    for name, items in spans.items():
        named[name.replace("_", ".", 1)] = list(items)
    return ProgramTrace(spans=named, idle=list(idle), chips=1)


@pytest.fixture
def serve(monkeypatch):
    """Route the readers' trace lookup to a hand-made program trace."""
    from chipbench import spans
    box = {}
    monkeypatch.setattr(spans, "for_cell",
                        lambda metric_file, cell: box.get(cell))
    return box


def test_covered_counts_each_interval_s_overlap_with_the_span_union():
    from chipbench.spans import covered
    idle = [(0, 100), (200, 300), (400, 500)]
    assert covered(idle, [(50, 250), (450, 460)]) == 50 + 50 + 10
    # overlapping spans count once; spans outside every interval count 0
    assert covered(idle, [(0, 60), (40, 100), (120, 180)]) == 100
    assert covered(idle, [span(90, 410)]) == 10 + 100 + 10
    assert covered(idle, []) == 0 and covered([], [(0, 10)]) == 0


def test_idle_is_split_into_pump_wait_fetch_and_the_host_rest(serve):
    # 1000 ns window; chip idle 300 ns in three gaps
    serve["criteo-lookup"] = program(
        idle=[(0, 100), (200, 300), (400, 500)],
        pump_wait=[span(50, 250)], pump_fetch=[span(450, 480)],
        pump_launch=[span(100, 120)])
    obs = SimpleNamespace(trace=SimpleNamespace(window_s=1e-6))
    assert reader("idle_host_share.lookup").read(obs) == pytest.approx(20.0)
    assert reader("idle_fetch_share.lookup").read(obs) == pytest.approx(3.0)


def test_self_time_leaves_out_children_on_the_same_thread(serve):
    from chipbench.spans import self_ns
    parent = span(0, 100, line=(1, 2))
    kids = [span(10, 30, line=(1, 2)), span(50, 60, line=(1, 2)),
            span(20, 90, line=(1, 3))]             # another thread
    assert self_ns(parent, kids) == 70
    serve["lineitem-q6"] = program(
        query_agg_where=[parent, span(200, 400, line=(1, 3))],
        query_fetch=kids + [span(250, 400, line=(1, 3))])
    obs = SimpleNamespace(trace=SimpleNamespace(window_s=1.0))
    # (70 + 50) / 2 ns in ms
    assert reader("query_host_ms.q6").read(obs) == pytest.approx(60e-6)


def test_counter_readers():
    obs = SimpleNamespace(stats_delta={"rows": 135, "launched_rows": 1024},
                          service_class={"samples": 9, "queue_p99_ms": 2.5})
    assert reader("launch_row_use.lookup").read(obs) == pytest.approx(
        100 * 135 / 1024)
    assert reader("queue_wait_p99_ms.lookup").read(obs) == 2.5


def test_readers_read_nothing_from_a_program_without_spans_or_counters(
        serve):
    obs = SimpleNamespace(trace=SimpleNamespace(window_s=1.0),
                          stats_delta={"rows": 10, "padded_rows": 5},
                          service_class={"samples": 9, "p99_ms": 4.0})
    serve["criteo-lookup"] = program(idle=[(0, 10)],
                                     pump_wait=[span(0, 5)])
    serve["lineitem-q6"] = program(idle=[(0, 10)])
    for name in LOOKUP + ("query_host_ms.q6",):
        assert reader(name).read(obs) is None, name
    obs.trace = None                         # an untraced run
    serve["criteo-lookup"] = program(idle=[(0, 10)],
                                     pump_launch=[span(0, 5)])
    assert reader("idle_host_share.lookup").read(obs) is None
    assert reader("idle_fetch_share.lookup").read(obs) is None


def test_from_profile_on_a_hand_made_profile():
    from chipbench.spans import from_profile

    def ev(name, start, dur, **stats):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                               stats=list(stats.items()))

    def line(name, events):
        return SimpleNamespace(name=name, events=events)

    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Ops", [ev("a", 0, 100), ev("b", 50, 100),
                         ev("c", 400, 100), ev("d", 600, 50)]),
        line("XLA Modules", [ev("jit_f(1)", 0, 700)])])
    host = SimpleNamespace(name="/host:CPU", lines=[
        line("python3", [ev("pump.wait", 150, 250),
                         ev("pump.launch", 420, 30, seq=3, tickets="1 2"),
                         ev("np.asarray(jax.Array)", 500, 10)]),
        line("python3", [ev("serve.submit", 100, 20, ticket=1)])])
    t = from_profile(SimpleNamespace(planes=[dev, host]))
    assert t.chips == 1 and t.idle == [(150, 400), (500, 600)]
    assert t.idle_ns() == 350
    (w,) = t.named("pump.wait")
    assert (w.start, w.end, w.line) == (150, 400, (1, 0))
    assert t.named("pump.launch")[0].args == {"seq": 3, "tickets": "1 2"}
    assert t.named("serve.submit")[0].line == (1, 1)
    assert "np.asarray(jax.Array)" not in t.spans


def test_for_cell_finds_the_trace_where_a_run_writes_it(tmp_path):
    from chipbench import spans
    metric = tmp_path / "chipbench" / "metrics" / "m.lookup.py"
    assert spans.for_cell(str(metric), "criteo-lookup") is None
    d = tmp_path / ".chipbench" / "trace" / "criteo-lookup" / "plugins" \
        / "profile" / "1"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(
        gzip.open(DATA / "lookup_spans.xplane.pb.gz").read())
    t = spans.for_cell(str(metric), "criteo-lookup")
    assert t.chips == 1 and t.named("pump.launch")


# window_s the recorded runs printed
WINDOW = {"lookup": 0.49510288300000127, "q6": 0.3079097439999998}


def recorded(name):
    from jax.profiler import ProfileData

    from chipbench import spans, trace
    prof = ProfileData.from_serialized_xspace(
        gzip.open(DATA / f"{name}_spans.xplane.pb.gz").read())
    return trace.reduce_profile(prof, WINDOW[name]), spans.from_profile(prof)


def test_new_metrics_read_finite_values_on_recorded_chip_traces(serve):
    lookup, spans_l = recorded("lookup")
    q6, spans_q = recorded("q6")
    assert spans_l.chips == spans_q.chips == 1
    serve["criteo-lookup"], serve["lineitem-q6"] = spans_l, spans_q
    # the counters the trace's spans stand for: every submitted row, and
    # coalesce (4) x bucket per launch; queue wait from submit to launch
    submit = {s.args["ticket"]: s for s in spans_l.named("serve.submit")}
    launched = [(s, [int(t) for t in str(s.args["tickets"]).split()])
                for s in spans_l.named("pump.launch")]
    waits = [(s.start - submit[t].end) * 1e-6 for s, ts in launched
             for t in ts if t in submit]
    obs = SimpleNamespace(
        trace=lookup,
        stats_delta={"rows": sum(s.args["rows"] for s in submit.values()),
                     "launched_rows": sum(4 * s.args["bucket"]
                                          for s, _ in launched)},
        service_class={"samples": len(waits),
                       "queue_p99_ms": float(np.percentile(waits, 99))})
    values = {name: reader(name).read(obs) for name in LOOKUP}
    obs = SimpleNamespace(trace=q6)
    values["query_host_ms.q6"] = reader("query_host_ms.q6").read(obs)
    for name, v in values.items():
        assert v is not None and math.isfinite(v) and v >= 0, name
    assert 0 < values["launch_row_use.lookup"] <= 100
    device_idle = 100 * lookup.idle_share
    assert values["idle_host_share.lookup"] <= device_idle
    assert values["idle_fetch_share.lookup"] <= device_idle
    # every long idle gap is named by a program span or a JAX host event
    assert lookup.idle_gaps
    assert not any(label == "host idle (no event)"
                   for label, _ in lookup.idle_gaps)
