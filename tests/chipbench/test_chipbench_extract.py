"""``lineitem-extract``: the filtered pull at rehearsal size on the CPU,
faults planted where the system serves its rows turn ``correct`` false,
the parameter walk keeps qgen's mix, and the cell's readers read its
counters and spans."""
from __future__ import annotations

import gzip
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_helpers import BIG_SEED, ROOT, run_cell
from conftest import SHIFTED_ONEHOT

CELL = "lineitem-extract"

# the last served row of every request is dropped: the scan's match
# count reads one short
DROPPED_ROW = """
from repro.core import pipeline
_ranks = pipeline.FeatureExecutor.where_ranks
def altered(self, pred):
    ranks, cnt = _ranks(self, pred)
    return ranks, max(cnt - 1, 0)
pipeline.FeatureExecutor.where_ranks = altered
"""


def reader(name):
    from chipbench.run import Bench
    return Bench(ROOT).reader(name)


def ref():
    from chipbench.run import Bench
    return Bench(ROOT).reference("tpch_lineitem_sf10_extract")


def test_rehearsal_is_correct_and_serves_through_where_lanes(tmp_path):
    code = ("import sys\nfrom chipbench.run import main\n"
            "sys.exit(main(sys.argv[1:]))")
    rc, last, out, err = run_cell(
        tmp_path, "--workload", CELL, "--seed", str(BIG_SEED + 7),
        "--seconds", "2", "--trace", "0", "--rehearse", code=code)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True, last["check"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["check"]) == {"count_mismatches", "exact_mismatches",
                                  "cont_max_rel_gap", "colsum_max_rel_gap"}
    assert "compiles_in_window=0" in out


@pytest.mark.parametrize("fault", ["dropped_row", "shifted_onehot"])
def test_planted_faults_are_not_correct(tmp_path, fault):
    plant = {"dropped_row": DROPPED_ROW, "shifted_onehot": SHIFTED_ONEHOT}
    code = ("import sys\n" + plant[fault]
            + "from chipbench.run import main\nsys.exit(main(sys.argv[1:]))")
    rc, last, out, err = run_cell(
        tmp_path, "--workload", CELL, "--seed", str(BIG_SEED + 2),
        "--seconds", "1", "--trace", "0", "--rehearse", code=code)
    assert rc == 0, err[-3000:]
    assert last is not None and last["correct"] is False, last
    failing = {k for k, c in last["check"].items() if c["value"] > c["limit"]}
    want = {"dropped_row": "count_mismatches",
            "shifted_onehot": "exact_mismatches"}[fault]
    assert want in failing, last["check"]


def test_control_fails_every_limit_set_from_readings():
    from chipbench import control
    for seed in (3, BIG_SEED):
        numbers = control.read(CELL, seed, 1.0, rehearse=True)
        for name in ("cont_max_rel_gap", "colsum_max_rel_gap"):
            assert numbers[name]["fails"], numbers
        for name in ("count_mismatches", "exact_mismatches"):
            assert numbers[name]["value"] == 0, numbers


def test_each_stream_walks_qgens_triples_with_both_quantities_paired():
    from chipbench.load import rng_for
    _, r = ref()
    walks = []
    for c in range(2):
        rng = rng_for(BIG_SEED, 20, c)
        walks.append([r.query_params("q6", rng) for _ in range(160)])
    for walk in walks:
        for lap in (walk[:80], walk[80:]):
            triples = {(p["year"], p["discount"], p["quantity"])
                       for p in lap}
            assert len(triples) == 80
            assert {t[0] for t in triples} == set(range(1993, 1998))
            assert {t[1] for t in triples} == set(range(2, 10))
        for a, b in zip(walk[::2], walk[1::2]):
            assert (a["year"], a["discount"]) == (b["year"], b["discount"])
            assert {a["quantity"], b["quantity"]} == {24, 25}
        assert walk[:80] != walk[80:]
    assert walks[0] != walks[1]
    again = rng_for(BIG_SEED, 20, 0)
    assert [r.query_params("q6", again) for _ in range(160)] == walks[0]


# lanes of 96 matches, so a rehearsal request spans several lanes and
# its last lane is partly empty
SMALL_LANES = """
from repro.core import pipeline
pipeline.WHERE_LANE = 96
"""

# the where path takes a shape from the match count, as a compaction at
# a cap of the count does: every request of a new count compiles
COUNT_SHAPED = """
import jax, jax.numpy as jnp
from repro.core import pipeline
_ranks = pipeline.FeatureExecutor.where_ranks
def altered(self, pred):
    ranks, cnt = _ranks(self, pred)
    jax.block_until_ready(jax.jit(lambda r: r[:cnt + 1])(ranks))
    return ranks, cnt
pipeline.FeatureExecutor.where_ranks = altered
"""


@pytest.mark.parametrize("plant", ["small_lanes", "count_shaped"])
def test_set_up_stops_a_where_path_that_compiles_per_count(tmp_path, plant):
    code = ("import sys\n"
            + {"small_lanes": SMALL_LANES, "count_shaped": COUNT_SHAPED}[plant]
            + "from chipbench.run import main\nsys.exit(main(sys.argv[1:]))")
    rc, last, out, err = run_cell(
        tmp_path, "--workload", CELL, "--seed", str(BIG_SEED + 3),
        "--seconds", "1", "--trace", "0", "--rehearse", code=code)
    if plant == "small_lanes":
        assert rc == 0, err[-3000:]
        assert last["correct"] is True and "compiles_in_window=0" in out
    else:
        assert rc != 0 and last is None and "window:" not in out
        assert "compiles its where path per match count" in err


def test_the_probes_are_other_parameter_sets_and_run_once():
    from chipbench.load import part
    _, r = ref()
    op = part(ROOT, "ops", "where")
    asked = []
    feats = np.ones((300, 36), np.float32)
    frontend = SimpleNamespace(
        submit=lambda where, klass: asked.append((where, klass)),
        result=lambda ticket: feats)
    dep = SimpleNamespace(
        service=object(), frontend=frontend, ref=r,
        program=SimpleNamespace(where=lambda q, p: tuple(p.values())))
    for p in r.PARAMS[:3]:
        assert op.ask(dep, "q6", p)["count"] == 300
    probes = [w for w, _ in asked[:op.PROBES + 1]]
    assert len(set(probes)) == op.PROBES + 1
    assert len(asked) == op.PROBES + 1 + 3
    assert {k for _, k in asked} == {"batch"}
    assert len(r.every_params("q6")) == 80
    with pytest.raises(KeyError):
        r.every_params("q1")


def test_reference_features_follow_the_definitions():
    cfg, r = ref()
    from chipbench.run import rehearsed
    cfg = rehearsed(cfg, True)
    raw = r.generate(cfg, 5, True)
    p = {"year": 1995, "discount": 5, "quantity": 24}
    rows = np.flatnonzero(r.q6_mask(raw, p))
    m = r._moments(raw)
    f = r.features(raw, rows, m)
    assert f.shape == (rows.size, r.OUT_DIM) == (rows.size, 36)
    np.testing.assert_allclose(f.sum(axis=0), r.column_sums(raw, rows, m),
                               rtol=1e-12)
    assert (f[:, r.EXACT] == np.round(f[:, r.EXACT])).all()
    assert (f[:, 3:35].sum(axis=1) == 5).all()          # five one-hots
    assert (f[:, 35] == 3).all()                        # shipped in 1995
    assert (f[:, 0] < 0).all()                          # quantity < mean
    want = r.compare_answers(raw, [(p, {
        "count": rows.size, "pos": np.arange(rows.size),
        "rows": f.astype(np.float32), "sums": f.sum(axis=0)})])
    assert want["count_mismatches"] == want["exact_mismatches"] == 0
    assert 0 < want["cont_max_rel_gap"] < 1e-7
    pos = r.sample_positions(p, 200_000)
    assert pos.size > r.SAMPLE and {0, 65535, 65536, 199_999} <= set(pos)


def test_counter_and_trace_readers():
    obs = SimpleNamespace(
        stats_delta={"rows": 900, "launched_rows": 1024, "launches": 4},
        trace=SimpleNamespace(
            chips=1, window_s=2.0, idle_share=0.25,
            stage=lambda pat: (0.5, 2) if "_packed_split_rows" in pat
            else (0.25, 2) if "_where_compact" in pat else (0.0, 0)),
        work={"rows": 1 << 20, "columns": 8, "table_bytes_per_row": 144,
              "out_bytes_per_row": 144},
        device_kind="TPU v5 lite")
    assert reader("launch_row_use.extract").read(obs) == \
        pytest.approx(100 * 900 / 1024)
    assert reader("device_idle.extract").read(obs) == pytest.approx(25.0)
    # two gathers of 225 served rows each: 2 x 225 x (4 + 32 + 144 + 144)
    # bytes in 0.5 s at 819 GB/s
    assert reader("gather_roofline.extract").read(obs) == pytest.approx(
        100 * 2 * 225 * 324 / 819e9 / 0.5)
    # two compactions of 256 rows: 20 probes and a row each, 4 bytes
    assert reader("compact_roofline.extract").read(obs) == pytest.approx(
        100 * 2 * 256 * 21 * 4 / 819e9 / 0.25)
    none = SimpleNamespace(stats_delta={}, trace=None)
    for name in ("launch_row_use.extract", "device_idle.extract",
                 "gather_roofline.extract", "compact_roofline.extract",
                 "where_ms.extract"):
        assert reader(name).read(none) is None, name


def test_where_ms_reads_the_mean_serve_where_span(tmp_path, monkeypatch):
    from jax.profiler import ProfileData
    metric = reader("where_ms.extract")
    obs = SimpleNamespace(trace=SimpleNamespace(window_s=1.0))
    monkeypatch.setattr(metric, "__file__",
                        str(tmp_path / "chipbench" / "metrics" / "m.py"))
    assert metric.read(obs) is None                   # no trace written
    d = tmp_path / ".chipbench" / "trace" / CELL / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(gzip.open(
        ROOT / "tests" / "chipbench" / "data" / "lookup_spans.xplane.pb.gz")
        .read())
    assert metric.read(obs) is None                   # a trace without it
    ev = [SimpleNamespace(name="serve.where", duration_ns=n)
          for n in (2_000_000, 4_000_000)]
    prof = SimpleNamespace(planes=[SimpleNamespace(
        name="/host:CPU", lines=[SimpleNamespace(name="python3",
                                                 events=ev)])])
    monkeypatch.setattr(ProfileData, "from_file", lambda path: prof)
    assert metric.read(obs) == pytest.approx(3.0)
