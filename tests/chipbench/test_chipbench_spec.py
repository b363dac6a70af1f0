"""``BENCHMARK.json`` keeps to the benchmark's contract, its files exist
where the harness looks for them by name, and the yardstick's pieces
(peaks, work, schedules, configurations) behave."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from chipbench_helpers import BIG_SEED, ROOT, bench_spec

SPEC = bench_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = SPEC["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    total = (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entry_keys():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


def test_every_config_is_used_and_its_files_exist():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("chipbench/configs/")
        cfg = json.loads(path.read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert "assumed" in cfg and "limits" in cfg and "source" in cfg
        for suffix in (".py", "_ref.py"):
            assert (path.parent / f"{c['name']}{suffix}").is_file()
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in SPEC["workloads"]:
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics_have_readers_bounds_and_moves():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
        if m["name"] != "setup_s":
            assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for cell in cells:       # each cell: setup_s, another, a per-layer one
        reported = [n for n, m in e2e.items() if cell in m.get("workloads",
                                                               cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])
    for m in SPEC["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e and "\n" not in m["layer"]
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)


def test_unknown_device_kind_is_an_error():
    from chipbench import peaks
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e.hbm_bytes_per_s == 819e9 and "TPU v5e" in v5e.source
    # 819 MB at the HBM peak takes 1 ms: a 2 ms stage is at half its roofline
    assert peaks.roofline_share(0, 819e6, 2e-3, v5e) == pytest.approx(50.0)


def test_work_counts_only_what_a_stage_must_move():
    from chipbench import work
    assert [work.device_width(k) for k in (2, 3, 50, 2526, 1351208)] == \
        [1, 2, 8, 16, 32]
    ops, nbytes = work.gather(10, 3, 64, 64)
    assert nbytes == 10 * (4 + 12 + 64 + 64)
    assert work.scan(64, [16, 4, 8])[1] == 64 * 28 / 8 + 8
    assert work.hist(64, 32, 5)[1] == 64 * 4 + 8 + 20


def test_open_schedules_offer_the_same_work_under_every_seed():
    from chipbench.load import part
    mix = json.loads((ROOT / "chipbench/traffic/lookup_open.json").read_text())
    schedule = part(ROOT, "loops", mix["loop"]).schedule
    a = schedule(mix, ROOT, 1, 2.0, 100_000)
    b = schedule(mix, ROOT, BIG_SEED, 2.0, 100_000)
    assert a.due.size == b.due.size == round(mix["rate_per_s"] * 2.0)
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    assert not np.array_equal(a.sizes, b.sizes)
    for s in (a, b):
        assert 0 < s.due.min() and s.due.max() < 2.0
        assert s.sizes.min() >= 64 and s.sizes.max() <= 1024
        assert s.rows.min() >= 0 and s.rows.max() < 100_000
        assert s.sizes.argmax() in s.check
        assert s.check.size == mix["check_sample"]


MIXES = sorted(p.stem for p in (ROOT / "chipbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_every_mix_names_parts_that_exist(mix):
    """A mix is data: its loop and each law or op it names is a file."""
    spec = json.loads((ROOT / "chipbench" / "traffic" / f"{mix}.json")
                      .read_text())
    assert spec["name"] == mix
    assert (ROOT / "chipbench" / "loops" / f"{spec['loop']}.py").is_file()
    for key, kind in (("arrivals", "arrivals"), ("rows_per_request", "sizes"),
                      ("row_ids", "keys")):
        if key in spec:
            path = ROOT / "chipbench" / kind / f"{spec[key]['dist']}.py"
            assert path.is_file(), path
    if "op" in spec and spec["loop"] != "open":
        assert (ROOT / "chipbench" / "ops" / f"{spec['op']}.py").is_file()


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_configs_rebuild_identically_from_the_seed(config):
    from chipbench.run import Bench, rehearsed
    cfg, ref, prog = Bench(ROOT).config(config)
    cfg = rehearsed(cfg, True)
    one, two = (ref.generate(cfg, BIG_SEED, True) for _ in range(2))
    other = ref.generate(cfg, 5, True)

    def arrays(raw):
        out = []
        for v in vars(raw).values():
            out += v if isinstance(v, list) else [v]
        return [np.asarray(x) for x in out]

    assert all(np.array_equal(x, y) for x, y in zip(arrays(one),
                                                      arrays(two)))
    assert not all(np.array_equal(x, y) for x, y in zip(arrays(one),
                                                          arrays(other)))
    p1, p2 = prog.plan(cfg, one), prog.plan(cfg, two)
    assert p1.device_bits == p2.device_bits
    assert all(np.array_equal(x, y) for x, y in zip(p1.packed_words,
                                                      p2.packed_words))
    bits = ref.work(one)["device_bits"]
    if isinstance(bits, dict):
        bits = [bits[c] for c in p1.columns]
    assert p1.device_bits == bits


def test_the_sweep_knee_needs_no_refusal_and_no_growing_backlog():
    from chipbench.sweep import steady
    row = {"failed": 0, "p99_ms": 40.0, "backlog_mid": 4, "backlog_close": 1}
    assert steady(row, 5000.0)
    assert not steady({**row, "failed": 1}, 5000.0)
    assert not steady({**row, "p99_ms": 6000.0}, 5000.0)
    assert not steady({**row, "backlog_close": 17}, 5000.0)
