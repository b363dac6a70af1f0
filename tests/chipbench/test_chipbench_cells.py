"""Every cell runs through the one command at rehearsal size on the CPU and
prints a last line that meets the benchmark's contract; without the
rehearsal flag, or without the system under test, it prints no result."""
from __future__ import annotations

import shutil

import pytest

from chipbench_helpers import BIG_SEED, ROOT, bench_spec, run_cell

SPEC = bench_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_prints_a_contract_line(tmp_path, cell, trace):
    rc, last, out, err = run_cell(
        tmp_path, "--workload", cell, "--seed", str(BIG_SEED),
        "--seconds", "1", "--trace", str(trace), "--rehearse")
    assert rc == 0, err[-3000:]
    assert last is not None, out[-2000:]
    assert list(last)[-1] == "check"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in last
    assert last["correct"] is True, last["check"]
    assert last["attempted"] > 0 and last["failed"] == 0
    dev = last["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert isinstance(dev["memory_peak_bytes"], int)
    for name, c in last["check"].items():
        assert c["value"] <= c["limit"], name
        assert f"check {name} " in err.strip().splitlines()[-len(
            last["check"]):][list(last["check"]).index(name)]
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m for m in SPEC[kind] if applies(m, cell)}
    assert set(last["metrics"]) <= set(allowed)
    for name, m in last["metrics"].items():
        assert m["unit"] == allowed[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        assert dev["window_s"] > 0 and "busy_s" in dev
        assert len(last["breakdown"]["device_ops"]) <= 10
    else:
        assert set(last["metrics"]) == set(allowed)
        assert last["metrics"]["setup_s"]["value"] > 0


def test_without_the_rehearsal_flag_the_cpu_is_refused(tmp_path):
    rc, last, out, err = run_cell(
        tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0")
    assert rc != 0
    assert last is None and "not a TPU" in err


def test_benchmark_files_alone_print_no_result(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, bare / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path[:] = [p for p in sys.path if 'src' not in p]"
            "; sys.path.insert(0, '.'); from chipbench.run import main; "
            "sys.exit(main(sys.argv[1:]))")
    rc, last, out, err = run_cell(
        tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0", "--rehearse", code=code, cwd=bare)
    assert rc != 0
    assert last is None
