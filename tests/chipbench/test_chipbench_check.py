"""``correct`` comes out false when it should: the lower-precision control
fails every cell's limits, and a run whose served answers are altered
where the system produces them prints ``correct: false``."""
from __future__ import annotations

import pytest

from chipbench_helpers import BIG_SEED, bench_spec, run_cell

CELLS = [w["name"] for w in bench_spec()["workloads"]]

# (cell, a fault planted in the system under test before the run starts)
FAULTS = {
    "criteo-lookup": """
from repro.core import pipeline
_gather = pipeline._packed_split_rows
def altered(*a, **k):
    return _gather(*a, **k) + 1e-3
pipeline._packed_split_rows = altered
""",
    "lineitem-q6": """
from repro.kernels.predicate_scan import ops
_counts = ops.masked_counts
def altered(*a, **k):
    return _counts(*a, **k).at[0].add(1)
ops.masked_counts = altered
""",
}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    from chipbench import control
    for seed in (3, BIG_SEED):
        numbers = control.read(cell, seed, 1.0, rehearse=True)
        assert numbers, cell
        assert any(n["fails"] for n in numbers.values()), numbers


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answers_are_not_correct(tmp_path, cell):
    code = ("import sys\n" + FAULTS[cell]
            + "from chipbench.run import main\nsys.exit(main(sys.argv[1:]))")
    rc, last, out, err = run_cell(
        tmp_path, "--workload", cell, "--seed", str(BIG_SEED + 1),
        "--seconds", "1", "--trace", "0", "--rehearse", code=code)
    assert rc == 0, err[-3000:]
    assert last is not None and last["correct"] is False, last
    assert any(c["value"] > c["limit"] for c in last["check"].values())


def test_every_cell_has_a_planted_fault():
    assert set(FAULTS) == set(CELLS)
