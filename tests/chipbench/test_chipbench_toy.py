"""A configuration, a traffic mix, a cell and a per-layer metric are added
as new files and new entries alone: a toy cell that exists only in this
test runs through the unchanged harness."""
from __future__ import annotations

import json
import shutil

from chipbench_helpers import BIG_SEED, ROOT, bench_spec, run_cell

TOY_CONFIG = {
    "name": "toy_ints", "source": "this test", "rows": 4096,
    "reduced": {}, "assumed": {}, "limits": {"max_rel_gap": 1e-06},
    "rehearse": {"rows": 4096},
}

TOY_REF = '''
import numpy as np
from dataclasses import dataclass
from chipbench.work import device_width


@dataclass
class Raw:
    rows: int
    values: np.ndarray
    codes: np.ndarray


def generate(cfg, seed, rehearse=False):
    rng = np.random.default_rng([seed % (1 << 63), 7])
    return Raw(cfg["rows"], np.arange(1, 101, dtype=np.int64),
               rng.integers(0, 100, cfg["rows"]).astype(np.int32))


def work(raw):
    return {"rows": raw.rows, "columns": 1, "device_bits": [device_width(100)],
            "table_bytes_per_row": 4, "out_bytes_per_row": 4}


def compare_rows(raw, rows, served, control=False):
    want = np.log1p(raw.values[raw.codes[rows]].astype(np.float64))
    got = want.astype(np.float32) if control else served[:, 0]
    return {"max_rel_gap": float(np.max(np.abs(got - want) / want))}
'''

TOY_PROGRAM = '''
import numpy as np
from repro.columnar import Column, Dictionary, Table
from repro.core import FeaturePlan, FeatureSet


def plan(cfg, raw):
    d = Dictionary(raw.values, np.bincount(raw.codes, minlength=100),
                   name="x", sorted_codes=True)
    table = Table({"x": Column(d, raw.codes)})
    return FeaturePlan(table, FeatureSet().add("x", "log"), packed=True)
'''

TOY_MIX = {
    "name": "toy_open", "loop": "open", "op": "rows", "klass": "batch",
    "rate_per_s": 30, "arrivals": {"dist": "toy_even"},
    "rows_per_request": {"dist": "loguniform", "lo": 8, "hi": 64},
    "row_ids": {"dist": "toy_uniform"}, "check_sample": 4,
    "check_longest": 1,
}

# new laws of an existing loop, each a file of its own
TOY_ARRIVALS = '''
"""toy_even: one request every 1/rate seconds."""


def due(spec, q, rng, seconds):
    return q * seconds
'''

TOY_KEYS = '''
"""toy_uniform: every row equally likely."""


def draw(spec, rng, n, k):
    return rng.integers(0, k, n)
'''

# a new kind of traffic: one client, each request sent when the last is in
TOY_SERIAL_MIX = {"name": "toy_serial", "loop": "toy_serial",
                  "klass": "batch", "requests": 12, "rows": 40}

TOY_LOOP = '''
"""toy_serial: one client sends fixed row requests one after another."""
import time

import numpy as np

from chipbench.load import Observed, delta, rng_for


class Loop:
    def __init__(self, mix, dep, seed, seconds):
        self.mix, self.dep, self.seconds = mix, dep, seconds
        rng = rng_for(seed, 40)
        self.requests = [rng.integers(0, dep.raw.rows, mix["rows"])
                         for _ in range(mix["requests"])]

    def _ask(self, rows):
        f = self.dep.frontend
        return f.result(f.submit(rows, klass=self.mix["klass"]), timeout=600)

    def warm(self):
        self._ask(self.requests[0])

    def run(self, tracer):
        svc = self.dep.service
        before = dict(svc.throughput_stats(1.0))
        tracer.start(self.seconds)
        lat, answers = [], []
        for i, rows in enumerate(self.requests):
            t = time.perf_counter()
            answers.append((i, self._ask(rows)))
            lat.append(time.perf_counter() - t)
        tracer.join()
        return Observed(loop="toy_serial", seconds=self.seconds,
                        attempted=len(lat), latency_ms=np.array(lat) * 1e3,
                        completed_in_window=len(lat), answers=answers,
                        stats_delta=delta(before, svc.throughput_stats(1.0)))

    def compare(self, ref, raw, answers):
        rows = np.concatenate([self.requests[i] for i, _ in answers])
        return ref.compare_rows(raw, rows,
                                np.concatenate([a for _, a in answers]))

    def control(self, ref, raw):
        return ref.compare_rows(raw, np.concatenate(self.requests), None,
                                control=True)
'''

TOY_METRIC = '''
"""toy_requests: requests the window offered."""


def read(obs):
    return float(obs.attempted)
'''


def toy_checkout(tmp_path, mix: dict, files: dict):
    """A copy of the benchmark's files plus the toy configuration, the mix,
    ``files`` (path under chipbench/ -> text), a cell and a metric: new
    files and new entries only. Returns (root, the latency metric)."""
    root = tmp_path / "checkout"
    root.mkdir()
    for p in bench_spec()["paths"]:
        shutil.copytree(ROOT / p, root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    d = root / "chipbench"
    new = {"configs/toy_ints.json": json.dumps(TOY_CONFIG),
           "configs/toy_ints_ref.py": TOY_REF,
           "configs/toy_ints.py": TOY_PROGRAM,
           f"traffic/{mix['name']}.json": json.dumps(mix),
           "metrics/toy_requests.py": TOY_METRIC, **files}
    for rel, text in new.items():
        assert not (d / rel).exists(), rel
        (d / rel).parent.mkdir(exist_ok=True)
        (d / rel).write_text(text)
    spec = bench_spec()
    spec["configs"].append({"name": "toy_ints", "source": "this test",
                            "file": "chipbench/configs/toy_ints.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "toy-cell", "config": "toy_ints",
                              "traffic": mix["name"], "chips": 1,
                              "why": "toy"})
    latency = next(m for m in spec["end_to_end"] if m["name"] != "setup_s"
                   and "criteo-lookup" in m.get("workloads", []))
    latency["workloads"].append("toy-cell")
    spec["per_layer"].append({"name": "toy_requests", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator (chipbench/loops)",
                              "moves": latency["name"],
                              "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, latency["name"]


def run_toy(tmp_path, root, latency: str, requests: float) -> None:
    for trace in (0, 1):
        rc, last, out, err = run_cell(
            tmp_path, "--workload", "toy-cell", "--seed", str(BIG_SEED),
            "--seconds", "1", "--trace", str(trace), "--rehearse",
            "--root", str(root))
        assert rc == 0, err[-3000:]
        assert last["correct"] is True, last
        if trace:
            assert last["metrics"]["toy_requests"]["value"] == requests
        else:
            assert set(last["metrics"]) == {"setup_s", latency}


def test_a_cell_added_as_files_and_entries_runs(tmp_path):
    root, latency = toy_checkout(tmp_path, TOY_MIX, {
        "arrivals/toy_even.py": TOY_ARRIVALS,
        "keys/toy_uniform.py": TOY_KEYS})
    run_toy(tmp_path, root, latency, 30.0)


def test_a_mix_with_a_new_loop_added_as_files_runs(tmp_path):
    root, latency = toy_checkout(tmp_path, TOY_SERIAL_MIX, {
        "loops/toy_serial.py": TOY_LOOP})
    run_toy(tmp_path, root, latency, 12.0)
