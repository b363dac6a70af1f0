"""Run one benchmark cell once and print its result line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the checkout root. The run builds the cell's deployment from the
seed, warms every launch shape its traffic uses, measures for ``--seconds``
(the profiler on only with ``--trace 1``), frees the system's state, checks
a sample of what the window served against the configuration's plain
reference, and prints one JSON object as the last line of standard output.
The numbers compared, each beside its limit, are the last lines of
standard error and the last key of that object.

It exits non-zero and prints no result when JAX's first device is not a
TPU or there are fewer chips than the cell asks for; ``--rehearse`` runs
on any backend at the configuration's tiny rehearsal size instead.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

from chipbench.load import load_module, part  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# counters that move only when serving left the device path
FALLBACK = ("host_gathers", "failovers", "retries", "devices_lost",
            "timeouts", "failed_tickets")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  COMPILE_EVENT)


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


@dataclass
class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""
    root: pathlib.Path

    def __post_init__(self):
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def reference(self, name: str) -> tuple[dict, object]:
        """(sizes, reference module) of a configuration; the reference
        imports nothing of the system under test."""
        d = self.root / "chipbench" / "configs"
        cfg = json.loads((d / f"{name}.json").read_text())
        return cfg, load_module(d / f"{name}_ref.py",
                                f"chipbench_config_{name}_ref")

    def config(self, name: str) -> tuple[dict, object, object]:
        """(sizes, reference module, loader module) of a configuration."""
        cfg, ref = self.reference(name)
        prog = load_module(self.root / "chipbench" / "configs" / f"{name}.py",
                           f"chipbench_config_{name}")
        return cfg, ref, prog

    def mix(self, name: str) -> dict:
        return json.loads((self.root / "chipbench" / "traffic"
                           / f"{name}.json").read_text())

    def reader(self, metric: str):
        return part(self.root, "metrics", metric)

    def metrics(self, kind: str, cell: str) -> list[dict]:
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]


@dataclass
class Deployment:
    root: pathlib.Path
    cfg: dict
    ref: object
    program: object
    raw: object
    plan: object = None
    service: object = None
    frontend: object = None

    def close(self) -> None:
        """Stop the service and drop every device array it holds."""
        if self.service is not None:
            self.service.shutdown()
        self.plan = self.service = self.frontend = None
        gc.collect()


def rehearsed(spec: dict, rehearse: bool) -> dict:
    """A config or mix with its ``rehearse`` sizes in place."""
    if not rehearse:
        return spec
    return {**spec, **spec.get("rehearse", {})}


class CompileClock:
    """JAX's compile events: seconds spent, and backend compiles counted."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs
        if event == COMPILE_EVENT:
            self.compiles += 1


def check_platform(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs


def deploy(bench: Bench, cell: dict, seed: int, rehearse: bool,
           steps: dict) -> Deployment:
    from repro.serve import FeatureFrontend, FeatureService, default_classes
    cfg, ref, prog = bench.config(cell["config"])
    cfg = rehearsed(cfg, rehearse)
    t = time.monotonic()
    raw = ref.generate(cfg, seed, rehearse)
    steps["generate_s"] = time.monotonic() - t
    t = time.monotonic()
    plan = prog.plan(cfg, raw)
    steps["plan_s"] = time.monotonic() - t
    t = time.monotonic()
    service = FeatureService(plan, classes=default_classes())
    frontend = FeatureFrontend(service)
    steps["service_s"] = time.monotonic() - t
    return Deployment(root=bench.root, cfg=cfg, ref=ref, program=prog,
                      raw=raw, plan=plan,
                      service=service, frontend=frontend)


def run(args) -> dict:
    bench = Bench(pathlib.Path(args.root) if args.root else ROOT)
    cell = bench.cell(args.workload)
    src = bench.root / "src"
    if src.is_dir() and str(src) not in sys.path:
        sys.path.insert(0, str(src))
    devs = check_platform(cell["chips"], args.rehearse)
    import jax
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    from chipbench import load
    enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    steps = {"start_s": time.monotonic() - T_PROCESS}
    dep = deploy(bench, cell, args.seed, args.rehearse, steps)
    mix = rehearsed(bench.mix(cell["traffic"]), args.rehearse)
    drv = load.loop_for(mix, dep, args.seed, args.seconds)
    t = time.monotonic()
    drv.warm()
    steps["warm_s"] = time.monotonic() - t
    compile_setup, compiles_setup = clock.seconds, clock.compiles
    setup_s = time.monotonic() - T_PROCESS
    trace_dir = None
    if args.trace:
        trace_dir = bench.root / ".chipbench" / "trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    tracer = load.Tracer(str(trace_dir) if trace_dir else None)
    obs = drv.run(tracer)
    if args.trace:
        obs.host_stall_max_ms = tracer.stall_max_s * 1e3
    compiles_window = clock.compiles - compiles_setup
    stats = obs.stats_delta
    fallback = {k: stats.get(k, 0) for k in FALLBACK}
    obs.failed += fallback["host_gathers"] + fallback["failovers"] \
        + fallback["retries"]
    if fallback["devices_lost"]:
        obs.failed = obs.attempted
    mem = devs[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    print("setup: " + " ".join(f"{k}={v:.3f}" for k, v in steps.items())
          + f" compile_s={compile_setup:.3f} compiles={compiles_setup}"
          + f" setup_s={setup_s:.3f}", flush=True)
    print(f"window: seconds={args.seconds} attempted={obs.attempted} "
          f"failed={obs.failed} completed_in_window={obs.completed_in_window}"
          f" compiles_in_window={compiles_window}", flush=True)
    print("fallback: " + " ".join(f"{k}={v}" for k, v in fallback.items()),
          flush=True)
    if obs.latency_ms is not None:
        lat = obs.latency_ms
        print("latency_ms: " + " ".join(
            f"p{q}={np.percentile(lat, q):.3f}" for q in (50, 90, 99))
            + f" max={lat.max():.3f} over_100={(lat > 100).sum()}"
            + (f" gen_lag_max={obs.gen_lag_ms.max():.3f}"
               if obs.gen_lag_ms is not None and obs.gen_lag_ms.size
               else ""), flush=True)
    print(f"memory: peak_bytes_in_use={peak} "
          f"bytes_in_use={mem.get('bytes_in_use')} "
          f"bytes_limit={mem.get('bytes_limit')}", flush=True)
    dep.close()
    obs.trace = tracer.reduce()
    obs.work = dep.ref.work(dep.raw)
    obs.device_kind = devs[0].device_kind
    kind = "per_layer" if args.trace else "end_to_end"
    values = {}
    for m in bench.metrics(kind, cell["name"]):
        name = m["name"]
        v = setup_s if name == "setup_s" else bench.reader(name).read(obs)
        if v is None and not args.trace:
            raise RuntimeError(f"end-to-end metric {name} read nothing")
        if v is not None:
            values[name] = {"value": float(v), "unit": m["unit"]}
    t = time.monotonic()
    numbers = drv.compare(dep.ref, dep.raw, obs.answers)
    limits = dep.cfg["limits"]
    check = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    correct = bool(check) and all(
        c["value"] <= c["limit"] for c in check.values())
    print(f"reference: {len(obs.answers)} answers compared in "
          f"{time.monotonic() - t:.3f} s", flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": obs.attempted,
           "failed": obs.failed, "metrics": values, "device": device}
    if obs.trace is not None:
        device["busy_s"] = obs.trace.busy_s
        device["window_s"] = obs.trace.window_s
        out["breakdown"] = {"device_ops": obs.trace.top_ops,
                            "idle_gaps": obs.trace.idle_gaps}
    out["check"] = check
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any backend at the tiny rehearsal sizes")
    ap.add_argument("--root", default=None,
                    help="checkout holding BENCHMARK.json (default: the one "
                    "this file is in)")
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"chipbench: the system under test is missing: {e}",
              file=sys.stderr)
        return 2
    for name, c in out["check"].items():
        if not math.isfinite(c["value"]):
            c["value"] = str(c["value"])        # JSON has no inf or nan
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
