"""Find an open-loop cell's knee once: one set-up, the offered rate stepped.

    python3 -m chipbench.sweep --workload criteo-lookup --seed <n> \\
        --seconds 8 --rates 200,400,600,800

Each step offers its rate for ``--seconds`` with a fresh schedule and
prints one row. The knee is the highest rate at which no request was
refused or failed (a missed deadline fails its request), the p99 stays
under the class deadline, and the backlog at the window's close is not
above twice the backlog at its middle plus 8. The cell's mix then offers
0.8 times the knee, written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def steady(row: dict, deadline_ms: float) -> bool:
    mid, close = row["backlog_mid"], row["backlog_close"]
    return (row["failed"] == 0 and row["p99_ms"] < deadline_ms
            and close <= 2 * mid + 8)


def main(argv: list[str] | None = None) -> int:
    from chipbench import load
    from chipbench.run import (Bench, NoChip, check_platform, deploy,
                               rehearsed)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        check_platform(cell["chips"], args.rehearse)
    except NoChip as e:
        print(f"chipbench.sweep: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    dep = deploy(bench, cell, args.seed, args.rehearse, {})
    mix = rehearsed(bench.mix(cell["traffic"]), args.rehearse)
    if "rate_per_s" not in mix:
        raise SystemExit("a sweep steps an open loop's rate")
    deadline_ms = dep.service.classes[mix["klass"]].deadline_ms or np.inf
    rows = []
    try:
        for step, rate in enumerate(float(r) for r in args.rates.split(",")):
            drv = load.loop_for(mix, dep, args.seed + step, args.seconds,
                                rate=rate)
            if step == 0:
                drv.warm()
            obs = drv.run(load.Tracer(None))
            lat = obs.latency_ms
            row = {"rate_per_s": rate, "attempted": obs.attempted,
                   "failed": obs.failed,
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p90_ms": float(np.percentile(lat, 90)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "p99_ms": float(np.percentile(lat, 99)),
                   "over_100ms": int((lat > 100).sum()),
                   "max_ms": float(lat.max()),
                   "gen_lag_p99_ms": float(np.percentile(obs.gen_lag_ms, 99)),
                   "service_p99_ms": float(obs.service_class["p99_ms"]),
                   "backlog_mid": obs.backlog[0],
                   "backlog_close": obs.backlog[1]}
            row["steady"] = steady(row, deadline_ms)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        dep.close()
    good = [r["rate_per_s"] for r in rows if r["steady"]]
    print(json.dumps({"knee_per_s": max(good) if good else None,
                      "rate_per_s_at_0.8": 0.8 * max(good) if good else None}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
