"""Read a cell's control: its plain reference, computed in the next lower
precision, put in the system's place.

    python3 -m chipbench.control --workload <name> --seeds 1 2 3

For each seed the cell's raw data and traffic are made as a run makes
them, and the numbers a run compares are read at the same requests with
the lower-precision reference served: bfloat16 where the configuration
serves float32, float32 where it sums in float64. A sound limit fails
every seed here. The system under test is not involved, so this needs no
chip; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def read(workload: str, seed: int, seconds: float,
         rehearse: bool = False, root: pathlib.Path = ROOT) -> dict:
    """{number: {"value", "limit", "fails"}} of one seed's control."""
    from chipbench import load
    from chipbench.run import Bench, Deployment, rehearsed
    bench = Bench(root)
    cell = bench.cell(workload)
    cfg, ref = bench.reference(cell["config"])
    cfg = rehearsed(cfg, rehearse)
    raw = ref.generate(cfg, seed, rehearse)
    dep = Deployment(root=root, cfg=cfg, ref=ref, program=None, raw=raw)
    mix = rehearsed(bench.mix(cell["traffic"]), rehearse)
    numbers = load.loop_for(mix, dep, seed, seconds).control(ref, raw)
    return {k: {"value": v, "limit": cfg["limits"][k],
                "fails": not v <= cfg["limits"][k]}
            for k, v in numbers.items()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="the run length whose traffic is read")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    failed_all = True
    for seed in args.seeds:
        numbers = read(args.workload, seed, args.seconds, args.rehearse)
        fails = any(n["fails"] for n in numbers.values())
        failed_all &= fails
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": fails, "numbers": numbers}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
