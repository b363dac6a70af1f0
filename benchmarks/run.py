"""Benchmark harness — one module per paper table/figure.

Output format: ``name,us_per_call,derived`` CSV lines, plus a JSON dump of
all records (``BENCH_full.json`` / ``BENCH_smoke.json``) for CI artifacts.

  table2  bits-to-encode + compression ratios          (paper Table 2, §5.1)
  table3  count-metadata stats vs scans                (paper §6.2)
  table4/5  ADV featurization vs recompute             (paper §6.3)
  table6  featurization catalog build/apply            (paper §6.1)
  serve   seed loop vs pump FeatureService vs packed
          range/random coalesced serving               (serving trajectory)
  query   predicate pushdown: on-device scan+compact+serve
          vs host filter-then-gather                   (paper §5/§6)
  fig1/2  end-to-end pipeline: traditional vs ADV      (paper Figs 1-2)
  roofline  dry-run derived terms (if results present) (EXPERIMENTS.md)

``--smoke`` shrinks every workload to tiny shapes (seconds, not minutes) so
CI can gate on the full module sweep every push.
"""
from __future__ import annotations

import argparse
import gc
import json
import platform
import sys
import traceback

from benchmarks import common
from repro.compile_cache import enable_compile_cache


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="record dump path (default BENCH_<mode>.json)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    common.set_smoke(args.smoke)
    mode = "smoke" if args.smoke else "full"
    out_path = args.json or f"BENCH_{mode}.json"

    print("name,us_per_call,derived")
    from benchmarks import (bench_compression, bench_count_stats, bench_adv,
                            bench_featurize, bench_query, bench_pipeline)
    mods = [bench_compression, bench_count_stats, bench_adv,
            bench_featurize, bench_query, bench_pipeline]
    try:
        from benchmarks import roofline
        mods.append(roofline)
    except ImportError:
        pass
    failures = 0
    for mod in mods:
        gc.collect()       # don't let one module's garbage time the next
        try:
            mod.run()
        except Exception:
            failures += 1
            print(f"# FAILED {mod.__name__}", file=sys.stderr)
            traceback.print_exc()
    with open(out_path, "w") as fh:
        json.dump({"mode": mode, "python": platform.python_version(),
                   "platform": platform.platform(),
                   "failed_modules": failures,
                   "records": common.RECORDS}, fh, indent=1)
    print(f"# wrote {len(common.RECORDS)} records to {out_path}",
          file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
