"""Benchmark harness — one bench per paper table/figure + roofline/kernels.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV rows (paper artifact -> bench module
mapping in DESIGN.md §6).  ``scripts/ci.sh`` chains the fast
(``-m "not slow"``) test suite with ``--fast --only fl_frameworks`` so the
perf artifacts in benchmarks/results/ stay reproducible in CI."""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced round counts (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench module suffixes")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (bench_cv, bench_fl_frameworks, bench_inversion,
                            bench_kernels, bench_roofline)
    from benchmarks.common import print_rows

    benches = {
        "fl_frameworks": bench_fl_frameworks,   # Fig 3a/3b/4a/4b
        "cv": bench_cv,                         # Fig 5
        "inversion": bench_inversion,           # §III-B Step 4
        "kernels": bench_kernels,               # kernel micro-benches
        "roofline": bench_roofline,             # EXPERIMENTS §Roofline
    }
    if args.only:
        keep = args.only.split(",")
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in benches.items():
        try:
            print_rows(mod.run(fast=args.fast))
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
