"""Benchmark harness: one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV.  Run as:
  PYTHONPATH=src python -m benchmarks.run [--only paper|kernels|moe|roofline]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import bench_engine, bench_kernels, bench_moe, \
        bench_paper, bench_roofline

    suites = {
        "paper": bench_paper.run,
        "kernels": bench_kernels.run,
        "engine": bench_engine.run,
        "moe": bench_moe.run,
        "roofline": bench_roofline.run,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        try:
            for line in fn():
                print(line, flush=True)
        except Exception:
            failures += 1
            print(f"{name},0.0,SUITE-ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
