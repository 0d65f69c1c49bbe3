"""Find an HTTP cell's knee: the open-loop rate its server sustains.

    python3 benchmarks/chip/sweep.py --workload <http cell> --seconds 5 \
        --rates 200 400 800 ...

One process compiles the cell's program once, then offers each rate for
``--seconds`` through a fresh load generator (the cell's mix with only its
rate changed) and prints, per rate, the completed rate, latency p50/p95,
failures and how late the generator ran. The knee is the highest rate
that completes what it is offered without a growing queue; a cell's mix
then states 0.8 x the knee. The benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

import run as bench

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    cell = bench.cellspec.load_cell(Path.cwd(), args.workload)
    bench._use_checkout_cache()
    bench._device_check(cell.chips)
    _, session = bench.build_session(cell)
    session.warmup()
    for rate in args.rates:
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate_rps=rate))
        c = dataclasses.replace(cell, mix=mix)
        child = bench.start_loadgen(c, args.seed, args.seconds)
        try:
            run = bench.Run(cell=c, seconds=args.seconds)
            t = time.perf_counter()
            _, attempted, failed, _ = bench.drive_http(session, run, child, False)
            wall = time.perf_counter() - t
        finally:
            child.kill()
            child.wait()
        lat = np.asarray(run.latencies_s) * 1e3
        print(json.dumps({
            "workload": cell.name, "rate_rps": rate, "attempted": attempted,
            "failed": failed, "completed_rps": run.images / run.window_s,
            "window_s": run.window_s, "wall_s": wall,
            "p50_ms": float(np.percentile(lat, 50, method="inverted_cdf")),
            "p95_ms": float(np.percentile(lat, 95, method="inverted_cdf")),
            "p99_ms": float(np.percentile(lat, 99, method="inverted_cdf")),
            "occupancy": run.scheduler.occupancy_mean,
            "steps": run.scheduler.steps,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
