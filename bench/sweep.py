"""Find the highest request rate a serving cell sustains, by a sweep on
the chip (the cell then offers a fixed share of it, written into its mix
file; runs of ``bench/run.py`` never search).

    python3 bench/sweep.py --workload <cell> --seconds <s> \\
        --rates 0.5 1 2 4 [--cadences 1 2 5] [--seed <n>]

Each (cadence, rate) point runs the cell's set-up and one window in this
process and prints one JSON line: latency median and 95th percentile,
the median latency of the first and last thirds of the window (a queue
that grows shows as a last third far above the first), responses and
failures, and the ingest backlog.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--cadences", type=float, nargs="*", default=None)
    ap.add_argument("--seed", type=int, default=2**31 + 2024)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import harness

    try:
        spec, cell, config, mix, devs = harness.prepare(args.workload)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 1
    harness.compile_cache()
    import jax

    from bench.clock import Clock

    clock = Clock(jax)
    for cadence in args.cadences or [mix["cadence_s"]]:
        for rate in args.rates:
            m = dict(mix, rate_per_s=rate, cadence_s=cadence)
            driver = harness.driver_for(m)(cell, config, m, args.seed, devs)
            try:
                driver.setup()
                harness.measure(driver, args.seconds, False, clock)
            finally:
                driver.teardown()
            for line in driver.notes():
                print(line, flush=True)
            ok = driver._answered()
            lat = np.array([(r["done"] - r["due"]) * 1e3 for r in ok])
            due = np.array([r["due"] - driver.t0 for r in ok])
            third = args.seconds / 3
            first = lat[due < third]
            last = lat[due >= 2 * third]
            lag = [(driver.acks[k] - driver.due[k]) * 1e3
                   for k in driver.acks]
            print(json.dumps({
                "cadence_s": cadence, "rate_per_s": rate,
                "responses": len(ok), "attempted": len(driver.records),
                "p50_ms": float(np.median(lat)) if lat.size else None,
                "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
                "first_third_median_ms":
                    float(np.median(first)) if first.size else None,
                "last_third_median_ms":
                    float(np.median(last)) if last.size else None,
                "batches_due": len(driver.due),
                "batches_applied": len(driver.acks),
                "ack_lag_max_ms": max(lag + [0.0]),
                **driver.end_to_end()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
