"""Readings that set the limits of the comparison that decides
``correct``: the program's on many seeds and the control's on a few,
read in one process so that set-up compiles once.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...]

For each seed it runs the cell's set-up, a short window at the cell's own
load and sizes, and the check; for each control seed it also puts the
reference computed one precision below the configuration's (bfloat16 for
float32) in the program's place.  One JSON line per reading; the runs of
``bench/run.py`` never do this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
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
    for seed in args.seeds:
        t = time.monotonic()
        driver = harness.driver_for(mix)(cell, config, mix, seed, devs)
        try:
            driver.setup()
            harness.measure(driver, args.seconds, False, clock)
        finally:
            driver.teardown()
        for line in driver.notes():
            print(line, flush=True)
        readings = {"seed": seed, "who": "program",
                    "checks": {n: v for n, v, _ in driver.checks()}}
        print(json.dumps(readings), flush=True)
        if seed in args.control_seeds:
            readings = {"seed": seed, "who": "control", "checks": {
                n: v for n, v, _ in driver.checks(control=True)}}
            print(json.dumps(readings), flush=True)
        print(f"seed {seed}: {time.monotonic() - t} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
