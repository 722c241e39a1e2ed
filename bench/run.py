"""Benchmark entry point: one run of one cell on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run refuses (exit 1,
no result line) where JAX finds no TPU or fewer chips than the cell asks
for.  The last line of standard output is the result as one JSON object.
"""
import sys
import time

T_START = time.monotonic()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import run

    sys.exit(run(sys.argv[1:], T_START))
