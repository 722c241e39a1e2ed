"""Benchmark harness: one module per paper table. CSV: name,us_per_call,derived.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table5,table6]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.core.backend import enable_compile_cache

from . import (bench_complexity, bench_dataset, bench_discovery,
               bench_distributed_dfg, bench_fusion, bench_graph,
               bench_kernels, bench_query, bench_segment_ops, bench_serving,
               bench_streaming, bench_table1_loading, bench_table2_sizes,
               bench_table5_ops, bench_table6_biglogs, bench_variants_prune,
               bench_window)
from .common import header

SUITES = {
    "table1": lambda full: bench_table1_loading.run(
        num_cases=200_000 if full else 50_000),
    "table2": lambda full: bench_table2_sizes.run(
        num_cases=100_000 if full else 20_000),
    "table5": lambda full: bench_table5_ops.run(scale=1.0 if full else 0.3),
    "table6": lambda full: bench_table6_biglogs.run(
        scale=1.0 if full else 0.05, levels=(1, 2, 3, 4, 5)),
    "complexity": lambda full: bench_complexity.run(
        sizes=(2_000, 8_000, 32_000, 128_000, 512_000) if full
        else (2_000, 8_000, 32_000)),
    "kernels": lambda full: bench_kernels.run(smoke=not full),
    # primitive-level Pallas-interpret vs XLA timings; always writes the
    # BENCH_segment_ops.json trajectory artifact (perf baseline for PRs)
    "segment_ops": lambda full: bench_segment_ops.run(
        full=full, out_json="BENCH_segment_ops.json"),
    # alpha + heuristics miners on the columnar state; always writes the
    # BENCH_discovery.json trajectory artifact (smoke-sized unless --full)
    "discovery": lambda full: bench_discovery.run(
        num_cases=200_000 if full else 20_000,
        out_json="BENCH_discovery.json"),
    # zone-map pushdown selectivity sweep; always writes the
    # BENCH_query.json trajectory artifact (skip-ratio baseline for PRs)
    "query": lambda full: bench_query.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_query.json"),
    # Dataset facade: multi-log pruning, 1-vs-N union overhead, and the
    # engine-dispatch crossover; writes BENCH_dataset.json
    "dataset": lambda full: bench_dataset.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_dataset.json"),
    # fused multi-verb collection vs separate scans + prefetch sweep;
    # writes BENCH_fusion.json
    "fusion": lambda full: bench_fusion.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_fusion.json"),
    # variant-band sketch pruning selectivity sweep (incl. fused 4-verb
    # collection); writes BENCH_variants.json
    "variants_prune": lambda full: bench_variants_prune.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_variants.json"),
    # sliding windows as merge-trees over cached group states + the
    # incremental append scenario; writes BENCH_window.json
    "window": lambda full: bench_window.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_window.json"),
    # the live mining service: concurrent query latency with and without
    # live ingest + the post-append warm-cache delta; writes
    # BENCH_serving.json
    "serving": lambda full: bench_serving.run(
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_serving.json"),
    # semiring closures vs host NumPy Floyd–Warshall + the mined graph
    # verbs; writes BENCH_graph.json
    "graph": lambda full: bench_graph.run(
        dense=(512, 0.5) if full else (384, 0.5),
        num_cases=200_000 if full else 50_000,
        out_json="BENCH_graph.json"),
    "distributed": lambda full: bench_distributed_dfg.run(
        num_cases=200_000 if full else 50_000),
    "streaming": lambda full: bench_streaming.run(
        num_cases=2_000_000 if full else 100_000),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (Table 6 at 10^6..5x10^6 cases)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()
    header()
    failed = []
    for name, fn in SUITES.items():
        if only and name not in only:
            continue
        try:
            fn(args.full)
        except Exception as e:
            failed.append(name)
            print(f"{name}/SUITE_ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"failed suites: {failed}")


if __name__ == "__main__":
    main()
