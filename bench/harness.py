"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the result line.

Everything particular to a cell is found by name: the configuration in
``BENCHMARK.json``'s ``configs`` entry, the traffic mix in
``bench/traffic/<traffic>.json`` (its ``kind`` names the module in
``bench/kinds``), and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  A driver has ``setup()``,
``window(seconds, span)``, ``teardown()``, ``end_to_end()``,
``counters()``, ``checks()`` and ``notes()``.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".traces"          # fixed, inside the checkout


class Refused(Exception):
    """The run cannot measure what the cell asks for (no result line)."""


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, cell, configuration, traffic mix) of a cell."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return spec, cell, config, mix


def cell_metrics(spec: dict, cell: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def open_device(chips: int):
    """The devices a cell runs on; refuses without a TPU, with fewer chips
    than the cell asks for, or without compiled Pallas kernels."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    try:
        from repro.core import backend
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}") from None
    if backend.resolve() != "pallas" or backend.interpret_mode():
        raise Refused(f"lowering {backend.resolve()!r} (interpret="
                      f"{backend.interpret_mode()}); the cell needs "
                      f"compiled Pallas kernels")
    return devs[:chips]


def compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else the fixed ``<checkout>/.jax_cache``."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def driver_for(mix: dict):
    return importlib.import_module(f"bench.kinds.{mix['kind']}").Driver


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    import jax

    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


def measure(driver, seconds: float, trace: bool, clock):
    """The window, traced or not; returns (trace or None, compiles)."""
    import jax

    before = clock.snapshot()
    if not trace:
        with span("window"):
            driver.window(seconds, span)
        tr = None
    else:
        from .trace import Trace

        out = TRACE_DIR / driver.name
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        with jax.profiler.trace(str(out), profiler_options=opts):
            with span("window"):
                driver.window(seconds, span)
        tr = Trace.load(str(out))
        shutil.rmtree(out, ignore_errors=True)
    after = clock.snapshot()
    compiles = (after["compiles"] - before["compiles"]
                + after["hits"] - before["hits"])
    return tr, compiles


def prepare(workload: str):
    """(spec, cell, config, mix, devices) of a cell on this host, with the
    compile cache in place; raises ``Refused``."""
    spec, cell, config, mix = load_cell(workload)
    sys.path.insert(0, str(ROOT / "src"))
    devs = open_device(int(cell["chips"]))
    return spec, cell, config, mix, devs


def run(argv=None, t_start: float | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    try:
        spec, cell, config, mix, devs = prepare(args.workload)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 1
    from .peaks import peaks

    peak = peaks(devs[0].device_kind)
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache: "
          f"{compile_cache()}", flush=True)
    out = execute(spec, cell, config, mix, args.seed, args.seconds,
                  bool(args.trace), devs, peak, t_start)
    print(f"run: {time.monotonic() - t_start} s", flush=True)
    for n, c in out["checks"].items():
        print(f"check {n}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def execute(spec, cell, config, mix, seed, seconds, trace, devs, peak,
            t_start) -> dict:
    """Set-up, window, check and metrics of one run: the result object."""
    import jax

    from .clock import Clock

    clock = Clock(jax)
    driver = driver_for(mix)(cell, config, mix, seed, devs)
    try:
        driver.setup()
        setup_s = time.monotonic() - t_start
        c0 = clock.snapshot()
        print(f"setup: {setup_s} s; compile cache hits={c0['hits']} "
              f"misses={c0['misses']} compile_s={c0['compile_s']}",
              flush=True)
        tr, compiles = measure(driver, seconds, trace, clock)
        mem = memory_peak(devs)
    finally:
        driver.teardown()
    c1 = clock.snapshot()
    print(f"window: compile cache hits={c1['hits'] - c0['hits']} "
          f"misses={c1['misses'] - c0['misses']} programs={compiles}",
          flush=True)
    for line in driver.notes():
        print(line, flush=True)
    checks = driver.checks()

    e2e, layer = cell_metrics(spec, cell["name"])
    metrics = {}
    if not trace:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from types import SimpleNamespace

        view = SimpleNamespace(trace=tr, peak=peak, chips=len(devs),
                               counters=dict(driver.counters(),
                                             compiles_in_window=compiles))
        for m in layer:
            value = reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = driver.attempted_failed()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        from . import trace as tmod

        device["busy_s"] = tmod.busy_s(tr)
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tmod.device_ops(tr),
                            "idle_gaps": tmod.idle_gaps(tr)}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out
