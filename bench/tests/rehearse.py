"""Run a cell's set-up, window, check and reduction at a tiny size on the
CPU through the same functions as ``bench/run.py`` (which itself refuses
the CPU).  Cells that ``BENCHMARK.json`` does not hold yet (see PERF.md,
Open questions) come from ``later_cells.json`` beside this file."""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path

from bench import harness
from bench.peaks import peaks

LATER = json.loads((Path(__file__).parent / "later_cells.json").read_text())


def spec_with_later() -> dict:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        have = {x["name"] for x in spec[key]}
        spec[key] += [x for x in LATER[key] if x["name"] not in have]
    for key in ("end_to_end", "per_layer"):
        by = {m["name"]: m for m in spec[key]}
        for m in LATER[key]:
            if m["name"] in by:
                by[m["name"]] = dict(by[m["name"]], workloads=sorted(set(
                    by[m["name"]].get("workloads", [])) | set(m["workloads"])))
            else:
                by[m["name"]] = m
        spec[key] = list(by.values())
    return spec


def cell_parts(cell_name: str):
    spec = spec_with_later()
    cell = {w["name"]: w for w in spec["workloads"]}[cell_name]
    cfile = {c["name"]: c for c in spec["configs"]}[cell["config"]]["file"]
    config = json.loads((harness.ROOT / cfile).read_text())
    mix = json.loads((harness.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return spec, cell, config, mix


def rehearse(cell_name: str, seed: int, seconds: float, trace: bool,
             log: dict, storage: dict | None = None, mix: dict | None = None):
    import jax

    spec, cell, config, base_mix = cell_parts(cell_name)
    config = copy.deepcopy(config)
    config["log"].update(log)
    config["storage"].update(storage or {})
    mix = dict(base_mix, **(mix or {}))
    devs = jax.devices()[:int(cell["chips"])]
    return harness.execute(spec, cell, config, mix, seed, seconds, trace,
                           devs, peaks("TPU v5 lite"), time.monotonic())
