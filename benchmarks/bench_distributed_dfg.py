"""Distributed DFG scaling: shard_map map-reduce over 1, 2, 4, ... of the
process's own devices (``jax.devices()``), in this process.

A chip belongs to one process, so no child is started: on a four-chip host
this spans the four chips; on a CPU host it spans however many host
devices JAX was started with (one, unless ``XLA_FLAGS`` asked for more).
A wrong count raises.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit


def _shard_counts(n_dev: int):
    s = 1
    while s <= n_dev:
        yield s
        s *= 2


def run(num_cases: int = 200_000):
    from repro.core import dfg
    from repro.core.eventframe import EventFrame
    from repro.data import synthetic
    from repro.distributed.dfg import dfg_sharded_host

    frame, _ = synthetic.generate(num_cases=num_cases, num_activities=26,
                                  seed=5)
    n_dev = len(jax.devices())
    n = frame.nrows
    pad = (-n) % n_dev                  # even shards on every device count
    if pad:
        cols = {k: jnp.pad(v, (0, pad), constant_values=-1)
                for k, v in frame.columns.items()}
        frame = EventFrame(cols, {}, jnp.pad(frame.rows_valid(), (0, pad)))
    ref = np.asarray(dfg(frame, 26, method="segment").counts)
    base = None
    for shards in _shard_counts(n_dev):
        if frame.nrows % shards:
            continue
        got = np.asarray(jax.block_until_ready(
            dfg_sharded_host(frame, 26, shards)).counts)
        if not (got == ref).all():
            raise AssertionError(f"sharded DFG over {shards} device(s) "
                                 f"differs from the single-device DFG")
        t0 = time.perf_counter()
        jax.block_until_ready(dfg_sharded_host(frame, 26, shards))
        dt = time.perf_counter() - t0
        base = base or dt
        emit(f"distributed_dfg/shards_{shards}", dt,
             f"events_per_s={n / dt:.0f};correct=True;"
             f"speedup={base / dt:.2f}x;device={jax.devices()[0].platform}")
