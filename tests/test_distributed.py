"""Distributed DFG / sort / compression: validated in an 8-device subprocess
(the XLA device-count flag must never leak into this test process)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(code: str, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


_PRE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import dfg
from repro.core.eventframe import ACTIVITY, CASE, EventFrame
from repro.data import synthetic

frame, tables = synthetic.generate(num_cases=5000, num_activities=13, seed=9)
n = frame.nrows
pad = (-n) % 8
cols = {k: jnp.pad(v, (0, pad), constant_values=-1) for k, v in frame.columns.items()}
frame = EventFrame(cols, {}, jnp.pad(frame.rows_valid(), (0, pad)))
"""


def test_sharded_dfg_matches_local_and_streaming():
    """sharded DFG == streaming DFG == single-shot DFG, bitwise (counts,
    starts, ends) — all three are the same chunk-kernel."""
    out = run_child(_PRE + """
from repro.core import ChunkedEventFrame, run_streaming
from repro.core.dfg import dfg_kernel
from repro.distributed.dfg import dfg_sharded_host
ref = dfg(frame, 13, method="segment")
stream = run_streaming(dfg_kernel(13), ChunkedEventFrame.from_frame(frame, 4096))
for nm in ("counts", "starts", "ends"):
    assert (np.asarray(getattr(stream, nm)) == np.asarray(getattr(ref, nm))).all(), nm
for shards in (1, 2, 4, 8):
    got = dfg_sharded_host(frame, 13, shards)
    for nm in ("counts", "starts", "ends"):
        assert (np.asarray(getattr(got, nm)) == np.asarray(getattr(ref, nm))).all(), (shards, nm)
print("OK", int(ref.counts.sum()))
""")
    assert out.startswith("OK")


def test_sharded_discovery_matches_local_and_streaming():
    """sharded discovery state (DFG + L2 triple counts) == streamed ==
    single-shot, bitwise, and the finalized models agree."""
    out = run_child(_PRE + """
from repro.core import ChunkedEventFrame, discovery
from repro.distributed.discovery import discovery_state_sharded_host
ref = discovery.discovery_state(frame, 13)
stream = discovery.streaming_discovery_state(
    ChunkedEventFrame.from_frame(frame, 4096), 13)
assert (np.asarray(stream.l2_counts) == np.asarray(ref.l2_counts)).all()
assert (np.asarray(stream.dfg.counts) == np.asarray(ref.dfg.counts)).all()
ref_alpha = discovery.discover_alpha(ref.dfg)
ref_net = discovery.discover_heuristics(ref)
for shards in (1, 2, 4, 8):
    got = discovery_state_sharded_host(frame, 13, shards)
    assert (np.asarray(got.l2_counts) == np.asarray(ref.l2_counts)).all(), shards
    for nm in ("counts", "starts", "ends"):
        assert (np.asarray(getattr(got.dfg, nm))
                == np.asarray(getattr(ref.dfg, nm))).all(), (shards, nm)
    m = discovery.discover_alpha(got.dfg)
    assert m.places == ref_alpha.places
    assert m.start_activities == ref_alpha.start_activities
    net = discovery.discover_heuristics(got)
    assert (np.asarray(net.dependency) == np.asarray(ref_net.dependency)).all()
    assert (np.asarray(net.graph) == np.asarray(ref_net.graph)).all()
print("OK", int(ref.l2_counts.sum()))
""")
    assert out.startswith("OK")


def test_distributed_sort_by_case():
    out = run_child(_PRE + """
from repro.distributed.sort import sort_by_case_sharded
perm = np.random.default_rng(0).permutation(frame.nrows)
scrambled = frame.take(jnp.asarray(perm))
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
case_s, act_s, ts_s, overflow = sort_by_case_sharded(scrambled, mesh)
assert not bool(overflow)
rows = np.asarray(case_s).reshape(8, -1)
for i, row in enumerate(rows):
    real = row[row >= 0]
    assert (np.diff(real) >= 0).all()
    assert (np.unique(real) % 8 == i).all()
# no case lost
total = sum(len(np.unique(r[r >= 0])) for r in rows)
orig = len(np.unique(np.asarray(frame[CASE])[np.asarray(frame.rows_valid())]))
assert total == orig, (total, orig)
print("OK")
""")
    assert out.strip().endswith("OK")


def test_psum_compressed_multidevice():
    out = run_child("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train import compression

mesh = jax.sharding.Mesh(np.array(jax.devices()), ("pod",))
g = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0

def f(gl):
    errs = compression.init_errors({"g": gl})
    mean, _ = compression.psum_compressed({"g": gl}, errs, "pod")
    return mean["g"]

got = shard_map(f, mesh=mesh, in_specs=(P("pod", None),), out_specs=P("pod", None))(g)
# every shard's result approximates the cross-pod mean
ref = g.mean(axis=0)
err = float(jnp.max(jnp.abs(got - ref[None])))
assert err < 0.05, err
print("OK", err)
""")
    assert out.startswith("OK")


def test_elastic_mesh_shrinks():
    out = run_child("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.train.ft import elastic_mesh
m = elastic_mesh(8, model_parallel=2)
assert dict(m.shape) == {"data": 4, "model": 2}
m = elastic_mesh(7, model_parallel=2)   # lost a device -> 3x2, 1 idle
assert dict(m.shape) == {"data": 3, "model": 2}
print("OK")
""")
    assert out.startswith("OK")


def test_sharded_pruned_query_matches_filter_then_mine():
    """distributed.query: zone-map-pruned scan sharded over 8 devices ==
    eager filter-then-mine, bitwise — ghost rows carry the halo across
    skipped row groups, the psum merge is the kernel's merge."""
    out = run_child("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import numpy as np
from repro.core import CASE, engine, ops
from repro.core.dfg import dfg_kernel
from repro.core.discovery import discovery_kernel
from repro.data import synthetic
from repro.storage import edf
from repro.query import Plan, col
from repro.distributed.query import (query_sharded_dfg_host,
                                     query_sharded_discovery_host)

frame, tables = synthetic.generate(num_cases=3000, num_activities=11, seed=4)
d = tempfile.mkdtemp()
p = os.path.join(d, "q.edf")
edf.write(p, frame, tables, row_group_rows=1111)
plan = Plan(p).filter(col(CASE).between(500, 900))
c = frame[CASE]
ff = ops.proj(frame, (c >= 500) & (c <= 900))
ref = engine.run_single(dfg_kernel(11), ff)
for shards in (1, 2, 4, 8):
    got, rep = query_sharded_dfg_host(plan, 11, shards)
    assert rep.groups_skipped > 0
    for nm in ("counts", "starts", "ends"):
        assert (np.asarray(getattr(got, nm)) == np.asarray(getattr(ref, nm))).all(), (shards, nm)
refd = engine.run_single(discovery_kernel(11), ff)
for shards in (2, 8):
    gotd, repd = query_sharded_discovery_host(plan, 11, shards)
    assert (np.asarray(gotd.l2_counts) == np.asarray(refd.l2_counts)).all()
    for nm in ("counts", "starts", "ends"):
        assert (np.asarray(getattr(gotd.dfg, nm)) == np.asarray(getattr(refd.dfg, nm))).all(), (shards, nm)
print("OK")
""")
    assert out.startswith("OK")
