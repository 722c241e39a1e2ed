"""Distributed DFG: the streaming chunk-kernel with ``psum`` as its merge.

Events are sharded over the data axis (columnar arrays cut into contiguous
ranges). Each shard runs the *same* ``core.dfg.dfg_kernel`` update that the
single-shot and out-of-core paths use; the one-row halo that stitches the
pair straddling a shard boundary is exactly the kernel's carry, recovered
with a single ``ppermute`` (last row of shard i becomes shard i+1's carry).
The reduce phase merges the per-shard states with one psum of the (A, A)
count matrix (+ two (A,) histograms): the paper's Spark shuffle collapses
into one all-reduce whose payload is independent of N.

There is no bespoke halo code here any more — carry construction and
boundary semantics live in ``core.engine`` and are shared verbatim with the
streaming engine, so sharded == streamed == single-shot, bitwise.

Complexity per device: O(N / devices) work, O(A^2) communication — compare
Table 4's O(N) single-node bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.dfg import DFG, dfg_kernel
from repro.core.eventframe import ACTIVITY, CASE, EventFrame


def shard_halo_carry(carry: dict, case, act, valid, *, axis_name, n_dev,
                     depth: int = 1) -> dict:
    """Recover the previous shard's last ``depth`` rows as this shard's
    carry, one ppermute per column; shard 0 keeps the kernel's init carry
    (its exists flags are False and mask everything).  ``depth=2`` also
    fills the two-back halo keys of ``discovery_kernel`` carries."""
    perm = [(i, i + 1) for i in range(n_dev - 1)]
    tail_case = jax.lax.ppermute(case[-depth:], axis_name, perm)
    tail_act = jax.lax.ppermute(act[-depth:], axis_name, perm)
    tail_valid = jax.lax.ppermute(valid[-depth:], axis_name, perm)
    exists = jax.lax.axis_index(axis_name) > 0
    carry = dict(carry,
                 case=tail_case[-1].astype(jnp.int32),
                 act=tail_act[-1].astype(jnp.int32),
                 rv=tail_valid[-1],
                 exists=exists)
    if depth >= 2:
        carry.update(case2=tail_case[-2].astype(jnp.int32),
                     act2=tail_act[-2].astype(jnp.int32),
                     rv2=tail_valid[-2],
                     exists2=exists)
    return carry


def fix_trailing_end(state: DFG, carry: dict, last_end) -> DFG:
    """Resolve the stream's final end activity on the shard that owns it
    (every other shard's trailing end is resolved by its successor)."""
    return DFG(state.counts, state.starts,
               state.ends.at[carry["act"]].add(last_end, mode="drop"))


def run_sharded_kernel(kernel, fix_end, case, act, valid, *, axis_name,
                       n_dev, halo_depth: int = 1):
    """Shard-local driver shared by the DFG and discovery lowerings:
    init, ppermute halo carry, one kernel update, last-shard end fix,
    psum merge.  Every shard must hold >= ``halo_depth`` rows — shard
    sizes are static at trace time, so violating it (a tiny frame on a
    wide mesh) raises here instead of silently clamping the halo index."""
    if case.shape[0] < halo_depth:
        raise ValueError(
            f"{kernel.name}: {case.shape[0]} row(s) per shard < halo depth "
            f"{halo_depth}; use fewer shards or a larger frame")
    state, carry = kernel.init()
    carry = shard_halo_carry(carry, case, act, valid, axis_name=axis_name,
                             n_dev=n_dev, depth=halo_depth)
    chunk = EventFrame({CASE: case, ACTIVITY: act}, {}, valid)
    state, carry = kernel.update(state, carry, chunk)
    is_last = jax.lax.axis_index(axis_name) == n_dev - 1
    last_end = (is_last & carry["rv"]).astype(jnp.int32)
    state = fix_end(state, carry, last_end)
    # merge == psum of the mergeable state, leaf by leaf
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), state)


def run_sharded_composed(kernel, fix_ends: dict, case, act, valid, *,
                         axis_name, n_dev):
    """Fused multi-state twin of :func:`run_sharded_kernel` for a
    ``core.engine.compose`` kernel: per-member ppermute halo (each member
    at *its* depth — the composed carry is a dict of member carries, so
    the top-level driver cannot use one depth for all), ONE composed
    update over the shard, per-member end fix, one leafwise psum.  Every
    distinct mergeable state crosses the wire once; the event columns
    cross zero extra times."""
    state, carry = kernel.init()
    depths = {m: (2 if "case2" in c else 1) for m, c in carry.items()}
    deepest = max(depths.values())
    if case.shape[0] < deepest:
        raise ValueError(
            f"{kernel.name}: {case.shape[0]} row(s) per shard < halo depth "
            f"{deepest}; use fewer shards or a larger frame")
    halo = {m: shard_halo_carry(c, case, act, valid, axis_name=axis_name,
                                n_dev=n_dev, depth=depths[m])
            for m, c in carry.items()}
    chunk = EventFrame({CASE: case, ACTIVITY: act}, {}, valid)
    state, carry = kernel.update(state, halo, chunk)
    is_last = jax.lax.axis_index(axis_name) == n_dev - 1
    state = {m: fix_ends[m](state[m], carry[m],
                            (is_last & carry[m]["rv"]).astype(jnp.int32))
             for m in state}
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), state)


def _local_state(case, act, valid, *, num_activities, axis_name, n_dev):
    return run_sharded_kernel(dfg_kernel(num_activities), fix_trailing_end,
                              case, act, valid, axis_name=axis_name,
                              n_dev=n_dev)


def dfg_sharded(frame: EventFrame, num_activities: int, mesh,
                axis_name: str = "data") -> DFG:
    """Full DFG (counts + start/end histograms) of a (case,time)-sorted
    frame sharded over ``axis_name``; replicated on every shard."""
    fn = shard_map(
        functools.partial(_local_state, num_activities=num_activities,
                          axis_name=axis_name, n_dev=mesh.shape[axis_name]),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
    )
    return jax.jit(fn)(frame[CASE], frame[ACTIVITY], frame.rows_valid())


def dfg_sharded_host(frame: EventFrame, num_activities: int, num_shards: int) -> DFG:
    """CPU-host validation path: shard on a host mesh of virtual devices."""
    devs = jax.devices()[:num_shards]
    mesh = jax.sharding.Mesh(devs, ("data",))
    return dfg_sharded(frame, num_activities, mesh)
