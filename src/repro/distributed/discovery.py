"""Distributed discovery: the discovery chunk-kernel with ``psum`` merge.

Same shape as ``distributed.dfg`` — literally: both lowerings run through
``distributed.dfg.run_sharded_kernel`` (init, ppermute halo carry, one
kernel update per shard, last-shard end fix, psum merge).  The only
variation here is the halo depth: L2-loop triples (``a, b, a``) can
straddle a shard boundary by *two* rows, so the carry is recovered from
each shard's last two rows instead of one.  The miners themselves
(``discover_alpha`` / ``discover_heuristics``) run on the merged state —
they are pure finalize and never see events.

Precondition: every shard holds at least two rows (pad the frame, as the
data-sharding helpers already do for alignment).
"""
from __future__ import annotations

import functools

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.discovery import (AlphaModel, DiscoveryState, HeuristicsNet,
                                  discover_alpha, discover_heuristics,
                                  discovery_kernel)
from repro.core.eventframe import ACTIVITY, CASE, EventFrame
from .dfg import fix_trailing_end, run_sharded_kernel


def _fix_end(state, carry, last_end):
    return {"dfg": fix_trailing_end(state["dfg"], carry, last_end),
            "l2": state["l2"]}


def _local_state(case, act, valid, *, num_activities, axis_name, n_dev):
    return run_sharded_kernel(discovery_kernel(num_activities), _fix_end,
                              case, act, valid, axis_name=axis_name,
                              n_dev=n_dev, halo_depth=2)


def discovery_state_sharded(frame: EventFrame, num_activities: int, mesh,
                            axis_name: str = "data") -> DiscoveryState:
    """DFG + L2 counts of a (case,time)-sorted frame sharded over
    ``axis_name``; replicated on every shard."""
    fn = shard_map(
        functools.partial(_local_state, num_activities=num_activities,
                          axis_name=axis_name, n_dev=mesh.shape[axis_name]),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
    )
    out = jax.jit(fn)(frame[CASE], frame[ACTIVITY], frame.rows_valid())
    return DiscoveryState(out["dfg"], out["l2"])


def alpha_sharded(frame: EventFrame, num_activities: int, mesh,
                  axis_name: str = "data", min_count: int = 1) -> AlphaModel:
    """Distributed alpha miner: psum-merged DFG state + host finalize."""
    state = discovery_state_sharded(frame, num_activities, mesh, axis_name)
    return discover_alpha(state.dfg, min_count)


def heuristics_sharded(frame: EventFrame, num_activities: int, mesh,
                       axis_name: str = "data", **thresholds) -> HeuristicsNet:
    """Distributed heuristics miner: psum-merged state + dense finalize."""
    state = discovery_state_sharded(frame, num_activities, mesh, axis_name)
    return discover_heuristics(state, **thresholds)


def discovery_state_sharded_host(frame: EventFrame, num_activities: int,
                                 num_shards: int) -> DiscoveryState:
    """CPU-host validation path: shard on a host mesh of virtual devices."""
    devs = jax.devices()[:num_shards]
    mesh = jax.sharding.Mesh(devs, ("data",))
    return discovery_state_sharded(frame, num_activities, mesh)
