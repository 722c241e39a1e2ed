"""Distributed pruned scans: surviving row groups sharded over devices.

The sharded engine's path is :func:`merge_tree_sharded`: one contiguous
span of row groups per chip, each span decoded onto and folded on its own
chip, the span states merged on one chip (``core.engine.merge_tree``).
The halo lowerings below (``query_sharded_multi`` and its ``_gather``)
are the older schedule, kept callable but off that path.

The query layer's pruned stream (``repro.query.exec.pruned_source``)
collapses zone-map-refuted row groups to O(segments) ghost rows; this
module concatenates that stream, splits it into equal contiguous shards
over the data axis, and reuses the ``distributed.dfg`` drivers verbatim —
one kernel update per shard, the boundary row recovered with a
``ppermute`` halo, the mergeable state combined with one ``psum``.  Ghost
rows ride along as ordinary all-masked rows, so the halo a shard hands to
its successor is exactly the carry the streaming path would have built,
and sharded == streamed == filter-then-mine, bitwise.

The one boundary the shards cannot resolve is the *stream's* final end
activity: the last physical row is padding (all-masked), so the trailing
end is re-applied host-side from the true tail row after the psum.

**Fused collection** (:func:`query_sharded_multi`) mines several
*distinct* mergeable states — ``"dfg"``, ``"discovery"``, ``"variants"``
— from ONE gathered stream and ONE ``shard_map``: the halo-carry state
kernels are ``core.engine.compose``-d, each member gets its own ppermute
halo at its own depth, and the psum carries every state in one leafwise
all-reduce.  Variants rides the same shard_map with its own lowering
(``distributed.variants`` — per-row affine hash maps, an ``all_gather``
boundary fold instead of a halo, so ghost rows and shards smaller than a
case both work).  ``query_sharded_dfg`` / ``query_sharded_discovery``
are its single-state special cases, so fused and separate runs share one
code path and are bitwise equal state-for-state.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import engine
from repro.core.dfg import DFG, dfg_kernel
from repro.core.discovery import DiscoveryState, discovery_kernel
from repro.core.eventframe import ACTIVITY, CASE
from repro.core.polyhash import BASE1, BASE2, SK_ADD1, SK_ADD2, SK_MUL1, \
    SK_MUL2
from repro.query.exec import (pruned_source, span_chunks, span_schedules,
                              span_workers)
from repro.query.plan import MultiPlan, Plan

from .dfg import fix_trailing_end, run_sharded_composed
from .discovery import _fix_end as fix_discovery_end
from .variants import run_sharded_variants

# every halo-carry distributed lowering a KernelSpec.sharded_state can name:
# state name -> (kernel factory(num_activities, method), shard-end fix)
STATE_DRIVERS = {
    "dfg": (dfg_kernel, fix_trailing_end),
    "discovery": (discovery_kernel, fix_discovery_end),
}

# every sharded state, halo-carry or bespoke ("variants" gathers affine
# hash maps and folds shard boundaries with an all_gather — see
# distributed.variants)
SHARDED_STATES = frozenset(STATE_DRIVERS) | {"variants"}


def _gather(plan: "Plan | MultiPlan", prune: bool, sketch: bool = False):
    """Concatenate the pruned stream's (case, activity, rows_valid).

    Multi-file plans concatenate every file's pruned scan in path order
    (``repro.query.multi_pruned_source``), so the shards of a dataset-wide
    mine see one contiguous sorted log with ghost rows standing in for
    every skipped row group of every file.  With ``sketch`` the stream's
    ghost chunks carry composed header sketch maps, and the gather also
    returns per-row affine hash maps ``(m1, b1, m2, b2)`` — real rows
    hash as ``(BASE, act+1)``, ghost segment rows as their composed
    sketch map, ghost padding rows as the identity — the sharded variants
    input.
    """
    src, report = pruned_source(plan.project((ACTIVITY, CASE)), prune=prune,
                                mask_exact=True, sketch=sketch)
    case_parts, act_parts, rv_parts, map_parts = [], [], [], []
    for chunk in src:
        if chunk.nrows == 0:
            continue
        case_parts.append(np.asarray(chunk[CASE]))
        act = np.asarray(chunk[ACTIVITY])
        act_parts.append(act)
        rv_parts.append(np.asarray(chunk.rows_valid(), bool))
        if sketch:
            if SK_MUL1 in chunk:
                map_parts.append(tuple(np.asarray(chunk[c]) for c in
                                       (SK_MUL1, SK_ADD1, SK_MUL2, SK_ADD2)))
            else:
                v = act.astype(np.uint32) + 1
                map_parts.append((np.full(v.shape, BASE1, np.uint32), v,
                                  np.full(v.shape, BASE2, np.uint32), v))
    if not case_parts:
        z = np.zeros(0, np.int64)
        maps = tuple(np.zeros(0, np.uint32) for _ in range(4)) \
            if sketch else None
        return z, z.astype(np.int32), np.zeros(0, bool), maps, report
    maps = tuple(np.concatenate([p[i] for p in map_parts])
                 for i in range(4)) if sketch else None
    return (np.concatenate(case_parts), np.concatenate(act_parts),
            np.concatenate(rv_parts), maps, report)


def _pad_to_shards(case, act, rv, n_dev: int, maps=None):
    """Pad with >= 1 all-masked copies of the last row so every shard is
    equally sized and the trailing end is *never* resolved on-device.
    Hash map padding is the *identity* map (1, 0): the padded rows extend
    the final case without touching its hash."""
    n = case.shape[0]
    if n == 0:
        case = np.zeros(1, np.int64)
        act = np.zeros(1, np.int32)
        rv = np.zeros(1, bool)
        if maps is not None:
            maps = tuple(np.zeros(1, np.uint32) for _ in range(4))
        n = 1
    pad = (-(n + 1)) % n_dev + 1
    case = np.concatenate([case, np.full(pad, case[-1], case.dtype)])
    act = np.concatenate([act, np.full(pad, act[-1], act.dtype)])
    rv = np.concatenate([rv, np.zeros(pad, bool)])
    if maps is not None:
        one = np.ones(pad, np.uint32)
        zero = np.zeros(pad, np.uint32)
        maps = tuple(np.concatenate([m, one if i % 2 == 0 else zero])
                     for i, m in enumerate(maps))
    return case, act, rv, maps


def _segment_markers(case):
    """Global ``(starts, seg, ends)`` of the padded case column — the
    variants lowering's segment geometry (host-derived once, sliced per
    shard by the shard_map)."""
    n = case.shape[0]
    starts = np.zeros(n, bool)
    starts[0] = True
    starts[1:] = case[1:] != case[:-1]
    seg = np.cumsum(starts, dtype=np.int64).astype(np.int32) - 1
    ends = np.zeros(n, bool)
    ends[:-1] = starts[1:]
    ends[-1] = True
    return starts, seg, ends


def _apply_tail_end(dfg: DFG, tail) -> DFG:
    if tail is None or not tail[2]:
        return dfg
    return DFG(dfg.counts, dfg.starts,
               dfg.ends.at[tail[1]].add(jnp.int32(1), mode="drop"))


def _finish_state(name: str, state, tail):
    """Host-side tail fix per distributed state (the stream's true last
    row is padding on-device; see module docstring)."""
    if name == "dfg":
        return _apply_tail_end(state, tail)
    if name == "discovery":
        return DiscoveryState(_apply_tail_end(state["dfg"], tail),
                              state["l2"])
    if name == "variants":
        return state            # no end-activity concept, nothing to fix
    raise KeyError(f"no distributed lowering named {name!r}; "
                   f"known: {sorted(SHARDED_STATES)}")


def query_sharded_multi(plan: "Plan | MultiPlan", states, num_activities: int,
                        mesh, axis_name: str = "data", *, prune: bool = True,
                        method: str = "auto", num_cases: int | None = None):
    """Mine every distributed state in ``states`` (distinct names from
    :data:`SHARDED_STATES`) from ONE gathered pruned stream and ONE
    ``shard_map``.  Returns ``({state_name: state}, ScanReport)`` — each
    state bitwise equal to its separate ``query_sharded_*`` run, with the
    event columns gathered and sharded exactly once however many verbs
    share the pass.  ``"variants"`` needs ``num_cases`` (its fingerprint
    table capacity) and yields ``(fp1, fp2, ncases)`` exactly like the
    streaming kernel's finalize."""
    states = tuple(dict.fromkeys(states))       # dedupe, keep order
    unknown = set(states) - SHARDED_STATES
    if not states or unknown:
        raise KeyError(f"distributed states must be a non-empty subset of "
                       f"{sorted(SHARDED_STATES)}; got {list(states)}")
    want_var = "variants" in states
    if want_var and num_cases is None:
        raise ValueError("states including 'variants' need num_cases= "
                         "(the fingerprint table capacity)")
    halo_states = tuple(s for s in states if s in STATE_DRIVERS)
    case, act, rv, maps, report = _gather(plan, prune, sketch=want_var)
    tail = (int(case[-1]), int(act[-1]), bool(rv[-1])) if case.size else None
    empty = case.size == 0
    n_dev = mesh.shape[axis_name]
    case, act, rv, maps = _pad_to_shards(case, act, rv, n_dev, maps)
    var_dev = want_var and num_cases > 0
    if want_var:
        starts, seg, ends = _segment_markers(case)
        ncases_seen = 0 if empty else int(seg[-1]) + 1
    kernel = engine.compose({s: STATE_DRIVERS[s][0](num_activities, method)
                             for s in halo_states}) if halo_states else None
    fix_ends = {s: STATE_DRIVERS[s][1] for s in halo_states}

    def local(case, act, valid, *var_args):
        out = {}
        if kernel is not None:
            out.update(run_sharded_composed(kernel, fix_ends, case, act,
                                            valid, axis_name=axis_name,
                                            n_dev=n_dev))
        if var_args:
            m1, b1, m2, b2, starts, seg, ends = var_args
            out["variants"] = run_sharded_variants(
                m1, b1, m2, b2, starts, seg, ends, num_cases,
                axis_name=axis_name, n_dev=n_dev)
        return out

    args = [jnp.asarray(case), jnp.asarray(act), jnp.asarray(rv)]
    if var_dev:
        args += [jnp.asarray(x) for x in (*maps, starts, seg, ends)]
    out = {}
    if kernel is not None or var_dev:
        fn = shard_map(local, mesh=mesh,
                       in_specs=(P(axis_name),) * len(args), out_specs=P())
        out = jax.jit(fn)(*args)
    result = {}
    for s in states:
        if s == "variants":
            fp1, fp2 = out.get("variants",
                               (jnp.zeros(0, jnp.uint32),) * 2)
            result[s] = (fp1, fp2,
                         jnp.int32(min(ncases_seen, num_cases)))
        else:
            result[s] = _finish_state(s, out[s], tail)
    return result, report


def _fold_span(kernel, index: int, pieces, prefetch: int, device, start,
               rec) -> engine.GroupState:
    """Span ``index``'s fresh fold on ``device``: its groups decoded by its
    read-ahead worker (``start``) and folded with the one-chip
    ``fold_group``, under the collect's ``obs`` record."""
    with obs.bind(rec), jax.default_device(device), \
            obs.span(f"shard.span.{index}"):
        chunks = span_chunks(pieces, prefetch, device, start)
        try:
            return engine.fold_group(kernel, chunks)
        finally:
            chunks.close()          # stops the read-ahead on an error


def _to_home(gs: engine.GroupState, device) -> engine.GroupState:
    return dataclasses.replace(gs, state=jax.device_put(gs.state, device),
                               carry=jax.device_put(gs.carry, device))


def merge_tree_sharded(plan: "Plan | MultiPlan", kernel,
                       num_shards: int | None = None, *, prune: bool = True,
                       prefetch: int | None = None):
    """Shard a pruned scan as a merge tree over chips.

    The pruned schedule of row groups is cut into ``num_shards`` contiguous
    spans (default: one per device).  Span *i* is read ahead, decoded, put
    on device *i* and folded there by its own worker
    (``query.exec.span_workers``), with the same ``fold_group`` and
    per-chunk programs the one-chip path runs;
    the span states then move to the first device, ``merge_tree`` combines
    them with the kernels' own stitches, and ``finalize_group`` runs once.
    No column of the log is concatenated on the host.  The merge is
    bitwise-associative, so the result equals the streamed fold, bitwise,
    for every kernel with a ``stitch``.

    Spans and counters on the report: ``shard.span.<i>`` (span *i*'s
    worker), ``shard.wait`` (the caller waiting for the span states),
    ``shard.merge`` (moving them plus ``merge_tree``) and ``chips_used``.

    Returns ``(result, ScanReport)``.
    """
    if not engine.mergeable(kernel):
        raise ValueError(f"kernel {kernel.name!r} defines no stitch — no "
                         f"merge-tree sharding (and no distributed state)")
    devs = jax.devices()
    n = len(devs) if num_shards is None else max(int(num_shards), 1)
    with obs.record() as rec, obs.span("scan"):
        spans, report = span_schedules(
            plan, n, prune=prune,
            mask_exact=getattr(kernel, "mask_exact", True),
            sketch=getattr(kernel, "ghost_sketch", False), prefetch=prefetch)
        homes = [devs[i % len(devs)] for i in range(len(spans))]
        futures = [
            fold.submit(functools.partial(_fold_span, kernel, i, pieces,
                                          report.prefetch, homes[i],
                                          read.submit, rec))
            for i, (pieces, (fold, read))
            in enumerate(zip(spans, span_workers(len(spans))))]
        with obs.span("shard.wait"):
            states = [f.result() for f in futures]
        with obs.span("shard.merge"):
            merged = engine.merge_tree(
                kernel, [_to_home(gs, devs[0]) for gs in states])
        result = engine.finalize_group(kernel, merged)
    obs.add(report, rec)
    report.chips_used = len(set(homes))
    return result, report


def query_sharded_dfg(plan: "Plan | MultiPlan", num_activities: int, mesh,
                      axis_name: str = "data", *, prune: bool = True,
                      method: str = "auto"):
    """Full DFG of a filtered log, mined from the pruned scan sharded over
    ``axis_name``.  Returns ``(DFG, ScanReport)``; counts/starts/ends are
    bitwise equal to ``dfg(filter(read(path)))``."""
    out, report = query_sharded_multi(plan, ("dfg",), num_activities, mesh,
                                      axis_name, prune=prune, method=method)
    return out["dfg"], report


def query_sharded_discovery(plan: "Plan | MultiPlan", num_activities: int, mesh,
                            axis_name: str = "data", *, prune: bool = True,
                            method: str = "auto"):
    """DFG + L2-loop discovery state over the pruned, sharded scan
    (feeds ``discover_alpha`` / ``discover_heuristics`` host-side)."""
    out, report = query_sharded_multi(plan, ("discovery",), num_activities,
                                      mesh, axis_name, prune=prune,
                                      method=method)
    return out["discovery"], report


def query_sharded_dfg_host(plan: "Plan | MultiPlan", num_activities: int, num_shards: int,
                           **kw):
    """CPU-host validation path (virtual device mesh), as in
    ``distributed.dfg.dfg_sharded_host``."""
    devs = jax.devices()[:num_shards]
    mesh = jax.sharding.Mesh(devs, ("data",))
    return query_sharded_dfg(plan, num_activities, mesh, **kw)


def query_sharded_discovery_host(plan: "Plan | MultiPlan", num_activities: int,
                                 num_shards: int, **kw):
    devs = jax.devices()[:num_shards]
    mesh = jax.sharding.Mesh(devs, ("data",))
    return query_sharded_discovery(plan, num_activities, mesh, **kw)
