"""Process discovery on the columnar substrate — alpha + heuristics miners.

The paper positions DFGs as the basis for discovery; PM4Py-GPU (arXiv
2204.04898) shows discovery is the payoff workload for columnar event
structures, and the Apache-Phoenix study (arXiv 1703.05481) maps the alpha
miner onto column-oriented scans.  Both miners here consume nothing but the
dense matrices the chunk-kernel engine already accumulates:

* **alpha miner** — footprint relations (``a -> b`` causality, ``a || b``
  parallelism, ``a # b`` choice) derived as masked matrix ops over the
  ``pair_count``-built DFG plus start/end histograms; places are the maximal
  (A, B) pairs of the classic algorithm (host-side set search over the
  boolean footprint — the only non-vectorized step, O(places), not O(N)).
* **heuristics miner** — dependency measure ``(a->b − b->a)/(a->b + b->a + 1)``
  with L1-loop (``a,a``) and L2-loop (``a,b,a``) handling, all dense (A, A)
  array math; AND/XOR split bindings as one (A, A, A) broadcast.

Both are the *finalize* step of a chunk kernel (``core.engine``): the alpha
miner finalizes the existing ``dfg_kernel`` state verbatim, the heuristics
miner finalizes :func:`discovery_kernel` — the DFG state extended with the
(A, A) L2-loop triple counts, stitched across chunk boundaries by a two-row
carry.  Discovery therefore works out-of-core over ``ChunkedEventFrame``
streams with bitwise whole-log parity (integer counting is order-exact) and,
via the same ``tree_sum`` merge, under the ``psum`` of
``repro.distributed.discovery`` — the third streaming-exact workload after
DFG and variants.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.segment_ops import pair_count

from .eventframe import ACTIVITY, CASE, EventFrame
from .dfg import DFG, dfg_kernel, stitch_dfg_state, _method_impl
from . import engine


# ----------------------------------------------------------- footprint
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Footprint:
    """The alpha relations as dense (A, A) boolean matrices.

    Every cell is classified by ``(direct[a, b], direct[b, a])``:
    ``causal`` = ``(1, 0)``, ``parallel`` = ``(1, 1)``, ``choice`` =
    ``(0, 0)`` — a partition, so two footprints agree on a cell iff their
    ``direct`` matrices agree in both orientations.
    """

    direct: jax.Array    # a > b  (b directly follows a at least min_count times)
    causal: jax.Array    # a -> b
    parallel: jax.Array  # a || b
    choice: jax.Array    # a # b

    def tree_flatten(self):
        return (self.direct, self.causal, self.parallel, self.choice), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_activities(self) -> int:
        return self.direct.shape[-1]


@jax.jit
def _footprint(counts: jax.Array, min_count: jax.Array) -> Footprint:
    d = counts >= min_count
    return Footprint(direct=d, causal=d & ~d.T, parallel=d & d.T,
                     choice=~d & ~d.T)


def footprint(source: DFG | jax.Array, min_count: int = 1) -> Footprint:
    """Alpha relations of a DFG (or a raw (A, A) count matrix); edges with
    fewer than ``min_count`` observations are treated as absent (noise)."""
    counts = source.counts if isinstance(source, DFG) else source
    return _footprint(counts, jnp.int32(min_count))


# ---------------------------------------------------------- alpha miner
@dataclasses.dataclass(frozen=True)
class AlphaModel:
    """Result of the alpha miner: a Petri net in (A, B)-pair form.

    ``places`` are the maximal pairs of activity sets ``(A, B)`` with every
    ``a in A`` causal to every ``b in B`` and both sets internally in
    choice; plus the implicit source place (into ``start_activities``) and
    sink place (out of ``end_activities``).  ``footprint`` keeps the
    relation matrices the model was built from — the footprint-matrix
    conformance object (``core.conformance.footprint_conformance``).
    """

    num_activities: int
    places: tuple[tuple[frozenset[int], frozenset[int]], ...]
    start_activities: frozenset[int]
    end_activities: frozenset[int]
    footprint: Footprint

    @property
    def num_places(self) -> int:
        return len(self.places) + 2  # + source/sink


def _maximal_pairs(causal: np.ndarray, choice: np.ndarray):
    """Classic alpha steps 3–4: the maximal (A, B) pairs.

    Any valid pair decomposes into valid singleton pairs (sub-pairs of a
    valid pair are valid), so the closure of singleton pairs under
    pairwise union reaches every element of X_L; Y_L is its maximal
    antichain.  Host-side over the boolean footprint — the alphabet is
    small and fixed, the log size never enters here.
    """
    a_n = causal.shape[0]
    base = [(frozenset((a,)), frozenset((b,)))
            for a in range(a_n) for b in range(a_n)
            if causal[a, b] and choice[a, a] and choice[b, b]]

    def ok(aa, bb):
        al, bl = sorted(aa), sorted(bb)
        return (causal[np.ix_(al, bl)].all()
                and choice[np.ix_(al, al)].all()
                and choice[np.ix_(bl, bl)].all())

    seen = set(base)
    frontier = list(base)
    while frontier:
        fresh = []
        for a1, b1 in frontier:
            for a2, b2 in base:
                cand = (a1 | a2, b1 | b2)
                if cand not in seen and ok(*cand):
                    seen.add(cand)
                    fresh.append(cand)
        frontier = fresh

    maximal = [p for p in seen
               if not any(q != p and p[0] <= q[0] and p[1] <= q[1]
                          for q in seen)]
    return tuple(sorted(maximal, key=lambda p: (sorted(p[0]), sorted(p[1]))))


def discover_alpha(d: DFG, min_count: int = 1) -> AlphaModel:
    """Alpha miner over an accumulated DFG state (whole-log, streamed, or
    psum-merged — the miner is pure finalize, it never sees events)."""
    fp = footprint(d, min_count)
    causal = obs.pull(fp.causal)
    choice = obs.pull(fp.choice)
    places = _maximal_pairs(causal, choice)
    starts = frozenset(int(i) for i in np.nonzero(obs.pull(d.starts))[0])
    ends = frozenset(int(i) for i in np.nonzero(obs.pull(d.ends))[0])
    return AlphaModel(num_activities=d.num_activities, places=places,
                      start_activities=starts, end_activities=ends,
                      footprint=fp)


# ----------------------------------------------------- heuristics miner
@dataclasses.dataclass(frozen=True)
class HeuristicsNet:
    """Result of the heuristics miner — all dense (A, A)/(A, A, A) arrays.

    ``dependency``'s off-diagonal is ``(a->b − b->a)/(a->b + b->a + 1)``;
    its diagonal is the L1-loop measure ``a->a / (a->a + 1)``.  ``l2`` is
    the symmetric L2-loop measure over ``a,b,a`` triple counts.  ``graph``
    is the thresholded dependency graph (L2 edges added in both directions
    for loop pairs where neither side already has an L1 loop).
    ``and_bindings[a, b1, b2]`` marks successor pairs of ``a`` that split
    as AND (concurrent) rather than XOR.
    """

    dependency: jax.Array     # (A, A) float32
    l2: jax.Array             # (A, A) float32
    graph: jax.Array          # (A, A) bool
    and_bindings: jax.Array   # (A, A, A) bool
    start_activities: frozenset[int]
    end_activities: frozenset[int]

    @property
    def num_activities(self) -> int:
        return self.graph.shape[-1]

    def edges(self):
        """Host-side sparse view of the dependency graph."""
        g = np.asarray(self.graph)
        dep = np.asarray(self.dependency)
        return [((int(a), int(b)), float(dep[a, b]))
                for a, b in zip(*np.nonzero(g))]


@jax.jit
def _heuristics_measures(counts: jax.Array, l2_counts: jax.Array):
    c = counts.astype(jnp.float32)
    dep = (c - c.T) / (c + c.T + 1.0)
    l1 = jnp.diag(c) / (jnp.diag(c) + 1.0)
    a = c.shape[0]
    eye = jnp.eye(a, dtype=bool)
    dep = jnp.where(eye, l1[:, None], dep)
    c2 = l2_counts.astype(jnp.float32)
    l2 = jnp.where(eye, 0.0, (c2 + c2.T) / (c2 + c2.T + 1.0))
    # AND-split measure m[a, b1, b2] = (b1<->b2 mass) / (a's output mass)
    and_m = (c + c.T)[None, :, :] / (c[:, :, None] + c[:, None, :] + 1.0)
    return dep, l2, and_m


@jax.jit
def _heuristics_graph(counts, l2_counts, dep, l2, and_m, dependency_threshold,
                      l2_threshold, min_count, and_threshold):
    a = counts.shape[0]
    eye = jnp.eye(a, dtype=bool)
    keep = (dep >= dependency_threshold) & ~eye & (counts >= min_count)
    loops1 = (jnp.diag(dep) >= dependency_threshold) & \
        (jnp.diag(counts) >= min_count)
    no_l1 = ~loops1[:, None] & ~loops1[None, :]
    sym2 = l2_counts + l2_counts.T
    keep2 = (l2 >= l2_threshold) & (sym2 >= min_count) & no_l1 & ~eye
    graph = keep | (eye & loops1[:, None]) | keep2 | keep2.T
    both = graph[:, :, None] & graph[:, None, :] & \
        ~jnp.eye(a, dtype=bool)[None, :, :]
    and_b = both & (and_m >= and_threshold)
    return graph, and_b


def discover_heuristics(state: "DiscoveryState | DFG",
                        l2_counts: jax.Array | None = None, *,
                        dependency_threshold: float = 0.5,
                        l2_threshold: float = 0.5,
                        and_threshold: float = 0.65,
                        min_count: int = 1) -> HeuristicsNet:
    """Heuristics miner over an accumulated :class:`DiscoveryState` (or a
    bare DFG plus its ``l2_counts``) — pure finalize, dense array math."""
    if isinstance(state, DiscoveryState):
        d, l2c = state.dfg, state.l2_counts
    else:
        d = state
        l2c = (jnp.zeros_like(d.counts) if l2_counts is None
               else jnp.asarray(l2_counts))
    dep, l2, and_m = _heuristics_measures(d.counts, l2c)
    graph, and_b = _heuristics_graph(
        d.counts, l2c, dep, l2, and_m,
        jnp.float32(dependency_threshold), jnp.float32(l2_threshold),
        jnp.int32(min_count), jnp.float32(and_threshold))
    starts = frozenset(int(i) for i in np.nonzero(obs.pull(d.starts))[0])
    ends = frozenset(int(i) for i in np.nonzero(obs.pull(d.ends))[0])
    return HeuristicsNet(dependency=dep, l2=l2, graph=graph,
                         and_bindings=and_b, start_activities=starts,
                         end_activities=ends)


# ------------------------------------------------------------ chunk kernel
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DiscoveryState:
    """Mergeable discovery accumulator: DFG + (A, A) L2-loop triple counts
    (``l2_counts[a, b]`` = #occurrences of the pattern ``a, b, a`` within a
    case).  ``merge`` is leafwise addition — the distributed merge is one
    psum of this pytree."""

    dfg: DFG
    l2_counts: jax.Array

    def tree_flatten(self):
        return (self.dfg, self.l2_counts), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_l2_carry(carry: engine.Carry) -> engine.Carry:
    """Extend a row carry with the two-back halo row (``exists2=False``
    masks every triple that would straddle the stream start)."""
    carry.update(case2=jnp.int32(-1), act2=jnp.int32(0),
                 rv2=jnp.bool_(False), exists2=jnp.bool_(False))
    return carry


def l2_triple_hits(chunk: engine.Chunk, carry: engine.Carry):
    """Per-row ``a, b, a`` detection with a two-row halo.

    Returns ``(prev2_act, prev_act, hit)``: row ``i`` contributes one
    ``l2_counts[act[i-2], act[i-1]]`` when all three rows share a case, are
    valid, and ``act[i] == act[i-2]`` — the carry supplies rows ``-1``/``-2``
    so any chunking yields the whole-log counts.  The one-row halo comes
    from ``engine.adjacent`` (the shared boundary semantics); only the
    two-back arrays are derived here.
    """
    adj = engine.adjacent(chunk, carry)
    case, act, rv = adj.case, adj.act, adj.rv
    n = case.shape[0]
    prev2_case = jnp.concatenate([carry["case2"][None].astype(case.dtype),
                                  carry["case"][None].astype(case.dtype),
                                  case[:-2]])[:n]
    prev2_act = jnp.concatenate([carry["act2"][None].astype(act.dtype),
                                 carry["act"][None].astype(act.dtype),
                                 act[:-2]])[:n]
    prev2_rv = jnp.concatenate([carry["rv2"][None], carry["rv"][None],
                                rv[:-2]])[:n]
    prev2_exists = jnp.concatenate([carry["exists2"][None],
                                    carry["exists"][None],
                                    jnp.ones((max(n - 2, 0),), bool)])[:n]
    hit = (adj.pair & (case == prev2_case)
           & prev2_rv & prev2_exists & (act == prev2_act))
    return prev2_act, adj.prev_act, hit


def next_l2_carry(carry: engine.Carry, old: engine.Carry,
                  chunk: engine.Chunk) -> engine.Carry:
    """Slide the two-back halo: the new two-back row is this chunk's
    second-to-last row (or, for a one-row chunk, the previous one-back)."""
    case = chunk[CASE]
    act = chunk[ACTIVITY]
    rv = chunk.rows_valid()
    if case.shape[0] >= 2:
        carry.update(case2=case[-2].astype(jnp.int32),
                     act2=act[-2].astype(jnp.int32),
                     rv2=rv[-2], exists2=jnp.bool_(True))
    else:
        carry.update(case2=old["case"], act2=old["act"], rv2=old["rv"],
                     exists2=old["exists"])
    return carry


def discovery_kernel(num_activities: int,
                     method: str = "auto") -> engine.ChunkKernel:
    """DFG + L2-loop counts as one mergeable chunk-kernel.

    The state is :class:`DiscoveryState`; the carry is the DFG kernel's
    one-row halo extended with the two-back row, so ``a, b, a`` triples
    split across chunk (or shard) boundaries are counted exactly once.
    ``method`` resolves through ``core.backend`` at factory time, like
    ``dfg_kernel``.
    """
    return _discovery_kernel(num_activities, _method_impl(method))


@lru_cache(maxsize=None)
def _discovery_kernel(num_activities: int, impl: str) -> engine.ChunkKernel:
    a = num_activities
    dk = _dfg_kernel_for(a, impl)

    def init():
        state, carry = dk.init()
        return ({"dfg": state, "l2": jnp.zeros((a, a), jnp.int32)},
                init_l2_carry(carry))

    @jax.jit
    def update(state, carry, chunk):
        p2, p1, hit = l2_triple_hits(chunk, carry)
        l2 = state["l2"] + pair_count(p2, p1, a, weights=hit, impl=impl)
        dfg_state, ncarry = dk.update(state["dfg"], carry, chunk)
        return ({"dfg": dfg_state, "l2": l2},
                next_l2_carry(ncarry, carry, chunk))

    def finalize(state, carry):
        return DiscoveryState(dk.finalize(state["dfg"], carry), state["l2"])

    def stitch(ctx):
        # the DFG half shares the one-row-halo stitch; the L2 half needs
        # the *two*-row halo: triples landing on b's first two rows were
        # invisible to b's fresh fold (its two-back carry had exists=False)
        at = ctx.a.tail
        ac = ctx.a.carry
        rows_b = ctx.b.head["rows"]
        b0 = rows_b[0]
        dfg_s = stitch_dfg_state(ctx.a.state["dfg"], ctx.b.state["dfg"],
                                 at, b0, ctx.straddle)
        l2 = ctx.a.state["l2"] + ctx.b.state["l2"]
        if ctx.straddle and at["rv"] and b0["rv"]:
            # triple (a[-2], a[-1], b0): a's two-back halo is in its carry
            if (bool(ac["exists2"]) and bool(ac["rv2"])
                    and int(ac["case2"]) == b0["case"]
                    and int(ac["act2"]) == b0["act"]):
                l2 = l2.at[int(ac["act2"]), at["act"]].add(1, mode="drop")
            # triple (a[-1], b0, b1): needs b's second leading row
            if ctx.b.rows >= 2:
                b1 = rows_b[1]
                if (b1["case"] == b0["case"] and b1["rv"]
                        and b1["case"] == at["case"]
                        and b1["act"] == at["act"]):
                    l2 = l2.at[at["act"], b0["act"]].add(1, mode="drop")
        overrides = {}
        if ctx.b.rows == 1:
            # the merged two-back row is a's last row, which b's one-row
            # fold could not know
            overrides = {"case2": jnp.int32(at["case"]),
                         "act2": jnp.int32(at["act"]),
                         "rv2": jnp.bool_(at["rv"]),
                         "exists2": jnp.bool_(True)}
        return {"dfg": dfg_s, "l2": l2}, overrides

    return engine.ChunkKernel(f"discovery[{impl}]", init, update,
                              engine.tree_sum, finalize,
                              columns=(ACTIVITY, CASE), stitch=stitch)


def _dfg_kernel_for(num_activities: int, impl: str) -> engine.ChunkKernel:
    # reuse the cached DFG kernel for the already-resolved impl
    method = {"xla": "segment", "matmul": "matmul", "pallas": "kernel"}[impl]
    return dfg_kernel(num_activities, method)


def alpha_kernel(num_activities: int, min_count: int = 1,
                 method: str = "auto") -> engine.ChunkKernel:
    """The alpha miner as the finalize of the *existing* DFG kernel state."""
    dk = dfg_kernel(num_activities, method)
    return engine.ChunkKernel(
        f"alpha[{dk.name}]", dk.init, dk.update, dk.merge,
        lambda s, c: discover_alpha(dk.finalize(s, c), min_count),
        mask_exact=dk.mask_exact, columns=dk.columns, stitch=dk.stitch)


def heuristics_kernel(num_activities: int, method: str = "auto",
                      **thresholds) -> engine.ChunkKernel:
    """The heuristics miner as the finalize of the discovery kernel state."""
    k = discovery_kernel(num_activities, method)
    return engine.ChunkKernel(
        f"heuristics[{k.name}]", k.init, k.update, k.merge,
        lambda s, c: discover_heuristics(k.finalize(s, c), **thresholds),
        mask_exact=k.mask_exact, columns=k.columns, stitch=k.stitch)


# ------------------------------------------------- whole-log entry points
def discovery_state(frame: EventFrame, num_activities: int,
                    method: str = "auto") -> DiscoveryState:
    """DFG + L2 counts of a (case,time)-sorted frame: the single-chunk
    special case of :func:`discovery_kernel`."""
    return engine.run_single(discovery_kernel(num_activities, method), frame)


def alpha(frame: EventFrame, num_activities: int, min_count: int = 1,
          method: str = "auto") -> AlphaModel:
    """Whole-log alpha miner (single-chunk special case)."""
    return engine.run_single(
        alpha_kernel(num_activities, min_count, method), frame)


def heuristics(frame: EventFrame, num_activities: int, method: str = "auto",
               **thresholds) -> HeuristicsNet:
    """Whole-log heuristics miner (single-chunk special case)."""
    return engine.run_single(
        heuristics_kernel(num_activities, method, **thresholds), frame)


# --------------------------------------------------------- streaming API
def streaming_discovery_state(chunks, num_activities: int,
                              method: str = "auto") -> DiscoveryState:
    """Out-of-core DFG + L2 accumulation: one pass, O(chunk) residency."""
    return engine.run_streaming(discovery_kernel(num_activities, method),
                                chunks)


def streaming_alpha(chunks, num_activities: int, min_count: int = 1,
                    method: str = "auto") -> AlphaModel:
    """Out-of-core alpha miner — bitwise-identical to the whole-log pass
    for any chunking (integer counting is order-exact)."""
    return engine.run_streaming(
        alpha_kernel(num_activities, min_count, method), chunks)


def streaming_heuristics(chunks, num_activities: int, method: str = "auto",
                         **thresholds) -> HeuristicsNet:
    """Out-of-core heuristics miner — bitwise-identical to whole-log."""
    return engine.run_streaming(
        heuristics_kernel(num_activities, method, **thresholds), chunks)


engine.register_kernel(engine.KernelSpec(
    "discovery",
    make=lambda dims, method="auto": discovery_kernel(
        dims.num_activities, method),
    columns=(ACTIVITY, CASE),
    sharded_state="discovery",
    from_sharded=lambda state, **_: state,
    doc="DFG + L2-loop triple counts (feeds alpha/heuristics host-side)"))
engine.register_kernel(engine.KernelSpec(
    "alpha",
    make=lambda dims, min_count=1, method="auto": alpha_kernel(
        dims.num_activities, min_count, method),
    columns=(ACTIVITY, CASE),
    sharded_state="dfg",
    from_sharded=lambda state, min_count=1, **_: discover_alpha(
        state, min_count),
    doc="alpha miner (finalize of the DFG state)"))
engine.register_kernel(engine.KernelSpec(
    "heuristics",
    make=lambda dims, method="auto", **thresholds: heuristics_kernel(
        dims.num_activities, method, **thresholds),
    columns=(ACTIVITY, CASE),
    sharded_state="discovery",
    from_sharded=lambda state, method="auto", **thresholds:
        discover_heuristics(state, **thresholds),
    doc="heuristics miner (finalize of the discovery state)"))
