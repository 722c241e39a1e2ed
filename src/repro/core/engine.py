"""Chunked out-of-core execution engine — mergeable chunk-kernels.

The paper's headline scenario (Table 6) is a log that does *not* fit in
device memory.  This module restructures every log algorithm around
device-sized partitions of a (case, time)-sorted log: an algorithm is a
:class:`ChunkKernel` — a 4-tuple ``(init, update, merge, finalize)``::

    state, carry = kernel.init()
    for chunk in chunks:                      # EventFrame chunks, in order
        state, carry = kernel.update(state, carry, chunk)
    result = kernel.finalize(state, carry)

* ``state`` is the mergeable partial result (count matrices, histograms,
  min/max accumulators).  ``merge(a, b)`` combines the states of two runs
  over consecutive log partitions whose boundary rows were stitched with
  carries; in the distributed lowering the merge is a ``psum``
  (``repro.distributed.dfg``) — one all-reduce whose payload is
  independent of N.
* ``carry`` is the one-row halo: the last row of the previous chunk
  (case id, activity, timestamp, row-validity, and an ``exists`` flag that
  is False only before the first row), plus kernel-specific streaming
  state (open global segment id, rolling variant hash, EFG prefix
  vector).  The carry is what stitches directly-follows pairs, case
  starts/ends, and case-local scans across chunk boundaries, so *any*
  chunking of a sorted log yields results identical to the whole-log pass
  — including cases split across many chunks.

The whole-log jitted entry points in ``core.dfg`` / ``core.stats`` /
``core.variants`` / ``core.performance`` / ``core.filtering`` are the
single-chunk special case of these kernels.  :func:`run_streaming` drives
a kernel over any iterable of chunks (``core.chunked.ChunkedEventFrame``:
EDF row groups on disk, an in-memory frame, or the synthetic generator)
with peak residency of one chunk's columns plus an O(1) carry.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Iterable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import polyhash
from .eventframe import ACTIVITY, CASE, TIMESTAMP, EventFrame

State = Any
Carry = dict
Chunk = EventFrame


@dataclasses.dataclass(frozen=True)
class ChunkKernel:
    """A log algorithm in mergeable chunk form (see module docstring).

    ``update`` is jit-compiled by the factory that builds the kernel; it
    retraces once per distinct chunk shape (a fixed-size chunk stream plus
    one tail shape compiles exactly twice).  Drivers pass each state and
    carry to ``update`` once and keep only what it returns: a fused
    kernel's ``update`` donates its inputs (:func:`compose`).

    ``mask_exact`` declares the kernel stays exact on a pruned stream:
    either masked rows contribute nothing to the state (the usual case —
    they may still move the carry's case/segment bookkeeping), or the
    kernel recovers whatever masked rows would have contributed from the
    ghost-chunk metadata the query layer supplies.  This is what lets
    ``repro.query`` replace a row group whose rows are all refuted by a
    predicate with an O(segments) ghost chunk instead of reading it.

    ``ghost_sketch`` asks the query layer to attach per-segment affine
    polyhash maps (``repro.core.polyhash.SKETCH_COLUMNS``, composed from
    EDF header sketches) to the ghost chunks it synthesizes — how the
    variants kernel replays the exact validity-blind hash of skipped runs
    without reading them, keeping ``mask_exact=True``.

    ``columns`` names the event columns ``update`` reads (what a
    projected scan must materialize for this kernel).  The empty tuple
    means "unknown — read everything"; :func:`compose` unions member
    column sets, so a fused kernel's scan can never starve one member of
    a column it needs.

    ``stitch`` declares the kernel's *group-state algebra* support: given
    a :class:`StitchCtx` pairing two :class:`GroupState` fresh folds, it
    returns the state (and carry overrides) of the fresh fold of the
    concatenation — an O(1) boundary-halo fix on top of elementwise
    combination.  ``None`` marks the kernel non-mergeable at the group
    level (order-sensitive float accumulation: the sum of f32 chunk
    contributions depends on fold order bitwise), in which case drivers
    fall back to the sequential ``update`` stream.  Everything a stitch
    may consume is exact under reordering (integer counts, min/max,
    uint32 hashes, integer-valued f32 below 2^24), which is what makes
    the merge associative *bitwise*, not just mathematically.
    """

    name: str
    init: Callable[[], tuple[State, Carry]]
    update: Callable[[State, Carry, Chunk], tuple[State, Carry]]
    merge: Callable[[State, State], State]
    finalize: Callable[[State, Carry], Any]
    mask_exact: bool = True
    columns: tuple = ()
    ghost_sketch: bool = False
    stitch: Callable[["StitchCtx"], tuple[State, dict]] | None = None


# ------------------------------------------------------- kernel registry
class Dims(NamedTuple):
    """The two capacity dimensions that size every kernel's state."""

    num_activities: int
    num_cases: int


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A terminal mining verb as *data* — the registry entry behind the
    ``repro.dataset`` facade (and any other generic driver).

    Instead of an if-chain mapping verb names to kernel factories, each
    algorithm module registers one spec describing everything a driver
    needs to run it over any source:

    * ``make(dims, **kwargs)`` — build the :class:`ChunkKernel` (``dims``
      carries both capacity dimensions; the factory picks the one(s) its
      state needs);
    * ``columns`` — the event columns the kernel's ``update`` reads (what a
      scan must project; predicates add their own columns at plan time);
    * ``sharded_state`` — name of the distributed driver that produces this
      verb's mergeable state (``"dfg"`` / ``"discovery"``), or ``None`` when
      the verb has no exact distributed lowering (order-sensitive float
      sums, validity-blind hashes);
    * ``from_sharded(state, **kwargs)`` — host-side finalize mapping that
      distributed state to the verb's result (identity for DFG, the model
      discovery step for alpha/heuristics);
    * ``members`` — for fused specs (:func:`compose_specs`): the member
      verb names, in collection order (empty for an ordinary verb).
    """

    name: str
    make: Callable[..., ChunkKernel]
    columns: tuple
    sharded_state: str | None = None
    from_sharded: Callable | None = None
    doc: str = ""
    members: tuple = ()


_KERNEL_SPECS: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    """Register (or replace) a terminal verb; returns the spec for chaining."""
    _KERNEL_SPECS[spec.name] = spec
    return spec


def _load_standard_specs() -> None:
    # algorithm modules register their specs at import time; make sure the
    # standard set is loaded before deciding a name is unknown
    from . import dfg, discovery, performance, stats, variants  # noqa: F401
    from repro.graph import verbs  # noqa: F401


def kernel_spec(name: str) -> KernelSpec:
    """Look up a registered verb by name (KeyError lists what exists and
    suggests close matches for typos)."""
    if name not in _KERNEL_SPECS:
        _load_standard_specs()
    try:
        return _KERNEL_SPECS[name]
    except KeyError:
        import difflib

        close = difflib.get_close_matches(name, _KERNEL_SPECS, n=3)
        hint = f" (did you mean {' / '.join(map(repr, close))}?)" if close else ""
        raise KeyError(f"no kernel spec named {name!r}{hint}; registered: "
                       f"{sorted(_KERNEL_SPECS)}") from None


def kernel_specs() -> dict[str, KernelSpec]:
    """Snapshot of the registry (import the core modules to populate it)."""
    _load_standard_specs()
    return dict(_KERNEL_SPECS)


# --------------------------------------------------------------- carries
def init_row_carry(**extra) -> Carry:
    """The halo before the first row: ``exists=False`` masks everything."""
    carry = {
        "case": jnp.int32(-1),
        "act": jnp.int32(0),
        "ts": jnp.float32(0.0),
        "rv": jnp.bool_(False),
        "exists": jnp.bool_(False),
    }
    carry.update(extra)
    return carry


def next_row_carry(carry: Carry, frame: Chunk, **extra) -> Carry:
    """Carry for the next chunk: this chunk's last row + kernel extras."""
    out = dict(carry)
    out["case"] = frame[CASE][-1].astype(jnp.int32)
    out["act"] = frame[ACTIVITY][-1].astype(jnp.int32)
    if TIMESTAMP in frame:
        out["ts"] = frame[TIMESTAMP][-1].astype(jnp.float32)
    out["rv"] = frame.rows_valid()[-1]
    out["exists"] = jnp.bool_(True)
    out.update(extra)
    return out


class Adjacent(NamedTuple):
    """Per-row arrays pairing each row with its predecessor (carry at row 0).

    Semantics match the whole-log adjacency exactly: ``pair`` marks
    directly-follows pairs (same case, both rows valid), ``new_seg`` marks
    case-segment starts *ignoring* validity (as ``ops.segment_ids_sorted``
    does), ``is_start``/``end_prev`` are the start/end-activity events.
    ``end_prev[i]`` says row ``i-1`` (the carry for ``i=0``) ended its case;
    the final row's end is resolved by ``finalize`` from the last carry.
    """

    case: jax.Array
    act: jax.Array
    rv: jax.Array
    ts: jax.Array
    prev_case: jax.Array
    prev_act: jax.Array
    prev_rv: jax.Array
    prev_ts: jax.Array
    prev_exists: jax.Array
    new_seg: jax.Array      # bool — row starts a new case segment
    pair: jax.Array         # bool — (prev row -> row) is a valid DF pair
    is_start: jax.Array     # bool — row is a start activity
    end_prev: jax.Array     # bool — previous row was an end activity


def adjacent(frame: Chunk, carry: Carry, *, need_ts: bool = False) -> Adjacent:
    case = frame[CASE]
    act = frame[ACTIVITY]
    rv = frame.rows_valid()
    n = case.shape[0]
    if TIMESTAMP in frame:
        ts = frame[TIMESTAMP].astype(jnp.float32)
    elif need_ts:
        raise KeyError(TIMESTAMP)   # timed kernel on an untimed frame
    else:
        ts = jnp.zeros((n,), jnp.float32)
    prev_case = jnp.concatenate([carry["case"][None].astype(case.dtype), case[:-1]])
    prev_act = jnp.concatenate([carry["act"][None].astype(act.dtype), act[:-1]])
    prev_ts = jnp.concatenate([carry["ts"][None].astype(ts.dtype), ts[:-1]])
    prev_rv = jnp.concatenate([carry["rv"][None], rv[:-1]])
    prev_exists = jnp.concatenate(
        [carry["exists"][None], jnp.ones((n - 1,), bool)])
    new_seg = (case != prev_case) | ~prev_exists
    pair = (case == prev_case) & prev_exists & rv & prev_rv
    is_start = new_seg & rv
    end_prev = (case != prev_case) & prev_exists & prev_rv
    return Adjacent(case, act, rv, ts, prev_case, prev_act, prev_rv, prev_ts,
                    prev_exists, new_seg, pair, is_start, end_prev)


def global_segments(adj: Adjacent, carry: Carry) -> jax.Array:
    """Global case-segment ids for a chunk: ``carry['seg']`` continues the
    numbering (``-1`` before the first row, so the first segment is 0)."""
    return carry["seg"] + jnp.cumsum(adj.new_seg.astype(jnp.int32))


# --------------------------------------------------------------- drivers
def run_streaming(kernel: ChunkKernel, chunks: Iterable[Chunk]):
    """Fold a kernel over an ordered chunk stream; O(chunk) residency."""
    state, carry = kernel.init()
    for i, chunk in enumerate(chunks):
        if chunk.nrows == 0:        # empty source / empty tail group
            continue
        with obs.span("fold.update", group=i):
            state, carry = kernel.update(state, carry, chunk)
    with obs.span("fold.finalize"):
        return kernel.finalize(state, carry)


def run_single(kernel: ChunkKernel, frame: Chunk):
    """The single-chunk special case: how the whole-log jitted entry points
    route through the same kernel code as the streaming/distributed paths."""
    state, carry = kernel.init()
    state, carry = kernel.update(state, carry, frame)
    return kernel.finalize(state, carry)


# ------------------------------------------------- group-state algebra
# A GroupState is the *fresh* fold of a kernel over one contiguous unit of
# the sorted log (a row group, a shard span, a whole file): state + carry
# from ``init()``, case segments numbered locally from 0, plus the boundary
# halo a later merge needs — the unit's leading row(s) and the lead run's
# histogram/affine summaries.  ``merge_group_states`` reconstructs, bitwise,
# the fresh fold of the concatenation of two units, so
#
#     finalize(merge_tree([fold_group(unit) for unit in units]))
#     ==  run_streaming(kernel, all chunks)            (bitwise)
#
# for every kernel with a ``stitch``.  That single identity is what makes
# eager (one unit), streaming (one unit per row group, cacheable), sharded
# (one unit per shard span), windowed (merge a slice of units), and
# incremental (re-merge cached units + fold fresh ones) the *same* schedule
# family over one algebra.
@dataclasses.dataclass
class GroupState:
    """Fresh fold of one contiguous unit: mergeable, cacheable, re-usable.

    ``head`` / ``tail`` are the boundary halo (host-side python values):
    ``head["rows"]`` holds up to two leading physical rows (the two-row
    stitch the L2-loop kernels need), ``head["hist"]`` the valid-activity
    histogram of the unit's *lead run* (all leading rows of its first
    case — the EFG cross term), ``head["affine"]`` the validity-blind
    polyhash map of that lead run (the variants hash correction).
    ``segments``/``rows`` count case segments (locally numbered from 0)
    and physical rows.  ``rows == 0`` is the merge identity.
    """

    state: State
    carry: Carry
    head: dict | None
    tail: dict | None
    segments: int
    rows: int


class StitchCtx(NamedTuple):
    """Everything a kernel ``stitch`` may consult to merge ``a ++ b``:
    ``straddle`` says the boundary splits one case segment, ``offset`` is
    the relabel added to ``b``'s local segment ids (``a.segments``, minus
    one when the straddling segment keeps ``a``'s numbering)."""

    a: GroupState
    b: GroupState
    straddle: bool
    offset: int


def mergeable(kernel: ChunkKernel) -> bool:
    """Does this kernel support the group-state algebra (has a stitch)?"""
    return kernel.stitch is not None


def empty_group_state(kernel: ChunkKernel) -> GroupState:
    """The merge identity: the fresh fold of zero rows."""
    state, carry = kernel.init()
    return GroupState(state, carry, None, None, 0, 0)


def shift_segments(arr: jax.Array, offset: int, fill=0) -> jax.Array:
    """Relabel a per-segment state vector by ``offset`` slots (how a merge
    maps ``b``'s local segment ids into the concatenation's numbering).
    Entries shifted past capacity drop — matching the sequential fold's
    out-of-range scatter drop.  The offset is traced, so merges at any
    offset share one program per state shape."""
    if offset <= 0:
        return arr
    return _shift_segments(arr, min(int(offset), arr.shape[0]), fill)


@jax.jit
def _shift_segments(arr, offset, fill):
    cap = arr.shape[0]
    padded = jnp.concatenate([jnp.full_like(arr, fill), arr])
    return jax.lax.dynamic_slice_in_dim(padded, cap - offset, cap)


def _compose4(a: tuple, b: tuple) -> tuple:
    """Compose two (mul1, add1, mul2, add2) affine-map quadruples."""
    m1, a1 = polyhash.compose(a[0], a[1], b[0], b[1])
    m2, a2 = polyhash.compose(a[2], a[3], b[2], b[3])
    return (m1, a1, m2, a2)


def fold_group(kernel: ChunkKernel, chunks: Iterable[Chunk]) -> GroupState:
    """Fold a kernel *freshly* over one contiguous unit of the stream,
    capturing the boundary halo a later :func:`merge_group_states` needs.

    The state/carry fold is exactly :func:`run_streaming`'s loop (bitwise);
    the halo bookkeeping is host-side numpy over the same chunks.  Ghost
    chunks participate like real ones: their rows are masked (so the lead
    histogram stays empty) and their sketch columns supply the lead run's
    composed affine map.
    """
    with obs.span("fold.group"):
        return _fold_group(kernel, chunks)


def _fold_group(kernel: ChunkKernel, chunks: Iterable[Chunk]) -> GroupState:
    state, carry = kernel.init()
    segments = 0
    rows = 0
    head_rows: list[dict] = []
    hist: dict[int, int] = {}
    affine = (1, 0, 1, 0)
    lead_open = True
    first_case = None
    tail = None
    for chunk in chunks:
        n = int(chunk.nrows)
        if n == 0:
            continue
        case = obs.pull(chunk[CASE])
        act = obs.pull(chunk[ACTIVITY])
        rv = obs.pull(chunk.rows_valid())
        cont = rows > 0 and int(case[0]) == tail["case"]
        changes = np.flatnonzero(case[1:] != case[:-1])
        segments += 1 + int(changes.size) - (1 if cont else 0)
        if rows == 0:
            first_case = int(case[0])
        while len(head_rows) < 2 and len(head_rows) < rows + n:
            i = len(head_rows) - rows
            head_rows.append({"case": int(case[i]), "act": int(act[i]),
                              "rv": bool(rv[i])})
        if lead_open and rows > 0 and not cont:
            lead_open = False
        if lead_open:
            k = int(changes[0]) + 1 if changes.size else n
            counts = np.bincount(act[:k][rv[:k]])
            for a_id in np.flatnonzero(counts):
                hist[int(a_id)] = hist.get(int(a_id), 0) + int(counts[a_id])
            if polyhash.SK_MUL1 in chunk:
                m1 = obs.pull(chunk[polyhash.SK_MUL1])[:k]
                a1 = obs.pull(chunk[polyhash.SK_ADD1])[:k]
                m2 = obs.pull(chunk[polyhash.SK_MUL2])[:k]
                a2 = obs.pull(chunk[polyhash.SK_ADD2])[:k]
                for i in np.flatnonzero((m1 != 1) | (a1 != 0)
                                        | (m2 != 1) | (a2 != 0)):
                    affine = _compose4(affine, (int(m1[i]), int(a1[i]),
                                                int(m2[i]), int(a2[i])))
            else:
                sk = polyhash.segment_sketch(act[:k], np.zeros(k, np.int64))
                affine = _compose4(affine, (int(sk["mul1"][0]),
                                            int(sk["add1"][0]),
                                            int(sk["mul2"][0]),
                                            int(sk["add2"][0])))
            if changes.size:
                lead_open = False
        with obs.span("fold.update"):
            state, carry = kernel.update(state, carry, chunk)
        rows += n
        tail = {"case": int(case[-1]), "act": int(act[-1]), "rv": bool(rv[-1])}
    if rows == 0:
        return GroupState(state, carry, None, None, 0, 0)
    head = {"case": first_case, "rows": tuple(head_rows),
            "hist": hist, "affine": affine}
    return GroupState(state, carry, head, tail, segments, rows)


def _shift_carry(carry, offset: int):
    """Recursively relabel every ``"seg"`` entry of a (possibly composed)
    carry by the merge's segment offset."""
    if not isinstance(carry, dict):
        return carry
    out = {}
    for k, v in carry.items():
        if k == "seg":
            out[k] = v + jnp.int32(offset)
        elif isinstance(v, dict):
            out[k] = _shift_carry(v, offset)
        else:
            out[k] = v
    return out


def _apply_overrides(carry: dict, overrides: dict) -> dict:
    out = dict(carry)
    for k, v in overrides.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _apply_overrides(out[k], v)
        else:
            out[k] = v
    return out


def _merge_head(a: GroupState, b: GroupState, straddle: bool) -> dict:
    head = dict(a.head)
    head["rows"] = (a.head["rows"] + b.head["rows"])[:2]
    if straddle and a.segments == 1:
        # a is entirely one case run that continues into b: the merged
        # unit's lead run is a's rows followed by b's lead run
        hist = dict(a.head["hist"])
        for act, cnt in b.head["hist"].items():
            hist[act] = hist.get(act, 0) + cnt
        head["hist"] = hist
        head["affine"] = _compose4(a.head["affine"], b.head["affine"])
    return head


def merge_group_states(kernel: ChunkKernel, a: GroupState,
                       b: GroupState) -> GroupState:
    """The algebra's ``merge``: the fresh fold of ``a ++ b``, bitwise.

    Elementwise state combination plus the kernel's O(1) boundary stitch;
    ``b``'s carry becomes the merged carry with its local segment ids
    relabelled (and any kernel-specific overrides applied).  Associative
    — merging reconstructs fresh folds, so any merge-tree shape over the
    same ordered units yields the same bits.
    """
    if a.rows == 0:
        return b
    if b.rows == 0:
        return a
    if kernel.stitch is None:
        raise ValueError(
            f"kernel {kernel.name!r} has no group-state stitch "
            "(order-sensitive float state); use the sequential fold")
    straddle = a.tail["case"] == b.head["case"]
    offset = a.segments - (1 if straddle else 0)
    state, overrides = kernel.stitch(StitchCtx(a, b, straddle, offset))
    carry = _shift_carry(b.carry, offset)
    if overrides:
        carry = _apply_overrides(carry, overrides)
    return GroupState(state, carry, _merge_head(a, b, straddle), b.tail,
                      a.segments + b.segments - (1 if straddle else 0),
                      a.rows + b.rows)


def merge_tree(kernel: ChunkKernel, states: Iterable[GroupState]) -> GroupState:
    """Reduce ordered unit states pairwise (a balanced merge tree).

    The tree shape is a free choice — the merge is bitwise-associative —
    so this is simultaneously the reduction the sharded engine runs over
    shard spans, the re-merge a sliding window runs over its ring of
    cached group states, and the combine an incremental collect runs over
    cached + fresh groups.
    """
    level = [s for s in states if s is not None and s.rows > 0]
    if not level:
        return empty_group_state(kernel)
    with obs.span("fold.merge"):
        while len(level) > 1:
            nxt = [merge_group_states(kernel, level[i], level[i + 1])
                   for i in range(0, len(level) - 1, 2)]
            if len(level) % 2:
                nxt.append(level[-1])
            level = nxt
    return level[0]


def finalize_group(kernel: ChunkKernel, gs: GroupState):
    """Terminal step of the algebra: the kernel's ordinary ``finalize``."""
    with obs.span("fold.finalize"):
        return kernel.finalize(gs.state, gs.carry)


def union_columns(column_sets: Iterable[tuple]) -> tuple:
    """Union column requirements in first-seen order; any *unknown* set
    (the empty tuple) makes the union unknown — read everything."""
    out: list = []
    for cols in column_sets:
        if not cols:
            return ()
        for c in cols:
            if c not in out:
                out.append(c)
    return tuple(out)


# One fused update program per member set, shared by every compose() over
# the same members: compose_specs(...).make() builds a new compose on every
# collect, and a jax.jit made there would trace and compile again each time.
_FUSED_UPDATES: dict[tuple, Callable] = {}


def _program(update: Callable):
    """The jitted program behind a member ``update``: the update itself, or
    the one a fused compose dispatches under its span; ``None`` for a plain
    Python function (which cannot join a fused program)."""
    fn = getattr(update, "program", update)
    return fn if isinstance(fn, jax.stages.Wrapped) else None


def _fused_update(members: tuple) -> Callable:
    """One jitted program running every ``(name, program)`` member update
    in order — the members inline into its trace — dispatched under the
    ``fold.update.fused`` span.

    It donates the state and carry it is given, so the outputs reuse
    their buffers: a TPU host spends tens of microseconds on every output
    buffer it has to allocate, and a fused profile returns some 150 of
    them a chunk, so without donation the dispatch alone outlasts the
    device's work.  ``keep_unused`` keeps the inputs a member ignores (a
    carry it rebuilds from the chunk) in the program, so their buffers are
    donated too.  Every driver drops the state and carry it passed in.
    """
    update = _FUSED_UPDATES.get(members)
    if update is None:
        @functools.partial(jax.jit, donate_argnums=(0, 1), keep_unused=True)
        def fused_update(state, carry, chunk):
            out_s, out_c = {}, {}
            for k, fn in members:
                out_s[k], out_c[k] = fn(state[k], carry[k], chunk)
            return out_s, out_c

        def update(state, carry, chunk):
            with obs.span("fold.update.fused"):
                return fused_update(state, carry, chunk)

        update.program = fused_update
        update = _FUSED_UPDATES.setdefault(members, update)
    return update


def compose(kernels: Mapping[str, ChunkKernel]) -> ChunkKernel:
    """Fuse kernels into one that shares a single pass over the stream.

    States/carries are dicts keyed like ``kernels``; ``finalize`` returns a
    dict of results. One disk scan computes DFG + stats + variants at once.

    When there are several members and every member ``update`` is jitted,
    the fused ``update`` is one jitted program over all of them (one
    dispatch a chunk), shared by every compose of the same member updates,
    and it consumes the state and carry it is given (they are donated);
    otherwise it calls each member's ``update`` in turn.

    The fused kernel's ``columns`` is the *union* of the members' column
    requirements (unknown if any member's is unknown), ``mask_exact`` the
    conjunction (every registered verb is pruning-exact, so fused scans
    always prune), and ``ghost_sketch`` the disjunction — one
    sketch-consuming member is enough for ghost chunks to carry sketches.
    """
    names = tuple(kernels)

    def init():
        pairs = {k: kernels[k].init() for k in names}
        return ({k: s for k, (s, _) in pairs.items()},
                {k: c for k, (_, c) in pairs.items()})

    programs = tuple((k, _program(kernels[k].update)) for k in names)
    if len(names) > 1 and all(fn is not None for _, fn in programs):
        update = _fused_update(programs)
    else:
        spans = {k: f"fold.update.{k}" for k in names}

        def update(state, carry, chunk):
            out_s, out_c = {}, {}
            for k in names:
                with obs.span(spans[k]):
                    out_s[k], out_c[k] = kernels[k].update(state[k],
                                                           carry[k], chunk)
            return out_s, out_c

    def merge(a, b):
        return {k: kernels[k].merge(a[k], b[k]) for k in names}

    def finalize(state, carry):
        return {k: kernels[k].finalize(state[k], carry[k]) for k in names}

    # the fused kernel joins the group-state algebra exactly when every
    # member does: its stitch slices the dict state/carry per member and
    # runs each member's stitch under the shared boundary halo
    stitch = None
    if all(k.stitch is not None for k in kernels.values()):
        def stitch(ctx):
            states, overrides = {}, {}
            for k in names:
                sub = StitchCtx(
                    dataclasses.replace(ctx.a, state=ctx.a.state[k],
                                        carry=ctx.a.carry[k]),
                    dataclasses.replace(ctx.b, state=ctx.b.state[k],
                                        carry=ctx.b.carry[k]),
                    ctx.straddle, ctx.offset)
                states[k], over = kernels[k].stitch(sub)
                if over:
                    overrides[k] = over
            return states, overrides

    return ChunkKernel("compose(" + ",".join(names) + ")",
                       init, update, merge, finalize,
                       mask_exact=all(k.mask_exact for k in kernels.values()),
                       columns=union_columns(
                           k.columns for k in kernels.values()),
                       ghost_sketch=any(
                           k.ghost_sketch for k in kernels.values()),
                       stitch=stitch)


def compose_specs(specs: Mapping[str, KernelSpec]) -> KernelSpec:
    """Fuse registered verbs into one first-class :class:`KernelSpec`.

    The fused spec is what makes multi-verb collection an ordinary verb to
    every driver: its ``make`` builds the :func:`compose` of the member
    kernels (``verb_kwargs`` routes per-verb options), its ``columns`` is
    the union of the member column sets (the projection a shared scan must
    read), and its ``sharded_state`` is ``"fused"`` exactly when *every*
    member has an exact distributed lowering — ``repro.distributed.query``
    then drives the composed state kernels through the same ppermute-halo
    + psum path in one pass.  Results come back as ``{verb: result}``,
    bitwise equal per verb to running each member alone.
    """
    specs = dict(specs)
    if not specs:
        raise ValueError("compose_specs() needs at least one verb")
    names = tuple(specs)

    def make(dims: Dims, verb_kwargs: Mapping[str, dict] | None = None,
             **common) -> ChunkKernel:
        vk = dict(verb_kwargs or {})
        unknown = set(vk) - set(names)
        if unknown:
            raise KeyError(f"verb_kwargs for verbs not in the fused set: "
                           f"{sorted(unknown)} (fusing {list(names)})")
        return compose({v: specs[v].make(dims, **{**common, **vk.get(v, {})})
                        for v in names})

    sharded = ("fused" if all(s.sharded_state is not None
                              for s in specs.values()) else None)
    return KernelSpec(
        name="fused(" + ",".join(names) + ")",
        make=make,
        columns=union_columns(s.columns for s in specs.values()),
        sharded_state=sharded,
        from_sharded=None,      # the fused driver finalizes per member
        doc="fused multi-verb collection: " + ", ".join(names),
        members=names)


def tree_sum(a, b):
    """The common merge: leafwise addition of two partial states."""
    return jax.tree.map(jnp.add, a, b)


# --------------------------------------------- convenience streaming API
# Thin front doors; kernel factories live next to their whole-log twins
# (lazy imports keep core.<algo> -> engine one-directional).
def streaming_dfg(chunks, num_activities: int, method: str = "segment"):
    from .dfg import dfg_kernel
    return run_streaming(dfg_kernel(num_activities, method=method), chunks)


def streaming_activity_counts(chunks, num_activities: int):
    from .stats import activity_counts_kernel
    return run_streaming(activity_counts_kernel(num_activities), chunks)


def streaming_case_sizes(chunks, num_cases: int):
    from .stats import case_sizes_kernel
    return run_streaming(case_sizes_kernel(num_cases), chunks)


def streaming_case_durations(chunks, num_cases: int):
    from .stats import case_durations_kernel
    return run_streaming(case_durations_kernel(num_cases), chunks)


def streaming_sojourn_times(chunks, num_activities: int):
    from .stats import sojourn_times_kernel
    return run_streaming(sojourn_times_kernel(num_activities), chunks)


def streaming_variant_fingerprints(chunks, num_cases: int):
    from .variants import variants_kernel
    return run_streaming(variants_kernel(num_cases), chunks)


def streaming_variant_counts(chunks, num_cases: int):
    from .variants import streaming_variant_counts as _svc
    return _svc(chunks, num_cases)


def streaming_performance_dfg(chunks, num_activities: int):
    from .performance import performance_dfg_kernel
    return run_streaming(performance_dfg_kernel(num_activities), chunks)


def streaming_eventually_follows(chunks, num_activities: int):
    from .performance import eventually_follows_kernel
    return run_streaming(eventually_follows_kernel(num_activities), chunks)
