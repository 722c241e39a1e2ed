"""Group-state algebra property tests (satellite of the merge-fold PR).

For every registered *mergeable* KernelSpec, under both
``REPRO_SEGMENT_BACKEND`` implementations: ``merge_group_states`` is
associative, ``empty_group_state`` is its identity, and any merge-tree
over fresh folds of contiguous slices — including single-row units and
states straddling row-group and file boundaries — finalizes bitwise
equal to mining the whole log in one fold.
"""
import dataclasses

import numpy as np
import pytest

from _prop import given, settings, strategies as st
from helpers import random_log, sorted_frame

import repro
from repro.core import backend, engine
from repro.core.eventframe import EventFrame
from repro.dataset import engines as ds_engines
from repro.query.statecache import state_cache
from repro.storage import edf

_DIMS = engine.Dims(5, 24)


def _mergeable_specs():
    out = []
    for name in sorted(engine.kernel_specs()):
        spec = engine.kernel_spec(name)
        if engine.mergeable(spec.make(_DIMS)):
            out.append(name)
    return out


MERGEABLE = _mergeable_specs()


def eq(a, b):
    """Structural bitwise equality over dataclasses/dicts/tuples/arrays
    (AlphaModel's elementwise ``__eq__`` breaks plain comparison)."""
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return type(a) is type(b) and all(
            eq(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, dict):
        return set(a) == set(b) and all(eq(a[k], b[k]) for k in a)
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(eq(x, y) for x, y in zip(a, b))
    if hasattr(a, "shape") or hasattr(b, "shape"):
        return np.array_equal(np.asarray(a), np.asarray(b))
    return a == b


def _slice(frame, a, b):
    return EventFrame({k: v[a:b] for k, v in frame.columns.items()},
                      {k: v[a:b] for k, v in frame.valid.items()},
                      frame.rows_valid()[a:b])


def _fold_slices(kernel, frame, bounds):
    return [engine.fold_group(kernel, [_slice(frame, a, b)] if b > a else [])
            for a, b in bounds]


@pytest.fixture(scope="module")
def log24():
    rng = np.random.default_rng(11)
    frame, tables = sorted_frame(
        random_log(rng, n_cases=24, n_acts=5, max_len=7))
    return frame


def test_registry_has_mergeable_kernels():
    # the algebra must cover the whole registry except the three
    # order-sensitive float folds
    assert set(MERGEABLE) >= {"dfg", "variants", "case_sizes",
                              "case_durations", "activity_counts",
                              "eventually_follows", "alpha", "heuristics",
                              "discovery"}


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1000), ca=st.integers(0, 120),
       cb=st.integers(0, 120), pick=st.integers(0, 1))
def test_merge_associativity_and_identity(seed, ca, cb, pick):
    """merge(merge(a,b),c) == merge(a,merge(b,c)); empty is the identity.

    Cut points are arbitrary row offsets, so slices routinely straddle a
    case (the stitch's hard path) and may be empty (the identity path).
    Each example draws one of the two segment backends.
    """
    with backend.use_backend(["xla", "pallas"][pick]):
        rng = np.random.default_rng(seed)
        frame, _ = sorted_frame(
            random_log(rng, n_cases=10, n_acts=5, max_len=6))
        n = frame.nrows
        i, j = sorted((min(ca, n), min(cb, n)))
        for name in MERGEABLE:
            kernel = engine.kernel_spec(name).make(engine.Dims(5, 10))
            a, b, c = _fold_slices(kernel, frame, [(0, i), (i, j), (j, n)])
            left = engine.merge_group_states(
                kernel, engine.merge_group_states(kernel, a, b), c)
            right = engine.merge_group_states(
                kernel, a, engine.merge_group_states(kernel, b, c))
            whole = engine.fold_group(kernel, [frame])
            r_left = engine.finalize_group(kernel, left)
            assert eq(r_left, engine.finalize_group(kernel, right)), name
            assert eq(r_left, engine.finalize_group(kernel, whole)), name
            # identity: merging the zero-row fold in on either side is a no-op
            empty = engine.empty_group_state(kernel)
            for s in (a, b, c):
                if s.rows:
                    assert engine.merge_group_states(kernel, empty, s) is s
                    assert engine.merge_group_states(kernel, s, empty) is s


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_single_row_units_merge_to_whole(impl, log24):
    """The extreme chunking: every physical row its own unit — every merge
    is a boundary stitch — still reduces to the whole-log bits."""
    with backend.use_backend(impl):
        frame = log24
        bounds = [(r, r + 1) for r in range(frame.nrows)]
        for name in MERGEABLE:
            kernel = engine.kernel_spec(name).make(_DIMS)
            states = _fold_slices(kernel, frame, bounds)
            got = engine.finalize_group(
                kernel, engine.merge_tree(kernel, states))
            ref = engine.finalize_group(
                kernel, engine.fold_group(kernel, [frame]))
            assert eq(ref, got), name


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000), pieces=st.integers(1, 9))
def test_merge_tree_shape_free(seed, pieces):
    """Balanced tree == left-to-right fold of merges: the tree shape is a
    free scheduling choice, not part of the result."""
    rng = np.random.default_rng(seed)
    frame, _ = sorted_frame(random_log(rng, n_cases=8, n_acts=4, max_len=5))
    cuts = sorted(int(rng.integers(0, frame.nrows + 1))
                  for _ in range(pieces - 1))
    bounds = list(zip([0] + cuts, cuts + [frame.nrows]))
    for name in ("dfg", "variants", "discovery", "eventually_follows"):
        kernel = engine.kernel_spec(name).make(engine.Dims(4, 8))
        states = _fold_slices(kernel, frame, bounds)
        tree = engine.merge_tree(kernel, states)
        linear = engine.empty_group_state(kernel)
        for s in states:
            linear = engine.merge_group_states(kernel, linear, s)
        assert eq(engine.finalize_group(kernel, tree),
                  engine.finalize_group(kernel, linear)), name


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_states_straddle_group_and_file_boundaries(impl, tmp_path, log24):
    """Group states harvested from on-disk row groups — cases straddling
    both row-group and file boundaries — re-merge to the scratch fold."""
    with backend.use_backend(impl):
        frame = log24
        n = frame.nrows
        p1 = str(tmp_path / f"a_{impl}.edf")
        p2 = str(tmp_path / f"b_{impl}.edf")
        # a mid-case cut between the files, tiny row groups within them
        edf.write(p1, _slice(frame, 0, 2 * n // 3), {}, version=3,
                  row_group_rows=13)
        edf.write(p2, _slice(frame, 2 * n // 3, n), {}, version=3,
                  row_group_rows=13)
        ds = repro.open([p1, p2], num_activities=_DIMS[0],
                        num_cases=_DIMS[1])
        state_cache().clear()
        for name in MERGEABLE:
            kernel, states, report = ds_engines.group_states_for(ds, name)
            assert report.groups_total >= 4     # boundaries actually exist
            got = engine.finalize_group(
                kernel, engine.merge_tree(kernel, states))
            ref = engine.finalize_group(
                kernel, engine.fold_group(kernel, [frame]))
            assert eq(ref, got), name


def test_variants_merges_at_any_offset_share_one_program(log24):
    """``shift_segments`` takes a traced offset: ``variants`` merges at
    three different offsets compile its program once, and the later
    merges compile nothing."""
    from repro import obs

    # a capacity no other test folds at, so the first merge compiles
    kernel = engine.kernel_spec("variants").make(engine.Dims(5, 29))
    n = log24.nrows
    whole = engine.finalize_group(kernel, engine.fold_group(kernel, [log24]))
    offsets, compiles = [], []
    for cut in (n // 4, n // 2, 3 * n // 4):
        a, b = _fold_slices(kernel, log24, [(0, cut), (cut, n)])
        with obs.record() as rec:
            merged = engine.merge_tree(kernel, [a, b])
        offsets.append(a.segments)
        compiles.append(dict(rec.compiles))
        assert eq(engine.finalize_group(kernel, merged), whole), cut
    assert len(set(offsets)) == 3, offsets
    assert compiles[0].get("jit(_shift_segments)") == 1, compiles[0]
    assert compiles[1:] == [{}, {}], compiles
