"""Columnar discovery (alpha + heuristics) vs the classic-log oracle.

Parity: on any random log the columnar miners must reproduce the
row-oriented reference (``core.classic_log``) — places, start/end sets,
dependency/L2 measures, kept edges — under both segment backends.
Streaming: any chunking of a sorted log yields models bitwise-identical to
the whole-log pass (integer counting is order-exact; the two-row carry
stitches L2 triples across boundaries).
"""
import numpy as np
import pytest
from _prop import given, settings, strategies as st

from repro.core import (ACTIVITY, CASE, ChunkedEventFrame, conformance,
                        discovery, use_backend)
from repro.core.classic_log import (alpha_reference, heuristics_reference,
                                    make_classic_log)

from helpers import random_log, sorted_frame

BACKENDS = ("xla", "pallas")


def _log_from_traces(traces):
    t = 0.0
    cases = []
    for i, tr in enumerate(traces):
        timed = []
        for a in tr:
            t += 1.0
            timed.append((a, t))
        cases.append((i, timed))
    return make_classic_log(cases)


def _labeled_places(model, acts):
    return {(frozenset(acts[i] for i in a), frozenset(acts[i] for i in b))
            for a, b in model.places}


def _labels(ids, acts):
    return frozenset(acts[i] for i in ids)


def _ref_matrix(measure: dict, acts) -> np.ndarray:
    m = np.zeros((len(acts), len(acts)), np.float64)
    for (x, y), v in measure.items():
        m[acts.index(x), acts.index(y)] = v
    return m


# ------------------------------------------------------------- textbook
def test_alpha_textbook_l1():
    """van der Aalst's L1: the miner must recover the canonical Y_L."""
    log = _log_from_traces([list("abcd")] * 3 + [list("acbd")] * 2
                           + [list("aed")])
    frame, tables = sorted_frame(log)
    acts = tables[ACTIVITY]
    model = discovery.alpha(frame, len(acts))
    want = {(frozenset("a"), frozenset("be")),
            (frozenset("a"), frozenset("ce")),
            (frozenset("be"), frozenset("d")),
            (frozenset("ce"), frozenset("d"))}
    assert _labeled_places(model, acts) == want
    assert _labels(model.start_activities, acts) == frozenset("a")
    assert _labels(model.end_activities, acts) == frozenset("d")
    assert model.num_places == len(want) + 2
    # the discovered footprint is perfectly self-conformant
    d = discovery.discovery_state(frame, len(acts)).dfg
    assert float(conformance.footprint_conformance(d, model)) == 1.0
    assert float(conformance.alpha_fitness(d, model)) == 1.0


def test_heuristics_loops():
    """L1 loops (e,e,e) stay diagonal; L2 loops (b,c,b) add both directions
    and are suppressed when a side already has an L1 loop."""
    log = _log_from_traces([list("abcbcbd")] * 3 + [list("aeeed")] * 2)
    frame, tables = sorted_frame(log)
    acts = tables[ACTIVITY]
    a = len(acts)
    state = discovery.discovery_state(frame, a)
    # L2 triple counts match the row-oriented count exactly
    ref_c2 = log.dfg_l2_iterative()
    got_c2 = np.asarray(state.l2_counts)
    assert {(acts[i], acts[j]): int(got_c2[i, j])
            for i, j in zip(*np.nonzero(got_c2))} == ref_c2
    net = discovery.discover_heuristics(state)
    _, _, ref_edges = heuristics_reference(log)
    got_edges = {(acts[i], acts[j]) for (i, j), _ in net.edges()}
    assert got_edges == ref_edges
    assert ("e", "e") in got_edges          # L1 loop on the diagonal
    assert ("b", "c") in got_edges and ("c", "b") in got_edges  # L2 pair
    fit = float(conformance.heuristics_fitness(state.dfg, net))
    assert 0.0 < fit <= 1.0


def test_heuristics_and_bindings():
    """a splits into concurrent b||c (AND) vs exclusive d|e (XOR)."""
    log = _log_from_traces([list("abcf")] * 5 + [list("acbf")] * 5
                           + [list("gdh")] * 5 + [list("geh")] * 5)
    frame, tables = sorted_frame(log)
    acts = tables[ACTIVITY]
    net = discovery.heuristics(frame, len(acts))
    ab = np.asarray(net.and_bindings)
    ia, ib, ic = acts.index("a"), acts.index("b"), acts.index("c")
    ig, idd, ie = acts.index("g"), acts.index("d"), acts.index("e")
    assert ab[ia, ib, ic] and ab[ia, ic, ib]      # b and c run concurrently
    assert not ab[ig, idd, ie] and not ab[ig, ie, idd]  # d xor e


# ------------------------------------------------- oracle parity property
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 5000))
def test_alpha_matches_reference(seed):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_cases=14, n_acts=5, max_len=6)
    frame, tables = sorted_frame(log)
    acts = tables[ACTIVITY]
    ref_places, ref_starts, ref_ends = alpha_reference(log)
    for backend in BACKENDS:
        with use_backend(backend):
            model = discovery.alpha(frame, len(acts))
        assert _labeled_places(model, acts) == ref_places, (seed, backend)
        assert _labels(model.start_activities, acts) == ref_starts
        assert _labels(model.end_activities, acts) == ref_ends


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 5000))
def test_heuristics_matches_reference(seed):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_cases=14, n_acts=5, max_len=6)
    frame, tables = sorted_frame(log)
    acts = tables[ACTIVITY]
    ref_dep, ref_l2, ref_edges = heuristics_reference(log)
    for backend in BACKENDS:
        with use_backend(backend):
            net = discovery.heuristics(frame, len(acts))
        np.testing.assert_allclose(np.asarray(net.dependency),
                                   _ref_matrix(ref_dep, acts),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=f"seed={seed} {backend}")
        np.testing.assert_allclose(np.asarray(net.l2),
                                   _ref_matrix(ref_l2, acts),
                                   rtol=1e-6, atol=1e-7)
        got_edges = {(acts[i], acts[j]) for (i, j), _ in net.edges()}
        assert got_edges == ref_edges, (seed, backend)


# ------------------------------------------------- streaming invariance
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 5000), n_chunks=st.integers(1, 12))
def test_discovery_chunk_invariance(seed, n_chunks):
    """Any chunking — including one-row chunks that split every L2 triple
    across three chunks — accumulates bitwise-identical discovery state."""
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_cases=18, n_acts=5, max_len=8)
    frame, tables = sorted_frame(log)
    a = len(tables[ACTIVITY])
    ref = discovery.discovery_state(frame, a)
    cuts = sorted(int(c) for c in rng.integers(1, max(frame.nrows, 2),
                                               size=n_chunks))
    src = ChunkedEventFrame.from_cuts(frame, cuts)
    got = discovery.streaming_discovery_state(src, a)
    for name in ("counts", "starts", "ends"):
        np.testing.assert_array_equal(np.asarray(getattr(got.dfg, name)),
                                      np.asarray(getattr(ref.dfg, name)),
                                      err_msg=f"seed={seed}:{name}")
    np.testing.assert_array_equal(np.asarray(got.l2_counts),
                                  np.asarray(ref.l2_counts),
                                  err_msg=f"seed={seed}:l2")
    # finalized models are identical too (pure functions of the state)
    ref_m = discovery.alpha(frame, a)
    got_m = discovery.streaming_alpha(ChunkedEventFrame.from_cuts(frame, cuts), a)
    assert got_m.places == ref_m.places
    assert got_m.start_activities == ref_m.start_activities
    assert got_m.end_activities == ref_m.end_activities
    ref_n = discovery.heuristics(frame, a)
    got_n = discovery.streaming_heuristics(
        ChunkedEventFrame.from_cuts(frame, cuts), a)
    np.testing.assert_array_equal(np.asarray(got_n.dependency),
                                  np.asarray(ref_n.dependency))
    np.testing.assert_array_equal(np.asarray(got_n.graph),
                                  np.asarray(ref_n.graph))


def test_single_row_chunks():
    """The adversarial chunking: every chunk is one row, every DF pair and
    every L2 triple straddles chunk boundaries."""
    log = _log_from_traces([list("abcbcbd"), list("aeeed"), list("ad")])
    frame, tables = sorted_frame(log)
    a = len(tables[ACTIVITY])
    ref = discovery.discovery_state(frame, a)
    got = discovery.streaming_discovery_state(
        ChunkedEventFrame.from_frame(frame, 1), a)
    np.testing.assert_array_equal(np.asarray(got.l2_counts),
                                  np.asarray(ref.l2_counts))
    np.testing.assert_array_equal(np.asarray(got.dfg.counts),
                                  np.asarray(ref.dfg.counts))


@pytest.mark.parametrize("backend", BACKENDS)
def test_streaming_from_edf(tmp_path, backend):
    """disk -> device: discovery over EDF row groups == whole-log, and the
    same state finalizes to the same models under either backend."""
    from repro.storage import edf

    rng = np.random.default_rng(23)
    log = random_log(rng, n_cases=40, n_acts=6, max_len=9)
    frame, tables = sorted_frame(log)
    a = len(tables[ACTIVITY])
    p = str(tmp_path / "disc.edf")
    edf.write(p, frame, tables, row_group_rows=37)
    with use_backend(backend):
        ref = discovery.discovery_state(frame, a)
        got = discovery.streaming_discovery_state(
            ChunkedEventFrame.from_edf(p), a)
    np.testing.assert_array_equal(np.asarray(got.dfg.counts),
                                  np.asarray(ref.dfg.counts))
    np.testing.assert_array_equal(np.asarray(got.l2_counts),
                                  np.asarray(ref.l2_counts))


def test_footprint_classes_partition():
    """causal/reverse-causal/parallel/choice partition the (A, A) cells."""
    rng = np.random.default_rng(3)
    log = random_log(rng, n_cases=20, n_acts=6, max_len=8)
    frame, tables = sorted_frame(log)
    a = len(tables[ACTIVITY])
    fp = discovery.footprint(discovery.discovery_state(frame, a).dfg)
    causal = np.asarray(fp.causal)
    parallel = np.asarray(fp.parallel)
    choice = np.asarray(fp.choice)
    total = (causal.astype(int) + causal.T.astype(int)
             + parallel.astype(int) + choice.astype(int))
    np.testing.assert_array_equal(total, np.ones((a, a), int))


# ------------------------------------------------ alpha place search
def _closure_oracle(causal: np.ndarray, choice: np.ndarray):
    """The place search as it was written over frozensets and ``np.ix_``
    grids, kept verbatim as the oracle of the bitmask search."""
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


def _brute_force_places(causal: np.ndarray, choice: np.ndarray) -> set:
    """Every subset pair, as ``alpha_reference`` enumerates them."""
    n = causal.shape[0]
    subsets = [frozenset(i for i in range(n) if m >> i & 1)
               for m in range(1, 1 << n)]
    cliques = [s for s in subsets if all(choice[x, y] for x in s for y in s)]
    valid = [(aa, bb) for aa in cliques for bb in cliques
             if all(causal[a, b] for a in aa for b in bb)]
    return {p for p in valid
            if not any(q != p and p[0] <= q[0] and p[1] <= q[1]
                       for q in valid)}


def _relations(direct: np.ndarray):
    """(causal, choice) of a boolean directly-follows matrix."""
    return direct & ~direct.T, ~direct & ~direct.T


def _random_direct(n: int, density: float, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).random((n, n)) < density


def _block_direct() -> np.ndarray:
    """Activities 0-3 each directly followed by each of 4-7, and nothing
    else: 15 x 15 = 225 valid pairs, one place."""
    d = np.zeros((8, 8), bool)
    d[:4, 4:] = True
    return d


def _l1_direct() -> np.ndarray:
    """L1's process: the support of the program generator's transition
    matrix for 26 activities, model seed 1 (every edge is observed in
    L1's 7x10^6 events)."""
    from repro.data.synthetic import random_process_model

    _, trans, _ = random_process_model(26, 1)
    return trans > 0


FOOTPRINTS = {
    **{f"random_{n}_{density}": (lambda n=n, density=density:
                                 _random_direct(n, density, 100 + n))
       for n in (4, 8, 12) for density in (0.15, 0.35)},
    "block_4x4": _block_direct,
    "l1": _l1_direct,
    "random_70_sparse": lambda: _random_direct(70, 0.02, 70),
    "no_free_activity": lambda: np.ones((5, 5), bool),
}


@pytest.mark.parametrize("name", sorted(FOOTPRINTS))
def test_maximal_pairs_match_the_closure(name):
    """The bitmask search returns the frozenset closure's tuple exactly,
    and brute force's places wherever brute force runs."""
    causal, choice = _relations(FOOTPRINTS[name]())
    got = discovery._maximal_pairs(causal, choice)
    assert got == _closure_oracle(causal, choice)
    assert all(type(i) is int for a, b in got for i in a | b)
    if causal.shape[0] <= 8:
        assert set(got) == _brute_force_places(causal, choice)
    if name == "block_4x4":
        assert got == ((frozenset(range(4)), frozenset(range(4, 8))),)
    if name == "l1":
        assert int(causal.sum()) == 144 and len(got) == 55
    if name == "no_free_activity":
        assert got == ()
