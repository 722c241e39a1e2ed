"""Graph analytics subsystem + model export.

The load-bearing invariants:

* the semiring primitive is bitwise identical across its Pallas and XLA
  lowerings (all three semirings, ragged shapes), and the closures equal
  a host NumPy Floyd–Warshall / BFS exactly;
* every graph verb is engine-invariant — eager == streaming == sharded
  (subprocess, 8 virtual devices) == windowed, bitwise, because the
  heavy state is the one mergeable DFG fold;
* ``merge_tree`` over case-aligned span permutations and arbitrary tree
  shapes reproduces the same DFG adjacency bitwise;
* exports round-trip: PNML places parse back exactly, dfg.json is a
  bitwise DFG round-trip, and an XES re-import re-mines to bitwise
  identical DFG state.
"""
import dataclasses
import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from helpers import random_log, sorted_frame
from repro.core import ACTIVITY, CASE, backend, engine, ops
from repro.core.dfg import DFG, dfg_kernel
from repro.core.discovery import discover_alpha, discover_heuristics
from repro.data import synthetic
from repro.graph import (BottleneckPaths, ProcessGraph, alpha_to_pnml,
                         bottleneck_paths, compile_graph, dfg_from_json,
                         dfg_to_json, discover_process_tree, frame_from_xes,
                         graph_to_dot, heuristics_to_dot, pnml_places,
                         reachability, read_pnml)
from repro.kernels.graph_ops import (SEMIRINGS, bool_closure, maxmin_closure,
                                     minplus_closure, semiring_matmul_pallas,
                                     semiring_matmul_ref)
from repro.storage import edf

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
A = 6
NC = 120
GRAPH_VERBS = ("graph", "reachability", "bottleneck_paths", "node_centrality")


@pytest.fixture(scope="module")
def logset(tmp_path_factory):
    """Three v3 files partitioning one sorted synthetic log."""
    frame, tables = synthetic.generate(num_cases=NC, num_activities=A, seed=5)
    d = tmp_path_factory.mktemp("graphds")
    case = np.asarray(frame[CASE])
    bounds = [0] + [int(np.searchsorted(case, c)) for c in (40, 80)] \
        + [frame.nrows]
    paths = []
    for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        p = str(d / f"part{i}.edf")
        edf.write(p, frame.take(jnp.arange(lo, hi)), tables,
                  version=3, row_group_rows=64)
        paths.append(p)
    return paths, frame, tables


def _eq(a, b) -> bool:
    """Bitwise structural equality over the query-result dataclasses."""
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            _eq(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_eq(x, y) for x, y in zip(a, b))
    if a is None or b is None:
        return a is b
    return np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- semiring primitive
def _tropical_oracle(a, b, semiring):
    if semiring == "min_plus":
        return np.min(a[:, :, None] + b[None, :, :], axis=1)
    return np.max(np.minimum(a[:, :, None], b[None, :, :]), axis=1)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("shape", [(4, 4, 4), (17, 9, 23), (130, 7, 131)])
def test_semiring_matmul_pallas_equals_ref_bitwise(semiring, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash((semiring, shape)) % 2**31)
    a = rng.integers(0, 50, (m, k)).astype(np.float32)
    b = rng.integers(0, 50, (k, n)).astype(np.float32)
    if semiring == "min_plus":        # +inf marks absent edges
        a[rng.random((m, k)) < 0.4] = np.inf
        b[rng.random((k, n)) < 0.4] = np.inf
    if semiring == "max_min":
        a[rng.random((m, k)) < 0.4] = -np.inf
        b[rng.random((k, n)) < 0.4] = -np.inf
    got_p = np.asarray(semiring_matmul_pallas(jnp.asarray(a), jnp.asarray(b),
                                              semiring, interpret=True))
    got_r = np.asarray(semiring_matmul_ref(jnp.asarray(a), jnp.asarray(b),
                                           semiring))
    assert np.array_equal(got_p, got_r), semiring
    if semiring == "plus_times":
        oracle = a @ b
    else:
        oracle = _tropical_oracle(a, b, semiring)
    assert np.array_equal(got_p, oracle.astype(np.float32))


def test_plus_times_float_gate(monkeypatch):
    """An inexact-float ``plus_times`` product sums in an order each
    lowering picks, so under the pallas backend it still takes the XLA
    reference unless the caller asserts exact operands (the boolean
    closure does) — the segment primitives' float guardrail."""
    from repro.kernels.graph_ops import ops as gops

    called = []

    def fake_pallas(a, b, semiring, **kw):
        called.append(semiring)
        return semiring_matmul_ref(a, b, semiring)

    monkeypatch.setattr(gops, "semiring_matmul_pallas", fake_pallas)
    p = jnp.full((3, 3), 1.0 / 3.0, jnp.float32)
    with backend.use_backend("pallas"):
        gops.semiring_matmul(p, p, "plus_times")
        assert called == []
        gops.semiring_matmul(p, p, "plus_times", assume_exact=True)
        gops.semiring_matmul(p, p, "min_plus")
        bool_closure(jnp.eye(3, dtype=bool), 2)
    assert called[:2] == ["plus_times", "min_plus"] and len(called) >= 3


def test_closures_match_host_oracles_under_both_backends():
    rng = np.random.default_rng(17)
    n = 11
    w = rng.integers(1, 9, (n, n)).astype(np.float32)
    w[rng.random((n, n)) < 0.6] = np.inf          # sparse edges
    adj = np.isfinite(w)

    # Floyd–Warshall oracles (min-plus and max-min)
    dist = np.where(np.eye(n, dtype=bool), 0.0, w)
    cap = np.where(adj, w, -np.inf)
    wide = np.where(np.eye(n, dtype=bool), np.inf, cap)
    for mid in range(n):
        dist = np.minimum(dist, dist[:, mid:mid + 1] + dist[mid:mid + 1, :])
        wide = np.maximum(wide, np.minimum(wide[:, mid:mid + 1],
                                           wide[mid:mid + 1, :]))
    # BFS reachability horizons
    reach_k = [np.eye(n, dtype=bool)]
    while len(reach_k) <= n:
        reach_k.append(reach_k[-1] | (reach_k[-1].astype(np.float32)
                                      @ adj.astype(np.float32) > 0))
    outs = {}
    for impl in ("pallas", "xla"):
        with backend.use_backend(impl):
            d = np.asarray(minplus_closure(jnp.asarray(
                np.where(adj, w, np.inf))))
            c = np.asarray(maxmin_closure(jnp.asarray(cap)))
            ks = {k: np.asarray(bool_closure(jnp.asarray(adj), k))
                  for k in (0, 1, 2, 3, None)}
        assert np.array_equal(d, dist.astype(np.float32)), impl
        assert np.array_equal(c, wide.astype(np.float32)), impl
        for k, got in ks.items():
            want = reach_k[-1] if k is None else reach_k[k]
            assert np.array_equal(got, want), (impl, k)
        outs[impl] = (d, c, ks)
    # bitwise across lowerings
    assert np.array_equal(outs["pallas"][0], outs["xla"][0])
    assert np.array_equal(outs["pallas"][1], outs["xla"][1])
    for k in outs["pallas"][2]:
        assert np.array_equal(outs["pallas"][2][k], outs["xla"][2][k])


# -------------------------------------------------------------- the IR
def test_compile_graph_embeds_state_exactly(logset):
    _, frame, tables = logset
    ds = repro.open(frame, tables=tables)
    d = ds.dfg()
    g = compile_graph(d)
    a = d.num_activities
    assert g.num_nodes == a + 2 and g.source == a and g.sink == a + 1
    f = np.asarray(g.freq)
    assert np.array_equal(f[:a, :a], np.asarray(d.counts))
    assert np.array_equal(f[a, :a], np.asarray(d.starts))
    assert np.array_equal(f[:a, a + 1], np.asarray(d.ends))
    assert f[a + 1].sum() == 0 and f[:, a].sum() == 0
    lab = ds.graph().node_labels()
    assert lab[-2:] == ("▶", "■")
    assert set(lab[:a]) == set(tables[ACTIVITY])
    with pytest.raises(TypeError):
        compile_graph(object())
    with pytest.raises(ValueError):
        g.with_labels(("x",))


# ------------------------------------------------------- engine parity
def test_graph_verbs_engine_parity_and_pruning(logset):
    paths, _, _ = logset
    ds = repro.open(paths).filter(
        (repro.col(CASE) >= 20) & (repro.col(CASE) <= 95))
    for verb in GRAPH_VERBS:
        ref = ds.collect(verb, engine="eager")
        got = ds.collect(verb, engine="streaming")
        assert _eq(got.result, ref.result), verb
        assert got.report.groups_skipped > 0, verb
    # the timed overlay: f32 waits accumulate in row order on both paths
    gt_e = ds.collect("graph", engine="eager", timed=True).result
    gt_s = ds.collect("graph", engine="streaming", timed=True).result
    assert _eq(gt_e, gt_s)
    assert gt_e.perf is not None and float(np.asarray(gt_e.perf).sum()) > 0
    bp = ds.collect("bottleneck_paths", engine="streaming",
                    weights="performance").result
    assert _eq(bp, ds.collect("bottleneck_paths", engine="eager",
                              weights="performance").result)
    assert bp.weights == "performance"


def test_graph_query_results_are_consistent(logset):
    paths, _, _ = logset
    ds = repro.open(paths)
    g = ds.graph()
    r_full = ds.reachability()
    # full closure reaches the sink from the source
    assert bool(np.asarray(r_full.mask)[g.source, g.sink])
    r1 = ds.reachability(1)
    assert np.array_equal(
        np.asarray(r1.mask),
        np.asarray(np.eye(g.num_nodes, dtype=bool) | np.asarray(g.adjacency)))
    bp = ds.bottlenecks()
    assert bp.path[0] == g.source and bp.path[-1] == g.sink
    f = np.asarray(g.freq)
    caps = [f[a, b] for a, b in zip(bp.path[:-1], bp.path[1:])]
    assert min(caps) == bp.bottleneck > 0
    c = ds.centrality()
    assert np.array_equal(np.asarray(c.in_degree), np.asarray(f.sum(0)))
    assert np.array_equal(np.asarray(c.out_degree), np.asarray(f.sum(1)))
    assert abs(float(np.asarray(c.flow).sum()) - 1.0) < 1e-5


def test_graph_sharded_parity_subprocess(logset):
    """sharded == eager for every graph verb at 2 and 8 shards; the timed
    overlay refuses the distributed lowering."""
    paths, _, _ = logset
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import repro
from repro.core.eventframe import CASE
from repro.query import col

def eq(a, b):
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            eq(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(eq(x, y) for x, y in zip(a, b))
    if a is None or b is None:
        return a is b
    return np.array_equal(np.asarray(a), np.asarray(b))

paths = {paths!r}
ds = repro.open(paths).filter((col(CASE) >= 15) & (col(CASE) <= 100))
for verb in {GRAPH_VERBS!r}:
    ref = ds.collect(verb, engine="eager").result
    for shards in (2, 8):
        got = ds.collect(verb, engine="sharded", num_shards=shards)
        assert got.engine == "sharded", (verb, shards)
        assert eq(got.result, ref), (verb, shards)
try:
    ds.collect("graph", engine="sharded", num_shards=2, timed=True)
    raise SystemExit("timed=True must refuse the sharded engine")
except ValueError as e:
    assert "no exact distributed lowering" in str(e)
try:
    ds.collect("bottleneck_paths", engine="sharded", num_shards=2,
               weights="performance")
    raise SystemExit("performance weights must refuse the sharded engine")
except ValueError as e:
    assert "no exact distributed lowering" in str(e)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().endswith("OK")


def test_graph_verbs_under_both_segment_backends(logset, tmp_path):
    """REPRO_SEGMENT_BACKEND={pallas,xla} subprocesses produce bitwise
    identical reachability masks and graph frequencies."""
    paths, _, _ = logset
    outs = {}
    for be in ("pallas", "xla"):
        out_npz = str(tmp_path / f"graph_{be}.npz")
        code = f"""
import numpy as np
import repro
ds = repro.open({paths!r})
g = ds.collect("graph").result
r = ds.collect("reachability", k=3).result
np.savez({out_npz!r}, freq=np.asarray(g.freq), mask=np.asarray(r.mask))
print("OK")
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        env["REPRO_SEGMENT_BACKEND"] = be
        res = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=560)
        assert res.returncode == 0, res.stderr[-3000:]
        assert res.stdout.strip().endswith("OK")
        outs[be] = dict(np.load(out_npz))
    assert np.array_equal(outs["pallas"]["freq"], outs["xla"]["freq"])
    assert np.array_equal(outs["pallas"]["mask"], outs["xla"]["mask"])


def test_windowed_graph_equals_compiled_windowed_dfg(logset):
    paths, _, _ = logset
    ds = repro.open(paths)
    w = ds.window(by="groups", size=3, step=3)
    graphs = w.collect("graph")
    dfgs = w.collect("dfg")
    assert len(graphs.results) == len(dfgs.results) > 1
    for g, d in zip(graphs.results, dfgs.results):
        assert _eq(g, compile_graph(d))


# ------------------------------------------- merge-permutation property
def test_merge_tree_span_permutations_identical_dfg(logset):
    """Case-aligned spans hold whole cases, so any span order (and any
    merge-tree shape) must reproduce the same DFG state bitwise."""
    _, frame, _ = logset
    a = int(np.asarray(frame[ACTIVITY]).max()) + 1
    kernel = dfg_kernel(a)
    case = np.asarray(frame[CASE])
    bounds = [0] + list(np.flatnonzero(case[1:] != case[:-1]) + 1) \
        + [frame.nrows]
    cuts = bounds[::7] + ([frame.nrows] if bounds[::7][-1] != frame.nrows
                          else [])
    spans = [frame.take(jnp.arange(lo, hi))
             for lo, hi in zip(cuts[:-1], cuts[1:])]
    groups = [engine.fold_group(kernel, [s]) for s in spans]
    ref = engine.finalize_group(kernel, engine.merge_tree(kernel, groups))
    # left fold == balanced tree (ordered)
    acc = groups[0]
    for g in groups[1:]:
        acc = engine.merge_group_states(kernel, acc, g)
    assert _eq(engine.finalize_group(kernel, acc), ref)
    # arbitrary permutations (spans are case-aligned: no straddle)
    rng = np.random.default_rng(23)
    for _ in range(4):
        perm = rng.permutation(len(groups))
        got = engine.finalize_group(
            kernel, engine.merge_tree(kernel, [groups[i] for i in perm]))
        assert _eq(got, ref), perm
        assert _eq(compile_graph(got), compile_graph(ref))


# ------------------------------------------------------ registry errors
def test_unknown_verb_raises_listing_and_suggesting():
    with pytest.raises(KeyError) as ei:
        engine.kernel_spec("reachabillity")
    msg = str(ei.value)
    assert "did you mean" in msg and "'reachability'" in msg
    assert "registered:" in msg and "'dfg'" in msg
    frame, tables = sorted_frame(random_log(np.random.default_rng(1),
                                            n_cases=4))
    with pytest.raises(KeyError) as ei2:
        repro.open(frame, tables=tables).collect("nosuch")
    assert "registered:" in str(ei2.value)


# ------------------------------------------------------------- exports
def _structured_log():
    return make_log([
        ("c1", ["a", "b", "d"]),
        ("c2", ["a", "c", "d"]),
        ("c3", ["a", "b", "d"]),
    ])


def make_log(cases):
    from repro.core import make_classic_log

    t = [0.0]

    def trace(acts):
        out = []
        for x in acts:
            t[0] += 1.0
            out.append((x, t[0]))
        return out

    return make_classic_log([(cid, trace(acts)) for cid, acts in cases])


def test_pnml_roundtrip_structural():
    frame, tables = sorted_frame(_structured_log())
    ds = repro.open(frame, tables=tables)
    model = ds.alpha()
    xml = alpha_to_pnml(model, labels=tables[ACTIVITY])
    places, transitions, arcs = read_pnml(xml)
    assert places["source"] == 1 and places["sink"] == 0
    assert len(places) == len(model.places) + 2
    assert sorted(transitions.values()) == sorted(tables[ACTIVITY])
    pairs, starts, ends = pnml_places(xml)
    assert pairs == model.places
    assert starts == model.start_activities
    assert ends == model.end_activities
    assert len(model.places) > 0


def test_dot_exports_are_wellformed():
    frame, tables = sorted_frame(_structured_log())
    ds = repro.open(frame, tables=tables)
    dot = heuristics_to_dot(ds.heuristics(), labels=tables[ACTIVITY])
    assert dot.startswith("digraph") and "__start ->" in dot \
        and "-> __end" in dot
    gdot = graph_to_dot(ds.graph())
    assert gdot.startswith("digraph")
    for lab in tables[ACTIVITY]:
        assert lab in gdot


def test_process_tree_notation():
    # pure sequence
    f, t = sorted_frame(make_log([("c1", ["a", "b", "c"]),
                                  ("c2", ["a", "b", "c"])]))
    assert discover_process_tree(repro.open(f, tables=t).dfg(),
                                 labels=t[ACTIVITY]) == "->( 'a', 'b', 'c' )"
    # choice inside a sequence
    f2, t2 = sorted_frame(_structured_log())
    tree = discover_process_tree(repro.open(f2, tables=t2).dfg(),
                                 labels=t2[ACTIVITY])
    assert tree.startswith("->(") and "X(" in tree
    # self-loop leaf
    f3, t3 = sorted_frame(make_log([("c1", ["a", "a", "b"])]))
    tree3 = discover_process_tree(repro.open(f3, tables=t3).dfg(),
                                  labels=t3[ACTIVITY])
    assert "*( 'a', tau )" in tree3
    # a ProcessGraph source works too and empty state is tau
    ds2 = repro.open(f2, tables=t2)
    assert discover_process_tree(ds2.graph()) == tree
    empty = DFG(jnp.zeros((3, 3), jnp.int32), jnp.zeros((3,), jnp.int32),
                jnp.zeros((3,), jnp.int32))
    assert discover_process_tree(empty) == "tau"


def test_dfg_json_roundtrip_bitwise(logset):
    _, frame, tables = logset
    ds = repro.open(frame, tables=tables)
    d = ds.dfg()
    text = dfg_to_json(d, labels=tables[ACTIVITY])
    doc = json.loads(text)
    assert set(doc) == {"activities", "dfg", "start_activities",
                        "end_activities"}
    d2, lab2 = dfg_from_json(text)
    assert lab2 == list(tables[ACTIVITY])
    for f in ("counts", "starts", "ends"):
        assert np.array_equal(np.asarray(getattr(d, f)),
                              np.asarray(getattr(d2, f))), f


def test_xes_export_reimport_remine_bitwise(tmp_path, logset):
    """write XES -> read it back -> re-mine: the DFG state (and therefore
    the compiled graph) is bitwise identical."""
    _, frame, tables = logset
    ds = repro.open(frame, tables=tables)
    p = str(tmp_path / "export.xes")
    ds.to_xes(p)
    frame2, tables2 = frame_from_xes(p)
    from repro.core import TIMESTAMP, EventFrame
    # XES carries labels, not codes: re-import dictionary-encodes in
    # first-occurrence order, so realign activity ids to the original
    # dictionary before comparing state bit for bit.
    perm = np.array([tables[ACTIVITY].index(lbl)
                     for lbl in tables2[ACTIVITY]], np.int32)
    cols = {k: np.asarray(frame2[k]) for k in frame2.names}
    cols[ACTIVITY] = perm[cols[ACTIVITY]]
    frame2 = EventFrame.from_numpy(
        cols, {k: np.asarray(v) for k, v in frame2.valid.items()})
    frame2 = ops.sort(frame2, (TIMESTAMP, CASE))
    ds2 = repro.open(frame2, tables={**tables2, ACTIVITY: tables[ACTIVITY]})
    d1, d2 = ds.dfg(), ds2.dfg()
    for f in ("counts", "starts", "ends"):
        assert np.array_equal(np.asarray(getattr(d1, f)),
                              np.asarray(getattr(d2, f))), f
    assert _eq(ds.collect("graph").result, ds2.collect("graph").result)


# ------------------------------------------------------------- service
def test_http_graph_endpoint(tmp_path):
    from repro.service import serve

    rng = np.random.default_rng(31)
    frame, tables = sorted_frame(random_log(rng, n_cases=16, n_acts=4))
    pdir = str(tmp_path / "parts")
    os.makedirs(pdir)
    edf.write(os.path.join(pdir, "part_00000.edf"), frame, tables,
              version=3, row_group_rows=16)
    httpd = serve(pdir, port=0, case_capacity=32)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return json.loads(r.read())

        out = get("/graph?query=bottleneck_paths")
        assert out["ok"]
        g = out["graph"]
        ref = repro.open(frame, tables=tables,
                         num_cases=out["snapshot"]["num_cases"]).graph()
        assert np.array_equal(np.asarray(g["freq"]), np.asarray(ref.freq))
        assert g["labels"] == list(ref.node_labels())
        assert g["source"] == ref.source and g["sink"] == ref.sink
        q = out["query"]
        assert q["_type"] == "BottleneckPaths" and q["bottleneck"] > 0
        plain = get("/graph")
        assert plain["ok"] and "query" not in plain
        with pytest.raises(urllib.error.HTTPError) as e400:
            get("/graph?query=nosuch")
        assert e400.value.code == 400
    finally:
        httpd.shutdown()
