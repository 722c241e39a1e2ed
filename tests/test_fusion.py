"""Fused multi-verb collection + double-buffered pruned scans.

The PR's acceptance bar: ``ds.collect_many([v1, v2, ...])`` is bitwise
equal, verb for verb, to the separate ``ds.collect(v)`` calls — under the
eager, streaming, and sharded engines, over multi-file plans, at any row
group size, with the prefetcher on or off.  Plus the satellites: the
``compose()`` column-union regression (a fused kernel must not starve a
member of a projected column), ``ReaderPool`` safety under the prefetch
thread, and pruning exactness with a variants member (header sketches
replay skipped runs, so the fused scan skips groups whatever the mix).
"""
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import ACTIVITY, CASE, TIMESTAMP, backend, engine
from repro.core.stats import sojourn_times_kernel
from repro.core.performance import performance_dfg_kernel
from repro.data import synthetic
from repro.query import col, cases_containing
from repro.query.exec import prefetch_depth, pruned_source
from repro.storage import edf
from repro.storage.edf import EDFReader, pooled_reader

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
A = 6
NC = 150

VERBS = ("dfg", "stats", "variants", "alpha", "heuristics")


def _split_paths(frame, tables, tmpdir, case_cuts, row_group_rows=97):
    case = np.asarray(frame[CASE])
    bounds = [0] + [int(np.searchsorted(case, c)) for c in case_cuts] \
        + [frame.nrows]
    paths = []
    for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        p = str(tmpdir / f"part{i}.edf")
        edf.write(p, frame.take(jnp.arange(lo, hi)), tables, version=3,
                  row_group_rows=row_group_rows)
        paths.append(p)
    return paths


@pytest.fixture(scope="module")
def logset(tmp_path_factory):
    frame, tables = synthetic.generate(num_cases=NC, num_activities=A, seed=5)
    d = tmp_path_factory.mktemp("fusion")
    paths = _split_paths(frame, tables, d, case_cuts=[50, 100])
    return paths, frame, tables


def _assert_tree_equal(a, b, msg=""):
    import dataclasses

    if isinstance(a, (jax.Array, np.ndarray)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=msg)
    elif dataclasses.is_dataclass(a) and not isinstance(a, type):
        assert type(a) is type(b), msg
        for f in dataclasses.fields(a):
            _assert_tree_equal(getattr(a, f.name), getattr(b, f.name),
                               f"{msg}.{f.name}")
    elif isinstance(a, dict):
        assert set(a) == set(b), msg
        for k in a:
            _assert_tree_equal(a[k], b[k], f"{msg}[{k}]")
    elif isinstance(a, (tuple, list)):
        assert len(a) == len(b), msg
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_tree_equal(x, y, f"{msg}[{i}]")
    else:
        assert a == b, f"{msg}: {a!r} != {b!r}"


# --------------------------------------------------- S1: compose() columns
def test_compose_unions_member_columns():
    """Regression: compose() used to drop per-kernel ``columns``, so a
    projected scan could starve a fused member of a column it reads."""
    soj = sojourn_times_kernel(A)
    perf = performance_dfg_kernel(A)
    assert TIMESTAMP in soj.columns and TIMESTAMP in perf.columns
    fused = engine.compose({"sojourn_times": soj, "performance_dfg": perf})
    assert set(fused.columns) == set(soj.columns) | set(perf.columns)
    # any member with unknown requirements poisons the union (read all)
    blind = engine.ChunkKernel("blind", soj.init, soj.update, soj.merge,
                               soj.finalize, columns=())
    assert engine.compose({"a": soj, "b": blind}).columns == ()


def test_fused_projection_carries_member_columns(logset):
    """The end-to-end form of the regression: a fused stats+performance
    collection over a *timestamp-projected* dataset must read the
    timestamp extent (projection = the fused union), bitwise equal to the
    separate runs."""
    paths, frame, _ = logset
    ds = repro.open(paths)
    res = ds.collect_many(["stats", "performance_dfg"], engine="streaming")
    assert TIMESTAMP in res.report.columns
    for verb in ("stats", "performance_dfg"):
        sep = ds.collect(verb, engine="streaming")
        _assert_tree_equal(res[verb], sep.result, verb)
    # an explicit projection narrower than the union is rejected, not
    # silently starved
    with pytest.raises(ValueError):
        ds.project([CASE, ACTIVITY]).collect_many(
            ["dfg", "stats"], engine="streaming")


def test_compose_specs_fused_spec():
    """The fused KernelSpec: union columns, sharded_state intersection,
    per-verb kwargs routing."""
    specs = {v: engine.kernel_spec(v) for v in ("dfg", "alpha")}
    fused = engine.compose_specs(specs)
    assert fused.members == ("dfg", "alpha")
    assert set(fused.columns) == {CASE, ACTIVITY}
    assert fused.sharded_state == "fused"       # every member shardable
    mixed = engine.compose_specs(
        {v: engine.kernel_spec(v) for v in ("dfg", "variants")})
    assert mixed.sharded_state == "fused"       # variants shards too now
    dims = engine.Dims(A, NC)
    k = fused.make(dims, verb_kwargs={"alpha": {"min_count": 2}})
    assert k.mask_exact
    with pytest.raises(KeyError):
        fused.make(dims, verb_kwargs={"nope": {}})
    with pytest.raises(ValueError):
        engine.compose_specs({})


# ------------------------------------------- S3: collect_many == collect
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_collect_many_matches_separate_collects(logset, impl):
    """One fused scan == N separate scans, verb for verb, multi-file,
    filtered, under both segment backends and both engines."""
    paths, frame, _ = logset
    with backend.use_backend(impl):
        ds = repro.open(paths).filter(col(ACTIVITY) != 2)
        for eng in ("eager", "streaming"):
            res = ds.collect_many(VERBS, engine=eng)
            assert res.engine == eng and res.verbs == VERBS
            for verb in VERBS:
                sep = ds.collect(verb, engine=eng)
                _assert_tree_equal(res[verb], sep.result,
                                   f"{impl}/{eng}/{verb}")


def test_collect_many_chunk_invariance(tmp_path):
    """Fused results are invariant to the row-group size the files were
    written with (the carry crosses group boundaries, fused or not)."""
    frame, tables = synthetic.generate(num_cases=80, num_activities=5,
                                       seed=11)
    results = []
    for rg in (37, 97, 10_000):
        d = tmp_path / f"rg{rg}"
        d.mkdir()
        paths = _split_paths(frame, tables, d, case_cuts=[40],
                             row_group_rows=rg)
        ds = repro.open(paths).filter(col(CASE) >= 10)
        results.append(ds.collect_many(VERBS, engine="streaming").results)
    for other in results[1:]:
        _assert_tree_equal(results[0], other, "chunk invariance")


def test_collect_many_case_predicate(logset):
    """A two-pass case predicate in the fused plan: phase one runs once,
    every member sees the same keep-mask broadcast."""
    paths, _, _ = logset
    ds = repro.open(paths).filter(cases_containing(1))
    res = ds.collect_many(["dfg", "stats"], engine="streaming")
    for verb in ("dfg", "stats"):
        _assert_tree_equal(res[verb],
                           ds.collect(verb, engine="streaming").result, verb)


def test_variants_member_keeps_pruning_and_results(logset):
    """Regression for the old ``mask_exact`` degradation cliff: adding
    variants to a fused set must NOT force the composite onto the
    unpruned stream — header sketches replay the skipped runs, so the
    fused scan still skips refuted groups and every member (variants
    included) stays bitwise equal to its separate run."""
    paths, _, _ = logset
    ds = repro.open(paths).filter((col(CASE) >= 20) & (col(CASE) <= 45))
    pruned = ds.collect_many(["dfg", "stats"], engine="streaming")
    assert pruned.report.groups_skipped > 0
    fused = ds.collect_many(["dfg", "stats", "variants"],
                            engine="streaming")
    assert fused.report.groups_skipped > 0          # no degradation branch
    assert fused.report.groups_skipped == pruned.report.groups_skipped
    for verb in ("dfg", "stats"):
        _assert_tree_equal(pruned.results[verb], fused.results[verb], verb)
    _assert_tree_equal(fused.results["variants"],
                       ds.collect("variants", engine="streaming").result,
                       "variants")
    _assert_tree_equal(fused.results["variants"],
                       ds.collect("variants", engine="eager").result,
                       "variants vs eager")


def test_collect_many_sharded_1_to_8(logset):
    """Fused sharded collection (one gathered stream, dfg + discovery
    states deduped, one shard_map) == eager, at 1..8 virtual devices."""
    paths, _, _ = logset
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import repro
from repro.query import col
from repro.core.eventframe import CASE

paths = {paths!r}
ds = repro.open(paths).filter((col(CASE) >= 30) & (col(CASE) <= 120))
VERBS = ("dfg", "alpha", "heuristics", "variants")
ref = {{v: ds.collect(v, engine="eager").result for v in VERBS}}
for shards in (1, 2, 4, 8):
    res = ds.collect_many(VERBS, engine="sharded", num_shards=shards)
    assert res.engine == "sharded"
    d, rd = res["dfg"], ref["dfg"]
    for nm in ("counts", "starts", "ends"):
        assert (np.asarray(getattr(d, nm))
                == np.asarray(getattr(rd, nm))).all(), (shards, nm)
    assert res["alpha"].places == ref["alpha"].places
    assert res["alpha"].start_activities == ref["alpha"].start_activities
    assert (np.asarray(res["heuristics"].graph)
            == np.asarray(ref["heuristics"].graph)).all(), shards
    fp1, fp2, nc = res["variants"]
    rf1, rf2, rnc = ref["variants"]
    assert (np.asarray(fp1) == np.asarray(rf1)).all(), shards
    assert (np.asarray(fp2) == np.asarray(rf2)).all(), shards
    assert int(nc) == int(rnc), shards
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().endswith("OK")


def test_explain_and_profile(logset):
    paths, _, _ = logset
    ds = repro.open(paths)
    text = ds.explain(verbs=["dfg", "stats", "variants"])
    assert "fused [dfg, stats, variants]" in text
    assert "pruned" in text and "prefetch" in text and "cost eager~" in text
    assert "unpruned" not in text       # variants no longer degrades
    prof = ds.profile(engine="eager")
    assert set(prof.verbs) >= {"dfg", "stats", "variants", "alpha",
                               "heuristics", "performance_dfg"}
    _assert_tree_equal(prof["dfg"], ds.collect("dfg", engine="eager").result,
                       "profile dfg")
    with pytest.raises(ValueError):
        ds.collect_many(["dfg", "dfg"])


# ----------------------------------- one fused program per chunk dispatch
@pytest.fixture(scope="module", params=[37, 97, 10_000])
def rg_logset(request, tmp_path_factory):
    frame, tables = synthetic.generate(num_cases=40, num_activities=A,
                                       seed=31)
    d = tmp_path_factory.mktemp(f"fused_rg{request.param}")
    return _split_paths(frame, tables, d, case_cuts=[20],
                        row_group_rows=request.param)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_program_matches_per_member_loop(rg_logset, impl, monkeypatch):
    """The single fused program runs the same member updates in the same
    order as the per-member loop: a streaming profile and a collect_many
    with the nested ``stats`` compose give the same bits either way."""
    from repro.dataset.engines import clear_result_cache
    from repro.query.statecache import state_cache

    verbs = ("dfg", "stats", "variants", "performance_dfg")

    def run():
        clear_result_cache()
        state_cache().clear()
        ds = repro.open(rg_logset)
        prof = ds.profile(engine="streaming")
        many = ds.collect_many(verbs, engine="streaming")
        return prof, many

    with backend.use_backend(impl):
        fused_prof, fused_many = run()
        with monkeypatch.context() as m:
            # every member update counts as plain Python: the loop path
            m.setattr(engine, "_program", lambda update: None)
            loop_prof, loop_many = run()
    assert "fold.update.fused" in fused_prof.report.timings
    assert "fold.update.fused" not in loop_prof.report.timings
    assert loop_prof.report.timings["fold.update.dfg"][0] \
        == loop_prof.report.groups_read
    _assert_tree_equal(fused_prof.results, loop_prof.results, "profile")
    _assert_tree_equal(fused_many.results, loop_many.results, "collect_many")


def test_fused_update_is_shared_across_makes():
    """compose_specs(...).make() builds a new compose on every collect; the
    jitted program it dispatches must be the same one, or every collect
    would trace and compile it again.  Nested composes (``stats``) are
    stable members too."""
    dims = engine.Dims(A, NC)
    fused = engine.compose_specs(
        {v: engine.kernel_spec(v) for v in ("dfg", "stats", "variants")})
    k1, k2 = fused.make(dims), fused.make(dims)
    assert k1.update is k2.update
    assert isinstance(k1.update.program, jax.stages.Wrapped)
    s1 = engine.kernel_spec("stats").make(dims)
    s2 = engine.kernel_spec("stats").make(dims)
    assert s1.update is s2.update
    # a one-member compose dispatches the member's own update
    solo = engine.compose_specs({"dfg": engine.kernel_spec("dfg")}).make(dims)
    assert not hasattr(solo.update, "program")


def test_fused_update_donates_state_and_carry(logset):
    """The fused program reuses the buffers of the state and carry it is
    given (a TPU host pays for every fresh output buffer); the chunk is
    left alone."""
    from repro.core.chunked import ChunkedEventFrame
    from repro.core.dfg import dfg_kernel

    _, frame, _ = logset
    fused = engine.compose({"dfg": dfg_kernel(A),
                            "sojourn_times": sojourn_times_kernel(A)})
    chunk = next(iter(ChunkedEventFrame.from_frame(frame, 61)))
    state, carry = fused.init()
    out = fused.update(state, carry, chunk)
    jax.block_until_ready(out)
    assert all(x.is_deleted() for x in jax.tree.leaves((state, carry)))
    assert not any(x.is_deleted() for x in jax.tree.leaves(chunk))


def test_second_profile_compiles_nothing(logset):
    """With the result memo and the state cache cleared, a second profile
    reruns every fold and finalize, and finds every program compiled."""
    from repro.dataset.engines import clear_result_cache
    from repro.query.statecache import state_cache

    paths, _, _ = logset
    reports = []
    for _ in range(2):
        clear_result_cache()
        state_cache().clear()
        reports.append(repro.open(paths).profile(engine="streaming").report)
    assert reports[1].timings["fold.update.fused"][0] \
        == reports[1].groups_read > 0
    assert reports[1].compiles == {}


def test_compose_with_a_plain_python_member_loops(logset):
    """A member whose update is not jitted cannot join a fused program:
    the composed kernel calls each member in turn, with its own span, and
    gives the member's own results."""
    from repro import obs
    from repro.core.chunked import ChunkedEventFrame
    from repro.core.dfg import dfg_kernel

    _, frame, _ = logset
    dk = dfg_kernel(A)
    soj = sojourn_times_kernel(A)
    plain = engine.ChunkKernel(
        "plain_dfg", dk.init, lambda s, c, chunk: dk.update(s, c, chunk),
        dk.merge, dk.finalize, columns=dk.columns)
    fused = engine.compose({"dfg": plain, "sojourn_times": soj})
    assert not hasattr(fused.update, "program")
    chunks = list(ChunkedEventFrame.from_frame(frame, 61))
    with obs.record() as rec:
        got = engine.run_streaming(fused, chunks)
    assert rec.timings["fold.update.dfg"][0] == len(chunks)
    assert "fold.update.fused" not in rec.timings
    _assert_tree_equal(got["dfg"], engine.run_streaming(dk, chunks), "dfg")
    _assert_tree_equal(got["sojourn_times"],
                       engine.run_streaming(soj, chunks), "sojourn")


# -------------------------------- S2: prefetcher + ReaderPool under threads
def test_prefetch_on_off_bitwise_identical(logset):
    """The double buffer changes wall clock, never bytes or results: the
    chunk streams at depth 0, 1 and 3 are element-for-element identical
    (columns, validity, masks), and so are fused results."""
    paths, _, _ = logset
    ds = repro.open(paths).filter(col(CASE) <= 90)
    plan = ds.plan(columns=(CASE, ACTIVITY, TIMESTAMP))
    streams, reports = [], []
    for depth in (0, 1, 3):
        src, rep = pruned_source(plan, prefetch=depth)
        streams.append([c for c in src])
        reports.append(rep)
        assert rep.prefetch == depth
    assert reports[0].bytes_read == reports[1].bytes_read \
        == reports[2].bytes_read
    for other in streams[1:]:
        assert len(streams[0]) == len(other)
        for a, b in zip(streams[0], other):
            assert set(a.columns) == set(b.columns)
            for k in a.columns:
                np.testing.assert_array_equal(np.asarray(a[k]),
                                              np.asarray(b[k]))
            np.testing.assert_array_equal(np.asarray(a.rows_valid()),
                                          np.asarray(b.rows_valid()))
    _assert_tree_equal(
        ds.collect_many(("dfg", "stats"), engine="streaming",
                        prefetch=0).results,
        ds.collect_many(("dfg", "stats"), engine="streaming",
                        prefetch=3).results, "prefetch parity")


def test_prefetch_env_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_QUERY_PREFETCH", raising=False)
    assert prefetch_depth() == 1          # default: one group ahead
    assert prefetch_depth(0) == 0 and prefetch_depth(4) == 4
    monkeypatch.setenv("REPRO_QUERY_PREFETCH", "2")
    assert prefetch_depth() == 2
    monkeypatch.setenv("REPRO_QUERY_PREFETCH", "0")
    assert prefetch_depth() == 0
    assert prefetch_depth(-3) == 0        # clamped, never negative


def test_prefetch_survives_midstream_reader_close(logset):
    """Closing the pooled reader while the prefetch thread is mid-file
    exercises the auto-reopen path under contention; results unchanged."""
    paths, _, _ = logset
    ds = repro.open(paths)
    ref = ds.collect_many(("dfg", "stats"), engine="streaming",
                          prefetch=0).results
    src, _ = pruned_source(ds.plan(columns=(CASE, ACTIVITY, TIMESTAMP)),
                           prefetch=2)
    chunks = []
    for i, chunk in enumerate(src):
        if i == 1:
            for p in paths:
                pooled_reader(p).close()    # yanked mid-iteration
        chunks.append(chunk)
    got = engine.run_streaming(
        engine.compose_specs(
            {v: engine.kernel_spec(v) for v in ("dfg", "stats")}
        ).make(engine.Dims(ds.num_activities, ds.num_cases)), chunks)
    _assert_tree_equal(got, ref, "close mid-stream")


def test_reader_pool_threaded_stress(tmp_path):
    """S2: one pooled reader hammered by concurrent readers + closers must
    never double-open, read through a closed handle, or interleave
    seek/read pairs — every thread sees bitwise-correct groups."""
    frame, tables = synthetic.generate(num_cases=60, num_activities=5,
                                       seed=23)
    p = str(tmp_path / "stress.edf")
    edf.write(p, frame, tables, version=3, row_group_rows=53)
    ref_reader = EDFReader(p)
    expected = [{k: np.asarray(v) for k, v in
                 ref_reader.read_group(g).columns.items()}
                for g in range(ref_reader.num_groups)]
    ref_reader.close()

    errors: list = []
    stop = threading.Event()

    def hammer():
        try:
            r = pooled_reader(p)
            for _ in range(30):
                for g in range(r.num_groups):
                    frame_g = r.read_group(g)
                    for k, v in frame_g.columns.items():
                        if not np.array_equal(np.asarray(v), expected[g][k]):
                            raise AssertionError(f"group {g} col {k} corrupt")
        except BaseException as exc:  # noqa: BLE001
            errors.append(exc)

    def closer():
        while not stop.is_set():
            pooled_reader(p).close()

    threads = [threading.Thread(target=hammer) for _ in range(6)]
    chaos = threading.Thread(target=closer, daemon=True)
    for t in threads:
        t.start()
    chaos.start()
    for t in threads:
        t.join(timeout=120)
    stop.set()
    chaos.join(timeout=10)
    assert not errors, errors[0]


def test_group_meta_synthesis_thread_safe(tmp_path):
    """v2 files synthesize zone metadata lazily; two threads racing on
    ``group_meta`` must agree (one synthesis per group, no torn dicts)."""
    frame, tables = synthetic.generate(num_cases=40, num_activities=5,
                                       seed=29)
    p = str(tmp_path / "v2.edf")
    edf.write(p, frame, tables, version=2, row_group_rows=41)
    reader = EDFReader(p)
    out: list = [None, None]

    def grab(slot):
        out[slot] = [reader.group_meta(g) for g in range(reader.num_groups)]

    ts = [threading.Thread(target=grab, args=(i,)) for i in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert out[0] is not None and out[1] is not None
    for m0, m1 in zip(out[0], out[1]):
        assert m0 is m1                   # same cached dict, not a re-synth
    reader.close()
