"""The sharded engine as a merge tree over chips: each chip folds its own
contiguous span of row groups, and the span states merge on one chip.

Checked in one 8-device subprocess (the XLA device-count flag must never
leak into this test process), whose findings each test below reads:
``collect`` / ``collect_many`` with ``engine="sharded"`` equal
``engine="streaming"`` bitwise and match the ``core.classic_log`` oracles
at 1-4 spans, two row-group sizes, unfiltered, with zone-map-refuted
groups between kept ones (ghosts at span edges) and with a case-level
predicate; each span's state is committed to its own device; the halo
gather is never called; merging the spans without their stitch is wrong.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")

VERBS = ("dfg", "variants", "alpha", "discovery", "case_sizes")
FILTERS = ("none", "ends_kept", "case_level")
GROUP_ROWS = (9, 37)
SPANS = (1, 2, 3, 4)

_CHILD = r"""
import os, sys, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax
import repro
from repro.core import ACTIVITY, CASE, engine
from repro.core.classic_log import ClassicEventLog, alpha_reference
from repro.dataset import engines
from repro.distributed import query as dq
from repro.query import cases_containing, col
from repro.storage import edf

sys.path.insert(0, TESTS)
from helpers import random_log, sorted_frame


def no_gather(*a, **k):
    raise AssertionError("the sharded engine called the halo gather")


dq._gather = no_gather
placed = []
_to_home = dq._to_home


def spy(gs, device):
    leaves = jax.tree.leaves((gs.state, gs.carry))
    placed.append(sorted({d.id for x in leaves for d in x.devices()})
                  + [all(x.committed for x in leaves)])
    return _to_home(gs, device)


dq._to_home = spy
devs = jax.devices()
out = {"devices": len(devs)}
log = random_log(np.random.default_rng(11), n_cases=60, n_acts=5, max_len=9)
frame, tables = sorted_frame(log)
acts = tables[ACTIVITY]
tmp = tempfile.mkdtemp()


def same(a, b):
    if isinstance(a, (jax.Array, np.ndarray)):
        return np.array_equal(np.asarray(a), np.asarray(b))
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            same(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, dict):
        return set(a) == set(b) and all(same(a[k], b[k]) for k in a)
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    return a == b


def kept_log(name):
    # the events each filter keeps, and every case of the file in order
    events = log.events
    if name == "ends_kept":
        events = [e for e in events if e[CASE] <= 12 or e[CASE] >= 47]
    elif name == "case_level":
        events = [e for e in events if e[CASE] >= 20]
        hit = {e[CASE] for e in events if e[ACTIVITY] == "C"}
        events = [e for e in events if e[CASE] in hit]
    return ClassicEventLog(events)


def filtered(ds, name):
    if name == "ends_kept":
        return ds.filter((col(CASE) <= 12) | (col(CASE) >= 47))
    if name == "case_level":
        return ds.filter(col(CASE) >= 20).filter(cases_containing("C"))
    return ds


def matrix(counts):
    m = np.zeros((len(acts), len(acts)), np.int64)
    for (x, y), v in counts.items():
        m[acts.index(x), acts.index(y)] = v
    return m


def vector(counts):
    v = np.zeros(len(acts), np.int64)
    for x, n in counts.items():
        v[acts.index(x)] = n
    return v


def labels(ids):
    return frozenset(acts[i] for i in ids)


def classic_ok(verb, got, kl):
    cases = sorted(log.case_ids)
    if verb in ("dfg", "discovery"):
        d = got if verb == "dfg" else got.dfg
        starts, ends = kl.start_end_activities()
        ok = (np.array_equal(np.asarray(d.counts), matrix(kl.dfg_iterative()))
              and np.array_equal(np.asarray(d.starts), vector(starts))
              and np.array_equal(np.asarray(d.ends), vector(ends)))
        if verb == "discovery":
            ok = ok and np.array_equal(np.asarray(got.l2_counts),
                                       matrix(kl.dfg_l2_iterative()))
        return ok
    if verb == "alpha":
        places, starts, ends = alpha_reference(kl)
        return ({(labels(a), labels(b)) for a, b in got.places} == places
                and labels(got.start_activities) == starts
                and labels(got.end_activities) == ends)
    if verb == "case_sizes":
        sizes = {c: 0 for c in cases}
        for e in kl.events:
            sizes[e[CASE]] += 1
        want = np.array([sizes[c] for c in cases])
        return np.array_equal(np.asarray(got)[:len(cases)], want)
    # variants hash whole cases, whatever the filter keeps: two cases share
    # a fingerprint exactly when they share a trace
    fp1, fp2, nc = (np.asarray(x) for x in got)
    traces = {c: tuple(e[ACTIVITY] for e in log.events if e[CASE] == c)
              for c in cases}
    by_fp, by_trace = {}, {}
    for i, c in enumerate(cases):
        by_fp.setdefault((int(fp1[i]), int(fp2[i])), set()).add(c)
        by_trace.setdefault(traces[c], set()).add(c)
    return (int(nc) == len(cases)
            and sorted(map(sorted, by_fp.values()))
            == sorted(map(sorted, by_trace.values())))


for rows in GROUP_ROWS:
    path = os.path.join(tmp, f"log{rows}.edf")
    edf.write(path, frame, tables, version=3, row_group_rows=rows)
    for name in FILTERS:
        ds = filtered(repro.open(path), name)
        kl = kept_log(name)
        ref = {v: ds.collect(v, engine="streaming").result for v in VERBS}
        for verb in VERBS:
            key = f"{verb}-{name}-{rows}"
            bad = []
            for n in SPANS:
                placed.clear()
                r = ds.collect(verb, engine="sharded", num_shards=n)
                if r.engine != "sharded":
                    bad.append(f"{n} spans: engine {r.engine}")
                if not same(r.result, ref[verb]):
                    bad.append(f"{n} spans: differs from streaming")
                if not classic_ok(verb, r.result, kl):
                    bad.append(f"{n} spans: differs from the classic oracle")
                want = [[i, True] for i in range(r.report.chips_used)]
                if placed != want:
                    bad.append(f"{n} spans: states on {placed}, want {want}")
            out[key] = bad
        many = ds.collect_many(VERBS, engine="sharded", num_shards=3)
        out[f"many-{name}-{rows}"] = [
            v for v in VERBS if not same(many[v], ref[v])]
        engines.clear_result_cache()

# the fault: span states merged without their boundary stitch
path = os.path.join(tmp, f"log{GROUP_ROWS[0]}.edf")
kernel = engine.kernel_spec("dfg").make(engine.Dims(len(acts), 60))
blind = dataclasses.replace(
    kernel, stitch=lambda ctx: (kernel.merge(ctx.a.state, ctx.b.state), {}))
plan = repro.open(path).plan(columns=(ACTIVITY, CASE))
good, _ = dq.merge_tree_sharded(plan, kernel, 4)
wrong, _ = dq.merge_tree_sharded(plan, blind, 4)
kl_full = kept_log("none")
starts, ends = kl_full.start_end_activities()
out["fault"] = {
    "good": bool(np.array_equal(np.asarray(good.counts),
                                matrix(kl_full.dfg_iterative()))
                 and np.array_equal(np.asarray(good.ends), vector(ends))),
    "blind": bool(np.array_equal(np.asarray(wrong.counts),
                                 matrix(kl_full.dfg_iterative()))
                  and np.array_equal(np.asarray(wrong.ends), vector(ends)))}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def findings():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    code = (f"TESTS = {HERE!r}\nGROUP_ROWS = {GROUP_ROWS!r}\n"
            f"FILTERS = {FILTERS!r}\nVERBS = {VERBS!r}\nSPANS = {SPANS!r}\n"
            + _CHILD)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("rows", GROUP_ROWS)
@pytest.mark.parametrize("name", FILTERS)
@pytest.mark.parametrize("verb", VERBS)
def test_sharded_collect_is_bitwise_and_placed(findings, verb, name, rows):
    """At 1-4 spans: equal to streaming bitwise and to the classic oracle,
    each span's state committed to its own device, no halo gather."""
    assert findings["devices"] == 8
    assert findings[f"{verb}-{name}-{rows}"] == []


@pytest.mark.parametrize("rows", GROUP_ROWS)
@pytest.mark.parametrize("name", FILTERS)
def test_sharded_collect_many_is_bitwise(findings, name, rows):
    assert findings[f"many-{name}-{rows}"] == []


def test_merging_spans_without_their_stitch_is_wrong(findings):
    """The stitch is what makes the span merge exact: merged by the plain
    elementwise ``merge``, the boundary pairs and case ends are lost."""
    assert findings["fault"] == {"good": True, "blind": False}


def test_normal_path_never_gathers(monkeypatch, tmp_path):
    """In this one-device process too: spans share the device, and the
    halo gather is never called."""
    import numpy as np

    from helpers import random_log, sorted_frame

    import repro
    from repro.distributed import query as dq
    from repro.storage import edf

    def no_gather(*a, **k):
        raise AssertionError("the sharded engine called the halo gather")

    monkeypatch.setattr(dq, "_gather", no_gather)
    frame, tables = sorted_frame(random_log(np.random.default_rng(3),
                                            n_cases=30, n_acts=4))
    path = str(tmp_path / "log.edf")
    edf.write(path, frame, tables, version=3, row_group_rows=20)
    ds = repro.open(path)
    want = ds.collect("dfg", engine="streaming").result
    for n in (1, 3):
        got = ds.collect("dfg", engine="sharded", num_shards=n)
        assert got.report.chips_used == 1
        for f in ("counts", "starts", "ends"):
            np.testing.assert_array_equal(np.asarray(getattr(got.result, f)),
                                          np.asarray(getattr(want, f)))
