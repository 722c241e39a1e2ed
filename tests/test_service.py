"""The live mining service: append path, ingestor, query server.

Covers the tentpole end to end — atomic ``edf.append`` (old groups
byte-identical, state cache hot), the crash-safe :class:`Ingestor`, and
the snapshot-consistent :class:`MiningService` — plus the satellite
regressions: pooled readers reopen under append (a second ``collect``
sees the new groups), result memoization survives a forced stat
collision (same size, same mtime_ns, different bytes), and the
mined-while-ingesting parity drill: every concurrently-returned result
bitwise equal to re-mining the snapshot it claims.
"""
import dataclasses
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from helpers import random_log, sorted_frame

import repro
from repro.core.eventframe import ACTIVITY, CASE, TIMESTAMP, EventFrame
from repro.dataset import engines as ds_engines
from repro.query.statecache import state_cache
from repro.service import (Ingestor, MiningService, ServiceError, serve,
                           to_jsonable)
from repro.service import ingest as ingest_mod
from repro.storage import edf

N_ACTS, N_CASES = 5, 40


def _fresh():
    state_cache().clear()
    ds_engines.clear_result_cache()


def _slice(frame, a, b):
    return EventFrame({k: v[a:b] for k, v in frame.columns.items()},
                      {k: v[a:b] for k, v in frame.valid.items()},
                      frame.rows_valid()[a:b])


def _case_cuts(frame, per):
    """Row offsets cutting ``frame`` on case boundaries every ``per``
    cases (batches stay case-aligned, like a real ingest feed)."""
    case = np.asarray(frame.columns[CASE])
    bounds = np.flatnonzero(case[1:] != case[:-1]) + 1
    cuts = [0] + [int(bounds[i]) for i in range(per - 1, len(bounds), per)]
    if cuts[-1] != frame.nrows:
        cuts.append(frame.nrows)
    return cuts


@pytest.fixture()
def log():
    rng = np.random.default_rng(11)
    return sorted_frame(random_log(rng, n_cases=N_CASES, n_acts=N_ACTS,
                                   max_len=8))


def _jeq(a, b):
    return json.dumps(to_jsonable(a)) == json.dumps(to_jsonable(b))


# ------------------------------------------------------------ append path
def test_append_roundtrip_and_signature_stability(tmp_path, log):
    frame, tables = log
    cut = _case_cuts(frame, N_CASES // 2)[1]
    p = str(tmp_path / "log.edf")
    edf.write(p, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    r0 = edf.EDFReader(p)
    sigs0 = [r0.group_signature(g) for g in range(r0.num_groups)]
    edf.append(p, _slice(frame, cut, frame.nrows), tables, row_group_rows=17)
    r1 = edf.EDFReader(p)
    assert r1.num_groups > len(sigs0)
    # old groups' content signatures survive the append untouched
    assert [r1.group_signature(g) for g in range(len(sigs0))] == sigs0
    got, got_tables = edf.read(p)
    for name in frame.names:
        assert np.array_equal(np.asarray(got.columns[name]),
                              np.asarray(frame.columns[name])), name
    assert got_tables == {k: list(v) for k, v in tables.items()}
    # the file signature moved in all three components' terms: content tag
    assert r1._sig != r0._sig and r1._sig[2] != r0._sig[2]


def test_append_atomic_when_replace_fails(tmp_path, log, monkeypatch):
    frame, tables = log
    p = str(tmp_path / "log.edf")
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(p, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    before = open(p, "rb").read()

    def boom(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(edf.os, "replace", boom)
    with pytest.raises(OSError):
        edf.append(p, _slice(frame, cut, frame.nrows), tables)
    monkeypatch.undo()
    # nothing landed, nothing torn, no temp litter
    assert open(p, "rb").read() == before
    assert [n for n in os.listdir(tmp_path) if ".tmp" in n] == []
    got, _ = edf.read(p)
    assert got.nrows == cut


def test_append_validates_schema_and_order(tmp_path, log):
    frame, tables = log
    p = str(tmp_path / "log.edf")
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(p, _slice(frame, 0, cut), tables, version=3)
    tail = _slice(frame, cut, frame.nrows)
    with pytest.raises(ValueError, match="case"):
        edf.append(p, _slice(frame, 0, cut), tables)    # reopens case 0
    with pytest.raises(ValueError, match="columns"):
        edf.append(p, tail.select([CASE, ACTIVITY]), tables)
    bad = EventFrame({**{k: np.asarray(v) for k, v in tail.columns.items()},
                      TIMESTAMP: np.asarray(tail.columns[TIMESTAMP],
                                            np.float64)}, dict(tail.valid))
    with pytest.raises(ValueError, match="dtype"):
        edf.append(p, bad, tables)
    with pytest.raises(ValueError, match="dictionary table"):
        edf.append(p, tail, {ACTIVITY: ["x", "y"]})     # not an extension
    # a v1 file refuses appends
    p1 = str(tmp_path / "v1.edf")
    edf.write(p1, _slice(frame, 0, cut), tables, version=1)
    with pytest.raises(ValueError, match="v1"):
        edf.append(p1, tail, tables)
    # zero-row appends are a no-op
    before = open(p, "rb").read()
    edf.append(p, _slice(frame, 0, 0), tables)
    assert open(p, "rb").read() == before


def test_append_keeps_state_cache_hot(tmp_path, log):
    frame, tables = log
    _fresh()
    p = str(tmp_path / "log.edf")
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(p, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    old_groups = edf.num_row_groups(p)
    ds = repro.open(p, num_cases=N_CASES)       # pinned capacity: the spec
    ds.collect("dfg", engine="streaming")       # fingerprint stays stable
    edf.append(p, _slice(frame, cut, frame.nrows), tables, row_group_rows=17)
    res = ds.collect("dfg", engine="streaming")
    # only the appended groups were decoded; the old ones merged from cache
    assert res.report.groups_cached == old_groups
    assert res.report.groups_folded == edf.num_row_groups(p) - old_groups
    scratch = repro.open(frame, tables=tables,
                         num_cases=N_CASES).collect("dfg", engine="eager")
    assert _jeq(res.result, scratch.result)


# ------------------------------------- satellite 1: staleness under append
def test_second_collect_sees_appended_groups(tmp_path, log):
    frame, tables = log
    _fresh()
    p = str(tmp_path / "log.edf")
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(p, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    ds = repro.open(p)                          # one handle, used twice
    first = ds.collect("activity_counts", engine="streaming")
    edf.append(p, _slice(frame, cut, frame.nrows), tables, row_group_rows=17)
    second = ds.collect("activity_counts", engine="streaming")
    assert second.report.groups_total > first.report.groups_total
    scratch = repro.open(frame, tables=tables).collect("activity_counts",
                                                       engine="eager")
    assert _jeq(second.result, scratch.result)
    assert not _jeq(first.result, second.result)


def test_stale_reader_fails_loudly_and_pin_holds_snapshot(tmp_path, log):
    frame, tables = log
    p = str(tmp_path / "log.edf")
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(p, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    stale = edf.EDFReader(p)
    stale.read_group(0)
    pinned = edf.EDFReader(p)
    with pinned.pin():
        edf.append(p, _slice(frame, cut, frame.nrows), tables)
        # an evicted (closed) stale reader refuses to decode the new bytes
        stale.close()
        with pytest.raises(edf.StaleFileError):
            stale.read_group(0)
        # but the pinned reader still reads its consistent old snapshot,
        # even through a deferred close (pool eviction mid-request)
        pinned.close()
        total = sum(pinned.read_group(g).nrows
                    for g in range(pinned.num_groups))
        assert total == cut
    assert pinned.closed                        # the deferred close landed
    # the pool hands out a fresh reader for the new generation
    assert edf.pooled_reader(p).nrows == frame.nrows


# -------------------------------- satellite 2: forced-stat-collision memo
def test_memo_survives_forced_stat_collision(tmp_path, log):
    frame, tables = log
    _fresh()
    acts = np.asarray(frame.columns[ACTIVITY])
    twin = EventFrame({**{k: np.asarray(v) for k, v in
                          frame.columns.items()},
                       ACTIVITY: ((acts + 1) % N_ACTS).astype(acts.dtype)},
                      dict(frame.valid))
    p = str(tmp_path / "log.edf")
    edf.write(p, frame, tables, codec="raw", version=3, row_group_rows=17)
    st = os.stat(p)
    first = repro.open(p).collect("activity_counts", engine="streaming")
    # rewrite with permuted single-digit activity ids: identical size, and
    # utime pins mtime_ns -> the stat signature alone cannot tell them apart
    edf.write(p, twin, tables, codec="raw", version=3, row_group_rows=17)
    os.utime(p, ns=(st.st_atime_ns, st.st_mtime_ns))
    assert (os.stat(p).st_size, os.stat(p).st_mtime_ns) == \
        (st.st_size, st.st_mtime_ns)
    second = repro.open(p).collect("activity_counts", engine="streaming")
    assert not _jeq(first.result, second.result)
    scratch = repro.open(twin, tables=tables).collect("activity_counts",
                                                      engine="eager")
    assert _jeq(second.result, scratch.result)


def test_header_tag_is_content_derived(tmp_path, log):
    frame, tables = log
    p, q = str(tmp_path / "a.edf"), str(tmp_path / "b.edf")
    edf.write(p, frame, tables, version=3, row_group_rows=17)
    edf.write(q, frame, tables, version=3, row_group_rows=17)
    assert edf.header_tag(p) == edf.header_tag(q)       # same content
    assert edf.file_sig(p)[2] == edf.header_tag(p)
    cut = _case_cuts(frame, N_CASES // 2)[1]
    edf.write(q, _slice(frame, 0, cut), tables, version=3, row_group_rows=17)
    assert edf.header_tag(p) != edf.header_tag(q)


# ------------------------------------------------------------ Dataset API
def test_dataset_append_api(tmp_path, log):
    frame, tables = log
    _fresh()
    cuts = _case_cuts(frame, 15)        # three case-aligned thirds
    p1, p2 = str(tmp_path / "a.edf"), str(tmp_path / "b.edf")
    edf.write(p1, _slice(frame, 0, cuts[1]), tables, version=3)
    edf.write(p2, _slice(frame, cuts[1], cuts[2]), tables, version=3)
    ds = repro.open([p1, p2])
    out = ds.append(_slice(frame, cuts[2], frame.nrows), row_group_rows=17)
    assert isinstance(out, repro.Dataset) and out.paths == ds.paths
    assert ds.num_cases == N_CASES              # live: this handle sees it
    scratch = repro.open(frame, tables=tables).collect("dfg", engine="eager")
    assert _jeq(ds.collect("dfg", engine="streaming").result, scratch.result)
    with pytest.raises(ValueError, match="last file"):
        ds.append(_slice(frame, 0, cuts[1]), path=p1)
    with pytest.raises(ValueError, match="file-backed"):
        repro.open(frame, tables=tables).append(frame)


# --------------------------------------------------------------- ingestor
def _write_batches(bdir, frame, tables, per=8, start=0, stop=None):
    cuts = _case_cuts(frame, per)
    stop = len(cuts) - 1 if stop is None else stop
    for i in range(start, stop):
        edf.write(os.path.join(bdir, f"batch_{i:04d}.edf"),
                  _slice(frame, cuts[i], cuts[i + 1]), tables, version=3)
    return stop - start


def test_ingestor_partitions_and_idempotence(tmp_path, log):
    frame, tables = log
    bdir, pdir = str(tmp_path / "in"), str(tmp_path / "out")
    os.makedirs(bdir)
    n = _write_batches(bdir, frame, tables)
    ing = Ingestor(pdir, bdir, partition_rows=frame.nrows // 3,
                   row_group_rows=16)
    assert ing.run_once() == n
    assert ing.run_once() == 0                  # skip-index: nothing redone
    assert len(ing.paths) >= 2                  # partition rollover happened
    got = [edf.read(p)[0] for p in ing.paths]
    assert sum(g.nrows for g in got) == frame.nrows
    joined = np.concatenate([np.asarray(g.columns[CASE]) for g in got])
    assert np.array_equal(joined, np.asarray(frame.columns[CASE]))
    # a new instance over the same index also redoes nothing
    assert Ingestor(pdir, bdir).run_once() == 0


def test_ingestor_crash_resume_both_windows(tmp_path, log):
    frame, tables = log
    bdir, pdir = str(tmp_path / "in"), str(tmp_path / "out")
    os.makedirs(bdir)
    cuts = _case_cuts(frame, 10)
    batches = [(f"batch_{i:04d}.edf", _slice(frame, cuts[i], cuts[i + 1]))
               for i in range(len(cuts) - 1)]
    for name, fr in batches:
        edf.write(os.path.join(bdir, name), fr, tables, version=3)
    ing = Ingestor(pdir, bdir, partition_rows=10**9, row_group_rows=16)
    ing.run_once(limit=1)
    part = os.path.basename(ing.paths[0])
    rows0 = edf.read_header(ing.paths[0])[0]["nrows"]

    # crash window A: pending recorded, apply never ran -> batch is redone
    ing._index["pending"] = {"batch": batches[1][0], "partition": part,
                             "rows": batches[1][1].nrows,
                             "nrows_before": rows0}
    ing._save_index()
    resumed = Ingestor(pdir, bdir, partition_rows=10**9, row_group_rows=16)
    assert batches[1][0] not in resumed.done_ids
    resumed.run_once(limit=1)
    rows1 = edf.read_header(resumed.paths[0])[0]["nrows"]
    assert rows1 == rows0 + batches[1][1].nrows

    # crash window B: apply landed, done never recorded -> acknowledged,
    # not re-applied (no duplicate rows)
    edf.append(resumed.paths[0], batches[2][1], tables, row_group_rows=16)
    resumed._index["pending"] = {"batch": batches[2][0], "partition": part,
                                 "rows": batches[2][1].nrows,
                                 "nrows_before": rows1}
    resumed._save_index()
    final = Ingestor(pdir, bdir, partition_rows=10**9, row_group_rows=16)
    assert batches[2][0] in final.done_ids
    final.run_once()                            # drains the remaining batches
    got, _ = edf.read(final.paths[0])
    assert got.nrows == frame.nrows
    assert np.array_equal(np.asarray(got.columns[CASE]),
                          np.asarray(frame.columns[CASE]))


def test_ingestor_retries_transient_write_failures(tmp_path, log,
                                                   monkeypatch):
    frame, tables = log
    bdir, pdir = str(tmp_path / "in"), str(tmp_path / "out")
    os.makedirs(bdir)
    _write_batches(bdir, frame, tables, per=N_CASES // 2)
    real_append, fails = edf.append, {"left": 2}

    def flaky(path, fr, tb=None, row_group_rows=None):
        if fails["left"]:
            fails["left"] -= 1
            raise OSError("transient")
        return real_append(path, fr, tb, row_group_rows)

    monkeypatch.setattr(ingest_mod.edf, "append", flaky)
    ing = Ingestor(pdir, bdir, partition_rows=10**9, row_group_rows=16,
                   max_retries=5, backoff=0.001)
    assert ing.run_once() == 2
    assert ing.retried == 2
    got, _ = edf.read(ing.paths[0])
    assert got.nrows == frame.nrows


# ---------------------------------------------------------- query service
def test_service_collect_claims_and_parity(tmp_path, log):
    frame, tables = log
    _fresh()
    pdir = str(tmp_path / "parts")
    os.makedirs(pdir)
    edf.write(os.path.join(pdir, "part_00000.edf"), frame, tables,
              version=3, row_group_rows=16)
    svc = MiningService(pdir, case_capacity=64)
    out = svc.collect("dfg", engine="streaming")
    claim = out["snapshot"]
    assert claim["rows"] == frame.nrows and claim["num_cases"] == 64
    assert claim["files"][0]["tag"] == edf.header_tag(
        os.path.join(pdir, "part_00000.edf"))
    ref = repro.open(frame, tables=tables,
                     num_cases=claim["num_cases"]).collect("dfg",
                                                           engine="eager")
    assert json.dumps(out["result"]) == json.dumps(to_jsonable(ref.result))
    with pytest.raises(ServiceError):
        svc.collect(None)
    with pytest.raises(ServiceError):
        MiningService(str(tmp_path / "empty")).collect("dfg")


def test_mined_while_ingesting_bitwise_parity(tmp_path):
    """The tentpole drill: one ingest thread appending case-aligned
    batches while client threads collect concurrently; every returned
    result must be bitwise equal (via canonical JSON) to re-mining the
    exact snapshot its claim names — which, appends being ordered and
    atomic, is a row prefix of the master log."""
    rng = np.random.default_rng(23)
    frame, tables = sorted_frame(random_log(rng, n_cases=60, n_acts=N_ACTS,
                                            max_len=7))
    _fresh()
    bdir, pdir = str(tmp_path / "in"), str(tmp_path / "out")
    os.makedirs(bdir)
    cuts = _case_cuts(frame, 6)
    ing = Ingestor(pdir, bdir, partition_rows=frame.nrows // 2,
                   row_group_rows=16, poll_interval=0.01)
    svc = MiningService(ing, case_capacity=64, max_attempts=6)

    def produce():
        for i in range(len(cuts) - 1):
            edf.write(os.path.join(bdir, f"batch_{i:04d}.edf"),
                      _slice(frame, cuts[i], cuts[i + 1]), tables, version=3)
            time.sleep(0.02)

    collected, errors = [], []

    def client():
        verbs = ("dfg", "activity_counts", "case_sizes")
        done, deadline = 0, time.monotonic() + 30
        while done < 6 and time.monotonic() < deadline:
            try:
                out = svc.collect(verbs[done % len(verbs)],
                                  engine="streaming")
                collected.append((out["verb"], out["snapshot"],
                                  json.dumps(out["result"])))
                done += 1
                time.sleep(0.01)
            except ServiceError:
                time.sleep(0.03)                # warming up: no partitions
            except Exception as e:              # pragma: no cover
                errors.append(e)
                return

    producer = threading.Thread(target=produce)
    producer.start()
    ing.start()
    time.sleep(0.05)
    clients = [threading.Thread(target=client) for _ in range(3)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    producer.join()
    # drain the tail so the final parity check covers the whole log
    while ing.run_once():
        pass
    ing.stop()
    assert not errors
    assert collected, "no client ever got a successful collect"
    seen_rows = set()
    for verb, claim, result_json in collected:
        rows = claim["rows"]
        seen_rows.add(rows)
        prefix = _slice(frame, 0, rows)
        ref = repro.open(prefix, tables=tables,
                         num_cases=claim["num_cases"]).collect(
                             verb, engine="eager")
        assert result_json == json.dumps(to_jsonable(ref.result)), \
            f"{verb} diverged at a {rows}-row snapshot"
    final = svc.collect("dfg", engine="streaming")
    assert final["snapshot"]["rows"] == frame.nrows


def test_http_endpoints(tmp_path, log):
    frame, tables = log
    _fresh()
    pdir = str(tmp_path / "parts")
    os.makedirs(pdir)
    edf.write(os.path.join(pdir, "part_00000.edf"), frame, tables,
              version=3, row_group_rows=16)
    httpd = serve(pdir, port=0, case_capacity=64)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return json.loads(r.read())

        health = get("/health")
        assert health["ok"] and health["rows"] == frame.nrows
        t0 = time.perf_counter()
        got = get("/collect?verb=dfg&engine=streaming")
        wall_us = (time.perf_counter() - t0) * 1e6
        # the handler's own time: inside the client's, and covering the scan
        assert 0 < got["elapsed_us"] <= wall_us
        scan_us = got["report"]["timings"]["scan"][1] * 1e6
        assert scan_us <= got["elapsed_us"]
        ref = repro.open(frame, tables=tables,
                         num_cases=got["snapshot"]["num_cases"]).collect(
                             "dfg", engine="eager")
        assert json.dumps(got["result"]) == json.dumps(
            to_jsonable(ref.result))
        # POST body routes kwargs (min_count reaches the alpha kernel)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/collect",
            data=json.dumps({"verb": "alpha", "min_count": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            alpha = json.loads(r.read())
        assert alpha["result"]["_type"] == "AlphaModel"
        win = get("/window?verb=dfg&by=groups&size=2&step=2")
        assert len(win["results"]) == len(win["bounds"])
        assert "state-cache" in get("/explain?verb=dfg")["explain"]
        with pytest.raises(urllib.error.HTTPError) as e404:
            get("/nope")
        assert e404.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e400:
            get("/collect")                     # missing verb
        assert e400.value.code == 400
    finally:
        httpd.shutdown()
