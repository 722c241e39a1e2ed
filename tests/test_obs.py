"""Spans and counters of the mining path (``repro.obs``): self time under
nesting, the read-ahead worker's spans on its scan's report, the spans
and syncs of a streaming ``profile``, their sums across reports, compile
counts by program, and the spans in a ``jax.profiler`` trace."""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import random_log, sorted_frame

import repro
from repro import obs
from repro.dataset import engines as ds_engines
from repro.query.exec import ScanReport, execute, merge_reports
from repro.query.plan import Plan
from repro.query.statecache import state_cache
from repro.storage import edf

GROUP_ROWS = 64
N_ACTS = 5


@pytest.fixture()
def log_path(tmp_path):
    rng = np.random.default_rng(7)
    frame, tables = sorted_frame(random_log(rng, n_cases=60, n_acts=N_ACTS,
                                            max_len=9))
    path = str(tmp_path / "log.edf")
    edf.write(path, frame, tables, version=3, row_group_rows=GROUP_ROWS)
    state_cache().clear()
    ds_engines.clear_result_cache()
    return path


def test_nesting_and_self_time():
    with obs.record() as rec:
        with obs.span("outer"):
            time.sleep(0.02)
            with obs.span("inner"):
                time.sleep(0.03)
            with obs.span("inner"):
                pass
    n, total, own = rec.timings["outer"]
    n_in, total_in, own_in = rec.timings["inner"]
    assert (n, n_in) == (1, 2)
    assert total_in >= 0.03 and own_in == pytest.approx(total_in)
    assert total >= total_in + 0.02
    assert own == pytest.approx(total - total_in)


def test_nested_record_adds_into_the_outer_and_unbound_spans_are_free():
    with obs.span("nowhere"):            # no record bound: nothing to add
        assert obs.current() is None
    with obs.record() as outer:
        with obs.span("request"):
            with obs.record() as inner:
                assert obs.current() is inner
                with obs.span("scan"):
                    obs.pull(jnp.arange(3))
        assert obs.current() is outer
    assert "request" not in inner.timings and inner.host_syncs == 1
    assert outer.timings["scan"] == inner.timings["scan"]
    assert outer.host_syncs == 1 and outer.timings["request"][0] == 1


def test_pull_counts_device_arrays_only():
    with obs.record() as rec:
        host = obs.pull(np.arange(4))
        dev = obs.pull(jnp.arange(4) > 1, bool)
    assert rec.host_syncs == 1
    assert isinstance(host, np.ndarray) and dev.dtype == bool


def test_threads_sharing_a_record_lose_no_update():
    rec = obs.Record()
    threads_n, each = (os.cpu_count() or 2) + 4, 300

    def work():
        with obs.bind(rec):
            for _ in range(each):
                with obs.span("shared"):
                    obs.put(3)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert rec.timings["shared"][0] == threads_n * each
    assert rec.bytes_to_device == 3 * threads_n * each


@pytest.mark.parametrize("prefetch", [0, 1])
def test_read_ahead_spans_land_on_the_scans_report(log_path, prefetch):
    from repro.core.dfg import dfg_kernel

    reader = edf.EDFReader(log_path)
    _, report = execute(Plan(log_path), dfg_kernel(N_ACTS),
                        prefetch=prefetch)
    groups = report.groups_read
    assert groups == reader.num_groups > 1
    for name in ("edf.fetch", "edf.decode", "edf.put", "scan.mask",
                 "fold.update"):
        assert report.timings[name][0] == groups, name
    assert report.timings["scan"][0] == 1
    # the consumer waits on the read-ahead queue only when there is one
    assert report.timings.get("scan.wait", (0,))[0] == (
        groups if prefetch else 0)
    assert report.bytes_to_device > 0
    assert report.host_syncs >= groups          # one case column a group


def test_streaming_profile_reports_its_layers(log_path):
    res = repro.open(log_path).profile(engine="streaming")
    rep = res.report
    groups = rep.groups_read
    assert groups == edf.EDFReader(log_path).num_groups
    for name in ("edf.decode", "scan.wait", "fold.update"):
        assert rep.timings[name][0] == groups, name
    assert rep.timings["fold.finalize"][0] == 1
    # every member update runs in one fused program, one dispatch a group
    assert rep.timings["fold.update.fused"][0] == groups
    assert not [k for k in rep.timings
                if k.startswith("fold.update.") and k != "fold.update.fused"]
    # the fused dispatch is a child of the group's update
    n, total, own = rep.timings["fold.update"]
    assert own < total
    assert rep.host_syncs >= groups
    out = rep.to_dict()
    assert out["timings"]["scan"][0] == 1 and out["host_syncs"] > 0
    assert out["bytes_to_device"] > 0


def test_grouped_collect_reports_fold_merge_and_cache(log_path):
    ds = repro.open(log_path)
    rep = ds.collect("dfg", engine="streaming").report
    groups = rep.groups_read
    assert rep.timings["fold.group"][0] == groups
    assert rep.timings["fold.merge"][0] == 1
    assert rep.timings["fold.finalize"][0] == 1
    assert rep.timings["cache.lookup"][0] == 2 * groups     # get, then put
    # fold_group pulls case, activity and validity of each group
    assert rep.host_syncs >= 3 * groups


def test_sharded_collect_reports_its_spans(log_path):
    """A four-chip sharded collect (virtual devices in a subprocess): one
    ``shard.span.<i>`` a span, the wait, the merge with ``fold.merge``
    inside it, ``chips_used``, and one decode a group read."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import repro
rep = repro.open({log_path!r}).collect("dfg", engine="sharded").report
print("RESULT " + json.dumps({{"timings": rep.timings,
                              "chips_used": rep.chips_used,
                              "groups_read": rep.groups_read}}))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__),
                                                   "..", "src"))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.split("RESULT ")[-1])
    t = got["timings"]
    assert got["chips_used"] == 4
    assert sorted(k for k in t if k.startswith("shard.span.")) == [
        f"shard.span.{i}" for i in range(4)]
    for name in ("shard.span.0", "shard.span.3", "shard.wait",
                 "shard.merge", "fold.merge", "fold.finalize", "scan"):
        assert t[name][0] == 1, name
    # fold.merge is the merge span's one child
    _, total, own = t["shard.merge"]
    assert total - own == pytest.approx(t["fold.merge"][1], abs=1e-6)
    assert t["edf.decode"][0] == got["groups_read"] > 4
    assert t["fold.group"][0] == 4


def test_merge_reports_sums_the_counters():
    a = ScanReport("a", ("x",), True, groups_read=2,
                   timings={"scan": (1, 2.0, 0.5), "fold.update": (2, 1.0,
                                                                   1.0)},
                   host_syncs=3, bytes_to_device=10,
                   compiles={"jit(update)": 1})
    b = ScanReport("b", ("x",), True, groups_read=5,
                   timings={"scan": (1, 1.0, 0.25)}, host_syncs=4,
                   bytes_to_device=5,
                   compiles={"jit(update)": 2, "jit(finalize)": 1})
    m = merge_reports([a, b])
    assert m.groups_read == 7
    assert m.timings == {"scan": (2, 3.0, 0.75), "fold.update": (2, 1.0, 1.0)}
    assert m.host_syncs == 7 and m.bytes_to_device == 15
    assert m.compiles == {"jit(update)": 3, "jit(finalize)": 1}
    assert a.timings["scan"] == (1, 2.0, 0.5)           # inputs untouched


def test_a_fresh_jit_counts_a_compile_under_its_name():
    def _obs_probe(x):
        return x * 3 + 1

    fn = jax.jit(_obs_probe)
    with obs.record() as rec:
        fn(jnp.arange(11))
    assert rec.compiles.get("jit(_obs_probe)") == 1
    with obs.record() as again:
        fn(jnp.arange(11))                  # compiled already
    assert "jit(_obs_probe)" not in again.compiles


def test_spans_appear_in_a_profiler_trace(log_path, tmp_path):
    from jax.profiler import ProfileData

    out = str(tmp_path / "trace")
    ds = repro.open(log_path)
    with jax.profiler.trace(out):
        with jax.profiler.TraceAnnotation("test.outer"):
            rep = ds.collect("variants", engine="streaming",
                             prefetch=0).report
    files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(files[-1]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    outer = [e for e in events if e[1] == "test.outer"]
    spans = [e for e in events if e[1].startswith(obs.PREFIX)]
    assert len(outer) == 1 and spans
    line, _, lo, hi = outer[0]
    for ln, name, s, e in spans:
        assert ln == line and lo <= s and e <= hi, name
    counts = {}
    for _, name, _, _ in spans:
        key = name[len(obs.PREFIX):]
        counts[key] = counts.get(key, 0) + 1
    assert counts == {k: v[0] for k, v in rep.timings.items()}


def test_alpha_place_search_has_its_span(log_path):
    ds = repro.open(log_path)
    rep = ds.collect("alpha", engine="streaming").report
    assert rep.timings["alpha.places"][0] == 1
    assert rep.timings["alpha.places"][1] <= rep.timings["fold.finalize"][1]
    assert "alpha.places" not in ds.collect("dfg",
                                            engine="streaming").report.timings
