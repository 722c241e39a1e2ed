"""Chip smoke run: the mining path end to end on a TPU, at paper scale.

Generates the paper's Table 6 L1 log from a seed (10^6 cases, ~7x10^6
events, 26 activities), writes it as EDFV0003 row groups, and drives it
through the entry points a user calls: ``repro.open`` -> ``collect`` /
``collect_many`` / ``profile`` on the eager and streaming engines, a
zone-map-pruned filtered query, ``window(...).collect``, and the HTTP
service answering ``/collect``, ``/profile`` and ``/graph`` requests.

Every result is checked twice: bitwise against the same verb mined on the
XLA reference lowering on the same chip, and — for DFG counts, start/end
histograms and variant fingerprints — against an independent NumPy
computation on the host.  Any mismatch or failed phase raises, and the
script exits non-zero without its result line.  It refuses to run where
JAX finds no TPU, where the Pallas lowering is not selected, or where
Pallas would run in interpret mode.

    python chip_smoke.py              # one chip: every phase
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

The last line of standard output is the JSON result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ROW_GROUP_ROWS = 100_000       # EDF row group: the streaming engine's unit
LEVEL = 1                      # paper Table 6 L1
CORE_VERBS = ("dfg", "variants", "alpha")


class Clock:
    """Per-phase wall time and the programs compiled or loaded from the
    persistent compile cache in it (``repro.obs`` compile counts of the
    phase's thread and of the scans it runs)."""

    def __init__(self):
        self.compiles = 0

    @contextlib.contextmanager
    def phase(self, name):
        from repro import obs

        t0 = time.perf_counter()
        with obs.record() as rec:
            yield
        wall = time.perf_counter() - t0
        n = sum(rec.compiles.values())
        self.compiles += n
        print(f"phase {name}: wall_s={wall} compiles={n}", flush=True)


def canon(result) -> str:
    """Canonical JSON of a mining result: equal strings iff bitwise-equal
    payloads (floats round-trip through repr; -0.0 and NaN are kept)."""
    from repro.service.server import to_jsonable

    return json.dumps(to_jsonable(result), sort_keys=True)


def check_equal(what, got, want) -> None:
    if canon(got) != canon(want):
        where = ""
        if isinstance(got, dict) and isinstance(want, dict):
            where = " in " + ", ".join(sorted(
                k for k in set(got) | set(want)
                if canon(got.get(k)) != canon(want.get(k))))
        raise AssertionError(f"parity {what}: results differ{where}")
    print(f"parity {what}: equal", flush=True)


# ------------------------------------------------------------ the log
def make_log(workdir: Path):
    """The L1 log, written as EDFV0003 row groups; returns the path and
    its (case, activity) columns on the host for the NumPy references."""
    from repro.core.eventframe import ACTIVITY, CASE
    from repro.data import synthetic
    from repro.storage import edf

    frame, tables = synthetic.generate(**synthetic.paper_table6_config(LEVEL))
    path = str(workdir / f"table6_l{LEVEL}.edf")
    edf.write(path, frame, tables, row_group_rows=ROW_GROUP_ROWS)
    case = np.asarray(frame[CASE]).astype(np.int64)
    act = np.asarray(frame[ACTIVITY]).astype(np.int64)
    print(f"log: {case.size} events, {int(case.max()) + 1} cases, "
          f"{len(tables[ACTIVITY])} activities, row groups of "
          f"{ROW_GROUP_ROWS}", flush=True)
    return path, case, act, len(tables[ACTIVITY])


# ------------------------------------------------ NumPy references
def numpy_dfg(case, act, num_activities):
    """(counts, starts, ends) of a (case, time)-sorted log, by hand."""
    a = num_activities
    same = case[1:] == case[:-1]
    counts = np.bincount(act[:-1][same] * a + act[1:][same],
                         minlength=a * a).reshape(a, a)
    first = np.concatenate([[True], ~same])
    last = np.concatenate([~same, [True]])
    return (counts, np.bincount(act[first], minlength=a),
            np.bincount(act[last], minlength=a))


def numpy_variants(case, act):
    """{(fp1, fp2): cases}: each case's two rolling hashes h <- h*B + act+1
    (mod 2^32), folded position by position across all cases at once."""
    from repro.core.polyhash import BASE1, BASE2

    starts = np.flatnonzero(np.concatenate([[True], case[1:] != case[:-1]]))
    lengths = np.diff(np.concatenate([starts, [case.size]]))
    tok = (act + 1).astype(np.uint32)
    h1 = np.zeros(starts.size, np.uint32)
    h2 = np.zeros(starts.size, np.uint32)
    for t in range(int(lengths.max())):
        live = lengths > t
        v = tok[starts[live] + t]
        h1[live] = h1[live] * np.uint32(BASE1) + v
        h2[live] = h2[live] * np.uint32(BASE2) + v
    pairs, counts = np.unique(np.stack([h1, h2], axis=1), axis=0,
                              return_counts=True)
    return {(int(p[0]), int(p[1])): int(c) for p, c in zip(pairs, counts)}


def check_dfg(what, dfg, ref) -> None:
    for name, got, want in zip(("counts", "starts", "ends"),
                               (dfg.counts, dfg.starts, dfg.ends), ref):
        if not np.array_equal(np.asarray(got), want):
            raise AssertionError(f"numpy {what} {name}: differs")
    print(f"numpy {what}: equal", flush=True)


def check_variants(what, raw, ref, num_cases) -> None:
    from repro.core.variants import _counts_from_fps

    fp1, fp2, ncases = raw
    if _counts_from_fps(fp1, fp2, min(int(ncases), num_cases)) != ref:
        raise AssertionError(f"numpy {what}: variant counts differ")
    print(f"numpy {what}: equal", flush=True)


def report_line(what, report) -> None:
    print(f"scan {what}: groups_total={report.groups_total} "
          f"groups_read={report.groups_read} "
          f"groups_skipped={report.groups_skipped} "
          f"groups_cached={report.groups_cached} "
          f"rows_read={report.rows_read}", flush=True)


# ------------------------------------------------------- one chip
def mine(ds, pruned, clock, tag, engines, verbs):
    """Every phase's results on the current lowering, keyed by phase:
    ``verbs`` and the fused ``profile`` on each engine, then the pruned
    query and the windows."""
    out = {}
    for engine in engines:
        for verb in verbs:
            with clock.phase(f"{tag}/{engine}/{verb}"):
                res = ds.collect(verb, engine=engine)
            out[(engine, verb)] = res.result
        with clock.phase(f"{tag}/{engine}/profile"):
            prof = ds.profile(engine=engine)
        out[(engine, "profile")] = prof.results
    with clock.phase(f"{tag}/pruned_filter_dfg"):
        res = pruned.collect("dfg", engine="streaming")
    report_line(f"{tag}/pruned_filter_dfg", res.report)
    out[("streaming", "pruned")] = res.result
    with clock.phase(f"{tag}/window_dfg"):
        win = ds.window(by="groups", size=10, step=5).collect("dfg")
    report_line(f"{tag}/window_dfg", win.report)
    out[("streaming", "window")] = list(win.results)
    return out


def one_chip(clock, workdir: Path) -> None:
    import repro
    from repro import col
    from repro.core import backend
    from repro.core.eventframe import CASE

    with clock.phase("setup/generate_and_write"):
        path, case, act, num_acts = make_log(workdir)
    ds = repro.open(path)
    num_cases = ds.num_cases
    cut = num_cases // 10
    pruned = ds.filter(col(CASE) < cut)

    with clock.phase("setup/numpy_references"):
        ref_dfg = numpy_dfg(case, act, num_acts)
        keep = case < cut
        ref_pruned = numpy_dfg(case[keep], act[keep], num_acts)
        ref_var = numpy_variants(case, act)

    got = mine(ds, pruned, clock, "pallas", ("eager", "streaming"),
               CORE_VERBS)
    # the reference lowering mines each verb once: the engines agree
    # bitwise, and the fused profile holds every verb's result
    with backend.use_backend("xla"):
        ref = mine(ds, pruned, clock, "xla", ("eager",), ())
    xla_profile = ref[("eager", "profile")]

    for engine in ("eager", "streaming"):
        for verb in CORE_VERBS:
            check_equal(f"{engine}/{verb} pallas==xla",
                        got[(engine, verb)], xla_profile[verb])
        check_equal(f"{engine}/profile pallas==xla",
                    got[(engine, "profile")], xla_profile)
        check_dfg(f"{engine}/dfg", got[(engine, "dfg")], ref_dfg)
        check_dfg(f"{engine}/profile.dfg", got[(engine, "profile")]["dfg"],
                  ref_dfg)
        check_variants(f"{engine}/variants", got[(engine, "variants")],
                       ref_var, num_cases)
    for phase in ("pruned", "window"):
        check_equal(f"{phase} pallas==xla", got[("streaming", phase)],
                    ref[("streaming", phase)])
    check_dfg("pruned_filter_dfg", got[("streaming", "pruned")], ref_pruned)

    with clock.phase("service"):
        serve_and_compare(path, num_cases)


def serve_and_compare(path: str, num_cases: int) -> None:
    """The HTTP service on a thread of this process: every response must
    be 200 and carry exactly the direct call's result."""
    import repro
    from repro.dataset.engines import clear_result_cache
    from repro.service.server import serve

    httpd = serve([path], port=0, case_capacity=num_cases)
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        def get(query):
            clear_result_cache()        # the direct call mines afresh
            url = f"http://127.0.0.1:{port}{query}"
            with urllib.request.urlopen(url, timeout=900) as r:
                body = json.loads(r.read())
            clear_result_cache()
            return body, repro.open([path], num_cases=body["snapshot"]
                                    ["num_cases"])

        def same(what, served, direct):
            if json.dumps(served, sort_keys=True) != canon(direct):
                raise AssertionError(f"service {what}: body differs from "
                                     f"the direct call")
            print(f"service {what}: 200, equal", flush=True)

        for verb in CORE_VERBS:
            body, ds = get(f"/collect?verb={verb}&engine=streaming")
            same(f"/collect {verb}", body["result"],
                 ds.collect(verb, engine="streaming").result)
        body, ds = get("/profile?engine=streaming")
        same("/profile", body["results"], ds.profile(engine="streaming").results)
        body, ds = get("/graph?query=bottleneck_paths&engine=streaming")
        graph = ds.collect("graph", engine="streaming").result
        same("/graph graph.freq", body["graph"]["freq"], graph.freq)
        same("/graph bottleneck_paths", body["query"],
             ds.collect("bottleneck_paths", engine="streaming").result)
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=60)


# ----------------------------------------------------- four chips
def four_chips(clock, workdir: Path) -> None:
    """The sharded engine over a 4-device mesh, against one-chip
    streaming: ``engine="auto"`` must pick ``sharded`` on this log."""
    import repro

    with clock.phase("setup/generate_and_write"):
        path, _, _, _ = make_log(workdir)
    ds = repro.open(path)
    for verb in CORE_VERBS:
        with clock.phase(f"streaming/{verb}"):
            want = ds.collect(verb, engine="streaming")
        with clock.phase(f"auto/{verb}"):
            got = ds.collect(verb, engine="auto")
        if got.engine != "sharded":
            raise AssertionError(f"auto picked {got.engine!r} for {verb}, "
                                 f"not 'sharded'")
        check_equal(f"{verb} sharded==streaming", got.result, want.result)


# ------------------------------------------------------------ main
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase over four chips")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.core import backend

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices; "
                         f"JAX sees {len(jax.devices())}")
    if backend.resolve() != "pallas" or backend.interpret_mode():
        raise SystemExit(f"lowering {backend.resolve()!r} "
                         f"(interpret={backend.interpret_mode()}); the chip "
                         f"path needs compiled Pallas kernels")
    cache_dir = backend.enable_compile_cache()
    clock = Clock()
    print(f"device: {dev.device_kind} x{len(jax.devices())}; lowering: "
          f"{backend.resolve()} interpret={backend.interpret_mode()}; "
          f"compile cache: {cache_dir}", flush=True)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        (four_chips if args.chips == 4 else one_chip)(clock, Path(tmp))

    for d in jax.devices()[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}", flush=True)
    print(f"compiles: {clock.compiles}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
