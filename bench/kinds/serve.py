"""A live log behind the HTTP service: appends and dashboard queries.

Set-up generates the configuration's log, ingests its first
``initial_batches`` batches of ``batch_cases`` cases through the
program's ``Ingestor`` (one closed, case-sorted batch per ``run_once``),
serves the partitions with ``MiningService`` on a local port, and sends
each request kind of the mix once.  In the window:

* an ingest thread applies batch ``k`` of the rest at ``k * cadence_s``
  after the window start (a batch not yet applied by then waits in the
  source: that is the backlog);
* a child process (``bench/loadgen.py``) sends ``rate_per_s * seconds``
  requests open loop.  Every seed draws the same inter-arrival gaps (the
  quantiles of the exponential distribution, scaled to fill the window)
  and the same number of each request kind, in another order.

Latency is timed from each request's due time to its delivery.  The
staleness of a response is its delivery time minus the due time of the
oldest batch that was due before delivery and that its snapshot lacks
(0 where it lacks none).  The check compares a sample of the bodies,
drawn from the seed, with the plain reference over exactly the rows each
snapshot claims, and checks every response's snapshot holds every batch
acknowledged before its request was sent.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

import ml_dtypes
import numpy as np

from .. import logs
from ..reference import compare, mining

LOADGEN = Path(__file__).resolve().parents[1] / "loadgen.py"


def traced(service_class):
    """The service with a host span around each request it handles, and a
    count of the requests in flight (teardown waits for them, so no
    handler thread still runs on the device when the process exits)."""
    import jax

    def wrap(name):
        method = getattr(service_class, name)

        def call(self, *args, **kwargs):
            with self.flight:
                self.in_flight += 1
            try:
                with jax.profiler.TraceAnnotation(f"bench.http.{name}"):
                    return method(self, *args, **kwargs)
            finally:
                with self.flight:
                    self.in_flight -= 1
        return call

    def init(self, *args, **kwargs):
        service_class.__init__(self, *args, **kwargs)
        self.flight = threading.Lock()
        self.in_flight = 0

    return type("TracedService", (service_class,), {
        "__init__": init,
        **{n: wrap(n) for n in ("collect", "profile", "window", "graph")}})


class Driver:
    def __init__(self, cell, config, mix, seed, devs):
        self.name = cell["name"]
        self.config, self.mix, self.seed = config, mix, seed
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x5E4]))
        self.workdir = self.httpd = self.child = None
        self.records = []
        self.acks = {}              # batch index -> ack time
        self.append_ms = []
        self.due = {}               # batch index -> due time

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from repro.service.ingest import Ingestor
        from repro.service.server import MiningService, serve

        cfg, st = self.config["log"], self.config["storage"]
        self.log = logs.generate(cfg, self.seed)
        bc = int(cfg["batch_cases"])
        starts = np.searchsorted(self.log.case,
                                 np.arange(0, self.log.num_cases, bc))
        self.bounds = np.append(starts, self.log.num_events)  # batch rows
        self.workdir = tempfile.mkdtemp(prefix=f"bench_{self.name}_")
        self.released = 0           # batches the source may hand out
        self.lock = threading.Lock()
        self.ingestor = Ingestor(
            os.path.join(self.workdir, "parts"), self._poll,
            partition_rows=int(st["partition_rows"]),
            row_group_rows=int(st["row_group_rows"]))
        self.initial = int(self.mix["initial_batches"])
        for _ in range(self.initial):
            with self.lock:
                self.released += 1
            self.ingestor.run_once(limit=1)
        self.service = traced(MiningService)(
            self.ingestor, case_capacity=int(st["case_capacity"]))
        self.httpd = serve(self.service, host="127.0.0.1", port=0)
        self.port = self.httpd.server_address[1]
        self.server_thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self.server_thread.start()
        for path, _ in self.mix["requests"]:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}{path}", timeout=600) as r:
                r.read()
        self.child = subprocess.Popen(
            [sys.executable, str(LOADGEN)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)

    def _poll(self, done_ids):
        with self.lock:
            upto = self.released
        out = []
        for k in range(upto):
            bid = f"batch_{k:06d}"
            if bid not in done_ids:
                frame, tables = logs.to_frame(self.log, int(self.bounds[k]),
                                              int(self.bounds[k + 1]))
                out.append((bid, frame, tables))
                break
        return out

    # ------------------------------------------------------------- plan
    def plan(self, seconds: float) -> list:
        """[(due offset, path, keep body)] for the window (module doc)."""
        n = max(int(round(float(self.mix["rate_per_s"]) * seconds)), 1)
        u = (np.arange(n) + 0.5) / n
        gaps = self.rng.permutation(-np.log1p(-u))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            / gaps.sum() * seconds
        kinds = []
        shares = [float(s) for _, s in self.mix["requests"]]
        counts = np.floor(np.array(shares) / sum(shares) * n).astype(int)
        for i in np.argsort(-(np.array(shares) * n - counts))[
                :n - counts.sum()]:
            counts[i] += 1
        for (path, _), c in zip(self.mix["requests"], counts):
            kinds += [path] * int(c)
        kinds = list(self.rng.permutation(kinds))
        keep = set()
        for path, _ in self.mix["requests"]:        # a sample of each kind
            idx = [i for i, k in enumerate(kinds) if k == path]
            pick = self.rng.permutation(idx)[:int(self.mix["sample_per_kind"])]
            keep.update(int(i) for i in pick)
        return [(float(d), p, i in keep) for i, (d, p) in
                enumerate(zip(due, kinds))]

    # ------------------------------------------------------------ window
    def window(self, seconds: float, span) -> None:
        requests = self.plan(seconds)
        self.t0 = time.monotonic() + 0.05
        self.t_end = self.t0 + seconds
        self.child.stdin.write(json.dumps({
            "port": self.port, "t0": self.t0,
            "timeout": float(self.mix["timeout_s"]),
            "requests": requests}) + "\n")
        self.child.stdin.flush()
        cadence = float(self.mix["cadence_s"])
        left = len(self.bounds) - 1 - self.initial
        nlive = min(int(math.ceil(seconds / cadence)), left)
        for j in range(nlive):
            self.due[self.initial + j] = self.t0 + j * cadence
        stop = threading.Event()
        ingest = threading.Thread(target=self._ingest, args=(stop, span),
                                  daemon=True)
        ingest.start()
        out, _ = self.child.communicate(
            timeout=seconds + float(self.mix["timeout_s"]) + 120)
        stop.set()
        ingest.join(timeout=120)
        self.records = json.loads(out)

    def _ingest(self, stop, span) -> None:
        for k in sorted(self.due):
            wait = self.due[k] - time.monotonic()
            if wait > 0 and stop.wait(wait):
                return
            if time.monotonic() > self.t_end or stop.is_set():
                return
            with self.lock:
                self.released = k + 1
            with span("ingest"):
                t = time.monotonic()
                applied = self.ingestor.run_once(limit=1)
                self.append_ms.append((time.monotonic() - t) * 1e3)
            if applied:
                self.acks[k] = time.monotonic()

    def teardown(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait(timeout=60)
        if self.httpd is not None:
            end = time.monotonic() + float(self.mix["timeout_s"]) + 300
            while self.service.in_flight and time.monotonic() < end:
                time.sleep(0.1)
            self.httpd.shutdown()
            self.httpd.server_close()
            self.server_thread.join(timeout=60)
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)

    # ----------------------------------------------------------- results
    def _answered(self):
        return [r for r in self.records if r and r.get("status") == 200
                and r.get("done") is not None]

    def _stale_ms(self, r) -> float:
        lacking = [k for k in self.due if self.bounds[k + 1] > r["rows"]]
        if not lacking or self.due[min(lacking)] >= r["done"]:
            return 0.0
        return (r["done"] - self.due[min(lacking)]) * 1e3

    def end_to_end(self) -> dict:
        ok = self._answered()
        lat = [(r["done"] - r["due"]) * 1e3 for r in ok]
        # a request that never answered misses every limit: it counts at
        # the longest wait the run saw
        worst = max(lat + [0.0])
        lat += [max(worst, (self.t_end - (r["due"] if r else self.t0)) * 1e3)
                for r in self.records if r not in ok]
        stale = [self._stale_ms(r) for r in ok]
        return {"query_p95_ms": float(np.percentile(lat, 95)),
                "stale_p95_ms": float(np.percentile(stale, 95))}

    def counters(self) -> dict:
        ok = self._answered()
        return {"groups_cached": sum(r["groups_cached"] for r in ok),
                "groups_folded": sum(r["groups_folded"] for r in ok),
                "append_ms": list(self.append_ms)}

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.records), len(self.records) - len(self._answered())

    def notes(self) -> list:
        ok = self._answered()
        late = [(r["sent"] - r["due"]) * 1e3 for r in self.records
                if r and r.get("sent") is not None]
        lag = [(self.acks[k] - self.due[k]) * 1e3 for k in self.acks]
        unapplied = [k for k in self.due if k not in self.acks
                     and self.due[k] <= self.t_end]
        by = {}
        for r in ok:
            by.setdefault(r["path"].split("?")[0], []).append(
                (r["done"] - r["due"]) * 1e3)
        out = [f"log: {self.log.num_events} events, {self.log.num_cases} "
               f"cases; batches ingested at set-up {self.initial}, due in "
               f"the window {len(self.due)}",
               f"generator lateness ms: max {max(late + [0.0])} p95 "
               f"{float(np.percentile(late, 95)) if late else 0.0}",
               f"ingest: applied {len(self.acks)}, due and not applied "
               f"{len(unapplied)}, ack lag ms max {max(lag + [0.0])}",
               f"responses: {len(ok)} of {len(self.records)}; errors "
               f"{[r.get('error') or r.get('status') for r in self.records if r not in ok][:5]}"]
        for k, v in sorted(by.items()):
            out.append(f"latency ms {k}: n={len(v)} median "
                       f"{float(np.median(v))} max {max(v)}")
        c = self.counters()
        out.append(f"state cache: groups_cached={c['groups_cached']} "
                   f"groups_folded={c['groups_folded']}")
        return out

    # ------------------------------------------------------------- check
    def checks(self, control: bool = False) -> list:
        """The sampled bodies against the reference over the rows each
        snapshot claims, and every snapshot against the acknowledged
        batches.  ``control`` puts the reference computed one precision
        below in the place of each sampled body."""
        t = time.monotonic()
        ok = self._answered()
        sent_misses = 0
        for r in ok:
            acked = [k for k, a in self.acks.items() if a < r["sent"]]
            if acked and self.bounds[max(acked) + 1] > r["rows"]:
                sent_misses += 1
        gap = compare.Gap()
        sampled = [r for r in self.records if r and r["keep"]]
        unanswered = 0
        for r in sampled:
            if r.get("status") == 200 and "body" in r:
                self._compare(r, gap, control)
            else:
                unanswered += 1
        print(f"reference and comparison: {time.monotonic() - t} s over "
              f"{len(sampled)} sampled responses; largest gaps at: "
              f"{gap.where}",
              flush=True)
        lim = self.mix["limits"]
        return [("mismatched", gap.mismatched, lim["mismatched"]),
                ("float_gap", gap.float_gap, lim["float_gap"]),
                ("snapshot_misses", sent_misses, 0),
                ("sample_unanswered", unanswered, 0)]

    def _compare(self, r, gap, control=False) -> None:
        body, rows = r["body"], int(r["rows"])
        snap = body["snapshot"]
        nb = int(np.searchsorted(self.bounds, rows))
        if self.bounds[nb] != rows or r["groups"] != nb:
            gap.miss(f"{r['path']} snapshot rows={rows} groups={r['groups']}")
            return
        path = r["path"]
        want = self._reference(path, body, rows, snap, np.float64)
        if want is None:
            gap.miss(f"{path}: no reference")
            return
        got = body
        if control:
            got = self._reference(path, body, rows, snap, ml_dtypes.bfloat16)
        compare.compare(got, want, path, gap)

    def _reference(self, path, body, rows, snap, fdt):
        """The reference of one response in the JSON form of its body."""
        log = self.log

        def ref(a, b):
            return mining.Log(log.case[a:b], log.act[a:b], log.ts[a:b],
                              int(snap["num_activities"]),
                              int(snap["num_cases"]), fdt=fdt)

        if path.startswith("/profile"):
            return {"results": mining.profile(ref(0, rows))}
        if path.startswith("/collect"):
            verb = path.split("verb=")[1].split("&")[0]
            return {"result": mining.verb(ref(0, rows), verb)}
        if path.startswith("/graph"):
            return mining.served_graph(ref(0, rows))
        if path.startswith("/window"):
            return {"results": [
                mining.verb(ref(int(self.bounds[lo]), int(self.bounds[hi])),
                            "dfg") for lo, hi in body["bounds"]]}
        return None
