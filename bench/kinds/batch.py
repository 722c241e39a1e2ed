"""Batch mining: a log written once at set-up, mined whole back to back.

Mix parameters: ``call`` (``profile``: every registered verb in one fused
pass; ``collect``: each of ``verbs`` in turn), ``engine``, and
``expect_engine`` (the engine ``auto`` has to pick, or null).  Before
every mine the result memo and the group-state cache are cleared, so each
mine is the first mine of a freshly written log.  One mine is the user's
whole request; the window runs mines until its end and the last one
finishes past it, so the rate covers all the work and all the time.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import ml_dtypes
import numpy as np

from .. import canon, logs
from ..reference import compare, mining


class Driver:
    def __init__(self, cell, config, mix, seed, devs):
        self.name = cell["name"]
        self.config, self.mix, self.seed = config, mix, seed
        self.mines = []         # (start, end, engines, results, report)
        self.t0 = None
        self.workdir = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import repro
        from repro.storage import edf

        self.log = logs.generate(self.config["log"], self.seed)
        self.workdir = tempfile.mkdtemp(prefix=f"bench_{self.name}_")
        path = f"{self.workdir}/log.edf"
        frame, tables = logs.to_frame(self.log)
        edf.write(path, frame, tables,
                  row_group_rows=int(self.config["storage"]["row_group_rows"]))
        del frame
        self.ds = repro.open(path)
        self._mine(None)                    # warm-up: every shape, compiled

    def _mine(self, span):
        import jax
        from repro.dataset.engines import clear_result_cache
        from repro.query.statecache import state_cache

        clear_result_cache()
        state_cache().clear()
        engine = self.mix["engine"]
        start = time.monotonic()
        if self.mix["call"] == "profile":
            res = self.ds.profile(engine=engine)
            results, engines = res.results, {"profile": res.engine}
            report = res.report
        else:
            results, engines, report = {}, {}, None
            for verb in self.mix["verbs"]:
                res = self.ds.collect(verb, engine=engine)
                results[verb], engines[verb] = res.result, res.engine
                report = res.report
        jax.block_until_ready(results)
        return start, time.monotonic(), engines, results, report

    # ------------------------------------------------------------ window
    def window(self, seconds: float, span) -> None:
        self.t0 = time.monotonic()
        end = self.t0 + seconds
        while time.monotonic() < end:
            with span("mine"):
                self.mines.append(self._mine(span))

    def teardown(self) -> None:
        self.ds = None
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)

    # ----------------------------------------------------------- results
    def end_to_end(self) -> dict:
        last = self.mines[-1][1]
        return {"mine_events_per_s":
                self.log.num_events * len(self.mines) / (last - self.t0)}

    def counters(self) -> dict:
        reports = [m[4] for m in self.mines if m[4] is not None]
        return {"mines": len(self.mines), "scan_reports": reports,
                "engines": [m[2] for m in self.mines]}

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.mines), 0

    def notes(self) -> list:
        out = [f"log: {self.log.num_events} events, {self.log.num_cases} "
               f"cases, {self.log.num_activities} activities"]
        engines = sorted({e for m in self.mines for e in m[2].values()})
        out.append(f"engine picked by {self.mix['engine']}: {engines}")
        if self.mines and self.mines[-1][4] is not None:
            r = self.mines[-1][4]
            out.append(f"scan: groups_total={r.groups_total} "
                       f"groups_read={r.groups_read} "
                       f"groups_skipped={r.groups_skipped} "
                       f"groups_cached={r.groups_cached} "
                       f"groups_folded={r.groups_folded} "
                       f"rows_read={r.rows_read}")
        times = [m[1] - m[0] for m in self.mines]
        out.append(f"mines: {len(times)}, seconds each: {times}")
        return out

    def checks(self, control: bool = False) -> list:
        """Each mine's results against the reference (after the window,
        with the program's state freed).  ``control`` puts the reference
        computed one precision below in the program's place."""
        t = time.monotonic()
        want = self.reference(np.float64)
        gap = compare.Gap()
        wrong_engine = 0
        expect = self.mix.get("expect_engine")
        if control:
            ctl = canon.plain(self.reference(ml_dtypes.bfloat16))
            mines = [(None, None, {}, ctl, None)]
        else:
            mines = self.mines
        for _, _, engines, results, _ in mines:
            got = canon.plain(results)
            for verb in want:
                compare.compare(got.get(verb), want[verb], verb, gap)
            wrong_engine += sum(e != expect for e in engines.values()
                                if expect)
        print(f"reference and comparison: {time.monotonic() - t} s; "
              f"largest gaps at: {gap.where}", flush=True)
        lim = self.mix["limits"]
        return [("mismatched", gap.mismatched, lim["mismatched"]),
                ("float_gap", gap.float_gap, lim["float_gap"]),
                ("engine_not_expected", wrong_engine, 0)]

    def reference(self, fdt) -> dict:
        log = self.log
        ref = mining.Log(log.case, log.act, log.ts, log.num_activities,
                         log.num_cases, fdt=fdt)
        verbs = (mining.VERBS if self.mix["call"] == "profile"
                 else self.mix["verbs"])
        return {v: mining.verb(ref, v) for v in verbs}
