"""Share of the sharded scans' time the collecting thread spent waiting
for the chips' span states: 100 x the ``shard.wait`` seconds over the
``scan`` seconds of the scan reports the run kept (``repro.obs`` timings
on the program's ``ScanReport``; a batch mine keeps its last collect's).
None where no report carries the span (a program without the span fold).
"""

SPAN = "shard.wait"


def read(run):
    part = scan = 0.0
    found = False
    for r in run.counters.get("scan_reports") or []:
        timings = getattr(r, "timings", None)
        if not isinstance(timings, dict):
            continue
        if SPAN in timings:
            found = True
            part += timings[SPAN][1]
        scan += timings.get("scan", (0, 0.0, 0.0))[1]
    return 100.0 * part / scan if found and scan > 0 else None
