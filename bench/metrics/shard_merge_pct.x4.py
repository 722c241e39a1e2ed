"""Share of the sharded scans' time spent moving the span states to the
merging chip and merging them there: 100 x the ``shard.merge`` seconds
over the ``scan`` seconds of the scan reports the run kept (``repro.obs``
timings; ``fold.merge`` nests inside).  None where no report carries the
span (a program without the span fold).
"""

SPAN = "shard.merge"


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
