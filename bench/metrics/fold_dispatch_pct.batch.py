"""Share of the traced window the mining thread spent in the fused
kernel's per-group update, dispatching each member verb's jitted update:
100 x the ``fold.update`` seconds of every mine's scan report
(``repro.obs`` timings on the program's ``ScanReport``) over the window.
None where the reports carry no timings, or without a trace."""

SPANS = ("fold.update",)


def read(run):
    window = getattr(run.trace, "window_s", 0.0) if run.trace else 0.0
    reports = run.counters.get("scan_reports") or []
    if window <= 0 or not reports:
        return None
    total = 0.0
    for r in reports:
        timings = getattr(r, "timings", None)
        if not isinstance(timings, dict):
            return None
        total += sum(timings.get(s, (0, 0.0, 0.0))[1] for s in SPANS)
    return 100.0 * total / window
