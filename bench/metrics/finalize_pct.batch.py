"""Share of the traced window the mining thread spent merging group
states (their stitches) and finalizing the result: 100 x the
``fold.merge`` and ``fold.finalize`` seconds of every mine's scan report
(``repro.obs`` timings on the program's ``ScanReport``) over the window.
None where the reports carry no timings, or without a trace."""

SPANS = ("fold.merge", "fold.finalize")


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
