"""How unevenly the chips' span workers finish: 100 x the sum over the
kept scan reports of (longest - shortest ``shard.span.<i>`` seconds) over
the sum of the longest (``repro.obs`` timings).  None where no report
carries ``shard.span.<i>`` spans (a program without the span fold)."""


def read(run):
    spread = longest = 0.0
    for r in run.counters.get("scan_reports") or []:
        timings = getattr(r, "timings", None)
        if not isinstance(timings, dict):
            continue
        spans = [t[1] for name, t in timings.items()
                 if name.startswith("shard.span.")]
        if spans:
            spread += max(spans) - min(spans)
            longest += max(spans)
    return 100.0 * spread / longest if longest > 0 else None
