"""Device-to-host pulls the program made per row group read: the
``host_syncs`` of every mine's scan report (``repro.obs`` counters on the
program's ``ScanReport``) over their ``groups_read``.  None where the
reports carry no such counter or read no group."""


def read(run):
    reports = run.counters.get("scan_reports") or []
    syncs = groups = 0
    for r in reports:
        n = getattr(r, "host_syncs", None)
        if not isinstance(n, int):
            return None
        syncs += n
        groups += getattr(r, "groups_read", 0)
    return syncs / groups if groups else None
