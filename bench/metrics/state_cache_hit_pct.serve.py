"""Group states served from the state cache, over those served or freshly
folded, summed over the scan reports of the window's responses."""


def read(run):
    hit = run.counters.get("groups_cached", 0)
    fold = run.counters.get("groups_folded", 0)
    return 100.0 * hit / (hit + fold) if hit + fold else None
