"""The readers of the program's own counters (``repro.obs`` timings and
syncs on each mine's ``ScanReport``) on hand-built runs, and their
silence on reports that lack those counters."""
from types import SimpleNamespace

import pytest

from bench import harness

SHARES = {"decode_wait_pct.batch": ("scan.wait",),
          "fold_dispatch_pct.batch": ("fold.update",),
          "finalize_pct.batch": ("fold.merge", "fold.finalize")}


def report(groups=71, syncs=80, **timings):
    return SimpleNamespace(groups_read=groups, host_syncs=syncs,
                           timings=timings)


def run(reports, window_s=10.0):
    tr = None if window_s is None else SimpleNamespace(window_s=window_s)
    return SimpleNamespace(trace=tr, counters={"scan_reports": reports})


MINES = [report(**{"scan.wait": (72, 0.5, 0.5),
                   "fold.update": (71, 2.0, 0.25),
                   "fold.update.dfg": (71, 0.5, 0.5),
                   "fold.merge": (1, 0.25, 0.25),
                   "fold.finalize": (1, 0.5, 0.5),
                   "scan": (1, 4.0, 0.75)}),
         report(**{"scan.wait": (72, 1.5, 1.5),
                   "fold.update": (71, 1.0, 0.25),
                   "fold.finalize": (1, 0.25, 0.25)})]


@pytest.mark.parametrize("name,want", [("decode_wait_pct.batch", 20.0),
                                       ("fold_dispatch_pct.batch", 30.0),
                                       ("finalize_pct.batch", 10.0)])
def test_time_shares_over_the_window(name, want):
    assert harness.reader(name)(run(MINES)) == pytest.approx(want)


def test_host_syncs_per_group():
    read = harness.reader("host_syncs_per_group.batch")
    assert read(run([report(71, 80), report(71, 62)])) == pytest.approx(1.0)
    assert read(run([report(0, 3)])) is None            # no group read


@pytest.mark.parametrize("name", sorted(SHARES) +
                         ["host_syncs_per_group.batch"])
def test_silent_without_the_counters(name):
    read = harness.reader(name)
    # the reports of a program that has no spans and counters
    bare = SimpleNamespace(groups_read=71, groups_cached=0, rows_read=7)
    assert read(run([bare])) is None
    assert read(run([MINES[0], bare])) is None
    assert read(run([])) is None
    assert read(SimpleNamespace(trace=None, counters={})) is None


@pytest.mark.parametrize("name", sorted(SHARES))
def test_shares_need_a_trace(name):
    assert harness.reader(name)(run(MINES, window_s=None)) is None


def test_shares_of_one_window_sum_to_at_most_all_of_it():
    total = sum(harness.reader(n)(run(MINES)) for n in SHARES)
    assert 0 <= total <= 100
