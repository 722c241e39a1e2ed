"""The plain references agree with the program at a small size on the
CPU, and the control (the reference one precision below) does not."""
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from bench import canon, logs
from bench.reference import compare, mining

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
LIMITS = json.loads((CONFIGS.parent / "traffic" / "batch_profile.json")
                    .read_text())["limits"]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    import repro
    from repro.storage import edf

    cfg = json.loads((CONFIGS / "table6_l1.json").read_text())["log"]
    log = logs.generate(dict(cfg, num_cases=1500), 2**31 + 41)
    path = str(tmp_path_factory.mktemp("ref") / "log.edf")
    frame, tables = logs.to_frame(log)
    edf.write(path, frame, tables, row_group_rows=2048)
    return log, repro.open(path)


def test_profile_matches_reference(small):
    log, ds = small
    got = canon.plain(ds.profile(engine="streaming").results)
    ref = mining.Log(log.case, log.act, log.ts, log.num_activities,
                     log.num_cases)
    gap = compare.compare(got, mining.profile(ref))
    assert gap.mismatched == 0, gap.where
    assert gap.float_gap <= LIMITS["float_gap"]


def test_control_fails(small):
    log, _ = small
    ref = mining.profile(mining.Log(log.case, log.act, log.ts,
                                    log.num_activities, log.num_cases))
    ctl = mining.profile(mining.Log(log.case, log.act, log.ts,
                                    log.num_activities, log.num_cases,
                                    fdt=ml_dtypes.bfloat16))
    gap = compare.compare(canon.plain(ctl), ref)
    assert gap.mismatched > LIMITS["mismatched"] or \
        gap.float_gap > LIMITS["float_gap"]


def test_graph_and_windows_match_reference(small):
    log, ds = small
    from repro.service.server import to_jsonable

    ref = mining.Log(log.case, log.act, log.ts, log.num_activities,
                     log.num_cases)
    g = ds.graph(engine="streaming")
    body = {"graph": {"freq": to_jsonable(g.freq), "perf": None,
                      "source": g.source, "sink": g.sink},
            "query": to_jsonable(ds.collect("bottleneck_paths",
                                            engine="streaming").result)}
    gap = compare.compare(body, mining.served_graph(ref))
    assert gap.mismatched == 0, gap.where


def test_eventually_follows_and_variants_by_hand():
    case = np.array([0, 0, 0, 1, 1, 2])
    act = np.array([1, 0, 1, 2, 2, 0])
    ts = np.arange(6, dtype=np.float32)
    ref = mining.Log(case, act, ts, 3, 4)
    efg = ref.eventually_follows()
    want = np.zeros((3, 3), int)
    want[1, 0] += 1
    want[1, 1] += 1
    want[0, 1] += 1
    want[2, 2] += 1
    assert (efg == want).all()
    h1, h2, n = ref.variants()
    assert n == 3 and h1[3] == 0
    b = mining.BASE1
    assert h1[0] == ((2 * b + 1) * b + 2) % 2**32
    assert h1[1] == (3 * b + 3) % 2**32


def test_compare_counts_each_kind_of_difference():
    want = {"a": np.array([1, 2, 3]), "f": np.array([1.0, np.inf]),
            "start_activities": [1, 2], "places": [[[0], [1, 2]]]}
    same = {"a": [1, 2, 3], "f": [1.0, float("inf")],
            "start_activities": [2, 1], "places": [[[0], [2, 1]]]}
    assert compare.compare(same, want).mismatched == 0
    bad = {"a": [1, 2, 4], "f": [1.5, 3.0], "start_activities": [2],
           "places": []}
    gap = compare.compare(bad, want)
    assert gap.mismatched == 1 + 1 + 1 + 1
    assert gap.float_gap == pytest.approx(0.5)
