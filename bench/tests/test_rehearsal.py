"""Each mix's set-up, window, check and reduction at a tiny size on the
CPU, through the functions ``bench/run.py`` calls, and the command's
refusal off the chip."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from rehearse import rehearse, spec_with_later

ROOT = Path(__file__).resolve().parents[2]
TINY = {
    # at this size engine="auto" picks eager; the chip's log takes the
    # streaming engine, so the rehearsal asks for it
    "l1_profile": dict(log=dict(num_cases=1500),
                       storage=dict(row_group_rows=2048),
                       mix=dict(engine="streaming")),
    "l1_core_x4": dict(log=dict(num_cases=1500),
                       storage=dict(row_group_rows=2048),
                       mix=dict(expect_engine=None)),
    "bpic19_dashboard": dict(
        log=dict(num_cases=3000, num_events=19020, batch_cases=100),
        storage=dict(partition_rows=8000, case_capacity=4096),
        mix=dict(initial_batches=15, rate_per_s=1.5)),
}


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(cell, trace):
    out = rehearse(cell, 2**31 + 99, 4.0, trace, **TINY[cell])
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    spec = spec_with_later()
    names = {m["name"] for m in spec["end_to_end"]
             if cell in m.get("workloads", [cell])}
    if trace:
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
        assert set(out["metrics"]) <= {m["name"] for m in spec["per_layer"]}
    else:
        assert set(out["metrics"]) == names
        assert all(v["value"] >= 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "l1_profile", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
