"""The four-chip cell on four virtual CPU devices: the sharded engine is
picked and proves correct, and with the exchange between devices left
out ``correct`` comes out false."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def run_child(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_SEGMENT_BACKEND="xla",
               # the tiny log is far under the 2M rows at which auto shards
               REPRO_DATASET_SHARD_ROWS="1000")
    r = subprocess.run([sys.executable, str(HERE / "sharded_child.py"),
                        *args], env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [((), True), (("fault",), False)])
def test_sharded_cell(fault, correct):
    out = run_child(*fault)
    assert out["correct"] is correct, out["checks"]
    assert out["checks"]["engine_not_expected"]["value"] == 0
