"""95th percentile of the benchmark's own timer around each
``Ingestor.run_once`` that appended a batch in the window."""
import numpy as np


def read(run):
    ms = run.counters.get("append_ms") or []
    return float(np.percentile(ms, 95)) if ms else None
