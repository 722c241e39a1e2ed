"""Share of the traced window in which no op ran on the device, mean over
the devices used (profiler trace, ``bench.trace.idle_pct``)."""
from bench import trace


def read(run):
    return None if run.trace is None else trace.idle_pct(run.trace)
