"""Share of device time in collective ops (all-gather, all-reduce,
collective-permute, reduce-scatter, all-to-all) in the traced window."""
from bench import trace


def read(run):
    return None if run.trace is None else trace.collective_pct(run.trace)
