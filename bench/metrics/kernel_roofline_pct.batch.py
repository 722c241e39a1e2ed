"""Sum of roofline times over sum of device times of the mining kernels
in the traced window (``bench.trace.kernel_roofline_pct``, work counted
by ``bench.work`` against ``bench.peaks``).  None where no mining kernel
ran."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    return trace.kernel_roofline_pct(run.trace, run.peak)
