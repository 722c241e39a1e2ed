"""Programs compiled or loaded from the compile cache inside the measured
window (JAX's monitoring events, ``bench.clock``)."""


def read(run):
    return run.counters.get("compiles_in_window")
