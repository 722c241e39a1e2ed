"""Public entry point for DFG pair counting.

``core.backend`` picks the lowering, by the one rule every segment
primitive follows: the Pallas MXU kernel (compiled on a TPU, run in
interpret mode elsewhere) or the scatter-add reference.
"""
from __future__ import annotations

from .dfg_count import dfg_count_pallas
from .ref import dfg_count_ref


def dfg_count(src, dst, w, num_activities: int, *, impl: str | None = None):
    # deferred: repro.core's package init imports the kernel packages
    from repro.core import backend

    if backend.resolve(impl) == "pallas":
        return dfg_count_pallas(src, dst, w, num_activities,
                                interpret=backend.interpret_mode())
    return dfg_count_ref(src, dst, w, num_activities)
