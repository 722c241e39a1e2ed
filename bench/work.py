"""Operations and bytes each mining primitive needs, from the shapes of
one call as the device trace records them (the HLO text of the op).

The count is the work of the primitive, not of its implementation:
inputs read once, outputs written once, one operation per event (two for
the affine scan, ``2*M*K*N`` for a semiring product), and no one-hot or
masked work.  So the same yardstick reads the same work whatever lowering
implements a kernel.  A ``segment_reduce`` output aliases its identity
filled input; it needs only the windows its events touch, at most one
output element per event, so neither the aliased input nor the rest of
the output counts.
"""
from __future__ import annotations

import functools
import math
import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
_SHAPE = re.compile(r"\b(pred|[sufb]f?\d+)\[([\d,]*)\]")
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")

# trace name of each mining primitive's kernel -> how its work is counted
KERNELS = ("pair_count_pallas", "histogram_pallas", "segment_reduce_pallas",
           "segmented_sum_scan_pallas", "segmented_affine_pallas",
           "semiring_matmul_pallas")


@functools.lru_cache(maxsize=None)
def op_name(hlo: str) -> str:
    """``%pair_count_pallas.3 = f32[...] custom-call(...)`` -> the op's
    name without its numeric suffix."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0].lstrip("%")


def shapes(hlo: str) -> tuple[list, list]:
    """(outputs, operands) of one HLO instruction as (dtype, dims)."""
    head, _, rest = hlo.partition(" = ")
    depth, split = 0, len(rest)
    for i, ch in enumerate(rest):            # the result type ends at the
        if ch in "([{":                      # first top-level space
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            split = i
            break
    out_txt, call = rest[:split], rest[split:]
    args = call[call.find("(") + 1:]
    depth = 1
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            args = args[:i]
            break

    def parse(txt):
        return [(t, tuple(int(d) for d in dims.split(",") if d))
                for t, dims in _SHAPE.findall(txt)]

    return parse(out_txt), parse(args)


def _nbytes(shape) -> int:
    t, dims = shape
    return ITEMSIZE.get(t, 4) * math.prod(dims)


@functools.lru_cache(maxsize=None)
def work(hlo: str) -> tuple[float, float] | None:
    """(operations, bytes) one call of a mining kernel needs, or None for
    an op that is not one of them."""
    name = op_name(hlo)
    if name not in KERNELS:
        return None
    outs, ins = shapes(hlo)
    if not outs or not ins:
        return None
    if name == "segment_reduce_pallas":
        events = math.prod(ins[2][1])
        read = sum(_nbytes(s) for s in ins if s != outs[0])
        written = min(math.prod(outs[0][1]), events) * ITEMSIZE.get(
            outs[0][0], 4)
        return float(events), float(read + written)
    nbytes = sum(_nbytes(s) for s in ins) + sum(_nbytes(s) for s in outs)
    if name == "semiring_matmul_pallas":
        (m, k), (_, n) = ins[0][1][-2:], ins[1][1][-2:]
        return 2.0 * m * k * n, float(nbytes)
    events = math.prod(ins[0][1])
    if name == "segmented_affine_pallas":
        return 2.0 * events, float(nbytes)
    return float(events), float(nbytes)


def roofline_s(hlo: str, peak: dict) -> float | None:
    """The least time the chip could take for one call: the larger of
    bytes over HBM bandwidth and operations over the highest op peak."""
    w = work(hlo)
    if w is None:
        return None
    ops, nbytes = w
    top = max(peak["bf16_flops_per_s"], peak["int8_ops_per_s"])
    return max(nbytes / peak["hbm_bytes_per_s"], ops / top)
