"""The comparison that decides ``correct``: a served or returned result
(JSON form) against the plain reference.

Two numbers come out of every comparison:

* ``mismatched``: integer, boolean, string and set elements that differ,
  plus structure that is missing or of another shape (limit 0);
* ``float_gap``: the largest gap of a float output, each leaf's
  ``max |got - want|`` over its largest finite ``|want|`` (infinities
  must sit where the reference has them, or they count as mismatched).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# outputs that are sets written as lists (order carries no meaning)
SET_KEYS = ("start_activities", "end_activities")


@dataclasses.dataclass
class Gap:
    mismatched: int = 0
    float_gap: float = 0.0
    where: list = dataclasses.field(default_factory=list)

    def add(self, other: "Gap") -> None:
        self.mismatched += other.mismatched
        self.float_gap = max(self.float_gap, other.float_gap)
        self.where += other.where

    def miss(self, path: str, n: int = 1) -> None:
        self.mismatched += int(n)
        if len(self.where) < 8:
            self.where.append(path)


def _places(x) -> set:
    return {(frozenset(a), frozenset(b)) for a, b in x}


def compare(got, want, path: str = "", gap: Gap | None = None) -> Gap:
    gap = Gap() if gap is None else gap
    if isinstance(want, dict):
        if not isinstance(got, dict):
            gap.miss(path)
            return gap
        for k, w in want.items():
            if k not in got:
                gap.miss(f"{path}.{k}")
            else:
                compare(got[k], w, f"{path}.{k}", gap)
        return gap
    key = path.rsplit(".", 1)[-1]
    if key == "places":
        if _places(got) != _places(want):
            gap.miss(path, len(_places(got) ^ _places(want)))
        return gap
    if key in SET_KEYS:
        if set(got) != set(want):
            gap.miss(path, len(set(got) ^ set(want)))
        return gap
    if want is None or isinstance(want, str):
        if got != want:
            gap.miss(path)
        return gap
    if isinstance(want, list) and any(
            isinstance(w, (np.ndarray, list, dict)) for w in want):
        if not isinstance(got, list) or len(got) != len(want):
            gap.miss(path)
            return gap
        for i, (g, w) in enumerate(zip(got, want)):
            compare(g, w, f"{path}[{i}]", gap)
        return gap
    w = np.asarray(want)
    try:
        g = np.asarray(got, dtype=np.float64 if w.dtype.kind == "f"
                       else None)
    except (TypeError, ValueError):
        gap.miss(path)
        return gap
    if g.shape != w.shape:
        gap.miss(path, max(w.size, 1))
        return gap
    if w.dtype.kind in "biu":
        bad = int(np.count_nonzero(g != w))
        if bad:
            gap.miss(path, bad)
        return gap
    w = w.astype(np.float64)
    fin = np.isfinite(w)
    bad = int(np.count_nonzero(~fin & (g != w)) + np.count_nonzero(
        fin & ~np.isfinite(g)))
    if bad:
        gap.miss(path, bad)
    if fin.any():
        scale = float(np.max(np.abs(w[fin])))
        gf, wf = g[fin], w[fin]
        diff = np.abs(np.where(np.isfinite(gf), gf, wf) - wf)
        rel = float(diff.max()) / scale if scale > 0 else float(diff.max())
        if rel > gap.float_gap:
            gap.float_gap = rel
            if rel > 0 and len(gap.where) < 8:
                gap.where.append(f"{path}~{rel:.3g}")
    return gap
