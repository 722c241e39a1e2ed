"""Results of the program as plain data: the JSON form the service
serves (``_type`` tags for structured results, lists for tuples), with
arrays kept as NumPy arrays so that large results compare quickly."""
from __future__ import annotations

import dataclasses
from typing import Mapping

import numpy as np


def plain(obj):
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, np.generic):
        return obj.item()
    if hasattr(obj, "__array__") and hasattr(obj, "shape"):
        return np.asarray(obj)
    if hasattr(obj, "_asdict"):
        return {"_type": type(obj).__name__,
                **{k: plain(v) for k, v in obj._asdict().items()}}
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {"_type": type(obj).__name__,
                **{f.name: plain(getattr(obj, f.name))
                   for f in dataclasses.fields(obj)}}
    if isinstance(obj, Mapping):
        return {str(k): plain(v) for k, v in obj.items()}
    if isinstance(obj, (frozenset, set)):
        return sorted(plain(v) for v in obj)
    if isinstance(obj, (list, tuple)):
        return [plain(v) for v in obj]
    return repr(obj)
