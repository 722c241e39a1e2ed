"""EventFrame: the paper's dataframe abstraction (Def. 3) as a JAX pytree.

A dataframe is ``D = (I, N, T, V, chi_val, chi_type)``:

* ``I``     — row indexes. Here implicit ``0..nrows-1``; projection keeps ``I``
              lazy through a ``row_valid`` mask (no dynamic shapes on device).
* ``N``     — attribute (column) names; pytree aux data.
* ``T``     — attribute types; carried by the arrays' dtypes.
* ``V``     — attribute values. Strings are dictionary-encoded to dense int32
              ids at the host boundary (see ``repro.data.tokenizer``); the
              device only ever sees numeric columns — this is the columnar /
              Parquet-dictionary story of the paper made TPU-native.
* ``chi_val``  — per-cell valuation: ``columns[name][i]``; ``epsilon`` (missing)
              is a per-column validity bitmask (Arrow-style), so integer
              columns stay integer.
* ``chi_type`` — ``columns[name].dtype``.

The structure is registered as a pytree so it can be sharded with
``NamedSharding``, passed through ``jit`` / ``shard_map``, and donated.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

# Canonical column names (XES vocabulary, dictionary-encoded on device).
CASE = "case:concept:name"
ACTIVITY = "concept:name"
TIMESTAMP = "time:timestamp"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EventFrame:
    """Columnar event dataframe. All columns share a common length ``nrows``.

    ``valid`` holds per-column epsilon masks only for columns that can have
    missing values (absent key => column is total). ``row_valid`` is the lazy
    projection mask: ``proj`` marks rows instead of compacting them, keeping
    shapes static under jit. ``compact`` materializes at the host boundary.
    """

    columns: dict[str, jax.Array]
    valid: dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    row_valid: jax.Array | None = None

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        col_names = tuple(sorted(self.columns))
        val_names = tuple(sorted(self.valid))
        children = (
            [self.columns[k] for k in col_names]
            + [self.valid[k] for k in val_names]
            + ([self.row_valid] if self.row_valid is not None else [])
        )
        aux = (col_names, val_names, self.row_valid is not None)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        col_names, val_names, has_rv = aux
        nc, nv = len(col_names), len(val_names)
        cols = dict(zip(col_names, children[:nc]))
        vals = dict(zip(val_names, children[nc : nc + nv]))
        rv = children[nc + nv] if has_rv else None
        return cls(columns=cols, valid=vals, row_valid=rv)

    # ------------------------------------------------------------ helpers
    @property
    def nrows(self) -> int:
        return int(next(iter(self.columns.values())).shape[0]) if self.columns else 0

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(sorted(self.columns))

    def __getitem__(self, name: str) -> jax.Array:
        return self.columns[name]

    def __contains__(self, name: str) -> bool:
        return name in self.columns

    def cell_valid(self, name: str) -> jax.Array:
        """epsilon mask for a column, combined with the row projection mask."""
        n = self.nrows
        v = self.valid.get(name, jnp.ones((n,), dtype=bool))
        if self.row_valid is not None:
            v = v & self.row_valid
        return v

    def rows_valid(self) -> jax.Array:
        if self.row_valid is not None:
            return self.row_valid
        return jnp.ones((self.nrows,), dtype=bool)

    def with_column(self, name: str, values: jax.Array, valid: jax.Array | None = None) -> "EventFrame":
        cols = dict(self.columns)
        cols[name] = values
        vals = dict(self.valid)
        if valid is not None:
            vals[name] = valid
        return EventFrame(cols, vals, self.row_valid)

    def select(self, names: Iterable[str]) -> "EventFrame":
        """Column projection — the paper's load-time attribute selection."""
        names = tuple(names)
        return EventFrame(
            {k: self.columns[k] for k in names},
            {k: v for k, v in self.valid.items() if k in names},
            self.row_valid,
        )

    def take(self, idx: jax.Array) -> "EventFrame":
        return EventFrame(
            {k: v[idx] for k, v in self.columns.items()},
            {k: v[idx] for k, v in self.valid.items()},
            self.row_valid[idx] if self.row_valid is not None else None,
        )

    def compact(self) -> "EventFrame":
        """Materialize the lazy projection mask (host boundary; dynamic shape)."""
        if self.row_valid is None:
            return self
        keep = np.asarray(self.row_valid)
        idx = np.nonzero(keep)[0]
        return EventFrame(
            {k: jnp.asarray(np.asarray(v)[idx]) for k, v in self.columns.items()},
            {k: jnp.asarray(np.asarray(v)[idx]) for k, v in self.valid.items()},
            None,
        )

    # --------------------------------------------------------- construct
    @staticmethod
    def from_numpy(columns: Mapping[str, np.ndarray],
                   valid: Mapping[str, np.ndarray] | None = None,
                   device: jax.Device | None = None) -> "EventFrame":
        """Put host columns on ``device`` (committed there), or on the
        default device when it is None."""
        lens = {k: len(v) for k, v in columns.items()}
        if len(set(lens.values())) > 1:
            raise ValueError(f"ragged columns: {lens}")
        put = jnp.asarray if device is None else \
            (lambda v: jax.device_put(v, device))
        return EventFrame(
            {k: put(v) for k, v in columns.items()},
            {k: put(v) for k, v in (valid or {}).items()},
        )

    def to_numpy(self) -> dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in self.columns.items()}


def concat_frames(parts) -> EventFrame:
    """Row-wise concatenation of same-schema frames (host-side).

    Epsilon masks and the lazy ``row_valid`` projection mask concatenate
    *separately* — folding ``row_valid`` into per-column validity would
    change what ``rows_valid()`` means to the kernels.  A column missing
    a part's epsilon mask contributes all-valid rows.  The single shared
    implementation behind dataset unions, eager multi-file loads, and
    pruned-scan materialization.
    """
    parts = list(parts)
    if not parts:
        raise ValueError("concat_frames() needs at least one frame")
    names = set(parts[0].names)
    for p in parts[1:]:
        if set(p.names) != names:
            raise ValueError(f"concat of frames with different columns: "
                             f"{sorted(names)} vs {sorted(p.names)}")
    cols = {k: np.concatenate([np.asarray(p.columns[k]) for p in parts])
            for k in parts[0].names}
    valid_names = set().union(*(set(p.valid) for p in parts))
    valid = {k: np.concatenate([
        np.asarray(p.valid[k]) if k in p.valid else np.ones(p.nrows, bool)
        for p in parts]) for k in valid_names}
    out = EventFrame.from_numpy(cols, valid)
    if any(p.row_valid is not None for p in parts):
        rv = np.concatenate([np.asarray(p.rows_valid()) for p in parts])
        out = EventFrame(out.columns, out.valid, jnp.asarray(rv))
    return out
