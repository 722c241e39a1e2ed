"""Tiling helpers shared by the Pallas kernels.

On the TPU a block's last two dimensions map onto (sublanes, lanes) of
(8, 128) vregs: a block must be a multiple of that, or span the whole
array dimension.  The segment-op kernels therefore carry the event stream
as one lane-dense ``(1, E)`` row (the leading 1 spans its whole dimension)
and cut it into ``(1, tile)`` blocks whose width is a whole number of
128-lane vregs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8


def round_up(x: int, m: int) -> int:
    """Smallest positive multiple of ``m`` that is >= ``x``."""
    return max(m, ((x + m - 1) // m) * m)


def lane_tile(block_e: int) -> int:
    """An event-tile width: ``block_e`` rounded up to whole vregs."""
    return round_up(block_e, LANES)


def out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """A kernel output's shape, varying over every mesh axis its inputs
    vary over — what ``shard_map``'s varying-axes check asks of a
    ``pallas_call`` traced inside it (outside one, the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def event_row(x: jax.Array, tile: int, fill) -> jax.Array:
    """``(n,)`` -> ``(1, n_pad)``: tail-padded with ``fill`` to whole tiles."""
    pad = (-x.shape[0]) % tile
    return jnp.pad(x, (0, pad), constant_values=fill).reshape(1, -1)
