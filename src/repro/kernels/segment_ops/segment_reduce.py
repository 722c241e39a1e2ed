"""Pallas kernel: sum/min/max over *sorted, consecutive* segment ids.

The event stream arrives sorted by (case, time), so segment ids are a
non-decreasing run ``0,0,1,2,2,2,...`` — a tile of ``W <= 1024`` events
touches at most ``W`` *consecutive* segments, hence at most two of the
1024-segment output windows ``(8, 128)`` into which the output is cut.

The grid is ``(event tiles, 2)``: step ``(k, j)`` folds tile k into its
first (j = 0) or last (j = 1) window, whose indices are scalar-prefetched
and drive the output ``BlockSpec``.  Because the ids are sorted, the
window sequence never goes back, so every window is visited in one run of
consecutive steps: it is loaded at the run's first step (from an input
aliased to the output, filled with the op identity) and written back once
after its last.  Windows no tile touches keep the identity, so only the
touched windows, never all ``num_segments``, pass through VMEM.

Inside a step each 128-event lane group is transposed to a column, and
each of the window's 8 rows takes a masked sublane reduction over the
group: ``out[r, c] = op(out[r, c], reduce over events e of where(local_e ==
128 r + c, v_e, identity))``.  Work is O(N * 1024), independent of the
number of segments.  Out-of-range ids (< 0 or >= num_segments) are
dropped, matching ``.at[...].op(mode="drop")``.  uint32 values are mapped
to int32 (order-preserving for min/max, a bitcast for the wrapping sum).

Contract: ids must be sorted and consecutive (as produced by
``ops.segment_ids_sorted`` / ``engine.global_segments``); out-of-range ids
may only lead or trail the run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import (LANES, SUBLANES, event_row, lane_tile,
                                 out_struct, round_up)

WINDOW = SUBLANES * LANES     # segments per output window: one (8, 128) vreg
_SIGN = np.uint32(0x80000000)


def _ident_scalar(op: str, dtype):
    """Python-scalar reduction identity (kernels cannot capture arrays)."""
    d = np.dtype(dtype)
    if op == "sum":
        return d.type(0).item()
    if np.issubdtype(d, np.floating):
        return float("inf") if op == "min" else float("-inf")
    info = np.iinfo(d)
    return info.max if op == "min" else info.min


# op -> (reduction over an axis, elementwise combine)
_OPS = {"sum": (jnp.sum, jnp.add),
        "min": (jnp.min, jnp.minimum),
        "max": (jnp.max, jnp.maximum)}


def _column(row):
    """(1, 128) -> (128, 1) through a full-vreg transpose."""
    return jnp.broadcast_to(row, (SUBLANES, LANES)).T[:, :1]


def _kernel(first_ref, last_ref, seg_ref, val_ref, init_ref, out_ref, *,
            op, num_segments, ident):
    k = pl.program_id(0)
    j = pl.program_id(1)
    first, last = first_ref[k], last_ref[k]
    win = jnp.where(j == 0, first, last)
    prev = jnp.where(j == 0,
                     jnp.where(k > 0, last_ref[jnp.maximum(k - 1, 0)], -1),
                     first)

    @pl.when(win != prev)                # first step of this window's run
    def _load():
        out_ref[...] = init_ref[...]

    @pl.when((j == 0) | (first != last))
    def _fold():
        reduce, combine = _OPS[op]
        lanes = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        rows = [out_ref[r:r + 1, :] for r in range(SUBLANES)]
        for g in range(seg_ref.shape[1] // LANES):
            group = slice(g * LANES, (g + 1) * LANES)
            seg = seg_ref[:, group]                          # (1, 128)
            local = seg - win * WINDOW
            ok = ((seg >= 0) & (seg < num_segments)
                  & (local >= 0) & (local < WINDOW))
            loc = _column(jnp.where(ok, local, -1))          # (128, 1)
            v = _column(val_ref[:, group])
            in_lane = (loc & (LANES - 1)) == lanes           # (128, 128)
            row_of = loc >> 7                    # -1 (dropped) matches no row
            for r in range(SUBLANES):
                cells = jnp.where(in_lane & (row_of == r), v, ident)
                rows[r] = combine(rows[r], reduce(cells, axis=0, keepdims=True))
        for r in range(SUBLANES):
            out_ref[r:r + 1, :] = rows[r]


def _windows(seg_tiles, num_segments):
    """Per event tile: the first and last output window it touches.  A tile
    with no in-range id repeats the previous tile's last window, so the
    window sequence stays non-decreasing and revisits stay consecutive."""
    ok = (seg_tiles >= 0) & (seg_tiles < num_segments)
    lo = jnp.min(jnp.where(ok, seg_tiles, num_segments), axis=1) // WINDOW
    hi = jnp.max(jnp.where(ok, seg_tiles, 0), axis=1) // WINDOW
    last = jax.lax.cummax(jnp.where(ok.any(axis=1), hi, 0))
    first = jnp.where(ok.any(axis=1), lo, last)
    return first.astype(jnp.int32), last.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "op", "block_e", "interpret"))
def segment_reduce_pallas(values: jax.Array, segment_ids: jax.Array,
                          num_segments: int, op: str = "sum", *,
                          block_e: int = 512, interpret: bool = True) -> jax.Array:
    """(num_segments,) reduction of ``values`` by sorted ``segment_ids``.

    ``block_e`` is rounded up to whole 128-lane vregs, at most ``WINDOW``.
    """
    n = values.shape[0]
    ident = _ident_scalar(op, values.dtype)
    if n == 0:
        return jnp.full((num_segments,), ident, values.dtype)
    unsigned = values.dtype == jnp.uint32
    vals = values
    if unsigned:
        if op != "sum":                  # order-preserving uint32 -> int32
            vals = vals ^ _SIGN
        vals = jax.lax.bitcast_convert_type(vals, jnp.int32)
    kident = _ident_scalar(op, vals.dtype)
    w = min(lane_tile(block_e), WINDOW)
    seg = event_row(segment_ids.astype(jnp.int32), w, -1)
    val = event_row(vals, w, kident)
    ne = seg.shape[1] // w
    first, last = _windows(seg.reshape(ne, w), num_segments)
    s_rows = round_up(-(-num_segments // LANES), SUBLANES)
    init = jnp.full((s_rows, LANES), kident, vals.dtype)

    def window(k, j, first, last):
        return (first[k] + j * (last[k] - first[k]), 0)

    event_spec = pl.BlockSpec((1, w), lambda k, j, first, last: (0, k))
    window_spec = pl.BlockSpec((SUBLANES, LANES), window)
    out = pl.pallas_call(
        functools.partial(_kernel, op=op, num_segments=num_segments,
                          ident=kident),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ne, 2),
            in_specs=[event_spec, event_spec, window_spec],
            out_specs=window_spec),
        out_shape=out_struct((s_rows, LANES), vals.dtype, seg, val, init),
        input_output_aliases={4: 0},
        name="segment_reduce_pallas",
        interpret=interpret,
    )(first, last, seg, val, init)
    out = out.reshape(-1)[:num_segments]
    if unsigned:
        out = jax.lax.bitcast_convert_type(out, jnp.uint32)
        if op != "sum":
            out = out ^ _SIGN
    return out
