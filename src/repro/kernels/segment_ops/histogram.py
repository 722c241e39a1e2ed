"""Pallas kernel: weighted bincount (the paper's ``c(e)`` counting, §5.4).

Values are *unsorted* dictionary-encoded ids; the bins are tiled into
``block_b``-row windows (grid axis i, bins on sublanes) and the event
stream into lane-dense ``(1, block_e)`` tiles (grid axis k — innermost,
so each window accumulates in VMEM across the whole stream):

    acc[b, lane] += sum over the tile's 128-lane groups of where(v == b, w, 0)

A VPU masked reduction — no scatter, no atomic traffic, and no cross-lane
work inside the loop: each window keeps one partial per lane, and the
wrapper sums the 128 lane partials once at the end.  Out-of-range values
are dropped (they match no bin).  Accumulation runs in the weight dtype:
int32 counting is exact at any magnitude (the lane sum wraps like the
scatter does); float32 weights are tile-reduced (order differs from
row-order scatter — the dispatch layer routes inexact-float weights to the
XLA lowering unless told otherwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import (LANES, SUBLANES, event_row, lane_tile,
                                 out_struct, round_up)


def _kernel(val_ref, w_ref, out_ref, *, block_b):
    i = pl.program_id(0)          # bin window
    k = pl.program_id(1)          # event tile (reduction — innermost)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bins = jax.lax.broadcasted_iota(jnp.int32, (block_b, LANES), 0) + i * block_b
    acc = out_ref[...]                                   # (block_b, 128)
    for g in range(val_ref.shape[1] // LANES):
        lanes = slice(g * LANES, (g + 1) * LANES)
        v = val_ref[:, lanes]                            # (1, 128)
        acc = acc + jnp.where(v == bins, w_ref[:, lanes], 0)
    out_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_e", "block_b", "interpret"))
def histogram_pallas(values: jax.Array, weights: jax.Array, num_bins: int, *,
                     block_e: int = 512, block_b: int = 128,
                     interpret: bool = True) -> jax.Array:
    """(num_bins,) weighted bincount of ``values`` (OOB dropped).

    ``block_e`` is rounded up to whole 128-lane vregs and ``block_b`` to
    whole 8-row sublane groups (never wider than the padded bin count).
    """
    n = values.shape[0]
    if n == 0:
        return jnp.zeros((num_bins,), weights.dtype)
    be = lane_tile(block_e)
    bb = round_up(min(block_b, round_up(num_bins, SUBLANES)), SUBLANES)
    b_pad = round_up(num_bins, bb)
    val = event_row(values.astype(jnp.int32), be, -1)
    w = event_row(weights, be, 0)

    event_spec = pl.BlockSpec((1, be), lambda i, k: (0, k))
    partial = pl.pallas_call(
        functools.partial(_kernel, block_b=bb),
        grid=(b_pad // bb, val.shape[1] // be),
        in_specs=[event_spec, event_spec],
        out_specs=pl.BlockSpec((bb, LANES), lambda i, k: (i, 0)),
        out_shape=out_struct((b_pad, LANES), weights.dtype, val, w),
        name="histogram_pallas",
        interpret=interpret,
    )(val, w)
    return partial.sum(axis=1, dtype=weights.dtype)[:num_bins]
