"""Pallas kernel: weighted (src, dst) pair counting on the MXU.

The generalization of the DFG-count kernel to any rectangular
(src, dst, weight) triple — directly-follows edges, performance-overlay
pairs, or any §5.4-style co-occurrence count:

    C = sum_i w_i * e[src_i] e[dst_i]^T  =  (onehot(src) * w)^T @ onehot(dst)

The systolic MXU *is* the counter — no hash map, no scatter; the paper's
worst-case collision pathology disappears by construction.

Layout: the event stream is one lane-dense ``(1, E)`` row cut into
``(1, block_e)`` tiles (grid axis k, the reduction axis — innermost, so
each output block accumulates in VMEM across iterations).  Each tile
builds its one-hots already transposed — ``(block_s, block_e)`` and
``(block_d, block_e)``, events on lanes, ids on sublanes — so no event
vector is ever reshaped into a column, and the MXU contracts the shared
event axis (an NT matmul).  The (S, D) count matrix is cut into
``block_s x block_d`` output tiles (grid axes i, j).  Accumulation is
float32 at full (``HIGHEST``) MXU precision — exact for integer-valued
weights while per-cell sums stay < 2^24; the dispatch layer routes
inexact-float weights to the XLA scatter unless told otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import event_row, lane_tile, out_struct, round_up

# contract the event (lane) axis of both transposed one-hots: (S, E) x (D, E)
_NT = (((1,), (1,)), ((), ()))


def _kernel(src_ref, dst_ref, w_ref, out_ref, *, block_s, block_d):
    i = pl.program_id(0)          # src tile
    j = pl.program_id(1)          # dst tile
    k = pl.program_id(2)          # event tile (reduction — innermost)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = src_ref[...]                           # (1, block_e) lane-dense
    d = dst_ref[...]
    w = w_ref[...]
    be = s.shape[1]
    ids_s = jax.lax.broadcasted_iota(jnp.int32, (block_s, be), 0) + i * block_s
    ids_d = jax.lax.broadcasted_iota(jnp.int32, (block_d, be), 0) + j * block_d
    xt = jnp.where(ids_s == s, w, 0.0)                           # (S_i, be)
    yt = jnp.where(ids_d == d, 1.0, 0.0)                         # (D_j, be)
    out_ref[...] += jax.lax.dot_general(
        xt, yt, _NT, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_src", "num_dst", "block_e",
                                             "block_s", "block_d", "interpret"))
def pair_count_pallas(src: jax.Array, dst: jax.Array, w: jax.Array,
                      num_src: int, num_dst: int, *,
                      block_e: int = 512, block_s: int = 128,
                      block_d: int = 128, interpret: bool = True) -> jax.Array:
    """(num_src, num_dst) float32 weighted pair counts (OOB dropped).

    ``block_e`` is rounded up to whole 128-lane vregs, ``block_s`` to
    whole 8-row sublane groups and ``block_d`` to whole 128-lane vregs.
    Padding events carry w == 0; the caller masks invalid pairs the same way.
    """
    e = src.shape[0]
    if e == 0:
        return jnp.zeros((num_src, num_dst), jnp.float32)
    be = lane_tile(block_e)
    bs = round_up(min(block_s, round_up(num_src, 8)), 8)
    bd = round_up(block_d, 128)
    s_pad, d_pad = round_up(num_src, bs), round_up(num_dst, bd)
    srcp = event_row(src.astype(jnp.int32), be, -1)
    dstp = event_row(dst.astype(jnp.int32), be, -1)
    wp = event_row(w.astype(jnp.float32), be, 0)
    ne = srcp.shape[1] // be

    event_spec = pl.BlockSpec((1, be), lambda i, j, k: (0, k))
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, block_d=bd),
        grid=(s_pad // bs, d_pad // bd, ne),
        in_specs=[event_spec, event_spec, event_spec],
        out_specs=pl.BlockSpec((bs, bd), lambda i, j, k: (i, j)),
        out_shape=out_struct((s_pad, d_pad), jnp.float32, srcp, dstp, wp),
        name="pair_count_pallas",
        interpret=interpret,
    )(srcp, dstp, wp)
    return out[:num_src, :num_dst]
