"""Pallas kernel: case-local (segmented) scans over the sorted stream.

Two scan monoids cover every case-local cumulative op in the core:

* ``sum``    — segmented inclusive prefix sum over vector rows; the
  eventually-follows prefix vectors of §5.4-style LTL counting.
* ``affine`` — per-row affine maps ``h -> h*m + b`` (mod 2^32).  The
  rolling variant hash ``h <- h * base + v`` is the special case
  ``m = base``.  Affine composition is associative, so the sequential
  fold becomes a parallel scan with *bitwise* identical output (32-bit
  integer arithmetic is exact mod 2^32; the kernel runs it as int32,
  whose wrapping multiply and add have the same bits as uint32's).

Layout: events lie along lanes.  A ``(K, block_e)`` tile (K = 1 for the
hash) runs a Hillis–Steele doubling scan with lane rotations
(``pltpu.roll``, log2(block_e) steps) under the standard segmented-scan
flag treatment: a lane whose accumulated flag is set ignores its
predecessor.  The open segment's running state crosses tiles through a
``(K, 1)`` carry block that stays resident in VMEM for the whole
sequential grid — the same one-row-halo idea as the streaming engine, one
level down.  Tail padding is the monoid identity (``m = 1, b = 0`` or a
zero row, flag clear), so the carry leaving the last tile is the true
stream state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import event_row, lane_tile, out_struct


def _shifted(x, d, lane, fill):
    """``x`` moved ``d`` lanes right along axis 1; the first ``d`` lanes
    take ``fill``."""
    return jnp.where(lane >= d, pltpu.roll(x, d, 1), fill)


def _affine_kernel(m_ref, b_ref, f_ref, c0_ref, ys_ref, carry_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = c0_ref[...]

    m = m_ref[...]                       # (1, W) int32 multipliers
    b = b_ref[...]                       # (1, W) int32 addends
    ff = f_ref[...]                      # (1, W) int32 segment-start flags
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    d = 1
    while d < m.shape[1]:                # static unroll: log2(W) VPU steps
        pm = _shifted(m, d, lane, 1)
        pb = _shifted(b, d, lane, 0)
        pf = _shifted(ff, d, lane, 0)
        take = ff == 0
        b = jnp.where(take, pb * m + b, b)   # compose prev∘cur (uses old m)
        m = jnp.where(take, pm * m, m)
        ff = ff | pf
        d *= 2
    ys = jnp.where(ff != 0, b, carry_ref[...] * m + b)
    ys_ref[...] = ys
    carry_ref[...] = ys[:, -1:]


def _segsum_kernel(v_ref, f_ref, c0_ref, ys_ref, carry_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = c0_ref[...]

    x = v_ref[...]                       # (K, W) — tail padding lanes are 0
    ff = jnp.broadcast_to(f_ref[...], x.shape)   # (1, W) flags, per row
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = 1
    while d < x.shape[1]:
        px = _shifted(x, d, lane, 0)
        pf = _shifted(ff, d, lane, 0)
        x = jnp.where(ff == 0, px + x, x)
        ff = ff | pf
        d *= 2
    ys = jnp.where(ff != 0, x, carry_ref[...] + x)
    ys_ref[...] = ys
    carry_ref[...] = ys[:, -1:]


def _as_int32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32) if x.dtype == jnp.uint32 else x


def _from_int32(x, dtype):
    return jax.lax.bitcast_convert_type(x, dtype) if dtype == jnp.uint32 else x


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def segmented_affine_pallas(mul: jax.Array, add: jax.Array,
                            seg_starts: jax.Array, carry: jax.Array, *,
                            block_e: int = 512, interpret: bool = True):
    """Inclusive segmented scan of explicit affine maps ``h -> h*mul + b``;
    returns ``(ys, carry_out)``.  uint32-exact, so bitwise across
    lowerings.  ``block_e`` is rounded up to whole 128-lane vregs."""
    n = mul.shape[0]
    if n == 0:
        return mul, carry
    dtype = mul.dtype
    w = lane_tile(block_e)
    m = event_row(_as_int32(mul), w, 1)              # padding: identity map
    b = event_row(_as_int32(add.astype(dtype)), w, 0)
    f = event_row(seg_starts.astype(jnp.int32), w, 0)
    c0 = _as_int32(jnp.asarray(carry, dtype)).reshape(1, 1)
    tile = pl.BlockSpec((1, w), lambda t: (0, t))
    resident = pl.BlockSpec((1, 1), lambda t: (0, 0))
    ys, cout = pl.pallas_call(
        _affine_kernel,
        grid=(m.shape[1] // w,),
        in_specs=[tile, tile, tile, resident],
        out_specs=[tile, resident],
        out_shape=[out_struct(m.shape, jnp.int32, m, b, f, c0),
                   out_struct((1, 1), jnp.int32, m, b, f, c0)],
        name="segmented_affine_pallas",
        interpret=interpret,
    )(m, b, f, c0)
    return _from_int32(ys[0, :n], dtype), _from_int32(cout[0, 0], dtype)


@functools.partial(jax.jit, static_argnames=("base", "block_e", "interpret"))
def segmented_polyhash_pallas(values: jax.Array, seg_starts: jax.Array,
                              carry: jax.Array, base: int, *,
                              block_e: int = 512, interpret: bool = True):
    """Inclusive segmented rolling hash ``h <- h*base + v``; returns
    ``(ys, carry_out)`` — the affine scan with every multiplier ``base``."""
    mul = jnp.full(values.shape, base, values.dtype)
    return segmented_affine_pallas(mul, values, seg_starts, carry,
                                   block_e=block_e, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def segmented_sum_scan_pallas(values: jax.Array, seg_starts: jax.Array,
                              carry: jax.Array, *,
                              block_e: int = 512, interpret: bool = True):
    """Inclusive segmented prefix sum over rows; returns ``(ys, carry_out)``.

    ``values`` is (N, K) with carry (K,), or (N,) with a scalar carry.
    Exact (hence bitwise impl-independent) for integer-valued inputs.
    ``block_e`` is rounded up to whole 128-lane vregs.
    """
    squeeze = values.ndim == 1
    vals = values.reshape(values.shape[0], -1)
    n, kdim = vals.shape
    if n == 0:
        return values, carry
    w = lane_tile(block_e)
    pad = (-n) % w
    v = jnp.pad(vals, ((0, pad), (0, 0))).T                  # (K, N_pad)
    f = event_row(seg_starts.astype(jnp.int32), w, 0)
    c0 = jnp.reshape(carry, (kdim, 1)).astype(vals.dtype)
    resident = pl.BlockSpec((kdim, 1), lambda t: (0, 0))
    ys, cout = pl.pallas_call(
        _segsum_kernel,
        grid=((n + pad) // w,),
        in_specs=[pl.BlockSpec((kdim, w), lambda t: (0, t)),
                  pl.BlockSpec((1, w), lambda t: (0, t)),
                  resident],
        out_specs=[pl.BlockSpec((kdim, w), lambda t: (0, t)), resident],
        out_shape=[out_struct(v.shape, vals.dtype, v, f, c0),
                   out_struct((kdim, 1), vals.dtype, v, f, c0)],
        name="segmented_sum_scan_pallas",
        interpret=interpret,
    )(v, f, c0)
    ys = ys[:, :n].T
    if squeeze:
        return ys.reshape(-1), cout[0, 0]
    return ys, cout.reshape(jnp.shape(carry))
