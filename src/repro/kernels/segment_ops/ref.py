"""XLA reference lowerings for the segmented primitives.

These are the paper's direct columnar translations — scatter-adds and
``lax.scan`` folds — kept verbatim from the pre-primitive core modules.
They are the parity oracles for the Pallas kernels and the mandatory
lowering for order-sensitive float accumulations (XLA scatter applies
updates in row order, which is what makes streaming == whole-log bitwise
for non-integer float weights).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Reduction identities, shared with the Pallas kernels so both lowerings
# return bitwise-identical values for empty segments.
_F32_MAX = jnp.float32(jnp.finfo(jnp.float32).max)


def reduce_identity(op: str, dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if op == "sum":
        return jnp.zeros((), dtype)
    if op == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(jnp.inf, dtype)
        return jnp.array(jnp.iinfo(dtype).max, dtype)
    if op == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(-jnp.inf, dtype)
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    raise ValueError(f"unknown segment_reduce op {op!r}")


@functools.partial(jax.jit, static_argnames=("num_segments", "op"))
def segment_reduce_ref(values: jax.Array, segment_ids: jax.Array,
                       num_segments: int, op: str = "sum") -> jax.Array:
    """Scatter lowering with a scratch slot for out-of-range ids.

    ``.at[]`` wraps *negative* indices (only ids >= size are dropped), so
    out-of-range ids — including -1 — are first routed to a scratch slot
    that is sliced off, the pre-primitive core idiom.
    """
    s = num_segments
    ok = (segment_ids >= 0) & (segment_ids < s)
    idx = jnp.where(ok, segment_ids, s)
    init = jnp.full((s + 1,), reduce_identity(op, values.dtype))
    if op == "sum":
        return init.at[idx].add(values)[:-1]
    if op == "min":
        return init.at[idx].min(values)[:-1]
    return init.at[idx].max(values)[:-1]


@functools.partial(jax.jit, static_argnames=("num_bins",))
def histogram_ref(values: jax.Array, num_bins: int, weights: jax.Array,
                  into: jax.Array | None = None) -> jax.Array:
    """Weighted bincount; out-of-range values hit a scratch bin (sliced off).

    ``into`` scatters onto an existing accumulator *in row order* — for
    float weights this is what keeps a chunked stream bitwise identical to
    the whole-log pass (additions hit the running state left-to-right
    instead of being grouped per chunk).
    """
    ok = (values >= 0) & (values < num_bins)
    idx = jnp.where(ok, values, num_bins)
    init = jnp.zeros((num_bins,), weights.dtype) if into is None else into
    init = jnp.concatenate([init, jnp.zeros((1,), weights.dtype)])
    return init.at[idx].add(weights)[:-1]


@functools.partial(jax.jit, static_argnames=("num_src", "num_dst"))
def pair_count_ref(src: jax.Array, dst: jax.Array, w: jax.Array,
                   num_src: int, num_dst: int,
                   into: jax.Array | None = None) -> jax.Array:
    """Flat-key scatter-add: ``counts[src_i, dst_i] += w_i`` (OOB dropped).

    The paper's map-reduce strategy (§5.4 strategy 1): pair keys reduced
    via scatter-add, masked pairs routed to a scratch bucket.  ``into``
    accumulates onto an existing (num_src, num_dst) state in row order
    (see ``histogram_ref``).
    """
    ok = ((src >= 0) & (src < num_src)) & ((dst >= 0) & (dst < num_dst))
    key = jnp.where(ok, src.astype(jnp.int32) * num_dst + dst, num_src * num_dst)
    init = (jnp.zeros((num_src * num_dst,), w.dtype) if into is None
            else into.reshape(-1))
    flat = jnp.concatenate([init, jnp.zeros((1,), w.dtype)]).at[key].add(w)
    return flat[:-1].reshape(num_src, num_dst)


@functools.partial(jax.jit, static_argnames=("num_src", "num_dst", "block"))
def pair_count_matmul(src: jax.Array, dst: jax.Array, w: jax.Array,
                      num_src: int, num_dst: int, block: int = 2048) -> jax.Array:
    """Blockwise one-hot matmul: ``C = sum_k (onehot(src_k) * w_k)^T @ onehot(dst_k)``.

    The XLA twin of the Pallas MXU kernel (float32 accumulation; exact for
    integer-valued weights with per-cell sums < 2^24).
    """
    n = src.shape[0]
    pad = (-n) % block
    srcp = jnp.pad(src.astype(jnp.int32), (0, pad), constant_values=-1)
    dstp = jnp.pad(dst.astype(jnp.int32), (0, pad), constant_values=-1)
    wp = jnp.pad(w.astype(jnp.float32), (0, pad))
    nblk = (n + pad) // block

    def body(c, xs):
        s, d, ww = xs
        x = jax.nn.one_hot(s, num_src, dtype=jnp.float32) * ww[:, None]
        y = jax.nn.one_hot(d, num_dst, dtype=jnp.float32)
        return c + jnp.dot(x.T, y, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32), None

    c, _ = jax.lax.scan(
        body, jnp.zeros((num_src, num_dst), jnp.float32),
        (srcp.reshape(nblk, block), dstp.reshape(nblk, block),
         wp.reshape(nblk, block)))
    return c.astype(w.dtype)


@jax.jit
def segmented_affine_ref(mul: jax.Array, add: jax.Array,
                         seg_starts: jax.Array, carry):
    """Sequential fold of explicit affine maps ``h -> h*mul + add``
    (segment starts reset ``h`` to 0 first).  Returns ``(ys, carry_out)``."""

    def step(h, xs):
        m, b, start = xs
        h = jnp.where(start, jnp.zeros_like(h), h) * m + b
        return h, h

    last, ys = jax.lax.scan(step, carry, (mul, add, seg_starts))
    return ys, last


@functools.partial(jax.jit, static_argnames=("op",))
def segmented_scan_ref(values: jax.Array, seg_starts: jax.Array,
                       carry, op: str = "sum", base=None):
    """Sequential ``lax.scan`` fold (the pre-primitive core formulation).

    Returns ``(ys_inclusive, carry_out)``; ``carry_out`` is the inclusive
    value at the final row (the open segment's running state).
    """
    if op == "sum":
        zero = jnp.zeros_like(carry)

        def step(h, xs):
            v, start = xs
            h = jnp.where(start, zero, h) + v
            return h, h

        last, ys = jax.lax.scan(step, carry, (values, seg_starts))
        return ys, last
    if op == "polyhash":
        b = jnp.asarray(base, values.dtype)

        def step(h, xs):
            v, start = xs
            h = jnp.where(start, jnp.zeros_like(h), h) * b + v
            return h, h

        last, ys = jax.lax.scan(step, carry, (values, seg_starts))
        return ys, last
    raise ValueError(f"unknown segmented_scan op {op!r}")
