"""Pallas kernel: tiled semiring matrix multiply for graph queries.

One kernel body, three semirings — the closures ``repro.graph`` iterates
to answer reachability / shortest-path / widest-path questions over the
dense process graph:

=============  ==================================  =======================
semiring       ``C[i, j]``                         graph meaning
=============  ==================================  =======================
``plus_times`` ``sum_k A[i, k] * B[k, j]``         path *counting* (and the
                                                   0/1 boolean closure once
                                                   the caller thresholds)
``min_plus``   ``min_k A[i, k] + B[k, j]``         shortest-path relaxation
``max_min``    ``max_k min(A[i, k], B[k, j])``     widest-path (bottleneck)
=============  ==================================  =======================

Tiling follows ``kernels.segment_ops.pair_count``: the output is cut into
``block_m x block_n`` tiles (grid axes i, j) and the contraction axis into
``block_k`` tiles (grid axis k — innermost, so each output block stays
resident in VMEM across its accumulation); every block is a whole number
of (8, 128) vregs.  ``plus_times`` rides the MXU (``jnp.dot`` at
``HIGHEST`` precision); the tropical semirings are VPU rank-1 updates, one
per contraction index: column kk of the A tile broadcast along lanes
against row kk of the B tile broadcast along sublanes, folded into the
output tile with ``min``/``max`` — no (bm, bk, bn) candidate tensor.

Exactness: ``min``/``max`` are order-insensitive and ``a + b`` /
``min(a, b)`` are single ops computed identically on every lowering, so
the tropical products are *bitwise* equal to the XLA oracle regardless of
tile shape.  ``plus_times`` accumulates f32 partial sums per k-tile —
exact (hence bitwise) for integer-valued operands while per-cell sums stay
below 2^24, which covers every 0/1 closure and count matrix here; the
dispatch layer documents the inexact-float caveat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import LANES, SUBLANES, out_struct, round_up

SEMIRINGS = ("plus_times", "min_plus", "max_min")

# additive identity of each semiring: the init value of an output tile and
# the padding value that can never win a reduction
IDENTITY = {"plus_times": 0.0,
            "min_plus": float("inf"),
            "max_min": float("-inf")}


def _kernel(a_ref, b_ref, out_ref, *, semiring):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, IDENTITY[semiring])

    a = a_ref[...]                              # (bm, bk)
    b = b_ref[...]                              # (bk, bn)
    if semiring == "plus_times":
        out_ref[...] += jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        return
    acc = out_ref[...]
    for kk in range(a.shape[1]):                # static unroll over block_k
        col, row = a[:, kk:kk + 1], b[kk:kk + 1, :]
        if semiring == "min_plus":
            acc = jnp.minimum(acc, col + row)
        else:                                   # max_min
            acc = jnp.maximum(acc, jnp.minimum(col, row))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("semiring", "block_m", "block_n",
                                             "block_k", "interpret"))
def semiring_matmul_pallas(a: jax.Array, b: jax.Array,
                           semiring: str = "plus_times", *,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """(M, N) float32 semiring product of ``a`` (M, K) and ``b`` (K, N).

    Inputs are padded with the semiring identity (pad rows/columns can
    never win a min/max and contribute 0 to a sum), the product runs on
    the padded tiles, and the (M, N) corner is sliced back out.
    ``block_m`` is rounded up to whole 8-row sublane groups and
    ``block_n``/``block_k`` to whole 128-lane vregs.
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; one of {SEMIRINGS}")
    block_m = round_up(block_m, SUBLANES)
    block_n, block_k = round_up(block_n, LANES), round_up(block_k, LANES)
    m, kk = a.shape
    _, n = b.shape
    ident = IDENTITY[semiring]
    mp = round_up(m, block_m)
    np_ = round_up(n, block_n)
    kp = round_up(kk, block_k)
    ap = jnp.pad(a.astype(jnp.float32), ((0, mp - m), (0, kp - kk)),
                 constant_values=ident)
    bp = jnp.pad(b.astype(jnp.float32), ((0, kp - kk), (0, np_ - n)),
                 constant_values=ident)
    # min_plus inputs must be finite-or-+inf (inf + inf = inf is a safe
    # pad; a -inf entry meeting the +inf pad would NaN) — the graph
    # closures only ever feed nonnegative weights with +inf for "no edge"
    out = pl.pallas_call(
        functools.partial(_kernel, semiring=semiring),
        grid=(mp // block_m, np_ // block_n, kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=out_struct((mp, np_), jnp.float32, ap, bp),
        name="semiring_matmul_pallas",
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]
