"""XLA reference lowerings for the graph semiring products (parity oracles).

``plus_times`` is a plain ``jnp.dot`` at ``HIGHEST`` precision (the TPU's
default f32 matmul rounds operands to bfloat16, which is not exact for
counts above 256); the tropical semirings are the
row-blocked broadcast reduction — blocked so the (rows, K, N) candidate
tensor never materializes for large graphs.  Tropical products are bitwise
identical to the Pallas tiles for any block shape (min/max are
order-insensitive; each candidate ``a + b`` / ``min(a, b)`` is one op
computed identically), which is what the parity tests assert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("semiring", "block_m"))
def semiring_matmul_ref(a: jax.Array, b: jax.Array,
                        semiring: str = "plus_times", *,
                        block_m: int = 16) -> jax.Array:
    """(M, N) float32 semiring product — the reference scatter-free path."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if semiring == "plus_times":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if semiring not in ("min_plus", "max_min"):
        raise ValueError(f"unknown semiring {semiring!r}")
    m = a.shape[0]
    pad = (-m) % block_m
    ident = jnp.inf if semiring == "min_plus" else -jnp.inf
    ap = jnp.pad(a, ((0, pad), (0, 0)), constant_values=ident)
    blocks = ap.reshape(-1, block_m, a.shape[1])

    def one(ab):
        if semiring == "min_plus":
            return jnp.min(ab[:, :, None] + b[None, :, :], axis=1)
        return jnp.max(jnp.minimum(ab[:, :, None], b[None, :, :]), axis=1)

    out = jax.lax.map(one, blocks)
    return out.reshape(-1, b.shape[1])[:m]
