"""Public entry points for the graph semiring primitives — backend dispatched.

``semiring_matmul`` is the one primitive (Pallas tiles / XLA reference,
selected like ``kernels.segment_ops`` via ``core.backend``); the closure
helpers below iterate it by repeated squaring — ``ceil(log2(n))`` products
instead of the n relaxation sweeps of Floyd–Warshall, which is what puts
all-pairs graph queries on the MXU's terms:

* :func:`bool_closure` — k-step boolean reachability.  The 0/1 operands
  ride the ``plus_times`` MXU product (``assume_exact``) and are
  re-thresholded after every multiply, so values stay in {0, 1} and the
  closure is exact (hence bitwise across lowerings) at any k.
* :func:`minplus_closure` — all-pairs shortest distances over a weight
  matrix with ``+inf`` marking absent edges and a zero diagonal (the
  min-plus identity makes D ⊗ D the "paths of ≤ 2x the hops" relaxation).
* :func:`maxmin_closure` — all-pairs widest (bottleneck) capacities over a
  capacity matrix with ``-inf`` marking absent edges and ``+inf`` on the
  diagonal.

Tropical closures are bitwise identical across lowerings for any weights;
with integer-valued weights they are also exactly the NumPy
Floyd–Warshall result (every candidate sum is exact below 2^24), which
the graph benchmark asserts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ref import semiring_matmul_ref
from .semiring import SEMIRINGS, semiring_matmul_pallas


def _backend():
    # deferred for the same reason as segment_ops.ops: core.backend's
    # parent package would re-enter this package mid-init
    from repro.core import backend

    return backend


def semiring_matmul(a: jax.Array, b: jax.Array,
                    semiring: str = "plus_times", *,
                    impl: str | None = None, assume_exact: bool = False,
                    **blocks) -> jax.Array:
    """(M, N) float32 semiring product of ``a @ b`` (see module docstring).

    ``impl`` forces a lowering; otherwise ``core.backend.resolve()`` picks
    (Pallas on TPU, the XLA reference elsewhere — same contract as the
    segment primitives, guardrail included: a ``plus_times`` sum is
    bitwise across lowerings only for integer-valued operands, so it takes
    the XLA reference unless the caller passes ``assume_exact=True``).
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; one of {SEMIRINGS}")
    be = _backend()
    chosen = be.resolve(impl, order_sensitive=semiring == "plus_times",
                        assume_exact=assume_exact)
    if chosen == "pallas":
        return semiring_matmul_pallas(a, b, semiring,
                                      interpret=be.interpret_mode(), **blocks)
    if chosen == "xla":
        return semiring_matmul_ref(a, b, semiring)
    raise ValueError(f"unknown semiring_matmul impl {chosen!r}")


def _steps(n: int, k: int) -> int:
    # squarings needed for a horizon of k edges on an n-node graph
    import math

    k = max(1, min(int(k), max(n - 1, 1)))
    return max(0, math.ceil(math.log2(k)))


def _or_and(x: jax.Array, y: jax.Array, impl: str | None) -> jax.Array:
    # boolean AND-OR product as a thresholded 0/1 MXU matmul: path counts
    # are exact integers below 2^24, so ``> 0`` recovers the exact OR
    return semiring_matmul(x.astype(jnp.float32), y.astype(jnp.float32),
                           "plus_times", impl=impl, assume_exact=True) > 0


def bool_closure(adj: jax.Array, k: int | None = None, *,
                 impl: str | None = None) -> jax.Array:
    """(N, N) bool: can j be reached from i in **at most** k steps?

    ``k=None`` (or k >= N-1) is the full transitive-reflexive closure —
    repeated squaring of the reflexive seed ``I | A`` (monotone: after s
    squarings the horizon is 2^s edges, and the closure saturates).  A
    finite k runs binary exponentiation of ``(I | A)^k`` instead, which
    never overshoots a non-power-of-two horizon.
    """
    n = adj.shape[0]
    base = jnp.eye(n, dtype=bool) | adj.astype(bool)
    if k is None:
        reach = base
        for _ in range(_steps(n, n - 1)):
            reach = _or_and(reach, reach, impl)
        return reach
    e = min(max(int(k), 0), max(n - 1, 1))
    acc = jnp.eye(n, dtype=bool)
    sq = base
    while e:
        if e & 1:
            acc = _or_and(acc, sq, impl)
        e >>= 1
        if e:
            sq = _or_and(sq, sq, impl)
    return acc


def minplus_closure(w: jax.Array, *, impl: str | None = None) -> jax.Array:
    """All-pairs shortest distances of a weight matrix (``+inf`` = no edge,
    diagonal forced to 0).  ``ceil(log2(n-1))`` min-plus squarings."""
    n = w.shape[0]
    d = jnp.where(jnp.eye(n, dtype=bool), 0.0, w.astype(jnp.float32))
    for _ in range(_steps(n, n - 1)):
        d = semiring_matmul(d, d, "min_plus", impl=impl)
    return d


def maxmin_closure(cap: jax.Array, *, impl: str | None = None) -> jax.Array:
    """All-pairs widest-path capacities (``-inf`` = no edge, diagonal
    forced to ``+inf`` — the max-min identity)."""
    n = cap.shape[0]
    d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, cap.astype(jnp.float32))
    for _ in range(_steps(n, n - 1)):
        d = semiring_matmul(d, d, "max_min", impl=impl)
    return d
